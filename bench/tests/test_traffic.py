"""The traffic generator: seeded, and with the distributions it claims."""

import numpy as np
import pytest

from bench import traffic as tr


def test_streams_repeat_per_seed_and_differ_between_streams():
    a = tr.rng(2**33 + 5, "x").integers(0, 1 << 30, 8)
    b = tr.rng(2**33 + 5, "x").integers(0, 1 << 30, 8)
    c = tr.rng(2**33 + 5, "y").integers(0, 1 << 30, 8)
    d = tr.rng(5, "x").integers(0, 1 << 30, 8)
    assert np.array_equal(a, b)
    assert not np.array_equal(a, c)
    assert not np.array_equal(a, d)


def test_uniform_keys_cover_the_universe():
    ks = tr.KeyStream({"dist": "uniform"}, 64, tr.rng(1, "k"))
    x = ks.draw(64 * 400)
    assert x.min() == 0 and x.max() == 63
    counts = np.bincount(x, minlength=64)
    assert counts.min() > 300 and counts.max() < 500


def test_zipf_keys_are_skewed():
    ks = tr.KeyStream({"dist": "zipf", "s": 0.99}, 1000, tr.rng(1, "k"))
    counts = np.sort(np.bincount(ks.draw(100_000), minlength=1000))[::-1]
    assert counts[0] > 20 * np.median(counts)


def test_unknown_distribution_is_refused():
    with pytest.raises(ValueError):
        tr.KeyStream({"dist": "pareto"}, 10, tr.rng(1, "k"))


def test_arrivals_depend_on_the_seed_alone():
    a = tr.Arrivals(1000.0, tr.rng(3, "arrivals"))
    b = tr.Arrivals(1000.0, tr.rng(3, "arrivals"))
    # the same schedule whatever the steps that take it
    got_a = np.concatenate([a.take(t, 10**9) for t in (0.3, 0.35, 2.0)])
    got_b = b.take(2.0, 10**9)
    assert np.array_equal(got_a, got_b)
    assert np.all(np.diff(got_b) >= 0)
    assert got_b.max() <= 2.0
    assert abs(got_b.size - 2000) < 5 * np.sqrt(2000)


def test_arrivals_take_respects_the_limit_and_keeps_the_rest():
    a = tr.Arrivals(1000.0, tr.rng(4, "arrivals"))
    first = a.take(1.0, 100)
    assert first.size == 100
    nxt = a.next_due(1.0)
    assert nxt is not None and nxt >= first[-1]
    rest = a.take(1.0, 10**9)
    assert rest[0] == nxt
    assert a.next_due(1.0) is None


def test_traffic_files_load():
    for cell in ("kmer.count", "kmer.lookup", "isx.sort"):
        t = tr.load(cell)
        assert t["loop"] in ("open", "closed")
