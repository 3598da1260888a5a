"""The seeded data and the plain references, on the CPU at tiny sizes."""

import numpy as np

from bench.configs import isx, kmer_hashmap as km


def test_unmix64_inverts_mix64():
    x = np.random.default_rng(0).integers(0, 2**63, 10_000,
                                          dtype=np.uint64) * np.uint64(2)
    x[:3] = [0, 1, 2**64 - 1]
    assert np.array_equal(km.unmix64(km.mix64(x)), x)


def test_key_lanes_are_distinct_and_invert_for_any_seed():
    idx = np.arange(1 << 16)
    for seed in (0, 2**31 + 7, 2**40 + 1):
        s = km.salt(seed)
        lanes = km.key_lanes(idx, s)
        keys = (lanes[:, 0].astype(np.uint64) << np.uint64(32)) | lanes[:, 1]
        assert np.unique(keys).size == idx.size
        back = km.key_index(lanes[:, 0], lanes[:, 1], s)
        assert np.array_equal(back, idx.astype(np.uint64))
    assert km.salt(1) != km.salt(2)


def test_extension_codes_are_two_one_hot_nibbles():
    c = km.extension_codes(np.random.default_rng(1), 1000)
    lo, hi = c & 0xFFFF, c >> 16
    for half in (lo, hi):
        assert set(np.unique(half)) <= {1, 1 << 4, 1 << 8, 1 << 12}


def table_of(idx, vals, s, nb=8, block=16):
    """A host table holding the given entries, one per slot."""
    tk = np.zeros((nb, 2, block), np.uint32)
    tv = np.zeros((nb, 2, block), np.uint32)
    st = np.zeros((nb, block), np.uint32)
    lanes = km.key_lanes(np.asarray(idx), s)
    for i, (k, v) in enumerate(zip(lanes, vals)):
        b, j = divmod(i, block)
        tk[b, :, j], tv[b, :, j], st[b, j] = k, v, km.READY
    return tk, tv, st


def test_compare_counts_finds_each_kind_of_fault():
    s = km.salt(9)
    sent = [np.array([0, 1, 1, 3]), np.array([3, 3])]
    ext = [np.array([1, 16, 256, 1], np.uint32),
           np.array([1, 1 << 16], np.uint32)]
    cnt, es = km.count_reference(sent, ext, 8)
    assert list(cnt[:4]) == [1, 2, 0, 3]
    assert es[1] == 16 + 256 and es[3] == 2 + (1 << 16)
    good = table_of([0, 1, 3], [(1, 1), (2, 272), (3, 2 + (1 << 16))], s)
    ok = km.compare_counts(good, s, 8, cnt, es)
    assert ok == {"missing": 0, "extra": 0, "wrong_value": 0}
    lost = table_of([0, 3], [(1, 1), (3, 2 + (1 << 16))], s)
    assert km.compare_counts(lost, s, 8, cnt, es)["missing"] == 1
    wrong = table_of([0, 1, 3], [(1, 1), (1, 272), (3, 2 + (1 << 16))], s)
    assert km.compare_counts(wrong, s, 8, cnt, es)["wrong_value"] == 1
    dup = table_of([0, 1, 3, 3], [(1, 1), (2, 272), (3, 2 + (1 << 16)),
                                  (0, 0)], s)
    assert km.compare_counts(dup, s, 8, cnt, es)["extra"] == 1
    unsent = table_of([0, 1, 3, 5], [(1, 1), (2, 272), (3, 2 + (1 << 16)),
                                     (1, 1)], s)
    assert km.compare_counts(unsent, s, 8, cnt, es)["extra"] == 1
    stranger = table_of([0, 1, 3, 10**9], [(1, 1), (2, 272),
                                           (3, 2 + (1 << 16)), (1, 1)], s)
    assert km.compare_counts(stranger, s, 8, cnt, es)["extra"] == 1


def test_compare_finds():
    fill = np.arange(20, dtype=np.uint32).reshape(10, 2)
    present = np.array([True, True, False])
    idx = np.array([2, 7, 10**6])
    vals = np.array([[4, 5], [14, 15], [0, 0]], np.uint32)
    found = present.copy()
    assert km.compare_finds(present, idx, found, vals, fill) == \
        {"found_wrong": 0, "value_wrong": 0}
    assert km.compare_finds(present, idx, ~found, vals, fill)["found_wrong"] \
        == 3
    bad = vals.copy()
    bad[1, 0] += 1
    assert km.compare_finds(present, idx, found, bad, fill)["value_wrong"] \
        == 1


def test_compare_sorted():
    rng = np.random.default_rng(2)
    n, kpc, space = 4, 64, 1 << 28
    shift = isx.bucket_shift(space, n)
    keys = rng.integers(0, space, n * kpc, dtype=np.uint32)
    ring = isx.ring_size(kpc, n)
    out = np.full(n * ring, isx.PAD, np.uint32)
    got = np.zeros(n, np.int32)
    for r in range(n):
        mine = np.sort(keys[(keys >> shift) == r])
        out[r * ring:r * ring + mine.size] = mine
        got[r] = mine.size
    assert isx.compare_sorted(keys, out, got, n, shift) == \
        {"misplaced": 0, "wrong_key": 0}
    bad = out.copy()
    bad[0] += 1
    assert isx.compare_sorted(keys, bad, got, n, shift)["wrong_key"] == 1
    short = got.copy()
    short[1] -= 1
    assert isx.compare_sorted(keys, out, short, n, shift)["wrong_key"] == 1
    moved = out.copy()
    moved[0] = np.uint32(3 << shift)
    assert isx.compare_sorted(keys, moved, got, n, shift)["misplaced"] == 1
