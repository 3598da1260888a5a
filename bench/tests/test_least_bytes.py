"""The least-work functions and the peak table."""

import numpy as np
import pytest

from bench import roofline


def test_touched_blocks_matches_a_simulation():
    rng = np.random.default_rng(0)
    nb, m = 4096, 4096
    sim = np.mean([np.unique(rng.integers(0, nb, m)).size for _ in range(20)])
    assert roofline.touched_blocks(m, nb) == pytest.approx(sim, rel=0.01)
    assert roofline.touched_blocks(0, nb) == 0.0


def test_probe_least_bytes_of_the_counting_batch():
    # 2^20 distinct keys over 2^20 blocks touch about 63% of them
    nb = 1 << 20
    touched = roofline.touched_blocks(nb, nb)
    assert touched / nb == pytest.approx(1 - np.exp(-1), rel=1e-4)
    ins = roofline.probe_least_bytes("insert", nb, nb, nb, 128, 2, 2)
    blk = 5 * 128 * 4
    assert ins == pytest.approx(2 * touched * blk + nb * 6 * 4)
    # about 4.2 ms at 819 GB/s
    assert ins / 819e9 == pytest.approx(4.2e-3, rel=0.05)
    fnd = roofline.probe_least_bytes("find", nb, nb, nb, 128, 2, 2)
    assert fnd == pytest.approx(touched * blk + nb * 6 * 4)
    with pytest.raises(ValueError):
        roofline.probe_least_bytes("erase", 1, 1, 1, 128, 2, 2)


def test_peaks_are_keyed_by_device_kind():
    p = roofline.peaks("TPU v5 lite")
    assert p["hbm_bytes_per_s"] == 819e9
    assert p["bf16_flops_per_s"] == 197e12
    with pytest.raises(KeyError):
        roofline.peaks("cpu")
