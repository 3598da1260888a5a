"""Per-kernel validation: Pallas (interpret) and vectorized-jnp vs the
sequential oracles, swept over shapes/dtypes/modes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.hashing import double_hash
from repro.kernels import binning, bloom_kernel, hash_probe, ops, ref
from repro.kernels import flash_attention as fa


def _mk_table(nb, B, lk, lv):
    return (jnp.zeros((nb, lk, B), jnp.uint32),
            jnp.zeros((nb, lv, B), jnp.uint32),
            jnp.zeros((nb, B), jnp.uint32))


def _mk_queries(rng, m, nb, lk, lv, key_space):
    qk = jnp.asarray(rng.integers(0, key_space, (m, lk)), jnp.uint32)
    mix = np.asarray(qk[:, 0])
    for i in range(1, lk):
        mix = mix * 31 + np.asarray(qk[:, i])
    qb = jnp.asarray(mix % nb, jnp.int32)
    qv = jnp.asarray(rng.integers(1, 1 << 20, (m, lv)), jnp.uint32)
    qvalid = jnp.asarray(rng.random(m) < 0.9)
    return qb, qk, qv, qvalid


SWEEP = [
    # nb, B, lk, lv, m
    (8, 16, 1, 1, 100),
    (16, 32, 2, 2, 400),
    (4, 8, 3, 1, 64),
    (32, 16, 2, 1, 900),
]


@pytest.mark.parametrize("nb,B,lk,lv,m", SWEEP)
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
@pytest.mark.parametrize("mode", [ref.MODE_SET, ref.MODE_ADD, ref.MODE_KEEP])
def test_insert_matches_oracle(rng, nb, B, lk, lv, m, impl, mode):
    tk, tv, st = _mk_table(nb, B, lk, lv)
    qb, qk, qv, qvalid = _mk_queries(rng, m, nb, lk, lv, key_space=m // 2)
    o = ref.hash_probe_insert_ref(tk, tv, st, qb, qk, qv, qvalid, mode)
    j = ops.bulk_insert(tk, tv, st, qb, qk, qv, qvalid, mode, impl=impl)
    for a, b_, name in zip(o, j, ["tkeys", "tvals", "status", "success"]):
        assert np.array_equal(np.asarray(a), np.asarray(b_)), \
            f"{name} mismatch ({impl}, mode={mode})"


@pytest.mark.parametrize("nb,B,lk,lv,m", SWEEP[:2])
@pytest.mark.parametrize("impl", ["jnp", "pallas"])
def test_find_matches_oracle(rng, nb, B, lk, lv, m, impl):
    tk, tv, st = _mk_table(nb, B, lk, lv)
    qb, qk, qv, qvalid = _mk_queries(rng, m, nb, lk, lv, key_space=m // 2)
    tk, tv, st, _ = ref.hash_probe_insert_ref(tk, tv, st, qb, qk, qv,
                                              qvalid, ref.MODE_SET)
    fb, fk, _, fvalid = _mk_queries(rng, m, nb, lk, lv, key_space=m)
    fo, vo = ref.hash_probe_find_ref(tk, tv, st, fb, fk, fvalid)
    fj, vj = ops.bulk_find(tk, tv, st, fb, fk, fvalid, impl=impl)
    assert np.array_equal(np.asarray(fo), np.asarray(fj))
    assert np.array_equal(np.asarray(vo), np.asarray(vj))


@pytest.mark.parametrize("nb,B,lk,lv,m", SWEEP[:2])
def test_arrivals_match_column_ops(rng, nb, B, lk, lv, m):
    """Owner-side arrival entry points (DESIGN.md section 1.10): probing
    straight off the contiguous (block | key | val) exchange segment is
    bit-identical to slicing the columns and running bulk_find /
    bulk_insert, across both impls."""
    tk, tv, st = _mk_table(nb, B, lk, lv)
    qb, qk, qv, qvalid = _mk_queries(rng, m, nb, lk, lv, key_space=m // 2)
    seg_i = jnp.concatenate([qb.astype(jnp.uint32)[:, None], qk, qv], axis=1)
    col = ops.bulk_insert(tk, tv, st, qb, qk, qv, qvalid, impl="jnp")
    for impl in ("jnp", "pallas"):
        got = ops.bulk_insert_arrivals(tk, tv, st, seg_i, qvalid, impl=impl)
        for a, b_, name in zip(col, got, ["tkeys", "tvals", "status", "ok"]):
            assert np.array_equal(np.asarray(a), np.asarray(b_)), \
                f"insert {name} ({impl})"
    tk, tv, st, _ = col[0], col[1], col[2], col[3]
    fb, fk, _, fvalid = _mk_queries(rng, m, nb, lk, lv, key_space=m)
    seg_f = jnp.concatenate([fb.astype(jnp.uint32)[:, None], fk], axis=1)
    fo, vo = ops.bulk_find(tk, tv, st, fb, fk, fvalid, impl="jnp")
    for impl in ("jnp", "pallas"):
        fg, vg = ops.bulk_find_arrivals(tk, tv, st, seg_f, fvalid, impl=impl)
        assert np.array_equal(np.asarray(fo), np.asarray(fg)), impl
        assert np.array_equal(np.asarray(vo), np.asarray(vg)), impl


def test_insert_stateful_sequence(rng):
    """Kernel equals oracle across a chain of dependent batches."""
    nb, B, lk, lv = 8, 16, 2, 1
    tko, tvo, sto = _mk_table(nb, B, lk, lv)
    tkp, tvp, stp = _mk_table(nb, B, lk, lv)
    for i in range(4):
        qb, qk, qv, qvalid = _mk_queries(rng, 120, nb, lk, lv, 60)
        tko, tvo, sto, oko = ref.hash_probe_insert_ref(
            tko, tvo, sto, qb, qk, qv, qvalid, ref.MODE_ADD)
        tkp, tvp, stp, okp = hash_probe.insert(
            tkp, tvp, stp, qb, qk, qv, qvalid, ref.MODE_ADD)
        assert np.array_equal(np.asarray(oko), np.asarray(okp)), f"batch {i}"
    assert np.array_equal(np.asarray(tvo), np.asarray(tvp))


class TestBloomKernel:
    @pytest.mark.parametrize("m,k,lanes", [(64, 4, 1), (333, 6, 2),
                                           (1000, 3, 2)])
    def test_hash_words(self, rng, m, k, lanes):
        items = jnp.asarray(rng.integers(0, 1 << 31, (m, lanes)), jnp.uint32)
        w_ref = ref.bloom_words_ref(double_hash(items, k, 64), k)
        w_ker = bloom_kernel.hash_words(items, k, tile=128)
        assert np.array_equal(np.asarray(w_ref), np.asarray(w_ker))

    def test_membership(self, rng):
        m = 500
        prior = jnp.asarray(rng.integers(0, 1 << 31, (m, 2)), jnp.uint32)
        words = jnp.asarray(rng.integers(0, 1 << 31, (m, 2)), jnp.uint32)
        valid = jnp.asarray(rng.random(m) < 0.8)
        expect = ((prior & words) == words).all(axis=1) & valid
        got = bloom_kernel.membership(prior, words, valid, tile=128)
        assert np.array_equal(np.asarray(expect), np.asarray(got))

    @pytest.mark.parametrize("impl", ["jnp", "pallas"])
    def test_bloom_insert_impls(self, rng, impl):
        nw, m = 32, 400
        fw = jnp.zeros((nw, 2), jnp.uint32)
        items = jnp.asarray(rng.integers(0, 50, (m, 2)), jnp.uint32)
        hb = jnp.asarray((np.asarray(items[:, 0]) * 3 +
                          np.asarray(items[:, 1])) % nw, jnp.int32)
        words = ref.bloom_words_ref(double_hash(items, 4, 64), 4)
        valid = jnp.asarray(rng.random(m) < 0.9)
        fo, po = ref.bloom_insert_ref(fw, hb, words, valid)
        fj, pj = ops.bloom_insert(fw, hb, words, valid, impl=impl)
        assert np.array_equal(np.asarray(fo), np.asarray(fj))
        assert np.array_equal(np.asarray(po), np.asarray(pj))


class TestBinning:
    @pytest.mark.parametrize("n,nbins,tile", [(100, 7, 32), (5000, 13, 512),
                                              (2048, 256, 256)])
    def test_histogram(self, rng, n, nbins, tile):
        bins = jnp.asarray(rng.integers(0, nbins, n), jnp.int32)
        valid = jnp.asarray(rng.random(n) < 0.7)
        h_ref = ref.bin_histogram_ref(bins, nbins, valid)
        h_ker = binning.histogram(bins, nbins, valid, tile=tile)
        assert np.array_equal(np.asarray(h_ref), np.asarray(h_ker))

    @pytest.mark.parametrize("n,nbins,nflows", [(100, 3, 2), (3000, 8, 4)])
    def test_ragged_slots_pallas_matches_jnp(self, rng, n, nbins, nflows):
        """The ragged-wire slot kernel against its jnp oracle, over
        every retry round of an uneven flow mix (the exchange engine
        dispatches whichever the backend picks — they must agree)."""
        bins = jnp.asarray(rng.integers(0, nbins, n), jnp.int32)
        flow = jnp.asarray(rng.integers(0, nflows, n), jnp.int32)
        valid = jnp.asarray(rng.random(n) < 0.8)
        _, offs = ops.multi_bin_offsets(bins, flow, nbins, nflows, valid)
        roww = jnp.asarray(rng.integers(2, 6, nflows), jnp.int32)
        caps = jnp.asarray(rng.integers(1, 9, nflows), jnp.int32)
        rounds = jnp.asarray(rng.integers(1, 4, nflows), jnp.int32)
        woff, wtot = [], 0
        for f in range(nflows):
            woff.append(wtot)
            wtot += int(caps[f]) * int(roww[f])
        woff = jnp.asarray(woff, jnp.int32)
        for r in range(int(rounds.max())):
            args = (bins, flow, offs, valid, r, woff, roww, caps, rounds,
                    wtot, nbins * wtot)
            sj = ops.ragged_slots(*args, impl="jnp")
            sp = ops.ragged_slots(*args, impl="pallas")
            assert np.array_equal(np.asarray(sj), np.asarray(sp)), r
            # in-round slots are unique (disjoint word ranges per item)
            live = np.asarray(sj) < nbins * wtot
            assert np.unique(np.asarray(sj)[live]).size == live.sum()

    @pytest.mark.parametrize("n,nbins,nflows", [(100, 3, 2), (3000, 8, 4)])
    def test_pack_rows_pallas_matches_jnp(self, rng, n, nbins, nflows):
        """The one-kernel wire pack against its two-pass jnp oracle
        (ragged_slots + scatter_rows), over every retry round: identical
        buffers, and every live item's words land at distinct addresses
        (sentinel rows — out of round, invalid, overflow — drop)."""
        bins = jnp.asarray(rng.integers(0, nbins, n), jnp.int32)
        flow = jnp.asarray(rng.integers(0, nflows, n), jnp.int32)
        valid = jnp.asarray(rng.random(n) < 0.8)
        _, offs = ops.multi_bin_offsets(bins, flow, nbins, nflows, valid)
        roww = jnp.asarray(rng.integers(2, 6, nflows), jnp.int32)
        caps = jnp.asarray(rng.integers(1, 9, nflows), jnp.int32)
        rounds = jnp.asarray(rng.integers(1, 4, nflows), jnp.int32)
        woff, wtot = [], 0
        for f in range(nflows):
            woff.append(wtot)
            wtot += int(caps[f]) * int(roww[f])
        woff = jnp.asarray(woff, jnp.int32)
        wmax = int(roww.max())
        # distinct nonzero payload per (item, lane) so uniqueness of the
        # packed words proves no two rows overlapped in the buffer
        rows = (jnp.arange(n, dtype=jnp.uint32)[:, None] * wmax
                + jnp.arange(wmax, dtype=jnp.uint32)[None, :] + 1)
        total = nbins * wtot
        for r in range(int(rounds.max())):
            args = (rows, bins, flow, offs, valid, r, woff, roww, caps,
                    rounds, wtot, total)
            bj = ops.pack_rows(*args, impl="jnp")
            bp = ops.pack_rows(*args, impl="pallas")
            assert np.array_equal(np.asarray(bj), np.asarray(bp)), r
            slots = np.asarray(ops.ragged_slots(
                bins, flow, offs, valid, r, woff, roww, caps, rounds,
                wtot, total, impl="jnp"))
            live = slots < total
            written = np.asarray(bj)[np.asarray(bj) != 0]
            expect_words = int((np.asarray(roww)[np.asarray(flow)])[live].sum())
            assert written.size == expect_words, r
            assert np.unique(written).size == written.size, r
            # rows past the round window / invalid contribute nothing
            drop_vals = np.asarray(rows)[~live].ravel()
            assert not np.intersect1d(written, drop_vals).size, r

    def test_place_rows_pallas_matches_jnp(self, rng):
        """Analytic-slot row placement (dense replies, owner assembly):
        kernel == scatter_rows oracle, OOB sentinel slots drop."""
        n, w, total = 200, 3, 900
        base = jnp.asarray(rng.permutation(total // w)[:n] * w, jnp.int32)
        base = jnp.where(jnp.asarray(rng.random(n) < 0.15), total, base)
        rows = jnp.asarray(rng.integers(1, 1 << 30, (n, w)), jnp.uint32)
        dst = jnp.asarray(rng.integers(0, 1 << 30, total), jnp.uint32)
        oj = ops.place_rows(dst, base, rows, impl="jnp")
        op_ = ops.place_rows(dst, base, rows, impl="pallas")
        assert np.array_equal(np.asarray(oj), np.asarray(op_))
        kept = np.asarray(base) >= total
        assert np.array_equal(np.asarray(oj)[np.asarray(base)[~kept]],
                              np.asarray(rows)[~kept, 0])


class TestFlashAttention:
    @pytest.mark.parametrize(
        "b,hq,hkv,tq,tk,d,causal,window",
        [(2, 4, 2, 64, 64, 32, True, 0),
         (1, 8, 1, 128, 128, 64, True, 0),     # MQA
         (2, 4, 4, 64, 128, 32, True, 0),      # suffix-aligned
         (1, 2, 2, 96, 96, 32, True, 32),      # sliding window
         (1, 4, 2, 1, 256, 64, True, 0),       # decode-like
         (2, 2, 2, 64, 64, 16, False, 0)])     # bidirectional
    def test_vs_oracle(self, rng, b, hq, hkv, tq, tk, d, causal, window):
        q = jnp.asarray(rng.standard_normal((b, hq, tq, d)),
                        jnp.float32) * 0.3
        k = jnp.asarray(rng.standard_normal((b, hkv, tk, d)),
                        jnp.float32) * 0.3
        v = jnp.asarray(rng.standard_normal((b, hkv, tk, d)), jnp.float32)
        o_ref = ref.flash_attention_ref(q, k, v, causal=causal,
                                        window=window)
        o_ker = fa.flash_attention(q, k, v, causal=causal, window=window,
                                   block_q=32, block_k=32)
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_ker),
                                   atol=3e-5, rtol=3e-5)

    def test_bf16(self, rng):
        q = jnp.asarray(rng.standard_normal((1, 2, 64, 32)),
                        jnp.bfloat16) * 0.3
        k = jnp.asarray(rng.standard_normal((1, 2, 64, 32)),
                        jnp.bfloat16) * 0.3
        v = jnp.asarray(rng.standard_normal((1, 2, 64, 32)), jnp.bfloat16)
        o_ref = ref.flash_attention_ref(q, k, v)
        o_ker = fa.flash_attention(q, k, v, block_q=32, block_k=32)
        np.testing.assert_allclose(
            np.asarray(o_ref, dtype=np.float32),
            np.asarray(o_ker, dtype=np.float32), atol=2e-2, rtol=2e-2)

    def test_blockwise_xla_path_matches(self, rng):
        """models/attention.blockwise == oracle (the dry-run path)."""
        from repro.models.attention import blockwise_attention
        q = jnp.asarray(rng.standard_normal((2, 4, 80, 32)),
                        jnp.float32) * 0.3
        k = jnp.asarray(rng.standard_normal((2, 2, 80, 32)),
                        jnp.float32) * 0.3
        v = jnp.asarray(rng.standard_normal((2, 2, 80, 32)), jnp.float32)
        o_ref = ref.flash_attention_ref(q, k, v, causal=True, window=24)
        o_blk = blockwise_attention(q, k, v, causal=True, window=24,
                                    q_block=32, k_block=16)
        np.testing.assert_allclose(np.asarray(o_ref), np.asarray(o_blk),
                                   atol=3e-5, rtol=3e-5)
