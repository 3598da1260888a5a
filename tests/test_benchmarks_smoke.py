"""Tier-1 smoke run of the exchange-layer microbenchmarks.

Runs micro_hashmap / micro_queue at tiny sizes (benchmarks/run.py
--smoke) so a perf-shaped regression in the exchange engine — extra
collectives, extra wire lanes — fails the suite, not just the nightly
benchmark sweep.
"""

import os
import sys

import pytest

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))


def test_micro_hashmap_smoke():
    from benchmarks import micro_hashmap
    results = micro_hashmap.run(smoke=True)
    for k in ("hashmap_insert", "hashmap_insert_buffer",
              "hashmap_find_atomic", "hashmap_find", "hashmap_find_2attempt"):
        assert results[k] > 0, k


def test_micro_queue_smoke():
    from benchmarks import micro_queue
    results = micro_queue.run(smoke=True)
    for k in ("cq_push_pushpop", "fq_push", "fq_pop", "fq_local_pop"):
        assert results[k] > 0, k


def test_micro_fused_arms_smoke():
    """The --fused arms run and report both schedules of each pair."""
    from benchmarks import micro_hashmap, micro_queue
    r = micro_hashmap.run(smoke=True, fused=True)
    assert r["hashmap_find_insert_fused"] > 0
    assert r["hashmap_find_insert_fine"] > 0
    r = micro_queue.run(smoke=True, fused=True)
    assert r["cq_push_pop_fused"] > 0
    assert r["cq_push_pop_fine"] > 0


def test_micro_wire_arms_smoke(capsys):
    """The --wire {scatter,fused} arms (DESIGN.md section 1.10): both
    wires run every variant, rows follow the shared CSV schema, and the
    wire choice never changes bytes, collectives, rounds, or hops.  (The
    fused wire's zero standalone scatters per commit are pinned in
    tests/test_wire_format.py.)"""
    from benchmarks import micro_hashmap, micro_queue
    from benchmarks.util import HEADER
    ncols = len(HEADER.split(","))
    hcols = HEADER.split(",")
    rs = micro_hashmap.run(smoke=True, wire="scatter")
    rf = micro_hashmap.run(smoke=True, wire="fused")
    rq = micro_queue.run(smoke=True, wire="fused")
    for k in ("hashmap_insert", "hashmap_find"):
        assert rs[k] > 0 and rf[k] > 0, k
    assert rq["fq_push"] > 0
    rows = [ln.split(",") for ln in capsys.readouterr().out.splitlines()
            if "," in ln]
    for cols in rows:
        assert len(cols) == ncols, cols
    by_name = {cols[0]: cols for cols in rows}
    for base in ("hashmap_insert", "hashmap_insert_buffer",
                 "hashmap_find_atomic", "hashmap_find",
                 "hashmap_find_2attempt"):
        s, f = by_name[base + "_scatter"], by_name[base + "_fused"]
        # identical collectives / bytes / rounds / hops on both wires
        for i in (2, 3, 4, 8):
            assert s[i] == f[i], (base, hcols[i], s[i], f[i])


def test_micro_skew_arms_smoke(capsys):
    """The --skew zipf arms run; the drop-mode arm loses items, the
    retry arm loses none, and every CSV row follows the shared schema
    (incl. the retry_rounds and dropped columns)."""
    from benchmarks import micro_hashmap, micro_queue
    from benchmarks.util import HEADER
    ncols = len(HEADER.split(","))
    rq = micro_queue.run(smoke=True, skew="zipf")
    assert rq["fq_push_skew_drop_dropped"] > 0
    assert rq["fq_push_skew_retry_dropped"] == 0
    rh = micro_hashmap.run(smoke=True, skew="zipf")
    assert rh["hashmap_insert_skew_drop_dropped"] > 0
    assert rh["hashmap_insert_skew_retry_dropped"] == 0
    rows = [ln for ln in capsys.readouterr().out.strip().splitlines()
            if "," in ln]
    assert rows, "benchmarks emitted no CSV rows"
    for ln in rows:
        assert len(ln.split(",")) == ncols, ln
    skew_tags = [ln for ln in rows if "_skew_" in ln]
    assert len(skew_tags) == 4
    for ln in skew_tags:
        cols = ln.split(",")
        assert cols[6] != "" and cols[7] != "", ln     # retry_rounds,dropped


def test_app_skew_arms_smoke(capsys):
    """The --skew zipf arms on the APPLICATION benchmarks (isx /
    meraculous / kmer): drop-mode arms lose items, retry arms lose none,
    and every skew row carries the retry_rounds/dropped columns of the
    shared CSV schema."""
    from benchmarks import isx, kmer, meraculous
    from benchmarks.util import HEADER
    ncols = len(HEADER.split(","))
    r = isx.run(smoke=True, skew="zipf")
    assert r["isx_skew_drop_dropped"] > 0
    assert r["isx_skew_retry_dropped"] == 0
    r = kmer.run(smoke=True, skew="zipf")
    assert r["kmer_insert_skew_drop_dropped"] > 0
    assert r["kmer_insert_skew_retry_dropped"] == 0
    r = meraculous.run(smoke=True, skew="zipf")
    assert r["meraculous_build_skew_drop_dropped"] > 0
    assert r["meraculous_build_skew_retry_dropped"] == 0
    rows = [ln for ln in capsys.readouterr().out.strip().splitlines()
            if "," in ln]
    skew_rows = [ln for ln in rows if "_skew_" in ln]
    assert len(skew_rows) == 6
    for ln in skew_rows:
        cols = ln.split(",")
        assert len(cols) == ncols, ln
        assert cols[6] != "" and cols[7] != "", ln     # retry_rounds,dropped


def test_lm_moe_skew_arm_smoke(capsys):
    """The lm_step --skew zipf arm (MoE dispatch under zipf-routed
    tokens): the drop arm loses tokens at uniform expert capacity, the
    suggest_rounds-driven retry arm serves every token, and both rows
    follow the shared CSV schema (retry_rounds + dropped columns)."""
    from benchmarks import lm_step
    from benchmarks.util import HEADER
    ncols = len(HEADER.split(","))
    results = {}
    lm_step._moe_skew_arm(results, smoke=True)
    assert results["lm_moe_skew_drop_dropped"] > 0
    assert results["lm_moe_skew_retry_dropped"] == 0
    rows = [ln for ln in capsys.readouterr().out.strip().splitlines()
            if ln.startswith("lm_moe_skew_")]
    assert len(rows) == 2
    for ln in rows:
        cols = ln.split(",")
        assert len(cols) == ncols, ln
        assert cols[6] != "" and cols[7] != "", ln     # retry_rounds,dropped
    # the retry arm's round count came from the heuristic, not a constant
    retry_row = [ln for ln in rows if "retry" in ln][0]
    assert int(retry_row.split(",")[6]) > 1


def test_micro_async_arms_smoke(capsys):
    """The --async arms (DESIGN.md section 1.9): the split-phase rows
    carry overlap_launches > 0 while every other cost column (including
    collectives/bytes/hops) matches the sync row exactly — the
    charge-once-at-wait attribution rule, checked end to end through
    the CSV schema."""
    from benchmarks import micro_hashmap, micro_queue
    from benchmarks.util import HEADER
    ncols = len(HEADER.split(","))
    rq = micro_queue.run(smoke=True, async_=True)
    assert rq["cq_push_pop_sync"] > 0 and rq["cq_push_pop_async"] > 0
    rh = micro_hashmap.run(smoke=True, async_=True)
    assert rh["hashmap_find_insert_sync"] > 0
    assert rh["hashmap_find_insert_async"] > 0
    rows = [ln for ln in capsys.readouterr().out.strip().splitlines()
            if "," in ln]
    for ln in rows:
        assert len(ln.split(",")) == ncols, ln
    for sync_tag, async_tag in (
            ("cq_push_pop_sync", "cq_push_pop_async"),
            ("hashmap_find_insert_sync", "hashmap_find_insert_async")):
        s = [ln.split(",") for ln in rows
             if ln.startswith(sync_tag + ",")][0]
        a = [ln.split(",") for ln in rows
             if ln.startswith(async_tag + ",")][0]
        # collectives, bytes, rounds, hops, lost, unreachable all equal
        for i in (2, 3, 4, 8, 9, 11):
            assert s[i] == a[i], (sync_tag, i, s[i], a[i])
        assert s[12] == "0", s          # sync arm defers nothing
        assert int(a[12]) > 0, a        # async arm reports its deferrals


def test_lm_moe_async_arm_smoke(capsys):
    """The lm_step --async arm: split-phase MoE dispatch overlaps the
    wire (overlap_launches > 0) with cost totals equal to the sync arm
    (ISSUE acceptance: lm_step --async)."""
    from benchmarks import lm_step
    from benchmarks.util import HEADER
    ncols = len(HEADER.split(","))
    results = {}
    lm_step._moe_async_arm(results, smoke=True)
    assert results["lm_moe_dispatch_async_overlap"] > 0
    assert results["lm_moe_dispatch_sync_overlap"] == 0
    rows = [ln for ln in capsys.readouterr().out.strip().splitlines()
            if ln.startswith("lm_moe_dispatch_")]
    assert len(rows) == 2
    s = [ln.split(",") for ln in rows if "_sync," in ln][0]
    a = [ln.split(",") for ln in rows if "_async," in ln][0]
    assert len(s) == ncols and len(a) == ncols
    for i in (2, 3, 4, 8, 9, 11):
        assert s[i] == a[i], (i, s[i], a[i])
    assert s[12] == "0" and int(a[12]) > 0


def test_micro_faults_arms_smoke(capsys):
    """The --faults arms (DESIGN.md section 1.8): seeded corruption under
    the integrity checksum loses items (never silently), the carry /
    re-send heal recovers every one of them, the degraded-commit probe
    reports its dead rank, and the rows carry the lost_bytes / recovered
    / unreachable columns of the shared CSV schema."""
    from benchmarks import micro_hashmap, micro_queue
    from benchmarks.util import HEADER
    ncols = len(HEADER.split(","))
    micro_queue.run(smoke=True, faults=True)
    micro_hashmap.run(smoke=True, faults=True)
    rows = [ln for ln in capsys.readouterr().out.strip().splitlines()
            if "," in ln]
    for ln in rows:
        assert len(ln.split(",")) == ncols, ln
    fault_rows = [ln for ln in rows if "_faults" in ln.split(",")[0]]
    assert len(fault_rows) == 2
    for ln in fault_rows:
        cols = ln.split(",")
        # lost_bytes, recovered, unreachable: filled, and non-trivial —
        # the injected corruption really invalidated wire bytes, the
        # heal pass really recovered items, the probe really masked a
        # dead rank
        assert int(cols[9]) > 0, ln
        assert int(cols[10]) > 0, ln
        assert int(cols[11]) == 1, ln


def test_micro_transport_arm_smoke(capsys):
    """The --transport hier arm: micro benchmarks run the exchange over
    the two-stage transport, rows are suffixed _hier, and the hops
    column shows the extra stage (2 per launch where dense logs 1)."""
    from benchmarks import micro_queue
    from benchmarks.util import HEADER
    ncols = len(HEADER.split(","))
    r = micro_queue.run(smoke=True, transport="hier")
    for k in ("fq_push", "fq_pop", "fq_local_pop"):
        assert r[k] > 0, k
    rows = [ln for ln in capsys.readouterr().out.strip().splitlines()
            if "," in ln]
    hier_rows = [ln for ln in rows if ln.split(",")[0].endswith("_hier")]
    assert hier_rows, "no _hier rows emitted"
    for ln in hier_rows:
        cols = ln.split(",")
        assert len(cols) == ncols, ln
    fq = [ln.split(",") for ln in hier_rows
          if ln.startswith("fq_push_hier,")][0]
    # 8 waves x 2 hops/launch (collectives == hops for pure requests)
    assert int(fq[8]) == int(fq[2]) and int(fq[8]) == 16


def test_smoke_costs_pin_round_reduction():
    """The benchmark-side cost observables see the fused exchange."""
    from benchmarks.util import trace_costs
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax import ShapeDtypeStruct as SDS
    from repro.core import ConProm, get_backend
    from repro.containers import hashmap as hm

    bk = get_backend(None)
    spec, st = hm.hashmap_create(bk, 1 << 10, SDS((), jnp.uint32),
                                 SDS((), jnp.uint32), block_size=16)
    keys = jnp.asarray(np.arange(64), jnp.uint32)
    st, _ = hm.insert(bk, spec, st, keys, keys, capacity=64)

    c2 = trace_costs(
        jax.jit(lambda s, k: hm.find(bk, spec, s, k, capacity=64,
                                     promise=ConProm.HashMap.find,
                                     attempts=2)), st, keys)
    c_seq = trace_costs(
        jax.jit(lambda s, k: hm.find(bk, spec, s, k, capacity=64,
                                     promise=ConProm.HashMap.find,
                                     attempts=2, speculative=False)),
        st, keys)
    assert c2.collectives == 2 and c2.rounds == 2
    assert c_seq.collectives == 4 and c_seq.rounds == 4


def test_run_exits_nonzero_after_error_row(monkeypatch, capsys):
    """A module that raises prints its ERROR row and fails the run."""
    from benchmarks import micro_queue, run
    import repro.launch.compile_cache as cc

    def boom(smoke=False):
        raise RuntimeError("boom")

    monkeypatch.setattr(micro_queue, "run", boom)
    monkeypatch.setattr(cc, "enable_compile_cache", lambda: "")
    monkeypatch.setattr(sys, "argv", ["run.py", "--smoke", "micro_queue"])
    with pytest.raises(SystemExit) as exc:
        run.main()
    assert exc.value.code not in (None, 0)
    assert "micro_queue,ERROR" in capsys.readouterr().out
