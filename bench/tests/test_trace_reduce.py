"""The trace reduction on synthesised events with known intervals, and
on a trace recorded here on the CPU for the host spans."""

import glob

import pytest

from bench import trace_reduce as trd

MS = 1_000_000  # ns


def summary():
    ops = {
        # chip 0: two overlapping ops, a gap, a third op past the window
        0: [("_insert_kernel", 0, 4 * MS), ("scatter.3", 2 * MS, 6 * MS),
            ("all-to-all.1", 8 * MS, 9 * MS), ("_insert_kernel", 9 * MS,
                                                 12 * MS)],
        # chip 1: one op covering half the window
        1: [("_find_kernel", 0, 5 * MS)],
    }
    spans = [("bench.window", 0, 10 * MS), ("bench.step", 0, 10 * MS),
             ("bench.wait", 6 * MS, 7 * MS), ("bench.resend", 7 * MS, 8 * MS)]
    return trd.reduce_events(ops, spans)


def test_merge_overlaps_and_touching():
    assert trd.merge([(5, 6), (0, 2), (1, 3), (3, 4)]) == [(0, 4), (5, 6)]


def test_busy_and_idle_share():
    s = summary()
    assert s.window == (0, 10 * MS)
    # chip 0: [0,6] + [8,10] (clipped) = 8 ms; chip 1: 5 ms -> mean 6.5
    assert s.busy_s() == pytest.approx(6.5e-3)
    assert s.idle_share() == pytest.approx(0.35)
    assert s.window_s == pytest.approx(0.01)


def test_op_seconds_matches_names_and_clips_to_window():
    s = summary()
    # chip 0: 4 ms + 1 ms (clipped at 10); averaged over 2 chips
    assert s.op_seconds(r"_insert_kernel") == pytest.approx(2.5e-3)
    assert s.op_seconds(r"scatter") == pytest.approx(2e-3)
    assert s.op_seconds(r"all-to-all") == pytest.approx(0.5e-3)
    assert s.op_seconds(r"nothing") == 0.0
    assert s.count(r"_insert_kernel") == 2
    assert s.count(r"nothing") == 0


def test_top_ops_and_idle_gaps_by_innermost_span():
    s = summary()
    top = dict(s.top_ops(10))
    assert top["_insert_kernel"] == pytest.approx(2.5e-3)
    assert list(top)[0] == "_insert_kernel"
    gaps = dict(s.idle_gaps(10))
    # a gap goes whole to the span open at its midpoint: chip 0 idles
    # [6,8] (midpoint 7, in resend), chip 1 idles [5,10] (7.5, in resend)
    assert gaps == {"bench.resend": pytest.approx((2e-3 + 5e-3) / 2)}


def test_window_falls_back_to_the_ops_extent():
    s = trd.reduce_events({0: [("a", 2, 5), ("b", 7, 9)]}, [])
    assert s.window == (2, 9)
    assert s.idle_share() == pytest.approx(2 / 7)


def test_no_device_ops_reads_nothing():
    s = trd.reduce_events({}, [("bench.window", 0, 10)])
    assert s.idle_share() is None
    assert s.busy_s() == 0.0


def test_recorded_trace_keeps_host_spans(tmp_path):
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x: jnp.sort(x) + 1)
    x = jnp.arange(1 << 12)
    f(x).block_until_ready()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.step"):
                f(x).block_until_ready()
    jax.profiler.stop_trace()
    path = trd.find_xplane(str(tmp_path))
    assert glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    s = trd.reduce_xplane(path, chips=1)
    names = [n for n, _, _ in s.spans]
    assert names.count("bench.step") == 3
    assert s.window_s > 0
    assert s.ops == {}          # the CPU has no TPU plane


def test_ops_are_named_through_the_program_that_runs_them():
    mods = [("jit_insert(123)", 0, 10), ("jit_find(9)", 20, 30)]
    ops = [("%insert.8 = (u32[4]) custom-call(u32[4] %a)", 1, 5),
           ("%fusion.2 = u32[4] fusion(u32[4] %b)", 5, 9),
           ("%fusion.2 = u32[4] fusion(u32[4] %c)", 21, 22),
           ("%sort.3 = u32[4] sort(u32[4] %d)", 12, 13)]
    labels = {("jit_insert", "insert.8"): "_insert_kernel",
              ("jit_insert", "fusion.2"): "scatter-fusion",
              ("jit_find", "fusion.2"): "gather-fusion"}
    got = [n for n, _, _ in trd._label_ops(ops, mods, labels)]
    assert got == ["_insert_kernel", "scatter-fusion", "gather-fusion",
                   "sort"]


def test_an_op_that_holds_others_counts_only_in_busy_time():
    ops = {0: [("while", 0, 10), ("gather-fusion", 1, 4),
               ("gather-fusion", 5, 8), ("sort", 12, 14)]}
    s = trd.reduce_events(ops, [("bench.window", 0, 20)])
    assert [n for n, _, _ in s.leaf_ops(0)] == ["gather-fusion",
                                                 "gather-fusion", "sort"]
    assert s.op_seconds("^gather") == pytest.approx(6e-9)
    assert s.op_seconds("^while") == 0.0
    assert s.busy_s() == pytest.approx(12e-9)
