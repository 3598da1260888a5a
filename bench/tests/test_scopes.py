"""Layer scopes read back from compiled HLO text, and device time shared
out by scope on synthesised traces."""

import pytest

from bench import hlo_names, scopes
from bench import trace_reduce as trd
from bench.tests.test_hlo_names import HLO as LABELLED

MS = 1_000_000  # ns

HLO = "\n".join([
    "HloModule jit_step, entry_computation_layout={()->()}",
    "",
    "%fused_computation.1 (param_0: u32[4]) -> u32[4] {",
    "  %param_0 = u32[4]{0} parameter(0)",
    '  ROOT %gather.1 = u32[4]{0} gather(%param_0, %param_0), metadata={'
    'op_name="jit(step)/bcl.queue.push/bcl.exchange.commit/'
    'bcl.transport.request/gather" stack_frame_id=3}',
    "}",
    "",
    "ENTRY %main.9 (p: u32[4]) -> u32[4] {",
    # a fusion is charged by the op_name XLA copied from its root
    '  %fusion.2 = u32[4]{0} fusion(u32[4]{0} %p), kind=kLoop, '
    'calls=%fused_computation.1, metadata={op_name="jit(step)/'
    'bcl.queue.push/bcl.exchange.commit/bcl.transport.request/gather"}',
    # a scope around a jitted library function call
    '  %fusion.3 = s32[4]{0} fusion(s32[4]{0} %q), kind=kLoop, '
    'calls=%fused_computation.1, metadata={op_name="jit(step)/'
    'bcl.hashmap.find/bcl.probe.bin/jit(searchsorted)/vmap()/while/body/'
    'closed_call/gather"}',
    # a Pallas call takes the innermost scope's name
    '  %bcl.exchange.bin.1 = s32[4]{0} custom-call(s32[4]{0} %d), '
    'custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/'
    'bcl.queue.push/bcl.exchange.commit/bcl.exchange.bin/pallas_call"}',
    # the harness's own op, and one XLA made with no metadata
    '  %sort.4 = u32[4]{0} sort(u32[4]{0} %r), metadata={op_name='
    '"jit(step)/sort"}',
    '  %custom-call.5 = u32[4]{0} custom-call(u32[4]{0} %s), '
    'custom_call_target="ConcatBitcast"',
    "  ROOT %copy.6 = u32[4]{0} copy(%fusion.2)",
    "}",
])


def test_innermost_scope_of_nested_scopes_and_fusion_root():
    got = scopes.scopes(HLO)
    assert got[("jit_step", "fusion.2")] == "bcl.transport.request"
    assert got[("jit_step", "gather.1")] == "bcl.transport.request"
    assert got[("jit_step", "fusion.3")] == "bcl.probe.bin"
    assert got[("jit_step", "bcl.exchange.bin.1")] == "bcl.exchange.bin"
    assert got[("jit_step", "sort.4")] is None
    assert got[("jit_step", "custom-call.5")] is None
    assert got[("jit_step", "copy.6")] is None


def test_scopes_name_the_instructions_labels_names():
    for text in (HLO, LABELLED):
        assert set(scopes.scopes(text)) == set(hlo_names.labels(text))
    assert set(scopes.scopes(LABELLED).values()) == {None}


def test_joined_names_split_back_into_scopes():
    labels = hlo_names.labels(HLO)
    names = scopes.joined(scopes.scopes(HLO), labels)
    assert names[("jit_step", "fusion.2")] == \
        "bcl.transport.request|gather-fusion"
    assert names[("jit_step", "sort.4")] == "(unscoped)|sort"
    assert scopes.scope_of(names[("jit_step", "fusion.3")]) == \
        "bcl.probe.bin"
    assert scopes.scope_of("(unscoped)|sort") == "(unscoped)"
    # an op the programs did not name reads by its fallback label
    assert scopes.scope_of("fusion") == "(unscoped)"


def _trace():
    """Chip 0 holds a while around two scoped gathers; chip 1 a probe
    kernel and an unnamed op; one op reaches past the window."""
    ops = {
        0: [("(unscoped)|while", 0, 10 * MS),
            ("bcl.transport.request|gather-fusion", 1 * MS, 4 * MS),
            ("bcl.queue.drain|gather-fusion", 5 * MS, 8 * MS),
            ("(unscoped)|sort", 12 * MS, 22 * MS)],
        1: [("bcl.probe.find|_find_kernel", 0, 6 * MS),
            ("fusion", 6 * MS, 7 * MS),
            ("bcl.probe.bin|gather-fusion@searchsorted", 7 * MS, 9 * MS)],
    }
    return trd.reduce_events(ops, [("bench.window", 0, 20 * MS)])


def test_scope_time_plus_unscoped_is_the_leaf_time():
    s = _trace()
    split = scopes.by_scope(s)
    assert split == pytest.approx({
        "(unscoped)": (8e-3 + 1e-3) / 2,       # sort clipped, fallback op
        "bcl.probe.find": 3e-3,
        "bcl.transport.request": 1.5e-3,
        "bcl.queue.drain": 1.5e-3,
        "bcl.probe.bin": 1e-3})
    leaf = sum(t for _, t in s.top_ops(10 ** 9))
    assert sum(split.values()) == pytest.approx(leaf)
    # the while holds the gathers: busy time counts it, self time not
    assert leaf < s.busy_s()


def test_scope_seconds_and_layers_by_prefix():
    s = _trace()
    assert scopes.scope_seconds(s, "bcl.probe.") == pytest.approx(4e-3)
    assert scopes.scope_seconds(s, "bcl.probe.bin") == pytest.approx(1e-3)
    assert scopes.scope_seconds(s, "bcl.hashmap.") == 0.0
    got = scopes.layers(s, "isx.sort", steps=2)
    assert got == pytest.approx({"transport_ms.isx": 0.75,
                                 "queue_ms.isx": 0.75})
    assert "exchange_ms.isx" not in got        # no op of the scope ran
    assert scopes.layers(s, "kmer.lookup", steps=0) == {}


def test_traced_run_reads_by_scope_on_the_cpu():
    """The whole path on a tiny ISx run: run.py's own traced run, read a
    second time by scope; the CPU has no TPU plane, so both readings are
    empty, and run.py's pieces are put back."""
    from bench import run
    from bench.tests.test_harness import TINY

    before = (hlo_names.labels, trd.reduce_xplane, run.load_config)
    result, got, cells = scopes.traced_by_scope(lambda: run.run_cell(
        "isx.sort", 2**31 + 5, 0.3, True, require_chip=False,
        overrides=TINY["isx.sort"]))
    assert (hlo_names.labels, trd.reduce_xplane, run.load_config) == before
    assert result["correct"]
    assert set(got) == {"scopes", "labels"} and got["scopes"].ops == {}
    steps = cells[0].counters()["steps"]
    assert steps >= 1
    lines = scopes.report("isx.sort", got, steps)
    assert lines[0] == "bench: scopes {}"
    assert f"steps={steps}" in lines[1]
