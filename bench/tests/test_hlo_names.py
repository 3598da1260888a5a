"""Labels from compiled HLO text: Pallas kernels by their function,
fusions by the op they were fused around."""

import base64
import json

from bench import hlo_names


def _custom_call(name, body: bytes, target="tpu_custom_call"):
    cfg = json.dumps({"custom_call_config": {
        "body": base64.b64encode(body).decode()}})
    return (f"  %{name} = (u32[8,2,128]{{2,1,0}}, s32[8,16]{{1,0}}) "
            f"custom-call(u32[8,2,128]{{2,1,0}} %p), "
            f'custom_call_target="{target}", backend_config={cfg}')


HLO = "\n".join([
    "HloModule jit_insert, entry_computation_layout={()->()}",
    "",
    "%fused_computation.2 (param_0: u32[4]) -> u32[4] {",
    "  %param_0 = u32[4]{0} parameter(0)",
    "  ROOT %scatter.1 = u32[4]{0} scatter(%param_0, %param_0, %param_0)",
    "}",
    "",
    "ENTRY %main.9 (p: u32[8,2,128]) -> u32[4] {",
    _custom_call("insert.8", b"\x01loc(_insert_kernel)\x02_lane_pick "
                 b"_insert_kernel.<locals>.body _find_kernel"),
    _custom_call("insert.6", b"_offsets_kernel _stage_fused"),
    '  %custom-call.41 = pred[4]{0} custom-call(pred[4]{0} %x), '
    'custom_call_target="ConcatBitcast"',
    '  %fusion.2 = u32[4]{0} fusion(u32[4]{0} %a), kind=kCustom, '
    'calls=%fused_computation.2',
    '  %fusion = u32[4]{0} fusion(u32[4]{0} %b), kind=kCustom, '
    'calls=%fused_computation.2, metadata={op_name="jit(insert)/gather" '
    'stack_frame_id=1}',
    '  %fusion.7 = s32[4]{0} fusion(s32[4]{0} %e), kind=kLoop, '
    'calls=%fused_computation.2, metadata={op_name="jit(insert)/'
    'jit(searchsorted)/vmap()/while/body/closed_call/gather"}',
    '  %sort.9 = s32[4]{0} sort(s32[4]{0} %f), metadata={op_name='
    '"jit(insert)/jit(_where)/sort"}',
    "  ROOT %sort.3 = (u32[4]{0}, s32[4]{0}) sort(u32[4]{0} %c, s32[4]{0} %d)",
    "}",
])


def test_labels():
    lab = hlo_names.labels(HLO)
    assert lab[("jit_insert", "insert.8")] == "_insert_kernel"
    assert lab[("jit_insert", "insert.6")] == "_offsets_kernel"
    assert lab[("jit_insert", "custom-call.41")] == "ConcatBitcast"
    assert lab[("jit_insert", "fusion.2")] == "scatter-fusion"
    assert lab[("jit_insert", "fusion")] == "gather-fusion"
    assert lab[("jit_insert", "sort.3")] == "sort"
    assert lab[("jit_insert", "fusion.7")] == "gather-fusion@searchsorted"
    assert lab[("jit_insert", "sort.9")] == "sort"
    assert lab[("jit_insert", "scatter.1")] == "scatter"


def test_event_names():
    ev = "%insert.8 = (u32[1048576,2,128]{2,1,0:T(2,128)}) custom-call(...)"
    assert hlo_names.instruction_of(ev) == "insert.8"
    assert hlo_names.fallback(ev) == "insert"
    assert hlo_names.module_of("jit_insert(3641075523008381251)") == \
        "jit_insert"


def test_labels_of_a_program_compiled_here():
    import jax
    import jax.numpy as jnp

    f = jax.jit(lambda x, i: jnp.sort(x.at[i].add(1)))
    text = f.lower(jnp.zeros(64), jnp.arange(8)).compile().as_text()
    lab = hlo_names.labels(text)
    assert lab and all(isinstance(k, tuple) and len(k) == 2 for k in lab)
    assert "sort" in set(lab.values()) or any(
        v.startswith("sort") for v in lab.values())
