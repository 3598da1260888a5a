"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler is installed without a chip, so each Pallas kernel is
compiled here for a described (not attached) ``v5e:2x2`` topology at the
widths the containers use on the chip (the hash probe kernels on query
tiles already binned, as ``hash_probe.insert``/``find`` and their
``_arrivals`` twins hand them over), plus one 1-chip hashmap insert +
find step under ``jax.shard_map``, which runs the whole wrapped path.  A kernel Mosaic refuses, or a program
that does not fit the chip's memory, fails here instead of on the chip.
Nothing runs: these tests say nothing about results or times.

Two more programs, the 1-chip ISx step (push + drain) and the 1-chip
speculative find, pin the library's layer scopes (``costs.scope``,
DESIGN.md section 1.11) in the compiled HLO: every op the library
traces carries a ``bcl.`` scope, and the program compiled with the
scopes is the program compiled without them, op for op.  They also pin
the lane-planar wire (DESIGN.md section 1.5): the transport reads its
arrivals and replies back without a gather.

The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests and only the worker that runs this
file loads the TPU library.  Kernel decisions are steered to the TPU by
patching ``repro.kernels.platform`` inside each test.
"""

import contextlib
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import ShapeDtypeStruct as SDS
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import repro.kernels as kernels
from repro.core import costs
from repro.kernels import binning, bloom_kernel, hash_probe, ops

U32, I32, BOOL = jnp.uint32, jnp.int32, jnp.bool_
SLOTS = 1 << 27          # one chip's table: 2^20 blocks of 128 slots
NB, BLOCK = SLOTS // 128, 128
N = 1 << 20              # one batch of keys / wire items
LK = LV = 2              # two-lane keys and values
Q = hash_probe.default_q_cap(N, NB)   # binned queries per block


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def on_tpu(monkeypatch):
    """Kernels compile with Mosaic, and "auto" picks the Pallas impls."""
    monkeypatch.setattr(kernels, "platform", lambda: "tpu")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    cache_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield
    jax.config.update("jax_enable_compilation_cache", cache_on)


def _table():
    return [((NB, LK, BLOCK), U32), ((NB, LV, BLOCK), U32),
            ((NB, BLOCK), U32)]


# name -> (function, [(shape, dtype), ...]) at the widths of the chip path
KERNELS = {
    "bin_offsets": (lambda b, v: binning.bin_offsets(b, 8, v),
                    [((N,), I32), ((N,), BOOL)]),
    "multi_bin_offsets": (
        lambda b, f, v: ops.multi_bin_offsets(b, f, 4, 2, v),
        [((N,), I32), ((N,), I32), ((N,), BOOL)]),
    "ragged_slots": (
        lambda b, f, o, v, wo, rw, c, r: ops.ragged_slots(
            b, f, o, v, 1, wo, rw, c, r, 4096, 1 << 30),
        [((N,), I32), ((N,), I32), ((N,), I32), ((N,), BOOL)]
        + [((2,), I32)] * 4),
    "stage_slots": (
        lambda b, f, o, v, wo, rw, c, lv: ops.stage_slots(
            b, f, o, v, wo, rw, c, lv, 4096, 1 << 30),
        [((N,), I32), ((N,), I32), ((N,), I32), ((N,), BOOL)]
        + [((2,), I32)] * 4),
    "insert_kernel": (
        lambda tk, tv, st, q: hash_probe.insert_binned(
            tk, tv, st, q, ops.MODE_SET, Q),
        _table() + [((NB, (LK + LV + 1) * Q), U32)]),
    "insert_add_kernel": (
        lambda tk, tv, st, q: hash_probe.insert_binned(
            tk, tv, st, q, ops.MODE_ADD, Q),
        _table() + [((NB, (LK + LV + 1) * Q), U32)]),
    "find_kernel": (
        lambda tk, tv, st, q: hash_probe.find_binned(tk, tv, st, q, Q),
        _table() + [((NB, (LK + 1) * Q), U32)]),
    "row_mix": (binning.row_mix, [((N, 1 + LK + LV + 1), U32)]),
    "bloom_membership": (bloom_kernel.membership,
                         [((N, 2), U32), ((N, 2), U32), ((N,), BOOL)]),
    "bloom_hash_words": (lambda x: bloom_kernel.hash_words(x, 4),
                         [((N, 1), U32)]),
}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_kernel_compiles_for_v5e(name, one_chip, on_tpu):
    fn, shapes = KERNELS[name]
    args = [SDS(s, d, sharding=one_chip) for s, d in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, f"{name}: no Mosaic kernel compiled"
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes)
    assert used < 16 * 10 ** 9, f"{name}: {used} bytes on a 16 GB chip"


def test_hashmap_step_compiles_under_shard_map(topo, on_tpu):
    """The 1-chip hashmap insert + find step, as ``chip_smoke.py`` runs
    it: SpmdBackend inside a checked jax.shard_map over ("bcl",)."""
    from repro.containers import hashmap as hm
    from repro.core import get_backend

    mesh = Mesh(np.array(topo.devices[:1]), ("bcl",))
    spec_box = {}

    def step(tk, tv, st, keys, vals, queries):
        bk = get_backend("bcl")
        spec, _ = hm.hashmap_create(bk, SLOTS, LK, LV, block_size=BLOCK)
        spec_box["spec"] = spec
        state, ok = hm.insert(bk, spec, hm.HashMapState(tk, tv, st), keys,
                              vals, capacity=N)
        state, found_vals, found = hm.find(bk, spec, state, queries,
                                           capacity=N)
        return tuple(state), ok, found_vals, found

    bcl = NamedSharding(mesh, P("bcl"))
    args = [SDS(s, d, sharding=bcl) for s, d in _table()
            + [((N, LK), U32), ((N, LV), U32), ((N, LK), U32)]]
    fn = jax.jit(jax.shard_map(step, mesh=mesh, in_specs=(P("bcl"),) * 6,
                               out_specs=((P("bcl"),) * 3, P("bcl"),
                                          P("bcl"), P("bcl"))),
                 donate_argnums=(0, 1, 2))
    compiled = fn.lower(*args).compile()
    assert spec_box["spec"].nblocks_local == NB
    assert compiled.as_text().count("tpu_custom_call") >= 3
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16 * 10 ** 9, f"{used} bytes on a 16 GB chip"


# --------------------------------------------------------------------------
# layer scopes
# --------------------------------------------------------------------------

#: every scope the library opens (``costs.scope``), layer by layer
SCOPES = {
    "bcl.hashmap.create", "bcl.hashmap.insert", "bcl.hashmap.find",
    "bcl.hashmap.find_insert", "bcl.queue.create", "bcl.queue.push",
    "bcl.queue.pop", "bcl.queue.push_pop", "bcl.queue.drain",
    "bcl.bloom.insert", "bcl.bloom.find", "bcl.bloom.insert_find",
    "bcl.exchange.bin", "bcl.exchange.commit", "bcl.exchange.finish",
    "bcl.transport.request", "bcl.transport.reply",
    "bcl.probe.bin", "bcl.probe.find", "bcl.probe.insert",
}
#: the ops a device trace shows as work
TRACED_KINDS = {"fusion", "custom-call", "gather", "scatter", "sort",
                "all-to-all"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%([\w.\-]+)\s*=\s*(?:\([^=]*?\)|\S+)"
                    r"\s+([\w\-]+)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_SCOPE = re.compile(r"(?:^|[/(])(bcl\.[\w.\-]+)")
_FRAME_TABLES = {"FileNames", "FunctionNames", "FileLocations",
                 "StackFrames"}
ISX_KEYS = 1 << 18       # keys of one ISx step at test size
FIND_SLOTS, FIND_N = 1 << 20, 1 << 16


def _isx_step(mesh):
    """One ISx iteration's library part: push every key to its bucket's
    queue, drain the chip's own queue (``bench/configs/isx.py``)."""
    from repro.containers import queue as q
    from repro.core import get_backend

    def step(keys, dest):
        bk = get_backend("bcl")
        spec, st = q.queue_create(bk, ISX_KEYS, SDS((), U32))
        st, _, dropped = q.push(bk, spec, st, keys, dest,
                                capacity=ISX_KEYS)
        rows, got = q.local_drain(spec, st)
        return rows, got, dropped[None]

    bcl = P("bcl")
    return (jax.shard_map(step, mesh=mesh, in_specs=bcl,
                          out_specs=(bcl,) * 3),
            [((ISX_KEYS,), U32), ((ISX_KEYS,), I32)])


def _speculative_find(mesh):
    """The lookup program of ``bench/configs/kmer_hashmap.py``: a
    2-attempt find, both attempts as flows of one plan."""
    from repro.containers import hashmap as hm
    from repro.core import get_backend

    def find(tk, tv, st, keys):
        bk = get_backend("bcl")
        spec, _ = hm.hashmap_create(bk, FIND_SLOTS, LK, LV,
                                    block_size=BLOCK)
        _, vals, found = hm.find(bk, spec, hm.HashMapState(tk, tv, st),
                                 keys, capacity=FIND_N)
        return vals, found

    nb = FIND_SLOTS // BLOCK
    shapes = [((nb, LK, BLOCK), U32), ((nb, LV, BLOCK), U32),
              ((nb, BLOCK), U32), ((FIND_N, LK), U32)]
    return (jax.shard_map(find, mesh=mesh, in_specs=(P("bcl"),) * 4,
                          out_specs=(P("bcl"),) * 2), shapes)


SCOPE_PROGRAMS = {"isx_step": _isx_step, "speculative_find": _speculative_find}


@pytest.fixture(scope="module")
def compiled_hlo(topo):
    """``hlo(name, scoped=True)``: the compiled HLO text of one program,
    with the library's scopes or with ``costs.scope`` a null context,
    compiled once for this module (inside a test, under ``on_tpu``)."""
    texts = {}

    def hlo(name, scoped=True):
        if (name, scoped) not in texts:
            mesh = Mesh(np.array(topo.devices[:1]), ("bcl",))
            sharded = NamedSharding(mesh, P("bcl"))
            real = costs.scope
            if not scoped:
                costs.scope = lambda op: contextlib.nullcontext()
            try:
                fn, shapes = SCOPE_PROGRAMS[name](mesh)
                args = [SDS(s, d, sharding=sharded) for s, d in shapes]
                texts[name, scoped] = (jax.jit(fn).lower(*args).compile()
                                       .as_text())
            finally:
                costs.scope = real
        return texts[name, scoped]
    return hlo


def _innermost_scope(op_name: str):
    found = _SCOPE.findall(op_name)
    return found[-1] if found else None


def _ops(text):
    """(instruction, opcode, op_name or None) of every instruction."""
    out = []
    for line in text.splitlines():
        m = _INSTR.match(line)
        if m:
            src = _OP_NAME.search(line)
            out.append((m.group(1), m.group(2),
                        src.group(1) if src else None))
    return out


def _without_names(text):
    """The module less its metadata: no ``metadata={...}``, no stack
    frame tables, and instructions renamed in order of appearance (XLA
    names a Pallas call after the innermost name-stack entry)."""
    kept, skip = [], False
    for line in text.splitlines():
        if line in _FRAME_TABLES:
            skip = True
        elif skip and not line:
            skip = False
        elif not skip:
            kept.append(re.sub(r",? metadata=\{[^}]*\}", "", line))
    names = {}
    rename = lambda m: "%" + names.setdefault(m.group(1), f"v{len(names)}")
    return [re.sub(r"%([\w.\-]+)", rename, line) for line in kept]


@pytest.mark.parametrize("name", sorted(SCOPE_PROGRAMS))
def test_library_ops_carry_a_layer_scope(name, compiled_hlo, on_tpu):
    """Every traced op the library emits (its ``op_name`` starts at the
    jitted program, ``jit(...)``) names a scope of the vocabulary; the
    ISx gathers land in the queue's drain and its scatters in the
    transport and the queue, the lookup's binning in the owner probe."""
    text = compiled_hlo(name)
    seen, missing = {}, []
    for instr, opcode, op_name in _ops(text):
        if opcode not in TRACED_KINDS or not (op_name or "").startswith(
                "jit("):
            continue
        scope = _innermost_scope(op_name)
        if scope is None:
            missing.append(f"{instr} ({opcode}): {op_name}")
        seen.setdefault(opcode, set()).add(scope)
    assert not missing, "ops with no layer scope:\n" + "\n".join(missing)
    assert set().union(*seen.values()) <= SCOPES
    if name == "isx_step":
        assert seen["gather"] == {"bcl.queue.drain"}
        assert "bcl.queue.push" in seen["scatter"]
        assert "bcl.exchange.bin" in seen["custom-call"]
    else:
        assert {"bcl.probe.bin", "bcl.probe.find"} <= seen["gather"]
        assert "bcl.probe.find" in seen["custom-call"]
        assert "bcl.probe.bin" in seen["sort"]
        assert "bcl.transport.reply" in seen["fusion"]


@pytest.mark.parametrize("name", sorted(SCOPE_PROGRAMS))
def test_scopes_change_no_op(name, compiled_hlo, on_tpu):
    """With its metadata and instruction names set aside, the program
    compiled with the scopes is the program compiled without them."""
    scoped = _without_names(compiled_hlo(name))
    plain = _without_names(compiled_hlo(name, scoped=False))
    assert len(scoped) > 100
    assert scoped == plain


@pytest.mark.parametrize("name", sorted(SCOPE_PROGRAMS))
def test_transport_reads_back_without_a_gather(name, compiled_hlo, on_tpu):
    """The wire's flow segments are lane-planar, so the transport's
    read-back of arrivals and replies (``rows_from_words``) is a reshape
    of major dimensions: no gather of either program carries a
    ``bcl.transport.`` scope."""
    gathers = [(instr, op_name) for instr, opcode, op_name
               in _ops(compiled_hlo(name)) if opcode == "gather"]
    assert gathers
    assert not [g for g in gathers if "bcl.transport." in (g[1] or "")]
