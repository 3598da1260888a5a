"""Ahead-of-time compiles of the main path's kernels for a TPU v5e.

The TPU compiler is installed without a chip, so each Pallas kernel is
compiled here for a described (not attached) ``v5e:2x2`` topology at the
widths the containers use on the chip (the hash probe kernels on query
tiles already binned, as ``hash_probe.insert``/``find`` and their
``_arrivals`` twins hand them over), plus one 1-chip hashmap insert +
find step under ``jax.shard_map``, which runs the whole wrapped path.  A kernel Mosaic refuses, or a program
that does not fit the chip's memory, fails here instead of on the chip.
Nothing runs: these tests say nothing about results or times.

The topology is described inside a fixture, never at import, so every
pytest worker collects the same tests and only the worker that runs this
file loads the TPU library.  Kernel decisions are steered to the TPU by
patching ``repro.kernels.platform`` inside each test.
"""

import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax import ShapeDtypeStruct as SDS
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P
from jax.sharding import SingleDeviceSharding

import repro.kernels as kernels
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
