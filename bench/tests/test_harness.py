"""Whole runs of every cell on the CPU at tiny sizes, through the
harness with its chip check skipped: correct on sound runs, not correct
under the control and under each fault a cell can have."""

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run
from bench.configs import isx, kmer_hashmap as km

ROOT = Path(__file__).resolve().parents[2]

TINY = {
    "kmer.count": {"config": {"slots_per_chip": 4096},
                   "traffic": {"batch_per_chip": 256, "distinct": 1024}},
    "kmer.lookup": {"config": {"slots_per_chip": 4096},
                    "traffic": {"max_batch_per_chip": 256,
                                "rate_per_chip": 4000,
                                "fill": {"distinct": 1000,
                                         "batch_per_chip": 256}}},
    "isx.sort": {"config": {"keys_per_chip": 4096}},
}
CELLS = list(TINY)

# kmer.count is out of BENCHMARK.json while the program stores some
# k-mers twice under its traffic (PERF.md, Open questions).  Its driver
# stays in bench/configs/kmer_hashmap.py, its traffic and metric readers
# in their files; these are the entries that bring it back.
KMER_COUNT = {
    "workload": {"name": "kmer.count", "config": "kmer_hashmap",
                 "traffic": "kmer.count", "chips": 1,
                 "why": "closed loop, 2^20 occurrences a batch from 742,741 "
                        "distinct k-mers (about 24 each a window), MODE_ADD "
                        "under the insert promise: the owner probe does the "
                        "work"},
    "per_layer": [
        {"name": "probe_ms.count", "unit": "ms", "better": "lower",
         "source": "device_trace", "layer": "owner probes",
         "moves": "ops_per_s", "workloads": ["kmer.count"]},
        {"name": "probe_roofline.count", "unit": "%", "better": "higher",
         "source": "device_trace", "layer": "owner probes",
         "moves": "ops_per_s", "workloads": ["kmer.count"]},
        {"name": "resends_per_batch", "unit": "calls", "better": "lower",
         "source": "program_counter", "layer": "containers",
         "moves": "ops_per_s", "workloads": ["kmer.count"]}],
}


@pytest.fixture(autouse=True)
def with_kmer_count(monkeypatch):
    def load(root=ROOT):
        bench = json.loads((root / "BENCHMARK.json").read_text())
        bench["workloads"].append(KMER_COUNT["workload"])
        for m in bench["end_to_end"]:
            if m["name"] == "ops_per_s":
                m["workloads"].append("kmer.count")
        bench["per_layer"] += KMER_COUNT["per_layer"]
        return bench
    monkeypatch.setattr(run, "load_benchmark", load)


def tiny_run(cell, seed=2**31 + 11, control=False, trace=False):
    return run.run_cell(cell, seed, 0.3, trace, require_chip=False,
                        overrides=TINY[cell], control=control)


def numbers(result):
    return {k: c["value"] for k, c in result["checks"].items()}


@pytest.mark.parametrize("cell", CELLS)
def test_sound_run_is_correct(cell):
    r = tiny_run(cell)
    assert r["correct"], r["checks"]
    assert r["attempted"] > 0 and r["failed"] == 0
    assert list(r)[-1] == "checks"
    want = ({"lookup_p95_ms", "setup_s"} if cell == "kmer.lookup"
            else {"ops_per_s", "setup_s"})
    assert set(r["metrics"]) == want
    assert all(m["value"] > 0 for m in r["metrics"].values())
    assert r["device"]["count"] == 1


@pytest.mark.parametrize("cell", CELLS)
def test_control_is_not_correct(cell):
    r = tiny_run(cell, control=True)
    assert not r["correct"], r["checks"]


def test_traced_run_has_device_fields():
    r = tiny_run("isx.sort", trace=True)
    assert r["correct"]
    assert {"busy_s", "window_s"} <= set(r["device"])
    assert r["device"]["window_s"] > 0
    assert set(r["breakdown"]) == {"device_ops", "idle_gaps"}


# --------------------------------------------------------------------------
# faults planted under the timed path
# --------------------------------------------------------------------------

def _first_half(x):
    n = x.shape[0]
    return x & (jnp.arange(n) < n // 2)


def hashmap_fault(kind):
    orig = km.hashmap_programs

    def build(*a, **k):
        create, insert, find = orig(*a, **k)
        if kind == "unchanged":
            return create, lambda st, k_, v, valid: (st, valid), find
        if kind == "half":
            return (create,
                    lambda st, k_, v, valid: (insert(st, k_, v,
                                                     _first_half(valid))[0],
                                              valid),
                    lambda st, k_, valid: find(st, k_, _first_half(valid)))
        if kind == "altered":
            def ins(st, k_, v, valid):
                return insert(st, k_, v.at[0, 0].add(1), valid)

            def fnd(st, k_, valid):
                vals, found = find(st, k_, valid)
                return vals.at[:, 1].add(1), found
            return create, ins, fnd
        raise ValueError(kind)
    return build


@pytest.mark.parametrize("cell", ["kmer.count", "kmer.lookup"])
@pytest.mark.parametrize("kind", ["unchanged", "half", "altered"])
def test_hashmap_fault_is_not_correct(monkeypatch, cell, kind):
    monkeypatch.setattr(km, "hashmap_programs", hashmap_fault(kind))
    r = tiny_run(cell)
    assert not r["correct"], (kind, numbers(r))


def isx_fault(kind):
    orig = isx.sort_program

    def build(mesh, kpc, key_space, capacity, transport=None):
        step = orig(mesh, kpc, key_space, capacity, transport)
        n = mesh.devices.size
        ring = isx.ring_size(kpc, n)
        if kind == "unchanged":
            def empty(keys):
                _, got, dropped = step(keys)
                return (jnp.full((ring * n,), isx.PAD, jnp.uint32),
                        got * 0, dropped)
            return empty
        if kind == "altered":
            def bump(keys):
                out, got, dropped = step(keys)
                return out.at[0].add(1), got, dropped
            return bump
        if kind == "lost_once":
            # one step of the window loses a key and does not count it
            # as dropped
            calls = [0]

            def lose(keys):
                out, got, dropped = step(keys)
                calls[0] += 1
                if calls[0] == 3:
                    got = got.at[0].add(-1)
                return out, got, dropped
            return lose
        return _isx_variant(mesh, kpc, key_space, capacity, kind)
    return build


def _isx_variant(mesh, kpc, key_space, capacity, kind):
    """The ISx step with half the keys left out."""
    from jax import ShapeDtypeStruct as SDS
    from jax.sharding import PartitionSpec as P
    from repro.containers import queue as q
    from repro.core import get_backend

    n = mesh.devices.size
    ring = isx.ring_size(kpc, n)
    shift = isx.bucket_shift(key_space, n)

    def step(keys):
        bk = get_backend("bcl")
        dest = (keys >> shift).astype(jnp.int32)
        valid = jnp.ones(keys.shape, bool)
        if kind == "half":
            valid = _first_half(valid)
        spec, st = q.queue_create(bk, ring, SDS((), jnp.uint32))
        st, _, dropped = q.push(bk, spec, st, keys, dest, capacity=capacity,
                                valid=valid)
        rows, got = q.local_drain(spec, st)
        out = jnp.sort(jnp.where(got, rows, jnp.uint32(isx.PAD)))
        return out, got.sum(dtype=jnp.int32)[None], dropped[None]

    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=P("bcl"),
                                 out_specs=(P("bcl"),) * 3))


@pytest.mark.parametrize("kind", ["unchanged", "half", "altered",
                                  "lost_once"])
def test_isx_fault_is_not_correct(monkeypatch, kind):
    monkeypatch.setattr(isx, "sort_program", isx_fault(kind))
    r = tiny_run("isx.sort")
    assert not r["correct"], (kind, numbers(r))


# --------------------------------------------------------------------------
# the command
# --------------------------------------------------------------------------

def _command(cwd, env_extra=None):
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env.update(JAX_PLATFORMS="cpu", **(env_extra or {}))
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "isx.sort", "--seed",
         "1", "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=env, capture_output=True, text=True, timeout=300)


def test_command_refuses_without_a_chip():
    p = _command(ROOT)
    assert p.returncode == 1, p.stderr
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_command_refuses_outside_a_checkout(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    p = _command(tmp_path)
    assert p.returncode != 0
    assert p.stdout.strip() == ""


def test_isx_keys_repeat_per_seed_and_stay_in_range():
    from bench.device import make_mesh

    make = isx.key_program(make_mesh(jax.devices()[:1]), 1024, 1 << 28, 2)
    a = [np.asarray(x) for x in make(np.uint32(7), np.uint32(1))]
    b = [np.asarray(x) for x in make(np.uint32(7), np.uint32(1))]
    c = [np.asarray(x) for x in make(np.uint32(7), np.uint32(2))]
    assert all(np.array_equal(x, y) for x, y in zip(a, b))
    assert not np.array_equal(a[0], c[0])
    assert not np.array_equal(a[0], a[1])
    assert max(x.max() for x in a) < (1 << 28)
