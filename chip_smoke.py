#!/usr/bin/env python3
"""Smoke run of the container library's main path on TPU.

    python3 chip_smoke.py               # one chip: hashmap, queue, bloom, serve
    python3 chip_smoke.py --chips 4     # four chips: hashmap + queue, dense
                                        # and two-stage transports

Every phase runs through ``SpmdBackend`` inside ``jax.shard_map`` over a
``("bcl",)`` mesh, the library's production path, and is checked against
a plain numpy reference built from ``--seed``:

  hashmap  2^27 slots per chip in blocks of 128, two-lane keys and values;
           2^25 distinct keys per chip inserted in batches of 2^20 (items
           that report failure are re-sent, as the container's contract
           says), then 2^20 present and 2^20 absent keys per chip found.
  queue    ISx-style push of 2^24 u32 keys per chip, then local_drain; the
           received multiset equals the input, each key on its bucket.
  bloom    a 2^30-bit filter, 2^24 inserts, then finds: no false negative.
  serve    ``launch/serve.py`` for stablelm-1.6b at published widths and
           depth: 4 requests, prompt 128, 32 generated tokens.

``--chips 4`` runs only hashmap and queue, once with the dense transport
and once with ``HierarchicalTransport(2, 2)``; the two must agree bit for
bit, with each other and with the reference, with state on all four
devices.

Everything runs in this one process, which holds the chips.  The script
refuses to run without a TPU (it never falls back to the CPU) and
outside a checkout (it needs ``src/repro``).  Earlier lines report each
phase; the last line is the JSON verdict.
"""

from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
SRC = ROOT / "src"

HM_SLOTS = 1 << 27        # table slots per chip
HM_BLOCK = 128            # buckets per block
HM_KEYS = 1 << 25         # distinct keys inserted per chip
HM_BATCH = 1 << 20        # insert / find batch per chip
HM_TRIES = 4              # re-sends of failed inserts per batch
Q_KEYS = 1 << 24          # queue pushes per chip
BLOOM_BITS = 1 << 30
BLOOM_ITEMS = 1 << 24
SERVE_ARGS = ["--arch", "stablelm-1.6b", "--requests", "4", "--batch", "4",
              "--prompt-len", "128", "--gen", "32"]


class PhaseFailed(Exception):
    pass


def log(msg: str) -> None:
    print(msg, flush=True)


def expect(ok: bool, what: str) -> None:
    if not ok:
        raise PhaseFailed(what)


# --------------------------------------------------------------------------
# seeded data
# --------------------------------------------------------------------------

def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer: a bijection on u64, so distinct in, distinct out."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> np.uint64(30))) * np.uint64(0xBF58476D1CE4E5B9)
        x = (x ^ (x >> np.uint64(27))) * np.uint64(0x94D049BB133111EB)
    return x ^ (x >> np.uint64(31))


def key_lanes(index: np.ndarray, seed: int) -> np.ndarray:
    """(N, 2) u32 key lanes of distinct key indices; distinct per index."""
    x = mix64(index.astype(np.uint64) + np.uint64(seed << 40))
    return np.stack([(x >> np.uint64(32)).astype(np.uint32),
                     x.astype(np.uint32)], axis=1)


# --------------------------------------------------------------------------
# phases
# --------------------------------------------------------------------------

def make_mesh(n: int):
    import jax
    from repro.compat import make_mesh as _make_mesh
    return _make_mesh((n,), ("bcl",), devices=jax.devices()[:n])


def _sharded(mesh, x):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    return jax.device_put(x, NamedSharding(mesh, P("bcl")))


def _compile(fn, *args):
    t = time.time()
    c = fn.lower(*args).compile()
    return c, time.time() - t


def hashmap_programs(mesh, transport=None, slots=HM_SLOTS, batch=HM_BATCH,
                     block=HM_BLOCK):
    """(create, insert, find) jitted over the mesh; insert donates the
    table and takes a validity mask, so failed items can be re-sent."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.containers import hashmap as hm
    from repro.core import get_backend

    n = mesh.devices.size
    cap = batch if n == 1 else -(-batch * 5 // (4 * n))   # per (src, dst)
    box = {}

    def create():
        spec, st = hm.hashmap_create(get_backend("bcl"), slots * n, 2, 2,
                                     block_size=block)
        box["spec"] = spec
        return tuple(st)

    def insert(state, k, v, valid):
        st, ok = hm.insert(get_backend("bcl"), box["spec"],
                           hm.HashMapState(*state), k, v, capacity=cap,
                           valid=valid, transport=transport)
        return tuple(st), ok

    def find(state, k):
        _, vals, found = hm.find(get_backend("bcl"), box["spec"],
                                 hm.HashMapState(*state), k, capacity=cap,
                                 transport=transport)
        return vals, found

    bcl = P("bcl")
    sm = lambda f, i, o: jax.shard_map(f, mesh=mesh, in_specs=i, out_specs=o)
    return (jax.jit(sm(create, (), bcl)),
            jax.jit(sm(insert, (bcl,) * 4, (bcl, bcl)), donate_argnums=0),
            jax.jit(sm(find, (bcl, bcl), (bcl, bcl))))


def hashmap_phase(mesh, seed: int, transport=None, slots=HM_SLOTS,
                  keys_per_chip=HM_KEYS, batch=HM_BATCH,
                  block=HM_BLOCK) -> dict:
    """Insert, then find, against a numpy reference; returns the result
    (find outputs and the table) and its timings."""
    import jax
    import jax.numpy as jnp

    n = mesh.devices.size
    create, insert, find = hashmap_programs(mesh, transport, slots, batch,
                                            block)

    t = time.time()
    rng = np.random.default_rng(seed)
    total = keys_per_chip * n
    klanes = key_lanes(np.arange(total), seed)
    vlanes = rng.integers(0, 1 << 32, (total, 2), dtype=np.uint32)
    nb = keys_per_chip // batch
    kb = [_sharded(mesh, klanes[i::nb]) for i in range(nb)]
    vb = [_sharded(mesh, vlanes[i::nb]) for i in range(nb)]
    qi = rng.integers(0, total, batch * n)
    absent = key_lanes(np.arange(total, total + batch * n), seed)
    fk = [_sharded(mesh, klanes[qi]), _sharded(mesh, absent)]
    all_valid = _sharded(mesh, np.ones(batch * n, bool))
    state = create()
    jax.block_until_ready(state)
    setup_s = time.time() - t

    ins, c1 = _compile(insert, state, kb[0], vb[0], all_valid)
    fnd, c2 = _compile(find, state, fk[0])

    t = time.time()
    sends = 0
    for i in range(nb):
        pending = all_valid
        for _ in range(HM_TRIES):
            state, ok = ins(state, kb[i], vb[i], pending)
            sends += 1
            pending = pending & ~ok
            if not bool(pending.any()):
                break
        expect(not bool(pending.any()),
               f"batch {i}: {int(pending.sum())} inserts still failing "
               f"after {HM_TRIES} sends")
    jax.block_until_ready(state)
    insert_s = time.time() - t
    t = time.time()
    got = [fnd(state, k) for k in fk]
    jax.block_until_ready(got)
    find_s = time.time() - t

    vals = np.concatenate([np.asarray(g[0]) for g in got])
    found = np.concatenate([np.asarray(g[1]) for g in got])
    want_found = np.concatenate([np.ones(batch * n, bool),
                                 np.zeros(batch * n, bool)])
    want_vals = np.concatenate([vlanes[qi], np.zeros((batch * n, 2),
                                                     np.uint32)])
    expect(np.array_equal(found, want_found),
           f"found flags: {int((found != want_found).sum())} differ")
    expect(np.array_equal(vals, want_vals),
           f"values: {int((vals != want_vals).any(axis=1).sum())} rows differ")
    ready = [int(jnp.sum((s.data & 3) == 2))
             for s in state[2].addressable_shards]
    expect(sum(ready) == total,
           f"{sum(ready)} READY slots for {total} distinct keys")
    return {"state": state, "vals": vals, "found": found,
            "devices": len({s.device for s in state[2].addressable_shards}),
            "ready_per_device": ready, "sends": sends, "setup_s": setup_s,
            "compile_s": c1 + c2, "run_s": insert_s + find_s,
            "insert_s": insert_s, "find_s": find_s}


def queue_program(mesh, transport=None, keys_per_chip=Q_KEYS):
    """ISx push + local_drain, jitted over the mesh."""
    import jax
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as SDS
    from jax.sharding import PartitionSpec as P
    from repro.containers import queue as q
    from repro.core import get_backend

    n = mesh.devices.size
    ring = keys_per_chip if n == 1 else keys_per_chip * 5 // 4
    cap = keys_per_chip if n == 1 else -(-keys_per_chip * 5 // (4 * n))

    def push(values, dest):
        bk = get_backend("bcl")
        spec, st = q.queue_create(bk, ring, SDS((), jnp.uint32))
        st, _, dropped = q.push(bk, spec, st, values, dest, capacity=cap,
                                transport=transport)
        rows, got = q.local_drain(spec, st)
        return rows, got, dropped[None]

    bcl = P("bcl")
    return jax.jit(jax.shard_map(push, mesh=mesh, in_specs=(bcl, bcl),
                                 out_specs=(bcl,) * 3)), ring


def queue_phase(mesh, seed: int, transport=None,
                keys_per_chip=Q_KEYS) -> dict:
    """ISx-style bucket push + local_drain against a numpy multiset."""
    import jax

    n = mesh.devices.size
    shift = 32 - (n.bit_length() - 1)
    push, ring = queue_program(mesh, transport, keys_per_chip)

    t = time.time()
    rng = np.random.default_rng(seed + 1)
    keys = rng.integers(0, 1 << 32, keys_per_chip * n, dtype=np.uint32)
    dest = (keys.astype(np.uint64) >> np.uint64(shift)).astype(np.int32) \
        if n > 1 else np.zeros(keys.shape, np.int32)
    args = (_sharded(mesh, keys), _sharded(mesh, dest))
    jax.block_until_ready(args)
    setup_s = time.time() - t

    fn, c = _compile(push, *args)
    t = time.time()
    rows, got, dropped = jax.block_until_ready(fn(*args))
    run_s = time.time() - t

    rows, got = np.asarray(rows), np.asarray(got)
    expect(int(np.asarray(dropped)[0]) == 0,
           f"{int(np.asarray(dropped)[0])} pushes dropped")
    expect(np.array_equal(np.sort(rows[got]), np.sort(keys)),
           "received multiset differs from the pushed keys")
    per = rows.reshape(n, ring)
    pgot = got.reshape(n, ring)
    for r in range(n):
        mine = per[r][pgot[r]].astype(np.uint64) >> np.uint64(shift)
        expect(n == 1 or bool((mine == r).all()),
               f"rank {r} holds keys of another bucket")
    return {"rows": rows, "got": got,
            "received_per_device": pgot.sum(axis=1).tolist(),
            "setup_s": setup_s, "compile_s": c, "run_s": run_s}


def bloom_programs(mesh, bits=BLOOM_BITS, items=BLOOM_ITEMS):
    """(create, insert, find) jitted over the mesh."""
    import jax
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as SDS
    from jax.sharding import PartitionSpec as P
    from repro.containers import bloom as bl
    from repro.core import get_backend

    box = {}

    def create():
        spec, st = bl.bloom_create(get_backend("bcl"), bits,
                                   SDS((), jnp.uint32))
        box["spec"] = spec
        return st.words

    def insert(words, x):
        st, already = bl.insert(get_backend("bcl"), box["spec"],
                                bl.BloomState(words), x, capacity=items)
        return st.words, already

    def find(words, x):
        return bl.find(get_backend("bcl"), box["spec"], bl.BloomState(words),
                       x, capacity=items)

    bcl = P("bcl")
    sm = lambda f, i, o: jax.shard_map(f, mesh=mesh, in_specs=i, out_specs=o)
    return (jax.jit(sm(create, (), bcl)),
            jax.jit(sm(insert, (bcl, bcl), (bcl, bcl)), donate_argnums=0),
            jax.jit(sm(find, (bcl, bcl), bcl)))


def bloom_phase(mesh, seed: int, bits=BLOOM_BITS,
                items=BLOOM_ITEMS) -> dict:
    """Insert distinct items, then find them and as many absent ones."""
    import jax

    create, insert, find = bloom_programs(mesh, bits, items)
    t = time.time()
    # an odd multiplier is a bijection mod 2^32: distinct indices, distinct items
    idx = np.arange(2 * items, dtype=np.uint64) + np.uint64(seed * 2 * items)
    vals = ((idx * np.uint64(0x9E3779B1)) % np.uint64(1 << 32)) \
        .astype(np.uint32)
    ins_x, abs_x = _sharded(mesh, vals[:items]), _sharded(mesh, vals[items:])
    words = create()
    jax.block_until_ready(words)
    setup_s = time.time() - t

    ins, c1 = _compile(insert, words, ins_x)
    fnd, c2 = _compile(find, words, ins_x)
    t = time.time()
    words, _ = ins(words, ins_x)
    hit = np.asarray(fnd(words, ins_x))
    fp = np.asarray(fnd(words, abs_x))
    run_s = time.time() - t
    expect(bool(hit.all()), f"{int((~hit).sum())} false negatives")
    rate = float(fp.mean())
    expect(rate < 0.01, f"false-positive rate {rate} above 1%")
    return {"fp_rate": rate, "setup_s": setup_s, "compile_s": c1 + c2,
            "run_s": run_s}


def serve_phase() -> dict:
    from repro.launch import serve
    res = serve.run(SERVE_ARGS)
    toks = np.asarray([t for out in res["outputs"].values() for t in out])
    expect(toks.size == 4 * 32, f"{toks.size} tokens generated, not 128")
    expect(bool(((toks >= 0) & (toks < res["vocab"])).all()),
           "token id outside [0, vocab)")
    expect(res["logits_finite"], "non-finite logits")
    return res


# --------------------------------------------------------------------------
# driver
# --------------------------------------------------------------------------

WIRE_OPS = ("multi_bin_offsets", "ragged_slots", "pack_rows", "place_rows")


def ops_line(ops) -> str:
    from repro.kernels import ops as kops
    return ", ".join(f"{op}={kops.lowering(op)}" for op in ops)


def run_one_chip(mesh, seed: int) -> None:
    hm = hashmap_phase(mesh, seed)
    log(f"[hashmap] slots={HM_SLOTS} block={HM_BLOCK} keys={HM_KEYS} "
        f"batch={HM_BATCH} lanes=2+2 sends={hm['sends']} | "
        + ops_line(WIRE_OPS + ("bulk_insert_arrivals",
                               "bulk_find_arrivals")))
    log(f"[hashmap] setup_s={hm['setup_s']:.2f} compile_s="
        f"{hm['compile_s']:.2f} run_s={hm['run_s']:.2f} (insert "
        f"{hm['insert_s']:.2f}, find {hm['find_s']:.2f}) check=PASS")
    del hm
    qu = queue_phase(mesh, seed)
    log(f"[queue] keys={Q_KEYS} | " + ops_line(WIRE_OPS))
    log(f"[queue] setup_s={qu['setup_s']:.2f} compile_s={qu['compile_s']:.2f}"
        f" run_s={qu['run_s']:.2f} check=PASS")
    del qu
    bf = bloom_phase(mesh, seed)
    log(f"[bloom] bits={BLOOM_BITS} items={BLOOM_ITEMS} fp_rate="
        f"{bf['fp_rate']} | "
        + ops_line(WIRE_OPS + ("bloom_insert", "bloom_find")))
    log(f"[bloom] setup_s={bf['setup_s']:.2f} compile_s={bf['compile_s']:.2f}"
        f" run_s={bf['run_s']:.2f} check=PASS")
    sv = serve_phase()
    log(f"[serve] {' '.join(SERVE_ARGS)} tokens={sv['n_decoded']} "
        f"compile_s={sv['compile_s']:.2f} run_s={sv['run_s']:.2f} "
        f"check=PASS")


def run_four_chips(mesh, seed: int) -> None:
    import jax
    import jax.numpy as jnp
    from repro.core import HierarchicalTransport

    same = jax.jit(lambda a, b: jnp.array_equal(a, b))
    dense = hashmap_phase(mesh, seed)
    hier = hashmap_phase(mesh, seed, transport=HierarchicalTransport(2, 2))
    for name, r in (("dense", dense), ("hier 2x2", hier)):
        log(f"[hashmap {name}] slots/chip={HM_SLOTS} keys/chip={HM_KEYS} "
            f"devices={r['devices']} ready/device={r['ready_per_device']} "
            f"sends={r['sends']} setup_s={r['setup_s']:.2f} compile_s="
            f"{r['compile_s']:.2f} run_s={r['run_s']:.2f} (insert "
            f"{r['insert_s']:.2f}, find {r['find_s']:.2f}) check=PASS")
        expect(r["devices"] == 4 and min(r["ready_per_device"]) > 0,
               f"hashmap {name}: state not spread over four devices")
    expect(all(bool(same(a, b)) for a, b in zip(dense["state"],
                                                 hier["state"])),
           "dense and hierarchical tables differ")
    expect(np.array_equal(dense["vals"], hier["vals"])
           and np.array_equal(dense["found"], hier["found"]),
           "dense and hierarchical finds differ")
    log("[hashmap] dense == hier: tables and finds bit-identical")
    del dense, hier
    qd = queue_phase(mesh, seed)
    qh = queue_phase(mesh, seed, transport=HierarchicalTransport(2, 2))
    for name, r in (("dense", qd), ("hier 2x2", qh)):
        log(f"[queue {name}] keys/chip={Q_KEYS} received/device="
            f"{r['received_per_device']} setup_s={r['setup_s']:.2f} "
            f"compile_s={r['compile_s']:.2f} run_s={r['run_s']:.2f} "
            f"check=PASS")
        expect(min(r["received_per_device"]) > 0,
               f"queue {name}: a device received nothing")
    expect(np.array_equal(qd["rows"], qh["rows"])
           and np.array_equal(qd["got"], qh["got"]),
           "dense and hierarchical queues differ")
    log("[queue] dense == hier: drained rings bit-identical")
    log("[wire] " + ops_line(WIRE_OPS + ("bulk_insert_arrivals",
                                         "bulk_find_arrivals")))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    if not (SRC / "repro").is_dir():
        print(f"chip_smoke: no src/repro next to {Path(__file__).name}; "
              "run it from a checkout of the repository", file=sys.stderr)
        return 2
    sys.path.insert(0, str(SRC))
    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX finds no TPU (platform "
              f"{devices[0].platform!r}); this script runs only on the chip",
              file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices, "
              f"JAX finds {len(devices)}", file=sys.stderr)
        return 1
    from repro.kernels import interpret_mode
    from repro.launch.compile_cache import enable_compile_cache
    if interpret_mode():
        print("chip_smoke: Pallas kernels would run in interpret mode",
              file=sys.stderr)
        return 1
    log(f"chip_smoke: {devices[0].device_kind} x{len(devices)}, "
        f"--chips {args.chips}, seed {args.seed}, compile cache "
        f"{enable_compile_cache()}")

    mesh = make_mesh(args.chips)
    t = time.time()
    try:
        if args.chips == 4:
            run_four_chips(mesh, args.seed)
        else:
            run_one_chip(mesh, args.seed)
    except PhaseFailed as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    log(f"chip_smoke: all phases passed in {time.time() - t:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
