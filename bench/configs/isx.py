"""Configuration ``isx``: the ISx bucket sort on BCL queues.

One step is one ISx iteration on every chip: bucket the chip's keys by
key range (one bucket per chip), push them to the owners' queues
through ``containers/queue.py`` (``queue.push``, dense transport),
``local_drain`` the chip's own queue and sort it.  The keys are uniform
u32 in [0, key_space), made on the device from the seed at set-up, a
few sets of them used in turn.

The push program was copied from the repository's ``chip_smoke.py``
(``queue_program``) and extended by the local sort.  The plain
reference counts the keys of each bucket and sorts them with numpy.
Once the window has closed, every step's count of keys received on
each chip is compared with the bucket's count, and a sample of the
steps, drawn from the seed, is compared key by key.
"""

from __future__ import annotations

import numpy as np

from bench import traffic as tr
from bench.device import shapes_of, transport_of

#: the number compared counts wrong keys: exact, limit 0
LIMITS = {"wrong_keys": 0}
PAD = 0xFFFFFFFF           # drained slots past the ring's tail sort last


def ring_size(keys_per_chip: int, n: int) -> int:
    return keys_per_chip if n == 1 else keys_per_chip * 5 // 4


def pair_capacity(keys_per_chip: int, n: int) -> int:
    return keys_per_chip if n == 1 else -(-keys_per_chip * 5 // (4 * n))


def bucket_shift(key_space: int, n: int) -> int:
    """Bucket of a key = key >> shift: equal key ranges, one per chip."""
    return (key_space.bit_length() - 1) - (n.bit_length() - 1)


def sort_program(mesh, keys_per_chip: int, key_space: int, capacity: int,
                 transport=None):
    """One ISx iteration jitted over the mesh: keys (per chip) ->
    (sorted ring, keys received, keys dropped)."""
    import jax
    import jax.numpy as jnp
    from jax import ShapeDtypeStruct as SDS
    from jax.sharding import PartitionSpec as P
    from repro.containers import queue as q
    from repro.core import get_backend

    n = mesh.devices.size
    ring = ring_size(keys_per_chip, n)
    shift = bucket_shift(key_space, n)

    def step(keys):
        bk = get_backend("bcl")
        dest = (keys >> shift).astype(jnp.int32)
        spec, st = q.queue_create(bk, ring, SDS((), jnp.uint32))
        st, _, dropped = q.push(bk, spec, st, keys, dest, capacity=capacity,
                                transport=transport)
        rows, got = q.local_drain(spec, st)
        out = jnp.sort(jnp.where(got, rows, jnp.uint32(PAD)))
        return out, got.sum(dtype=jnp.int32)[None], dropped[None]

    bcl = P("bcl")
    return jax.jit(jax.shard_map(step, mesh=mesh, in_specs=bcl,
                                 out_specs=(bcl,) * 3))


def key_program(mesh, keys_per_chip: int, key_space: int, sets: int):
    """Every key set in one jitted call on the device: uniform u32 in
    [0, key_space), from the seed."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    n = mesh.devices.size
    bits = key_space.bit_length() - 1

    def make(lo, hi):
        key = jax.random.fold_in(jax.random.key(lo), hi)
        return tuple(jax.random.bits(jax.random.fold_in(key, s),
                                     (keys_per_chip * n,), jnp.uint32)
                     >> (32 - bits) for s in range(sets))

    out = NamedSharding(mesh, P("bcl"))
    return jax.jit(make, out_shardings=(out,) * sets)


def bucket_counts(keys: np.ndarray, n: int, shift: int) -> np.ndarray:
    """Keys of each chip's bucket."""
    return np.bincount(keys >> np.uint32(shift), minlength=n)


def compare_sorted(keys: np.ndarray, out: np.ndarray, got: np.ndarray,
                   n: int, shift: int) -> dict:
    """Numbers that decide one sampled step: keys on a chip that is not
    their bucket's, and positions of a chip's received, sorted keys that
    differ from numpy's sort of that bucket (a missing or extra key
    counts once per position it shifts)."""
    ring = out.size // n
    misplaced = wrong = 0
    bucket = keys >> np.uint32(shift)
    for r in range(n):
        mine = out[r * ring:r * ring + int(got[r])]
        want = np.sort(keys[bucket == r])
        misplaced += int(((mine >> np.uint32(shift)) != r).sum())
        m = min(mine.size, want.size)
        wrong += int((mine[:m] != want[:m]).sum()) + abs(mine.size - want.size)
    return {"misplaced": misplaced, "wrong_key": wrong}


class SortCell:
    """Closed loop of ISx iterations; the control pushes with each
    (source, destination) pair's capacity cut to 3/4 of its mean share,
    so keys are dropped, which the delivery guarantee forbids."""

    loop = "closed"

    def __init__(self, mesh, cfg, traffic, seed, span, control=False):
        self.mesh, self.span, self.seed = mesh, span, seed
        self.n = mesh.devices.size
        self.kpc = int(cfg["keys_per_chip"])
        self.key_space = int(cfg["key_space"])
        self.sets = int(traffic["key_sets"])
        self.samples = int(traffic["check_samples"])
        cap = pair_capacity(self.kpc, self.n)
        if control:
            cap = self.kpc * 3 // (4 * self.n)
        self.step_fn = sort_program(mesh, self.kpc, self.key_space, cap,
                                    transport_of(traffic.get("transport",
                                                             "dense")))
        self.pick = tr.rng(seed, "check_sample")
        self.kept = []          # (step, set, out, got) of the sampled steps
        self.gots = []          # (set, got) of every step
        self.attempted = self.failed = self.steps = 0

    def setup(self) -> None:
        import jax
        with self.span("make_keys"):
            self.keys = key_program(self.mesh, self.kpc, self.key_space,
                                    self.sets)(
                np.uint32(self.seed & 0xFFFFFFFF),
                np.uint32((self.seed >> 32) & 0xFFFFFFFF))
            jax.block_until_ready(self.keys)
        self.window_programs = [(self.step_fn, (shapes_of(self.keys[0]),))]
        with self.span("warm_up"):
            np.asarray(self.step_fn(self.keys[0])[1])

    def step(self) -> int:
        s = self.steps % self.sets
        with self.span("dispatch"):
            out, got, dropped = self.step_fn(self.keys[s])
        with self.span("wait"):
            # the push reports the global count on every chip
            dropped = int(np.asarray(dropped)[0])
        total = self.kpc * self.n
        self.attempted += total
        self.failed += dropped
        self.gots.append((s, got))
        # reservoir sample of the steps to compare, drawn from the seed
        if len(self.kept) < self.samples:
            self.kept.append((self.steps, s, out, got))
        else:
            j = int(self.pick.integers(0, self.steps + 1))
            if j < self.samples:
                self.kept[j] = (self.steps, s, out, got)
        self.steps += 1
        return total - dropped

    def check(self) -> dict:
        import jax
        shift = bucket_shift(self.key_space, self.n)
        nums = {"dropped": self.failed, "count_wrong": 0, "misplaced": 0,
                "wrong_key": 0}
        keys = {}
        for s in sorted({s for s, _ in self.gots}):
            keys[s] = np.asarray(jax.device_get(self.keys[s]))
        self.keys = None
        want = {s: bucket_counts(k, self.n, shift) for s, k in keys.items()}
        for s, got in self.gots:
            nums["count_wrong"] += int(np.abs(
                np.asarray(jax.device_get(got)) - want[s]).sum())
        for _, s, out, got in self.kept:
            out, got = jax.device_get((out, got))
            got_n = compare_sorted(keys[s], np.asarray(out),
                                   np.asarray(got), self.n, shift)
            for k in got_n:
                nums[k] += got_n[k]
        self.kept, self.gots = [], []
        self.detail = nums
        return {"wrong_keys": (sum(nums.values()), LIMITS["wrong_keys"])}

    def counters(self) -> dict:
        return {"steps": self.steps}


def build(mesh, cfg: dict, traffic: dict, seed: int, span, control=False):
    return SortCell(mesh, cfg, traffic, seed, span, control)
