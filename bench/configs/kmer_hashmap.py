"""Configuration ``kmer_hashmap``: the distributed k-mer table.

The table of a k-mer counting pass and of Meraculous contig generation
(sizes in ``kmer_hashmap.json``).  A key is a k-mer of k = 31 packed
into 64 bits and held as two u32 lanes; a value is two u32 lanes, the
occurrence count and the packed extension counts.  The table is
``containers/hashmap.py`` over a ``("bcl",)`` mesh, driven through
``SpmdBackend`` inside ``jax.shard_map``, the library's production path.

Two traffic ops drive it:

  insert  closed loop: batches of k-mer occurrences drawn from a seeded
          set of distinct k-mers, inserted with ``MODE_ADD`` under the
          insert promise; items whose ``ok`` is false are re-sent.
  find    open loop: a table filled at set-up through the container's
          own insert, then seeded lookups, half of present k-mers and
          half of absent ones, under the find promise.

This file holds the program builders, the seeded data and the plain
numpy reference.  ``mix64`` and the builders were copied from the
repository's ``chip_smoke.py``; the key lanes add the seed as a 64-bit
salt so that any seed gives distinct keys.
"""

from __future__ import annotations

import time

import numpy as np

from bench import roofline
from bench import traffic as tr
from bench.device import shapes_of, sharded, transport_of

U64 = np.uint64
M1, M2 = 0xBF58476D1CE4E5B9, 0x94D049BB133111EB
READY = 2
ABSENT_SPAN = 1 << 40      # absent k-mers: indices above the present ones


# --------------------------------------------------------------------------
# seeded data
# --------------------------------------------------------------------------

def mix64(x: np.ndarray) -> np.ndarray:
    """splitmix64's finalizer: a bijection on u64, so distinct in, distinct out."""
    with np.errstate(over="ignore"):
        x = (x ^ (x >> U64(30))) * U64(M1)
        x = (x ^ (x >> U64(27))) * U64(M2)
    return x ^ (x >> U64(31))


def _unshift(y: np.ndarray, s: int) -> np.ndarray:
    x = y.copy()
    for _ in range(64 // s):
        x = y ^ (x >> U64(s))
    return x


def unmix64(y: np.ndarray) -> np.ndarray:
    """Inverse of :func:`mix64`."""
    with np.errstate(over="ignore"):
        x = _unshift(y, 31) * U64(pow(M2, -1, 1 << 64))
        x = _unshift(x, 27) * U64(pow(M1, -1, 1 << 64))
    return _unshift(x, 30)


def salt(seed: int) -> np.uint64:
    return mix64(np.array([seed % (1 << 64)], U64) ^ U64(0x9E3779B97F4A7C15))[0]


def key_lanes(index: np.ndarray, s: np.uint64) -> np.ndarray:
    """(N, 2) u32 lanes (high, low) of the 64-bit keys of k-mer indices."""
    with np.errstate(over="ignore"):
        x = mix64(index.astype(U64) + s)
    return np.stack([(x >> U64(32)).astype(np.uint32), x.astype(np.uint32)],
                    axis=1)


def key_index(hi: np.ndarray, lo: np.ndarray, s: np.uint64) -> np.ndarray:
    """k-mer index of each (high, low) key: the inverse of key_lanes."""
    x = (hi.astype(U64) << U64(32)) | lo.astype(U64)
    with np.errstate(over="ignore"):
        return unmix64(x) - s


def extension_codes(gen: np.random.Generator, n: int) -> np.ndarray:
    """One occurrence's extension lane: a 1 in the 4-bit counter of its
    left base (bits 0-15) and of its right base (bits 16-31)."""
    left = gen.integers(0, 4, n, dtype=np.uint32)
    right = gen.integers(0, 4, n, dtype=np.uint32)
    return (np.uint32(1) << (4 * left)) | (np.uint32(1) << (16 + 4 * right))


# --------------------------------------------------------------------------
# program builders
# --------------------------------------------------------------------------

def pair_capacity(batch: int, n: int) -> int:
    """Exchange capacity per (source, destination) pair."""
    return batch if n == 1 else -(-batch * 5 // (4 * n))


def hashmap_programs(mesh, slots: int, block: int, lanes: tuple[int, int],
                     insert_batch: int, find_batch: int, mode: int,
                     transport=None):
    """(create, insert, find) jitted over the mesh, under the insert and
    the find promise.  ``insert`` donates the table and takes a validity
    mask, so failed items can be re-sent; ``find`` takes a validity
    mask, so a batch can be padded."""
    import jax
    from jax.sharding import PartitionSpec as P
    from repro.containers import hashmap as hm
    from repro.core import get_backend
    from repro.core.promises import ConProm

    n = mesh.devices.size
    box = {}

    def create():
        spec, st = hm.hashmap_create(get_backend("bcl"), slots * n, lanes[0],
                                     lanes[1], block_size=block)
        box["spec"] = spec
        return tuple(st)

    def insert(state, k, v, valid):
        st, ok = hm.insert(get_backend("bcl"), box["spec"],
                           hm.HashMapState(*state), k, v,
                           capacity=pair_capacity(insert_batch, n),
                           promise=ConProm.HashMap.insert, valid=valid,
                           mode=mode, transport=transport)
        return tuple(st), ok

    def find(state, k, valid):
        _, vals, found = hm.find(get_backend("bcl"), box["spec"],
                                 hm.HashMapState(*state), k,
                                 capacity=pair_capacity(find_batch, n),
                                 promise=ConProm.HashMap.find, valid=valid,
                                 transport=transport)
        return vals, found

    bcl = P("bcl")
    sm = lambda f, i, o: jax.shard_map(f, mesh=mesh, in_specs=i, out_specs=o)
    return (jax.jit(sm(create, (), bcl)),
            jax.jit(sm(insert, (bcl,) * 4, (bcl, bcl)), donate_argnums=0),
            jax.jit(sm(find, (bcl,) * 3, (bcl, bcl))))


# --------------------------------------------------------------------------
# plain reference
# --------------------------------------------------------------------------

def occupied_entries(tkeys, tvals, status):
    """(hi, lo, value lanes) of every READY slot of a host copy of the
    table: tkeys/tvals (nb, L, B), status (nb, B)."""
    occ = (status & 3) == READY
    blk, slot = np.nonzero(occ)
    return (tkeys[blk, 0, slot], tkeys[blk, 1, slot],
            tvals[blk, :, slot])


def count_reference(indices, ext, distinct: int):
    """Per k-mer index: occurrences and the u32 sum of extension codes."""
    if indices:
        idx = np.concatenate(indices)
        cnt = np.bincount(idx, minlength=distinct)
        ext_sum = np.bincount(idx, weights=np.concatenate(ext),
                              minlength=distinct)
    else:
        cnt = np.zeros(distinct, np.int64)
        ext_sum = np.zeros(distinct)
    return ((cnt % (1 << 32)).astype(np.uint32),
            (ext_sum.astype(np.int64) % (1 << 32)).astype(np.uint32))


def compare_counts(table, s, distinct: int, want_cnt, want_ext) -> dict:
    """Numbers that decide a counting run: k-mers sent but missing from
    the table, entries that are no sent k-mer or a second copy of one,
    and entries whose count or extension lanes differ."""
    hi, lo, vals = occupied_entries(*table)
    idx = key_index(hi, lo, s)
    known = idx < U64(distinct)
    kidx = idx[known].astype(np.int64)
    copies = np.bincount(kidx, minlength=distinct)
    sent = want_cnt > 0
    extra = int((~known).sum()) + int((copies > 1).sum()) \
        + int((copies[~sent] > 0).sum())
    missing = int((sent & (copies == 0)).sum())
    wrong = int(((vals[known, 0] != want_cnt[kidx])
                 | (vals[known, 1] != want_ext[kidx])).sum())
    return {"missing": missing, "extra": extra, "wrong_value": wrong}


def compare_finds(present, idx, found, vals, fill_vals) -> dict:
    """Numbers that decide a lookup run: found flags that differ from
    the reference, and found values that differ from what was inserted."""
    want_vals = np.where(present[:, None], fill_vals[np.where(present, idx, 0)],
                         0).astype(np.uint32)
    return {"found_wrong": int((found != present).sum()),
            "value_wrong": int((present & found
                                & (vals != want_vals).any(axis=1)).sum())}


# --------------------------------------------------------------------------
# cells
# --------------------------------------------------------------------------

#: the number compared counts wrong answers: exact, limit 0
LIMITS = {"wrong_kmers": 0, "wrong_answers": 0}


class _Table:
    """The table of one cell, its programs, and the re-send loop."""

    def __init__(self, mesh, cfg: dict, traffic: dict, seed: int, span,
                 insert_batch: int, find_batch: int, mode: int):
        self.mesh, self.cfg, self.span = mesh, cfg, span
        self.seed = seed
        self.n = mesh.devices.size
        self.salt = salt(seed)
        self.slots = int(cfg["slots_per_chip"])
        self.block = int(cfg["block_size"])
        self.lanes = (int(cfg["key_lanes"]), int(cfg["value_lanes"]))
        self.max_sends = int(traffic["max_sends"])
        self.create, self.insert, self.find = hashmap_programs(
            mesh, self.slots, self.block, self.lanes, insert_batch,
            find_batch, mode, transport_of(traffic.get("transport", "dense")))
        self.state = None
        self.calls = 0

    def check_load(self, distinct: int) -> None:
        cap = float(self.cfg["max_load"]) * self.slots * self.n
        if distinct > cap:
            raise ValueError(f"{distinct} distinct keys exceed the table's "
                             f"load limit of {cap:.0f} slots")

    def send(self, k, v, pending: np.ndarray, after_dispatch=None):
        """Insert one batch, re-sending items whose ok is false, at most
        ``max_sends`` calls; returns the host mask of items never acked.
        ``after_dispatch`` runs on the host while the first call runs."""
        valid = sharded(self.mesh, pending)
        pending = pending.copy()
        for i in range(self.max_sends):
            with self.span("dispatch"):
                self.state, ok = self.insert(self.state, k, v, valid)
                self.calls += 1
            if i == 0 and after_dispatch is not None:
                after_dispatch()
            with self.span("wait"):
                ok = np.asarray(ok)
            with self.span("check_ok"):
                pending &= ~ok
                if not pending.any():
                    break
            with self.span("resend"):
                valid = sharded(self.mesh, pending)
        return pending

    def host_table(self):
        import jax
        tk, tv, st = jax.device_get(self.state)
        self.state = None
        return np.asarray(tk), np.asarray(tv), np.asarray(st)


class CountCell(_Table):
    """k-mer counting: closed-loop MODE_ADD inserts of seeded occurrences.

    The control (``control=True``) inserts with MODE_SET: the last
    occurrence overwrites the others, the lost update that the counting
    guarantee forbids.
    """

    loop = "closed"

    def __init__(self, mesh, cfg, traffic, seed, span, control=False):
        self.batch = int(traffic["batch_per_chip"])
        from repro.kernels import ops as kops
        super().__init__(mesh, cfg, traffic, seed, span, self.batch,
                         self.batch,
                         kops.MODE_SET if control else kops.MODE_ADD)
        self.distinct = int(traffic["distinct"])
        self.check_load(self.distinct)
        self.total = self.batch * self.n
        self.keys = tr.KeyStream(traffic["keys"], self.distinct,
                                 tr.rng(seed, "occurrences"))
        self.ext_gen = tr.rng(seed, "extensions")
        self.sent_idx, self.sent_ext = [], []
        self.attempted = self.failed = self.batches = 0

    def _make(self):
        with self.span("make_batch"):
            idx = self.keys.draw(self.total)
            ext = extension_codes(self.ext_gen, self.total)
            vals = np.stack([np.ones(self.total, np.uint32), ext], axis=1)
            self._next = (idx, ext, sharded(self.mesh, key_lanes(idx, self.salt)),
                          sharded(self.mesh, vals))

    def setup(self) -> None:
        self.state = self.create()
        # warm-up: the insert program with every item invalid leaves the
        # table empty
        zero = sharded(self.mesh, np.zeros((self.total, 2), np.uint32))
        args = (self.state, zero, zero,
                sharded(self.mesh, np.zeros(self.total, bool)))
        self.window_programs = [(self.insert, shapes_of(args))]
        self.state, ok = self.insert(*args)
        np.asarray(ok)
        self.calls = 0
        self._make()

    def step(self) -> int:
        idx, ext, k, v = self._next
        never = self.send(k, v, np.ones(self.total, bool),
                          after_dispatch=self._make)
        acked = ~never
        self.sent_idx.append(idx[acked].astype(np.int64))
        self.sent_ext.append(ext[acked])
        self.attempted += self.total
        self.failed += int(never.sum())
        self.batches += 1
        return int(acked.sum())

    def check(self) -> dict:
        table = self.host_table()
        want_cnt, want_ext = count_reference(self.sent_idx, self.sent_ext,
                                             self.distinct)
        self.detail = compare_counts(table, self.salt, self.distinct,
                                     want_cnt, want_ext)
        self.detail["unacked"] = self.failed
        return {"wrong_kmers": (sum(self.detail.values()),
                                LIMITS["wrong_kmers"])}

    def counters(self) -> dict:
        nb = self.slots // self.block
        least = sum(roofline.probe_least_bytes(
            "insert", np.unique(idx).size / self.n, idx.size / self.n, nb,
            self.block, *self.lanes) for idx in self.sent_idx)
        return {"batches": self.batches, "insert_calls": self.calls,
                "probe_least_bytes": least}


class LookupCell(_Table):
    """Read-only serving: seeded lookups against a table filled at set-up.

    The fill inserts ``fill.distinct`` k-mers with seeded values through
    the container's own insert.  Requests form one seeded stream, half
    present k-mers and half absent ones, served in order: while the
    device runs one batch, the host puts the keys of the next requests
    on the device, so that a step's dispatch sends only the mask of the
    requests that are due.  The control (``control=True``) leaves out
    the fill's last send before serving, a stale read that the
    read-your-writes guarantee forbids.
    """

    loop = "open"

    def __init__(self, mesh, cfg, traffic, seed, span, control=False):
        fill = traffic["fill"]
        self.distinct = int(fill["distinct"])
        self.fill_batch = int(fill["batch_per_chip"])
        self.max_batch = int(traffic["max_batch_per_chip"])
        from repro.kernels import ops as kops
        super().__init__(mesh, cfg, traffic, seed, span, self.fill_batch,
                         self.max_batch, kops.MODE_SET)
        self.check_load(self.distinct)
        self.control = control
        self.present_share = float(traffic["present_share"])
        self.req = tr.rng(seed, "requests")
        self.total = self.max_batch * self.n
        # request j of a batch sits at row pos[j]: spread over the chips
        i = np.arange(self.total)
        self.pos = (i % self.n) * self.max_batch + i // self.n
        self.queue = (np.zeros(0, bool), np.zeros(0, np.int64),
                      np.zeros((0, 2), np.uint32))
        self.results = []
        self.fill_failed = 0
        self.attempted = self.failed = self.steps = 0

    def _requests(self):
        """The next ``total`` requests of the stream: (present, k-mer
        index, key lanes), made a batch's worth at a time."""
        present, idx, lanes = self.queue
        if present.size < self.total:
            p = self.req.random(self.total) < self.present_share
            hit = self.req.integers(0, self.distinct, self.total)
            miss = self.distinct + self.req.integers(0, ABSENT_SPAN,
                                                     self.total)
            new = np.where(p, hit, miss)
            present = np.concatenate([present, p])
            idx = np.concatenate([idx, new])
            lanes = np.concatenate([lanes, key_lanes(new, self.salt)])
            self.queue = present, idx, lanes
        return present[:self.total], idx[:self.total], lanes[:self.total]

    def _stage(self) -> None:
        """Put the keys of the next batch's requests on the device."""
        keys = np.empty((self.total, 2), np.uint32)
        keys[self.pos] = self._requests()[2]
        self.keys = sharded(self.mesh, keys)

    def setup(self) -> None:
        gen = tr.rng(self.seed, "fill")
        self.fill_vals = gen.integers(0, 1 << 32, (self.distinct, 2),
                                      dtype=np.uint32)
        self.state = self.create()
        per = self.fill_batch * self.n
        sends = -(-self.distinct // per)
        for i in range(sends - 1 if self.control else sends):
            lo, hi = i * per, min((i + 1) * per, self.distinct)
            idx = np.arange(lo, lo + per)
            pending = idx < hi
            idx = np.minimum(idx, hi - 1)
            with self.span("fill"):
                never = self.send(
                    sharded(self.mesh, key_lanes(idx, self.salt)),
                    sharded(self.mesh, self.fill_vals[idx]), pending)
            self.fill_failed += int(never.sum())
        self.calls = 0
        self._stage()
        # warm-up: the find program on an all-invalid batch
        args = (self.state, self.keys,
                sharded(self.mesh, np.zeros(self.total, bool)))
        self.window_programs = [(self.find, shapes_of(args))]
        np.asarray(self.find(*args)[1])

    def serve(self, n: int) -> float:
        """Look up the next ``n`` requests of the stream in one padded
        batch; returns the host clock when the answers are ready."""
        with self.span("dispatch"):
            valid = np.zeros(self.total, bool)
            valid[self.pos[:n]] = True
            vals, found = self.find(self.state, self.keys,
                                    sharded(self.mesh, valid))
        present, idx, _ = self._requests()
        self.results.append((present[:n], idx[:n], vals, found))
        self.queue = tuple(q[n:] for q in self.queue)
        with self.span("make_batch"):
            self._stage()
        with self.span("wait"):
            found.block_until_ready()
            done = time.perf_counter()
        self.attempted += n
        self.steps += 1
        return done

    def check(self) -> dict:
        import jax
        self.state = self.keys = None
        nums = {"found_wrong": 0, "value_wrong": 0}
        for present, idx, vals, found in self.results:
            vals, found = jax.device_get((vals, found))
            rows = self.pos[:present.size]
            got = compare_finds(present, idx, np.asarray(found)[rows],
                                np.asarray(vals)[rows], self.fill_vals)
            for k in nums:
                nums[k] += got[k]
        self.results = []
        nums["fill_unacked"] = self.fill_failed
        self.detail = nums
        return {"wrong_answers": (sum(nums.values()),
                                  LIMITS["wrong_answers"])}

    def counters(self) -> dict:
        return {"steps": self.steps, "lookups": self.attempted}


def build(mesh, cfg: dict, traffic: dict, seed: int, span, control=False):
    cells = {"insert": CountCell, "find": LookupCell}
    return cells[traffic["op"]](mesh, cfg, traffic, seed, span, control)
