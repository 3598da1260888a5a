"""The one traffic generator: every cell's load comes from a data file
``bench/traffic/<cell>.json`` read through this module.

A traffic file holds parameters only:

  loop      "closed" (the next batch is sent when the last one is
            acknowledged) or "open" (requests arrive on a seeded Poisson
            schedule at ``rate_per_chip`` and wait for a batch of at
            most ``max_batch_per_chip``)
  op        which operation of the configuration the cell drives
  keys      {"dist": "uniform" | "zipf", "s": exponent for zipf}
  ...       sizes the configuration's driver reads (batch, fill, ...)

Everything random is drawn from ``--seed`` in a fixed order, so a seed
gives the same batches, keys and arrival times in every run; only which
arrivals share a batch depends on the system's speed.
"""

from __future__ import annotations

import json
from pathlib import Path

import numpy as np

TRAFFIC_DIR = Path(__file__).resolve().parent / "traffic"


def load(cell: str) -> dict:
    with open(TRAFFIC_DIR / f"{cell}.json") as f:
        return json.load(f)


def rng(seed: int, stream: str) -> np.random.Generator:
    """Independent seeded stream per purpose (batches, values, arrivals)."""
    tag = int.from_bytes(stream.encode(), "little")
    return np.random.default_rng([seed % (1 << 64), seed >> 64, tag])


class KeyStream:
    """Key indices in [0, universe) under the traffic's distribution.

    ``uniform`` draws every index alike.  ``zipf`` ranks the indices by
    a seeded permutation and draws rank r with weight 1 / (r+1)^s, the
    bounded Zipf law of YCSB's request generator.
    """

    def __init__(self, keys: dict, universe: int, gen: np.random.Generator):
        self.universe = int(universe)
        self.gen = gen
        self.dist = keys.get("dist", "uniform")
        if self.dist == "zipf":
            s = float(keys["s"])
            w = np.arange(1, self.universe + 1, dtype=np.float64) ** -s
            self.cdf = np.cumsum(w / w.sum())
            self.perm = gen.permutation(self.universe)
        elif self.dist != "uniform":
            raise ValueError(f"unknown key distribution {self.dist!r}")

    def draw(self, n: int) -> np.ndarray:
        if self.dist == "uniform":
            return self.gen.integers(0, self.universe, n, dtype=np.int64)
        r = np.searchsorted(self.cdf, self.gen.random(n), side="right")
        return self.perm[np.minimum(r, self.universe - 1)].astype(np.int64)


class Arrivals:
    """A seeded Poisson arrival schedule, made as needed.

    The gaps between arrivals are exponential with mean 1 / rate, drawn
    ``chunk`` at a time, so the schedule depends on the seed alone.
    ``take(until, limit)`` removes and returns the due times (seconds
    from the window's start) of the earliest at most ``limit`` arrivals
    due by ``until``.
    """

    def __init__(self, rate: float, gen: np.random.Generator,
                 chunk: int = 1 << 16):
        self.rate, self.gen, self.chunk = float(rate), gen, int(chunk)
        self.last = 0.0
        self.pending = np.zeros(0, np.float64)

    def _make(self, until: float) -> None:
        chunks = [self.pending]
        while self.last <= until:
            t = self.last + np.cumsum(
                self.gen.exponential(1.0 / self.rate, self.chunk))
            chunks.append(t)
            self.last = float(t[-1])
        if len(chunks) > 1:
            self.pending = np.concatenate(chunks)

    def take(self, until: float, limit: int) -> np.ndarray:
        self._make(until)
        k = min(int(np.searchsorted(self.pending, until, side="right")),
                int(limit))
        out, self.pending = self.pending[:k], self.pending[k:]
        return out

    def waiting(self, until: float) -> int:
        """Arrivals due by ``until`` and not yet taken."""
        self._make(until)
        return int(np.searchsorted(self.pending, until, side="right"))

    def next_due(self, until: float) -> float | None:
        """Due time of the next arrival, if one is due by ``until``."""
        self._make(until)
        if self.pending.size and self.pending[0] <= until:
            return float(self.pending[0])
        return None
