"""Reduce a profiler trace to the device numbers the benchmark reports.

A traced run writes one ``.xplane.pb``.  ``reduce_xplane`` reads it with
``jax.profiler.ProfileData`` and keeps two things:

  device ops   per chip, every event on the ``XLA Ops`` line of a
               ``/device:TPU:<n>`` plane: (label, start_ns, end_ns), the
               label from ``hlo_names.labels`` of the compiled programs
               (the kernel's function name for a Pallas kernel), found
               by the program that the ``XLA Modules`` line shows running
  host spans   every event on the host threads whose name starts with
               the harness's span prefix (``bench.``): make batch,
               dispatch, wait, check ok, re-send, ...

``TraceSummary`` turns those into busy time (the union of a chip's op
intervals inside the traced window), idle share, summed device time of
the ops whose label matches a pattern, and the idle gaps labelled by the
innermost host span open at the gap's midpoint.  An op that holds others
(a ``while`` and its body) counts in busy time but not in op time, where
its inner ops count.  Every per-chip number is averaged over the chips
used.
"""

from __future__ import annotations

import bisect
import glob
import os
import re
from collections import defaultdict
from dataclasses import dataclass, field

from bench import hlo_names

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
WINDOW_SPAN = "bench.window"


def merge(intervals):
    """Union of (start, end) intervals as a sorted, disjoint list."""
    out = []
    for s, e in sorted(intervals):
        if out and s <= out[-1][1]:
            if e > out[-1][1]:
                out[-1][1] = e
        else:
            out.append([s, e])
    return [tuple(iv) for iv in out]


def clip(intervals, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in intervals
            if e > lo and s < hi]


def leaves(events):
    """The events that hold no other event of the list."""
    evs = sorted(events, key=lambda ev: (ev[1], -ev[2]))
    holds = [False] * len(evs)
    stack = []
    for i, (_, s, e) in enumerate(evs):
        while stack and evs[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= evs[stack[-1]][2]:
            holds[stack[-1]] = True
        stack.append(i)
    return [ev for ev, h in zip(evs, holds) if not h]


@dataclass
class TraceSummary:
    """Device ops per chip and host spans, on one clock (nanoseconds)."""

    ops: dict                   # chip -> [(name, start_ns, end_ns)]
    spans: list                 # [(name, start_ns, end_ns)]
    window: tuple               # (start_ns, end_ns) of the traced window
    _busy: dict = field(default_factory=dict, repr=False)
    _leaves: dict = field(default_factory=dict, repr=False)

    @property
    def chips(self) -> int:
        return len(self.ops)

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_intervals(self, chip):
        if chip not in self._busy:
            self._busy[chip] = merge(clip(
                [(s, e) for _, s, e in self.ops[chip]], *self.window))
        return self._busy[chip]

    def leaf_ops(self, chip):
        if chip not in self._leaves:
            self._leaves[chip] = leaves(self.ops[chip])
        return self._leaves[chip]

    def busy_s(self) -> float:
        """Seconds in which some op ran, averaged over chips."""
        if not self.ops:
            return 0.0
        return sum(sum(e - s for s, e in self.busy_intervals(c))
                   for c in self.ops) * 1e-9 / self.chips

    def idle_share(self) -> float | None:
        if not self.ops or self.window_s <= 0:
            return None
        return 1.0 - self.busy_s() / self.window_s

    def op_seconds(self, pattern: str) -> float:
        """Summed device time of ops whose name matches ``pattern``
        (``re.search``), inside the window, averaged over chips."""
        rx = re.compile(pattern)
        lo, hi = self.window
        tot = 0
        for c in self.ops:
            for name, s, e in self.leaf_ops(c):
                if rx.search(name):
                    tot += max(0, min(e, hi) - max(s, lo))
        return tot * 1e-9 / max(self.chips, 1)

    def count(self, pattern: str) -> int:
        """Events matching ``pattern`` that start in the window, all chips."""
        rx = re.compile(pattern)
        lo, hi = self.window
        return sum(1 for c in self.ops for name, s, _ in self.leaf_ops(c)
                   if rx.search(name) and lo <= s < hi)

    def top_ops(self, k: int = 10):
        """[[name, seconds]] of the k ops that took most device time."""
        lo, hi = self.window
        tot = defaultdict(int)
        for c in self.ops:
            for name, s, e in self.leaf_ops(c):
                tot[name] += max(0, min(e, hi) - max(s, lo))
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9 / self.chips] for n, t in top if t > 0]

    def idle_gaps(self, k: int = 10):
        """[[host span, seconds]]: idle device time summed by the
        innermost host span open at each gap's midpoint, averaged over
        chips; the k largest."""
        spans = sorted(self.spans, key=lambda sp: sp[1])
        starts = [sp[1] for sp in spans]
        tot = defaultdict(int)
        lo, hi = self.window
        for c in self.ops:
            edges = [lo] + [t for iv in self.busy_intervals(c)
                            for t in iv] + [hi]
            for s, e in zip(edges[0::2], edges[1::2]):
                if e <= s:
                    continue
                # spans of one thread nest, so the latest-started span
                # that still covers the midpoint is the innermost
                mid = (s + e) // 2
                label = "no span"
                for i in range(bisect.bisect_right(starts, mid) - 1, -1, -1):
                    if spans[i][2] > mid:
                        label = spans[i][0]
                        break
                tot[label] += e - s
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[n, t * 1e-9 / max(self.chips, 1)] for n, t in top]


def reduce_events(ops, spans, window_span: str = WINDOW_SPAN) -> TraceSummary:
    """Summary of already-extracted events; the window is the first
    ``window_span`` host span, else the extent of the device ops."""
    win = [(s, e) for name, s, e in spans if name == window_span]
    if win:
        window = win[0]
    else:
        pts = [t for evs in ops.values() for _, s, e in evs for t in (s, e)]
        window = (min(pts), max(pts)) if pts else (0, 0)
    return TraceSummary(ops, spans, window)


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if len(paths) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, "
                           f"found {len(paths)}")
    return paths[0]


def _label_ops(op_events, module_events, labels: dict):
    """(label, start, end) of each op, named through the program that
    runs it: the module event that holds the op's start."""
    mods = sorted(module_events, key=lambda ev: ev[1])
    starts = [m[1] for m in mods]
    out = []
    for name, s, e in op_events:
        i = bisect.bisect_right(starts, s) - 1
        module = hlo_names.module_of(mods[i][0]) if i >= 0 and \
            s < mods[i][2] else None
        label = labels.get((module, hlo_names.instruction_of(name)))
        out.append((label or hlo_names.fallback(name), s, e))
    return out


def reduce_xplane(path: str, chips: int, labels: dict | None = None,
                  window_span: str = WINDOW_SPAN) -> TraceSummary:
    """Read a ``.xplane.pb``: device ops of the first ``chips`` TPU
    planes, labelled by ``labels`` ({(module, instruction): label}),
    and every host span named with :data:`SPAN_PREFIX`."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    ops, spans = {}, []
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            chip = int(m.group(1))
            if chip >= chips:
                continue
            evs, mods = [], []
            for line in plane.lines:
                got = [(ev.name, int(ev.start_ns), int(ev.end_ns))
                       for ev in line.events] \
                    if line.name in (OPS_LINE, MODULES_LINE) else []
                (evs if line.name == OPS_LINE else mods).extend(got)
            ops[chip] = _label_ops(evs, mods, labels or {})
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans.extend((ev.name, int(ev.start_ns), int(ev.end_ns))
                             for ev in line.events
                             if ev.name.startswith(SPAN_PREFIX))
    return reduce_events(ops, spans, window_span)
