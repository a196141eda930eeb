"""Reduce a profiler trace of the window to per-layer numbers.

The JAX profiler writes an ``.xplane.pb``; ``load_xplane`` flattens it to
events ``(plane, line, name, start_ns, dur_ns)`` and ``reduce`` works on
those alone, so a small recorded trace (``tests/data/``) checks the
arithmetic on the CPU.

  * Device events are those of the planes ``/device:TPU:<n>``.  Busy time
    is the union of the intervals of their op line (``XLA Ops``), clipped
    to the window and averaged over the devices that ran anything.
  * The window runs from the start of the first host span ``bench.drain``
    to the end of the last one: the harness wraps every drain in such a
    span, and the drivers name their own spans ``bench.<step>``.
  * A kernel's time is the summed device time of the module events
    (``XLA Modules``) whose name contains the pattern its driver gives.
  * The claim gap is the device-idle time from the end of each claim
    kernel to the start of the next compute kernel.
  * Every idle stretch of the window is cut where a host span starts or
    ends, and each piece is labelled with the innermost span running in
    it (the driver's own spans), or ``between-drains`` where no drain was
    running.
"""
from __future__ import annotations

import dataclasses
import re
from collections import defaultdict
from typing import NamedTuple, Optional

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINE = "XLA Ops"
MODULE_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
DRAIN_SPAN = SPAN_PREFIX + "drain"
OUTSIDE = "between-drains"


class Event(NamedTuple):
    plane: str
    line: str
    name: str
    start_ns: float
    dur_ns: float

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load_xplane(path: str) -> list:
    """Device op and module events, and the benchmark's host spans."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        device = bool(DEVICE_PLANE.match(plane.name))
        for line in plane.lines:
            if device and line.name not in (OP_LINE, MODULE_LINE):
                continue
            for e in line.events:
                if device or e.name.startswith(SPAN_PREFIX):
                    out.append(Event(plane.name, line.name, _short(e.name),
                                     float(e.start_ns), float(e.duration_ns)))
    return out


def _short(name: str) -> str:
    """An op's HLO instruction name without its text: ``%copy.3 = s32[..]
    copy(..)`` becomes ``%copy.3``; other names pass unchanged."""
    return name.split(" = ", 1)[0]


def _union(intervals):
    """Sorted disjoint union of (start, end) intervals."""
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def _covered(merged, lo, hi) -> float:
    """Length of [lo, hi] that the disjoint intervals ``merged`` cover."""
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in merged)


@dataclasses.dataclass
class Reduction:
    n_drains: int
    window_ns: float
    busy_ns: float                 # averaged over the devices used
    kernel_ns: dict                # role -> device ns in the window
    claim_gap_ns: Optional[float]  # summed over the window's drains
    top_ops: list                  # [(op name, ns)], longest first
    idle_gaps: list                # [(host span, idle ns)], longest first

    def per_drain(self, ns: float) -> float:
        return ns / self.n_drains


def reduce(events, kernels: dict, compute_role: Optional[str] = None,
           top: int = 10) -> Reduction:
    """Per-layer numbers of a window of drains.

    ``kernels`` maps a role (``claim``, ``mandel``, ...) to a substring of
    its module name; ``compute_role`` is the role whose kernel follows the
    claim kernel in a drain.
    """
    drains = sorted((e for e in events if e.line != OP_LINE
                     and e.line != MODULE_LINE and e.name == DRAIN_SPAN),
                    key=lambda e: e.start_ns)
    if not drains:
        raise ValueError("no drain span in the trace")
    lo, hi = drains[0].start_ns, max(e.end_ns for e in drains)
    spans = [e for e in events if not DEVICE_PLANE.match(e.plane)]

    def clip(e):
        return max(e.start_ns, lo), min(e.end_ns, hi)

    ops = [e for e in events if DEVICE_PLANE.match(e.plane)
           and e.line == OP_LINE and e.end_ns > lo and e.start_ns < hi]
    modules = sorted((e for e in events if DEVICE_PLANE.match(e.plane)
                      and e.line == MODULE_LINE and e.end_ns > lo
                      and e.start_ns < hi), key=lambda e: e.start_ns)
    per_device = defaultdict(list)
    for e in ops:
        per_device[e.plane].append(clip(e))
    merged = {p: _union(iv) for p, iv in per_device.items()}
    devices = max(len(merged), 1)
    busy = sum(_covered(m, lo, hi) for m in merged.values()) / devices

    kernel_ns = {}
    for role, pattern in kernels.items():
        hits = [e for e in modules if pattern in e.name]
        if hits:
            kernel_ns[role] = sum(b - a for a, b in map(clip, hits))

    gap = None
    if compute_role is not None and "claim" in kernel_ns \
            and compute_role in kernel_ns:
        gap = 0.0
        cpat, kpat = kernels["claim"], kernels[compute_role]
        for i, e in enumerate(modules):
            if cpat not in e.name:
                continue
            nxt = next((f for f in modules[i + 1:] if kpat in f.name
                        and f.plane == e.plane), None)
            if nxt is None:
                continue
            a, b = e.end_ns, min(nxt.start_ns, hi)
            if b > a:
                gap += (b - a) - _covered(merged.get(e.plane, []), a, b)

    by_op = defaultdict(float)
    for e in ops:
        a, b = clip(e)
        by_op[e.name] += (b - a) / devices
    top_ops = sorted(by_op.items(), key=lambda kv: -kv[1])[:top]

    idle = defaultdict(float)
    cuts = sorted({t for e in spans for t in (e.start_ns, e.end_ns)})
    for m in merged.values() or [[]]:
        edges = [lo] + [x for s, e in m for x in (s, e)] + [hi]
        for a, b in zip(edges[::2], edges[1::2]):
            # split the idle stretch where a host span starts or ends
            inner = [t for t in cuts if a < t < b]
            for x, y in zip([a] + inner, inner + [b]):
                if y > x:
                    idle[_label(spans, (x + y) / 2)] += (y - x) / devices
    idle_gaps = sorted(idle.items(), key=lambda kv: -kv[1])[:top]
    return Reduction(len(drains), hi - lo, busy, kernel_ns, gap, top_ops,
                     idle_gaps)


def _label(spans, t) -> str:
    """The innermost (shortest) host span running at time ``t``."""
    live = [e for e in spans if e.start_ns <= t <= e.end_ns]
    if not live:
        return OUTSIDE
    return min(live, key=lambda e: e.dur_ns).name[len(SPAN_PREFIX):]
