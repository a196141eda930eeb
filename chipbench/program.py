#!/usr/bin/env python3
"""The program's own spans and counters in a traced window of drains.

The program records a host span ``repro.<phase>`` around each phase of a
self-scheduled loop, with counters as its stats (``src/repro/tracing.py``),
on the profiler's clock.  ``load`` reads them from the window's
``.xplane.pb``; ``reduce`` sets them against the harness's drains
(``bench.drain``) and the device's op events (``trace.load_xplane``):

  * host time of each span name, clipped to the drains;
  * each counter, summed per drain;
  * the device-idle time inside drains, cut where a program span starts
    or ends and put down to the innermost program span running, or to
    none (``untraced_idle_ns``): what the program's spans leave
    unexplained.

The readers ``metrics/<name>.py`` of ``METRICS`` read that reduction as
``ctx.program`` and return None where it is absent.  ``run.py`` hands its
readers no program spans yet, so as a script this makes one traced run
of a cell, as ``run.py --trace 1`` does, and prints its result line with
those metrics and the per-span split added:

    python3 chipbench/program.py --workload <name> --seed <n> \\
        [--record <file.json>]

``--record`` writes the events of the window's first three drains, for
the recorded-trace tests (``tests/data/``).
"""
from __future__ import annotations

import dataclasses
import json
import sys
from collections import defaultdict
from pathlib import Path
from typing import NamedTuple

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import trace  # noqa: E402

PREFIX = "repro."
UNTRACED = "untraced"
#: per-layer metrics that read ``ctx.program``
METRICS = ("claim_host_ms", "claim_readback_ms", "launch_ms",
           "claim_tables_ms", "report_plane_ms", "tile_costs_ms",
           "claim_step_use_pct", "idle_untraced_ms")


class Span(NamedTuple):
    plane: str
    line: str       # the host thread
    name: str
    start_ns: float
    dur_ns: float
    stats: dict     # the span's counters

    @property
    def end_ns(self) -> float:
        return self.start_ns + self.dur_ns


def load(path: str) -> list:
    """The program's host spans (``repro.*``) with their counters."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        if trace.DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith(PREFIX):
                    out.append(Span(plane.name, line.name, e.name,
                                    float(e.start_ns), float(e.duration_ns),
                                    dict(e.stats)))
    return out


@dataclasses.dataclass
class ProgramReduction:
    n_drains: int
    host_ns: dict          # span name -> host ns inside the drains
    counters: dict         # span name -> {counter: [sum per drain]}
    spans_per_drain: list  # program spans that started in each drain
    idle_ns: dict          # innermost span name -> device-idle ns in drains
    untraced_idle_ns: float  # device-idle ns in drains under no span

    def per_drain(self, ns: float) -> float:
        return ns / self.n_drains

    def host_ms(self, *names):
        """Host ms per drain of the spans ``names`` together; None when
        none of them ran."""
        ns = [self.host_ns[n] for n in names if n in self.host_ns]
        return self.per_drain(sum(ns)) / 1e6 if ns else None


def reduce(spans, events) -> ProgramReduction:
    """The program's spans of ``spans`` against the drains and device ops
    of ``events`` (``trace.load_xplane``).  A drain's spans are those that
    start inside its ``bench.drain`` on the same host thread."""
    drains = sorted((e for e in events if e.name == trace.DRAIN_SPAN
                     and not trace.DEVICE_PLANE.match(e.plane)),
                    key=lambda e: e.start_ns)
    if not drains:
        raise ValueError("no drain span in the trace")
    mine = [[s for s in spans if (s.plane, s.line) == (d.plane, d.line)
             and d.start_ns <= s.start_ns < d.end_ns] for d in drains]

    per_device = defaultdict(list)
    for e in events:
        if trace.DEVICE_PLANE.match(e.plane) and e.line == trace.OP_LINE:
            per_device[e.plane].append((e.start_ns, e.end_ns))
    merged = [trace._union(iv) for iv in per_device.values()]
    devices = max(len(merged), 1)

    host = defaultdict(float)
    counters = defaultdict(lambda: defaultdict(lambda: [0] * len(drains)))
    idle = defaultdict(float)
    for i, (d, ss) in enumerate(zip(drains, mine)):
        for s in ss:
            host[s.name] += min(s.end_ns, d.end_ns) - s.start_ns
            for key, value in s.stats.items():
                counters[s.name][key][i] += value
        cuts = sorted({t for s in ss for t in (s.start_ns, s.end_ns)
                       if d.start_ns < t < d.end_ns})
        for m in merged or [[]]:
            edges = [d.start_ns] + [x for a, b in m for x in (a, b)
                                    if d.start_ns < b and a < d.end_ns]
            edges = [min(max(t, d.start_ns), d.end_ns) for t in edges]
            edges.append(d.end_ns)
            for a, b in zip(edges[::2], edges[1::2]):
                inner = [t for t in cuts if a < t < b]
                for x, y in zip([a] + inner, inner + [b]):
                    if y > x:
                        label = _innermost(ss, (x + y) / 2)
                        idle[label] += (y - x) / devices
    untraced = idle.pop(UNTRACED, 0.0)
    return ProgramReduction(
        len(drains), dict(host),
        {n: dict(c) for n, c in counters.items()},
        [len(ss) for ss in mine], dict(idle), untraced)


def _innermost(spans, t) -> str:
    """The shortest span running at time ``t``, or ``UNTRACED``."""
    live = [s for s in spans if s.start_ns <= t <= s.end_ns]
    return min(live, key=lambda s: s.dur_ns).name if live else UNTRACED


def traced_run(name: str, seed: int, *, cfg=None, traffic=None,
               device=None, interpret: bool = False, record=None) -> dict:
    """One traced run of the cell ``name`` (``run.run_cell``), its result
    line with ``program`` added: the ``METRICS`` and the per-span split.

    ``run_cell`` reads and deletes its trace itself; for this one run
    ``trace.load_xplane`` is wrapped so that the program's spans are read
    from the same file.
    """
    from chipbench import cells, run
    from chipbench.reference import chunk_plan

    bench = cells.load_benchmark()
    workload = cells.workload(name, bench)
    c, t = cells.load_cell(name, bench)
    cfg, traffic = cfg or c, traffic or t
    got = {}
    real = trace.load_xplane

    def load_both(path):
        got["events"], got["spans"] = real(path), load(path)
        return got["events"]

    trace.load_xplane = load_both
    try:
        result = run.run_cell(workload, cfg, traffic, bench, seed=seed,
                              seconds=run.TRACE_SECONDS, trace=True,
                              device=device or run.chip_device(
                                  workload["chips"]),
                              interpret=interpret)
    finally:
        trace.load_xplane = real

    events, spans = got["events"], got["spans"]
    mod = cells.driver(cfg["entry"])
    ctx = run.Ctx(trace.reduce(events, mod.KERNELS, mod.COMPUTE), None,
                  None)
    ctx.program = prog = reduce(spans, events)
    drv = mod.Driver(cfg, traffic, seed)
    chunks = len(chunk_plan(drv.technique, drv.N, drv.P)[0])
    ms = 1e-6 / prog.n_drains
    result["program"] = {
        "metrics": {m: v for m in METRICS
                    if (v := cells.metric_reader(m)(ctx)) is not None},
        "host_ms": {n: ns * ms for n, ns in sorted(prog.host_ns.items())},
        "idle_ms": {**{n: ns * ms for n, ns in sorted(
            prog.idle_ns.items(), key=lambda kv: -kv[1])},
                    UNTRACED: prog.untraced_idle_ns * ms},
        "idle_in_drains_ms": (sum(prog.idle_ns.values())
                              + prog.untraced_idle_ns) * ms,
        "spans_per_drain": sorted(set(prog.spans_per_drain)),
        "claims_per_drain_are_the_closed_form": all(
            n == chunks for n in prog.counters.get(
                "repro.claim", {}).get("claims", [None])),
        "compiled": sum(sum(c.get("compiled", [])) for c in
                        prog.counters.values()),
        "traced_drain_ms": ctx.red.window_ns * ms,
    }
    if record:
        _record(record, events, spans)
    return result


def _record(path, events, spans, drains: int = 3) -> None:
    """Write the events of the first ``drains`` drains as JSON."""
    ds = sorted((e for e in events if e.name == trace.DRAIN_SPAN),
                key=lambda e: e.start_ns)[:drains]
    lo, hi = ds[0].start_ns, ds[-1].end_ns

    def inside(e):
        return e.end_ns > lo and e.start_ns < hi

    with open(path, "w") as f:
        json.dump({"events": [list(e) for e in events if inside(e)],
                   "spans": [list(s) for s in spans if inside(s)]}, f)


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--record", help="write the first three drains here")
    args = ap.parse_args(argv)

    sys.path.insert(0, str(ROOT / "src"))
    import jax

    from chipbench import run
    from repro import kernels
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    try:
        result = traced_run(args.workload, args.seed, record=args.record)
    except run.NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    if kernels.interpreted_calls:
        print(f"chipbench: {kernels.interpreted_calls} kernel calls ran "
              "interpreted", file=sys.stderr)
        return 3
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
