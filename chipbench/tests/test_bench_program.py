"""The program's spans against the drains and the device, on the CPU."""
import json
from pathlib import Path

import numpy as np
import pytest

from chipbench import cells, program, trace
from chipbench.program import Span
from chipbench.trace import Event

DATA = Path(__file__).parent / "data"
HOST, DEV, THREAD = "/host:CPU", "/device:TPU:0", "python3"


def _span(name, a, b, thread=THREAD, **stats):
    return Span(HOST, thread, "repro." + name, a, b - a, stats)


def _synthetic():
    """Two drains: claim, tables and compute launch, with device ops (ns)."""
    events = [Event(HOST, THREAD, trace.DRAIN_SPAN, 0, 100),
              Event(HOST, THREAD, trace.DRAIN_SPAN, 120, 80)]
    for a, b in [(15, 18), (70, 95), (165, 190)]:
        events.append(Event(DEV, trace.OP_LINE, "op", a, b - a))
    spans = [
        _span("claim", 5, 45, steps=10, claims=3),
        _span("claim.launch", 10, 15, compiled=0),
        _span("claim.readback", 20, 40),
        _span("tables", 50, 60),
        _span("compute.launch", 60, 70, compiled=1),
        _span("report", 100, 110),                 # between the drains
        _span("claim", 120, 150, steps=10, claims=3),
        _span("report", 130, 140, thread="other"),  # not the drain's thread
        _span("tables", 150, 160),
        _span("compute.launch", 160, 210, compiled=0),  # past the drain
    ]
    return spans, events


def test_host_time_is_clipped_to_the_drains():
    prog = program.reduce(*_synthetic())
    assert prog.n_drains == 2 and prog.spans_per_drain == [5, 3]
    assert prog.host_ns == {"repro.claim": 70, "repro.claim.launch": 5,
                            "repro.claim.readback": 20, "repro.tables": 20,
                            "repro.compute.launch": 50}


def test_idle_goes_to_the_innermost_span_or_to_none():
    prog = program.reduce(*_synthetic())
    # drain 1 idles 0..15, 18..70 and 95..100; drain 2 120..165, 190..200
    assert prog.idle_ns == {"repro.claim": 42, "repro.claim.launch": 5,
                            "repro.claim.readback": 20, "repro.tables": 20,
                            "repro.compute.launch": 25}
    assert prog.untraced_idle_ns == 15  # 0..5, 45..50, 95..100


def test_counters_are_summed_per_drain():
    prog = program.reduce(*_synthetic())
    assert prog.counters == {
        "repro.claim": {"steps": [10, 10], "claims": [3, 3]},
        "repro.claim.launch": {"compiled": [0, 0]},
        "repro.compute.launch": {"compiled": [1, 0]}}


def _ctx(prog):
    class Ctx:
        pass

    ctx = Ctx()
    ctx.red, ctx.work, ctx.peaks, ctx.program = None, None, None, prog
    return ctx


def test_readers_on_the_program_reduction():
    ctx = _ctx(program.reduce(*_synthetic()))
    read = cells.metric_reader
    assert read("claim_host_ms")(ctx) == 35 / 1e6
    assert read("claim_readback_ms")(ctx) == 10 / 1e6
    assert read("launch_ms")(ctx) == 27.5 / 1e6
    assert read("claim_tables_ms")(ctx) == 10 / 1e6
    assert read("claim_step_use_pct")(ctx) == pytest.approx(30.0)
    assert read("idle_untraced_ms")(ctx) == 7.5 / 1e6
    assert read("report_plane_ms")(ctx) is None
    assert read("tile_costs_ms")(ctx) is None


@pytest.mark.parametrize("metric", program.METRICS)
def test_readers_return_none_without_program_spans(metric):
    read = cells.metric_reader(metric)
    assert read(_ctx(None)) is None
    red = trace.reduce(*_synthetic()[1:], {})

    class HarnessCtx:  # what run.py hands its readers today
        pass

    ctx = HarnessCtx()
    ctx.red, ctx.work, ctx.peaks = red, None, None
    assert read(ctx) is None
    assert read(_ctx(program.reduce([], _synthetic()[1]))) is None


def _recorded(name):
    with open(DATA / name) as f:
        data = json.load(f)
    if isinstance(data, list):  # device events and harness spans only
        return [], [Event(*e) for e in data]
    return ([Span(*s) for s in data["spans"]],
            [Event(*e) for e in data["events"]])


def _busy_bins(events, lo, hi, res):
    """Busy bins of [lo, hi) at ``res`` ns, from the device op events."""
    busy = np.zeros(int((hi - lo) // res) + 1, bool)
    for e in events:
        if e.plane == DEV and e.line == trace.OP_LINE:
            a = int(max(e.start_ns - lo, 0) // res)
            b = int(max(min(e.end_ns, hi) - lo, 0) // res)
            busy[a:b + 1] = True
    return busy


def _brute_force(spans, events, res=10.0):
    """(host ns per span name, idle ns per innermost span name) from a
    timeline of bins, each idle bin inside a drain owned by the shortest
    program span covering it."""
    drains = sorted((e for e in events if e.name == trace.DRAIN_SPAN),
                    key=lambda e: e.start_ns)
    lo, hi = drains[0].start_ns, drains[-1].end_ns
    busy = _busy_bins(events, lo, hi, res)
    inside = np.zeros(len(busy), bool)
    host = {}
    names = [program.UNTRACED]
    owner = np.zeros(len(busy), int)
    width = np.full(len(busy), np.inf)
    for d in drains:
        inside[int(np.ceil((d.start_ns - lo) / res)):
               int((d.end_ns - lo) // res)] = True
        for s in spans:
            if not d.start_ns <= s.start_ns < d.end_ns:
                continue
            end = min(s.end_ns, d.end_ns)
            host[s.name] = host.get(s.name, 0.0) + end - s.start_ns
            names.append(s.name)
            a = int(np.ceil((s.start_ns - lo) / res - 0.5))
            b = int((end - lo) // res)
            sel = slice(max(a, 0), b)
            shorter = width[sel] > s.dur_ns
            owner[sel][shorter] = len(names) - 1
            width[sel][shorter] = s.dur_ns
    idle = {}
    for j in owner[~busy & inside]:
        idle[names[j]] = idle.get(names[j], 0.0) + res
    return host, idle


def test_recorded_attention_trace_against_a_brute_force_timeline():
    """Three drains of ``attn-dsk67-tp8-varlen`` traced on a TPU v5e, with
    the program's spans and counters."""
    spans, events = _recorded("attn-varlen-3drains.json")
    prog = program.reduce(spans, events)
    host, idle = _brute_force(spans, events)
    assert prog.n_drains == 3 and set(prog.spans_per_drain) == {7}
    for name, ns in host.items():
        assert prog.host_ns[name] == pytest.approx(ns)
    slack = 10.0 * 4 * sum(e.line == trace.OP_LINE for e in events)
    got = dict(prog.idle_ns, **{program.UNTRACED: prog.untraced_idle_ns})
    assert set(got) == set(idle)
    for name, ns in idle.items():
        assert abs(got[name] - ns) <= slack + 0.01 * ns
    # 35 gss chunks of 1536 tiles, in a loop of 1577 steps, every drain
    assert prog.counters["repro.claim"] == {"steps": [1577] * 3,
                                            "claims": [35] * 3}
    assert sum(prog.idle_ns.values()) > 4 * prog.untraced_idle_ns


def test_a_trace_without_program_spans_leaves_all_idle_untraced():
    spans, events = _recorded("mandel-gss-3drains.json")
    assert spans == []
    prog = program.reduce(spans, events)
    _, idle = _brute_force(spans, events)
    assert prog.host_ns == {} and prog.idle_ns == {}
    slack = 10.0 * 4 * sum(e.line == trace.OP_LINE for e in events)
    assert abs(prog.untraced_idle_ns - idle[program.UNTRACED]) <= slack
    for metric in program.METRICS:
        assert cells.metric_reader(metric)(_ctx(prog)) is None


def test_traced_run_reports_the_program_metrics():
    """A whole traced run of the gss Mandelbrot cell, at a test size, on
    the CPU (all its device time counts as idle there)."""
    from chipbench.tests.test_bench_correct import CPU, MANDEL, _tiny

    cfg, tr = _tiny(MANDEL)
    r = program.traced_run(MANDEL, 2**31 + 9, cfg=cfg, traffic=tr,
                           device=dict(CPU, kind="TPU v5 lite"),
                           interpret=True)
    assert r["correct"]
    p = r["program"]
    assert p["spans_per_drain"] == [8]
    assert p["claims_per_drain_are_the_closed_form"]
    assert p["compiled"] == 0
    assert set(p["metrics"]) == set(program.METRICS) - {"tile_costs_ms"}
