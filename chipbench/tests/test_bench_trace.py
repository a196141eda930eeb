"""The reduction from trace events to per-layer numbers, on the CPU."""
import json
from pathlib import Path

import pytest

from chipbench import cells, trace
from chipbench.trace import Event

DATA = Path(__file__).parent / "data"
HOST, DEV = "/host:CPU", "/device:TPU:0"


def _host(name, a, b):
    return Event(HOST, "python", "bench." + name, a, b - a)


def _dev(line, name, a, b):
    return Event(DEV, line, name, a, b - a)


def _two_drains():
    """Two drains of claim kernel, host gap, compute kernel (ns)."""
    ev = [_host("drain", 0, 100), _host("claim", 0, 40),
          _host("compute", 40, 100), _host("drain", 120, 200),
          _host("claim", 120, 150), _host("compute", 150, 200)]
    for a, b, mod in [(10, 20, "jit_protocol_call(1)"),
                      (50, 90, "jit_persistent_call(2)"),
                      (125, 130, "jit_protocol_call(1)"),
                      (160, 195, "jit_persistent_call(2)")]:
        ev += [_dev(trace.MODULE_LINE, mod, a, b),
               _dev(trace.OP_LINE, mod.split("(")[0] + ".op", a, b)]
    ev.append(_dev(trace.OP_LINE, "copy", 30, 35))  # a stray op in the gap
    return ev


KERNELS = {"claim": "protocol_call", "mandel": "persistent_call"}


def test_busy_union_window_and_kernel_time_per_drain():
    red = trace.reduce(_two_drains(), KERNELS, "mandel")
    assert red.n_drains == 2 and red.window_ns == 200
    assert red.busy_ns == 10 + 5 + 40 + 5 + 35
    assert red.per_drain(red.kernel_ns["claim"]) == 7.5
    assert red.per_drain(red.kernel_ns["mandel"]) == 37.5


def test_claim_gap_leaves_out_device_work_inside_it():
    red = trace.reduce(_two_drains(), KERNELS, "mandel")
    assert red.claim_gap_ns == (30 - 5) + 30


def test_idle_gaps_labelled_by_innermost_host_span():
    red = trace.reduce(_two_drains(), KERNELS, "mandel")
    # the stretch 90..125 runs through compute, no drain, and a claim
    assert dict(red.idle_gaps) == {"claim": 50, "compute": 35,
                                   trace.OUTSIDE: 20}
    assert red.idle_gaps[0] == ("claim", 50)


def test_top_ops_longest_first():
    red = trace.reduce(_two_drains(), KERNELS, "mandel")
    assert red.top_ops[0] == ("jit_persistent_call.op", 75)


def test_metric_readers_on_the_reduction():
    red = trace.reduce(_two_drains(), KERNELS, "mandel")

    class Ctx:
        pass

    ctx = Ctx()
    ctx.red, ctx.work, ctx.peaks = red, None, None
    read = cells.metric_reader
    assert read("claim_kernel_us")(ctx) == 7.5 / 1e3
    assert read("claim_gap_ms")(ctx) == 27.5 / 1e6
    assert read("mandel_kernel_ms")(ctx) == 37.5 / 1e6
    assert read("attn_kernel_ms")(ctx) is None
    assert read("attn_roofline")(ctx) is None
    assert read("device_idle_pct")(ctx) == pytest.approx(52.5)


def test_no_drain_span_is_an_error():
    with pytest.raises(ValueError):
        trace.reduce([_dev(trace.OP_LINE, "x", 0, 1)], KERNELS, "mandel")


def _recorded():
    """Three drains of ``mandel-1152-ct1000-gss`` traced on a TPU v5e."""
    with open(DATA / "mandel-gss-3drains.json") as f:
        return [Event(*e) for e in json.load(f)]


def _bitmap(events, lo, hi, res):
    """Busy bins of [lo, hi) at ``res`` ns, from the device op events."""
    import numpy as np

    busy = np.zeros(int((hi - lo) // res) + 1, bool)
    for e in events:
        if e.plane == DEV and e.line == trace.OP_LINE:
            a = int(max(e.start_ns - lo, 0) // res)
            b = int(max(min(e.end_ns, hi) - lo, 0) // res)
            busy[a:b + 1] = True
    return busy


def test_recorded_trace_against_a_brute_force_timeline():
    import numpy as np

    ev = _recorded()
    red = trace.reduce(ev, KERNELS, "mandel")
    drains = sorted((e for e in ev if e.name == trace.DRAIN_SPAN),
                    key=lambda e: e.start_ns)
    lo, hi = drains[0].start_ns, drains[-1].end_ns
    res = 10.0
    busy = _bitmap(ev, lo, hi, res)
    slack = res * 2 * sum(e.line == trace.OP_LINE for e in ev)
    assert red.n_drains == 3 and red.window_ns == hi - lo
    assert abs(red.busy_ns - busy.sum() * res) <= slack

    mods = [e for e in ev if e.line == trace.MODULE_LINE]
    for role, pattern in KERNELS.items():
        want = sum(e.dur_ns for e in mods if pattern in e.name)
        assert red.kernel_ns[role] == pytest.approx(want)
    assert red.per_drain(red.kernel_ns["mandel"]) > 1e6  # ms-long kernel

    claims = [e for e in mods if "protocol_call" in e.name]
    gap = 0.0
    for c in claims:
        nxt = min((e.start_ns for e in mods if "persistent_call" in e.name
                   and e.start_ns > c.end_ns), default=None)
        a, b = int((c.end_ns - lo) // res), int((nxt - lo) // res)
        gap += (~busy[a:b]).sum() * res
    assert abs(red.claim_gap_ns - gap) <= slack
    assert red.claim_gap_ns > 0

    # label every idle bin by the shortest host span covering it
    spans = [e for e in ev if e.plane != DEV]
    names = [trace.OUTSIDE] + [e.name[len("bench."):] for e in spans]
    owner = np.zeros(len(busy), int)
    width = np.full(len(busy), np.inf)
    for j, e in enumerate(spans, 1):
        a = int(np.ceil((e.start_ns - lo) / res - 0.5))
        b = int((e.end_ns - lo) // res)
        sel = slice(max(a, 0), b)
        shorter = width[sel] > e.dur_ns
        owner[sel][shorter], width[sel][shorter] = j, e.dur_ns
    labels = {}
    for j in owner[:-1][~busy[:-1]]:
        labels[names[j]] = labels.get(names[j], 0.0) + res
    got = dict(red.idle_gaps)
    assert set(got) == set(labels)
    for name, ns in labels.items():
        assert abs(got[name] - ns) <= slack + 0.01 * ns
