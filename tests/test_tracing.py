"""Program spans and counters (``repro.tracing``) under the JAX profiler.

Each traced test starts and stops the profiler itself, in ``try/finally``:
a process holds one profiler session at a time.  Kernels run in interpret
mode on the CPU, at small sizes.
"""
import subprocess
import sys
from pathlib import Path

import jax
import numpy as np
import pytest

from repro import dls, tracing
from repro.core.chunk_calculus import max_steps_bound
from repro.device import host_spec
from repro.device.persistent import claim_schedule
from repro.kernels import (flash_attention_persistent, mandelbrot_persistent,
                           moe_experts_persistent)

ROOT = Path(__file__).resolve().parent.parent
P, TILE = 8, 8

CLAIM = {"repro.claim.costs": "repro.claim",
         "repro.claim.launch": "repro.claim",
         "repro.claim": None, "repro.tables": None,
         "repro.compute.launch": None}
# the schedule is read back where its host view is first asked for: by
# the session's report plane, or after the compute kernel has run
NESTING = {
    "mandelbrot": dict(CLAIM, **{"repro.session.open": None,
                                 "repro.report": None,
                                 "repro.claim.readback": "repro.report"}),
    "attention": dict(CLAIM, **{"repro.tile_costs": None,
                                "repro.claim.readback": None}),
    "moe": dict(CLAIM, **{"repro.route": None,
                          "repro.route.readback": "repro.route",
                          "repro.tile_costs": None,
                          "repro.claim.readback": None}),
}


def _traced(tmp_path, fn):
    """(``fn()``, the ``repro.*`` host spans recorded while it ran), the
    spans as dicts in order of their start."""
    from jax.profiler import ProfileData

    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        out = fn()
    finally:
        jax.profiler.stop_trace()
    path = next(tmp_path.rglob("*.xplane.pb"))
    spans = [dict(thread=line.name, name=e.name, start=e.start_ns,
                  end=e.start_ns + e.duration_ns, stats=dict(e.stats))
             for plane in ProfileData.from_file(str(path)).planes
             if not plane.name.startswith("/device")
             for line in plane.lines for e in line.events
             if e.name.startswith(tracing.PREFIX)]
    return out, sorted(spans, key=lambda s: s["start"])


def _parent(s, spans):
    """Name of the innermost span enclosing ``s`` on its thread, or None."""
    outer = [p for p in spans if p is not s and p["thread"] == s["thread"]
             and p["start"] <= s["start"] and s["end"] <= p["end"]]
    return min(outer, key=lambda p: p["end"] - p["start"])["name"] \
        if outer else None


def _mandel_drain(N, technique):
    """One self-scheduled Mandelbrot loop of N tiles through the session
    path, as a user of ``repro.dls`` writes it."""
    side = int(round(N ** 0.5)) * TILE
    s = dls.loop(N, technique=technique, P=P, runtime="device")
    s.execute(None, executor="device", costs=np.ones(N))
    out, sched = mandelbrot_persistent(
        side, side, ct=8, block_h=TILE, block_w=TILE, workers=P,
        schedule=s.runtime.schedule)
    jax.block_until_ready(out)
    return sched


def _attention_launch():
    """Self-scheduled attention, launched: ``(out, schedule)``."""
    B, H, T, D = 2, 2, 256, 128
    q, k, v = (jax.random.normal(key, (B, H, T, D))
               for key in jax.random.split(jax.random.key(0), 3))
    return flash_attention_persistent(
        q, k, v, lengths=[256, 100], causal=True, technique="gss",
        workers=P, blk_q=128, blk_k=128)


def _read_after_compute(launch):
    """One drain as the chip benchmark runs it: launch, wait for the
    output, then read the schedule's host view."""
    out, sched = launch()
    jax.block_until_ready(out)
    sched.starts
    return sched


def _attention_drain():
    return _read_after_compute(_attention_launch)


def _moe_drain():
    return _read_after_compute(_moe_launch)


def _moe_launch():
    """One chip's 4 of 16 experts, top 2 of 16: expert 1 takes every
    token, expert 2 none; launched: ``(y, schedule)``."""
    T, d, F, E = 40, 128, 128, 4
    ks = jax.random.split(jax.random.key(1), 4)
    x = jax.random.normal(ks[0], (T, d))
    wg, wu = (jax.random.normal(k, (E, d, F)) for k in ks[1:3])
    wd = jax.random.normal(ks[3], (E, F, d))
    ids = np.stack([np.full(T, 1), 4 + np.arange(T) % 12], 1)
    ids[:7, 1] = 0  # 7 rows for expert 0, 0 for 2, 3 for 3
    ids[7:10, 1] = 3
    return moe_experts_persistent(x, wg, wu, wd, ids.astype(np.int32),
                                  np.full((T, 2), 0.5, np.float32),
                                  held=range(4), workers=P, blk=8)


DRAINS = {"mandelbrot": lambda: _mandel_drain(81, "gss"),
          "attention": _attention_drain, "moe": _moe_drain}


@pytest.mark.parametrize("path", sorted(DRAINS))
def test_spans_nest_as_documented(tmp_path, path):
    _, spans = _traced(tmp_path, DRAINS[path])
    got = {s["name"]: _parent(s, spans) for s in spans}
    assert got == NESTING[path]
    assert len(spans) == len(NESTING[path])  # each phase once a drain


def test_claim_counters_are_the_grants_and_the_loop_length(tmp_path):
    sched, spans = _traced(tmp_path, lambda: _mandel_drain(81, "gss"))
    (claim,) = [s for s in spans if s["name"] == "repro.claim"]
    S = int(max_steps_bound(host_spec("gss", 81, P)))
    assert claim["stats"] == {"claims": sched.n_steps, "steps": S}
    assert (sched.n_steps, S) == (17, 100)
    # the schedule comes back as one packed int32 vector of 4*S + 2*P,
    # read by the report plane before the compute kernel is launched
    (readback,) = [s for s in spans if s["name"] == "repro.claim.readback"]
    assert _parent(readback, spans) == "repro.report"
    assert readback["stats"] == {"arrays": 1, "bytes": 4 * (4 * S + 2 * P),
                                 "after_launch": 0}


@pytest.mark.parametrize("N,technique", [(81, "gss"), (144, "ss")])
def test_the_report_plane_logs_every_claim_in_one_call(tmp_path, N,
                                                        technique):
    sched, spans = _traced(tmp_path, lambda: _mandel_drain(N, technique))
    assert len(spans) == len(NESTING["mandelbrot"]) == 8
    (report,) = [s for s in spans if s["name"] == "repro.report"]
    assert report["stats"] == {"rows": sched.n_steps}


def test_route_readback_counts_the_held_pairs_once(tmp_path):
    _, spans = _traced(tmp_path, _moe_drain)
    (readback,) = [s for s in spans if s["name"] == "repro.route.readback"]
    # loads 7, 40, 0, 3 in blocks of 8: 1 + 5 + 0 + 1 live tiles; one
    # int32 a held expert comes back
    assert readback["stats"] == {"bytes": 4 * 4, "pairs": 50,
                                 "max_load": 40, "live_tiles": 7}


def test_launch_counts_a_compile_on_the_first_call_only(tmp_path):
    def twice():
        # a signature no other test compiles in this process
        for _ in range(2):
            sched = claim_schedule("tss", 15, 3)
            jax.block_until_ready(mandelbrot_persistent(
                40, 24, ct=4, block_h=TILE, block_w=TILE, workers=3,
                schedule=sched)[0])

    _, spans = _traced(tmp_path, twice)
    for name in ("repro.claim.launch", "repro.compute.launch"):
        assert [s["stats"] for s in spans if s["name"] == name] == \
            [{"compiled": 1}, {"compiled": 0}]


def test_span_count_per_drain_does_not_grow_with_the_claims(tmp_path):
    few, spans_few = _traced(tmp_path / "gss",
                             lambda: _mandel_drain(81, "gss"))
    many, spans_many = _traced(tmp_path / "ss",
                               lambda: _mandel_drain(1296, "ss"))
    assert (few.n_steps, many.n_steps) == (17, 1296)
    assert [s["name"] for s in spans_few] == [s["name"] for s in spans_many]
    (claim,) = [s for s in spans_many if s["name"] == "repro.claim"]
    assert claim["stats"] == {"claims": 1296, "steps": 1296}
    (readback,) = [s for s in spans_many
                   if s["name"] == "repro.claim.readback"]
    assert readback["stats"] == {"arrays": 1,
                                 "bytes": 4 * (4 * 1296 + 2 * P),
                                 "after_launch": 0}


LAUNCHES = {"attention": _attention_launch, "moe": _moe_launch}


@pytest.mark.parametrize("path", sorted(LAUNCHES))
def test_compute_is_launched_before_the_schedule_is_read(tmp_path, path):
    """Under the profiler, the wrapper launches the compute kernel on the
    device-built tables and returns without reading the schedule back."""
    def launch():
        out, sched = LAUNCHES[path]()
        jax.block_until_ready(out)
        return sched

    sched, spans = _traced(tmp_path, launch)
    names = [s["name"] for s in spans]
    assert "repro.compute.launch" in names
    assert "repro.claim.readback" not in names
    assert "_host" not in vars(sched)
    assert sched.launched


@pytest.mark.parametrize("path", sorted(LAUNCHES))
def test_the_wrappers_return_with_the_schedule_unread(path):
    assert not tracing.enabled()
    out, sched = LAUNCHES[path]()
    assert "_host" not in vars(sched) and sched.launched
    jax.block_until_ready(out)
    assert int(sched.counts.sum()) == sched.n_steps  # now read back


@pytest.mark.parametrize("path", sorted(LAUNCHES))
def test_a_read_after_the_compute_launch_counts_after_launch(tmp_path,
                                                             path):
    sched, spans = _traced(tmp_path, DRAINS[path])
    (launch,) = [s for s in spans if s["name"] == "repro.compute.launch"]
    (readback,) = [s for s in spans if s["name"] == "repro.claim.readback"]
    assert readback["start"] >= launch["end"]
    S = (sched.packed.shape[0] - 2 * P) // 4
    assert readback["stats"] == {"arrays": 1, "bytes": 4 * (4 * S + 2 * P),
                                 "after_launch": 1}
    # the claims counter is the plan's count, exact with no read-back,
    # and the tables handed to the compute kernel are that wide
    (tables,) = [s for s in spans if s["name"] == "repro.tables"]
    assert tables["stats"] == {"width": sched.n_steps}
    (claim,) = [s for s in spans if s["name"] == "repro.claim"]
    assert claim["stats"] == {"claims": sched.n_steps, "steps": S}


class _Jitted:
    """Counts how often a launch span asks for the cache size."""

    def __init__(self):
        self.asked = 0

    def _cache_size(self):
        self.asked += 1
        return self.asked


def test_without_a_profiler_spans_record_nothing_and_count_nothing(
        tmp_path):
    fn = _Jitted()
    assert not tracing.enabled()
    with tracing.span("off", claims=3):
        with tracing.launch("off.launch", fn):
            pass
    assert fn.asked == 0  # counters are not computed while off

    def on():
        assert tracing.enabled()
        with tracing.launch("on.launch", fn):
            pass

    _, spans = _traced(tmp_path, on)
    assert [s["name"] for s in spans] == ["repro.on.launch"]
    assert spans[0]["stats"] == {"compiled": 1} and fn.asked == 2


def test_host_runtimes_stay_free_of_jax():
    code = ("import sys; from repro import dls; "
            "s = dls.loop(16, 'ss', P=2); "
            "s.execute(lambda a, b: None, executor='serial'); "
            "assert 'jax' not in sys.modules")
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True, timeout=120,
                       env={"PYTHONPATH": str(ROOT / "src"),
                            "PATH": "/usr/bin:/bin"})
    assert r.returncode == 0, r.stderr
