"""``correct`` comes out false when the timed path is broken, on the CPU.

Each test drives a whole run of a cell, at a size a test run can hold,
with the chip check skipped and the Pallas kernels interpreted, and
breaks the program underneath: a drain that leaves its output unchanged,
half of the loop left out, one answer altered where it is produced, the
claim loop leaving the window's counters as it found them, or chunks
granted off the technique's closed form on host and device alike.  The
control tests put the plain reference, computed in the precision below
the configuration's, in the program's place.  (No cell spans chips, so
no exchange between chips can be left out.)
"""
import jax.numpy as jnp
import numpy as np
import pytest

import repro.kernels
from chipbench import cells, control, reference, run

MANDEL, ATTN = "mandel-1152-ct1000-gss", "attn-dsk67-tp8-varlen"
CPU = {"platform": "cpu", "kind": "cpu", "count": 1}


def _tiny(name):
    cfg, tr = cells.load_cell(name)
    if cfg["entry"] == "mandel_session":
        cfg = dict(cfg, width=32, height=32, ct=20)
        tr = dict(tr, tile_h=8, tile_w=16)
    else:
        cfg = dict(cfg, max_position_embeddings=256)
        tr = dict(tr, batch=2, pool=2,
                  lengths=dict(tr["lengths"], median=100, max=256))
    return cfg, tr


def _run(name, trace=False, device=CPU):
    bench = cells.load_benchmark()
    cfg, tr = _tiny(name)
    return run.run_cell(cells.workload(name, bench), cfg, tr, bench,
                        seed=2**31 + 5, seconds=0.2, trace=trace,
                        device=device, interpret=True)


def _wrap(monkeypatch, entry, fault):
    """Replace ``repro.kernels.<entry>`` by ``fault`` over its output."""
    real = getattr(repro.kernels, entry)

    def broken(*args, **kw):
        out, sched = real(*args, **kw)
        return fault(out), sched

    monkeypatch.setattr(repro.kernels, entry, broken)


@pytest.mark.parametrize("name", [MANDEL, ATTN])
def test_sound_runs_are_correct(name):
    r = _run(name)
    assert r["correct"] and r["failed"] == 0 and r["attempted"] > 0
    assert all(c["value"] <= c["limit"] for c in r["checks"].values())


MANDEL_FAULTS = {
    "output_unchanged": lambda out: jnp.zeros_like(out),
    "half_left_out": lambda out: out.at[out.shape[0] // 2:].set(0),
    "answer_altered": lambda out: out.at[3, 5].add(1),
}
ATTN_FAULTS = {
    "output_unchanged": lambda out: jnp.zeros_like(out),
    "half_left_out": lambda out: out.at[out.shape[0] // 2:].set(0),
    "answer_altered": lambda out: out.at[0, 0, 0, 0].add(1),
}


@pytest.mark.parametrize("fault", sorted(MANDEL_FAULTS))
def test_broken_mandelbrot_is_not_correct(monkeypatch, fault):
    _wrap(monkeypatch, "mandelbrot_persistent", MANDEL_FAULTS[fault])
    r = _run(MANDEL)
    assert not r["correct"] and r["failed"] > 0
    assert r["checks"]["pixel_mismatches"]["value"] > 0


@pytest.mark.parametrize("fault", sorted(ATTN_FAULTS))
def test_broken_attention_is_not_correct(monkeypatch, fault):
    _wrap(monkeypatch, "flash_attention_persistent", ATTN_FAULTS[fault])
    r = _run(ATTN)
    c = r["checks"]["max_abs_err"]
    assert not r["correct"] and c["value"] > c["limit"]


@pytest.mark.parametrize("name", [MANDEL, ATTN])
def test_claim_loop_leaving_the_window_unchanged_is_not_correct(
        monkeypatch, name):
    import repro.device.persistent as persistent

    real = persistent.protocol_call

    def stale(slab, csum, **kw):
        return (slab,) + tuple(real(slab, csum, **kw)[1:])

    monkeypatch.setattr(persistent, "protocol_call", stale)
    r = _run(name)
    assert not r["correct"]
    assert r["checks"]["loop_pointer_short"]["value"] > 0


@pytest.mark.parametrize("name", [MANDEL, ATTN])
def test_chunks_off_the_closed_form_are_not_correct(monkeypatch, name):
    """A smallest chunk of 3, where the configuration states 1: host plan
    and device calculus move together, and any partition still renders
    the same output, so only the closed form of ``reference.py`` sees it."""
    import importlib

    mod = importlib.import_module(
        "repro.device.executor" if name == MANDEL
        else "repro.kernels.flash_attention.persistent")
    real = mod.claim_schedule

    def coarse(*args, **kw):
        return real(*args, **dict(kw, chunk=3))

    monkeypatch.setattr(mod, "claim_schedule", coarse)
    r = _run(name)
    assert not r["correct"]
    assert r["checks"]["schedule_mismatch"]["value"] > 0
    assert r["checks"]["schedule_sum_off"]["value"] == 0
    out = [n for n in r["checks"] if n not in (
        "schedule_mismatch", "schedule_sum_off", "loop_pointer_short")]
    assert all(r["checks"][n]["value"] <= r["checks"][n]["limit"]
               for n in out)


def test_gss_closed_form_by_hand():
    """Eq. 1 at N = 81, P = 8: ceil((7/8)^i * 81 / 8), the last chunk cut
    to what is left; not ceil(R / P) of the remainder R."""
    sizes, starts = reference.chunk_plan("gss", 81, 8)
    assert list(sizes) == [11, 9, 8, 7, 6, 6, 5, 4, 4, 4, 3, 3, 3, 2, 2, 2, 2]
    assert list(starts) == list(np.cumsum(sizes) - sizes)


@pytest.mark.parametrize("technique", ["static", "ss", "gss", "tss", "fac2"])
def test_chunk_plan_partitions_the_loop(technique):
    for N in (1, 7, 81, 1296, 1536):
        for P in (1, 3, 8, 288):
            sizes, starts = reference.chunk_plan(technique, N, P)
            assert sizes.min() >= 1 and sizes.sum() == N
            assert list(starts) == list(np.cumsum(sizes) - sizes)


@pytest.mark.parametrize("technique", ["static", "ss", "gss", "tss", "fac2"])
def test_the_program_grants_the_closed_form(technique):
    """The program's planner agrees with the reference at the cells' sizes
    and around them: a change to either shows here first."""
    from repro.core.chunk_calculus import plan
    from repro.device import host_spec

    for N in (1, 2, 7, 63, 64, 65, 81, 100, 1296, 1536, 4096):
        for P in (1, 2, 7, 8, 16, 288):
            want = reference.chunk_plan(technique, N, P)
            got = plan(host_spec(technique, N, P))
            assert np.array_equal(got[0], want[0]), (N, P)
            assert np.array_equal(got[1], want[1]), (N, P)


@pytest.mark.parametrize("name", [MANDEL, ATTN])
def test_the_control_fails_the_limit(name):
    """The reference in a lower precision (bfloat16 for Mandelbrot's
    float32, float8 for attention's bfloat16) reads past the limit."""
    cfg, tr = _tiny(name)
    got = control.readings(name, 2**31 + 5, True, interpret=True, cfg=cfg,
                           traffic=tr)
    limit = cfg["limits"][got["number"]]
    assert got["program"] <= limit < got["control"]


def test_a_traced_run_reads_its_trace():
    """The CPU has no TPU plane: the traced path runs end to end, the
    readers of device kernels find nothing, and the device reads idle."""
    r = _run(MANDEL, trace=True, device=dict(CPU, kind="TPU v5 lite"))
    assert r["correct"]
    assert set(r["metrics"]) == {"device_idle_pct"}
    assert r["metrics"]["device_idle_pct"]["value"] == 100.0
    assert r["device"]["window_s"] > 0 and r["device"]["busy_s"] == 0
    assert [n for n, _ in r["breakdown"]["idle_gaps"]][0] in (
        "claim", "compute")
