#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 chipbench/run.py --workload <name> --seed <n> --seconds <s> \\
        --trace <0|1>

Set-up (timed from process start: imports, the device, the inputs drawn
from the seed, compilation and one drain of every loop in the traffic's
pool) is followed by back-to-back drains for ``--seconds``.  A drain is
one self-scheduled loop, from its claim to its last tile written, ended
by ``block_until_ready``.

  --trace 0  prints the cell's end-to-end metrics: ``drain_ms`` (window
             over drains), ``drain_p95_ms`` and ``setup_s``;
  --trace 1  profiles a window of at most ``TRACE_SECONDS`` and prints the
             cell's per-layer metrics, read from the device trace by
             ``metrics/<name>.py``, with the longest device ops and idle
             gaps as ``breakdown``.

After the window the kept drains are compared with the plain references
(``reference.py``) and every drain's schedule with the technique's closed
form there.  The
last line of stdout is one JSON object; each number compared is printed
beside its limit on stderr and under ``checks``.  Without a TPU, or with
fewer chips than the cell asks for, it exits 2 and prints no result.
"""
import time

T_START = time.perf_counter()  # set-up is timed from here

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from chipbench import cells, trace as trace_mod  # noqa: E402
from chipbench.peaks import peaks  # noqa: E402
from chipbench.spans import span  # noqa: E402
from chipbench.traffic import Reservoir  # noqa: E402

TRACE_SECONDS = 2.0


class NoChip(RuntimeError):
    pass


def chip_device(chips: int) -> dict:
    """The TPU this run measures; raises ``NoChip`` without enough of them."""
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"no TPU: JAX found {devs[0].platform!r}")
    if len(devs) < chips:
        raise NoChip(f"{chips} chips asked, {len(devs)} found")
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": chips}


def _memory_peak(count: int) -> int:
    import jax

    stats = [d.memory_stats() or {} for d in jax.devices()[:count]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class WindowWatch:
    """Set around the window: counts jit traces (there should be none) and
    the collector's pauses, diagnostics the driver ignores.  What set-up
    built lives to the end, so it is frozen out of the collector's full
    passes for the window's length."""

    def __enter__(self):
        import jax

        self.compiles, self.pauses, self._t = 0, [], None
        jax.monitoring.register_event_duration_secs_listener(self._event)
        gc.callbacks.append(self._gc)
        gc.freeze()
        return self

    def __exit__(self, *exc):
        import jax

        gc.unfreeze()
        gc.callbacks.remove(self._gc)
        jax.monitoring.unregister_event_duration_listener(self._event)

    def _event(self, event, *_args, **_kw):
        if event == "/jax/core/compile/jaxpr_trace_duration":
            self.compiles += 1

    def _gc(self, phase, _info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            self.pauses.append(time.perf_counter() - self._t)


def schedule_checks(drv, schedules) -> list:
    """Per drain: are the granted chunks the technique's closed form
    (``reference.chunk_plan``), do they sum to N, and is the slab's loop
    pointer at N or past it?"""
    import numpy as np

    from chipbench.reference import chunk_plan

    sizes, starts = chunk_plan(drv.technique, drv.N, drv.P)
    out = []
    for st, sz, slab, lp_slot in schedules:
        out.append({
            "schedule_mismatch": int(not (np.array_equal(sz, sizes)
                                          and np.array_equal(st, starts))),
            "schedule_sum_off": abs(int(np.sum(sz)) - drv.N),
            "loop_pointer_short": max(0, drv.N - int(np.asarray(slab)[lp_slot])),
        })
    return out


def judge(readings: list, limits: dict) -> tuple:
    """(failed drains, {name: {"value": worst, "limit": limit}})."""
    failed, worst = 0, {}
    for r in readings:
        bad = False
        for name, value in r.items():
            worst[name] = max(worst.get(name, value), value)
            bad |= not value <= limits[name]
        failed += bad
    return failed, {n: {"value": v, "limit": limits[n]}
                    for n, v in worst.items()}


class Ctx:
    """What a per-layer metric reader sees."""

    def __init__(self, red, work, peaks_):
        self.red, self.work, self.peaks = red, work, peaks_


def run_cell(workload: dict, cfg: dict, traffic: dict, bench: dict, *,
             seed: int, seconds: float, trace: bool, device: dict,
             interpret: bool = False, t_start: float = T_START) -> dict:
    """One run of one cell; returns the result line as a dict."""
    import jax
    import numpy as np

    mod = cells.driver(cfg["entry"])
    drv = mod.Driver(cfg, traffic, seed, interpret=interpret)
    drv.setup()
    setup_s = time.perf_counter() - t_start

    window = min(seconds, TRACE_SECONDS) if trace else seconds
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    keep = Reservoir(traffic.get("check_sample", 4), seed)
    times, schedules = [], []
    try:
        if trace:
            # device and TraceMe events only: the Python tracer would time
            # every function call of the host's claim plane, and slow it
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
        with WindowWatch() as watch:
            t_win = time.perf_counter()
            while True:
                t0 = time.perf_counter()
                if t0 - t_win >= window and times:
                    break
                i = len(times)
                with span("drain"):
                    out, sched = drv.drain(i)
                times.append(time.perf_counter() - t0)
                schedules.append(sched)
                keep.offer((i % drv.pool, out))
                del out
            window_s = time.perf_counter() - t_win
        if trace:
            jax.profiler.stop_trace()
            events = trace_mod.load_xplane(
                next(Path(trace_dir).rglob("*.xplane.pb")).as_posix())
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)

    device = dict(device, memory_peak_bytes=_memory_peak(device["count"]))
    limits = dict(cfg["limits"], schedule_mismatch=0, schedule_sum_off=0,
                  loop_pointer_short=0)
    readings = schedule_checks(drv, schedules)
    for i, r in drv.compare(keep.kept).items():
        readings[i] = dict(readings[i], **r)
    failed, checks = judge(readings, limits)

    metrics, breakdown = {}, None
    if not trace:
        values = {"drain_ms": 1e3 * window_s / len(times),
                  "drain_p95_ms": 1e3 * float(np.percentile(times, 95)),
                  "setup_s": setup_s}
        for m in cells.end_to_end_for(workload["name"], bench):
            metrics[m["name"]] = {"value": values[m["name"]],
                                  "unit": m["unit"]}
    else:
        red = trace_mod.reduce(events, mod.KERNELS, mod.COMPUTE)
        work = [drv.work(i) for i in range(len(times))]
        work = (None if any(w is None for w in work) else
                {k: sum(w[k] for w in work) for k in work[0]})
        ctx = Ctx(red, work, peaks(device["kind"]))
        for m in cells.per_layer_for(workload["name"], bench):
            value = cells.metric_reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
        device["busy_s"] = red.busy_ns * 1e-9
        device["window_s"] = red.window_ns * 1e-9
        breakdown = {
            "device_ops": [[n, ns * 1e-9] for n, ns in red.top_ops],
            "idle_gaps": [[n, ns * 1e-9] for n, ns in red.idle_gaps]}

    result = {"correct": failed == 0 and bool(times), "attempted": len(times),
              "failed": failed, "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    # diagnostics the driver ignores
    result["compiles_in_window"] = watch.compiles
    result["drain_max_ms"] = 1e3 * max(times)
    result["gc_total_ms"] = 1e3 * sum(watch.pauses)
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    bench = cells.load_benchmark()
    workload = cells.workload(args.workload, bench)
    cfg, traffic = cells.load_cell(args.workload, bench)
    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chipbench: no program under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    try:
        device = chip_device(workload["chips"])
    except NoChip as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2

    import jax

    from repro import kernels
    from repro.launch.cache import enable_compile_cache

    enable_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    result = run_cell(workload, cfg, traffic, bench, seed=args.seed,
                      seconds=args.seconds, trace=bool(args.trace),
                      device=device)
    if kernels.interpreted_calls:
        print(f"chipbench: {kernels.interpreted_calls} kernel calls ran "
              "interpreted", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
