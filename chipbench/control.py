#!/usr/bin/env python3
"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 chipbench/control.py --workload <name> --seeds 1 2 ... \\
        --control-seeds 1 2 3

For each seed this builds the cell's pool exactly as a run does, drains
every loop of it once through the timed path, and prints the reading of
each against the plain reference (the program's readings: the lower end
of a limit).  For the control seeds it puts the reference itself in the
program's place, computed in the precision below the one the
configuration states (the driver's ``CONTROL_DTYPE``), and prints that
reading (the upper end):

  * Mandelbrot, float32: the escape counts computed in bfloat16;
  * attention, bfloat16: q, k and v rounded to float8_e4m3fn, the output
    to bfloat16.

One JSON line per reading.  The benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from chipbench import cells  # noqa: E402


def readings(workload: str, seed: int, control: bool,
             interpret: bool = False, cfg=None, traffic=None) -> dict:
    """{"program": worst reading, "control": worst control reading}."""
    import jax.numpy as jnp

    bench = cells.load_benchmark()
    if cfg is None:
        cfg, traffic = cells.load_cell(workload, bench)
    mod = cells.driver(cfg["entry"])
    drv = mod.Driver(cfg, traffic, seed, interpret=interpret)
    drv.setup()
    kept = {i: (i % drv.pool, drv.drain(i)[0]) for i in range(drv.pool)}
    (name,) = cfg["limits"]
    out = {"workload": workload, "seed": seed, "number": name,
           "program": max(r[name] for r in drv.compare(kept).values())}
    if control:
        dtype = getattr(jnp, mod.CONTROL_DTYPE)
        out["control"] = max(drv.control(p, dtype) for p in range(drv.pool))
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax

    from repro.launch.cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("control: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    for seed in args.seeds:
        print(json.dumps(readings(args.workload, seed,
                                  seed in args.control_seeds)), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
