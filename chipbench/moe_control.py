#!/usr/bin/env python3
"""Readings the MoE cell's limits of ``correct`` are set from, on the chip.

    python3 chipbench/moe_control.py --workload moe-dsv3-ep32-topics \\
        --seeds 1 2 ... --control-seeds 1 2 3

``control.py`` reads a cell with one limit; this cell has three
(``routing_mismatches``, ``routing_tie_mismatches``, ``max_abs_err``).
For each seed this builds the cell's pool exactly as a run does, drains
each drain of a cycle of layers and batches once through the timed path,
and prints its readings against the plain reference (the lower ends of
the limits).  For the control seeds it also puts the reference itself in
the program's place, with its tokens, router and expert weights in the
driver's ``CONTROL_DTYPE`` (float8_e4m3fn, the precision below the
configuration's bfloat16) and its output in bfloat16, and prints those
readings (the upper ends).  One JSON line per drain.  The benchmark's own
runs never run this.
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
             interpret: bool = False, cfg=None, traffic=None):
    """One dict per drain of a cycle: the program's readings, and the
    control's where asked."""
    import jax.numpy as jnp

    if cfg is None:
        cfg, traffic = cells.load_cell(workload)
    mod = cells.driver(cfg["entry"])
    drv = mod.Driver(cfg, traffic, seed, interpret=interpret)
    drv.setup()
    for i in range(drv.cycle):
        (y, ids), _ = drv.drain(i)
        chosen = mod.chosen_experts(ids, E_all=drv.E_all)
        out = {"workload": workload, "seed": seed, "drain": i,
               "loads": [int(n) for n in drv.loads[i]],
               "program": drv.readings(i, chosen, y)}
        del y, ids
        if control:
            out["control"] = drv.control(i, getattr(jnp, mod.CONTROL_DTYPE))
        yield out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    args = ap.parse_args(argv)

    import jax

    from repro.launch.cache import enable_compile_cache

    if jax.devices()[0].platform != "tpu":
        print("moe_control: no TPU", file=sys.stderr)
        return 2
    enable_compile_cache()
    for seed in args.seeds:
        for r in readings(args.workload, seed, seed in args.control_seeds):
            print(json.dumps(r), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
