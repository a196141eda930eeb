#!/usr/bin/env python3
"""Compile every cell's kernels for a described TPU v5e, without the chip.

    JAX_PLATFORMS=cpu PYTHONPATH=src python3 chipbench/rehearse.py

For each cell of ``BENCHMARK.json`` this compiles, with the TPU's own
compiler, the protocol kernel (``protocol_call``) and the compute kernel
(``persistent_call``) at the shapes the cell's window drives, which the
cell's driver gives (``rehearsal``), and prints each program's memory
analysis.  The claim-table width ``C`` is the largest per-worker claim
count of the technique's closed-form plan walked with the cell's cost
model.  Nothing runs: this says what compiles and what memory it asks
for, never a time.
"""
from __future__ import annotations

import functools
import os
import sys
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE.parent), str(HERE.parent / "src")]

from chipbench import cells  # noqa: E402


def _widths(technique, N, P, costs):
    """Largest claims per worker under the earliest-free walk of the plan."""
    import numpy as np

    from chipbench.reference import chunk_plan

    sizes, starts = chunk_plan(technique, N, P)
    csum = np.concatenate([[0.0], np.cumsum(costs)]).astype(np.float32)
    clocks = np.zeros(P, np.float32)
    counts = np.zeros(P, np.int64)
    for st, sz in zip(starts, sizes):
        w = int(np.argmin(clocks))
        clocks[w] = np.float32(clocks[w] + (csum[st + sz] - csum[st]))
        counts[w] += 1
    return int(counts.max())


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from repro.core.chunk_calculus import max_steps_bound
    from repro.device import host_spec
    from repro.device.persistent import protocol_call

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])

    def compile_(fn, shapes):
        args = [jax.ShapeDtypeStruct(s, dt, sharding=chip) for s, dt in shapes]
        c = jax.jit(fn, out_shardings=chip).lower(*args).compile()
        assert "tpu_custom_call" in c.as_text()
        m = c.memory_analysis()
        return (f"args={m.argument_size_in_bytes} out={m.output_size_in_bytes}"
                f" temp={m.temp_size_in_bytes}")

    for cell in cells.load_benchmark()["workloads"]:
        cfg, tr = cells.load_cell(cell["name"])
        P, t = cfg["workers"], tr["technique"]
        N, prog, shapes = cells.driver(cfg["entry"]).rehearsal(
            cfg, tr, lambda N, costs, t=t, P=P: _widths(t, N, P, costs))
        mem = compile_(prog, shapes)
        S = int(max_steps_bound(host_spec(t, N, P)))
        proto = functools.partial(
            protocol_call, technique=t, N=N, P=P, chunk=1, max_chunk=None,
            S=S, i_slot=0, lp_slot=1, interpret=False)
        pmem = compile_(proto, [((2,), jnp.int32), ((N + 1,), jnp.float32)])
        print(f"{cell['name']}: N={N} S={S} C={shapes[1][0][1]} | "
              f"protocol_call {pmem} | persistent_call {mem}", flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
