"""Self-scheduled persistent grids vs static grids (DESIGN.md Sec. 14).

The question the device subsystem exists to answer: on a *variable-cost*
tile space, does a fixed worker fleet claiming chunks through the device
window beat the static contiguous partition?  Two workloads:

  * mandelbrot -- per-tile cost = total escape iterations (interior tiles
    burn CT per pixel, exterior ones almost nothing);
  * varlen attention -- per-tile cost = kv blocks actually attended
    (seeded variable batch lengths).

CPU CI measures the *modeled makespan* (earliest-free-worker clock over
the real per-tile cost distribution) -- the device-independent signal;
with an accelerator present it additionally times the persistent kernel
against the static grid wall-clock.  ``--smoke`` adds the correctness
asserts CI pins: chunk-sequence parity with the host plan, conservation
to N, and makespan improvement on both workloads.
"""
from __future__ import annotations

import argparse
import time

import numpy as np


def _static_makespan(costs, P: int) -> float:
    """Makespan of the static grid's contiguous equal-count partition."""
    N = len(costs)
    per = -(-N // P)
    return max(float(np.sum(costs[w * per:(w + 1) * per])) for w in range(P))


def _modeled(name: str, costs, P: int, techniques, smoke: bool) -> None:
    from repro.core.chunk_calculus import plan
    from repro.device import claim_schedule, host_spec

    N = len(costs)
    static_ms = _static_makespan(costs, P)
    ideal = float(np.sum(costs)) / P
    print(f"{name}_static_P{P},,makespan={static_ms:.3e} ideal={ideal:.3e}")
    best = None
    for tech in techniques:
        t0 = time.perf_counter()
        sched = claim_schedule(tech, N, P, costs=costs)
        ms = sched.makespan()  # the schedule read back to the host
        us = (time.perf_counter() - t0) * 1e6
        if smoke:
            sizes, starts = plan(host_spec(tech, N, P))
            assert np.array_equal(sched.sizes, sizes), f"{tech}: size parity"
            assert np.array_equal(sched.starts, starts), f"{tech}: start parity"
            assert int(sched.sizes.sum()) == N, f"{tech}: conservation"
        print(f"{name}_{tech}_P{P},{us:.0f},"
              f"makespan={ms:.3e} vs_static={ms / static_ms:.3f} "
              f"claims={sched.n_steps}")
        if best is None or ms < best:
            best = ms
    assert best is not None and best < static_ms, (
        f"{name}: self-scheduling must beat the static partition "
        f"({best:.3e} !< {static_ms:.3e})")


def _accelerated(quick: bool) -> None:
    """Wall-clock persistent vs static on a real device (skipped on CPU)."""
    import jax

    from repro.kernels import (
        flash_attention, flash_attention_persistent, mandelbrot,
        mandelbrot_persistent,
    )

    def t(fn):
        jax.block_until_ready(fn())  # compile
        t0 = time.perf_counter()
        jax.block_until_ready(fn())
        return (time.perf_counter() - t0) * 1e6

    w, ct = (1024, 500) if quick else (4096, 2000)
    us_static = t(lambda: mandelbrot(w, ct=ct))
    us_pers = t(lambda: mandelbrot_persistent(w, ct=ct, workers=8)[0])
    print(f"mandelbrot_wallclock_{w},{us_pers:.0f},static={us_static:.0f} "
          f"speedup={us_static / us_pers:.2f}x")
    assert us_pers < us_static, "persistent mandelbrot must win on device"

    import jax.numpy as jnp
    rng = np.random.default_rng(0)
    B, H, T, D = (4, 8, 2048, 64) if not quick else (2, 4, 1024, 64)
    lengths = rng.integers(T // 8, T + 1, B).astype(np.int32)
    q = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, H, T, D)), jnp.float32)
    us_static = t(lambda: flash_attention(q, k, v, causal=True))
    us_pers = t(lambda: flash_attention_persistent(
        q, k, v, causal=True, lengths=lengths, workers=8)[0])
    print(f"attention_wallclock_T{T},{us_pers:.0f},static={us_static:.0f} "
          f"speedup={us_static / us_pers:.2f}x")
    assert us_pers < us_static, "persistent varlen attention must win on device"


def main(quick: bool = True, smoke: bool = False) -> None:
    import jax

    from repro.kernels import mandelbrot
    from repro.kernels.flash_attention.persistent import varlen_tile_costs
    from repro.kernels.mandelbrot.persistent import mandelbrot_tile_costs

    print("name,us_per_call,derived")
    techniques = ("ss", "gss", "tss", "fac2") if not smoke else \
        ("static", "ss", "gss", "tss", "fac2")
    P = 8

    # mandelbrot: the real escape-count cost surface of a small render
    w, ct, blk = (256, 200, 16) if quick else (1024, 1000, 32)
    counts = np.asarray(mandelbrot(w, ct=ct, block_h=blk, block_w=blk))
    costs = mandelbrot_tile_costs(counts, blk, blk)
    _modeled("mandel", costs, P, [t for t in techniques if t != "static"],
             smoke)

    # varlen attention: seeded skewed batch lengths
    rng = np.random.default_rng(7)
    B, H, T, blk_q, blk_k = (8, 8, 2048, 128, 128) if quick else \
        (16, 16, 8192, 128, 128)
    lengths = rng.integers(T // 16, T + 1, B)
    nq = -(-T // blk_q)
    costs = varlen_tile_costs(lengths, H, nq, blk_q, blk_k, causal=True)
    _modeled("attn_varlen", costs, P,
             [t for t in techniques if t != "static"], smoke)

    if jax.default_backend() != "cpu":
        _accelerated(quick)
    else:
        print("# wall-clock persistent-vs-static comparison needs an "
              "accelerator; modeled makespans above are the CPU CI signal")
    if smoke:
        print("# smoke asserts passed: parity, conservation, makespan win")


if __name__ == "__main__":
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--full", action="store_true", help="larger grids")
    ap.add_argument("--smoke", action="store_true",
                    help="CI mode: add parity/conservation/makespan asserts")
    args = ap.parse_args()
    main(quick=not args.full, smoke=args.smoke)
