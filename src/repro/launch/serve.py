"""Serving driver: batched generation + DLS continuous-batching stats.

    PYTHONPATH=src python -m repro.launch.serve --arch tinyllama-1.1b \
        --requests 64 --batch 8

Published widths by default; ``--reduced`` serves the reduced
same-family config (CPU-sized).
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from repro.configs import ARCHS, get_config
from repro.launch.cache import enable_compile_cache
from repro.models import api
from repro.serve import ContinuousBatcher, Engine, Request


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-sized)")
    ap.add_argument("--requests", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--technique", default="gss")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch).reduced() if args.reduced else get_config(args.arch)
    params = api.init_params(jax.random.key(args.seed), cfg)
    eng = Engine(cfg, params, batch_size=args.batch)
    rng = np.random.default_rng(args.seed)

    # one real batched generation (throughput probe)
    prompts = rng.integers(0, cfg.vocab, size=(args.batch, 16)).astype(np.int32)
    t0 = time.perf_counter()
    out = eng.generate(prompts, max_new=args.max_new)
    dt = time.perf_counter() - t0
    print(f"[serve] generated {out.shape} in {dt:.2f}s "
          f"({args.batch*args.max_new/dt:.1f} tok/s)")

    # DLS continuous-batching admission vs static split (simulated clock,
    # heavy-tailed generation lengths -- the variable-cost loop of serving)
    lens = (rng.pareto(1.5, size=args.requests) * 20 + 4).astype(int)
    reqs = [Request(rid=i, prompt=np.zeros(8, np.int32), max_new=int(l))
            for i, l in enumerate(lens)]

    def cost(chunk, worker):
        return float(sum(0.01 * r.max_new + 0.02 for r in chunk))

    cb = ContinuousBatcher(n_workers=args.batch, technique=args.technique)
    t_dls = cb.schedule(reqs, cost)
    t_static = cb.schedule(reqs, cost, static=True)
    print(f"[serve] makespan: DLS({args.technique})={t_dls.max():.2f}s "
          f"static={t_static.max():.2f}s  "
          f"p99 latency: {np.percentile(t_dls,99):.2f}s vs "
          f"{np.percentile(t_static,99):.2f}s")


if __name__ == "__main__":
    main()
