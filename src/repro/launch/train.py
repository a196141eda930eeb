"""Training driver.

Single-host (CPU or one accelerator process):
    PYTHONPATH=src python -m repro.launch.train --arch tinyllama-1.1b \
        --reduced --steps 100 --batch 8 --seq 128 --ckpt /tmp/ck

Multi-host deployment notes (real cluster):
  * run one process per host with jax.distributed.initialize(); the DLS
    sampler then uses the KVStoreWindow automatically (window="auto"),
  * add --mesh to shard params/steps over the local device mesh.
The dry-run (dryrun.py) is the scale-validation path for the 512-chip mesh.
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCHS, get_config
from repro.launch.cache import enable_compile_cache
from repro.optim import AdamWConfig
from repro.train import TrainConfig, Trainer


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="tinyllama-1.1b", choices=sorted(ARCHS))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced same-family config (CPU-trainable)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--samples", type=int, default=100_000)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--technique", default="fac2",
                    help="DLS technique for the data sampler")
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--remat", default="none", choices=["none", "dots", "full"])
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--hosts", type=int, default=1)
    ap.add_argument("--host-id", type=int, default=0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    print(f"[train] {cfg.name}: {cfg.param_count()/1e6:.1f}M params, "
          f"{jax.device_count()} device(s)")

    tcfg = TrainConfig(
        steps=args.steps, per_host_batch=args.batch, seq_len=args.seq,
        n_samples=args.samples, n_hosts=args.hosts, host_id=args.host_id,
        technique=args.technique, microbatches=args.microbatches,
        remat=args.remat, ckpt_dir=args.ckpt, ckpt_every=args.ckpt_every,
        seed=args.seed,
    )
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps)
    trainer = Trainer(cfg, tcfg, opt)
    trainer.run()
    print(f"[train] done: final loss {trainer.history[-1]:.4f} "
          f"(first {trainer.history[0]:.4f})")


if __name__ == "__main__":
    main()
