#!/usr/bin/env python3
"""Smoke run of the main path on a TPU, through the entry points users call.

    python chip_smoke.py [--seed 0]     # one chip: phases a-e
    python chip_smoke.py --chips 4      # four chips: the device-window
                                        # hierarchy and its host reference

One chip, one process.  Phases, each printing what it checked:

  a  the device is a TPU (exits non-zero otherwise, printing no result);
  b  the paper's Mandelbrot (1152^2, CT=1000, 128^2 tiles: N=81) scheduled
     on the device by ``dls.loop(..., runtime="device")`` for every
     device technique: the granted chunks equal the host ``plan()`` index
     for index, and the persistent kernel run on that schedule equals the
     static grid exactly;
  c  self-scheduled varlen attention in tinyllama's head layout vs the
     float32 oracle, next to the static kernel;
  d  PSIA spin images vs their oracle, exactly;
  e  tinyllama-1.1b at its published widths (seeded random weights)
     served by ``Engine.generate``, its prefill logits checked against a
     float32 ``api.forward``.

Every kernel runs compiled (``interpret=False``); the run fails if any
kernel call resolved to interpret mode.  Seconds printed are host wall
clock around calls that end in ``block_until_ready``: ``first_s`` includes
compilation, ``warm_s`` is the same call again.  The last line of stdout
is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent

# phase b: the paper's Mandelbrot (benchmarks/fig5_mandelbrot.py)
MANDEL_WIDTH, MANDEL_CT, TILE, WORKERS = 1152, 1000, 128, 8
DEVICE_TECHNIQUES = ("static", "ss", "gss", "tss", "fac2")
REF_MISMATCH_BOUND = 0.005  # chaotic boundary pixels (tests/test_kernels.py)
# phase c: tinyllama's head layout; B=3 at T=2048 is the most the
# persistent kernel's whole-array blocks fit in a v5e's VMEM
VARLEN_B, VARLEN_T, HEADS, KV_HEADS, HEAD_DIM = 3, 2048, 32, 4, 64
ATTN_ATOL = 3e-2  # bf16 output rounding of |o| <~ 4 (tests/test_kernels.py)
# phase d
SPIN_POINTS, SPIN_IMAGES, SPIN_BIN = 8192, 2048, 0.5
# phase e
ARCH, SERVE_BATCH, PROMPT_LEN, NEW_TOKENS = "tinyllama-1.1b", 4, 128, 16
# bf16 serving path vs a float32 forward (float32 matmuls): measured 0.0067
# at 2 and 0.011 at 6 full-width layers on the CPU, growing ~sqrt(depth)
LOGITS_REL_L2 = 0.05
# --chips 4
HIER_N, HIER_P, HIER_NODES = 4096, 8, 4


def _say(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def _timed(fn):
    """(result, seconds) of ``fn()`` run to completion on the device."""
    import jax

    t0 = time.perf_counter()
    out = jax.block_until_ready(fn())
    return out, time.perf_counter() - t0


def _twice(fn):
    """(result, first_s, warm_s): the first call compiles."""
    _, first = _timed(fn)
    out, warm = _timed(fn)
    return out, first, warm


def phase_device(chips: int):
    import jax

    devs = jax.devices()
    d = devs[0]
    if d.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU (JAX found {d.platform!r})")
    if len(devs) < chips:
        raise SystemExit(f"chip_smoke: {chips} chips asked, {len(devs)} found")
    from repro import kernels

    assert kernels.resolve_interpret(None) is False
    kernels.interpreted_calls = 0
    _say("a", f"platform={d.platform} kind={d.device_kind} count={len(devs)}")
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def phase_mandelbrot():
    import numpy as np

    from repro import dls
    from repro.core.chunk_calculus import plan
    from repro.device import host_spec
    from repro.kernels import mandelbrot, mandelbrot_persistent, mandelbrot_ref
    from repro.kernels.mandelbrot.persistent import mandelbrot_tile_costs

    W, CT = MANDEL_WIDTH, MANDEL_CT
    static, first, warm = _twice(lambda: mandelbrot(
        W, ct=CT, block_h=TILE, block_w=TILE, interpret=False))
    static = np.asarray(static)
    _say("b", f"static grid {W}x{W} CT={CT}: first_s={first:.3f} "
              f"warm_s={warm:.3f}")
    costs = mandelbrot_tile_costs(static, TILE, TILE)
    N = len(costs)

    for t in DEVICE_TECHNIQUES:
        def drain():
            s = dls.loop(N, technique=t, P=WORKERS, runtime="device")
            s.execute(None, executor="device", costs=costs, interpret=False)
            return s

        s, first, warm = _twice(drain)
        sched = s.runtime.schedule
        sizes, starts = plan(host_spec(t, N, WORKERS))
        assert np.array_equal(sched.sizes, sizes), (t, sched.sizes, sizes)
        assert np.array_equal(sched.starts, starts), (t, sched.starts, starts)
        assert int(sched.sizes.sum()) == N
        _, lp_slot = s.runtime.counter_slots()
        lp = int(np.asarray(s.runtime.window.slab())[lp_slot])
        assert lp >= N, (t, lp)

        (out, _), pfirst, pwarm = _twice(lambda: mandelbrot_persistent(
            W, ct=CT, block_h=TILE, block_w=TILE, workers=WORKERS,
            schedule=sched, interpret=False))
        assert np.array_equal(np.asarray(out), static), t
        _say("b", f"{t}: {sched.n_steps} chunks == host plan, sum={N}, "
                  f"slab lp={lp}>=N; persistent == static exactly | "
                  f"claim first_s={first:.3f} warm_s={warm:.3f} "
                  f"persistent first_s={pfirst:.3f} warm_s={pwarm:.3f}")

    ref = np.asarray(mandelbrot_ref(W, ct=CT))
    share = float((static != ref).mean())
    assert share < REF_MISMATCH_BOUND, share
    _say("b", f"static vs mandelbrot_ref: mismatch share {share:.6f} "
              f"< {REF_MISMATCH_BOUND}")


def phase_attention(seed: int):
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import (attention_oracle, flash_attention,
                               flash_attention_persistent)

    B, T = VARLEN_B, VARLEN_T
    rng = np.random.default_rng(seed)
    # heavy-tailed lengths, one row at the full extent
    lengths = np.minimum(T, 64 + (rng.pareto(1.0, B) * 256).astype(np.int64))
    lengths[0] = T
    lengths = lengths.astype(np.int32)
    kq, kk, kv = jax.random.split(jax.random.key(seed), 3)
    q = jax.random.normal(kq, (B, HEADS, T, HEAD_DIM), jnp.bfloat16)
    k = jax.random.normal(kk, (B, KV_HEADS, T, HEAD_DIM), jnp.bfloat16)
    v = jax.random.normal(kv, (B, KV_HEADS, T, HEAD_DIM), jnp.bfloat16)

    (pers, sched), pfirst, pwarm = _twice(lambda: flash_attention_persistent(
        q, k, v, lengths=lengths, causal=True, technique="gss",
        workers=WORKERS, interpret=False))
    stat, sfirst, swarm = _twice(lambda: flash_attention(
        q, k, v, causal=True, interpret=False))
    with jax.default_matmul_precision("float32"):
        ref = np.asarray(attention_oracle(
            q.astype(jnp.float32), k.astype(jnp.float32),
            v.astype(jnp.float32), causal=True))
    assert int(sched.sizes.sum()) == sched.N
    pers = np.asarray(pers, np.float32)
    stat = np.asarray(stat, np.float32)
    # causal rows below a row's length never see a column past it, so the
    # dense oracle over the padded batch is the reference for those rows
    errs = []
    for b, L in enumerate(lengths):
        errs.append(max(float(np.abs(pers[b, :, :L] - ref[b, :, :L]).max()),
                        float(np.abs(stat[b, :, :L] - ref[b, :, :L]).max())))
    err = max(errs)
    assert np.isfinite(err) and err <= ATTN_ATOL, errs
    _say("c", f"B={B} H={HEADS} Hkv={KV_HEADS} T={T} D={HEAD_DIM} bf16 "
              f"lengths={lengths.tolist()}: persistent ({sched.n_steps} "
              f"chunks, N={sched.N}) and static vs f32 oracle max|err|="
              f"{err:.4g} <= {ATTN_ATOL} | persistent first_s={pfirst:.3f} "
              f"warm_s={pwarm:.3f} static first_s={sfirst:.3f} "
              f"warm_s={swarm:.3f}")


def phase_spin_images(seed: int):
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import spin_images, spin_images_oracle

    rng = np.random.default_rng(seed)
    pts = rng.normal(size=(SPIN_POINTS, 3)).astype(np.float32)
    nrm = rng.normal(size=(SPIN_POINTS, 3)).astype(np.float32)
    nrm /= np.linalg.norm(nrm, axis=1, keepdims=True)
    pts, nrm = jnp.asarray(pts), jnp.asarray(nrm)
    got, first, warm = _twice(lambda: spin_images(
        pts, nrm, SPIN_IMAGES, bin_size=SPIN_BIN, interpret=False))
    want = spin_images_oracle(pts, nrm, SPIN_IMAGES, bin_size=SPIN_BIN)
    got, want = np.asarray(got), np.asarray(want)
    assert want.sum() > 0
    assert np.array_equal(got, want), (
        f"{int((got != want).sum())} of {got.size} bins differ")
    _say("d", f"{SPIN_POINTS} points, {SPIN_IMAGES} images, bin "
              f"{SPIN_BIN}: kernel == oracle exactly ({int(want.sum())} "
              f"hits) | first_s={first:.3f} warm_s={warm:.3f}")


def phase_serve(seed: int):
    import dataclasses

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs import get_config
    from repro.models import api
    from repro.serve import Engine

    cfg = get_config(ARCH)
    params = api.init_params(jax.random.key(seed), cfg)
    eng = Engine(cfg, params, batch_size=SERVE_BATCH)
    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, cfg.vocab, size=(SERVE_BATCH, PROMPT_LEN))
    prompts = prompts.astype(np.int32)

    (logits, _), pfirst, pwarm = _twice(
        lambda: eng.prefill(prompts, NEW_TOKENS))
    toks, gfirst, gwarm = _twice(lambda: eng.generate(prompts, NEW_TOKENS))
    logits = np.asarray(logits, np.float32)
    assert toks.shape == (SERVE_BATCH, NEW_TOKENS)
    assert ((toks >= 0) & (toks < cfg.vocab)).all()
    assert np.isfinite(logits).all()
    assert np.array_equal(toks[:, 0], logits.argmax(-1))

    cfg32 = dataclasses.replace(cfg, dtype="float32")
    p32 = jax.tree.map(lambda x: x.astype(jnp.float32), params)
    fwd = jax.jit(lambda p, t: api.forward(p, cfg32, {"tokens": t})[:, -1])
    with jax.default_matmul_precision("float32"):
        ref, ffirst = _timed(lambda: fwd(p32, jnp.asarray(prompts)))
    ref = np.asarray(ref)
    rel = float(np.linalg.norm(logits - ref) / np.linalg.norm(ref))
    assert rel < LOGITS_REL_L2, rel
    _say("e", f"{cfg.name} L={cfg.n_layers} d={cfg.d_model} "
              f"H={cfg.n_heads}/{cfg.n_kv_heads} ff={cfg.d_ff} "
              f"vocab={cfg.vocab} {cfg.dtype}, {cfg.param_count() / 1e9:.3f}B "
              f"params: generate {toks.shape} tokens in [0, vocab), logits "
              f"finite; prefill vs f32 forward rel L2={rel:.4g} < "
              f"{LOGITS_REL_L2} (XLA attention: the kernel is not taken "
              f"with a cache) | prefill first_s={pfirst:.3f} "
              f"warm_s={pwarm:.3f} generate first_s={gfirst:.3f} "
              f"warm_s={gwarm:.3f} f32 forward first_s={ffirst:.3f}")


def phase_hierarchy():
    import jax
    import numpy as np

    from repro import dls
    from repro.core.rma import HierarchicalWindow, ThreadWindow
    from repro.launch.mesh import make_device_hierarchy

    def drain(window):
        s = dls.loop(HIER_N, technique="gss", P=HIER_P,
                     runtime="hierarchical", nodes=HIER_NODES, window=window)
        return s.execute(None, executor="serial")

    devs = jax.devices()[:HIER_NODES]
    hw = make_device_hierarchy()
    assert hw.nodes == HIER_NODES, hw.nodes
    (rep, t_dev) = _timed(lambda: drain(hw))
    for node, w in enumerate(hw.local_windows):
        assert w.slab().devices() == {devs[node]}, (node, w.slab().devices())
    host = HierarchicalWindow(
        HIER_NODES, global_window=ThreadWindow(),
        local_windows=[ThreadWindow() for _ in range(HIER_NODES)])
    ref = drain(host)

    def claims(r):
        return [[(c.start, c.size) for c in per] for per in r.per_pe_claims]

    assert claims(rep) == claims(ref)
    assert int(rep.per_pe_iters.sum()) == HIER_N
    n = sum(len(per) for per in rep.per_pe_claims)
    _say("4", f"gss N={HIER_N} P={HIER_P} over {HIER_NODES} device windows "
              f"({[str(d) for d in devs]}): each slab on its own device; "
              f"{n} claims == host ThreadWindow hierarchy; global RMWs "
              f"{rep.n_rmw_global}, local {rep.n_rmw_local} | "
              f"drain_s={t_dev:.3f}")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4: run only the four-chip device-window check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    src = ROOT / "src"
    if not (src / "repro").is_dir():
        print(f"chip_smoke: no repro package under {src}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(src))
    from repro.launch.cache import enable_compile_cache

    cache_dir = Path(enable_compile_cache())
    device = phase_device(args.chips)
    if args.chips == 4:
        phase_hierarchy()
    else:
        phase_mandelbrot()
        phase_attention(args.seed)
        phase_spin_images(args.seed)
        phase_serve(args.seed)

    from repro import kernels

    assert kernels.interpreted_calls == 0, kernels.interpreted_calls
    entries = sum(1 for _ in cache_dir.iterdir()) if cache_dir.is_dir() else 0
    _say("cache", f"{cache_dir}: {entries} entries")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
