"""DeepSeek-V3's routed experts through the self-scheduled expert kernel.

CPU, interpret mode, seeded random weights at a small size: d = 64,
F = 32, 32 routed experts in 4 groups, top 4 of the best 2 groups, one
shared expert; a chip holds 8 experts.  The program (``layers.moe_route``,
``kernels.moe_experts_persistent``, ``layers.moe_held_block``) is checked
against the float32 reference of ``models/reference.py``.  In float32 the
kernel's matmuls are exact float32 on the CPU, so 1e-4 is rounding only;
the bfloat16 case rounds the operands the kernel is fed and its output.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import ModelConfig
from repro.kernels import moe_experts_persistent
from repro.kernels.moe_experts.persistent import (TB, WIN,
                                                  expert_tile_costs,
                                                  route_pairs, rows_bucket,
                                                  windows)
from repro.models import layers
from repro.models.reference import (experts_reference, moe_gates_reference,
                                    moe_layer_reference)

D, F, E_ALL, K, BLK = 64, 32, 32, 4, 8
CFG = ModelConfig(name="dsv3-tiny", family="moe", n_layers=1, d_model=D,
                  n_heads=0, n_kv_heads=0, d_ff=F, vocab=64,
                  n_experts=E_ALL, top_k=K, n_shared_experts=1, n_group=4,
                  topk_group=2, routed_scaling_factor=2.5, dtype="float32")
HELD = tuple(range(8, 16))  # the second of 4 chips' shares


def _weights(E, dtype=jnp.float32, seed=1):
    p = layers.moe_held_init(jax.random.key(seed), CFG, range(E), dtype)
    return p["wg"], p["wu"], p["wd"]


def _dense(ids, w, T):
    """(T, E_ALL) gates from the router's (T, K) choices."""
    g = np.zeros((T, E_ALL), np.float32)
    np.put_along_axis(g, np.asarray(ids), np.asarray(w, np.float32), axis=1)
    return jnp.asarray(g)


def _hand_routing(T, rng):
    """Held expert 8 gets no token, 9 gets every token, the rest a random
    share whose counts are not multiples of the block; every token picks
    K distinct experts."""
    ids = np.zeros((T, K), np.int32)
    for t in range(T):
        others = rng.choice([e for e in range(E_ALL) if e not in (8, 9)],
                            K - 1, replace=False)
        ids[t] = [9, *others]
    return ids, rng.uniform(0.1, 1.0, (T, K)).astype(np.float32)


@pytest.mark.parametrize("technique,workers",
                         [("gss", 3), ("ss", 2), ("fac2", 4), ("static", 3)])
def test_kernel_matches_the_reference_under_hand_made_routing(technique,
                                                              workers):
    T = 37  # the last row block of every expert is partial
    rng = np.random.default_rng(0)
    ids, w = _hand_routing(T, rng)
    x = jax.random.normal(jax.random.key(2), (T, D), jnp.float32)
    wg, wu, wd = _weights(len(HELD))
    y, sched = moe_experts_persistent(x, wg, wu, wd, ids, w, held=HELD,
                                      technique=technique, workers=workers,
                                      blk=BLK)
    want = experts_reference(x, wg, wu, wd, _dense(ids, w, T), HELD)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4,
                               rtol=1e-4)
    R = -(-T // BLK)
    assert sched.N == len(HELD) * R and int(np.sum(sched.sizes)) == sched.N
    # expert 8's tiles cost nothing; expert 9's hold all T rows
    loads = np.asarray(route_pairs(ids, w, held=HELD)[0])
    assert loads[0] == 0 and loads[1] == T
    assert list(expert_tile_costs(loads, T, BLK)[R:2 * R]) == \
        [8, 8, 8, 8, 5]


def test_route_pairs_sorts_the_held_pairs_by_expert_and_by_block():
    T = TB + 3  # two blocks of tokens, the second of 3
    ids = np.zeros((T, 2), np.int32) + 5  # expert 5 is held by no one here
    ids[[0, 2, TB + 1], 0] = 1
    ids[[2, TB, TB + 2], 1] = 2
    w = np.zeros((T, 2), np.float32)
    w[:, 0], w[:, 1] = 0.25, 0.75
    loads, tok, pw, loc, bounds = route_pairs(ids, w, held=(1, 2, 9))
    assert list(np.asarray(loads)) == [3, 3, 0]
    assert list(np.asarray(tok)[0, :3]) == [0, 2, TB + 1]
    assert list(np.asarray(tok)[1, :3]) == [2, TB, TB + 2]
    assert np.all(np.asarray(tok)[:, 3:] >= T)  # none past the load
    np.testing.assert_allclose(np.asarray(pw)[:2, :3],
                               [[0.25] * 3, [0.75] * 3])
    # per block of TB tokens: where in the block the routed tokens sit
    loc = np.asarray(loc).reshape(3, 2, TB)
    assert list(loc[0, 0, :2]) == [0, 2] and list(loc[0, 1, :1]) == [1]
    assert list(loc[1, 0, :1]) == [2] and list(loc[1, 1, :2]) == [0, 2]
    assert np.asarray(bounds).tolist() == [[0, 2, 3], [0, 1, 3], [0, 0, 0]]
    # 1 + 1 + 0 live tiles of 2 rows, rounded up to 8 tiles
    assert rows_bucket(np.asarray(loads), 2) == 16


@pytest.mark.parametrize("lo,n,want", [(0, 0, 0), (13, 0, 0), (8, 1, 1),
                                       (13, WIN - 5, 1), (13, WIN - 4, 2),
                                       (16, 3 * WIN, 3)])
def test_the_combine_fetches_the_windows_it_waits_for(lo, n, want):
    """An expert with no rows in a block fetches no window, wherever its
    rows start: the interpreter does not hang on a wait that nothing
    signals, the chip does."""
    assert int(windows(jnp.int32(lo), jnp.int32(n))) == want


def test_dropless_under_any_skew_unlike_the_capacity_block():
    """Every token on the same four held experts: the kernel computes all
    4T pairs; ``moe_block`` at capacity factor 1 keeps K*T/E of them."""
    T = 64
    ids = np.tile(np.array([8, 9, 10, 11], np.int32), (T, 1))
    w = np.full((T, K), 0.25, np.float32)
    x = jax.random.normal(jax.random.key(3), (T, D), jnp.float32)
    wg, wu, wd = _weights(len(HELD))
    y, _ = moe_experts_persistent(x, wg, wu, wd, ids, w, held=HELD,
                                  workers=4, blk=BLK)
    want = experts_reference(x, wg, wu, wd, _dense(ids, w, T), HELD)
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), atol=1e-4,
                               rtol=1e-4)
    assert np.all(np.abs(np.asarray(want)).sum(-1) > 0)  # no row dropped
    loads = np.asarray(route_pairs(ids, w, held=HELD)[0])
    assert list(loads) == [T] * 4 + [0] * 4

    # the capacity block under the same skew: its router sends every
    # token to the same experts, and all but capacity of them are dropped
    # (it is dropless up to 256 tokens, so it is given 512)
    cfg = ModelConfig(name="cap", family="moe", n_layers=1, d_model=D,
                      n_heads=0, n_kv_heads=0, d_ff=F, vocab=64,
                      n_experts=8, top_k=K, capacity_factor=1.0,
                      dtype="float32")
    p = layers.moe_init(jax.random.key(4), cfg, jnp.float32)
    p["router"] = jnp.zeros((D, 8)).at[:, :K].set(1.0)
    xs = jnp.abs(jax.random.normal(jax.random.key(9), (1, 512, D)))
    capped = layers.moe_block(p, xs, cfg)[0]  # positive rows: 0..3 win
    C = round(cfg.capacity_factor * K * 512 / 8)
    assert int((np.abs(np.asarray(capped)).sum(-1) == 0).sum()) == 512 - C


def test_router_picks_by_group_then_by_expert_by_hand():
    """8 experts in 4 groups of 2, the best 2 groups, top 3.

    Scores (sigmoid of the logits) of one token, by expert:
        group 0: 0.90 0.10   sum of top 2: 1.00
        group 1: 0.80 0.75            1.55
        group 2: 0.85 0.05            0.90
        group 3: 0.70 0.65            1.35
    The best groups are 1 and 3, so expert 0 (0.90) and expert 4 (0.85),
    the two highest scores, are not chosen: the top 3 are 2, 3 and 6, with
    weights 0.80, 0.75, 0.70 normalized, times the scaling factor 2.
    """
    cfg = ModelConfig(name="hand", family="moe", n_layers=1, d_model=8,
                      n_heads=0, n_kv_heads=0, d_ff=4, vocab=8, n_experts=8,
                      top_k=3, n_group=4, topk_group=2,
                      routed_scaling_factor=2.0, dtype="float32")
    s = np.array([0.90, 0.10, 0.80, 0.75, 0.85, 0.05, 0.70, 0.65])
    logits = np.log(s / (1 - s)).astype(np.float32)
    x = jnp.eye(8, dtype=jnp.float32)[:1]  # the token's row picks logits
    router = jnp.asarray(np.tile(logits, (8, 1)) * np.eye(8)[:, :1])
    ids, w = layers.moe_route(x, router, jnp.zeros(8), cfg)
    assert list(np.asarray(ids[0])) == [2, 3, 6]
    np.testing.assert_allclose(np.asarray(w[0]),
                               2 * np.array([0.80, 0.75, 0.70]) / 2.25,
                               rtol=1e-5)
    gates = moe_gates_reference(x, router, jnp.zeros(8), cfg)
    np.testing.assert_allclose(np.asarray(gates[0]),
                               [0, 0, 0.80 / 1.125, 0.75 / 1.125, 0, 0,
                                0.70 / 1.125, 0], rtol=1e-5)
    # a bias steers the choice, not the weights: lifting expert 1 by 0.9
    # makes group 0 the best (0.90 + 1.00), and the top 3 are then 1, 0
    # and 2, weighted by their scores 0.10, 0.90 and 0.80
    bias = jnp.zeros(8).at[1].set(0.9)
    ids, w = layers.moe_route(x, router, bias, cfg)
    assert sorted(np.asarray(ids[0])) == [0, 1, 2]
    np.testing.assert_allclose(sorted(np.asarray(w[0])),
                               2 * np.array([0.10, 0.80, 0.90]) / 1.80,
                               rtol=1e-5)


@pytest.mark.parametrize("seed", [0, 1, 2])
def test_router_matches_the_reference_gates(seed):
    p = layers.moe_held_init(jax.random.key(seed), CFG, HELD, jnp.float32)
    x = jax.random.normal(jax.random.key(seed + 10), (128, D), jnp.float32)
    p["bias"] = jax.random.normal(jax.random.key(seed + 20), (E_ALL,)) * 0.1
    ids, w = layers.moe_route(x, p["router"], p["bias"], CFG)
    np.testing.assert_allclose(
        np.asarray(_dense(ids, w, 128)),
        np.asarray(moe_gates_reference(x, p["router"], p["bias"], CFG)),
        atol=1e-5)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-4),
                                       (jnp.bfloat16, 3e-2)])
def test_held_block_matches_the_reference_layer(dtype, tol):
    """The model path: route, the held experts through the kernel, the
    shared expert.  In bfloat16 the reference reads the same rounded
    values; the tolerance covers bfloat16 rounding of the hidden
    activations and of three outputs summed (|y| about 2 here)."""
    p = layers.moe_held_init(jax.random.key(5), CFG, HELD, dtype)
    x = jax.random.normal(jax.random.key(6), (2, 48, D)).astype(dtype)
    got = layers.moe_held_block(p, x, CFG, held=HELD, workers=3, blk=BLK)
    want = moe_layer_reference(p, x, CFG, held=HELD)
    assert got.shape == x.shape and got.dtype == dtype
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want), atol=tol, rtol=tol)


def test_the_shares_add_up_to_the_uncut_layer():
    """Four chips hold 8 experts each: their held parts, plus the shared
    expert counted once, give the reference layer over all 32 experts."""
    full = layers.moe_held_init(jax.random.key(7), CFG, range(E_ALL),
                                jnp.float32)
    x = jax.random.normal(jax.random.key(8), (1, 96, D))
    flat = x[0]
    ids, w = layers.moe_route(flat, full["router"], full["bias"], CFG)
    total = layers.mlp_block(full["shared"], flat)
    for chip in range(4):
        held = range(8 * chip, 8 * chip + 8)
        part, _ = moe_experts_persistent(
            flat, full["wg"][held.start:held.stop],
            full["wu"][held.start:held.stop],
            full["wd"][held.start:held.stop], ids, w, held=held, workers=3,
            blk=BLK)
        total = total + part
    want = moe_layer_reference(full, x, CFG, held=range(E_ALL))[0]
    np.testing.assert_allclose(np.asarray(total), np.asarray(want),
                               atol=1e-4, rtol=1e-4)
