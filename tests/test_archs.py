"""Per-architecture smoke tests: reduced config, one forward + train step +
decode step on CPU, asserting output shapes and no NaNs.

The FULL configs are exercised only at the ShapeDtypeStruct level: the
sharding rules below cover every parameter leaf of every architecture.

The model matrix jit-compiles ~2 minutes of models on CPU, so it lives in
the slow tier; the tier-1 suite covers the model plane through the
sharding-rule tests here and the kernel/substrate tests.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, applicable_shapes, get_config
from repro.models import api

B, T = 2, 32


def _batch(cfg, key):
    ks = jax.random.split(key, 3)
    batch = {"tokens": jax.random.randint(ks[0], (B, T), 0, cfg.vocab)}
    if cfg.is_encdec:
        batch["src_embeds"] = api.frontend_stub_embeds(cfg, B, T, ks[1])
    elif cfg.frontend:  # vlm: prefix patch embeddings
        batch["prefix_embeds"] = api.frontend_stub_embeds(
            cfg, B, cfg.n_prefix_tokens, ks[1])
    return batch


def _loss_fn(params, cfg, batch):
    logits = api.forward(params, cfg, batch)
    labels = batch["tokens"]
    logits = logits[:, -labels.shape[1]:]  # drop vlm prefix positions
    lp = jax.nn.log_softmax(logits, axis=-1)
    return -jnp.take_along_axis(lp, labels[..., None], axis=-1).mean()


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_forward_and_train_step(arch):
    cfg = get_config(arch).reduced()
    params = api.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg, jax.random.key(1))

    logits = api.forward(params, cfg, batch)
    total_T = T + (cfg.n_prefix_tokens if cfg.frontend == "vision" else 0)
    assert logits.shape == (B, total_T, cfg.vocab)
    assert bool(jnp.isfinite(logits).all()), "NaN/Inf in forward logits"

    loss, grads = jax.jit(jax.value_and_grad(_loss_fn), static_argnums=1)(
        params, cfg, batch)
    assert bool(jnp.isfinite(loss)), "NaN loss"
    flat = jax.tree.leaves(grads)
    assert all(bool(jnp.isfinite(g).all()) for g in flat), "NaN in grads"
    # a step must change the params
    new = jax.tree.map(lambda p, g: p - 1e-2 * g.astype(p.dtype), params, grads)
    loss2 = _loss_fn(new, cfg, batch)
    assert bool(jnp.isfinite(loss2))


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_smoke_decode(arch):
    cfg = get_config(arch).reduced()
    params = api.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg, jax.random.key(1))
    src_len = T if cfg.is_encdec else None
    cache = api.init_cache(cfg, B, 2 * T, src_len=src_len)
    logits, cache = api.prefill(params, cfg, batch, cache)
    assert logits.shape == (B, cfg.vocab)
    assert bool(jnp.isfinite(logits).all())
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    for _ in range(3):
        logits, cache = api.decode_step(params, cfg, tok, cache)
        assert bool(jnp.isfinite(logits).all())
        tok = jnp.argmax(logits, -1).astype(jnp.int32)


@pytest.mark.slow
@pytest.mark.parametrize("arch", sorted(ARCHS))
def test_decode_consistent_with_forward(arch):
    """Greedy decode logits == teacher-forced forward logits (same prefix)."""
    cfg = get_config(arch).reduced()
    params = api.init_params(jax.random.key(0), cfg)
    batch = _batch(cfg, jax.random.key(1))
    src_len = T if cfg.is_encdec else None
    cache = api.init_cache(cfg, B, 2 * T, src_len=src_len)

    pre = {k: (v[:, : T // 2] if k == "tokens" else v) for k, v in batch.items()}
    lg, cache = api.prefill(params, cfg, pre, cache)
    full = api.forward(params, cfg, pre)
    np.testing.assert_allclose(
        np.asarray(lg), np.asarray(full[:, -1]), atol=2e-3, rtol=2e-3)

    nxt = batch["tokens"][:, T // 2]
    lg2, cache = api.decode_step(params, cfg, nxt, cache)
    pre2 = {k: (batch["tokens"][:, : T // 2 + 1] if k == "tokens" else v)
            for k, v in batch.items()}
    full2 = api.forward(params, cfg, pre2)
    np.testing.assert_allclose(
        np.asarray(lg2), np.asarray(full2[:, -1]), atol=2e-3, rtol=2e-3)


@pytest.mark.slow
def test_shape_cells_cover_assignment():
    """10 archs x shapes: long_500k only for sub-quadratic families."""
    total = sum(len(applicable_shapes(c)) for c in ARCHS.values())
    # 10 archs x 3 universal shapes + 3 sub-quadratic archs (danube/mamba2/
    # zamba2) x long_500k
    assert total == 33
    subq = {n for n, c in ARCHS.items() if "long_500k" in applicable_shapes(c)}
    assert subq == {"h2o-danube-3-4b", "mamba2-370m", "zamba2-2.7b"}


@pytest.mark.slow
def test_param_counts_match_names():
    expect = {
        "qwen3-moe-235b-a22b": (232e9, 0.1), "deepseek-67b": (67e9, 0.05),
        "tinyllama-1.1b": (1.1e9, 0.05), "mamba2-370m": (0.37e9, 0.05),
        "zamba2-2.7b": (2.7e9, 0.15), "stablelm-12b": (12e9, 0.05),
        "h2o-danube-3-4b": (4e9, 0.05),
    }
    for name, (target, tol) in expect.items():
        n = get_config(name).param_count()
        assert abs(n - target) / target < tol + 0.05, (name, n)


@pytest.mark.slow
def test_moe_active_params():
    cfg = get_config("qwen3-moe-235b-a22b")
    assert cfg.active_param_count() < 0.12 * cfg.param_count()


def test_shard_rules_cover_all_archs():
    """Every param leaf of every arch gets a rank-matching PartitionSpec."""
    import jax

    from repro.configs import ARCHS
    from repro.models import api
    from repro.shard import params_pspecs

    for name, cfg in ARCHS.items():
        sds = api.abstract_params(cfg)
        specs = params_pspecs(sds)
        arr_leaves = jax.tree_util.tree_flatten(sds)[0]
        from jax.sharding import PartitionSpec as P

        spec_leaves = jax.tree_util.tree_flatten(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
        assert len(arr_leaves) == len(spec_leaves)
        for a, s in zip(arr_leaves, spec_leaves):
            assert len(s) == a.ndim, (name, a.shape, s)


def test_fix_divisibility_drops_bad_axes():
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P

    from repro.shard import fix_divisibility

    mesh = jax.make_mesh((1,), ("model",))  # model size 1: everything divides
    tree = {"a": jax.ShapeDtypeStruct((7, 8), jnp.float32)}
    fixed = fix_divisibility(tree, {"a": P("model", None)}, mesh)
    assert fixed["a"] == P("model", None)

    # fake a 4-wide axis via test mesh helper semantics
    import numpy as np

    class FakeMesh:
        axis_names = ("model",)
        devices = np.empty((4,), dtype=object)

    fixed = fix_divisibility(tree, {"a": P("model", None)}, FakeMesh())
    assert fixed["a"] == P(None, None)  # 7 % 4 != 0 -> dropped
    fixed = fix_divisibility({"a": jax.ShapeDtypeStruct((8, 7), jnp.float32)},
                             {"a": P("model", None)}, FakeMesh())
    assert fixed["a"] == P("model", None)
