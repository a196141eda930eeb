"""Drives DeepSeek-V3's routed experts on one chip's share.

One drain is one MoE layer's routed experts on one batch of tokens, as
the program runs them: ``moe_route`` (the group-limited sigmoid router
over all 256 experts), then ``moe_experts_persistent`` over the 8 held
experts (the held pairs sorted by expert, their counts read back, the
(expert, row-block) tiles claimed in the protocol kernel, the streaming
expert kernel and the combine).  It ends when the (T, d) output is on the
device.  Drain ``i`` runs layer ``i % layers`` on batch ``i % pool``.

Set-up makes, on the device and in bfloat16, the configuration's dtype:

  * the tokens: each is the RMS-normalized ``centre[z] + noise * n`` of a
    topic ``z``, with Zipf-distributed topic counts.  Centres, topics,
    noise and routers come from the traffic's ``data_key``, so every seed
    routes the same tokens to the same experts: the held loads, and the
    work of every drain, are the same for every seed.  The seed orders
    the tokens of each batch;
  * the held experts' weights of each layer, from the seed.

Each of the drains in a cycle of layers and batches is drained once
before the window, which compiles every shape the window will meet.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

from chipbench import moe_counts, moe_reference, reference
from chipbench import traffic as traffic_gen

KERNELS = {"claim": "protocol_call", "route": "route",
           "moe": "persistent_call"}
COMPUTE = "moe"
#: the control's precision: the one below the configuration's bfloat16
CONTROL_DTYPE = "float8_e4m3fn"
#: a token is near a tie when its reference margin (the 4th-5th group
#: score gap or the 8th-9th expert score gap, in sigmoid units) is below
#: this: about 100 times the typical float32 rounding of a 7168-term dot
#: product (sqrt(7168) * 2^-24 on a logit of order 1, a quarter of it in
#: sigmoid units), the one thing the program's router (bf16 products,
#: f32 sums) and the reference's (f32 at highest) do not share
TIE_MARGIN = 1e-4


def topic_counts(T: int, topics: int, zipf: float) -> np.ndarray:
    """Tokens of each topic: ``T * p_z``, ``p_z`` proportional to
    ``(z + 1) ** -zipf``, rounded by largest remainder to sum to T."""
    p = np.arange(1, topics + 1, dtype=np.float64) ** -zipf
    share = T * p / p.sum()
    n = np.floor(share).astype(np.int64)
    n[np.argsort(-(share - n), kind="stable")[:T - n.sum()]] += 1
    return n


def batch_topics(traffic: dict, b: int, T: int) -> np.ndarray:
    """Topic of each token of batch ``b``, from the data key alone."""
    z = np.repeat(np.arange(traffic["topics"]),
                  topic_counts(T, traffic["topics"], traffic["zipf"]))
    return np.random.default_rng([traffic["data_key"], b]).permutation(z)


@functools.partial(jax.jit, static_argnames=("noise",))
def token_chunk(key, centres, topic, *, noise: float):
    """One chunk of token rows: RMS-normalized ``centre[z] + noise * n``."""
    v = centres[topic] + noise * jax.random.normal(
        key, (topic.shape[0], centres.shape[1]), jnp.float32)
    v = v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + 1e-6)
    return v.astype(jnp.bfloat16)


@functools.partial(jax.jit, static_argnames=("noise",))
def _tokens(key, centres, topic, perm, *, noise: float):
    nc = topic.shape[0]
    keys = jax.vmap(lambda c: jax.random.fold_in(key, c))(jnp.arange(nc))
    x = jax.lax.map(lambda a: token_chunk(a[0], centres, a[1], noise=noise),
                    (keys, topic))
    return x.reshape(-1, centres.shape[1])[perm]


def data_keys(traffic: dict, d: int, E_all: int):
    """(centres (topics, d) f32, batch key of b, router of layer l), all
    from the traffic's data key."""
    key = jax.random.key(traffic["data_key"])
    centres = jax.random.normal(jax.random.fold_in(key, 0),
                                (traffic["topics"], d), jnp.float32)

    def batch_key(b):
        return jax.random.fold_in(key, 1 + b)

    def router(l):
        k = jax.random.fold_in(key, 1000 + l)
        return (jax.random.normal(k, (d, E_all), jnp.float32)
                * d ** -0.5).astype(jnp.bfloat16)

    return centres, batch_key, router


@functools.partial(jax.jit, static_argnames=("E", "d", "F"))
def _experts(key, *, E, d, F):
    kg, ku, kd = jax.random.split(key, 3)
    bf16 = jnp.bfloat16
    return (jax.random.normal(kg, (E, d, F), bf16) * bf16(d ** -0.5),
            jax.random.normal(ku, (E, d, F), bf16) * bf16(d ** -0.5),
            jax.random.normal(kd, (E, F, d), bf16) * bf16(F ** -0.5))


@functools.partial(jax.jit, static_argnames=("E_all",))
def chosen_experts(ids, *, E_all):
    """(T, E_all) bool: the experts each token chose."""
    return (ids[:, :, None] == jnp.arange(E_all)).any(1)


@jax.jit
def _routing_readings(chosen, w_ref, margin, y, y_ref):
    """(mismatches off a tie, mismatches near one, max |y - y_ref| over
    the tokens whose choice agrees with the reference's)."""
    diff = jnp.any(chosen != (w_ref > 0), axis=1)
    near = margin < TIE_MARGIN
    err = jnp.abs(y.astype(jnp.float32) - y_ref).max(axis=1)
    return (jnp.sum(diff & ~near), jnp.sum(diff & near),
            jnp.max(jnp.where(diff, 0.0, err)))


def rehearsal(cfg: dict, traffic: dict, claim_width):
    """(N, program, argument shapes) of the kernel module the window
    drives, for ``rehearse.py`` to compile without the chip.

    ``claim_width(N, costs)`` gives the claim tables' width, here the
    widest over the held loads the traffic file records.
    """
    from repro.kernels.moe_experts import persistent as moe

    T, d, F = traffic["tokens"], cfg["hidden_size"], \
        cfg["moe_intermediate_size"]
    blk, P = traffic["block"], cfg["workers"]
    E = cfg["n_routed_experts"]
    N = E * -(-T // blk)
    loads = traffic["held_loads"]
    C = max(claim_width(N, moe.expert_tile_costs(n, T, blk)) for n in loads)
    C = max(8, 1 << (C - 1).bit_length())  # as the entry pads it
    M = max(moe.rows_bucket(n, blk) for n in loads)
    prog = functools.partial(moe.persistent_call, M=M, blk=blk,
                             interpret=False)
    i32, bf16 = jnp.int32, jnp.bfloat16
    nb = -(-T // moe.TB)
    return N, prog, [((P,), i32), ((P, C), i32), ((P, C), i32), ((E,), i32),
                     ((E, T), i32), ((E, T), jnp.float32),
                     ((E, nb * moe.TB), i32), ((E, nb + 1), i32),
                     ((T, d), bf16), ((E, d, F), bf16), ((E, d, F), bf16),
                     ((E, F, d), bf16)]


def model_config(cfg: dict):
    """The program's ``ModelConfig`` of the configuration's MoE layer."""
    from repro.configs.base import ModelConfig

    if (cfg["scoring_func"], cfg["topk_method"], cfg["norm_topk_prob"]) \
            != ("sigmoid", "noaux_tc", True):
        raise ValueError("the program routes DeepSeek-V3's way only")
    return ModelConfig(
        name="deepseek-v3-moe", family="moe",
        n_layers=cfg["num_hidden_layers"], d_model=cfg["hidden_size"],
        n_heads=cfg["num_attention_heads"],
        n_kv_heads=cfg["num_key_value_heads"],
        d_ff=cfg["moe_intermediate_size"], vocab=cfg["vocab_size"],
        n_experts=cfg["published"]["n_routed_experts"],
        top_k=cfg["num_experts_per_tok"],
        n_shared_experts=cfg["n_shared_experts"], n_group=cfg["n_group"],
        topk_group=cfg["topk_group"],
        routed_scaling_factor=cfg["routed_scaling_factor"],
        dtype=cfg["torch_dtype"], source=cfg["source"])


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 interpret: bool = False):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.interpret = interpret
        self.d, self.F = cfg["hidden_size"], cfg["moe_intermediate_size"]
        lo, hi = cfg["held_experts"]
        self.held = tuple(range(lo, hi))
        self.E_all = cfg["published"]["n_routed_experts"]
        self.layers = cfg["num_hidden_layers"]
        self.P = cfg["workers"]
        self.T, self.blk = traffic["tokens"], traffic["block"]
        self.technique = traffic["technique"]
        self.pool = int(traffic["pool"])
        self.cycle = math.lcm(self.pool, self.layers)
        self.N = len(self.held) * -(-self.T // self.blk)

    def setup(self) -> None:
        # the program's entry points: a program without them fails here
        from repro.kernels import moe_experts_persistent
        from repro.models.layers import moe_route

        self._experts_fn, self._route_fn = moe_experts_persistent, moe_route
        self.mcfg = model_config(self.cfg)
        centres, batch_key, router = data_keys(self.traffic, self.d,
                                               self.E_all)
        chunk = min(self.traffic["chunk"], self.T)
        r = traffic_gen.rng(self.seed)
        self.x = []
        for b in range(self.pool):
            topic = batch_topics(self.traffic, b, self.T).reshape(-1, chunk)
            self.x.append(jax.block_until_ready(_tokens(
                batch_key(b), centres, jnp.asarray(topic, jnp.int32),
                jnp.asarray(r.permutation(self.T), jnp.int32),
                noise=float(self.traffic["noise"]))))
        del centres
        self.routers = [router(l) for l in range(self.layers)]
        self.bias = jnp.zeros(self.E_all, jnp.float32)
        wkey = jax.random.key(traffic_gen.device_key_seed(self.seed))
        self.weights = [jax.block_until_ready(_experts(
            jax.random.fold_in(wkey, l), E=len(self.held), d=self.d,
            F=self.F)) for l in range(self.layers)]
        held = jnp.asarray(self.held)
        self.loads = []
        for i in range(self.cycle):
            (_, ids), _ = self.drain(i)
            self.loads.append(np.asarray(
                (ids[:, :, None] == held).any(1).sum(0)))

    def drain(self, i: int):
        """((output, router's choices), schedule record) of drain ``i``."""
        l, p = i % self.layers, i % self.pool
        x = self.x[p]
        ids, w = self._route_fn(x, self.routers[l], self.bias, self.mcfg)
        y, sched = self._experts_fn(
            x, *self.weights[l], ids, w, held=self.held,
            technique=self.technique, workers=self.P, blk=self.blk,
            interpret=self.interpret)
        jax.block_until_ready(y)
        return (y, ids), (sched.starts, sched.sizes, sched.slab, 1)

    def work(self, i: int) -> dict:
        """Useful FLOPs and bytes of drain ``i`` (``moe_counts.py``)."""
        loads = self.loads[i % self.cycle]
        return {"flops": moe_counts.expert_flops(loads, self.d, self.F),
                "bytes": moe_counts.expert_bytes(loads, self.d, self.F,
                                                 itemsize=2)}

    def readings(self, i: int, chosen, y) -> dict:
        """Drain ``i``'s choices (T, E_all) and output against the float32
        reference of the drain's tokens, router and weights:

        ``routing_mismatches``: tokens whose choice of experts differs
        from the reference's, off a tie (``TIE_MARGIN``);
        ``routing_tie_mismatches``: the same among tokens near one;
        ``max_abs_err``: the largest |y - reference| over the tokens whose
        choice agrees.  The choice of all 8 experts is compared, not only
        of the held ones: the others set the held weights too.
        """
        l, p = i % self.layers, i % self.pool
        w_ref, margin, y_ref = self._reference(self.x[p], self.routers[l],
                                               self.weights[l])
        off, near, err = _routing_readings(chosen, w_ref, margin, y, y_ref)
        return {"routing_mismatches": int(off),
                "routing_tie_mismatches": int(near),
                "max_abs_err": float(err)}

    def compare(self, kept: dict) -> dict:
        """{drain: readings} of the kept drains (``readings``)."""
        return {i: self.readings(i, chosen_experts(ids, E_all=self.E_all), y)
                for i, (_, (y, ids)) in kept.items()}

    def control(self, i: int, dtype) -> dict:
        """The readings of the reference in the program's place: its
        tokens, router and expert weights rounded to ``dtype``, its output
        to bfloat16; the reference it is read against keeps them whole."""
        l, p = i % self.layers, i % self.pool
        x, router, *weights = [
            reference.rounded(a, dtype).astype(jnp.float32)
            for a in (self.x[p], self.routers[l], *self.weights[l])]
        w_ctl, _, y_ctl = self._reference(x, router, weights)
        return self.readings(i, w_ctl > 0,
                             reference.rounded(y_ctl, jnp.bfloat16))

    def _reference(self, x, router, weights):
        """The reference's ``(routing weights, tie margins, held part)``."""
        cfg = self.cfg
        w, margin = moe_reference.route(
            x, router, self.bias, n_group=cfg["n_group"],
            topk_group=cfg["topk_group"], top_k=cfg["num_experts_per_tok"],
            scaling=cfg["routed_scaling_factor"])
        lo, hi = cfg["held_experts"]
        return w, margin, moe_reference.held_part(x, *weights, w[:, lo:hi])
