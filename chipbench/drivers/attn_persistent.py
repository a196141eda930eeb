"""Drives self-scheduled varlen causal attention.

One drain is one call of ``flash_attention_persistent(q, k, v,
lengths=..., causal=True, technique=..., workers=P)``: the program builds
its tile cost model, claims the tile space in the protocol kernel and
runs the persistent attention kernel on that schedule.  It ends when the
output is on the device.

Set-up draws the traffic's pool of batches from the seed: row lengths on
the host (``traffic.py``), and q, k and v on the device in one jitted
call, in bfloat16, the precision the configuration serves.  Each batch is
drained once before the window, which compiles every claim-table width
the window will meet.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from chipbench import counts, reference, traffic as traffic_gen

KERNELS = {"claim": "protocol_call", "attn": "persistent_call"}
COMPUTE = "attn"
#: the control's precision: the one below the configuration's bfloat16
CONTROL_DTYPE = "float8_e4m3fn"


def rehearsal(cfg: dict, traffic: dict, claim_width):
    """(N, program, argument shapes) of the compute kernel the window
    drives, for ``rehearse.py`` to compile without the chip.

    ``claim_width(N, costs)`` gives the claim tables' width, here under
    the program's own tile cost model with every row at full length.
    """
    import numpy as np

    from repro.kernels.flash_attention import persistent as attn

    B, T = traffic["batch"], cfg["max_position_embeddings"]
    H, Hkv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    D, blk, P = cfg["head_dim"], traffic["block"], cfg["workers"]
    nq = T // blk
    N = B * H * nq
    C = claim_width(N, attn.varlen_tile_costs(np.full(B, T), H, nq, blk,
                                               blk, True))
    prog = functools.partial(attn.persistent_call, causal=True,
                             scale=D ** -0.5, blk_q=blk, blk_k=blk,
                             interpret=False)
    i32, bf16 = jnp.int32, jnp.bfloat16
    return N, prog, [((P,), i32), ((P, C), i32), ((P, C), i32), ((B,), i32),
                     ((B, H, T, D), bf16), ((B, Hkv, T, D), bf16),
                     ((B, Hkv, T, D), bf16)]


@functools.partial(jax.jit, static_argnames=("pool", "shape_q", "shape_kv"))
def _make_pool(key, *, pool, shape_q, shape_kv):
    out = []
    for k in jax.random.split(key, pool):
        kq, kk, kv = jax.random.split(k, 3)
        out.append((jax.random.normal(kq, shape_q, jnp.bfloat16),
                    jax.random.normal(kk, shape_kv, jnp.bfloat16),
                    jax.random.normal(kv, shape_kv, jnp.bfloat16)))
    return tuple(out)


@jax.jit
def _live_max_err(out_row, ref, length):
    """max |out - ref| over the queries below ``length``: (H, T, D) rows."""
    live = jnp.arange(ref.shape[1]) < length
    err = jnp.abs(out_row.astype(jnp.float32) - ref)
    return jnp.max(jnp.where(live[None, :, None], err, 0.0))


class Driver:
    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 interpret: bool = False):
        self.cfg, self.traffic, self.seed = cfg, traffic, seed
        self.interpret = interpret
        self.H = cfg["num_attention_heads"]
        self.Hkv = cfg["num_key_value_heads"]
        self.D = cfg["head_dim"]
        self.T = cfg["max_position_embeddings"]
        self.P = cfg["workers"]
        self.B = traffic["batch"]
        self.blk = traffic["block"]
        self.technique = traffic["technique"]
        self.pool = int(traffic["pool"])
        self.N = self.B * self.H * -(-self.T // self.blk)

    def setup(self) -> None:
        self.lengths = traffic_gen.pool_lengths(self.traffic, self.seed)
        key = jax.random.key(traffic_gen.device_key_seed(self.seed))
        self.data = jax.block_until_ready(_make_pool(
            key, pool=self.pool, shape_q=(self.B, self.H, self.T, self.D),
            shape_kv=(self.B, self.Hkv, self.T, self.D)))
        for i in range(self.pool):
            self.drain(i)

    def drain(self, i: int):
        """(output, schedule record) of one batch, drained."""
        from repro.kernels import flash_attention_persistent

        p = i % self.pool
        q, k, v = self.data[p]
        out, sched = flash_attention_persistent(
            q, k, v, lengths=self.lengths[p], causal=True,
            technique=self.technique, workers=self.P, blk_q=self.blk,
            blk_k=self.blk, interpret=self.interpret)
        jax.block_until_ready(out)
        return out, (sched.starts, sched.sizes, sched.slab, 1)

    def work(self, i: int) -> dict:
        """Useful FLOPs and bytes of drain ``i`` (``counts.py``)."""
        L = self.lengths[i % self.pool]
        return {"flops": counts.causal_attention_flops(L, self.H, self.D),
                "bytes": counts.attention_bytes(L, self.H, self.Hkv, self.D,
                                                itemsize=2)}

    def row_error(self, p: int, b: int, out_row) -> float:
        """max |out - float32 reference| over the live queries of row
        ``b`` of batch ``p``."""
        q, k, v = (x[b] for x in self.data[p])
        ref = reference.causal_attention_row(q, k, v)
        return float(_live_max_err(out_row, ref, int(self.lengths[p][b])))

    def compare(self, kept: dict) -> dict:
        """{drain: readings} of the kept drains' outputs.

        ``max_abs_err``: the largest |output - float32 reference| over the
        queries below each row's length.
        """
        return {i: {"max_abs_err": max(self.row_error(p, b, out[b])
                                       for b in range(self.B))}
                for i, (p, out) in kept.items()}

    def control(self, p: int, dtype) -> float:
        """The reading of the reference in the program's place, its inputs
        in ``dtype``, its output in bfloat16."""
        errs = []
        for b in range(self.B):
            q, k, v = (reference.rounded(x[b], dtype) for x in self.data[p])
            ctl = reference.rounded(
                reference.causal_attention_row(q, k, v), jnp.bfloat16)
            errs.append(self.row_error(p, b, ctl))
        return max(errs)
