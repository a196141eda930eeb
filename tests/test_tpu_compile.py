"""The main-path kernels compile for a TPU v5e chip, at their real sizes.

The chip is described, not attached: ``get_topology_desc`` gives a
``v5e:2x2`` host and each kernel's jittable core is compiled ahead of time
for one of its chips, with the TPU's own compiler.  That catches what the
interpreter cannot: scalar stores to vector memory, unaligned dynamic
slices, loop-carry layouts, VMEM capacity.  Nothing runs, so these tests
say nothing about results or times.

The topology is described inside a fixture, never while a module is
imported: only one process at a time may load the TPU library, and it
keeps it until it exits.
"""
import functools
import os
import re

import pytest

N_TILES, P = 81, 8            # the paper's 1152^2 Mandelbrot in 128^2 tiles
WIDTH, CT = 1152, 1000
VARLEN_B, T = 3, 2048         # the largest tinyllama varlen batch in VMEM
HEADS, KV_HEADS, HEAD_DIM = 32, 4, 64
# DeepSeek-V3's routed experts: one chip's 8, 65536 tokens a drain
MOE_D, MOE_F, MOE_E, MOE_T, MOE_BLK = 7168, 2048, 8, 65536, 256


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 -- any failure means "no TPU compiler"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a TPU compile cannot be read back without the chip: keep it out of
    # any persistent cache the environment configured
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _compile(one_chip, fn, *shapes):
    """Compile ``fn`` for the described chip; returns its HLO text."""
    import jax

    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
    # a kernel with no operands is placed by its output sharding
    compiled = jax.jit(fn, out_shardings=one_chip).lower(*args).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the Pallas kernel, not a fallback
    return text


@pytest.mark.parametrize("technique", ["gss", "fac2", "tss"])
def test_protocol_kernel_compiles(one_chip, technique):
    import jax.numpy as jnp

    from repro.core.chunk_calculus import max_steps_bound
    from repro.device import host_spec, plan_claims
    from repro.device.persistent import protocol_call

    S = int(max_steps_bound(host_spec(technique, N_TILES, P)))
    fn = functools.partial(
        protocol_call, technique=technique, N=N_TILES, P=P, chunk=1,
        max_chunk=None, S=S, i_slot=0, lp_slot=1, interpret=False)
    text = _compile(one_chip, fn, ((256,), jnp.int32),
                    ((N_TILES + 1,), jnp.float32))
    assert "%dls_protocol" in text  # the kernel's name in the device trace
    # the slab, the schedule packed for one read-back, and the per-worker
    # claim tables as wide as the plan, for the compute kernel
    C = plan_claims(technique, N_TILES, P)
    header = re.sub(r"\{[^{}]*\}", "", text.splitlines()[0])  # no layouts
    assert (f"->(s32[256], s32[{4 * S + 2 * P}], s32[{P}], s32[{P},{C}], "
            f"s32[{P},{C}])") in header
    assert " scatter(" not in text


def test_static_mandelbrot_compiles(one_chip):
    from repro.kernels.mandelbrot.kernel import mandelbrot_counts_pallas

    _compile(one_chip, lambda: mandelbrot_counts_pallas(
        WIDTH, WIDTH, ct=CT, interpret=False))


def test_persistent_mandelbrot_compiles(one_chip):
    import jax.numpy as jnp

    from repro.kernels.mandelbrot.persistent import persistent_call

    C = 16  # claims per worker
    fn = functools.partial(
        persistent_call, width=WIDTH, height=WIDTH, ct=CT,
        xlim=(-2.0, 1.0), ylim=(-1.5, 1.5), block_h=128, block_w=128,
        interpret=False)
    text = _compile(one_chip, fn, ((P,), jnp.int32), ((P, C), jnp.int32),
                    ((P, C), jnp.int32))
    assert "%mandelbrot_persistent" in text


def test_static_flash_attention_compiles(one_chip):
    import jax.numpy as jnp

    from repro.kernels.flash_attention.kernel import flash_attention_pallas

    fn = functools.partial(flash_attention_pallas, causal=True,
                           interpret=False)
    _compile(one_chip, fn, ((1, HEADS, T, HEAD_DIM), jnp.bfloat16),
             ((1, KV_HEADS, T, HEAD_DIM), jnp.bfloat16),
             ((1, KV_HEADS, T, HEAD_DIM), jnp.bfloat16))


@pytest.mark.parametrize("B,fits", [(VARLEN_B, True), (VARLEN_B + 1, False)])
def test_persistent_flash_attention_compiles(one_chip, B, fits):
    """VARLEN_B rows fit the chip's VMEM as whole-array blocks; one more
    does not."""
    import contextlib

    import jax
    import jax.numpy as jnp

    from repro.kernels.flash_attention.persistent import persistent_call

    C = 64  # claims per worker
    fn = functools.partial(persistent_call, causal=True,
                           scale=HEAD_DIM ** -0.5, blk_q=128, blk_k=128,
                           interpret=False)
    refused = pytest.raises(jax.errors.JaxRuntimeError, match="vmem")
    with contextlib.nullcontext() if fits else refused:
        text = _compile(one_chip, fn, ((P,), jnp.int32), ((P, C), jnp.int32),
                        ((P, C), jnp.int32), ((B,), jnp.int32),
                        ((B, HEADS, T, HEAD_DIM), jnp.bfloat16),
                        ((B, KV_HEADS, T, HEAD_DIM), jnp.bfloat16),
                        ((B, KV_HEADS, T, HEAD_DIM), jnp.bfloat16))
        assert "%attention_persistent" in text


def test_persistent_moe_experts_compile(one_chip):
    """The streaming expert kernel at DeepSeek-V3's widths, with its
    gather and combine; what it asks of VMEM fits the chip's 128 MiB
    (one expert's weights, 88 MB, would not fit twice)."""
    import jax.numpy as jnp

    from repro.kernels.moe_experts.persistent import (persistent_call,
                                                      vmem_limit)

    limit = vmem_limit(MOE_BLK, MOE_D, MOE_F, jnp.bfloat16)
    assert limit <= 100 << 20
    C, M = 32, 18432  # claims per worker; rows of 72 live tiles
    fn = functools.partial(persistent_call, M=M, blk=MOE_BLK,
                           interpret=False)
    i32, bf16 = jnp.int32, jnp.bfloat16
    text = _compile(one_chip, fn, ((P,), i32), ((P, C), i32), ((P, C), i32),
                    ((MOE_E,), i32), ((MOE_E, MOE_T), i32),
                    ((MOE_E, MOE_T), jnp.float32), ((MOE_E, MOE_T), i32),
                    ((MOE_E, MOE_T // 256 + 1), i32),
                    ((MOE_T, MOE_D), bf16), ((MOE_E, MOE_D, MOE_F), bf16),
                    ((MOE_E, MOE_D, MOE_F), bf16),
                    ((MOE_E, MOE_F, MOE_D), bf16))
    assert "%moe_experts_persistent" in text and "%moe_combine" in text
    # the expert kernel writes only the rows gathered, not a worst case
    assert re.search(rf"f32\[{M + 32},{MOE_D}\][^ ]* custom-call", text)
    # no scatter: its loops cost ms a drain on the chip
    assert " scatter(" not in text


def test_spin_images_compile(one_chip):
    import jax.numpy as jnp

    from repro.kernels.spin_image.kernel import spin_images_pallas

    n_points, n_images = 8192, 2048
    fn = functools.partial(spin_images_pallas, n_images=n_images,
                           bin_size=0.5, interpret=False)
    _compile(one_chip, fn, ((n_points, 3), jnp.float32),
             ((n_points, 3), jnp.float32))
