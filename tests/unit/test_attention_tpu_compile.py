"""The Pallas attention kernels compile through Mosaic for a TPU v5e that
is described, not attached: what interpret mode cannot show (tiling rules,
unaligned slices, VMEM limits), at no chip time.

The topology is described inside a fixture, never at import: only one
process may load the TPU's library, and under several test workers every
worker imports this file while one runs it. Keep these tests in this one
file.
"""

import jax
import jax.numpy as jnp
import pytest

from metaopt_tpu.ops.attention import CausalMask, flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no libtpu here, or it is held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture(scope="module", autouse=True)
def no_compile_cache():
    """A compile for a described device is written to the persistent cache
    but cannot be read back without a chip: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


# (batch, Sq, Sk, heads, head width): the benchmark cell's attention, its
# long twin, a sequence of several tiles, the zoo's default trial length
# (one tile under a lane tile), lengths that pad, cross attention with a
# short query side, and one head of a tp=8 shard
SHAPES = [(64, 256, 256, 8, 64), (64, 512, 512, 8, 64), (4, 1024, 1024, 8, 64),
          (32, 64, 64, 8, 64), (4, 200, 77, 8, 64), (4, 40, 300, 8, 64),
          (4, 256, 256, 1, 64)]


@pytest.mark.parametrize("masked", [True, False], ids=["masked", "unmasked"])
@pytest.mark.parametrize("shape", SHAPES, ids=lambda s: "x".join(map(str, s)))
def test_forward_and_backward_compile_for_a_v5e(one_chip, shape, masked):
    b, sq, sk, h, d = shape
    q = jax.ShapeDtypeStruct((b, sq, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((b, sk, h, d), jnp.bfloat16, sharding=one_chip)
    args = [q, kv, kv]
    if masked:
        args.append(jax.ShapeDtypeStruct((b, sq, sk), jnp.bool_,
                                         sharding=one_chip))

    def loss(q, k, v, mask=None):
        out = flash_attention(q, k, v, mask, impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        *args).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2  # fwd, bwd


# (length, window, query heads, K/V heads, head width): SmallThinker's global
# and window layers at the benchmark cell's 8192 tokens and at the model's
# 16384, a length that pads, and 64-wide heads
STRUCTURAL = [(8192, None, 28, 4, 128), (8192, 4096, 28, 4, 128),
              (16384, 4096, 28, 4, 128), (16384, None, 28, 4, 128),
              (1000, 300, 8, 2, 128), (2048, 512, 8, 8, 64)]


@pytest.mark.parametrize("case", STRUCTURAL,
                         ids=lambda c: "x".join(map(str, c)))
def test_structural_masks_and_grouped_heads_compile_for_a_v5e(one_chip, case):
    """VMEM stays bounded: a program holds one head of the other sequence,
    2 MiB at 8192 x 128, whatever the number of heads."""
    s, window, h, hkv, d = case
    q = jax.ShapeDtypeStruct((1, s, h, d), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, s, hkv, d), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, CausalMask(window), impl="pallas")
        return jnp.sum(out.astype(jnp.float32) ** 2)

    compiled = jax.jit(jax.grad(loss, argnums=(0, 1, 2))).lower(
        q, kv, kv).compile()
    assert compiled.as_text().count("tpu_custom_call") == 2  # fwd, bwd
