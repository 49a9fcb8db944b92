"""Compile rehearsals for a described TPU v5e: the Pallas kernels of the
serving path at qwen3-1.7b widths, compiled by the TPU compiler without
a chip attached. Interpret mode accepts block shapes the chip's
compiler refuses (a paged-decode K/V block of size 1 on the kv-head
axis was one), so these run the real compiler on real shapes. Nothing
executes, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# qwen3-1.7b: 8 kv heads, 2 query heads per kv head, head_dim 128, and
# 128-token pages (attn_block); d_model 2048 with 128-blocks for BSR
HK, G, D, PAGE = 8, 2, 128, 128
SLOTS, PAGES_PER_SLOT, D_MODEL, BLOCK = 8, 5, 2048, 128


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with JAX's persistent cache off:
    a compile for a described chip is written to the cache but cannot
    be read back without one."""
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(
            platform="tpu", topology_name="v5e:2x2"
        )
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    prev = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", prev)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()  # the kernel is there
    return compiled


def test_paged_decode_kernel_compiles_for_v5e(one_chip):
    from repro.kernels.paged_attention import paged_decode_attention_pallas

    n_pages = SLOTS * PAGES_PER_SLOT + 1
    pool = _spec(one_chip, (n_pages, HK, PAGE, D), jnp.bfloat16)
    sched = _spec(one_chip, (SLOTS, PAGES_PER_SLOT), jnp.int32)
    _compile(
        lambda *a: paged_decode_attention_pallas(*a, sm_scale=D**-0.5),
        _spec(one_chip, (SLOTS, HK, G, D), jnp.bfloat16),
        pool,
        pool,
        sched,
        sched,
        sched,
        _spec(one_chip, (SLOTS,), jnp.int32),
    )


@pytest.mark.parametrize("bm", [8, 256])
def test_bsr_matmul_compiles_for_v5e(one_chip, bm):
    """bm 8 is the decode batch ``ops.bsr_matmul`` pads to, 256 its
    prefill tile."""
    from repro.kernels.bsr_matmul import bsr_matmul_pallas

    nb, r = D_MODEL // BLOCK, 4
    _compile(
        lambda x, blocks, cols: bsr_matmul_pallas(x, blocks, cols, bm=bm),
        _spec(one_chip, (2 * bm, D_MODEL), jnp.bfloat16),
        _spec(one_chip, (nb, r, BLOCK, BLOCK), jnp.bfloat16),
        _spec(one_chip, (nb, r), jnp.int32),
    )
