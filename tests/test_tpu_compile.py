"""Compile rehearsals for a described TPU v5e: the Pallas kernels of the
serving path at qwen3-1.7b widths, compiled by the TPU compiler without
a chip attached. Interpret mode accepts block shapes the chip's
compiler refuses (a paged-decode K/V block of size 1 on the kv-head
axis was one), so these run the real compiler on real shapes. The
paged decode step is compiled whole too: its memory analysis and
optimized HLO show whether the KV pool stays in place. Nothing
executes, so they say nothing about results or times.

The topology is described inside a fixture, never at import: only one
process may load the TPU library, and every test worker imports this
file.
"""

import os
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

# qwen3-1.7b: 8 kv heads, 2 query heads per kv head, head_dim 128, and
# 128-token pages (attn_block); d_model 2048 with 128-blocks for BSR
HK, G, D, PAGE = 8, 2, 128, 128
D_MODEL, BLOCK = 2048, 128
LAYERS = 4  # a stacked pool's depth (the model has 28)


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


@pytest.mark.parametrize(
    "slots, w",
    [
        pytest.param(32, 8, id="pixelfly-chat"),
        pytest.param(32, 40, id="dense-chat"),
        pytest.param(16, 9, id="pixelfly-longctx"),
    ],
)
def test_paged_decode_kernel_compiles_for_v5e(one_chip, slots, w):
    """The decode shapes the cells run: ``w`` schedule pages a slot (every
    page of 5120 tokens for the dense twin, the sparse schedule of 40 and
    88 pages for pixelfly); each grid step a (HK, PAGE, D) K/V slab."""
    from repro.kernels.paged_attention import paged_decode_attention_pallas

    n_pages = slots * w + 1
    pool = _spec(one_chip, (LAYERS, n_pages, HK, PAGE, D), jnp.bfloat16)
    sched = _spec(one_chip, (slots, w), jnp.int32)
    _compile(
        lambda *a: paged_decode_attention_pallas(*a, sm_scale=D**-0.5),
        _spec(one_chip, (slots, HK, G, D), jnp.bfloat16),
        pool,
        pool,
        _spec(one_chip, (), jnp.int32),
        sched,
        sched,
        sched,
        _spec(one_chip, (slots,), jnp.int32),
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


def _decode_step_memory(sharding, cfg, n_pages):
    """Compile the engine's decode step (Pallas reads, the pools donated)
    for a described chip: (memory analysis, optimized HLO, pool bytes)."""
    from repro.models import transformer as T

    def place(tree):
        return jax.tree.map(lambda a: _spec(sharding, a.shape, a.dtype), tree)

    params = place(
        jax.eval_shape(lambda: T.init_model(jax.random.PRNGKey(0), cfg))
    )
    pools = place(
        jax.eval_shape(lambda: T.init_paged_cache(cfg, n_pages, PAGE))
    )
    slots, per_seq = 32, 40
    step = jax.jit(
        lambda p, c, t, pos, tab: T.decode_step_paged(
            cfg, p, c, t, pos, tab, impl="pallas", paged_impl="pallas"
        ),
        donate_argnums=(1,),
    )
    compiled = step.lower(
        params,
        pools,
        _spec(sharding, (slots,), jnp.int32),
        _spec(sharding, (slots,), jnp.int32),
        _spec(sharding, (slots, per_seq), jnp.int32),
    ).compile()
    pool_bytes = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(pools))
    return compiled.memory_analysis(), compiled.as_text(), pool_bytes


def _pool_copies(hlo: str, n_pages: int) -> list[str]:
    """Copies and fusions in an optimized HLO module that produce a whole
    pool ``bf16[L, n_pages, Hk, page, D]`` or one layer's pool, other
    than a fusion whose root is a scatter (the in-place page write)."""
    roots, comp = {}, None
    for line in hlo.splitlines():
        head = re.match(r"(?:ENTRY )?%([\w.-]+) \(", line)
        if head and line.rstrip().endswith("{"):
            comp = head.group(1)
        root = re.match(r"\s*ROOT %[\w.-]+ = \S+ ([\w-]+)\(", line)
        if root and comp is not None:
            roots[comp] = root.group(1)
    shaped = re.compile(
        rf"= bf16\[(?:\d+,)?{n_pages},{HK},{PAGE},{D}\]\S* "
        r"(copy|copy-start|copy-done|fusion)\("
    )
    bad = []
    for line in hlo.splitlines():
        m = shaped.search(line)
        if not m:
            continue
        calls = re.search(r"calls=%([\w.-]+)", line)
        if m.group(1) == "fusion" and calls and roots.get(calls.group(1)) == "scatter":
            continue
        bad.append(line.strip()[:160])
    return bad


@pytest.mark.parametrize("sparse", [True, False], ids=["pixelfly", "dense"])
def test_decode_step_keeps_the_pool_in_place(one_chip, sparse):
    """The decode step at qwen3-1.7b widths (4 of its 28 layers, the
    benchmark's 32 slots of 40 pages) updates the donated pools in
    place: its temporaries do not grow with the pool, the whole pool is
    aliased to the output, and no copy or fusion makes a pool-shaped
    buffer besides the page write's in-place scatter. Both pool sizes
    are beyond what the core's VMEM could stage (a stack of 64 MiB or
    less may be copied there whole, which a full-depth pool never is)."""
    from repro.configs import registry

    over = {"num_layers": LAYERS}
    if sparse:
        over.update(sparse_density=0.2, attn_block=PAGE)
    cfg = registry.get("qwen3-1.7b", sparse=sparse, **over)
    temps = []
    for n_pages in (128, 192):
        mem, hlo, pool_bytes = _decode_step_memory(one_chip, cfg, n_pages)
        assert "tpu_custom_call" in hlo  # the Pallas read is there
        assert mem.alias_size_in_bytes == pool_bytes
        assert _pool_copies(hlo, n_pages) == []
        temps.append(mem.temp_size_in_bytes)
    one_page = HK * PAGE * D * 2  # one layer's (Hk, page, D) bf16 page
    assert abs(temps[1] - temps[0]) < one_page, temps
