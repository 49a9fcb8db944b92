"""Pallas TPU kernel: paged block-sparse decode attention.

One decode step for every serving slot against the block-paged KV cache
(``repro.serving.PagedKVCache``): instead of materializing the scheduled
pages with ``jnp.take`` gathers (the portable path in
``repro.models.layers``), the kernel reads the K/V *pools* directly.
The page-id schedule — the pixelfly butterfly/local/global block ids
each slot's query visits, already mapped through the slot's page table
to physical page ids — is scalar-prefetched, so the BlockSpec index maps
can steer each grid step's DMA at the right pool page before the kernel
body runs. This is the step ROADMAP calls "as fast as the hardware
allows": page-table indirection and the O(b·log n) sparse schedule fused
into one pass over VMEM-resident accumulators.

Layout and masking:
  - grid ``(B, w)`` — slots x schedule slots, with no kv-head axis: every
    kv head of a slot reads the same page at the same schedule slot, so
    one grid step visits one page for all heads. The schedule axis is
    sequential so the online-softmax statistics (m, l) and the output
    accumulator stay resident in VMEM, each ``(Hk*G, ·)``: every head's
    query rows stacked.
  - q is pre-grouped ``(B, Hk, G, D)`` (GQA: G query heads share one
    kv head) and read as the slot's ``(Hk*G, D)`` block; each grid step
    contracts it with the page's ``(Hk*page, D)`` keys in one 2-D
    product and keeps head h's diagonal block (its G rows against its
    own page columns), so the softmax runs on the stacked ``(Hk*G,
    page)`` scores; the probabilities go back block-diagonal for one
    product with the stacked values.
  - the pools are the model's stacked, head-major ``(L, n_pages, Hk,
    page, D)`` buffers, read in place: the layer index is
    scalar-prefetched with the schedule, so one grid step's K/V block
    is the whole ``(Hk, page, D)`` slab at ``(layer, phys)`` and the
    layer scan never slices a per-layer pool out for the kernel. A
    page-major pool ``(..., page, Hk, D)`` would interleave the heads
    within each page row.
  - ``logical`` carries the *logical* block id of every schedule slot:
    key position ``logical * page + offset`` is masked against the
    slot's current position, which also neutralizes the shared trash
    page (physical page 0) — idle/unallocated table entries alias it,
    and their logical positions land beyond ``pos``.
  - ``keep`` disables duplicate schedule slots (butterfly XOR
    collisions) so no key is double-counted, mirroring the
    first-occurrence mask of the jnp reference path.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["paged_decode_attention_pallas"]

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _kernel(
    layer_ref,
    phys_ref,
    logical_ref,
    keep_ref,
    pos_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    w: int,
    g: int,
    page: int,
    sm_scale: float,
):
    b = pl.program_id(0)
    t = pl.program_id(1)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(keep_ref[b, t] == 1)
    def _visit():
        _, hk, _, d = k_ref.shape
        q = q_ref[0]  # (Hk*G, D): every head's query rows, stacked
        k = k_ref[0].reshape(hk * page, d)  # every head's page, stacked
        v = v_ref[0].reshape(hk * page, d)
        # one product over all heads; head h's scores are the diagonal
        # block of rows [h*G, (h+1)*G) and columns [h*page, (h+1)*page)
        s_all = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32
        )  # (Hk*G, Hk*page)
        row = jax.lax.broadcasted_iota(jnp.int32, (hk * g, page), 0)
        s = s_all[:, (hk - 1) * page :]
        for h in range(hk - 2, -1, -1):
            s = jnp.where(row < (h + 1) * g, s_all[:, h * page : (h + 1) * page], s)
        s = s * sm_scale  # (Hk*G, page)
        kpos = logical_ref[b, t] * page + jax.lax.broadcasted_iota(
            jnp.int32, s.shape, 1
        )
        s = jnp.where(kpos <= pos_ref[b], s, _NEG_INF)
        m_prev = m_ref[:, :1]  # (Hk*G, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        still_masked = m_cur <= _NEG_INF / 2
        alpha = jnp.where(still_masked, 1.0, jnp.exp(m_prev - m_cur))
        p = jnp.where(still_masked, 0.0, jnp.exp(s - m_cur))
        l_prev = l_ref[:, :1]
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + p.sum(axis=-1, keepdims=True), l_ref.shape
        )
        # each head's probabilities in its own column block, zero elsewhere,
        # so one product against the stacked V gives every head's output
        p_blocks = jnp.concatenate(
            [
                jnp.where((row >= h * g) & (row < (h + 1) * g), p, 0.0)
                for h in range(hk)
            ],
            axis=1,
        ).astype(v.dtype)  # (Hk*G, Hk*page)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p_blocks, v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(t == w - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("sm_scale", "interpret"))
def paged_decode_attention_pallas(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer: jax.Array,
    phys: jax.Array,
    logical: jax.Array,
    keep: jax.Array,
    pos: jax.Array,
    *,
    sm_scale: float,
    interpret: bool = False,
) -> jax.Array:
    """q: (B, Hk, G, D). k_pages, v_pages: (L, n_pages, Hk, page, D)
    stacked pools; layer: int32 scalar, the layer they are read at (a
    caller with one layer's pool passes ``pool[None]`` and 0).

    phys/logical/keep: (B, w) int32 — physical page id, logical block id
    and keep flag per schedule slot; pos: (B,) int32 current token
    position per slot. Returns (B, Hk, G, D) in q's dtype.
    """
    b, hk, g, d = q.shape
    _, _, hk_p, page, d_p = k_pages.shape
    if (hk_p, d_p) != (hk, d):
        raise ValueError("pool head/dim mismatch with q")
    if phys.shape != logical.shape or phys.shape != keep.shape:
        raise ValueError("schedule arrays must share shape (B, w)")
    w = phys.shape[1]
    rows = hk * g

    grid = (b, w)

    def q_map(bi, t, layer_ref, phys_ref, logical_ref, keep_ref, pos_ref):
        del t, layer_ref, phys_ref, logical_ref, keep_ref, pos_ref
        return (bi, 0, 0)

    def kv_map(bi, t, layer_ref, phys_ref, logical_ref, keep_ref, pos_ref):
        del logical_ref, keep_ref, pos_ref
        return (layer_ref[0], phys_ref[bi, t], 0, 0, 0)

    kernel = functools.partial(_kernel, w=w, g=g, page=page, sm_scale=sm_scale)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=5,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, rows, d), q_map),
                pl.BlockSpec((pl.squeezed, 1, hk, page, d), kv_map),
                pl.BlockSpec((pl.squeezed, 1, hk, page, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, rows, d), q_map),
            scratch_shapes=[
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, 128), jnp.float32),
                pltpu.VMEM((rows, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((b, rows, d), q.dtype),
        interpret=interpret,
        # the kernel's name in a profiler trace
        name="paged_decode_attention_pallas",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
    )(
        jnp.asarray(layer, jnp.int32).reshape(1),
        phys.astype(jnp.int32),
        logical.astype(jnp.int32),
        keep.astype(jnp.int32),
        pos.astype(jnp.int32),
        q.reshape(b, rows, d),
        k_pages,
        v_pages,
    )
    return out.reshape(b, hk, g, d)
