"""Pallas TPU kernel: block-sparse flash attention over a static schedule.

One pass, online softmax, visiting only the KV blocks named by the static
pixelfly block schedule (local + butterfly strides + global — see
``repro.core.attn_pattern``). This is the TPU analogue of the paper's
Triton block-sparse attention: the *schedule* is the sparsity, so skipped
KV blocks are never read from HBM, giving the O(S·b·log S) key reads per
query block the paper's speedups come from.

Layout: q, k, v are (BH, S, D) with batch*heads collapsed; grid is
(BH, nqb, max_nkv) with the KV-slot axis sequential so the softmax
statistics (m, l) and the output accumulator stay resident in VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["block_sparse_attention_pallas"]

_NEG_INF = float(jnp.finfo(jnp.float32).min)


def _kernel(
    sched_ref,
    valid_ref,
    q_ref,
    k_ref,
    v_ref,
    o_ref,
    m_ref,
    l_ref,
    acc_ref,
    *,
    nkv: int,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
):
    i = pl.program_id(1)
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    @pl.when(valid_ref[i, t] == 1)
    def _visit():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        s = (
            jnp.dot(q, k.T, preferred_element_type=jnp.float32) * sm_scale
        )  # (bq, bk)
        if causal:
            j = sched_ref[i, t]
            row = i * block_q + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 0
            )
            col = j * block_k + jax.lax.broadcasted_iota(
                jnp.int32, s.shape, 1
            )
            s = jnp.where(col <= row, s, _NEG_INF)
        m_prev = m_ref[:, :1]  # (bq, 1)
        m_cur = jnp.maximum(m_prev, s.max(axis=-1, keepdims=True))
        still_masked = m_cur <= _NEG_INF / 2
        alpha = jnp.where(still_masked, 1.0, jnp.exp(m_prev - m_cur))
        p = jnp.where(still_masked, 0.0, jnp.exp(s - m_cur))
        l_prev = l_ref[:, :1]
        l_ref[...] = jnp.broadcast_to(
            l_prev * alpha + p.sum(axis=-1, keepdims=True), l_ref.shape
        )
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32
        )
        m_ref[...] = jnp.broadcast_to(m_cur, m_ref.shape)

    @pl.when(t == nkv - 1)
    def _flush():
        l = l_ref[:, :1]
        o_ref[0] = (acc_ref[...] / jnp.where(l == 0.0, 1.0, l)).astype(
            o_ref.dtype
        )


@functools.partial(
    jax.jit,
    static_argnames=("sm_scale", "causal", "block_q", "block_k", "interpret"),
)
def block_sparse_attention_pallas(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    kv_index: jax.Array,
    valid: jax.Array,
    *,
    sm_scale: float,
    causal: bool,
    block_q: int,
    block_k: int,
    interpret: bool = False,
) -> jax.Array:
    """q, k, v: (BH, S, D). kv_index/valid: (nqb, max_nkv) int32."""
    bh, sq, d = q.shape
    sk = k.shape[1]
    nqb, nkv = kv_index.shape
    if sq % block_q or sk % block_k:
        raise ValueError("sequence lengths must be multiples of block sizes")
    if nqb != sq // block_q:
        raise ValueError("schedule rows must match q blocks")

    grid = (bh, nqb, nkv)

    def q_map(bhi, i, t, sched_ref, valid_ref):
        del t
        return (bhi, i, 0)

    def kv_map(bhi, i, t, sched_ref, valid_ref):
        return (bhi, sched_ref[i, t], 0)

    def o_map(bhi, i, t, sched_ref, valid_ref):
        del t
        return (bhi, i, 0)

    kernel = functools.partial(
        _kernel,
        nkv=nkv,
        sm_scale=sm_scale,
        causal=causal,
        block_q=block_q,
        block_k=block_k,
    )
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, block_q, d), q_map),
                pl.BlockSpec((1, block_k, d), kv_map),
                pl.BlockSpec((1, block_k, d), kv_map),
            ],
            out_specs=pl.BlockSpec((1, block_q, d), o_map),
            scratch_shapes=[
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, 128), jnp.float32),
                pltpu.VMEM((block_q, d), jnp.float32),
            ],
        ),
        out_shape=jax.ShapeDtypeStruct(q.shape, q.dtype),
        interpret=interpret,
        # the kernel's name in a profiler trace
        name="block_sparse_attention_pallas",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(kv_index, valid, q, k, v)
