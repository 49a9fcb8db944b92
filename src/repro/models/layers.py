"""Transformer building blocks: norms, RoPE/M-RoPE, GQA attention, MLPs.

Every GEMM goes through ``repro.core.pixelfly`` so the *same* layer code
serves both the dense baseline and the Pixelfly-sparsified model — the
paper's parameterization is a config flag, not a fork of the model zoo.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.core import attn_pattern as ap
from repro.core.pixelfly import LinearSpec, apply_linear, init_linear
from repro.kernels import ops

P_AXES_BATCH = ("pod", "data")


def constrain(cfg: ModelConfig, x: jax.Array, *spec) -> jax.Array:
    """with_sharding_constraint guarded by launcher knobs: a no-op unless
    the launcher set tp_size/batch_axes (so model code runs unchanged on a
    single device)."""
    if not cfg.batch_axes and (not cfg.tp_size or cfg.tp_size <= 1):
        return x
    from jax.sharding import PartitionSpec as P

    return jax.lax.with_sharding_constraint(x, P(*spec))


def paged_pool_entry(cfg: ModelConfig, hk: int, d: int) -> int | None:
    """Which of a head-major paged KV pool leaf's head axes the model
    axis shards: -3 (kv_heads) preferred, -1 (head_dim) fallback, None
    when neither divides (or no TP). The page axes always replicate —
    the host rewrites the page table every step, so any page must be
    addressable from any slot. Must agree with
    ``distributed.sharding._paged_pool_spec`` (the buffer's resting
    NamedSharding) so the in-jit constraints never force a reshard."""
    tp = cfg.tp_size or 1
    if tp <= 1:
        return None
    if hk % tp == 0 and hk >= tp:
        return -3
    if d % tp == 0 and d >= tp:
        return -1
    return None


def constrain_paged_pool(cfg: ModelConfig, buf: jax.Array) -> jax.Array:
    """Pin a slot-shared page pool's sharding inside a jit'd step: leaves
    are (..., kv_heads, page, head_dim); one head axis shards on the
    model axis per ``paged_pool_entry``, everything else replicates."""
    ax = paged_pool_entry(cfg, buf.shape[-3], buf.shape[-1])
    if ax is None:
        return buf
    spec: list = [None] * buf.ndim
    spec[buf.ndim + ax] = "model"
    return constrain(cfg, buf, *spec)


def _attn_activation_specs(cfg: ModelConfig, seq: int):
    """How to shard (b, s, hk, g, d) attention activations over the model
    axis, in preference order:
    1. kv-heads divisible by TP -> classic head sharding;
    2. q-heads divisible -> "repeat KV" (Megatron GQA practice: duplicate
       the small KV heads on every shard, shard the 64 q-heads; §Perf C4 —
       avoids the per-layer seq<->TP activation reshards of option 3);
    3. sequence-parallel (q-slice per shard against replicated KV).
    """
    tp = cfg.tp_size
    ba = cfg.batch_axes or None
    if tp <= 1:
        return None
    if cfg.num_kv_heads % tp == 0:
        return {
            "mode": "heads",
            "q": (ba, None, "model", None, None),
            "kv": (ba, None, "model", None),
            "o": (ba, None, "model", None, None),
        }
    # NOTE(§Perf C4/A4, refuted): a "repeat_kv" mode (duplicate KV heads,
    # shard the divisible q-head dim — Megatron GQA practice) measured
    # +48% collective bytes here: the repeat materialization + its
    # backward segment-reduce cost more than the seq<->TP reshards it
    # removed. Sequence-parallel stays the default for kv%tp != 0.
    if seq % tp == 0 and seq >= tp:
        return {
            "mode": "seq",
            "q": (ba, "model", None, None, None),
            "kv": (ba, None, None, None),
            "o": (ba, "model", None, None, None),
        }
    return None


# ----------------------------------------------------------------------
# Norms
# ----------------------------------------------------------------------


def init_rmsnorm(dim: int, dtype=jnp.float32) -> dict:
    return {"scale": jnp.ones((dim,), dtype)}


def rmsnorm(params: dict, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * params["scale"].astype(jnp.float32)).astype(x.dtype)


def head_rmsnorm(scale: jax.Array, x: jax.Array, eps: float = 1e-5) -> jax.Array:
    """qk-norm: RMS over the head dim of (..., heads, head_dim)."""
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(var + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


# ----------------------------------------------------------------------
# RoPE / M-RoPE
# ----------------------------------------------------------------------


def _rope_angles(positions: jax.Array, head_dim: int, theta: float) -> tuple:
    """positions (...,) -> cos, sin of shape (..., head_dim//2)."""
    half = head_dim // 2
    freqs = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    ang = positions[..., None].astype(jnp.float32) * freqs
    return jnp.cos(ang), jnp.sin(ang)


def apply_rope(
    x: jax.Array,
    positions: jax.Array,
    theta: float,
    mrope_sections: tuple[int, ...] = (),
) -> jax.Array:
    """x: (B, S, H, D). positions: (B, S) or (B, S, 3) for M-RoPE.

    M-RoPE (Qwen2-VL): the half-dim is split into sections, each rotated by
    its own position stream (temporal / height / width).
    """
    b, s, h, d = x.shape
    half = d // 2
    if mrope_sections:
        if positions.ndim != 3:
            raise ValueError("M-RoPE needs positions (B, S, n_sections_streams)")
        cs, ss = [], []
        off = 0
        for i, sec in enumerate(mrope_sections):
            # section frequencies are the global freq slice [off, off+sec),
            # each rotated by its own position stream (t / h / w)
            freqs = theta ** (
                -jnp.arange(off, off + sec, dtype=jnp.float32) / half
            )
            ang = positions[..., i][..., None].astype(jnp.float32) * freqs
            cs.append(jnp.cos(ang))
            ss.append(jnp.sin(ang))
            off += sec
        cos = jnp.concatenate(cs, axis=-1)
        sin = jnp.concatenate(ss, axis=-1)
    else:
        cos, sin = _rope_angles(positions, d, theta)
    cos = cos[:, :, None, :]  # (B, S, 1, half)
    sin = sin[:, :, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    y1 = xf1 * cos - xf2 * sin
    y2 = xf2 * cos + xf1 * sin
    return jnp.concatenate([y1, y2], axis=-1).astype(x.dtype)


# ----------------------------------------------------------------------
# Attention core math (portable paths; Pallas path goes via kernels.ops)
# ----------------------------------------------------------------------


def _grouped_logits(q: jax.Array, k: jax.Array) -> jax.Array:
    """q (B,Sq,Hk,G,D), k (B,Sk,Hk,D) -> (B,Hk,G,Sq,Sk) fp32."""
    return jnp.einsum(
        "bqhgd,bkhd->bhgqk", q, k, preferred_element_type=jnp.float32
    )


def _grouped_out(p: jax.Array, v: jax.Array) -> jax.Array:
    """p (B,Hk,G,Sq,Sk), v (B,Sk,Hk,D) -> (B,Sq,Hk,G,D)."""
    return jnp.einsum(
        "bhgqk,bkhd->bqhgd", p, v, preferred_element_type=jnp.float32
    )


def _online_softmax_scan(q32, out_dtype, k, v, chunk, mask_fn) -> jax.Array:
    """Shared online-softmax attention core: lax.scan over KV chunks,
    never materializing the (Sq, Sk) score matrix.

    q32 (B,Sq,Hk,G,D) fp32 with ``sm_scale`` already folded in; k, v
    (B,Sk,Hk,D) with Sk a ``chunk`` multiple; ``mask_fn(ci)`` returns a
    bool mask broadcastable to the (B,Hk,G,Sq,chunk) scores of chunk
    ``ci`` (False = masked out), or None for no masking. Both the dense
    causal path and the prefix partial-prefill path run this exact body,
    so a numerics fix lands in every caller at once.
    """
    b, sq, hk, g, d = q32.shape
    n_chunks = k.shape[1] // chunk
    kc = k.reshape(b, n_chunks, chunk, hk, d)
    vc = v.reshape(b, n_chunks, chunk, hk, d)

    @jax.checkpoint
    def body(carry, inputs):
        m, l, acc = carry
        ci, kb, vb = inputs
        s = _grouped_logits(q32.astype(out_dtype), kb).astype(jnp.float32)
        mask = mask_fn(ci)
        if mask is not None:
            s = jnp.where(mask, s, -jnp.inf)
        m_new = jnp.maximum(m, s.max(axis=-1))
        masked = jnp.isneginf(m_new)
        alpha = jnp.where(masked, 1.0, jnp.exp(m - m_new))
        p = jnp.where(
            masked[..., None], 0.0, jnp.exp(s - m_new[..., None])
        )
        l_new = l * alpha + p.sum(axis=-1)
        pv = jnp.einsum(
            "bhgqk,bkhd->bhgqd", p.astype(vb.dtype), vb,
            preferred_element_type=jnp.float32,
        )
        acc_new = acc * alpha[..., None] + pv
        return (m_new, l_new, acc_new), None

    m0 = jnp.full((b, hk, g, sq), -jnp.inf, jnp.float32)
    l0 = jnp.zeros((b, hk, g, sq), jnp.float32)
    a0 = jnp.zeros((b, hk, g, sq, d), jnp.float32)
    idx = jnp.arange(n_chunks)
    (m, l, acc), _ = jax.lax.scan(
        body, (m0, l0, a0), (idx, kc.swapaxes(0, 1), vc.swapaxes(0, 1))
    )
    l = jnp.where(l == 0.0, 1.0, l)
    out = acc / l[..., None]  # (b,hk,g,sq,d)
    return out.transpose(0, 3, 1, 2, 4).astype(out_dtype)


def flash_attention_jnp(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    *,
    causal: bool,
    chunk: int,
    sm_scale: float,
) -> jax.Array:
    """Memory-efficient causal attention: lax.scan over KV chunks with
    online softmax. q (B,Sq,Hk,G,D); k, v (B,Sk,Hk,D). Never materializes
    the (Sq, Sk) score matrix. ``Sk`` need not be a chunk multiple: KV is
    zero-padded to one and the padded keys masked out.
    """
    sq = q.shape[1]
    sk_real = sk = k.shape[1]
    chunk = min(chunk, sk)
    pad = (-sk) % chunk  # KV need not be a chunk multiple: pad and mask
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q32 = q.astype(jnp.float32) * sm_scale
    qpos = jnp.arange(sq)

    def mask_fn(ci):
        if not (causal or pad):
            return None
        kpos = ci * chunk + jnp.arange(chunk)
        mask = jnp.broadcast_to(kpos[None, :] < sk_real, (sq, chunk))
        if causal:
            mask = mask & (kpos[None, :] <= qpos[:, None])
        return mask[None, None, None]

    return _online_softmax_scan(q32, q.dtype, k, v, chunk, mask_fn)


# NOTE on scaling: q32 above holds q * sm_scale in fp32; _grouped_logits is
# fed `q32.astype(q.dtype)` so the MXU sees the model dtype. The scale is
# folded into q before the matmul (standard flash trick).


def sparse_attention_jnp(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    schedule: ap.BlockSchedule,
    *,
    causal: bool,
    sm_scale: float,
) -> jax.Array:
    """Portable pixelfly block-sparse attention, fully vectorized over q
    blocks (sparse FLOPs & bytes in HLO): every q block gathers only its
    scheduled KV blocks. No per-block loop — a loop would dynamic-slice
    the (possibly model-sharded) q-block axis and force GSPMD to
    replicate the attention compute on every shard.

    q (B,Sq,Hk,G,D); k, v (B,Sk,Hk,D).
    """
    b, sq, hk, g, d = q.shape
    sk = k.shape[1]
    bq, bk = schedule.block_q, schedule.block_k
    nqb = sq // bq
    kv_idx = jnp.asarray(schedule.kv_index)  # (nqb, w)
    valid = jnp.asarray(schedule.valid)  # (nqb, w)
    w = kv_idx.shape[1]

    qb = q.reshape(b, nqb, bq, hk, g, d)
    kb = k.reshape(b, sk // bk, bk, hk, d)
    vb = v.reshape(b, sk // bk, bk, hk, d)
    kg = jnp.take(kb, kv_idx, axis=1)  # (b, nqb, w, bk, hk, d)
    vg = jnp.take(vb, kv_idx, axis=1)

    s = (
        jnp.einsum(
            "biqhgd,biwkhd->bihgqwk", qb, kg,
            preferred_element_type=jnp.float32,
        )
        * sm_scale
    )  # (b, nqb, hk, g, bq, w, bk)
    kpos = kv_idx[:, :, None] * bk + jnp.arange(bk)[None, None, :]  # (nqb,w,bk)
    ok = (valid[:, :, None] == 1) & jnp.ones((1, 1, bk), bool)
    if causal:
        qpos = (
            jnp.arange(nqb)[:, None] * bq + jnp.arange(bq)[None, :]
        )  # (nqb, bq)
        ok = ok[:, None] & (kpos[:, None] <= qpos[..., None, None])
        # ok: (nqb, bq, w, bk)
        s = jnp.where(ok[None, :, None, None], s, -jnp.inf)
    else:
        s = jnp.where(ok[None, :, None, None, None], s, -jnp.inf)
    sf = s.reshape(*s.shape[:-2], w * bk)
    m = sf.max(axis=-1, keepdims=True)
    m = jnp.where(jnp.isneginf(m), 0.0, m)
    p = jnp.exp(sf - m)
    l = p.sum(axis=-1, keepdims=True)
    p = (p / jnp.where(l == 0.0, 1.0, l)).reshape(s.shape)
    out = jnp.einsum(
        "bihgqwk,biwkhd->biqhgd", p.astype(vg.dtype), vg,
        preferred_element_type=jnp.float32,
    ).astype(q.dtype)
    return out.reshape(b, sq, hk, g, d)


def _pixelfly_visible(
    qpos: jax.Array,
    kpos: jax.Array,
    *,
    block: int,
    local_blocks: int,
    global_blocks: int,
    max_stride: int,
) -> jax.Array:
    """Elementwise causal pixelfly visibility by *absolute* positions.

    Exactly the causal-visible entries of
    ``attn_pattern.pixelfly_attention_block_mask`` on a power-of-two
    block grid: local window, global cross, and the butterfly strides
    (``qb ^ kb`` a power of two below the stride cap). Causal-visible
    entries never depend on the total block count — the stretched-grid
    construction only moves entries above the diagonal — so this rule is
    bucket-size invariant and a cached prefix sees the same mask its
    donor prefill used."""
    qb = qpos // block
    kb = kpos // block
    diff = qb ^ kb
    stride = (diff > 0) & ((diff & (diff - 1)) == 0)
    if max_stride:
        from repro.core.butterfly import next_pow2

        stride &= diff < next_pow2(max_stride)
    vis = (
        (kb < global_blocks)
        | (qb < global_blocks)
        | ((qb >= kb) & (qb - kb < local_blocks))
        | stride
    )
    return vis & (kpos <= qpos)


def prefix_flash_attention_jnp(
    q: jax.Array,
    k_suf: jax.Array,
    v_suf: jax.Array,
    k_pre: jax.Array,
    v_pre: jax.Array,
    prefix_len: jax.Array,
    *,
    sm_scale: float,
    chunk: int,
    block_cfg: tuple[int, int, int, int] | None = None,
) -> jax.Array:
    """Partial-prefill attention: suffix queries over [prefix ; suffix].

    q (B,Sq,Hk,G,D) are the *uncached suffix* queries, sitting at
    absolute positions ``prefix_len[b] + i``; k_suf/v_suf (B,Sq,Hk,D)
    their keys; k_pre/v_pre (B,Lp,Hk,D) the cached prefix K/V gathered
    through the page table (rows valid where j < prefix_len[b] — the
    rest is trash-page padding). One lax.scan over concatenated KV
    chunks with online softmax, like ``flash_attention_jnp`` but with
    per-row masks (prefix validity + causal on absolute positions).

    ``block_cfg`` = (block, local_blocks, global_blocks, max_stride)
    applies the elementwise pixelfly causal mask (``_pixelfly_visible``)
    so a sparse-attention model's partial prefill matches its full
    prefill; requires ``prefix_len`` to be block-aligned. None = dense
    causal.
    """
    sq = q.shape[1]
    lp = k_pre.shape[1]
    k = jnp.concatenate([k_pre.astype(k_suf.dtype), k_suf], axis=1)
    v = jnp.concatenate([v_pre.astype(v_suf.dtype), v_suf], axis=1)
    sk = lp + sq
    chunk = min(chunk, sk)
    pad = (-sk) % chunk
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
    q32 = q.astype(jnp.float32) * sm_scale
    qpos = prefix_len[:, None] + jnp.arange(sq)[None, :]  # (B, Sq) abs

    def mask_fn(ci):
        j = ci * chunk + jnp.arange(chunk)  # flat [prefix ; suffix] index
        is_pre = j[None, :] < lp
        # absolute key positions: prefix token j sits at j, suffix token
        # j - lp at prefix_len + (j - lp); padded tails land beyond every
        # query position and die to the causal mask
        kpos = jnp.where(
            is_pre, j[None, :], prefix_len[:, None] + (j[None, :] - lp)
        )  # (B, chunk)
        valid = jnp.where(
            is_pre, j[None, :] < prefix_len[:, None], j[None, :] < sk
        )
        mask = (
            valid[:, None, :]
            & (kpos[:, None, :] <= qpos[:, :, None])
        )  # (B, Sq, chunk)
        if block_cfg is not None:
            blk, loc, glo, stride = block_cfg
            mask &= _pixelfly_visible(
                qpos[:, :, None],
                kpos[:, None, :],
                block=blk,
                local_blocks=loc,
                global_blocks=glo,
                max_stride=stride,
            )
        return mask[:, None, None]

    return _online_softmax_scan(q32, q.dtype, k, v, chunk, mask_fn)


def decode_attention_jnp(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    sm_scale: float,
) -> jax.Array:
    """Single-token decode: q (B,1,Hk,G,D) vs cache (B,S,Hk,D), valid <= pos."""
    s = _grouped_logits(q, k_cache) * sm_scale  # (B,Hk,G,1,S)
    sk = k_cache.shape[1]
    ok = jnp.arange(sk) <= pos
    s = jnp.where(ok[None, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_out(p.astype(v_cache.dtype), v_cache).astype(q.dtype)


def sparse_decode_attention_jnp(
    q: jax.Array,
    k_cache: jax.Array,
    v_cache: jax.Array,
    pos: jax.Array,
    *,
    sm_scale: float,
    block: int,
    local_blocks: int,
    global_blocks: int,
) -> jax.Array:
    """Beyond-paper: pixelfly-sparse *decode* — the current token's query
    attends only to its butterfly/local/global key blocks, so a 500k-token
    cache costs O(b·log n) reads instead of O(n). Block indices are computed
    from ``pos`` with the same XOR rule as the static pattern.
    """
    b_, _, hk, g, d = q.shape
    smax = k_cache.shape[1]
    nb = smax // block
    # identity page table: the contiguous cache is the paged layout with
    # logical block == physical block, so the schedule helper is shared
    table = jnp.arange(nb, dtype=jnp.int32)[None]
    idx, _, first = paged_sparse_schedule(
        table,
        jnp.asarray(pos)[None],
        block,
        local_blocks=local_blocks,
        global_blocks=global_blocks,
    )
    idx, first = idx[0], first[0]  # (w,)
    kg = jnp.take(k_cache.reshape(b_, nb, block, hk, d), idx, axis=1)
    vg = jnp.take(v_cache.reshape(b_, nb, block, hk, d), idx, axis=1)
    w = idx.shape[0]
    kg = kg.reshape(b_, w * block, hk, d)
    vg = vg.reshape(b_, w * block, hk, d)
    s = _grouped_logits(q, kg) * sm_scale
    kpos = (idx[:, None] * block + jnp.arange(block)[None, :]).reshape(-1)
    ok = kpos <= pos
    s = jnp.where(ok[None, None, None, None, :], s, -jnp.inf)
    # Duplicate blocks (XOR collisions) would double-count keys.
    ok2 = jnp.repeat(first, block)
    s = jnp.where(ok2[None, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return _grouped_out(p.astype(vg.dtype), vg).astype(q.dtype)


def paged_sparse_schedule(
    page_table: jax.Array,
    pos: jax.Array,
    page: int,
    *,
    local_blocks: int,
    global_blocks: int,
) -> tuple[jax.Array, jax.Array, jax.Array]:
    """Per-slot pixelfly decode schedule over a paged cache.

    The cache page is the attention block, so the sparse decode schedule
    is a page-id computation: global anchors + local window + butterfly
    XOR strides of the slot's *current* block, clamped causal. Returns
    ``(logical, phys, keep)``, each (B, w): logical block ids, physical
    page ids (mapped through ``page_table``), and a first-occurrence mask
    disabling duplicate slots (XOR collisions would double-count keys).
    Shared by the jnp gather path and the Pallas kernel's scalar
    prefetch, so both read exactly the same pages.
    """
    b, np_ = page_table.shape
    cur = (pos // page).astype(jnp.int32)  # (B,) current logical block
    n_str = int(math.log2(np_)) if np_ > 1 else 0
    idx = [jnp.full((b,), i, jnp.int32) for i in range(global_blocks)]
    for j in range(local_blocks):
        idx.append(jnp.maximum(cur - j, 0))
    for t in range(n_str):
        idx.append(cur ^ (1 << t))
    idx = jnp.stack(idx, axis=1)  # (B, w) logical block ids
    idx = jnp.minimum(idx, jnp.maximum(cur, 0)[:, None])  # causal blocks only
    w = idx.shape[1]
    phys = jnp.take_along_axis(page_table, idx, axis=1)  # (B, w)
    order = jnp.argsort(idx, axis=1, stable=True)
    sorted_idx = jnp.take_along_axis(idx, order, axis=1)
    newgrp = jnp.concatenate(
        [jnp.ones((b, 1), bool), jnp.diff(sorted_idx, axis=1) != 0], axis=1
    )
    keep = jnp.zeros((b, w), bool).at[jnp.arange(b)[:, None], order].set(
        newgrp
    )
    return idx, phys, keep


def gather_pages(pool: jax.Array, layer, rows: jax.Array) -> jax.Array:
    """Read pages ``rows`` (B, n) of layer ``layer`` out of a stacked
    head-major pool (L, n_pages, Hk, page, D) into a contiguous
    (B, n * page, Hk, D) key/value sequence in logical order — the
    portable read the Pallas kernel is checked against. The layer is
    indexed inside the one gather, so no per-layer pool is sliced out."""
    b, n = rows.shape
    _, _, hk, page, d = pool.shape
    g = pool[layer, rows]  # (B, n, Hk, page, D)
    return g.swapaxes(2, 3).reshape(b, n * page, hk, d)


def write_paged_token(
    pool: jax.Array,
    layer,
    phys: jax.Array,
    off: jax.Array,
    new: jax.Array,
) -> jax.Array:
    """Write each slot's new token row ``new`` (B, Hk, D) into row
    ``off`` (B,) of page ``phys`` (B,) at layer ``layer`` of a stacked
    head-major pool (L, n_pages, Hk, page, D); returns the pool.

    Each slot's token lands in its own page (the engine's copy-on-write
    guard makes the slot that page's only writer); idle slots all route
    to the shared trash page 0 (never read back), where last-write-wins
    is harmless. Each slot's whole (Hk, page, D) page is read, its row
    replaced and the page written back: whole tiles keep the pool in its
    resting layout, so a donated pool is updated in place — a scatter of
    single rows makes the compiler relayout the whole pool around it."""
    page = pool.shape[3]
    row = jax.lax.broadcasted_iota(jnp.int32, (1, 1, page, 1), 2)
    pages = jnp.where(
        row == off[:, None, None, None],
        new[:, :, None].astype(pool.dtype),
        pool[layer, phys],
    )
    return pool.at[layer, phys].set(pages)


def _paged_attention_kernel(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer,
    logical: jax.Array,
    phys: jax.Array,
    keep: jax.Array,
    pos: jax.Array,
    *,
    sm_scale: float,
    impl: str,
) -> jax.Array:
    """Dispatch a (B,1,Hk,G,D) paged decode read to the Pallas kernel."""
    from repro.kernels.paged_attention import paged_decode_attention_pallas

    o = paged_decode_attention_pallas(
        q[:, 0],
        k_pages,
        v_pages,
        layer,
        phys,
        logical,
        keep,
        pos,
        sm_scale=sm_scale,
        interpret=(impl == "interpret"),
    )
    return o[:, None]


def paged_decode_attention_jnp(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    sm_scale: float,
    impl: str | None = None,
) -> jax.Array:
    """Decode against a block-paged KV cache (dense over logical pages).

    q (B,1,Hk,G,D); k_pages, v_pages (L, n_pages, Hk, page, D) stacked
    pools, read at layer ``layer`` (int32 scalar; one layer's pool is
    ``pool[None]`` at 0); page_table (B, P) int32 physical page per
    logical page; pos (B,) int32 position of the *current* token per
    slot. Unallocated table entries point at the shared trash page 0 —
    their keys land beyond ``pos`` and are masked.

    ``impl``: None/"gather" -> portable jnp gathers (the reference
    oracle); "pallas"/"interpret" -> the fused Pallas kernel reading the
    pools in place (``repro.kernels.paged_attention``).
    """
    if impl not in (None, "gather", "pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    b = q.shape[0]
    page = k_pages.shape[3]
    np_ = page_table.shape[1]
    if impl in ("pallas", "interpret"):
        logical = jnp.broadcast_to(
            jnp.arange(np_, dtype=jnp.int32)[None], (b, np_)
        )
        keep = jnp.ones((b, np_), jnp.int32)
        return _paged_attention_kernel(
            q, k_pages, v_pages, layer, logical, page_table, keep, pos,
            sm_scale=sm_scale, impl=impl,
        )
    kg = gather_pages(k_pages, layer, page_table)
    vg = gather_pages(v_pages, layer, page_table)
    s = _grouped_logits(q, kg) * sm_scale  # (B,Hk,G,1,S)
    ok = jnp.arange(np_ * page)[None, :] <= pos[:, None]  # logical order
    s = jnp.where(ok[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return _grouped_out(p.astype(vg.dtype), vg).astype(q.dtype)


def paged_sparse_decode_attention_jnp(
    q: jax.Array,
    k_pages: jax.Array,
    v_pages: jax.Array,
    layer,
    page_table: jax.Array,
    pos: jax.Array,
    *,
    sm_scale: float,
    local_blocks: int,
    global_blocks: int,
    impl: str | None = None,
) -> jax.Array:
    """Pixelfly-sparse paged decode: each slot's query gathers only the KV
    *pages* its butterfly/local/global schedule visits — the cache page is
    the attention block, so the sparse schedule is a page-id computation.
    O(b·log n) page reads per token instead of O(n). Shapes as in
    ``paged_decode_attention_jnp`` but with per-slot page gathers; same
    ``impl`` switch (the Pallas kernel prefetches the page-id schedule).
    """
    if impl not in (None, "gather", "pallas", "interpret"):
        raise ValueError(f"unknown impl {impl!r}")
    b = q.shape[0]
    page = k_pages.shape[3]
    idx, phys, keep = paged_sparse_schedule(
        page_table, pos, page,
        local_blocks=local_blocks, global_blocks=global_blocks,
    )
    if impl in ("pallas", "interpret"):
        return _paged_attention_kernel(
            q, k_pages, v_pages, layer, idx, phys, keep, pos,
            sm_scale=sm_scale, impl=impl,
        )
    kg = gather_pages(k_pages, layer, phys)
    vg = gather_pages(v_pages, layer, phys)
    s = _grouped_logits(q, kg) * sm_scale
    kpos = (
        idx[:, :, None] * page + jnp.arange(page)[None, None, :]
    ).reshape(b, -1)
    ok = kpos <= pos[:, None]
    s = jnp.where(ok[:, None, None, None, :], s, -jnp.inf)
    ok2 = jnp.repeat(keep, page, axis=1)
    s = jnp.where(ok2[:, None, None, None, :], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    p = jnp.where(jnp.isnan(p), 0.0, p)
    return _grouped_out(p.astype(vg.dtype), vg).astype(q.dtype)


# ----------------------------------------------------------------------
# Attention module
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class AttnSpec:
    cfg: ModelConfig

    def _lin(self, din: int, dout: int, bias: bool) -> LinearSpec:
        c = self.cfg
        if c.sparse:
            return LinearSpec.pixelfly(
                din,
                dout,
                c.sparse_density,
                block=c.sparse_block,
                lowrank_frac=c.lowrank_frac,
                use_bias=bias,
                dtype=c.jdtype,
            )
        return LinearSpec.dense(din, dout, use_bias=bias, dtype=c.jdtype)

    @property
    def wq(self) -> LinearSpec:
        return self._lin(self.cfg.d_model, self.cfg.q_dim, self.cfg.qkv_bias)

    @property
    def wk(self) -> LinearSpec:
        return self._lin(self.cfg.d_model, self.cfg.kv_dim, self.cfg.qkv_bias)

    @property
    def wv(self) -> LinearSpec:
        return self._lin(self.cfg.d_model, self.cfg.kv_dim, self.cfg.qkv_bias)

    @property
    def wo(self) -> LinearSpec:
        return self._lin(self.cfg.q_dim, self.cfg.d_model, False)


def init_attention(key: jax.Array, spec: AttnSpec) -> dict:
    c = spec.cfg
    ks = jax.random.split(key, 4)
    p = {
        "wq": init_linear(ks[0], spec.wq),
        "wk": init_linear(ks[1], spec.wk),
        "wv": init_linear(ks[2], spec.wv),
        "wo": init_linear(ks[3], spec.wo),
    }
    if c.qk_norm:
        p["q_norm"] = jnp.ones((c.head_dim,), jnp.float32)
        p["k_norm"] = jnp.ones((c.head_dim,), jnp.float32)
    return p


@functools.lru_cache(maxsize=64)
def _train_schedule(
    seq_q: int, seq_k: int, block: int, local: int, stride: int, glob: int
) -> ap.BlockSchedule:
    mask = ap.pixelfly_attention_block_mask(
        seq_q,
        seq_k,
        ap.AttentionPatternConfig(
            block=block,
            local_blocks=local,
            max_stride=stride,
            global_blocks=glob,
        ),
        causal=True,
    )
    return ap.block_schedule(mask, block, block)


def apply_attention(
    spec: AttnSpec,
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    mode: str = "train",  # train | prefill | decode[_sparse] | decode_paged[_sparse]
    cache: dict | None = None,
    pos: jax.Array | None = None,
    page_table: jax.Array | None = None,
    impl: str | None = None,
    paged_impl: str | None = None,
    layer: jax.Array | None = None,
):
    """Returns (y, new_cache). x: (B, S, D) [S=1 for decode].

    Paged decode modes: ``cache`` holds the group's stacked slot-shared
    page pools ``k``/``v`` (L, n_pages, Hk, page, D) and ``layer`` (int32
    scalar) the layer this block is; the token is written and the pages
    read at that layer, in place, and the updated stacked pools are
    returned. ``prefill_prefix`` reads one layer's pools (n_pages, Hk,
    page, D). In both, ``pos`` is per-slot (B,) and ``page_table`` (B, P)
    maps each slot's logical pages to physical ones. ``paged_impl``
    selects the paged decode read: None/"gather" portable jnp gathers, or
    "pallas"/"interpret" for the fused page-pool kernel.

    Named scopes mark the parts in a profiler trace: ``attn_proj`` (the
    q/k/v and output projections, with qk-norm and rope), and in the
    paged modes ``kv_write`` (the new token's write into the pool) and
    ``paged_attention`` (the read).
    """
    c = spec.cfg
    b, s, _ = x.shape
    hk, g, d = c.num_kv_heads, c.num_heads // c.num_kv_heads, c.head_dim
    scale = d ** -0.5

    with jax.named_scope("attn_proj"):
        q = apply_linear(spec.wq, params["wq"], x, impl=impl)
        k = apply_linear(spec.wk, params["wk"], x, impl=impl)
        v = apply_linear(spec.wv, params["wv"], x, impl=impl)
        q = q.reshape(b, s, c.num_heads, d)
        k = k.reshape(b, s, hk, d)
        v = v.reshape(b, s, hk, d)
        if c.qk_norm:
            q = head_rmsnorm(params["q_norm"], q, c.norm_eps)
            k = head_rmsnorm(params["k_norm"], k, c.norm_eps)
        q = apply_rope(q, positions, c.rope_theta, c.mrope_sections)
        k = apply_rope(k, positions, c.rope_theta, c.mrope_sections)
        qg = q.reshape(b, s, hk, g, d)
    if mode in ("train", "prefill", "prefill_prefix"):
        aspec = _attn_activation_specs(c, s)
        if aspec is not None:
            qg = constrain(c, qg, *aspec["q"])
            k = constrain(c, k, *aspec["kv"])
            v = constrain(c, v, *aspec["kv"])

    new_cache = cache
    if mode == "prefill_prefix":
        # Cache-aware partial prefill: ``cache`` holds the slot-shared
        # page pools, ``page_table`` (B, P_pre) the *cached prefix*
        # pages, ``pos`` (B,) the per-request prefix lengths (page
        # multiples; 0 for misses). Suffix queries attend the gathered
        # full-prefix keys plus their own causal window; the fresh
        # suffix K/V is returned for the caller's page scatter, the
        # shared prefix pages are read-only.
        assert cache is not None and pos is not None and page_table is not None
        kp = gather_pages(cache["k"][None], 0, page_table)
        vp = gather_pages(cache["v"][None], 0, page_table)
        block_cfg = (
            (
                c.attn_block,
                c.attn_local_blocks,
                c.attn_global_blocks,
                c.attn_max_stride,
            )
            if c.sparse_attention
            and s >= c.attn_block
            and s % c.attn_block == 0
            else None
        )
        o = prefix_flash_attention_jnp(
            qg, k, v, kp, vp, pos,
            sm_scale=scale, chunk=c.attn_chunk, block_cfg=block_cfg,
        )
        new_cache = {"k": k, "v": v}
        aspec = _attn_activation_specs(c, s)
        if aspec is not None:
            o = constrain(c, o, *aspec["o"])
    elif mode in ("decode_paged", "decode_paged_sparse"):
        assert cache is not None and pos is not None and page_table is not None
        assert layer is not None
        page = cache["k"].shape[3]
        # tensor-parallel decode: head-partition the fresh K/V and the
        # grouped queries on the same axis the pool shards, so the write
        # scatter and the attention read stay shard-local (the only
        # collective left is wo's psum).
        pool_ax = paged_pool_entry(c, hk, d)
        ba = c.batch_axes or None
        if pool_ax == -3:
            qg = constrain(c, qg, ba, None, "model", None, None)
            k = constrain(c, k, ba, None, "model", None)
            v = constrain(c, v, ba, None, "model", None)
        elif pool_ax == -1:
            qg = constrain(c, qg, ba, None, None, None, "model")
            k = constrain(c, k, ba, None, None, "model")
            v = constrain(c, v, ba, None, None, "model")
        with jax.named_scope("kv_write"):
            phys = jnp.take_along_axis(
                page_table, (pos // page)[:, None], axis=1
            )
            phys = phys[:, 0]
            off = pos % page
            kc = constrain_paged_pool(
                c, write_paged_token(cache["k"], layer, phys, off, k[:, 0])
            )
            vc = constrain_paged_pool(
                c, write_paged_token(cache["v"], layer, phys, off, v[:, 0])
            )
        new_cache = {"k": kc, "v": vc}
        with jax.named_scope("paged_attention"):
            if mode == "decode_paged_sparse" and page == c.attn_block:
                o = paged_sparse_decode_attention_jnp(
                    qg,
                    kc,
                    vc,
                    layer,
                    page_table,
                    pos,
                    sm_scale=scale,
                    local_blocks=c.attn_local_blocks,
                    global_blocks=c.attn_global_blocks,
                    impl=paged_impl,
                )
            else:
                o = paged_decode_attention_jnp(
                    qg, kc, vc, layer, page_table, pos, sm_scale=scale,
                    impl=paged_impl,
                )
    elif mode in ("decode", "decode_sparse"):
        assert cache is not None and pos is not None
        kc = jax.lax.dynamic_update_slice(
            cache["k"], k.astype(cache["k"].dtype), (0, pos, 0, 0)
        )
        vc = jax.lax.dynamic_update_slice(
            cache["v"], v.astype(cache["v"].dtype), (0, pos, 0, 0)
        )
        new_cache = {"k": kc, "v": vc}
        smax = kc.shape[1]
        if mode == "decode_sparse" and (
            smax % c.attn_block or smax < 2 * c.attn_block
        ):
            mode = "decode"  # cache too small/ragged for block gathers
        if mode == "decode_sparse":
            o = sparse_decode_attention_jnp(
                qg,
                kc,
                vc,
                pos,
                sm_scale=scale,
                block=c.attn_block,
                local_blocks=c.attn_local_blocks,
                global_blocks=c.attn_global_blocks,
            )
        else:
            o = decode_attention_jnp(qg, kc, vc, pos, sm_scale=scale)
    else:
        use_sparse = (
            c.sparse_attention and s >= c.attn_block and s % c.attn_block == 0
        )
        if use_sparse:
            sched = _train_schedule(
                s,
                s,
                c.attn_block,
                c.attn_local_blocks,
                c.attn_max_stride,
                c.attn_global_blocks,
            )
            if impl in ("pallas", "interpret"):
                qf = q.transpose(0, 2, 1, 3)
                kf = jnp.repeat(k, g, axis=2).transpose(0, 2, 1, 3)
                vf = jnp.repeat(v, g, axis=2).transpose(0, 2, 1, 3)
                o = ops.block_sparse_attention(
                    qf, kf, vf, sched, causal=True, sm_scale=scale, impl=impl
                )
                o = o.transpose(0, 2, 1, 3).reshape(b, s, hk, g, d)
            else:
                o = sparse_attention_jnp(
                    qg, k, v, sched, causal=True, sm_scale=scale
                )
        else:
            o = flash_attention_jnp(
                qg, k, v, causal=True, chunk=c.attn_chunk, sm_scale=scale
            )
        if mode == "prefill":
            new_cache = {"k": k, "v": v}
        aspec = _attn_activation_specs(c, s)
        if aspec is not None:
            o = constrain(c, o, *aspec["o"])
    o = o.reshape(b, s, c.q_dim)
    with jax.named_scope("attn_proj"):
        y = apply_linear(spec.wo, params["wo"], o, impl=impl)
    ba = c.batch_axes or None
    y = constrain(c, y, ba, *([None] * (y.ndim - 1)))
    return y, new_cache


# ----------------------------------------------------------------------
# MLP (SwiGLU)
# ----------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MlpSpec:
    cfg: ModelConfig
    d_ff: int

    def _lin(self, din: int, dout: int) -> LinearSpec:
        c = self.cfg
        if c.sparse:
            return LinearSpec.pixelfly(
                din,
                dout,
                c.sparse_density,
                block=c.sparse_block,
                lowrank_frac=c.lowrank_frac,
                dtype=c.jdtype,
            )
        return LinearSpec.dense(din, dout, dtype=c.jdtype)

    @property
    def wg(self) -> LinearSpec:
        return self._lin(self.cfg.d_model, self.d_ff)

    @property
    def wu(self) -> LinearSpec:
        return self._lin(self.cfg.d_model, self.d_ff)

    @property
    def wd(self) -> LinearSpec:
        return self._lin(self.d_ff, self.cfg.d_model)


def init_mlp(key: jax.Array, spec: MlpSpec) -> dict:
    ks = jax.random.split(key, 3)
    return {
        "wg": init_linear(ks[0], spec.wg),
        "wu": init_linear(ks[1], spec.wu),
        "wd": init_linear(ks[2], spec.wd),
    }


def apply_mlp(
    spec: MlpSpec, params: dict, x: jax.Array, *, impl: str | None = None
) -> jax.Array:
    c = spec.cfg
    ba = c.batch_axes or None
    gate = apply_linear(spec.wg, params["wg"], x, impl=impl)
    up = apply_linear(spec.wu, params["wu"], x, impl=impl)
    if c.tp_size and c.tp_size > 1 and spec.d_ff % c.tp_size == 0:
        hid = (ba, *([None] * (x.ndim - 2)), "model")
        gate = constrain(c, gate, *hid)
        up = constrain(c, up, *hid)
    h = jax.nn.silu(gate.astype(jnp.float32)).astype(x.dtype) * up
    y = apply_linear(spec.wd, params["wd"], h, impl=impl)
    return constrain(c, y, ba, *([None] * (y.ndim - 1)))


# ----------------------------------------------------------------------
# Embeddings / head
# ----------------------------------------------------------------------


def init_embedding(key: jax.Array, cfg: ModelConfig) -> dict:
    v = cfg.padded_vocab
    p = {
        "tok": (
            jax.random.normal(key, (v, cfg.d_model), jnp.float32) * 0.02
        ).astype(cfg.jdtype)
    }
    return p


def embed_tokens(cfg: ModelConfig, params: dict, tokens: jax.Array) -> jax.Array:
    return jnp.take(params["tok"], tokens, axis=0)


def init_lm_head(key: jax.Array, cfg: ModelConfig) -> dict:
    if cfg.tie_embeddings:
        return {}
    v = cfg.padded_vocab
    std = 1.0 / math.sqrt(cfg.d_model)
    return {
        "w": (
            jax.random.normal(key, (cfg.d_model, v), jnp.float32) * std
        ).astype(cfg.jdtype)
    }


def lm_logits(
    cfg: ModelConfig, head: dict, embed: dict, x: jax.Array
) -> jax.Array:
    with jax.named_scope("lm_head"):
        w = embed["tok"].T if cfg.tie_embeddings else head["w"]
        return jnp.einsum(
            "...d,dv->...v", x, w, preferred_element_type=jnp.float32
        )
