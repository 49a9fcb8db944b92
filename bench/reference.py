"""Plain float32 reference of the served models (qwen3 with and without
pixelfly), independent of the program under test.

It follows the published Qwen3 decoder: token embedding; per layer
RMSNorm -> q/k/v projections -> per-head RMSNorm of q and k -> rotary
embedding (half split, theta from the config) -> grouped-query causal
attention -> output projection -> residual; RMSNorm -> SwiGLU MLP ->
residual; final RMSNorm; logits against the tied embedding. It runs one
layer at a time over a whole sequence, attention in blocks of queries,
with ``jax.default_matmul_precision("highest")``, so it fits on the chip
after the program's state is freed.

The pixelfly twin (paper §3.3) replaces each projection by
``W = gamma * B + (1 - gamma) * U V^T`` where ``B`` is a flat block
butterfly, built here as a dense masked matrix from the pattern this
module derives from the config, and masks attention by blocks: a key
block is visible to a query block when it is among the first
``global_blocks``, within ``local_blocks`` behind it, or when the two
block ids differ in exactly one bit (a butterfly stride), for strides
below the butterfly grid: the largest power of two not above the
slot's block count (``max_len / block``), as the paged decode schedule
reads it.

``precision="fp8"`` is the control: every matrix product takes its
operands through float8 e4m3 with one scale per tensor for weights and
per row for activations, as an fp8 serving path would.
"""

from __future__ import annotations

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["ModelSpec", "Reference", "pixelfly_cols", "split_sparse_lowrank"]

QUERY_BLOCK = 512
HEAD_ROWS = 256
# sequences are padded to a multiple of this, so a few compiled shapes
# serve every request
SEQ_PAD = 2048


# ----------------------------------------------------------------------
# Pixelfly pattern arithmetic (paper §3.3; the program keeps its own)
# ----------------------------------------------------------------------


def next_pow2(x: int) -> int:
    return 1 << (x - 1).bit_length() if x > 1 else 1


def _max_stride_for_density(n_in: int, b: int, density: float) -> int:
    nb_in = max(1, n_in // b)
    g = next_pow2(nb_in)
    slots = max(1, int(density * n_in / b))
    return max(1, 1 << min(slots - 1, g.bit_length() - 1))


def split_sparse_lowrank(
    n_out: int, n_in: int, density: float, block: int, lowrank_frac: float
) -> tuple[int, int]:
    """(rank, max butterfly stride) for a layer's density budget: about
    ``lowrank_frac`` of it to U V^T, rank a multiple of 32, the rest to
    the widest flat butterfly that fits."""
    total = density * n_out * n_in
    per_rank = n_out + n_in
    rank = int(lowrank_frac * total / per_rank)
    rank = max(32, (rank // 32) * 32)
    while rank > 32 and rank * per_rank > 1.5 * lowrank_frac * total:
        rank -= 32
    remaining = max(0.0, total - rank * per_rank)
    dens = remaining / (n_out * n_in)
    return rank, _max_stride_for_density(n_in, block, max(dens, block / n_in))


def pixelfly_cols(n_out: int, n_in: int, block: int, max_stride: int) -> np.ndarray:
    """(nb_out, r) input block feeding each slot of each output block:
    the block diagonal and strides 1, 2, .., max_stride/2, on the
    smallest power-of-two grid covering both sides, stretched back."""
    nb_out, nb_in = n_out // block, n_in // block
    g = next_pow2(max(nb_out, nb_in))
    k = min(next_pow2(max_stride), g)
    strides = [1 << t for t in range(k.bit_length() - 1)]
    cols = np.empty((nb_out, 1 + len(strides)), np.int32)
    for i in range(nb_out):
        gi = i * g // nb_out
        cols[i] = [c * nb_in // g for c in [gi] + [gi ^ s for s in strides]]
    return cols


@dataclasses.dataclass(frozen=True)
class ModelSpec:
    layers: int
    d_model: int
    heads: int
    kv_heads: int
    head_dim: int
    d_ff: int
    vocab: int
    rope_theta: float
    norm_eps: float
    sparse: bool = False
    density: float = 0.2
    block: int = 128
    lowrank_frac: float = 0.25
    attn_block: int = 128
    local_blocks: int = 2
    global_blocks: int = 1

    def linear_shape(self, name: str) -> tuple[int, int]:
        d, q, kv, f = self.d_model, self.heads * self.head_dim, self.kv_heads * self.head_dim, self.d_ff
        return {
            "attn/wq": (d, q), "attn/wk": (d, kv), "attn/wv": (d, kv),
            "attn/wo": (q, d), "mlp/wg": (d, f), "mlp/wu": (d, f),
            "mlp/wd": (f, d),
        }[name]

    def pattern(self, name: str) -> tuple[np.ndarray, int, int]:
        """(cols, rank, block) of a pixelfly projection."""
        n_in, n_out = self.linear_shape(name)
        b = self.block
        while b > 8 and (n_in % b or n_out % b):
            b //= 2
        rank, stride = split_sparse_lowrank(n_out, n_in, self.density, b, self.lowrank_frac)
        return pixelfly_cols(n_out, n_in, b, stride), rank, b


# ----------------------------------------------------------------------
# Precision
# ----------------------------------------------------------------------


def _fp8(x: jax.Array, axis) -> jax.Array:
    """Round ``x`` through float8 e4m3 with one scale per slice over
    ``axis`` (None: the whole tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def _mm(a: jax.Array, w: jax.Array, fp8: bool) -> jax.Array:
    """a (..., k) @ w (k, n) in float32, operands through fp8 if asked."""
    if fp8:
        a, w = _fp8(a, -1), _fp8(w, None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def _rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    """x (L, H, D), pos (L,): rotate halves."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


# ----------------------------------------------------------------------
# The reference
# ----------------------------------------------------------------------


class Reference:
    """``logits(tokens, read)``: float32 logits at positions ``read`` of
    the sequence ``tokens`` (prompt then served tokens)."""

    def __init__(self, spec: ModelSpec, weights, max_len: int, *, precision: str = "f32"):
        if precision not in ("f32", "fp8"):
            raise ValueError(f"unknown precision {precision!r}")
        self.spec = spec
        self.weights = weights  # bench.weights.LayerWeights
        self.fp8 = precision == "fp8"
        nblk = max(1, max_len // spec.attn_block)
        self.stride_grid = 1 << (nblk.bit_length() - 1)
        self._layer_fn = jax.jit(self._layer)
        self._head_fn = jax.jit(self._head)
        self._dense_w = jax.jit(self._dense_weights)

    # -- weights ---------------------------------------------------------
    def _dense_weights(self, w: dict) -> dict:
        """Projection matrices (in, out) of one layer, pixelfly made dense."""
        s = self.spec
        out = {}
        for name in ("attn/wq", "attn/wk", "attn/wv", "attn/wo", "mlp/wg", "mlp/wu", "mlp/wd"):
            if not s.sparse:
                out[name] = w[f"{name}/w"]
                continue
            n_in, n_out = s.linear_shape(name)
            cols, rank, b = s.pattern(name)
            blocks = w[f"{name}/blocks"]
            if blocks.shape != (n_out // b, cols.shape[1], b, b):
                raise ValueError(f"{name}: blocks {blocks.shape} do not fit the pattern")
            if w[f"{name}/U"].shape != (n_in, rank):
                raise ValueError(f"{name}: U {w[f'{name}/U'].shape} is not rank {rank}")
            rows = np.broadcast_to(np.arange(n_out // b)[:, None], cols.shape)
            wb = jnp.zeros((n_in // b, n_out // b, b, b), jnp.float32)
            wb = wb.at[cols, rows].add(blocks)
            wb = wb.transpose(0, 2, 1, 3).reshape(n_in, n_out)
            g = w[f"{name}/gamma"]
            low = jnp.matmul(w[f"{name}/U"], w[f"{name}/V"].T, precision=jax.lax.Precision.HIGHEST)
            out[name] = g * wb + (1.0 - g) * low
        for k in ("attn_norm/scale", "mlp_norm/scale", "attn/q_norm", "attn/k_norm"):
            out[k] = w[k]
        return out

    # -- one layer over the whole sequence -----------------------------
    def _visible(self, qpos, kpos):
        s = self.spec
        causal = kpos[None, :] <= qpos[:, None]
        if not s.sparse:
            return causal
        qb = (qpos // s.attn_block)[:, None]
        kb = (kpos // s.attn_block)[None, :]
        diff = qb ^ kb
        stride = (diff > 0) & ((diff & (diff - 1)) == 0) & (diff < self.stride_grid)
        local = (qb >= kb) & (qb - kb < s.local_blocks)
        glob = (kb < s.global_blocks) | (qb < s.global_blocks)
        return causal & (local | glob | stride)

    def _layer(self, x, w, n_real):
        s = self.spec
        fp8 = self.fp8
        L = x.shape[0]
        pos = jnp.arange(L)
        h = _rmsnorm(x, w["attn_norm/scale"], s.norm_eps)
        q = _mm(h, w["attn/wq"], fp8).reshape(L, s.heads, s.head_dim)
        k = _mm(h, w["attn/wk"], fp8).reshape(L, s.kv_heads, s.head_dim)
        v = _mm(h, w["attn/wv"], fp8).reshape(L, s.kv_heads, s.head_dim)
        q = _rope(_rmsnorm(q, w["attn/q_norm"], s.norm_eps), pos, s.rope_theta)
        k = _rope(_rmsnorm(k, w["attn/k_norm"], s.norm_eps), pos, s.rope_theta)
        g = s.heads // s.kv_heads
        kr = jnp.repeat(k, g, axis=1)  # (L, H, D): head h reads kv head h // g
        vr = jnp.repeat(v, g, axis=1)
        kmask = pos < n_real

        def block(qi):
            start = qi * QUERY_BLOCK
            qb = jax.lax.dynamic_slice_in_dim(q, start, QUERY_BLOCK, 0)
            qpos = start + jnp.arange(QUERY_BLOCK)
            if fp8:
                sc = jnp.einsum("qhd,khd->hqk", _fp8(qb, -1), _fp8(kr, -1),
                                precision=jax.lax.Precision.HIGHEST)
            else:
                sc = jnp.einsum("qhd,khd->hqk", qb, kr, precision=jax.lax.Precision.HIGHEST)
            sc = sc * s.head_dim ** -0.5
            ok = self._visible(qpos, pos) & kmask[None, :]
            sc = jnp.where(ok[None], sc, -jnp.inf)
            p = jax.nn.softmax(sc, axis=-1)
            vv = _fp8(vr, 0) if fp8 else vr
            pp = _fp8(p, -1) if fp8 else p
            return jnp.einsum("hqk,khd->qhd", pp, vv, precision=jax.lax.Precision.HIGHEST)

        o = jax.lax.map(block, jnp.arange(L // QUERY_BLOCK))
        o = o.reshape(L, s.heads * s.head_dim)
        x = x + _mm(o, w["attn/wo"], fp8)
        h = _rmsnorm(x, w["mlp_norm/scale"], s.norm_eps)
        a = jax.nn.silu(_mm(h, w["mlp/wg"], fp8)) * _mm(h, w["mlp/wu"], fp8)
        return x + _mm(a, w["mlp/wd"], fp8)

    def _head(self, x, read, scale, emb):
        h = _rmsnorm(x[read], scale, self.spec.norm_eps)
        if self.fp8:
            h, emb = _fp8(h, -1), _fp8(emb, None)
        return jnp.einsum("qd,vd->qv", h, emb, precision=jax.lax.Precision.HIGHEST)

    def logits(self, tokens: np.ndarray, read: np.ndarray) -> np.ndarray:
        """(len(read), vocab) float32 logits predicting the token after
        each position in ``read`` of ``tokens``."""
        return self.logits_many([tokens], [read])[0]

    def logits_many(self, seqs: list, reads: list) -> list[np.ndarray]:
        """``logits`` for several sequences, layer by layer: each layer's
        weights are made once for all of them."""
        top = self.weights.top()
        xs, ns = [], []
        for tokens in seqs:
            n = int(tokens.size)
            L = -(-n // SEQ_PAD) * SEQ_PAD
            padded = np.zeros((L,), np.int32)
            padded[:n] = tokens
            xs.append(top["embed/tok"][jnp.asarray(padded)])
            ns.append(n)
        for li in range(self.spec.layers):
            w = self._dense_w(self.weights.layer(li))
            xs = [self._layer_fn(x, w, jnp.int32(n)) for x, n in zip(xs, ns)]
        emb = top["embed/tok"][: self.spec.vocab]
        out = []
        for x, read in zip(xs, reads):
            read = np.asarray(read, np.int32)
            rows_out = []
            for i in range(0, read.size, HEAD_ROWS):
                rows = np.zeros((HEAD_ROWS,), np.int32)
                chunk = read[i : i + HEAD_ROWS]
                rows[: chunk.size] = chunk
                got = self._head_fn(x, jnp.asarray(rows), top["final_norm/scale"], emb)
                rows_out.append(np.asarray(got)[: chunk.size])
            out.append(np.concatenate(rows_out))
        return out
