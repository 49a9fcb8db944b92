"""Building blocks of the plain references: float32 products at the
highest precision, their fp8 control, norms, rotary embedding, and the
pixelfly pattern arithmetic (paper §3.3), all independent of the program
under test (which keeps its own).

The pixelfly twin of a projection is ``W = gamma * B + (1 - gamma) * U
V^T`` where ``B`` is a flat block butterfly: about ``lowrank_frac`` of
the layer's density budget goes to ``U V^T``, the rest to the widest
flat butterfly that fits.

The fp8 control takes every matrix product's operands through float8
e4m3 with one scale per tensor for weights and per row for activations,
as an fp8 serving path would.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

__all__ = ["next_pow2", "split_sparse_lowrank", "pixelfly_cols", "fp8", "mm", "rmsnorm", "rope"]


# ----------------------------------------------------------------------
# Pixelfly pattern arithmetic
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


# ----------------------------------------------------------------------
# Precision
# ----------------------------------------------------------------------


def fp8(x: jax.Array, axis) -> jax.Array:
    """Round ``x`` through float8 e4m3 with one scale per slice over
    ``axis`` (None: the whole tensor)."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=axis is not None)
    s = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (x / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s


def mm(a: jax.Array, w: jax.Array, fp8_: bool) -> jax.Array:
    """a (..., k) @ w (k, n) in float32, operands through fp8 if asked."""
    if fp8_:
        a, w = fp8(a, -1), fp8(w, None)
    return jnp.matmul(a, w, precision=jax.lax.Precision.HIGHEST)


def rmsnorm(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x (L, H, D), pos (L,): rotate halves."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
