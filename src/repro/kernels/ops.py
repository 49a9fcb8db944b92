"""Public jit'd entry points for the kernels, with backend dispatch.

``impl`` selects between:
  - "pallas"     : the Pallas TPU kernel (compiled; TPU only)
  - "interpret"  : the Pallas kernel body interpreted on CPU (validation)
  - "gather"     : portable pure-jnp path with *sparse* FLOPs (default off
                   TPU; this is what the multi-pod dry-run lowers)
  - "dense_mask" : masked dense GEMM oracle (tests only)

``default_impl()`` picks per-platform so model code never hard-codes one.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref
from repro.kernels.bsr_attention import block_sparse_attention_pallas
from repro.kernels.bsr_matmul import bsr_matmul_pallas

__all__ = [
    "default_impl",
    "impl_for_mesh",
    "bsr_matmul",
    "block_sparse_attention",
]


def default_impl() -> str:
    try:
        platform = jax.devices()[0].platform
    except RuntimeError:  # pragma: no cover - no backend at all
        platform = "cpu"
    return "pallas" if platform == "tpu" else "gather"


def impl_for_mesh(impl: str | None, n_devices: int) -> str | None:
    """Clamp a kernel impl (None = the platform default) for a mesh.

    A Pallas TPU kernel has no SPMD partitioning rule: XLA refuses to
    partition a Mosaic call over sharded operands ("wrap the call in a
    shard_map"). The jnp gather paths are built from ops GSPMD
    partitions natively, so an engine spanning several devices takes
    them; a single-device mesh keeps the requested impl.
    """
    if n_devices > 1 and impl in (None, "pallas", "interpret"):
        return "gather"
    return impl


def bsr_matmul(
    x: jax.Array,
    blocks: jax.Array,
    cols: jax.Array,
    *,
    impl: str | None = None,
) -> jax.Array:
    """y = x @ W for a flat-block-butterfly BSR weight.

    x: (..., n_in) -> (..., nb_out * b). Leading dims are flattened for the
    Pallas path and restored after.
    """
    impl = impl or default_impl()
    if impl == "gather":
        if isinstance(cols, np.ndarray):
            # static table -> scatter-free custom-VJP path (§Perf C2)
            return ref.bsr_matmul_custom_vjp(x, blocks, cols)
        return ref.bsr_matmul_gather(x, blocks, cols)
    if impl == "dense_mask":
        return ref.bsr_matmul_dense_mask(x, blocks, cols)
    if impl in ("pallas", "interpret"):
        *lead, n_in = x.shape
        b = int(np.prod(lead)) if lead else 1
        nb_out, _, blk, _ = blocks.shape
        # Pad the flattened batch to a tile multiple.
        bm = min(256, max(8, b))
        pad = (-b) % bm
        x2 = x.reshape(b, n_in)
        if pad:
            x2 = jnp.pad(x2, ((0, pad), (0, 0)))
        y = bsr_matmul_pallas(
            x2, blocks, cols, bm=bm, interpret=(impl == "interpret")
        )
        if pad:
            y = y[:b]
        return y.reshape(*lead, nb_out * blk).astype(x.dtype)
    raise ValueError(f"unknown impl {impl!r}")


def block_sparse_attention(
    q: jax.Array,
    k: jax.Array,
    v: jax.Array,
    schedule,
    *,
    causal: bool = False,
    sm_scale: float | None = None,
    impl: str | None = None,
) -> jax.Array:
    """Block-sparse attention. q,k,v: (B, H, S, D); schedule: BlockSchedule
    (plus its originating boolean block mask, used by the reference path).
    """
    impl = impl or default_impl()
    scale = sm_scale if sm_scale is not None else q.shape[-1] ** -0.5
    if impl in ("gather", "dense_mask"):
        from repro.core.attn_pattern import BlockSchedule  # noqa: F401

        mask = _schedule_to_block_mask(schedule, k.shape[-2])
        return ref.block_sparse_attention_ref(
            q,
            k,
            v,
            mask,
            block_q=schedule.block_q,
            block_k=schedule.block_k,
            causal=causal,
            sm_scale=scale,
        )
    if impl in ("pallas", "interpret"):
        b, h, s, d = q.shape
        sk = k.shape[-2]
        qf = q.reshape(b * h, s, d)
        kf = k.reshape(b * h, sk, d)
        vf = v.reshape(b * h, sk, d)
        out = block_sparse_attention_pallas(
            qf,
            kf,
            vf,
            jnp.asarray(schedule.kv_index),
            jnp.asarray(schedule.valid),
            sm_scale=scale,
            causal=causal,
            block_q=schedule.block_q,
            block_k=schedule.block_k,
            interpret=(impl == "interpret"),
        )
        return out.reshape(b, h, s, d)
    raise ValueError(f"unknown impl {impl!r}")


def _schedule_to_block_mask(schedule, seq_k: int) -> np.ndarray:
    nkb = -(-seq_k // schedule.block_k)
    mask = np.zeros((schedule.nqb, nkb), dtype=bool)
    for i in range(schedule.nqb):
        for t in range(schedule.max_nkv):
            if schedule.valid[i, t]:
                mask[i, schedule.kv_index[i, t]] = True
    return mask
