"""Pallas TPU kernel: flat-block-butterfly (BSR) sparse matmul.

Computes ``y = x @ W`` where ``W`` is an ``(n_in, n_out)`` flat block
butterfly matrix stored as nonzero blocks only:

  x      : (B, n_in)              activations
  blocks : (nb_out, r, b, b)      block slot (i, t) maps input block
                                  ``cols[i, t]`` to output block ``i``
  cols   : (nb_out, r) int32      static column-block index table
  y      : (B, nb_out * b)

TPU adaptation of the paper's Triton DSD block-sparse GEMM:

- grid = (B/bm, nb_out, r): the two outer axes are parallel, the nnz-slot
  axis is an arbitrary (sequential) reduction into the revisited output
  block — output lives in VMEM across the ``t`` loop, so partial sums never
  round-trip to HBM.
- the column-index table rides in scalar memory via
  ``PrefetchScalarGridSpec``; the ``x`` BlockSpec's index_map reads it to
  gather the right input block — the block gather *is* the sparsity.
- ``b`` is a multiple of 128 so every ``jnp.dot`` maps onto full MXU tiles;
  accumulation is fp32 (``preferred_element_type``) regardless of the
  parameter dtype.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["bsr_matmul_pallas"]


def _kernel(cols_ref, x_ref, w_ref, o_ref, acc_ref, *, r: int):
    t = pl.program_id(2)

    @pl.when(t == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    acc_ref[...] += jnp.dot(
        x_ref[...], w_ref[0, 0], preferred_element_type=jnp.float32
    )

    @pl.when(t == r - 1)
    def _flush():
        o_ref[...] = acc_ref[...].astype(o_ref.dtype)


@functools.partial(
    jax.jit, static_argnames=("bm", "interpret", "out_dtype")
)
def bsr_matmul_pallas(
    x: jax.Array,
    blocks: jax.Array,
    cols: jax.Array,
    *,
    bm: int = 256,
    interpret: bool = False,
    out_dtype=None,
) -> jax.Array:
    """``y[:, i*b:(i+1)*b] = sum_t x[:, cols[i,t]*b:...] @ blocks[i, t]``."""
    if x.ndim != 2:
        raise ValueError("x must be (batch, n_in); flatten leading dims first")
    B, n_in = x.shape
    nb_out, r, b, b2 = blocks.shape
    if b != b2:
        raise ValueError("blocks must be square")
    if n_in % b:
        raise ValueError("n_in must be a multiple of the block size")
    bm = min(bm, B)
    if B % bm:
        raise ValueError(f"batch {B} must be a multiple of bm {bm}")
    out_dtype = out_dtype or x.dtype

    grid = (B // bm, nb_out, r)

    def x_map(i, j, t, cols_ref):
        return (i, cols_ref[j, t])

    def w_map(i, j, t, cols_ref):
        del i
        return (j, t, 0, 0)

    def o_map(i, j, t, cols_ref):
        del t
        return (i, j)

    return pl.pallas_call(
        functools.partial(_kernel, r=r),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((bm, b), x_map),
                pl.BlockSpec((1, 1, b, b), w_map),
            ],
            out_specs=pl.BlockSpec((bm, b), o_map),
            scratch_shapes=[pltpu.VMEM((bm, b), jnp.float32)],
        ),
        out_shape=jax.ShapeDtypeStruct((B, nb_out * b), out_dtype),
        interpret=interpret,
        # the kernel's name in a profiler trace
        name="bsr_matmul_pallas",
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary", "arbitrary"),
        ),
    )(cols, x, blocks)
