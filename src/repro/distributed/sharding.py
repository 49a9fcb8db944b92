"""Sharding rules: parameter / batch / cache PartitionSpecs per mesh.

Two layouts (DESIGN.md §4), selected per architecture by the launcher:

- ``Strategy("tp")``: tensor/expert parallel over ``model`` on the natural
  tensor axis (heads / ffn / experts / vocab / butterfly block-rows), FSDP
  (ZeRO-3) over ``data`` (+``pod``) on a second axis, batch over
  (pod, data). For big models.
- ``Strategy("fsdp")``: no TP — all axes are data axes; batch shards over
  everything and parameters are FSDP-sharded where divisible. For small
  models, where TP-16 would be dominated by per-layer activation
  collectives (measured: smollm-360m on 16x16 spent 26ms/step on
  collectives under TP vs ~0 under FSDP).

All rules are divisibility-guarded; anything non-divisible falls back to
replication so every (arch x shape x mesh) cell lowers.
"""

from __future__ import annotations

import re
from typing import Any

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.launch.mesh import MODEL_AXIS

__all__ = [
    "Strategy",
    "param_specs",
    "param_shardings",
    "batch_specs",
    "cache_specs",
    "named",
    "constrain_pools",
]


class Strategy:
    """How the mesh axes are used ("tp" vs "fsdp" — see module docstring)."""

    def __init__(self, mesh: Mesh, kind: str = "tp"):
        if kind not in ("tp", "fsdp"):
            raise ValueError(kind)
        self.kind = kind
        self.mesh = mesh
        names = mesh.axis_names
        if kind == "tp":
            self.model_axis: str | None = (
                MODEL_AXIS if MODEL_AXIS in names else None
            )
            self.fsdp: tuple[str, ...] = tuple(
                a for a in ("pod", "data") if a in names
            )
        else:
            self.model_axis = None
            self.fsdp = tuple(
                a for a in ("pod", "data", "model") if a in names
            )
        self.batch: tuple[str, ...] = self.fsdp

    @property
    def tp_size(self) -> int:
        return self.mesh.shape[self.model_axis] if self.model_axis else 1


def _axsize(mesh: Mesh, axes) -> int:
    if not axes:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    return int(np.prod([mesh.shape[a] for a in axes]))


def _fits(mesh: Mesh, dim: int, axes) -> bool:
    return axes is not None and dim % _axsize(mesh, axes) == 0


def _maybe(st: Strategy, dim: int, axes):
    if not axes:
        return None
    return axes if _fits(st.mesh, dim, axes) else None


def _batch_axes_for(st: Strategy, dim: int):
    """Largest suffix of the batch axes that divides ``dim`` (None if only
    a trivial size-1 sharding remains)."""
    axes = st.batch
    while axes and dim % _axsize(st.mesh, axes) != 0:
        axes = axes[1:]
    if not axes or _axsize(st.mesh, axes) == 1:
        return None
    return axes


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        else:
            parts.append(str(k))
    return "/".join(parts)


def _greedy(st: Strategy, dims: tuple[int, ...]) -> list:
    """Put model then fsdp on the largest divisible dims."""
    entries: list[Any] = [None] * len(dims)
    used: set[int] = set()
    for axes in (st.model_axis, st.fsdp or None):
        if axes is None:
            continue
        cands = [
            d
            for d in range(len(dims))
            if d not in used and _fits(st.mesh, dims[d], axes)
            and dims[d] >= _axsize(st.mesh, axes)
        ]
        if cands:
            d = max(cands, key=lambda i: dims[i])
            entries[d] = axes
            used.add(d)
    return entries


def _param_spec(st: Strategy, path: str, shape: tuple[int, ...]) -> P:
    mesh = st.mesh
    ma, fsdp = st.model_axis, st.fsdp or None
    # Scanned groups carry a leading layer dim; shared groups (zamba2's
    # shared attention block) are stored unstacked.
    stacked = path.startswith("groups/") and not path.startswith(
        "groups/shared_"
    )
    lead = 1 if stacked else 0
    dims = shape[lead:]

    def pad(*entries):
        return P(*([None] * lead), *entries)

    # ---- embeddings / lm head
    if re.search(r"embed/tok$", path):
        return P(_maybe(st, shape[0], ma), _maybe(st, shape[1], fsdp))
    if re.search(r"head/w$", path):
        return P(_maybe(st, shape[0], fsdp), _maybe(st, shape[1], ma))

    # ---- MoE experts (E, ...): expert-parallel over model
    if "/moe/" in path:
        if re.search(r"/router$", path):
            return pad(None, _maybe(st, dims[-1], ma))
        if re.search(r"/moe/w[gud]($|/)", path):
            ent = [None] * len(dims)
            ent[0] = _maybe(st, dims[0], ma)
            if ent[0] is None and fsdp:  # fsdp strategy: shard experts on fsdp
                ent[0] = _maybe(st, dims[0], fsdp)
                return pad(*ent)
            cands = [
                d for d in range(len(dims) - 1, 0, -1)
                if fsdp and _fits(mesh, dims[d], fsdp)
            ]
            if cands:
                d = max(cands, key=lambda i: dims[i])
                ent[d] = fsdp
            return pad(*ent)

    # ---- pixelfly sparse linears
    if re.search(r"/blocks$", path):  # (nb_out, r, b, b)
        nb, r, b1, b2 = dims
        if _fits(mesh, nb, ma):
            return pad(ma, None, None, _maybe(st, b2, fsdp))
        return pad(
            _maybe(st, nb, fsdp), None, None, _maybe(st, b2, ma)
        )
    if re.search(r"/U$", path):
        return pad(_maybe(st, dims[0], fsdp), None)
    if re.search(r"/V$", path):
        spec0 = _maybe(st, dims[0], ma) or _maybe(st, dims[0], fsdp)
        return pad(spec0, None)

    # ---- dense linears
    if re.search(r"/(wo|wd|out_proj)/w$", path):
        return pad(_maybe(st, dims[0], ma), _maybe(st, dims[1], fsdp))
    if re.search(r"/(wq|wk|wv|wg|wu|in_proj|w1|w2|qkv|proj)/w$", path):
        return pad(_maybe(st, dims[0], fsdp), _maybe(st, dims[1], ma))
    if re.search(r"/b$", path) and len(dims) == 1:
        return pad(_maybe(st, dims[0], ma))

    # ---- ssm internals
    if re.search(r"/conv_w$", path):
        return pad(None, _maybe(st, dims[1], ma))

    if len(dims) <= 1:
        return pad(*([None] * len(dims)))
    return pad(*_greedy(st, dims))


def param_specs(st: Strategy, params) -> Any:
    return jax.tree_util.tree_map_with_path(
        lambda p, a: _param_spec(st, _path_str(p), tuple(a.shape)), params
    )


def named(mesh_or_st, tree):
    mesh = mesh_or_st.mesh if isinstance(mesh_or_st, Strategy) else mesh_or_st
    return jax.tree.map(lambda s: NamedSharding(mesh, s), tree)


def constrain_pools(pools, shardings):
    """Pin the paged-pool layout on an in-jit pool write (the PR 7
    invariant jaxlint enforces as JL005): without the constraint GSPMD
    is free to materialize the whole pool under a different layout
    around the ``.at[...].set`` and reshard it back.  ``shardings`` is
    the pool-shaped tree of ``NamedSharding`` from ``PagedKVCache`` (or
    None on a single-device engine, where this is a no-op).  Unlike the
    ``PartitionSpec``-based ``layers.constrain_paged_pool``, a
    ``NamedSharding`` carries its mesh, so callers need no ambient
    ``with mesh:`` context."""
    if shardings is None:
        return pools
    return jax.tree.map(
        lambda b, s: jax.lax.with_sharding_constraint(b, s), pools, shardings
    )


def param_shardings(st: Strategy, params):
    return named(st, param_specs(st, params))


def batch_specs(st: Strategy, batch) -> Any:
    """Shard every batch leaf's leading (batch) dim as much as divisible."""

    def spec(a):
        if a.ndim == 0:
            return P()
        return P(_batch_axes_for(st, a.shape[0]), *([None] * (a.ndim - 1)))

    return jax.tree.map(spec, batch)


def _paged_pool_spec(st: Strategy, shape: tuple[int, ...]) -> P:
    """Slot-shared head-major page pools: ``(count, n_pages, kv_heads,
    page, head_dim)`` leaves (or the per-layer 4-dim view inside a group
    scan). The page axes are allocator-owned — any physical page may map
    into any slot's table, and the host rewrites the page table every
    step — so they must stay replicated; only the *head* axes shard.
    kv_heads on the model axis is classic head-parallel attention;
    head_dim is the fallback for GQA head counts the mesh doesn't
    divide. Data axes replicate: data parallelism over serving traffic
    is replica routing at the engine layer (``serving.router``), not a
    sharded pool. Must agree with ``layers.paged_pool_entry`` — the
    in-jit constraint and the buffer sharding pin the same layout."""
    ent: list[Any] = [None] * len(shape)
    ma = st.model_axis
    if ma and len(shape) >= 3:
        for d in (len(shape) - 3, len(shape) - 1):
            if _fits(st.mesh, shape[d], ma) and shape[d] >= _axsize(
                st.mesh, ma
            ):
                ent[d] = ma
                break
    return P(*ent)


def cache_specs(st: Strategy, caches, *, layout: str = "decode") -> Any:
    """Decode caches: (count, B, ...) leaves. Batch over the data axes when
    divisible, model on the LAST divisible trailing dim (head_dim/state) —
    not the sequence dim, where a seq-sharded KV cache forces GSPMD to
    reshard around every dynamic_update_slice.

    ``layout="paged"`` switches to the serving engine's slot-shared page
    pools, whose leaves are (count, n_pages, kv_heads, page, head_dim)
    with no batch dim at all — see ``_paged_pool_spec``."""
    mesh = st.mesh
    if layout == "paged":
        return jax.tree.map(
            lambda a: _paged_pool_spec(st, tuple(a.shape)), caches
        )
    if layout != "decode":
        raise ValueError(f"unknown cache layout {layout!r}")

    def spec(a):
        if a.ndim <= 1:
            return P(*([None] * a.ndim))
        ent = [None] * a.ndim
        baxes = _batch_axes_for(st, a.shape[1])
        batch_sharded = bool(baxes) and a.shape[1] >= _axsize(mesh, baxes)
        if batch_sharded:
            ent[1] = baxes
        if st.model_axis:
            cands = [
                d
                for d in range(2, a.ndim)
                if _fits(mesh, a.shape[d], st.model_axis)
                and a.shape[d] >= _axsize(mesh, st.model_axis)
            ]
            if cands:
                ent[cands[-1]] = st.model_axis
        if not batch_sharded and st.batch:
            # batch=1 long-context decode: shard the longest remaining dim
            # (the 500k sequence axis) over the data axes instead of
            # replicating a multi-GB cache on every device.
            cands = [
                d
                for d in range(2, a.ndim)
                if ent[d] is None and _fits(mesh, a.shape[d], st.batch)
                and a.shape[d] >= _axsize(mesh, st.batch)
            ]
            if cands:
                d = max(cands, key=lambda i: a.shape[i])
                ent[d] = st.batch
        return P(*ent)

    return jax.tree.map(spec, caches)
