"""Unified decoder LM covering all assigned families.

A model is a sequence of *layer groups* (``cfg.layer_groups()``); each group
is a run of structurally identical blocks scanned with ``jax.lax.scan`` (+
``jax.checkpoint`` for training), so HLO size and compile time are O(#groups)
— not O(depth) — even for the 95-layer / 61-layer configs. Shared groups
(zamba2's shared attention block) reuse one parameter subtree at several
positions but keep per-position caches.

Entry points:
  forward_train(cfg, params, batch)            -> (loss, metrics)
  prefill(cfg, params, batch, max_len)         -> (logits, cache)
  decode_step(cfg, params, cache, tokens, pos) -> (logits, cache)

Serving (block-paged KV cache, ``repro.serving``):
  init_paged_cache(cfg, n_pages, page)         -> paged cache pools
  prefill_paged(cfg, params, tokens, plens, caches, page_rows)
                                               -> ((N, V) last-real-token logits, caches)
                                               [batched: N requests, one bucket]
  decode_step_paged(cfg, params, caches, tokens, positions, page_table)
                                               -> (logits, caches)  [ragged positions]
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.analysis.guards import hot_path
from repro.configs.base import GroupSpec, ModelConfig
from repro.models import layers as L
from repro.models import moe as moe_lib
from repro.models import ssm as ssm_lib

__all__ = [
    "init_model",
    "init_cache",
    "init_paged_cache",
    "forward_train",
    "prefill",
    "prefill_paged",
    "decode_step",
    "decode_step_paged",
    "param_count",
]


# ----------------------------------------------------------------------
# Block init / apply (one layer)
# ----------------------------------------------------------------------


def _block_specs(cfg: ModelConfig, kind: str):
    if kind in ("dense", "shared_attn"):
        d_ff = cfg.d_ff
        if kind == "dense" and cfg.family == "moe" and cfg.moe_dense_ff:
            d_ff = cfg.moe_dense_ff
        return {"attn": L.AttnSpec(cfg), "mlp": L.MlpSpec(cfg, d_ff)}
    if kind == "moe":
        return {"attn": L.AttnSpec(cfg), "moe": moe_lib.MoeSpec(cfg)}
    if kind == "ssm":
        return {"ssm": ssm_lib.SsmSpec(cfg)}
    raise ValueError(kind)


def _init_block(key: jax.Array, cfg: ModelConfig, kind: str) -> dict:
    specs = _block_specs(cfg, kind)
    ks = jax.random.split(key, 4)
    if kind in ("dense", "shared_attn"):
        return {
            "attn_norm": L.init_rmsnorm(cfg.d_model),
            "attn": L.init_attention(ks[0], specs["attn"]),
            "mlp_norm": L.init_rmsnorm(cfg.d_model),
            "mlp": L.init_mlp(ks[1], specs["mlp"]),
        }
    if kind == "moe":
        return {
            "attn_norm": L.init_rmsnorm(cfg.d_model),
            "attn": L.init_attention(ks[0], specs["attn"]),
            "mlp_norm": L.init_rmsnorm(cfg.d_model),
            "moe": moe_lib.init_moe(ks[1], specs["moe"]),
        }
    if kind == "ssm":
        return {
            "norm": L.init_rmsnorm(cfg.d_model),
            "ssm": ssm_lib.init_ssm(ks[0], specs["ssm"]),
        }
    raise ValueError(kind)


def _is_placeholder(c) -> bool:
    return c is None or (isinstance(c, jax.Array) and c.size == 0)


def _apply_block(
    cfg: ModelConfig,
    kind: str,
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    mode: str,
    cache: dict | None,
    pos: jax.Array | None,
    impl: str | None,
    page_table: jax.Array | None = None,
    paged_impl: str | None = None,
    layer: jax.Array | None = None,
):
    """Returns (x, new_cache, lb_loss). ``cache`` may be a zero-size
    placeholder array (cache-less scan); it is normalized to None here and a
    placeholder is returned when the block produces no cache. In paged
    decode ``cache`` is the group's stacked pools and ``layer`` the block's
    index into them.

    The block's parts run under named scopes (``norm``, ``mlp``, and
    inside ``apply_attention`` ``attn_proj``, ``kv_write`` and
    ``paged_attention``): they name its operations in a profiler trace
    and change nothing else in the program."""
    if _is_placeholder(cache):
        cache = None
    specs = _block_specs(cfg, kind)
    lb = jnp.zeros((), jnp.float32)
    # Pin the residual stream's batch sharding at every block boundary so
    # GSPMD never drifts into replicating tokens inside the layer scan.
    ba = cfg.batch_axes or None
    x = L.constrain(cfg, x, ba, *([None] * (x.ndim - 1)))
    if kind == "ssm":
        with jax.named_scope("norm"):
            h = L.rmsnorm(params["norm"], x, cfg.norm_eps)
        if mode in ("decode", "decode_sparse"):
            y, cache = ssm_lib.apply_ssm_decode(
                specs["ssm"], params["ssm"], h, cache, impl=impl
            )
        else:
            y, cache = ssm_lib.apply_ssm_train(
                specs["ssm"],
                params["ssm"],
                h,
                impl=impl,
                return_state=(mode == "prefill"),
            )
        if cache is None:
            cache = jnp.zeros((0,))
        return x + y, cache, lb

    with jax.named_scope("norm"):
        h = L.rmsnorm(params["attn_norm"], x, cfg.norm_eps)
    attn_mode = mode
    if mode == "decode" and cfg.sparse_attention:
        attn_mode = "decode_sparse"
    if mode == "decode_paged" and cfg.sparse_attention:
        attn_mode = "decode_paged_sparse"
    y, cache = L.apply_attention(
        specs["attn"],
        params["attn"],
        h,
        positions,
        mode=attn_mode,
        cache=cache,
        pos=pos,
        page_table=page_table,
        impl=impl,
        paged_impl=paged_impl,
        layer=layer,
    )
    x = x + y
    with jax.named_scope("norm"):
        h = L.rmsnorm(params["mlp_norm"], x, cfg.norm_eps)
    with jax.named_scope("mlp"):
        if kind == "moe":
            y, aux = moe_lib.apply_moe(
                specs["moe"], params["moe"], h, impl=impl
            )
            lb = aux["lb_loss"]
        else:
            y = L.apply_mlp(specs["mlp"], params["mlp"], h, impl=impl)
    if cache is None:
        cache = jnp.zeros((0,))
    return x + y, cache, lb


# ----------------------------------------------------------------------
# Model init
# ----------------------------------------------------------------------


def init_model(key: jax.Array, cfg: ModelConfig) -> dict:
    groups = cfg.layer_groups()
    n_keys = len(groups) + 3
    ks = jax.random.split(key, n_keys)
    params: dict = {
        "embed": L.init_embedding(ks[0], cfg),
        "head": L.init_lm_head(ks[1], cfg),
        "final_norm": L.init_rmsnorm(cfg.d_model),
        "groups": {},
    }
    done: set[str] = set()
    for i, g in enumerate(groups):
        if g.param_key in done:
            continue
        done.add(g.param_key)
        kg = ks[3 + i]
        if g.shared or g.count == 1:
            p = _init_block(kg, cfg, g.kind)
            if not g.shared:
                p = jax.tree.map(lambda a: a[None], p)  # still scanned
            params["groups"][g.param_key] = p
        else:
            layer_keys = jax.random.split(kg, g.count)
            params["groups"][g.param_key] = jax.vmap(
                lambda k: _init_block(k, cfg, g.kind)
            )(layer_keys)
    return params


def param_count(params) -> int:
    return sum(int(x.size) for x in jax.tree.leaves(params))


# ----------------------------------------------------------------------
# Caches
# ----------------------------------------------------------------------


def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> list:
    """One cache entry per layer group, stacked over the group's layers."""
    caches = []
    for g in cfg.layer_groups():
        if g.kind == "ssm":
            spec = ssm_lib.SsmSpec(cfg)
            one = ssm_lib.init_ssm_cache(spec, batch, cfg.jdtype)
        else:
            one = {
                "k": jnp.zeros(
                    (batch, max_len, cfg.num_kv_heads, cfg.head_dim),
                    cfg.jdtype,
                ),
                "v": jnp.zeros(
                    (batch, max_len, cfg.num_kv_heads, cfg.head_dim),
                    cfg.jdtype,
                ),
            }
        caches.append(jax.tree.map(lambda a: jnp.broadcast_to(
            a[None], (g.count,) + a.shape), one))
    return caches


def init_paged_cache(
    cfg: ModelConfig, n_pages: int, page: int, *, shardings: list | None = None
) -> list:
    """Slot-shared page pools, one per layer group (stacked over layers).

    Leaves are head-major ``(count, n_pages, kv_heads, page, head_dim)``:
    one (page, head_dim) tile per page and head, which is the block the
    Pallas paged-decode kernel reads. Physical page 0 is reserved as the
    trash page (idle slots and unallocated table entries point at it);
    the serving allocator hands out pages 1..n_pages-1. ``shardings`` (a
    matching tree of ``NamedSharding``, built from
    ``sharding.cache_specs(layout="paged")``) places each pool across the
    mesh at creation — head axes sharded on the model axis, page axes
    replicated — so tensor-parallel decode never starts from a
    single-device pool.
    """
    caches = []
    for g in cfg.layer_groups():
        if g.kind == "ssm":
            raise NotImplementedError(
                "paged serving caches cover attention families; SSM state "
                "is slot-indexed, not paged"
            )
        caches.append(
            {
                "k": jnp.zeros(
                    (g.count, n_pages, cfg.num_kv_heads, page, cfg.head_dim),
                    cfg.jdtype,
                ),
                "v": jnp.zeros(
                    (g.count, n_pages, cfg.num_kv_heads, page, cfg.head_dim),
                    cfg.jdtype,
                ),
            }
        )
    if shardings is not None:
        caches = jax.tree.map(jax.device_put, caches, shardings)
    return caches


# ----------------------------------------------------------------------
# Group execution (scan over layers)
# ----------------------------------------------------------------------


def _run_group(
    cfg: ModelConfig,
    g: GroupSpec,
    gparams: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    mode: str,
    cache,
    pos,
    impl,
    page_table=None,
    paged_impl=None,
):
    """Scan ``g.count`` blocks. Returns (x, new_cache, lb_sum)."""
    if g.shared:
        # one param set reused; caches still stacked per occurrence
        stacked_p = jax.tree.map(
            lambda a: jnp.broadcast_to(a[None], (g.count,) + a.shape), gparams
        )
    else:
        stacked_p = gparams
    if mode == "decode_paged":
        return _run_group_paged_decode(
            cfg, g, stacked_p, x, positions, pools=cache, pos=pos,
            impl=impl, page_table=page_table, paged_impl=paged_impl,
        )

    def body(carry, xs):
        xc, lb_sum = carry
        p, c_in = xs
        xc, c_out, lb = _apply_block(
            cfg, g.kind, p, xc, positions,
            mode=mode, cache=c_in, pos=pos, impl=impl,
            page_table=page_table, paged_impl=paged_impl,
        )
        return (xc, lb_sum + lb), c_out

    body_fn = body
    if cfg.remat and mode == "train":
        body_fn = jax.checkpoint(body)

    if cache is None:
        cache = jnp.zeros((g.count, 0))  # per-layer placeholder
    init = (x, jnp.zeros((), jnp.float32))
    xs = (stacked_p, cache)

    n1, n2 = _remat_factors(g.count) if (cfg.remat and mode == "train") else (0, 0)
    # the scan's own operations (slicing each layer's inputs and cache
    # out of the stacked ones, stacking the layers' fresh caches) carry
    # this scope and no block scope in a profiler trace
    with jax.named_scope("layer_scan"):
        if n1 > 1 and n2 > 1:
            # Two-level (sqrt-n) remat: the backward pass keeps the residual
            # stream at n1 outer checkpoints instead of all n layers —
            # 95-layer deepseek saves 19+5 activations instead of 95.
            xs2 = jax.tree.map(
                lambda a: a.reshape(n1, n2, *a.shape[1:]), xs
            )

            @jax.checkpoint
            def outer(carry, xs_outer):
                return jax.lax.scan(body_fn, carry, xs_outer)

            (x, lb), cache_out = jax.lax.scan(outer, init, xs2)
            cache_out = jax.tree.map(
                lambda a: a.reshape(n1 * n2, *a.shape[2:]), cache_out
            )
        else:
            (x, lb), cache_out = jax.lax.scan(body_fn, init, xs)
    return x, cache_out, lb


def _run_group_paged_decode(
    cfg: ModelConfig,
    g: GroupSpec,
    stacked_p: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    pools: dict,
    pos,
    impl,
    page_table,
    paged_impl,
):
    """Paged decode's layer scan: the group's stacked pools ride in the
    carry and each block writes and reads them in place at its layer
    index, so the pools stay in the (donated) buffers they came in —
    the other modes' scan would slice every layer's pool out of the
    stack and write a fresh stack back. Returns (x, pools, lb_sum)."""

    def body(carry, xs):
        xc, lb_sum, pools = carry
        p, layer = xs
        xc, pools, lb = _apply_block(
            cfg, g.kind, p, xc, positions,
            mode="decode_paged", cache=pools, pos=pos, impl=impl,
            page_table=page_table, paged_impl=paged_impl, layer=layer,
        )
        return (xc, lb_sum + lb, pools), None

    init = (x, jnp.zeros((), jnp.float32), pools)
    layers = jnp.arange(g.count, dtype=jnp.int32)
    # the scan's own operations (slicing each layer's parameters out of
    # the stacked ones) carry this scope and no block scope in a trace
    with jax.named_scope("layer_scan"):
        (x, lb, pools), _ = jax.lax.scan(body, init, (stacked_p, layers))
    return x, pools, lb


def _remat_factors(n: int) -> tuple[int, int]:
    """Factor n = n1 * n2 with n2 as close to sqrt(n) as possible."""
    best = (n, 1)
    for d in range(2, int(n ** 0.5) + 1):
        if n % d == 0:
            best = (n // d, d)
    return best


# ----------------------------------------------------------------------
# Entry points
# ----------------------------------------------------------------------


def _positions(cfg: ModelConfig, batch: dict, b: int, s: int) -> jax.Array:
    if "positions" in batch:
        return batch["positions"]
    p = jnp.broadcast_to(jnp.arange(s)[None, :], (b, s))
    if cfg.mrope_sections:
        p = jnp.broadcast_to(p[..., None], (b, s, len(cfg.mrope_sections)))
    return p


def _inputs_to_x(cfg: ModelConfig, params: dict, batch: dict) -> jax.Array:
    if "embeds" in batch:
        return batch["embeds"].astype(cfg.jdtype)
    return L.embed_tokens(cfg, params["embed"], batch["tokens"])


def _backbone(
    cfg: ModelConfig,
    params: dict,
    x: jax.Array,
    positions: jax.Array,
    *,
    mode: str,
    caches=None,
    pos=None,
    impl=None,
    page_table=None,
    paged_impl=None,
):
    groups = cfg.layer_groups()
    lb_total = jnp.zeros((), jnp.float32)
    new_caches = []
    for i, g in enumerate(groups):
        c_in = caches[i] if caches is not None else None
        x, c_out, lb = _run_group(
            cfg, g, params["groups"][g.param_key], x, positions,
            mode=mode, cache=c_in, pos=pos, impl=impl,
            page_table=page_table, paged_impl=paged_impl,
        )
        new_caches.append(c_out)
        lb_total = lb_total + lb
    with jax.named_scope("norm"):
        x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return x, new_caches, lb_total


def forward_train(
    cfg: ModelConfig, params: dict, batch: dict, *, impl: str | None = None
):
    """batch: {"tokens" | "embeds", "labels" (B,S) int32} -> (loss, metrics)."""
    x = _inputs_to_x(cfg, params, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, batch, b, s)
    x, _, lb = _backbone(cfg, params, x, positions, mode="train", impl=impl)
    logits = L.lm_logits(cfg, params["head"], params["embed"], x)
    labels = batch["labels"]
    logz = jax.nn.logsumexp(logits, axis=-1)
    # Gold logit via a fused indicator reduce, NOT take_along_axis: a gather
    # along the model-sharded vocab axis would force GSPMD to all-gather the
    # full (B, S, V) logits on every device.
    vocab_iota = jax.lax.broadcasted_iota(jnp.int32, logits.shape, logits.ndim - 1)
    gold = jnp.sum(
        jnp.where(vocab_iota == labels[..., None], logits, 0.0), axis=-1
    )
    mask = (labels >= 0).astype(jnp.float32)
    nll = ((logz - gold) * mask).sum() / jnp.clip(mask.sum(), 1.0)
    loss = nll + 0.01 * lb
    return loss, {"nll": nll, "lb_loss": lb}


def prefill(
    cfg: ModelConfig,
    params: dict,
    batch: dict,
    *,
    impl: str | None = None,
):
    """Full-sequence inference pass; returns (last-token logits, caches)."""
    x = _inputs_to_x(cfg, params, batch)
    b, s, _ = x.shape
    positions = _positions(cfg, batch, b, s)
    x, caches, _ = _backbone(
        cfg, params, x, positions, mode="prefill", impl=impl
    )
    logits = L.lm_logits(cfg, params["head"], params["embed"], x[:, -1])
    return logits, caches


def prefill_paged(
    cfg: ModelConfig,
    params: dict,
    tokens: jax.Array,
    plens: jax.Array,
    caches: list,
    page_rows: jax.Array,
    *,
    prefix_rows: jax.Array | None = None,
    prefix_lens: jax.Array | None = None,
    full_tokens: jax.Array | None = None,
    full_plens: jax.Array | None = None,
    impl: str | None = None,
    sampler: dict | None = None,
    sampler_candidates: int | None = None,
):
    """Batched bucketed prefill into a block-paged KV cache.

    One jit'd full-sequence pass over a whole admission group — no
    per-token loop and no per-request call: ``tokens`` (N, S) holds N
    prompts right-padded to the shared page-multiple bucket ``S``;
    ``plens`` (N,) int32 the real prompt lengths; ``page_rows``
    (N, S//page) each request's physical pages (entries past
    ``pages_for_len(plen)`` point at the trash page 0, so padding keys
    scatter there and real pages stay untouched). The causal schedule
    runs inside ``apply_attention`` prefill mode; keys written for padded
    positions land beyond ``plen`` in logical order and are masked by
    every decode read.

    Cache-aware *partial* prefill (prefix cache hits): when
    ``prefix_lens`` (N,) is given, ``tokens``/``plens`` carry only each
    request's *uncached suffix* (page-aligned — hits cover full pages)
    and ``prefix_rows`` (N, P_pre) the physical pages already holding
    its prefix K/V (trash-padded past the real prefix). Suffix queries
    run at absolute positions ``prefix_len + i`` and attend the full
    prefix through the page table (``apply_attention`` mode
    ``prefill_prefix``); only suffix K/V is computed and scattered —
    shared prefix pages are never written. ``full_tokens``/``full_plens``
    (the whole prompt, any bucket) seed the sampler's presence buffer,
    which must cover cached prefix tokens too.

    Returns (logits at each request's last real token (N, V), updated
    paged caches) — or, when ``sampler`` is given (the engine's packed
    per-request sampling params, ``repro.serving.sampling``), the fused
    first-token sample: (token ids (N,) int32, caches, presence), so the
    host syncs N ints instead of (N, V) logits.
    """
    x = _inputs_to_x(cfg, params, {"tokens": tokens})
    b, s, _ = x.shape
    if prefix_lens is None:
        positions = _positions(cfg, {}, b, s)
        x, kv, _ = _backbone(
            cfg, params, x, positions, mode="prefill", impl=impl
        )
    else:
        positions = prefix_lens[:, None] + jnp.arange(s)[None, :]
        if cfg.mrope_sections:
            positions = jnp.broadcast_to(
                positions[..., None], (b, s, len(cfg.mrope_sections))
            )
        x, kv, _ = _backbone(
            cfg, params, x, positions, mode="prefill_prefix",
            caches=caches, pos=prefix_lens, page_table=prefix_rows,
            impl=impl,
        )
    # (N, d) hidden state at each request's last *real* prompt token
    xe = jnp.take_along_axis(x, (plens - 1)[:, None, None], axis=1)[:, 0]
    logits = L.lm_logits(cfg, params["head"], params["embed"], xe)

    new_caches = []
    for pool, fresh in zip(caches, kv):
        def scat(buf, kvs):
            count, _, hk, page, d = buf.shape
            fb = kvs.reshape(count, b, s // page, page, hk, d).swapaxes(3, 4)
            # page_rows (N, P): scatter every request's pages in one shot.
            # Rows collide only on the shared trash page 0 (padding), where
            # last-write-wins is fine — trash is masked by logical position
            # on every read.
            # pin the pool layout through the scatter: the page axes stay
            # replicated, the head axis keeps its model-axis shard
            return L.constrain_paged_pool(
                cfg, buf.at[:, page_rows].set(fb.astype(buf.dtype))
            )

        new_caches.append(jax.tree.map(scat, pool, fresh))
    if sampler is None:
        return logits, new_caches
    # in-function import: repro.serving imports this module at init time
    from repro.serving import sampling as sampling_lib

    # partial prefill: presence must be seeded from the WHOLE prompt
    # (cached prefix included), not just the suffix this call computed
    ptoks = tokens if full_tokens is None else full_tokens
    pplens = plens if full_plens is None else full_plens
    toks, presence = sampling_lib.sample_prefill(
        logits, ptoks, pplens, sampler, valid_vocab=cfg.vocab_size,
        candidates=sampler_candidates,
    )
    return toks, new_caches, presence


def decode_step(
    cfg: ModelConfig,
    params: dict,
    caches: list,
    tokens: jax.Array,
    pos: jax.Array,
    *,
    impl: str | None = None,
):
    """One decode step. tokens (B,) int32; pos () int32. Returns
    (logits (B, V), new caches)."""
    x = L.embed_tokens(cfg, params["embed"], tokens[:, None])
    b = x.shape[0]
    positions = jnp.broadcast_to(pos[None, None], (b, 1))
    if cfg.mrope_sections:
        positions = jnp.broadcast_to(
            positions[..., None], (b, 1, len(cfg.mrope_sections))
        )
    x, new_caches, _ = _backbone(
        cfg, params, x, positions, mode="decode", caches=caches, pos=pos,
        impl=impl,
    )
    logits = L.lm_logits(cfg, params["head"], params["embed"], x[:, 0])
    return logits, new_caches


@hot_path
def decode_step_paged(
    cfg: ModelConfig,
    params: dict,
    caches: list,
    tokens: jax.Array,
    positions: jax.Array,
    page_table: jax.Array,
    *,
    impl: str | None = None,
    paged_impl: str | None = None,
    sampler: dict | None = None,
    sampler_candidates: int | None = None,
):
    """Slot-indexed decode step over a block-paged KV cache.

    tokens (B,) int32 one token per slot; positions (B,) int32 *ragged*
    per-slot write positions; page_table (B, P) int32 logical -> physical
    page map. Idle slots pass position 0 with an all-trash page row.
    ``paged_impl`` picks the paged attention read ("gather" jnp reference
    vs the "pallas"/"interpret" page-pool kernel). Returns
    (logits (B, V), new caches) — or, when ``sampler`` is given, the
    fused logits->token sample over every slot (ragged occupancy rides
    along: idle slots' samples are ignored host-side):
    (token ids (B,) int32, caches, presence). Either way one host sync
    per step suffices.
    """
    x = L.embed_tokens(cfg, params["embed"], tokens[:, None])
    b = x.shape[0]
    pos2 = positions[:, None]
    if cfg.mrope_sections:
        pos2 = jnp.broadcast_to(
            pos2[..., None], (b, 1, len(cfg.mrope_sections))
        )
    x, new_caches, _ = _backbone(
        cfg, params, x, pos2, mode="decode_paged", caches=caches,
        pos=positions, page_table=page_table, impl=impl,
        paged_impl=paged_impl,
    )
    logits = L.lm_logits(cfg, params["head"], params["embed"], x[:, 0])
    if sampler is None:
        return logits, new_caches
    from repro.serving import sampling as sampling_lib

    toks, presence = sampling_lib.sample_decode(
        logits, sampler, valid_vocab=cfg.vocab_size,
        candidates=sampler_candidates,
    )
    return toks, new_caches, presence
