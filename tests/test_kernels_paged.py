"""Differential parity: Pallas paged-decode kernel vs the jnp paged oracle.

The kernel (`repro.kernels.paged_attention`, run in interpret mode on CPU)
must reproduce ``paged_decode_attention_jnp`` / ``paged_sparse_decode_
attention_jnp`` (impl=None gather paths) across GQA ratios, dtypes,
ragged per-slot positions, partially-filled last pages, partially
allocated page-table rows, and idle slots parked on the trash page.
The trash page is poisoned with huge values so any masking divergence
between the two paths is loud, not a rounding blip. Pools are stacked
over layers ``(L, n_pages, Hk, page, D)`` with distinct contents per
layer, as the layer scan passes them, so a read of the wrong layer
shows too.
"""

import functools

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.models import layers as L

PAGE, PPS, D = 8, 4, 16  # page size, pages per slot, head dim
LAYERS = 3  # stacked pool depth; the tests read layers 0 and 2
ATOL = {jnp.float32: 1e-5, jnp.bfloat16: 1e-2}

# (kv heads, head dim, page, pages per slot) of the parity cases: the
# suite's small geometry, and qwen3-1.7b's head shape (8 kv heads of 128,
# 128-token pages), where the kernel's blocks hold (8, 128, 128) slabs
SMALL = (2, D, PAGE, PPS)
QWEN3_1_7B = (8, 128, 128, 3)
PARITY_CASES = [
    pytest.param(dtype, g, layer, SMALL, id=f"{dtype.__name__}-{g}-{layer}")
    for dtype in (jnp.float32, jnp.bfloat16)
    for g in (1, 2, 4)
    for layer in (0, 2)
] + [
    pytest.param(dtype, 2, 1, QWEN3_1_7B, id=f"qwen3-1.7b-{dtype.__name__}")
    for dtype in (jnp.float32, jnp.bfloat16)
]


def _scenario(b, hk, d, *, seed=0, dtype=jnp.float32, trash_slot=True,
              partial_slot=True, page=PAGE, pps=PPS):
    """Random stacked pools + page table with the parity suite's edge
    cases: ragged positions (partial last pages), a partially-allocated
    row, and an idle slot whose row is all trash page."""
    rng = np.random.default_rng(seed)
    n_pages = b * pps + 1
    # head-major pools stacked over layers, each layer its own contents
    k = rng.standard_normal((LAYERS, n_pages, hk, page, d))
    v = rng.standard_normal((LAYERS, n_pages, hk, page, d))
    # poison the trash page: an unmasked read of page 0 shows up as a
    # huge output delta instead of hiding inside the tolerance
    k[:, 0] = 1e4
    v[:, 0] = -1e4
    perm = rng.permutation(np.arange(1, n_pages))
    table = np.zeros((b, pps), np.int32)
    pos = np.zeros((b,), np.int32)
    nxt = 0
    for s in range(b):
        if partial_slot and s == b - 1 and b > 1:
            n_alloc = 1  # partially-allocated row, trash tail
        else:
            n_alloc = pps
        table[s, :n_alloc] = perm[nxt:nxt + n_alloc]
        nxt += n_alloc
        # ragged: land mid-page so the last page is partially filled
        pos[s] = int(rng.integers(0, n_alloc * page))
    if trash_slot and b > 2:
        table[1] = 0  # idle slot: all-trash row, position 0
        pos[1] = 0
    return (
        jnp.asarray(k, dtype),
        jnp.asarray(v, dtype),
        jnp.asarray(table),
        jnp.asarray(pos),
        rng,
    )


def _q(rng, b, hk, g, d, dtype):
    return jnp.asarray(rng.standard_normal((b, 1, hk, g, d)), dtype)


def _run(fn, dtype):
    """Execute both impls, skipping when the CPU backend can't run the
    interpreted kernel's dtype (same idiom as the bsr attention tests)."""
    ref = fn(None)
    try:
        got = fn("interpret")
        got.block_until_ready()
    except Exception as e:  # pragma: no cover - backend-dependent
        if "Unsupported element type" in str(e):
            pytest.skip("CPU backend cannot execute this dtype")
        raise
    np.testing.assert_allclose(
        np.asarray(got, np.float32),
        np.asarray(ref, np.float32),
        atol=ATOL[dtype],
        rtol=ATOL[dtype],
    )


@pytest.mark.parametrize("dtype, g, layer, geom", PARITY_CASES)
def test_dense_paged_parity(g, dtype, layer, geom):
    b, (hk, d, page, pps) = 4, geom
    k, v, table, pos, rng = _scenario(b, hk, d, dtype=dtype, page=page, pps=pps)
    q = _q(rng, b, hk, g, d, dtype)
    _run(
        lambda impl: L.paged_decode_attention_jnp(
            q, k, v, layer, table, pos, sm_scale=d ** -0.5, impl=impl
        ),
        dtype,
    )


@pytest.mark.parametrize("dtype, g, layer, geom", PARITY_CASES)
def test_sparse_paged_parity(g, dtype, layer, geom):
    b, (hk, d, page, pps) = 4, geom
    k, v, table, pos, rng = _scenario(b, hk, d, dtype=dtype, page=page, pps=pps)
    q = _q(rng, b, hk, g, d, dtype)
    _run(
        lambda impl: L.paged_sparse_decode_attention_jnp(
            q, k, v, layer, table, pos, sm_scale=d ** -0.5,
            local_blocks=2, global_blocks=1, impl=impl,
        ),
        dtype,
    )


def test_kernel_grid_steps_each_page_once_for_all_heads():
    """The kernel's grid is (slots, schedule pages) with no kv-head axis:
    one grid step reads one page of every head, its K/V block the whole
    (Hk, page, D) slab at (layer, phys)."""
    from repro.kernels.paged_attention import paged_decode_attention_pallas

    b, w = 3, 5
    hk, d, page, _ = QWEN3_1_7B
    pool = jax.ShapeDtypeStruct((LAYERS, b * w + 1, hk, page, d), jnp.bfloat16)
    sched = jax.ShapeDtypeStruct((b, w), jnp.int32)
    jaxpr = jax.make_jaxpr(
        functools.partial(paged_decode_attention_pallas, sm_scale=d ** -0.5)
    )(
        jax.ShapeDtypeStruct((b, hk, 2, d), jnp.bfloat16), pool, pool,
        jax.ShapeDtypeStruct((), jnp.int32), sched, sched, sched,
        jax.ShapeDtypeStruct((b,), jnp.int32),
    )
    calls = _pallas_calls(jaxpr.jaxpr)
    assert len(calls) == 1
    gm = calls[0].params["grid_mapping"]
    assert tuple(gm.grid) == (b, w)
    # block sizes per dim; a squeezed (layer) dim has none
    blocks = [
        tuple(getattr(dim, "block_size", None) for dim in bm.block_shape)
        for bm in gm.block_mappings
    ]
    assert blocks.count((None, 1, hk, page, d)) == 2  # K and V


def _pallas_calls(jaxpr):
    """Every ``pallas_call`` equation in ``jaxpr`` and its sub-jaxprs."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            found.append(eqn)
        for param in eqn.params.values():
            inner = getattr(param, "jaxpr", param)
            if hasattr(inner, "eqns"):
                found += _pallas_calls(inner)
    return found


@pytest.mark.parametrize("impl", [None, "interpret"])
@pytest.mark.parametrize("layer", [0, 2])
@pytest.mark.parametrize("sparse", [False, True], ids=["dense", "sparse"])
def test_stacked_layer_read_equals_single_layer_pool(sparse, layer, impl):
    """Reading layer ``layer`` of the stacked pools gives exactly what the
    same read gives on that layer's pool alone (``pool[layer][None]`` at
    layer 0), for both schedules and both reads."""
    b, hk, g = 4, 2, 2
    k, v, table, pos, rng = _scenario(b, hk, D)
    q = _q(rng, b, hk, g, D, jnp.float32)
    if sparse:
        def read(kp, vp, li):
            return L.paged_sparse_decode_attention_jnp(
                q, kp, vp, li, table, pos, sm_scale=D ** -0.5,
                local_blocks=2, global_blocks=1, impl=impl,
            )
    else:
        def read(kp, vp, li):
            return L.paged_decode_attention_jnp(
                q, kp, vp, li, table, pos, sm_scale=D ** -0.5, impl=impl
            )
    got = read(k, v, jnp.int32(layer))
    want = read(k[layer][None], v[layer][None], 0)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    other = read(k[1][None], v[1][None], 0)  # the layers differ
    assert not np.allclose(np.asarray(got), np.asarray(other))


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_sparse_parity_random_schedules(seed):
    """Fuzz the schedule geometry: random local/global widths and ragged
    positions must stay in lockstep between kernel and oracle."""
    rng = np.random.default_rng(seed)
    b, hk, g = int(rng.integers(2, 5)), 2, int(rng.integers(1, 3))
    local = int(rng.integers(1, 3))
    glob = int(rng.integers(0, 2))
    k, v, table, pos, rng2 = _scenario(b, hk, D, seed=seed + 10)
    q = _q(rng2, b, hk, g, D, jnp.float32)
    _run(
        lambda impl: L.paged_sparse_decode_attention_jnp(
            q, k, v, seed % LAYERS, table, pos, sm_scale=D ** -0.5,
            local_blocks=local, global_blocks=glob, impl=impl,
        ),
        jnp.float32,
    )


def test_dense_kernel_matches_contiguous_cache_oracle():
    """Materializing each slot's pages into a contiguous cache and running
    plain decode attention is the ground truth; the paged kernel must
    match it — not just the paged gather path — on fully-backed slots."""
    b, hk, g = 2, 2, 2
    k, v, table, pos, rng = _scenario(
        b, hk, D, trash_slot=False, partial_slot=False
    )
    q = _q(rng, b, hk, g, D, jnp.float32)
    layer = 1
    got = L.paged_decode_attention_jnp(
        q, k, v, layer, table, pos, sm_scale=D ** -0.5, impl="interpret"
    )
    tbl = np.asarray(table)
    for s in range(b):
        kc = jnp.asarray(
            np.asarray(k)[layer, tbl[s]]
            .swapaxes(1, 2).reshape(1, PPS * PAGE, hk, D)
        )
        vc = jnp.asarray(
            np.asarray(v)[layer, tbl[s]]
            .swapaxes(1, 2).reshape(1, PPS * PAGE, hk, D)
        )
        want = L.decode_attention_jnp(
            q[s:s + 1], kc, vc, pos[s], sm_scale=D ** -0.5
        )
        np.testing.assert_allclose(
            np.asarray(got[s:s + 1]), np.asarray(want), atol=1e-5, rtol=1e-5
        )


def test_sparse_kernel_covering_schedule_equals_dense():
    """With few enough pages the butterfly/local/global schedule covers
    every causal block, so the sparse kernel must equal the dense paged
    reference exactly (modulo fp tolerance)."""
    rng = np.random.default_rng(5)
    b, hk, g, pps = 3, 2, 2, 2
    n_pages = b * pps + 1
    k = rng.standard_normal((n_pages, hk, PAGE, D))
    v = rng.standard_normal((n_pages, hk, PAGE, D))
    k[0], v[0] = 1e4, -1e4
    perm = rng.permutation(np.arange(1, n_pages))
    table = jnp.asarray(perm.reshape(b, pps).astype(np.int32))
    pos = jnp.asarray(rng.integers(0, pps * PAGE, b).astype(np.int32))
    q = _q(rng, b, hk, g, D, jnp.float32)
    # one layer's pools, passed as a stack of one
    k, v = jnp.asarray(k, jnp.float32)[None], jnp.asarray(v, jnp.float32)[None]
    got = L.paged_sparse_decode_attention_jnp(
        q, k, v, 0, table, pos, sm_scale=D ** -0.5,
        local_blocks=2, global_blocks=1, impl="interpret",
    )
    want = L.paged_decode_attention_jnp(
        q, k, v, 0, table, pos, sm_scale=D ** -0.5
    )
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(want), atol=1e-5, rtol=1e-5
    )


def test_trash_page_slot_is_benign():
    """A slot whose whole row is the trash page (idle slot, position 0)
    must stay finite and identical across impls — the serving engine
    parks evicted slots exactly like this."""
    b, hk, g = 4, 2, 1
    k, v, table, pos, rng = _scenario(b, hk, D)  # slot 1 is all-trash
    q = _q(rng, b, hk, g, D, jnp.float32)
    got = L.paged_sparse_decode_attention_jnp(
        q, k, v, 2, table, pos, sm_scale=D ** -0.5,
        local_blocks=2, global_blocks=1, impl="interpret",
    )
    ref = L.paged_sparse_decode_attention_jnp(
        q, k, v, 2, table, pos, sm_scale=D ** -0.5,
        local_blocks=2, global_blocks=1,
    )
    assert np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(
        np.asarray(got), np.asarray(ref), atol=1e-5, rtol=1e-5
    )


def test_paged_sparse_schedule_properties():
    """The shared schedule helper: logical ids causal (never beyond the
    slot's current block), physical ids come from the page table, and the
    keep mask marks exactly the first occurrence of each logical block."""
    rng = np.random.default_rng(9)
    b, pps, page = 5, 8, 4
    table = jnp.asarray(
        rng.integers(1, 40, size=(b, pps)).astype(np.int32)
    )
    pos = jnp.asarray(rng.integers(0, pps * page, b).astype(np.int32))
    idx, phys, keep = L.paged_sparse_schedule(
        table, pos, page, local_blocks=2, global_blocks=1
    )
    idx, phys, keep = map(np.asarray, (idx, phys, keep))
    cur = np.asarray(pos) // page
    tbl = np.asarray(table)
    for s in range(b):
        assert (idx[s] <= cur[s]).all() and (idx[s] >= 0).all()
        assert (phys[s] == tbl[s][idx[s]]).all()
        seen = set()
        for t in range(idx.shape[1]):
            assert bool(keep[s, t]) == (idx[s, t] not in seen)
            seen.add(idx[s, t])


@pytest.mark.parametrize("layer", [0, 2])
def test_write_paged_token_touches_only_its_rows(layer):
    """The decode step's token write: active slots write one row of a
    page of their own, idle slots all alias the trash page 0. Written
    rows equal the new K/V bit for bit; every other row of every page,
    and every page of the other layers, stays bit-identical."""
    rng = np.random.default_rng(21)
    b, hk, n_pages = 6, 2, 9
    pool = jnp.asarray(
        rng.standard_normal((LAYERS, n_pages, hk, PAGE, D)), jnp.bfloat16
    )
    phys = np.array([3, 0, 7, 0, 5, 0], np.int32)  # slots 1, 3, 5 idle
    off = np.array([0, 0, PAGE - 1, 0, 3, 0], np.int32)
    new = jnp.asarray(rng.standard_normal((b, hk, D)), jnp.float32)
    got = np.asarray(
        L.write_paged_token(
            pool, jnp.int32(layer), jnp.asarray(phys), jnp.asarray(off), new
        ),
        np.float32,
    )
    want = np.asarray(pool, np.float32).copy()
    new_bf16 = np.asarray(new.astype(jnp.bfloat16), np.float32)
    for s in (0, 2, 4):
        want[layer, phys[s], :, off[s]] = new_bf16[s]
    trash = np.zeros(n_pages, bool)
    trash[0] = True
    # the trash page takes one of the idle slots' rows: never read back
    np.testing.assert_array_equal(got[:, ~trash], want[:, ~trash])
    np.testing.assert_array_equal(
        np.delete(got, layer, axis=0), np.delete(want, layer, axis=0)
    )
    idle_rows = [new_bf16[s] for s in (1, 3, 5)]
    assert any(np.array_equal(got[layer, 0, :, 0], r) for r in idle_rows)
    np.testing.assert_array_equal(got[layer, 0, :, 1:], want[layer, 0, :, 1:])


def test_decode_step_writes_each_layer_in_place():
    """A whole paged decode step through the layer scan: every layer's
    pool changes only in the rows the active slots write (and the trash
    page), and every layer takes its own K/V there."""
    import jax

    from repro.configs import registry
    from repro.models import transformer as T

    cfg = registry.get_smoke("qwen3-1.7b", sparse=True)
    page, per_seq, slots = cfg.attn_block, 2, 4
    n_pages = slots * per_seq + 1
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    rng = np.random.default_rng(3)
    pools = jax.tree.map(
        lambda a: jnp.asarray(rng.standard_normal(a.shape), a.dtype),
        T.init_paged_cache(cfg, n_pages, page),
    )
    table = np.zeros((slots, per_seq), np.int32)
    table[0] = [1, 2]
    table[2] = [5, 6]  # slots 1 and 3 idle: all-trash rows
    pos = np.array([page + 4, 0, 9, 0], np.int32)
    before = jax.tree.map(np.asarray, pools)
    _, after = T.decode_step_paged(
        cfg, params, pools, jnp.asarray([1, 2, 3, 4], jnp.int32),
        jnp.asarray(pos), jnp.asarray(table), paged_impl="interpret",
    )
    written = {(2, 4), (5, 9)}  # (physical page, row) of slots 0 and 2
    for old, new in zip(jax.tree.leaves(before), jax.tree.leaves(after)):
        new = np.asarray(new)
        changed = np.argwhere((old != new).any(axis=(2, 4)))  # (l, page, row)
        rows = {(int(p), int(r)) for _, p, r in changed if p != 0}
        assert rows == written
        for p, r in written:  # each layer wrote its own K/V
            rows_by_layer = {layer.tobytes() for layer in new[:, p, :, r]}
            assert len(rows_by_layer) == len(new)
