"""Differential parity: Pallas paged-decode kernel vs the jnp paged oracle.

The kernel (`repro.kernels.paged_attention`, run in interpret mode on CPU)
must reproduce ``paged_decode_attention_jnp`` / ``paged_sparse_decode_
attention_jnp`` (impl=None gather paths) across GQA ratios, dtypes,
ragged per-slot positions, partially-filled last pages, partially
allocated page-table rows, and idle slots parked on the trash page.
The trash page is poisoned with huge values so any masking divergence
between the two paths is loud, not a rounding blip.
"""

import numpy as np
import pytest

import jax.numpy as jnp

from repro.models import layers as L

PAGE, PPS, D = 8, 4, 16  # page size, pages per slot, head dim
ATOL = {jnp.float32: 1e-5, jnp.bfloat16: 1e-2}


def _scenario(b, hk, d, *, seed=0, dtype=jnp.float32, trash_slot=True,
              partial_slot=True):
    """Random pools + page table with the parity suite's edge cases:
    ragged positions (partial last pages), a partially-allocated row,
    and an idle slot whose row is all trash page."""
    rng = np.random.default_rng(seed)
    n_pages = b * PPS + 1
    k = rng.standard_normal((n_pages, hk, PAGE, d))  # head-major pools
    v = rng.standard_normal((n_pages, hk, PAGE, d))
    # poison the trash page: an unmasked read of page 0 shows up as a
    # huge output delta instead of hiding inside the tolerance
    k[0] = 1e4
    v[0] = -1e4
    perm = rng.permutation(np.arange(1, n_pages))
    table = np.zeros((b, PPS), np.int32)
    pos = np.zeros((b,), np.int32)
    nxt = 0
    for s in range(b):
        if partial_slot and s == b - 1 and b > 1:
            n_alloc = 1  # partially-allocated row, trash tail
        else:
            n_alloc = PPS
        table[s, :n_alloc] = perm[nxt:nxt + n_alloc]
        nxt += n_alloc
        # ragged: land mid-page so the last page is partially filled
        pos[s] = int(rng.integers(0, n_alloc * PAGE))
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


@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_dense_paged_parity(g, dtype):
    b, hk = 4, 2
    k, v, table, pos, rng = _scenario(b, hk, D, dtype=dtype)
    q = _q(rng, b, hk, g, D, dtype)
    _run(
        lambda impl: L.paged_decode_attention_jnp(
            q, k, v, table, pos, sm_scale=D ** -0.5, impl=impl
        ),
        dtype,
    )


@pytest.mark.parametrize("g", [1, 2, 4])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_sparse_paged_parity(g, dtype):
    b, hk = 4, 2
    k, v, table, pos, rng = _scenario(b, hk, D, dtype=dtype)
    q = _q(rng, b, hk, g, D, dtype)
    _run(
        lambda impl: L.paged_sparse_decode_attention_jnp(
            q, k, v, table, pos, sm_scale=D ** -0.5,
            local_blocks=2, global_blocks=1, impl=impl,
        ),
        dtype,
    )


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
            q, k, v, table, pos, sm_scale=D ** -0.5,
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
    got = L.paged_decode_attention_jnp(
        q, k, v, table, pos, sm_scale=D ** -0.5, impl="interpret"
    )
    tbl = np.asarray(table)
    for s in range(b):
        kc = jnp.asarray(
            np.asarray(k)[tbl[s]].swapaxes(1, 2).reshape(1, PPS * PAGE, hk, D)
        )
        vc = jnp.asarray(
            np.asarray(v)[tbl[s]].swapaxes(1, 2).reshape(1, PPS * PAGE, hk, D)
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
    k, v = jnp.asarray(k, jnp.float32), jnp.asarray(v, jnp.float32)
    got = L.paged_sparse_decode_attention_jnp(
        q, k, v, table, pos, sm_scale=D ** -0.5,
        local_blocks=2, global_blocks=1, impl="interpret",
    )
    want = L.paged_decode_attention_jnp(
        q, k, v, table, pos, sm_scale=D ** -0.5
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
        q, k, v, table, pos, sm_scale=D ** -0.5,
        local_blocks=2, global_blocks=1, impl="interpret",
    )
    ref = L.paged_sparse_decode_attention_jnp(
        q, k, v, table, pos, sm_scale=D ** -0.5,
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
