"""Serving subsystem: scheduler, paged KV cache, engine vs Server oracle."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import registry
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import Server
from repro.models import transformer as T
from repro.serving import (
    Engine,
    EngineConfig,
    PagedKVCache,
    Request,
    Scheduler,
)


def _smoke_cfg(**kw):
    return registry.get_smoke("qwen3-1.7b").replace(
        num_layers=2, vocab_size=128, **kw
    )


# ----------------------------------------------------------------------
# Scheduler (no model)
# ----------------------------------------------------------------------


def test_scheduler_admits_and_evicts_under_trace():
    sch = Scheduler(2)
    reqs = [
        Request(i, np.array([1, 2, 3]), max_new_tokens=4) for i in range(5)
    ]
    for r in reqs[:3]:
        sch.submit(r)
    # only two slots: third request stays queued
    s0 = sch.admit(step=0)
    s1 = sch.admit(step=0)
    assert (s0.slot, s1.slot) == (0, 1)
    assert sch.admit(step=0) is None
    assert sch.occupancy == 1.0 and len(sch.waiting) == 1
    # evicting frees the slot for the queued request, mid-flight
    sch.evict(0)
    assert sch.occupancy == 0.5
    s2 = sch.admit(step=3)
    assert s2.slot == 0 and s2.request.uid == 2 and s2.admit_step == 3
    # late arrivals join the same queue
    for r in reqs[3:]:
        sch.submit(r)
    sch.evict(1)
    assert sch.admit(step=5).request.uid == 3
    assert not sch.idle
    sch.evict(0), sch.evict(1)
    assert sch.admit(step=6).request.uid == 4
    sch.evict(0)
    assert sch.idle


def test_scheduler_evict_empty_slot_raises():
    sch = Scheduler(1)
    with pytest.raises(ValueError):
        sch.evict(0)


# ----------------------------------------------------------------------
# Paged KV cache
# ----------------------------------------------------------------------


def test_paged_cache_page_accounting():
    cfg = _smoke_cfg()
    kv = PagedKVCache(cfg, max_slots=2, max_len=4 * cfg.attn_block)
    total = kv.free_pages
    assert kv.n_pages == 2 * 4 + 1
    kv.alloc_upto(0, 0)
    kv.alloc_upto(0, 3 * kv.page)  # pages 0..3
    assert kv.free_pages == total - 4
    assert (kv.page_table[0, :4] > 0).all()  # page 0 is reserved (trash)
    kv.free_slot(0)
    assert kv.free_pages == total and (kv.page_table[0] == 0).all()
    with pytest.raises(ValueError):
        kv.alloc_upto(1, 4 * kv.page)  # beyond per-slot capacity


def test_paged_prefill_roundtrips_vs_contiguous_cache():
    """prefill_paged writes the same K/V the contiguous prefill produces,
    page-scattered; gathering the slot's pages reconstructs them."""
    cfg = _smoke_cfg()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    page = cfg.attn_block
    plen = page  # one full page: no padding ambiguity
    prompt = np.random.default_rng(0).integers(
        0, cfg.vocab_size, plen, dtype=np.int32
    )

    # contiguous reference: prefill mode keeps the raw K/V
    _, ref = T.prefill(cfg, params, {"tokens": jnp.asarray(prompt[None])})

    kv = PagedKVCache(cfg, max_slots=2, max_len=2 * page)
    kv.alloc_upto(1, plen - 1)  # slot 1: catches slot/page mix-ups
    rows = jnp.asarray(kv.table_row(1, 1))[None]  # (N=1, P=1)
    _, kv.buffers = T.prefill_paged(
        cfg, params, jnp.asarray(prompt[None]),
        jnp.asarray([plen], jnp.int32), kv.buffers, rows,
    )
    for pool, r in zip(kv.buffers, ref):
        for name in ("k", "v"):
            # gather the slot's head-major page back into (count, S, hk, d)
            got = np.asarray(pool[name][:, kv.page_table[1, 0]]).swapaxes(1, 2)
            want = np.asarray(r[name][:, 0, :plen])
            np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-6)


def test_paged_prefill_batched_matches_per_request():
    """One (N, S) prefill call writes each request's pages exactly as N
    separate (1, S) calls would, and returns per-request last-real-token
    logits; bucket padding scatters only to the trash page."""
    cfg = _smoke_cfg()
    params = T.init_model(jax.random.PRNGKey(0), cfg)
    page = cfg.attn_block
    s = 2 * page
    rng = np.random.default_rng(3)
    plens = [page // 2, 2 * page, page + 3]  # ragged, crossing a page
    prompts = [
        rng.integers(0, cfg.vocab_size, p).astype(np.int32) for p in plens
    ]

    # reference: one call per request into its own cache
    kv_ref = PagedKVCache(cfg, max_slots=4, max_len=s)
    ref_logits = []
    for i, (pl, pr) in enumerate(zip(plens, prompts)):
        kv_ref.alloc_upto(i, pl - 1)
        tokens = np.zeros((1, s), np.int32)
        tokens[0, :pl] = pr
        lg, kv_ref.buffers = T.prefill_paged(
            cfg, params, jnp.asarray(tokens), jnp.asarray([pl], jnp.int32),
            kv_ref.buffers, jnp.asarray(kv_ref.bucket_row(i, pl, 2))[None],
        )
        ref_logits.append(np.asarray(lg[0]))

    # batched: N=4 (one padding row), same physical page layout
    kv_b = PagedKVCache(cfg, max_slots=4, max_len=s)
    tokens = np.zeros((4, s), np.int32)
    plens_b = np.ones((4,), np.int32)
    rows = np.zeros((4, 2), np.int32)
    for i, (pl, pr) in enumerate(zip(plens, prompts)):
        kv_b.alloc_upto(i, pl - 1)
        tokens[i, :pl] = pr
        plens_b[i] = pl
        rows[i] = kv_b.bucket_row(i, pl, 2)
    logits, kv_b.buffers = T.prefill_paged(
        cfg, params, jnp.asarray(tokens), jnp.asarray(plens_b),
        kv_b.buffers, jnp.asarray(rows),
    )
    assert logits.shape[0] == 4
    for i in range(3):
        np.testing.assert_allclose(
            np.asarray(logits[i]), ref_logits[i], rtol=1e-5, atol=1e-5
        )
    for pool_b, pool_r in zip(kv_b.buffers, kv_ref.buffers):
        for name in ("k", "v"):
            # identical allocation order -> identical physical pages;
            # compare every real (non-trash) page
            np.testing.assert_allclose(
                np.asarray(pool_b[name][:, 1:]),
                np.asarray(pool_r[name][:, 1:]),
                rtol=1e-6,
                atol=1e-6,
            )


# ----------------------------------------------------------------------
# Engine
# ----------------------------------------------------------------------


@pytest.mark.parametrize("sparse", [False, True])
def test_engine_matches_server_greedy(sparse):
    """Greedy tokens must match the Server oracle exactly. The sparse case
    is exact too: with 2 pages per slot the butterfly/local/global window
    covers every causal block, so the engine's sparse prefill + paged
    sparse decode equal dense attention — which is also what the Server
    computes (its ragged cache falls back to dense decode)."""
    cfg = _smoke_cfg(sparse_attention=sparse)
    mesh = make_local_mesh()
    server = Server(cfg, mesh)
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(3, 8), dtype=np.int32
    )
    ref = server.generate(prompts, 5)

    eng = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(max_slots=3, max_len=128),
        params=server.params,
    )
    for b in range(3):
        eng.submit(prompts[b], 5)
    fins = sorted(eng.drain(max_steps=50), key=lambda f: f.uid)
    out = np.stack([f.tokens for f in fins])
    np.testing.assert_array_equal(out, ref)


def test_engine_matches_server_adversarial_schedule():
    """Randomized (seeded) admission order, interleaved stepping, and
    mid-stream slot reuse must still reproduce the fixed-batch Server's
    greedy tokens exactly, per request. With 2 slots and 6 requests every
    slot is reused multiple times, and random step() bursts between
    submissions shuffle which requests share a decode batch."""
    cfg = _smoke_cfg(sparse_attention=True)
    mesh = make_local_mesh()
    server = Server(cfg, mesh)
    rng = np.random.default_rng(7)
    plens, gens = [8, 16], [3, 5]
    reqs = [
        (
            rng.integers(0, cfg.vocab_size, plens[i % 2]).astype(np.int32),
            gens[int(rng.integers(0, 2))],
        )
        for i in range(6)
    ]
    # oracle batched per prompt length; greedy decode is append-only, so
    # generating max(gens) once covers every per-request gen length
    ref = {}
    for plen in plens:
        ids = [i for i, (p, _) in enumerate(reqs) if p.size == plen]
        out = server.generate(
            np.stack([reqs[i][0] for i in ids]), max(gens)
        )
        for row, i in enumerate(ids):
            ref[i] = out[row, : reqs[i][1]]

    eng = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(max_slots=2, max_len=128),
        params=server.params,
    )
    order = list(range(6))
    rng.shuffle(order)  # adversarial: admission order != submission order
    uids = {}
    fins = []
    while order:
        k = int(rng.integers(1, 3))
        for i in order[:k]:
            uids[eng.submit(*reqs[i])] = i
        order = order[k:]
        for _ in range(int(rng.integers(0, 4))):  # random step bursts
            fins += eng.step()
    fins += eng.drain(max_steps=200)

    assert sorted(f.uid for f in fins) == sorted(uids)
    # slot reuse actually happened mid-stream
    assert max(f.admit_step for f in fins) > min(
        f.finish_step for f in fins
    )
    for f in fins:
        np.testing.assert_array_equal(f.tokens, ref[uids[f.uid]])


def test_engine_continuous_batching_mixed_lengths():
    """More requests than slots, ragged lengths, late arrivals: everything
    finishes, pages don't leak, and slots refill mid-flight."""
    cfg = _smoke_cfg(sparse_attention=True)
    eng = Engine(
        cfg,
        make_local_mesh(),
        engine_cfg=EngineConfig(max_slots=2, max_len=128),
    )
    rng = np.random.default_rng(1)
    gens: dict[int, int] = {}
    for _ in range(3):
        gen = int(rng.integers(2, 7))
        uid = eng.submit(
            rng.integers(0, cfg.vocab_size, int(rng.integers(2, 40))), gen
        )
        gens[uid] = gen
    fins = []
    for _ in range(3):
        fins += eng.step()
    late = eng.submit(rng.integers(0, cfg.vocab_size, 5), 3)
    gens[late] = 3
    fins += eng.drain(max_steps=100)

    assert sorted(f.uid for f in fins) == sorted(gens)
    for f in fins:
        assert f.finish_reason == "length"
        assert len(f.tokens) == gens[f.uid]
    # some admission happened after step 0 (continuous batching)
    assert max(f.admit_step for f in fins) > 0
    # all pages returned to the free list
    assert eng.kv.free_pages == eng.kv.n_pages - 1
    assert eng.scheduler.idle
    assert eng.stats_summary()["mean_occupancy"] > 0


def test_engine_non_pow2_bucket_matches_server():
    """Regression: max_len=192 (3 pages) makes a non-power-of-two bucket
    whose 192-token prefill used to trip ``assert sk % chunk == 0`` in
    flash_attention_jnp (attn_chunk=128). A 140-token prompt must serve
    and match the Server oracle on the dense smoke config."""
    cfg = _smoke_cfg()
    assert 192 % cfg.attn_chunk != 0  # the shape that used to crash
    mesh = make_local_mesh()
    server = Server(cfg, mesh)
    prompt = np.random.default_rng(5).integers(
        0, cfg.vocab_size, 140, dtype=np.int32
    )
    ref = server.generate(prompt[None], 4)[0]
    eng = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(max_slots=1, max_len=192),
        params=server.params,
    )
    eng.submit(prompt, 4)
    fins = eng.drain(max_steps=30)
    np.testing.assert_array_equal(fins[0].tokens, ref)


def test_engine_batched_admission_single_prefill_call():
    """A same-bucket group of N waiting requests is admitted by ONE jit'd
    prefill call (tokens (N, S)) and one host sync; the per-request
    baseline (max_prefill_batch=1) issues N calls on the same trace."""
    cfg = _smoke_cfg()
    mesh = make_local_mesh()
    prompts = np.random.default_rng(2).integers(
        0, cfg.vocab_size, size=(4, 8), dtype=np.int32
    )

    def serve(engine):
        calls = []
        orig = engine._prefill

        def counting(*a):
            calls.append(tuple(a[1].shape))  # tokens shape
            return orig(*a)

        engine._prefill = counting
        for b in range(4):
            engine.submit(prompts[b], 3)
        fins = engine.drain(max_steps=30)
        return calls, sorted(fins, key=lambda f: f.uid)

    eng = Engine(
        cfg, mesh, engine_cfg=EngineConfig(max_slots=4, max_len=64)
    )
    calls, fins = serve(eng)
    assert calls == [(4, 64)]  # one (N, S) program, one call
    assert eng.stats_summary()["prefill_calls"] == 1
    assert eng.stats_summary()["mean_prefill_batch"] == 4.0
    assert eng.stats_summary()["prefill_by_bucket"] == {
        "4x64": {"calls": 1, "requests": 4}
    }

    base = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(
            max_slots=4, max_len=64, max_prefill_batch=1
        ),
        params=eng.params,
    )
    bcalls, bfins = serve(base)
    assert bcalls == [(1, 64)] * 4
    # same greedy tokens either way
    for f, g in zip(fins, bfins):
        np.testing.assert_array_equal(f.tokens, g.tokens)


def test_engine_batched_ragged_buckets_match_server():
    """Batched admission with ragged prompt lengths crossing bucket
    boundaries (1-, 2- and 4-page buckets admitted in the same step) must
    reproduce the Server oracle per request."""
    cfg = _smoke_cfg(sparse_attention=True)
    mesh = make_local_mesh()
    server = Server(cfg, mesh)
    rng = np.random.default_rng(11)
    page = cfg.attn_block
    plens = [8, page - 1, page + 5, 2 * page + 9, 3, 2 * page]
    reqs = [
        rng.integers(0, cfg.vocab_size, p).astype(np.int32) for p in plens
    ]
    ref = {}
    for plen in sorted(set(plens)):
        ids = [i for i, p in enumerate(plens) if p == plen]
        out = server.generate(np.stack([reqs[i] for i in ids]), 4)
        for row, i in enumerate(ids):
            ref[i] = out[row]

    eng = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(max_slots=6, max_len=4 * page),
        params=server.params,
    )
    uids = {eng.submit(reqs[i], 4): i for i in range(6)}
    fins = eng.drain(max_steps=40)
    assert len(fins) == 6
    # several buckets were in flight in the same admission pass
    assert len(eng.stats_summary()["prefill_by_bucket"]) >= 3
    for f in fins:
        np.testing.assert_array_equal(f.tokens, ref[uids[f.uid]])


def test_engine_lookahead_admits_past_oversized_request():
    """Page-pressure admission: with an oversubscribed page pool, an
    oversized head-of-queue request must not head-of-line-block smaller
    ones behind it — lookahead admits them first, and the big one lands
    once pages free up. Tokens still match the oracle."""
    cfg = _smoke_cfg(sparse_attention=True)
    mesh = make_local_mesh()
    server = Server(cfg, mesh)
    page = cfg.attn_block
    rng = np.random.default_rng(13)
    big = rng.integers(0, cfg.vocab_size, 2 * page + 4).astype(np.int32)
    small = [
        rng.integers(0, cfg.vocab_size, 6 + i).astype(np.int32)
        for i in range(2)
    ]
    ref_big = server.generate(big[None], 3)[0]
    ref_small = [server.generate(p[None], 3)[0] for p in small]

    # slots=3, 5 usable pages (pool oversubscribed vs worst-case 9):
    # hog 3 pages first so the 3-page "big" request cannot be admitted
    # while the two 1-page smalls behind it still can
    eng = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(max_slots=3, max_len=3 * page, n_pages=6),
        params=server.params,
    )
    hog = rng.integers(0, cfg.vocab_size, 2 * page + 4).astype(np.int32)
    eng.submit(hog, 8)
    eng.step()  # hog admitted: 3 of 5 usable pages taken
    uid_big = eng.submit(big, 3)
    uid_small = [eng.submit(p, 3) for p in small]
    fins = eng.step()
    # big (3 pages) skipped, both smaller ones (1 page each) admitted
    active_uids = {s.request.uid for s in eng.scheduler.active()}
    assert uid_big not in active_uids
    assert set(uid_small) <= active_uids
    fins += eng.drain(max_steps=60)
    by_uid = {f.uid: f for f in fins}
    assert by_uid[uid_big].admit_step > max(
        by_uid[u].admit_step for u in uid_small
    )
    np.testing.assert_array_equal(by_uid[uid_big].tokens, ref_big)
    for u, r in zip(uid_small, ref_small):
        np.testing.assert_array_equal(by_uid[u].tokens, r)


def test_engine_oversubscribed_pool_survives_decode_growth():
    """Regression: admission budgets a request's *lifetime* pages (prompt
    + decode growth), not just the prompt. With a 2-usable-page pool and
    two one-page prompts that each grow into a second page mid-decode,
    naive prompt-only budgeting admits both and crashes ``alloc_upto``
    with 'KV cache out of pages'; lifetime budgeting serializes them and
    every request finishes."""
    cfg = _smoke_cfg()
    page = cfg.attn_block
    eng = Engine(
        cfg,
        make_local_mesh(),
        engine_cfg=EngineConfig(max_slots=2, max_len=2 * page, n_pages=3),
    )
    rng = np.random.default_rng(17)
    uids = [
        eng.submit(
            rng.integers(0, cfg.vocab_size, page).astype(np.int32), 4
        )
        for _ in range(2)
    ]
    fins = eng.drain(max_steps=60)  # must not raise
    assert sorted(f.uid for f in fins) == sorted(uids)
    assert all(len(f.tokens) == 4 for f in fins)
    # sequential admission under page pressure, then full cleanup
    assert fins[0].admit_step != fins[1].admit_step
    assert eng.kv.free_pages == eng.kv.n_pages - 1
    assert not eng._page_need


def test_engine_eos_and_capacity_finish():
    cfg = _smoke_cfg()
    eng = Engine(
        cfg,
        make_local_mesh(),
        engine_cfg=EngineConfig(max_slots=1, max_len=64),
    )
    prompt = np.arange(8, dtype=np.int32)
    # learn the greedy stream, then replay with one of its tokens as eos
    eng.submit(prompt, 4)
    toks = [int(t) for t in eng.drain(max_steps=30)[0].tokens]
    eos = toks[-1]
    k = toks.index(eos)  # greedy replay stops at its first occurrence
    eng.submit(prompt, 4, eos_id=eos)
    fin = eng.drain(max_steps=30)[0]
    assert fin.finish_reason == "eos" and len(fin.tokens) == k + 1
    # capacity: request asks for more tokens than the slot can hold
    eng.submit(np.arange(60, dtype=np.int32), 50)
    fin = eng.drain(max_steps=30)[0]
    assert fin.finish_reason == "capacity"
    assert 60 + len(fin.tokens) <= 64 + 1
