"""Property tests: PagedKVCache allocator invariants.

Random alloc/free traces — plus refcounted share / copy-on-write /
park / evict traces — against a pure-python model of the free list and
the prefix cache's page index. The invariants the serving engine
depends on every step: refcounts never go negative and always equal the
number of slots mapping a page (a refcount-1 page is owned by exactly
one slot), free ∪ owned ∪ cached is exactly the pool, the trash page
(physical page 0) is never handed out or refcounted, a failed
``alloc_upto`` rolls back atomically, and freeing a slot restores
``free_pages`` and zeroes its ``page_table`` row.

A seeded numpy fuzz always runs (so the invariants gate every PR even
without dev deps); when ``hypothesis`` is installed the same traces are
additionally explored generatively with shrinking.
"""

import numpy as np
import pytest

try:
    from hypothesis import given, settings, strategies as st

    HAVE_HYPOTHESIS = True
except ImportError:  # pragma: no cover - dev dep optional
    HAVE_HYPOTHESIS = False

from repro.configs import registry
from repro.serving import PagedKVCache, SwapManager

SLOTS, PAGES_PER_SLOT, PAGE = 3, 4, 4
MAX_LEN = PAGES_PER_SLOT * PAGE


def _tiny_cfg():
    # shrink every model dim; only the cache geometry matters here
    return registry.get_smoke("qwen3-1.7b").replace(
        num_layers=1, num_heads=2, num_kv_heads=1, head_dim=8,
        attn_block=PAGE,
    )


def _check_invariants(
    kv: PagedKVCache, pins: dict[int, int] | None = None
) -> None:
    """``pins``: outstanding swap pins per page (page -> count) — a
    swapped-out sequence's shared prefix is kept live by references
    that no slot owns until resume. None asserts the no-pins steady
    state (refcount == number of owning slots exactly)."""
    pins = {p: n for p, n in (pins or {}).items() if n > 0}
    owned = [p for pages in kv._owned.values() for p in pages]
    counts: dict[int, int] = {}
    for p in owned:
        counts[p] = counts.get(p, 0) + 1
    # the trash page is never owned, freed, parked or refcounted
    assert 0 not in owned and 0 not in kv._free and 0 not in kv._cached
    assert kv._ref[0] == 0 and 0 not in pins
    # refcounts are never negative and (at op boundaries) equal the
    # number of slots mapping each page plus its outstanding swap pins
    # — in particular a refcount-1 unpinned page is owned by exactly
    # ONE slot
    assert (kv._ref >= 0).all()
    for p in range(1, kv.n_pages):
        assert kv._ref[p] == counts.get(p, 0) + pins.get(p, 0)
    # no slot maps the same page twice
    for pages in kv._owned.values():
        assert len(pages) == len(set(pages))
    # conservation: free ∪ owned ∪ cached ∪ pin-only == pool, disjoint
    pin_only = {p for p in pins if p not in counts}
    assert set(kv._free) | set(counts) | kv._cached | pin_only == set(
        range(1, kv.n_pages)
    )
    assert not set(kv._free) & (set(counts) | kv._cached | pin_only)
    assert not set(counts) & kv._cached
    assert not kv._cached & pin_only
    assert len(kv._free) == len(set(kv._free))
    assert kv.free_pages == (
        kv.n_pages - 1 - len(counts) - len(kv._cached) - len(pin_only)
    )
    # page_table rows mirror the owned lists, trash-padded
    for slot in range(kv.max_slots):
        pages = kv._owned.get(slot, [])
        assert list(kv.page_table[slot, : len(pages)]) == pages
        assert (kv.page_table[slot, len(pages):] == 0).all()


def _run_trace(ops) -> None:
    kv = PagedKVCache(_tiny_cfg(), max_slots=SLOTS, max_len=MAX_LEN)
    assert kv.n_pages == SLOTS * PAGES_PER_SLOT + 1
    for op, slot, pos in ops:
        if op == "alloc":
            before = len(kv._owned.get(slot, []))
            kv.alloc_upto(slot, pos)
            # monotone: never shrinks, backs exactly pos // page + 1
            assert len(kv._owned[slot]) == max(before, pos // PAGE + 1)
        else:
            kv.free_slot(slot)
            assert slot not in kv._owned
            assert (kv.page_table[slot] == 0).all()
        _check_invariants(kv)
    for slot in range(SLOTS):
        kv.free_slot(slot)
    # full teardown restores every page
    assert kv.free_pages == kv.n_pages - 1
    assert (kv.page_table == 0).all()


def _roundtrip(positions, slot) -> None:
    kv = PagedKVCache(_tiny_cfg(), max_slots=SLOTS, max_len=MAX_LEN)
    total = kv.free_pages
    for pos in positions:
        kv.alloc_upto(slot, pos)
    want = max(p // PAGE + 1 for p in positions)
    assert kv.free_pages == total - want
    assert (kv.page_table[slot, :want] > 0).all()
    kv.free_slot(slot)
    assert kv.free_pages == total
    assert (kv.page_table[slot] == 0).all()
    _check_invariants(kv)


@pytest.mark.parametrize("seed", range(8))
def test_alloc_free_trace_never_leaks_seeded(seed):
    rng = np.random.default_rng(seed)
    ops = [
        (
            "alloc" if rng.random() < 0.7 else "free",
            int(rng.integers(0, SLOTS)),
            int(rng.integers(0, MAX_LEN)),
        )
        for _ in range(int(rng.integers(5, 40)))
    ]
    _run_trace(ops)


@pytest.mark.parametrize("seed", range(4))
def test_alloc_free_roundtrip_seeded(seed):
    rng = np.random.default_rng(100 + seed)
    positions = [int(rng.integers(0, MAX_LEN)) for _ in range(int(rng.integers(1, 8)))]
    _roundtrip(positions, int(rng.integers(0, SLOTS)))


def _run_share_trace(ops, strategy=None) -> None:
    """Extended trace over the refcounted API: share (pin + adopt),
    copy-on-write splits, radix parking (free with a keep hook), LRU
    eviction, and host-memory swap round trips (swap_out pins the
    shared prefix, evacuates the rest, frees the slot; swap_in adopts
    the pinned prefix back and restores the host pages — mirroring the
    engine's preemption flow), with the full conservation/refcount
    invariant — including outstanding swap pins — checked after every
    op. ``tree`` models the prefix cache's page index.

    ``strategy`` runs the identical trace over a mesh-sharded pool
    (``PagedKVCache(strategy=)``): the allocator is host-side and
    layout-agnostic, so every invariant must hold unchanged while the
    device buffers live sharded across the mesh."""
    kv = PagedKVCache(
        _tiny_cfg(), max_slots=SLOTS, max_len=MAX_LEN, strategy=strategy
    )
    tree: set[int] = set()
    sm = SwapManager(kv, page_in_tree=lambda p: p in tree)
    records: list = []  # outstanding swap-outs
    pins: dict[int, int] = {}  # page -> live swap pins
    for op, slot, arg in ops:
        if op == "alloc":
            before = list(kv._owned.get(slot, []))
            try:
                kv.alloc_upto(slot, arg)
            except RuntimeError:
                # atomic: a failed grow must not retain anything
                assert kv._owned.get(slot, []) == before
        elif op == "free":
            if arg % 2:  # "insert": index the slot's pages, then park
                tree.update(kv._owned.get(slot, []))
            kv.free_slot(slot, keep=lambda p: p in tree)
        elif op == "share":
            src = arg % SLOTS
            src_pages = kv._owned.get(src, [])
            if slot != src and not kv._owned.get(slot) and src_pages:
                take = src_pages[: 1 + arg % len(src_pages)]
                for p in take:
                    kv.incref(p)
                kv.adopt(slot, take)
        elif op == "adopt_cached":
            if not kv._owned.get(slot) and kv._cached:
                take = sorted(kv._cached)[: 1 + arg % 3]
                for p in take:
                    kv.take_cached(p)
                kv.adopt(slot, take)
        elif op == "cow":
            owned = kv._owned.get(slot, [])
            li = arg % len(owned) if owned else 0
            if owned and kv._free and (
                kv.refcount(owned[li]) > 1 or owned[li] in tree
            ):
                old = owned[li]
                new = kv.cow_page(slot, li, keep=lambda p: p in tree)
                assert new != old and kv.refcount(new) == 1
        elif op == "evict":
            if kv._cached:
                victim = sorted(kv._cached)[arg % len(kv._cached)]
                kv.release_cached(victim)
                tree.discard(victim)
        elif op == "swap_out":
            if kv._owned.get(slot):
                rec = sm.swap_out(
                    slot, max_pin=arg % (PAGES_PER_SLOT + 1)
                )
                sm.finalize(rec)
                for p in rec.pin_pages:
                    pins[p] = pins.get(p, 0) + 1
                records.append(rec)
        elif op == "swap_in":
            tgt = next(
                (s for s in range(SLOTS) if not kv._owned.get(s)), None
            )
            if records and tgt is not None:
                rec = records[arg % len(records)]
                n_pin = len(rec.pin_pages)
                if kv.free_pages >= rec.n_logical - n_pin:
                    records.remove(rec)
                    # the engine's resume: the radix re-match pins the
                    # resident prefix, adopt turns those pins into the
                    # slot's references, fresh pages take the host copies
                    for p in rec.pin_pages:
                        kv.incref(p)
                    kv.adopt(tgt, list(rec.pin_pages))
                    kv.alloc_upto(tgt, rec.n_logical * PAGE - 1)
                    sm.swap_in(rec, tgt, n_resident=n_pin)
                    for p in rec.pin_pages:
                        pins[p] -= 1
        elif op == "discard":
            if records:
                rec = records.pop(arg % len(records))
                for p in rec.pin_pages:
                    pins[p] = pins.get(p, 0) - 1
                sm.discard(rec)
        _check_invariants(kv, pins)
    for rec in records:  # abandon outstanding swaps
        for p in rec.pin_pages:
            pins[p] = pins.get(p, 0) - 1
        sm.discard(rec)
    for slot in range(SLOTS):
        kv.free_slot(slot)  # no keep hook: nothing new parks
        _check_invariants(kv, pins)
    for p in sorted(kv._cached):
        kv.release_cached(p)
    assert kv.free_pages == kv.n_pages - 1
    assert (kv._ref == 0).all()


_SHARE_OPS = [
    "alloc", "free", "share", "adopt_cached", "cow", "evict",
    "swap_out", "swap_in", "discard",
]


@pytest.mark.parametrize("seed", range(8))
def test_share_cow_evict_trace_seeded(seed):
    rng = np.random.default_rng(200 + seed)
    ops = [
        (
            _SHARE_OPS[int(rng.integers(0, len(_SHARE_OPS)))],
            int(rng.integers(0, SLOTS)),
            int(rng.integers(0, MAX_LEN)),
        )
        for _ in range(int(rng.integers(10, 60)))
    ]
    _run_share_trace(ops)


def _mesh_strategy():
    """A (1, 8) tensor-parallel Strategy when the test process runs with
    8 simulated host devices (scripts/tier1.sh's mesh leg), else None.
    The tiny cfg's head_dim=8 divides tp=8, so the pool's last axis
    shards on the model axis."""
    import jax

    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_tp_mesh

    if len(jax.devices()) < 8:
        return None
    return shd.Strategy(make_tp_mesh(8), "tp")


@pytest.mark.parametrize("seed", range(4))
def test_share_cow_evict_trace_sharded_pool(seed):
    """The full share/COW/park/evict/swap fuzz over a pool sharded
    across a simulated 8-device mesh: refcount conservation and swap pin
    semantics are host-side bookkeeping and must be identical whatever
    the device layout — COW's jit'd page copy and the swap manager's
    gather/scatter run on sharded buffers."""
    st = _mesh_strategy()
    if st is None:
        pytest.skip(
            "needs XLA_FLAGS=--xla_force_host_platform_device_count=8"
        )
    rng = np.random.default_rng(700 + seed)
    ops = [
        (
            _SHARE_OPS[int(rng.integers(0, len(_SHARE_OPS)))],
            int(rng.integers(0, SLOTS)),
            int(rng.integers(0, MAX_LEN)),
        )
        for _ in range(int(rng.integers(10, 60)))
    ]
    _run_share_trace(ops, strategy=st)


def test_capacity_and_exhaustion_errors():
    kv = PagedKVCache(_tiny_cfg(), max_slots=SLOTS, max_len=MAX_LEN)
    with pytest.raises(ValueError):
        kv.alloc_upto(0, MAX_LEN)  # beyond per-slot capacity
    # freeing an unallocated slot is a no-op, not an error
    kv.free_slot(1)
    _check_invariants(kv)
    # drain the pool: allocation must fail loudly, not hand out trash
    for slot in range(SLOTS):
        kv.alloc_upto(slot, MAX_LEN - 1)
    assert kv.free_pages == 0
    kv.free_slot(0)
    kv._free.clear()  # simulate exhaustion with slot 0 unbacked
    with pytest.raises(RuntimeError):
        kv.alloc_upto(0, 0)
    assert 0 not in [p for ps in kv._owned.values() for p in ps]


if HAVE_HYPOTHESIS:

    @given(
        ops=st.lists(
            st.tuples(
                st.sampled_from(["alloc", "free"]),
                st.integers(0, SLOTS - 1),
                st.integers(0, MAX_LEN - 1),
            ),
            max_size=40,
        )
    )
    @settings(max_examples=25, deadline=None)
    def test_alloc_free_trace_never_leaks(ops):
        _run_trace(ops)

    @given(
        positions=st.lists(
            st.integers(0, MAX_LEN - 1), min_size=1, max_size=8
        ),
        slot=st.integers(0, SLOTS - 1),
    )
    @settings(max_examples=25, deadline=None)
    def test_alloc_free_roundtrip_restores_free_pages(positions, slot):
        _roundtrip(positions, slot)

    @given(
        ops=st.lists(
            st.tuples(
                st.sampled_from(_SHARE_OPS),
                st.integers(0, SLOTS - 1),
                st.integers(0, MAX_LEN - 1),
            ),
            max_size=60,
        )
    )
    @settings(max_examples=25, deadline=None)
    def test_share_cow_evict_trace(ops):
        _run_share_trace(ops)
