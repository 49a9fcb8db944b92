"""Pool sizing (``harness.size_pool``) on the CPU at small widths, with
the serving cache's shapes from the program and from an architecture
module's ``cache_shapes`` that adds state kept per slot."""

import types

import jax
import jax.numpy as jnp
import pytest

from bench import harness
from bench.tests import smoke
from bench.weights import tree_bytes

STATE_PER_SLOT = 1 << 15  # float32 numbers a slot's state holds


class Device:
    """A chip that holds ``bytes_limit`` bytes."""

    def __init__(self, bytes_limit):
        self.bytes_limit = bytes_limit

    def memory_stats(self):
        return {"bytes_limit": self.bytes_limit}


def _state(caches):
    return caches[-1]["state"]


def _thread_state(monkeypatch):
    """The programs take the per-slot state as their cache's last entry
    and hand it back updated, as a program with a state path does: the
    state goes in, is donated, and comes out in place."""
    from repro.models import transformer as T

    dec, pre = T.decode_step_paged, T.prefill_paged

    def decode_step_paged(cfg, p, caches, *args, **kw):
        logits, new = dec(cfg, p, caches[:-1], *args, **kw)
        return logits, new + [{"state": _state(caches) + 1.0}]

    def prefill_paged(cfg, p, t, plens, caches, *args, **kw):
        out, new = pre(cfg, p, t, plens, caches[:-1], *args, **kw)
        return out, new + [{"state": _state(caches) + 1.0}]

    monkeypatch.setattr(T, "decode_step_paged", decode_step_paged)
    monkeypatch.setattr(T, "prefill_paged", prefill_paged)


def _arch_with_state():
    from repro.models import transformer as T

    def cache_shapes(cfg, n_pages, page, slots):
        pools = jax.eval_shape(lambda: T.init_paged_cache(cfg, n_pages, page))
        return pools + [{"state": jax.ShapeDtypeStruct((slots, STATE_PER_SLOT), jnp.float32)}]

    return types.SimpleNamespace(cache_shapes=cache_shapes)


def test_per_slot_state_is_counted_once_and_not_in_a_page(monkeypatch):
    """The same chip sized with and without per-slot state: a page's
    bytes are the same; at each probe pool, each program needs the
    state's bytes more, once (the programs donate it, so it is not
    counted again as their output); and the pool is smaller."""
    from repro.launch.mesh import make_tp_mesh
    from repro.models import transformer as T

    c = smoke.cell("pixelfly-chat", "chat")
    o = smoke.options(c)
    cfg = c.arch.program_config({**c.config, **o.widths}, smoke=o.program)
    mesh = make_tp_mesh(1)
    shapes = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.PRNGKey(0))
    slots = c.mix.engine["max_slots"]
    state_bytes = slots * STATE_PER_SLOT * 4
    # room for the parameters, the reserve, the programs' temporaries and
    # a pool of about 18 pages of the 21 the slots could fill
    dev = Device(harness.POOL_RESERVE_BYTES + tree_bytes(shapes) + (23 << 19))
    plain = harness.size_pool(cfg, mesh, shapes, c.mix, dev)
    _thread_state(monkeypatch)
    held = harness.size_pool(cfg, mesh, shapes, c.mix, dev, _arch_with_state())
    assert held["page_bytes"] == plain["page_bytes"] > 0
    assert held["probes"].keys() == plain["probes"].keys()
    for pages, needs in held["probes"].items():
        for got, base in zip(needs, plain["probes"][pages]):
            assert got - base == pytest.approx(state_bytes, rel=0.01), pages
    per_seq = c.mix.engine["max_len"] // cfg.attn_block
    assert per_seq + 1 < held["n_pages"] < plain["n_pages"] < slots * per_seq + 1


def test_without_the_hook_the_program_gives_the_pool():
    """A module without ``cache_shapes`` sizes the pool as the program's
    ``init_paged_cache`` has it."""
    from repro.launch.mesh import make_tp_mesh
    from repro.models import transformer as T

    c = smoke.cell("dense-chat", "chat")
    o = smoke.options(c)
    cfg = c.arch.program_config({**c.config, **o.widths}, smoke=o.program)
    shapes = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.PRNGKey(0))
    # room for every slot's worst case
    dev = Device(harness.POOL_RESERVE_BYTES + tree_bytes(shapes) + (64 << 20))
    assert not hasattr(c.arch, "cache_shapes")
    got = harness.size_pool(cfg, make_tp_mesh(1), shapes, c.mix, dev, c.arch)
    pools = jax.eval_shape(lambda: T.init_paged_cache(cfg, 2, cfg.attn_block))
    assert got["page_bytes"] == tree_bytes(pools) // 2
    e = c.mix.engine
    assert got["n_pages"] == e["max_slots"] * e["max_len"] // cfg.attn_block + 1
