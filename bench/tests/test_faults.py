"""A run with the timed path broken underneath has to come out not
correct: the harness's whole run on the CPU (no look for a chip), with
one fault planted in the engine's decode step after set-up."""

import time

import jax
import jax.numpy as jnp
import pytest

from bench import session
from bench.tests import smoke


def alter_tokens(engine):
    """Every third decode step hands back other token ids than it
    computed, where they are produced."""
    orig, calls = engine._decode, [0]

    def altered(*args):
        toks, pool = orig(*args)
        calls[0] += 1
        if calls[0] % 3 == 0:
            toks = (toks + 1) % engine.cfg.vocab_size
        return toks, pool

    engine._decode = altered


def freeze_state(engine):
    """Decode steps return the KV pool they were given: the keys and
    values of the step's tokens are never kept."""
    orig = engine._decode

    def frozen(params, pool, *args):
        toks, _ = orig(params, jax.tree.map(jnp.copy, pool), *args)
        return toks, pool

    engine._decode = frozen


@pytest.mark.parametrize("fault", [alter_tokens, freeze_state], ids=["token_altered", "state_unchanged"])
@pytest.mark.parametrize("like", ["pixelfly-chat", "dense-chat"])
def test_fault_is_not_correct(like, fault):
    c = smoke.cell(like, "chat")
    result, _ = session.run(
        c, 4242, 3.0, False, time.perf_counter(), test=smoke.options(c, fault=fault)
    )
    assert not result["correct"]
    assert result["compared"]["served_logit_gap_max"]["value"] > c.cell["served_logit_gap_limit"]


@pytest.mark.parametrize("fault", [alter_tokens, freeze_state], ids=["token_altered", "state_unchanged"])
def test_backlog_fault_is_not_correct(fault):
    """The same faults in the backlog cell, planted after its first wave
    was admitted in set-up."""
    c = smoke.cell("pixelfly-longctx", "longctx")
    result, _ = session.run(
        c, 2**31 + 4243, 3.0, False, time.perf_counter(), test=smoke.options(c, fault=fault)
    )
    assert not result["correct"]
    assert result["compared"]["served_logit_gap_max"]["value"] > c.cell["served_logit_gap_limit"]
