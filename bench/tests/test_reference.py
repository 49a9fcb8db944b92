"""The benchmark's plain float32 reference against the program's paged
prefill and paged decode, by logits, at small widths on the CPU; and
the fp8 control, which has to fail where the program passes."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench.arch import qwen3forcausallm as Q
from bench.arch.qwen3forcausallm import Reference
from bench.tests import smoke
from bench.weights import LayerWeights, make_params

PAGE = smoke.BLOCK
# at these widths the float32 program reads a gap of 0 and the fp8
# control 0.08 to 0.13 (seeds 7 and 99, both twins)
SMOKE_LIMIT = 0.01


def _setup(like):
    c = smoke.cell(like, "chat")
    o = smoke.options(c)
    conf = {**c.config, **o.widths}
    cfg = c.arch.program_config(conf, smoke=o.program)
    spec = Q.model_spec(conf)
    from repro.models import transformer as T

    shapes = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.PRNGKey(0))
    return c, cfg, spec, shapes


@pytest.mark.parametrize("like", ["pixelfly-chat", "dense-chat"])
def test_reference_matches_paged_prefill_and_decode(like):
    """Prefill a prompt into a paged pool, then decode through the pool
    with the Pallas kernel interpreted: each step's logits equal the
    reference's at that position."""
    from repro.models import transformer as T

    c, cfg, spec, shapes = _setup(like)
    seed = 2**35 + 17
    params = make_params(shapes, seed, spec.vocab)
    max_len = c.mix.engine["max_len"]
    ref = Reference(spec, LayerWeights(shapes, seed, spec.vocab), max_len)
    rng = np.random.default_rng(0)
    plen, steps = 3 * PAGE - 5, 12  # decode crosses into a fresh page
    toks = rng.integers(0, spec.vocab, plen + steps, dtype=np.int32)
    per_seq = max_len // PAGE
    n_pages = per_seq + 1
    pool = T.init_paged_cache(cfg, n_pages, PAGE)
    table = np.zeros((1, per_seq), np.int32)
    table[0] = rng.permutation(np.arange(1, n_pages))[:per_seq]
    s = 4 * PAGE  # the power-of-two bucket of plen
    tokens = np.zeros((1, s), np.int32)
    tokens[0, :plen] = toks[:plen]
    logits, pool = T.prefill_paged(
        cfg, params, jnp.asarray(tokens), jnp.asarray([plen], jnp.int32), pool,
        jnp.asarray(table[:, : s // PAGE]),
    )
    got = [np.asarray(logits[0, : spec.vocab])]
    for i in range(steps - 1):
        p = plen + i
        logits, pool = T.decode_step_paged(
            cfg, params, pool, jnp.asarray([toks[p]]), jnp.asarray([p], jnp.int32),
            jnp.asarray(table), paged_impl="interpret",
        )
        got.append(np.asarray(logits[0, : spec.vocab]))
    want = ref.logits(toks[: plen + steps - 1], np.arange(plen - 1, plen + steps - 1))
    np.testing.assert_allclose(np.stack(got), want, atol=2e-4, rtol=2e-4)


@pytest.mark.parametrize("like", ["pixelfly-chat", "dense-chat"])
def test_fp8_control_fails_where_the_program_passes(like):
    """Through a whole run, with the fp8 control in the program's place
    in the comparison that decides ``correct``: the run comes out not
    correct, while the program's own served tokens (float32 at this
    size) read no gap on the same sample."""
    import time

    from bench import session

    c = smoke.cell(like, "chat", limit=SMOKE_LIMIT)
    result, extra = session.run(
        c, 99, 3.0, False, time.perf_counter(), test=smoke.options(c), control=True
    )
    assert not result["correct"]
    assert result["compared"]["served_logit_gap_max"]["value"] > SMOKE_LIMIT
    assert extra["program_gap"] <= 1e-3


def test_weights_regenerate_bit_for_bit():
    _, cfg, spec, shapes = _setup("pixelfly-chat")
    params = make_params(shapes, 5, spec.vocab)
    lw = LayerWeights(shapes, 5, spec.vocab)
    one = lw.layer(1)
    grp = params["groups"]["dense_0"]
    np.testing.assert_array_equal(np.asarray(one["attn/wq/blocks"]), np.asarray(grp["attn"]["wq"]["blocks"][1]))
    np.testing.assert_array_equal(np.asarray(lw.top()["embed/tok"]), np.asarray(params["embed"]["tok"]))
    tok = np.asarray(params["embed"]["tok"])
    assert not tok[spec.vocab :].any()


def test_backlog_fp8_control_fails_where_the_program_passes():
    """The same through the backlog cell's whole run: the longest-context
    request leads the sample, the control's picks fail, the program's
    own tokens read no gap."""
    import time

    from bench import session

    c = smoke.cell("pixelfly-longctx", "longctx", limit=SMOKE_LIMIT)
    result, extra = session.run(
        c, 2**31 + 99, 3.0, False, time.perf_counter(), test=smoke.options(c), control=True
    )
    assert not result["correct"]
    assert result["compared"]["served_logit_gap_max"]["value"] > SMOKE_LIMIT
    assert extra["program_gap"] <= 1e-3
    longest = max((r for r in extra["record"]["window"].reqs if r.served),
                  key=lambda r: (r.plen + len(r.served), r.idx))
    assert extra["sample"][0] is longest
