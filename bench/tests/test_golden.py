"""Values the benchmark's yardstick gives on the CPU at small widths,
pinned as literals and digests in ``golden.json`` (recorded before the
architecture, its weights and the arrival kinds moved into modules of
their own): the Qwen3 reference's logits and its fp8 control's, the
work counts at the published widths, the digests of the generated
weights, and the poisson schedules of both chat mixes. A change to the
yardstick that changes any of them changes what every cell reads."""

import hashlib
import json
from pathlib import Path

import jax
import numpy as np
import pytest

from bench import harness
from bench.tests import smoke
from bench.traffic import generator
from bench.weights import LayerWeights, make_params

GOLDEN = json.loads((Path(__file__).parent / "golden.json").read_text())
SEED = 2**33 + 7


def _digest(arrs) -> str:
    h = hashlib.sha256()
    for a in arrs:
        a = np.asarray(a)
        h.update(str(a.dtype).encode())
        h.update(str(a.shape).encode())
        h.update(np.ascontiguousarray(a).tobytes())
    return h.hexdigest()[:32]


def _smoke(like, dtype="float32"):
    from repro.models import transformer as T

    c = smoke.cell(like, "chat")
    o = smoke.options(c)
    conf = {**c.config, **o.widths, "torch_dtype": dtype}
    cfg = c.arch.program_config(conf, smoke={**o.program, "dtype": dtype})
    shapes = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.PRNGKey(0))
    return c, conf, shapes


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("like", ["pixelfly-chat", "dense-chat"])
def test_weights_digests(like, dtype):
    _, conf, shapes = _smoke(like, dtype)
    want = GOLDEN[f"weights/{like}/{dtype}"]
    vocab = conf["vocab_size"]
    assert _digest(jax.tree.leaves(make_params(shapes, SEED, vocab))) == want["params"]
    lw = LayerWeights(shapes, SEED, vocab)
    for li in (0, 1):
        layer = lw.layer(li)
        assert sorted(layer) == want["layer_keys"]
        assert _digest([layer[k] for k in sorted(layer)]) == want[f"layer{li}"]
    top = lw.top()
    assert sorted(top) == want["top_keys"]
    assert _digest([top[k] for k in sorted(top)]) == want["top"]


@pytest.mark.parametrize("precision", ["f32", "fp8"])
@pytest.mark.parametrize("like", ["pixelfly-chat", "dense-chat"])
def test_reference_logits(like, precision):
    c, conf, shapes = _smoke(like)
    vocab = conf["vocab_size"]
    ref = c.arch.reference(conf, LayerWeights(shapes, SEED, vocab), c.mix.engine["max_len"],
                           precision=precision)
    toks = np.random.default_rng(1).integers(0, vocab, 150).astype(np.int32)
    lg = ref.logits(toks, np.array([0, 75, 149]))
    want = GOLDEN[f"logits/{like}/{precision}"]
    assert lg.argmax(-1).tolist() == want["argmax"]
    np.testing.assert_allclose(lg[:, [0, 17, 255, 511]], want["cols"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(lg.sum(-1), want["sum"], rtol=1e-5, atol=1e-4)


@pytest.mark.parametrize("max_len", [5120, 11264])
@pytest.mark.parametrize("config", ["qwen3-1.7b-pixelfly", "qwen3-1.7b-dense"])
def test_work_counts(config, max_len):
    conf = json.loads((harness.ROOT / "bench" / "configs" / f"{config}.json").read_text())
    w = harness.arch_module(conf).work(conf, max_len)
    want = GOLDEN[f"work/{config}/{max_len}"]
    assert w.matmul_params == want["matmul_params"]
    assert [w.prefill_flops(n) for n in (1, 1000, 4096, 8192)] == want["prefill"]
    assert [w.decode_flops(p) for p in (0, 127, 128, 5000, 11000)] == want["decode"]
    assert list(w.paged_decode([0, 127, 1000, 5119, 11263])) == want["paged"]
    assert list(w.paged_decode([4095])) == want["paged1"]
    assert [list(w.bsr_matmul(r)) for r in (1, 16, 32)] == want["bsr"]


@pytest.mark.parametrize("mix", ["chat-0.8rps", "chat-0.48rps"])
def test_poisson_schedules(mix):
    m = generator.load_mix(mix)
    for seed in (2**31 + 12345, 7):
        s = generator.schedule(m, seed, 51.0, 151936)
        want = GOLDEN[f"sched/{mix}/{seed}"]
        assert len(s) == want["n"] and s.in_setup == 0
        assert _digest([s.due_s]) == want["due"]
        np.testing.assert_allclose(s.due_s[:3], want["due_head"], rtol=1e-12)
        assert _digest([s.prompt_len, s.output_len]) == want["lens"]
        assert _digest(s.prompts) == want["prompts"]
    # the sweep's rate override
    s = generator.schedule(m, 7, 51.0, 151936, rate=1.3)
    want = GOLDEN[f"sched/{mix}/rate1.3"]
    assert len(s) == want["n"]
    assert _digest([s.due_s]) == want["due"]
    assert _digest([s.prompt_len, s.output_len]) == want["lens"]
    assert _digest(s.prompts) == want["prompts"]
