"""End-to-end system behaviour: the full lifecycle a production run sees —
train, checkpoint, preempt, ELASTIC restart on a different mesh layout,
continue training, then serve from the trained weights."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import Server
from repro.training.data import SyntheticLM
from repro.training.loop import TrainConfig, Trainer
from repro.training.optimizer import OptConfig


def _mk(tmp_path, mesh, steps=40):
    cfg = registry.get_smoke("qwen3-1.7b", sparse=True).replace(
        num_layers=2, vocab_size=128
    )
    data = SyntheticLM(128, 32, 4, seed=0)
    return Trainer(
        cfg,
        OptConfig(lr=5e-3, warmup_steps=2, total_steps=steps),
        data,
        mesh,
        TrainConfig(steps=steps, ckpt_dir=str(tmp_path), ckpt_every=100,
                    log_every=1000),
    )


@pytest.mark.slow
def test_full_lifecycle(tmp_path):
    d = tmp_path / "run"
    # phase 1: train on a (1, 1) data x model mesh, then "preempt"
    t1 = _mk(d, make_local_mesh(data=1, model=1))
    h1 = t1.run(12)
    t1._on_preempt(None, None)
    t1.run(5)  # stops immediately + checkpoints
    from repro.training import checkpoint as ck
    assert ck.latest_step(str(d)) == 12

    # phase 2: ELASTIC restart on a different mesh layout (model axis used)
    t2 = _mk(d, make_local_mesh(data=1, model=1))
    assert t2.step == 12
    h2 = t2.run(10)
    # training continues downward overall
    assert np.mean([h["loss"] for h in h2[-3:]]) < h1[0]["loss"]

    # phase 3: serve from the trained parameters
    cfg = t2.model_cfg
    server = Server(cfg, t2.mesh)
    server.params = t2.state["params"]
    prompts = np.random.default_rng(0).integers(
        0, cfg.vocab_size, size=(2, 8), dtype=np.int32
    )
    out = server.generate(prompts, gen_len=4)
    assert out.shape == (2, 4)
    assert (out >= 0).all() and (out < cfg.padded_vocab).all()


def test_walker_agrees_with_xla_on_loop_free_programs():
    """Property: on programs without loops, the HLO walker's FLOPs match
    XLA's own cost_analysis (the walker only *adds* trip-count awareness)."""
    from repro.analysis import roofline

    rng = np.random.default_rng(0)
    for m, k, n in [(64, 32, 16), (128, 128, 128), (96, 256, 32)]:
        a = jnp.asarray(rng.standard_normal((m, k)), jnp.float32)
        b = jnp.asarray(rng.standard_normal((k, n)), jnp.float32)
        c = jax.jit(lambda a, b: (a @ b)).lower(a, b).compile()
        walker = roofline.analyze_hlo(c.as_text()).flops
        ca = c.cost_analysis() or {}
        xla = ca.get("flops", 0.0)
        assert abs(walker - xla) <= 0.02 * max(walker, xla) + 1, (m, k, n)


def test_budget_allocation_end_to_end():
    """§3.3 rule of thumb: every layer type gets density ~= the global
    budget; the realized model density is within tolerance of the ask."""
    from repro.analysis.roofline import active_params

    for density in [0.15, 0.3]:
        cfg_s = registry.get("qwen3-1.7b", sparse=True, density=density)
        cfg_d = registry.get("qwen3-1.7b")
        ratio = active_params(cfg_s) / active_params(cfg_d)
        assert density * 0.5 < ratio < density * 2.0 + 0.1, (density, ratio)
