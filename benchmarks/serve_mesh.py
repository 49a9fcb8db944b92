"""Mesh-serving benchmark: tensor-parallel decode throughput per device
count, plus data-parallel replica routing, on a simulated host mesh.

Device count is fixed at the first backend initialization, so this
module force-creates its simulated devices *before importing jax*
(``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count=8``)
and therefore must run in its own process —
``benchmarks/serve_engine.py`` invokes it via ``subprocess`` and folds
the result into ``BENCH_serve.json`` as the ``mesh`` trajectory.

Per tp in {1, 2, 4, 8}: one engine on a ``(1, tp)`` device slice with
the KV pools sharded over the ``model`` axis (``PagedKVCache``'s paged
layout), serving the identical uniform trace. Token streams — greedy
*and* seeded-sampled — are asserted bit-identical to the tp=1 engine's
(``streams_equal``); the per-tp rows track decode tok/s so the
trajectory shows how sharded decode scales with device count. A
2-replica ``ReplicaRouter`` run rides along for the data-parallel path,
stream-checked against the same oracle.

Simulated CPU devices share one host, so tok/s here measures sharding
*overhead*, not speedup — the number to watch is how far below the
tp=1 row the tp=8 row sits, and that streams stay equal.

Progress goes to stderr; the final line on stdout is the JSON payload.

  python -m benchmarks.serve_mesh [--smoke]
"""

from __future__ import annotations

import os


def _force_host_devices(n: int = 8) -> None:
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n}".strip()
        )
    os.environ.setdefault("JAX_PLATFORMS", "cpu")


_force_host_devices()  # must precede the jax import

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

import jax  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import registry  # noqa: E402
from repro.launch.mesh import make_tp_mesh  # noqa: E402
from repro.serving import Engine, EngineConfig, SamplingParams  # noqa: E402
from repro.serving.router import ReplicaRouter  # noqa: E402

ARCH = "qwen3-1.7b"


def _log(msg: str) -> None:
    print(f"serve_mesh: {msg}", file=sys.stderr, flush=True)


def _serve(eng, prompts, gen: int, sampled: bool) -> dict[int, list[int]]:
    for i, p in enumerate(prompts):
        sp = (
            SamplingParams(temperature=0.8, top_k=40, seed=100 + i)
            if sampled
            else None
        )
        eng.submit(p, gen, sampling=sp)
    fins = eng.drain()
    # uid counters run across serves; key by submit order so streams
    # from different engines/passes compare directly
    ordered = sorted(fins, key=lambda f: f.uid)
    return {i: f.tokens.tolist() for i, f in enumerate(ordered)}


def _measure_tp(tp: int, cfg, prompts, gen: int, repeats: int):
    """One engine on a (1, tp) slice: serve the trace greedy and
    sampled (first pass warms each program set), then time greedy
    repeats and keep the best decode tok/s."""
    eng = Engine(
        cfg,
        make_tp_mesh(tp),
        engine_cfg=EngineConfig(
            max_slots=len(prompts), max_len=len(prompts[0]) + gen + 1
        ),
        strategy="tp",
        seed=0,
    )
    greedy = _serve(eng, prompts, gen, sampled=False)  # warms greedy jits
    sampled = _serve(eng, prompts, gen, sampled=True)  # warms sampled jits
    best = None
    for _ in range(repeats):
        eng.reset_stats()
        t0 = time.perf_counter()
        fins = _serve(eng, prompts, gen, sampled=False)
        wall = time.perf_counter() - t0
        out = eng.stats_summary()
        out["wall_tok_s"] = round(
            sum(len(t) for t in fins.values()) / wall, 2
        )
        out["wall_s"] = round(wall, 4)
        if best is None or out["decode_tok_s"] > best["decode_tok_s"]:
            best = out
    row = {
        "devices": tp,
        "paged_impl": eng.paged_impl,
        "decode_tok_s": best["decode_tok_s"],
        "wall_tok_s": best["wall_tok_s"],
        "wall_s": best["wall_s"],
        "p95_token_latency_ms": best["p95_token_latency_ms"],
    }
    return row, greedy, sampled


def _measure_router(cfg, prompts, gen: int, oracle: dict) -> dict:
    """2-replica data-parallel routing (tp=1 per replica): router uids
    follow submit order, so streams must equal the single engine's."""
    router = ReplicaRouter(
        cfg,
        replicas=2,
        tp=1,
        engine_cfg=EngineConfig(
            max_slots=len(prompts), max_len=len(prompts[0]) + gen + 1
        ),
        seed=0,
    )
    _serve(router, prompts, gen, sampled=True)  # warm both replicas
    t0 = time.perf_counter()
    streams = _serve(router, prompts, gen, sampled=True)
    wall = time.perf_counter() - t0
    equal = streams == oracle
    assert equal, "replica routing changed token streams"
    return {
        "replicas": 2,
        "tp": 1,
        "wall_s": round(wall, 4),
        "wall_tok_s": round(
            sum(len(t) for t in streams.values()) / wall, 2
        ),
        "streams_equal": equal,
        "per_replica_finished": [
            int(e.stats.finished) for e in router.engines
        ],
    }


def run(smoke: bool = False) -> dict:
    cfg = registry.get_smoke(ARCH, sparse=True)
    batch, prompt_len, gen, repeats = 4, 32, 16, 2
    if smoke:
        cfg = cfg.replace(num_layers=2, vocab_size=256)
        batch, prompt_len, gen, repeats = 2, 8, 4, 1
    n_dev = len(jax.devices())
    tps = [t for t in (1, 2, 4, 8) if t <= n_dev]
    rng = np.random.default_rng(0)
    prompts = [
        rng.integers(0, cfg.vocab_size, prompt_len).astype(np.int32)
        for _ in range(batch)
    ]

    by_tp, oracle_g, oracle_s = {}, None, None
    equal = True
    for tp in tps:
        _log(f"tp={tp} ({n_dev} devices, smoke={smoke})")
        row, greedy, sampled = _measure_tp(tp, cfg, prompts, gen, repeats)
        if tp == 1:
            oracle_g, oracle_s = greedy, sampled
        else:
            ok = greedy == oracle_g and sampled == oracle_s
            equal = equal and ok
            assert ok, f"tp={tp} streams diverged from single-device oracle"
        by_tp[str(tp)] = row
    _log("router replicas=2")
    router = _measure_router(cfg, prompts, gen, oracle_s)

    payload = {
        "smoke": smoke,
        "devices": n_dev,
        "platform": jax.devices()[0].platform,
        "batch": batch,
        "prompt_len": prompt_len,
        "gen": gen,
        "streams_equal": equal,
        "by_tp": by_tp,
        "router": router,
    }
    print(json.dumps(payload), flush=True)
    return payload


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale dry run (tier-1 gate)")
    run(smoke=ap.parse_args().smoke)
