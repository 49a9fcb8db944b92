"""Mesh-sharded serving: paged-pool sharding rules (always run) and
tensor-parallel / data-parallel stream parity on a simulated 8-device
mesh (run under ``JAX_PLATFORMS=cpu`` with
``--xla_force_host_platform_device_count=8`` — scripts/tier1.sh's mesh
leg; skipped on the default single-device test process).

The parity standard is the engine's own: identical *token streams*
(greedy argmax and seeded sampling), not bitwise logits — TP all-reduce
changes fp summation order, and the sampler's noise is keyed on
(request seed, sample index) only, so streams are device-layout
invariant.
"""

import jax
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import registry
from repro.distributed import sharding
from repro.launch.mesh import make_tp_mesh
from repro.serving import Engine, EngineConfig
from repro.serving.router import ReplicaRouter
from repro.serving.sampling import SamplingParams

requires_mesh = pytest.mark.skipif(
    len(jax.devices()) < 8,
    reason="needs a simulated 8-device mesh "
    "(XLA_FLAGS=--xla_force_host_platform_device_count=8)",
)


def _abstract_mesh(data: int, model: int):
    return jax.sharding.AbstractMesh((data, model), ("data", "model"))


# ----------------------------------------------------------------------
# Sharding rules (no devices needed — run in the default tier-1 pass)
# ----------------------------------------------------------------------


def test_paged_cache_specs_shard_kv_heads_when_divisible():
    st = sharding.Strategy(_abstract_mesh(1, 2), "tp")
    pools = [
        {
            "k": jax.ShapeDtypeStruct((3, 9, 4, 64, 64), np.float32),
            "v": jax.ShapeDtypeStruct((3, 9, 4, 64, 64), np.float32),
        }
    ]
    specs = sharding.cache_specs(st, pools, layout="paged")
    assert specs[0]["k"] == P(None, None, "model", None, None)
    assert specs[0]["v"] == P(None, None, "model", None, None)


def test_paged_cache_specs_head_dim_fallback_and_replication():
    st = sharding.Strategy(_abstract_mesh(1, 8), "tp")
    # kv_heads=2 does not divide tp=8; head_dim=64 does
    pool = {"k": jax.ShapeDtypeStruct((2, 9, 2, 64, 64), np.float32)}
    specs = sharding.cache_specs(st, [pool], layout="paged")
    assert specs[0]["k"] == P(None, None, None, None, "model")
    # neither head axis divisible -> fully replicated (never the page axes)
    pool = {"k": jax.ShapeDtypeStruct((2, 16, 3, 8, 5), np.float32)}
    specs = sharding.cache_specs(st, [pool], layout="paged")
    assert specs[0]["k"] == P(None, None, None, None, None)


def test_paged_cache_specs_fsdp_replicates():
    # fsdp strategy has no model axis: pools replicate, page axes and
    # head axes alike (DP is replica routing, not a sharded pool)
    st = sharding.Strategy(_abstract_mesh(4, 2), "fsdp")
    pool = {"k": jax.ShapeDtypeStruct((2, 9, 4, 64, 64), np.float32)}
    specs = sharding.cache_specs(st, [pool], layout="paged")
    assert specs[0]["k"] == P(None, None, None, None, None)


def test_cache_specs_decode_layout_unchanged():
    # the contiguous (count, B, S, ...) decode layout keeps its rule
    st = sharding.Strategy(_abstract_mesh(2, 2), "tp")
    caches = [{"k": jax.ShapeDtypeStruct((2, 4, 128, 4, 64), np.float32)}]
    specs = sharding.cache_specs(st, caches)
    assert specs[0]["k"][1] is not None  # batch dim sharded over data


def test_multi_device_mesh_takes_gather_paths():
    # a Pallas call cannot be partitioned over sharded operands: any
    # mesh of more than one device clamps every kernel impl to gather,
    # while one device keeps the platform default (None) or the request
    from repro.kernels.ops import impl_for_mesh

    for impl in (None, "pallas", "interpret", "gather"):
        assert impl_for_mesh(impl, 4) == "gather"
        assert impl_for_mesh(impl, 1) == impl


def test_unknown_cache_layout_raises():
    st = sharding.Strategy(_abstract_mesh(1, 2), "tp")
    with pytest.raises(ValueError):
        sharding.cache_specs(st, [], layout="nope")


# ----------------------------------------------------------------------
# Simulated-mesh parity (8 forced host devices)
# ----------------------------------------------------------------------


def _cfg():
    return registry.get_smoke("qwen3-1.7b", sparse=True).replace(
        num_layers=2, vocab_size=256
    )


def _prompts(n=4):
    rng = np.random.default_rng(0)
    return [
        rng.integers(1, 250, size=ln).astype(np.int32)
        for ln in (9, 17, 5, 12)[:n]
    ]


def _run_engine(tp: int, sampled: bool, cfg, params=None):
    eng = Engine(
        cfg,
        make_tp_mesh(tp),
        engine_cfg=EngineConfig(max_slots=4, max_len=64, prefix_cache=True),
        strategy="tp",
        seed=0,
        params=params,
    )
    for i, p in enumerate(_prompts()):
        sp = (
            SamplingParams(temperature=0.8, top_k=40, seed=100 + i)
            if sampled
            else None
        )
        eng.submit(p, 12, sampling=sp)
    fins = eng.drain(max_steps=80)
    return {f.uid: f.tokens.tolist() for f in fins}, eng


@requires_mesh
@pytest.mark.parametrize("tp", [2, 8])
@pytest.mark.parametrize("sampled", [False, True])
def test_tp_streams_bit_identical_to_single_device(tp, sampled):
    cfg = _cfg()
    base, _ = _run_engine(1, sampled, cfg)
    got, eng = _run_engine(tp, sampled, cfg)
    assert eng.paged_impl == "gather"  # pallas has no partitioning rule
    assert got == base


@requires_mesh
def test_tp_pool_buffers_actually_sharded():
    cfg = _cfg()
    _, eng = _run_engine(2, False, cfg)
    spec = tuple(eng.kv.buffers[0]["k"].sharding.spec)
    spec = spec + (None,) * (5 - len(spec))  # jax trims trailing Nones
    # smoke kv_heads=2 divides tp=2: classic head sharding on axis 2
    assert spec == (None, None, "model", None, None)
    assert eng.kv.shardings is not None


@requires_mesh
@pytest.mark.parametrize("tp", [1, 2])
def test_replica_router_streams_match_single_engine(tp):
    cfg = _cfg()
    base, _ = _run_engine(1, True, cfg)
    router = ReplicaRouter(
        cfg,
        replicas=2,
        tp=tp,
        engine_cfg=EngineConfig(max_slots=4, max_len=64, prefix_cache=True),
        seed=0,
    )
    uids = []
    for i, p in enumerate(_prompts()):
        uids.append(
            router.submit(
                p,
                12,
                sampling=SamplingParams(
                    temperature=0.8, top_k=40, seed=100 + i
                ),
            )
        )
    fins = {f.uid: f.tokens.tolist() for f in router.drain(max_steps=200)}
    # same submit order -> same router uids as the single engine's
    assert fins == base
    # traffic actually spread over both replicas
    assert all(n == 0 for n in router._outstanding)
    assert len(router.engines) == 2
    assert sum(e.stats.finished for e in router.engines) == len(uids)


@requires_mesh
def test_router_rejects_when_devices_insufficient():
    with pytest.raises(ValueError):
        ReplicaRouter(_cfg(), replicas=16, tp=8)


@requires_mesh
def test_router_zero_traffic_replica_observability():
    """A replica that never saw a request must not poison fleet
    aggregation: merged metrics stay parseable, fleet percentiles come
    from the replicas that do have samples, and the fleet /slo state
    is well-defined."""
    from repro.obs import SloConfig
    from repro.obs.prom import parse, render

    cfg = _cfg()
    router = ReplicaRouter(
        cfg,
        replicas=2,
        engine_cfg=EngineConfig(
            max_slots=4,
            max_len=64,
            monitor=60.0,
            slo=SloConfig(
                target=0.99, fast_window_s=10.0, slow_window_s=60.0
            ),
        ),
        seed=0,
    )
    # one request -> least-loaded routing sends it to replica 0 only
    router.submit(_prompts(1)[0], 8)
    fins = router.drain(max_steps=80)
    assert len(fins) == 1
    assert router.engines[0].stats.finished == 1
    assert router.engines[1].stats.finished == 0

    flat = parse(render(router.merged_metrics()))
    assert flat["repro_serve_requests_finished_total"] == 1

    s = router.stats_summary()
    assert s["requests_finished"] == 1
    assert [r["requests_finished"] for r in s["per_replica"]] == [1, 0]
    assert s["per_replica"][1]["p50_token_latency_ms"] == 0.0

    v = router.windowed_vars()
    assert v["enabled"] and v["replicas"] == 2
    # fleet percentile == replica 0's (replica 1 contributes nothing,
    # and an average-of-averages would halve it)
    v0 = router.engines[0].windowed_vars()
    assert v["token_latency_ms"] == v0["token_latency_ms"]
    assert v["queue_depth"] == 0 and v["running_slots"] == 0

    slo = router.slo_state()
    assert slo["enabled"] and slo["state"] == "OK"
    assert len(slo["per_replica"]) == 2
