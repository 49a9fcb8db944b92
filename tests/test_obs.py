"""Observability layer: metrics registry, span tracer, exporters, and
their engine wiring.

The load-bearing contracts, in rough order of importance:

  * Tracing is observation only — a tracer-disabled engine emits
    bit-identical tokens AND does literally zero obs work on the decode
    hot path (proved by counting calls into the tracer's clock).
  * ``stats_summary()`` keeps its exact schema: BENCH trajectories and
    the goodput report parse it by key.
  * The Prometheus snapshot and ``stats_summary()`` are two views of
    the same registry and must agree.
  * Per-request spans survive preemption+resume with sane ordering,
    and the Perfetto export of a real serve validates (matched B/E,
    monotonic timestamps, nonempty slot tracks).
  * ``Engine.reset_stats()`` mid-traffic resets registry and ring
    atomically: open spans close as truncated, nothing dangles.
"""

import json

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis.guards import DispatchGuard
from repro.configs import registry
from repro.launch.mesh import make_local_mesh
from repro.obs import (
    MetricsRegistry,
    NULL_TRACER,
    Tracer,
    validate_trace_file,
)
from repro.obs.perfetto import TraceValidationError, validate_trace
from repro.obs.prom import parse, render, write_snapshot
from repro.serving import Engine, EngineConfig, ScheduleParams
from repro.serving.router import ReplicaRouter


def _smoke_cfg(**kw):
    return registry.get_smoke("qwen3-1.7b").replace(
        num_layers=2, vocab_size=128, **kw
    )


def _mesh():
    return make_local_mesh()


# ----------------------------------------------------------------------
# metrics primitives (no engine)
# ----------------------------------------------------------------------


def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    c = reg.counter("c_total", "a counter")
    c.inc()
    c.inc(4)
    assert c.value == 5 and isinstance(c.value, int)
    with pytest.raises(ValueError):
        c.inc(-1)

    lc = reg.counter("lc_total", "labeled", labelname="bucket")
    lc.inc(2, label=(4, 32))
    lc.inc(1, label=(4, 32))
    lc.inc(7, label=(8, 64))
    assert lc.get((4, 32)) == 3 and lc.value == 10

    g = reg.gauge("g", "a gauge")
    g.set(3)
    g.inc(-1)
    assert g.value == 2

    h = reg.histogram("h_seconds", "a histogram")
    for v in (0.001, 0.002, 0.003, 0.004):
        h.observe(v)
    assert h.count == 4 and h.sum == pytest.approx(0.010)
    assert h.percentile(50) == pytest.approx(
        float(np.percentile([0.001, 0.002, 0.003, 0.004], 50))
    )
    # cumulative buckets are monotone and end at count
    cum = h.cumulative_buckets()
    assert [n for _, n in cum] == sorted(n for _, n in cum)
    assert cum[-1][1] == 4

    # get-or-create returns the same object; kind mismatch is an error
    assert reg.counter("c_total", "a counter") is c
    with pytest.raises(TypeError):
        reg.gauge("c_total", "wrong kind")


def test_registry_merge_sums_and_concatenates():
    a, b = MetricsRegistry(), MetricsRegistry()
    a.counter("n_total", "n").inc(2)
    b.counter("n_total", "n").inc(3)
    a.counter("lab_total", "l", labelname="k").inc(1, label="x")
    b.counter("lab_total", "l", labelname="k").inc(5, label="x")
    b.counter("only_b_total", "o").inc(9)
    a.histogram("lat_seconds", "l").observe(1.0)
    b.histogram("lat_seconds", "l").observe(3.0)
    m = MetricsRegistry.merged([a, b])
    assert m["n_total"].value == 5
    assert m["lab_total"].get("x") == 6
    assert m["only_b_total"].value == 9
    # merged percentiles are over the union of raw samples, not
    # averages of per-registry percentiles
    assert m["lat_seconds"].count == 2
    assert m["lat_seconds"].percentile(50) == pytest.approx(2.0)
    # sources unchanged
    assert a["n_total"].value == 2 and b["n_total"].value == 3


def test_prom_render_parse_roundtrip():
    reg = MetricsRegistry()
    reg.counter("repro_x_total", "things").inc(7)
    lc = reg.counter("repro_y_total", "labeled", labelname="bucket")
    lc.inc(3, label=(4, 32))
    reg.gauge("repro_g", "gauge").set(2)
    h = reg.histogram("repro_h_seconds", "hist")
    h.observe(0.5)
    h.observe(2.0)
    text = render(reg)
    assert "# HELP repro_x_total things" in text
    assert "# TYPE repro_h_seconds histogram" in text
    got = parse(text)
    assert got["repro_x_total"] == 7.0
    assert got['repro_y_total{bucket="4x32"}'] == 3.0
    assert got["repro_g"] == 2.0
    assert got["repro_h_seconds_count"] == 2.0
    assert got["repro_h_seconds_sum"] == pytest.approx(2.5)
    assert got['repro_h_seconds_bucket{le="+Inf"}'] == 2.0
    with pytest.raises(ValueError):
        parse("repro_bad_total not-a-number\n")


# ----------------------------------------------------------------------
# tracer primitives (no engine)
# ----------------------------------------------------------------------


def test_tracer_interning_and_ring_wrap():
    tr = Tracer(capacity=8)
    t = tr.track("t")
    assert tr.track("t") == t  # stable ids
    names = [tr.name(f"n{i}") for i in range(20)]
    for n in names:
        tr.instant(t, n)
    assert tr.n_recorded == 20 and tr.n_events == 8
    evs = tr.events()
    # oldest-first window over the last `capacity` events
    assert [e["name"] for e in evs] == [f"n{i}" for i in range(12, 20)]
    assert all(
        a["ts_ns"] <= b["ts_ns"] for a, b in zip(evs, evs[1:])
    )


def test_tracer_reset_truncates_open_spans():
    tr = Tracer(capacity=64)
    t = tr.track("t")
    n = tr.name("span")
    tr.begin(t, n)
    tr.begin(t, n)  # nested
    assert tr.open_spans() == {"t": ["span", "span"]}
    tr.reset()
    assert tr.truncated_spans == 2
    assert tr.open_spans() == {} and tr.n_events == 0
    # ends for pre-reset spans are no-ops, not corruption
    tr.end(t, n)
    assert tr.n_events == 0
    # fresh spans after reset work normally
    tr.begin(t, n)
    tr.end(t, n)
    assert [e["kind"] for e in tr.events()] == [0, 1]


def test_null_tracer_surface():
    assert NULL_TRACER.enabled is False
    assert NULL_TRACER.begin(0, 0) == 0
    NULL_TRACER.end(0, 0)
    NULL_TRACER.reset()
    assert NULL_TRACER.events() == []
    with pytest.raises(RuntimeError):
        NULL_TRACER.export_perfetto("/dev/null")


def test_perfetto_validator_rejects_garbage(tmp_path):
    with pytest.raises(TraceValidationError):
        validate_trace({"traceEvents": "nope"})
    # unmatched E for a never-opened span
    bad = {
        "traceEvents": [
            {"ph": "E", "pid": 0, "tid": 0, "ts": 1, "name": "x"},
        ]
    }
    with pytest.raises(TraceValidationError):
        validate_trace(bad)


# ----------------------------------------------------------------------
# engine wiring
# ----------------------------------------------------------------------


def test_traced_engine_spans_survive_preempt_resume(tmp_path):
    """Per-request lifecycle under preemption: the victim's decode span
    closes at preemption (a1=1), swap_out/swap_in instants bracket the
    host round-trip, a new decode span opens at resume, and the whole
    timeline exports to a valid Perfetto file."""
    cfg = _smoke_cfg()
    eng = Engine(
        cfg,
        _mesh(),
        engine_cfg=EngineConfig(max_slots=2, max_len=128, trace=True),
    )
    rng = np.random.default_rng(1)
    bg = [
        eng.submit(rng.integers(1, 127, 8).astype(np.int32), 40)
        for _ in range(2)
    ]
    for _ in range(6):
        eng.step()
    eng.submit(
        rng.integers(1, 127, 8).astype(np.int32),
        4,
        schedule=ScheduleParams(priority=3, deadline_s=120.0),
    )
    fins = eng.drain(max_steps=500)
    assert eng.stats.preemptions >= 1
    victims = [f.uid for f in fins if f.preemptions > 0]
    assert victims and set(victims) <= set(bg)
    uid = victims[0]

    evs = [e for e in eng.tracer.events() if e["a0"] == uid]
    by_name = {}
    for e in evs:
        by_name.setdefault(e["name"], []).append(e)
    # preemption closed the decode span with the marker arg...
    closes = [
        e for e in by_name["decode"] if e["kind"] == 1 and e["a1"] == 1
    ]
    assert len(closes) == 1
    # ...and the lifecycle instants appear in causal order
    order = [
        by_name["preempt"][0]["ts_ns"],
        by_name["swap_out"][0]["ts_ns"],
        by_name["swap_in"][0]["ts_ns"],
        by_name["finished"][0]["ts_ns"],
    ]
    assert order == sorted(order)
    assert closes[0]["ts_ns"] <= by_name["swap_out"][0]["ts_ns"]
    # resume opened a fresh decode span after the swap_in
    reopens = [
        e
        for e in by_name["decode"]
        if e["kind"] == 0 and e["ts_ns"] >= by_name["swap_in"][0]["ts_ns"]
    ]
    assert reopens
    # queue-churn instants from the scheduler hook
    kinds = {e["name"] for e in eng.tracer.events()}
    assert {"submit", "admit", "resume", "queued", "prefill"} <= kinds

    out = tmp_path / "trace.json"
    n = eng.export_perfetto(str(out))
    rep = validate_trace_file(str(out))
    assert rep["events"] == n and rep["slot_tracks"] >= 1
    assert rep["spans"] > 0
    # per-step engine spans correlate compiles: steady-state decode
    # steps carry a zero compile delta
    steps = [
        e
        for e in eng.tracer.events()
        if e["name"] == "decode_step" and e["kind"] == 1
    ]
    assert steps and all(e["a1"] >= 0 for e in steps)
    assert any(e["a1"] == 0 for e in steps)


def test_tracer_disabled_bit_identical_and_zero_obs_work(monkeypatch):
    """trace=False must be free: same tokens, and not a single call
    into the tracer clock from the serve loop."""
    import repro.obs.trace as trace_mod

    cfg = _smoke_cfg()
    mesh = _mesh()
    rng = np.random.default_rng(5)
    prompts = [rng.integers(1, 127, 12).astype(np.int32) for _ in range(3)]

    calls = {"n": 0}
    real = trace_mod.perf_counter_ns

    def counting():
        calls["n"] += 1
        return real()

    monkeypatch.setattr(trace_mod, "perf_counter_ns", counting)

    streams = {}
    for on in (False, True):
        eng = Engine(
            cfg,
            mesh,
            engine_cfg=EngineConfig(max_slots=2, max_len=64, trace=on),
        )
        calls["n"] = 0
        for p in prompts:
            eng.submit(p, 8)
        fins = eng.drain(max_steps=300)
        streams[on] = [
            f.tokens.tolist() for f in sorted(fins, key=lambda f: f.uid)
        ]
        if on:
            assert calls["n"] > 0 and eng.tracer.n_recorded > 0
        else:
            assert calls["n"] == 0, (
                "disabled engine touched the tracer clock "
                f"{calls['n']} time(s)"
            )
    assert streams[True] == streams[False]


def test_stats_summary_golden_keys():
    """The exact stats_summary schema — BENCH trajectories, bench_diff
    and the goodput report all index into this dict by key."""
    cfg = _smoke_cfg()
    eng = Engine(
        cfg,
        _mesh(),
        engine_cfg=EngineConfig(
            max_slots=2, max_len=64, prefix_cache=True, trace=True
        ),
    )
    rng = np.random.default_rng(6)
    for _ in range(3):
        eng.submit(rng.integers(1, 127, 12).astype(np.int32), 4)
    eng.drain(max_steps=300)
    s = eng.stats_summary()
    assert list(s) == [
        "requests_finished",
        "generated_tokens",
        "by_sampler",
        "pages_reclaimed_early",
        "prefix_cache",
        "preemption",
        "rejected",
        "slo",
        "ttft_ms",
        "queue_wait_ms",
        "dispatch_guard",
        "prefill_calls",
        "prefill_requests",
        "mean_prefill_batch",
        "prefill_by_bucket",
        "prefill_tokens",
        "prefill_s",
        "decode_s",
        "total_s",
        "decode_steps",
        "tok_s",
        "decode_tok_s",
        "prefill_tok_s",
        "p50_token_latency_ms",
        "p95_token_latency_ms",
        "p99_token_latency_ms",
        "mean_occupancy",
        "min_occupancy",
        "max_occupancy",
    ]
    assert set(s["prefix_cache"]) == {
        "enabled",
        "lookups",
        "hit_tokens",
        "prompt_tokens",
        "hit_pages",
        "hit_rate",
        "cow_copies",
        "decode_indexed_pages",
        "inserted_pages",
        "evicted_pages",
        "cached_pages",
    }
    assert set(s["preemption"]) == {
        "preemptions",
        "resumes",
        "swap_outs",
        "swap_ins",
        "out_pages",
        "in_pages",
        "out_bytes",
        "in_bytes",
        "pinned_pages",
    }
    assert set(s["dispatch_guard"]) == {"step_compiles", "host_syncs"}
    assert set(s["slo"]) == {"with_deadline", "met", "attainment"}
    assert set(s["ttft_ms"]) == {"p50_ms", "p95_ms", "p99_ms"}
    assert s["by_sampler"] == {"greedy": {"requests": 3, "tokens": 12}}
    assert s["requests_finished"] == 3
    # one sanctioned host sync per decode step, plus one per prefill
    assert s["dispatch_guard"]["host_syncs"] >= s["decode_steps"]
    # everything is JSON-serializable (the BENCH payload requires it)
    json.dumps(s)


def test_prom_snapshot_agrees_with_stats_summary(tmp_path):
    cfg = _smoke_cfg()
    eng = Engine(
        cfg, _mesh(), engine_cfg=EngineConfig(max_slots=2, max_len=64)
    )
    rng = np.random.default_rng(7)
    for _ in range(3):
        eng.submit(rng.integers(1, 127, 10).astype(np.int32), 5)
    eng.drain(max_steps=300)
    s = eng.stats_summary()
    out = tmp_path / "metrics.prom"
    write_snapshot(str(out), eng.metrics)
    got = parse(out.read_text())
    assert got["repro_serve_requests_finished_total"] == s[
        "requests_finished"
    ]
    assert got["repro_serve_generated_tokens_total"] == s[
        "generated_tokens"
    ]
    assert got["repro_serve_decode_steps_total"] == s["decode_steps"]
    assert got["repro_serve_prefill_tokens_total"] == s["prefill_tokens"]
    assert got['repro_serve_finished_by_sampler_total{sampler="greedy"}'] \
        == s["by_sampler"]["greedy"]["requests"]
    assert got["repro_serve_step_latency_seconds_count"] == s[
        "decode_steps"
    ]
    assert got["repro_serve_host_syncs_total"] == s["dispatch_guard"][
        "host_syncs"
    ]


def test_reset_stats_mid_traffic_is_atomic(tmp_path):
    """reset_stats() while requests are in flight: the registry zeroes,
    open spans close as truncated (no orphan B), and both the summary
    and a subsequent export stay consistent."""
    cfg = _smoke_cfg()
    eng = Engine(
        cfg,
        _mesh(),
        engine_cfg=EngineConfig(max_slots=2, max_len=64, trace=True),
    )
    rng = np.random.default_rng(8)
    for _ in range(2):
        eng.submit(rng.integers(1, 127, 10).astype(np.int32), 12)
    for _ in range(3):
        eng.step()
    assert eng.tracer.open_spans()  # decode spans are live mid-traffic
    before = eng.stats.decode_steps
    assert before > 0

    eng.reset_stats()
    assert eng.tracer.truncated_spans > 0
    assert eng.tracer.open_spans() == {}
    assert eng.stats.decode_steps == 0 and eng.stats.finished == 0
    assert eng.metrics["repro_serve_decode_steps_total"].value == 0

    fins = eng.drain(max_steps=300)
    assert len(fins) == 2  # traffic survives the reset
    s = eng.stats_summary()
    assert s["requests_finished"] == 2
    assert s["decode_steps"] > 0
    # the post-reset ring still exports cleanly: pre-reset decode spans
    # were force-closed, so their late end() calls recorded nothing
    out = tmp_path / "after_reset.json"
    eng.export_perfetto(str(out))
    validate_trace_file(str(out))
    # the stats view rebind is total: ServeStats/SwapStats/PrefixStats
    # all write into the fresh registry
    assert eng.stats.registry is eng.metrics
    assert eng.swap.stats.out_pages == 0


def test_engine_config_trace_validation():
    with pytest.raises(ValueError):
        EngineConfig(max_slots=1, max_len=32, trace=-4)
    assert EngineConfig(max_slots=1, max_len=32, trace=1024).trace == 1024


def test_router_merged_stats_and_export(tmp_path):
    cfg = _smoke_cfg()
    router = ReplicaRouter(
        cfg,
        replicas=1,
        engine_cfg=EngineConfig(max_slots=2, max_len=64, trace=True),
    )
    rng = np.random.default_rng(9)
    for _ in range(3):
        router.submit(rng.integers(1, 127, 10).astype(np.int32), 4)
    fins = router.drain(max_steps=300)
    assert len(fins) == 3
    s = router.stats_summary()
    assert s["requests_finished"] == 3
    assert len(s["per_replica"]) == 1
    assert s["per_replica"][0]["requests_finished"] == 3
    # merged registry agrees with the single replica's own
    assert (
        router.merged_metrics()["repro_serve_generated_tokens_total"].value
        == router.engines[0].stats.generated
    )
    out = tmp_path / "router_trace.json"
    n = router.export_perfetto(str(out))
    rep = validate_trace_file(str(out))
    assert rep["events"] == n and rep["slot_tracks"] >= 1
    router.reset_stats()
    assert router.stats_summary()["requests_finished"] == 0


def test_dispatch_guard_feeds_metrics_registry():
    reg = MetricsRegistry()
    with DispatchGuard(
        max_compiles=None, raise_on_sync=False, metrics=reg
    ):
        y = jax.jit(lambda x: x + 1)(jnp.arange(3.0))
        jax.device_get(y)
    assert reg["repro_guard_explicit_syncs_total"].value == 1
    assert reg["repro_guard_compiles_total"].value >= 1
    assert reg["repro_guard_implicit_syncs_total"].value == 0
    # counters accumulate across guarded regions on the same registry
    with DispatchGuard(
        max_compiles=None, raise_on_sync=False, metrics=reg
    ):
        jax.device_get(jnp.zeros(2))
    assert reg["repro_guard_explicit_syncs_total"].value == 2
