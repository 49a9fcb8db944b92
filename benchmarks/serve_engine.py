"""Serving benchmark: continuous-batching engine vs the fixed-batch Server.

Three measurements on the same smoke config and shared weights:

1. **uniform** — the exact workload the seed ``Server`` can run (one
   fixed-size batch, equal prompt/gen lengths) on both paths. The engine
   wins on prefill alone: one jit'd bucketed pass vs a per-token python
   loop through the decode step.
2. **mixed** — what only the engine can do: ragged prompt/gen lengths,
   twice as many requests as slots, late arrivals submitted mid-flight.
   Continuous batching shows up in the occupancy stats (slots refill the
   step after an eviction).
3. **prefill-heavy** — many short ragged requests with tiny gen lengths,
   where admission dominates: batched bucketed prefill (one jit'd call +
   one host sync per same-bucket group) vs the per-request-admission
   baseline (``max_prefill_batch=1``) on the identical trace. Both
   engines are warmed up front and each repeat measures the two modes
   back-to-back; the committed speedup is the median *paired* ratio,
   so a patch of machine load hits both legs of a pair instead of
   skewing whichever mode's block it landed in.
4. **decode-by-sampler** — the uniform workload served greedy vs fully
   sampled (temperature + top-k + top-p + repetition penalty, seeded per
   request). Sampling is fused into the jit'd decode step, so sampled
   decode tok/s should sit within ~10% of greedy.
5. **prefix-cache** — a shared-system-prompt trace (every request = one
   long common prefix + a short unique tail, tiny gens: admission/TTFT
   dominates) served with the radix-tree prefix cache on vs off on
   identical engines. Cache-on admissions map the shared prefix pages
   straight into the slot and prefill only the suffix, so
   ``admission_speedup`` (prefill seconds, off/on) is the headline
   number; token streams are asserted identical either way.
6. **goodput** — SLO-aware scheduling under seeded traffic
   (``repro.serving.workloads``): a *burst* trace (deadline'd
   high-priority burst landing on a pool full of long background
   decodes) and a *long-tail* trace (open-loop Poisson arrivals, an
   interactive deadline'd tier over a heavy batch tail), each served
   with preemption on vs off on the same seed. The headline is SLO
   attainment: with preemption the burst swaps the background out to
   host memory (``repro.serving.swap``) and meets its deadlines;
   without, it queues behind the slots and misses them. Token streams
   are asserted bit-identical across modes — preemption is a pure
   scheduling change. A *chat* trace (multi-turn conversations, prefix
   cache on) rides along to measure turn-2+ admissions hitting the
   decode-written pages the engine indexes at finish.
7. **observability** — tracer overhead: the uniform workload on
   identical warm engines with span tracing on vs off, measured as
   paired repeats (median traced/off decode-tok/s ratio). Tracing must
   stay near-free (~2% budget at production scale; the smoke floor is
   looser because microsecond steps amplify scheduler jitter) and must
   not change a single token. ``--trace-out`` exports the traced ring
   as Perfetto JSON, which tier 1 round-trips through the validator.
8. **observability_live** — the full live-telemetry plane (rolling
   windows, burn-rate SLO monitor, per-step memory gauges) on vs off,
   same paired-repeat protocol as scenario 7 with a committed 0.95
   monitored/off decode floor, streams bit-identical. ``--listen``
   additionally scrapes ``/metrics`` + ``/healthz`` *mid-decode* and
   asserts the ``/vars`` windowed percentiles agree with the final
   ``stats_summary()``. A *slo_shed* sub-scenario overloads a
   no-preemption engine with long low-priority decodes against a
   deadline'd high-priority stream: with ``SloConfig(shed=True)`` the
   CRITICAL burn state drops the queued background as structured
   ``REJECT_SHED`` rejections and high-priority SLO attainment must be
   strictly higher than with shedding off.
9. **mesh** — tensor-parallel decode on a simulated 8-device host mesh
   plus 2-replica data-parallel routing, via ``benchmarks.serve_mesh``
   in a subprocess (the simulated devices must be forced before jax
   initializes a backend, which this process has already done). Tracks
   decode tok/s per device count and asserts greedy and sampled streams
   bit-identical to the single-device engine's.

Every (N, S) prefill bucket a timed trace will hit is compiled *before*
the clock starts (``_warm_buckets``), so latency percentiles measure
steady-state serving, not JIT.

Emits one CSV row per scenario and writes ``BENCH_serve.json`` (under
``--json DIR`` when invoked via ``benchmarks.run``).

``--smoke`` shrinks the model and every trace to a seconds-scale dry
run of every scenario (JSON goes to a temp dir, never clobbering the
tracked ``BENCH_serve.json``) — ``scripts/tier1.sh`` runs it so
benchmark-script breakage fails tier 1 instead of rotting silently.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import subprocess
import sys
import time
from pathlib import Path

import jax
import numpy as np

from benchmarks.common import emit, emit_json
from repro.configs import registry
from repro.launch.mesh import make_local_mesh
from repro.launch.serve import Server
from repro.serving import Engine, EngineConfig, SamplingParams, ServeStats
from repro.serving import workloads

ARCH = "qwen3-1.7b"
BATCH = 4
PROMPT_LEN = 32
GEN = 16
# the fully-loaded sampled scenario (every filter live)
SAMPLED = SamplingParams(
    temperature=0.8, top_k=40, top_p=0.95, repetition_penalty=1.1
)


def _warm_buckets(
    engine: Engine,
    lens: list[int],
    sampling: SamplingParams | None = None,
) -> None:
    """Compile every prefill program a trace can reach before timing: for
    each S bucket the lens map to, drive one admission group at every
    power-of-two batch size up to ``max_prefill_batch`` (plus the decode
    program via drain). ``sampling`` warms the same buckets' *sampled*
    program variants instead of the plain ones. Resets the engine's
    stats afterwards."""
    vocab = engine.cfg.vocab_size
    rng = np.random.default_rng(4321)
    nvals, n = {1}, 1
    while 2 * n <= engine.ecfg.max_prefill_batch:
        n *= 2
        nvals.add(n)
    for s in sorted({engine._bucket(ln) for ln in lens}):
        # -1: a full-slot prompt would capacity-finish straight after
        # prefill, and the warm drain would never touch the decode path
        plen = min(s, engine.ecfg.max_len) - 1
        for n in sorted(nvals):
            for _ in range(n):
                engine.submit(
                    rng.integers(0, vocab, plen).astype(np.int32), 2,
                    sampling=sampling,
                )
            engine.drain()
    engine.reset_stats()


def _measure_uniform(
    engine: Engine,
    prompts: np.ndarray,
    gen: int,
    sampling: SamplingParams | None = None,
    repeats: int = 3,
) -> dict:
    """Warm the jits, then serve the uniform wave ``repeats`` times and
    keep the best run by decode tok/s (every program is warm, so repeats
    are i.i.d. — best-of shields the scenario from load noise).
    ``sampling``: per-request params (request b gets seed+b); None keeps
    the greedy default."""
    _warm_buckets(engine, [prompts.shape[1]], sampling)
    best: dict | None = None
    for _ in range(repeats):
        engine.reset_stats()
        t0 = time.perf_counter()
        for b in range(prompts.shape[0]):
            engine.submit(
                prompts[b],
                gen,
                sampling=None
                if sampling is None
                else dataclasses.replace(sampling, seed=sampling.seed + b),
            )
        finished = engine.drain()
        wall_s = time.perf_counter() - t0
        out = engine.stats_summary()
        tokens = sum(len(f.tokens) for f in finished)
        out["wall_tok_s"] = round(tokens / wall_s, 2)
        out["wall_s"] = round(wall_s, 4)
        if best is None or out["decode_tok_s"] > best["decode_tok_s"]:
            best = out
    return best


def _measure_guarded(
    engine: Engine, prompts: np.ndarray, gen: int, *, enforce: bool
) -> dict:
    """One uniform wave with the steady-state decode loop inside a
    DispatchGuard: proves (``enforce=True``, raising — the tier-1 /
    --guards mode) or records (``enforce=False``, counting) that decode
    performs zero recompiles and zero implicit device→host transfers
    per step after warmup, with exactly one explicit batched fetch (the
    next-token row) per step."""
    from repro.analysis.guards import DispatchGuard

    for b in range(prompts.shape[0]):
        engine.submit(prompts[b], gen)
    engine.step()  # warmup step: admission prefill + first decode
    # Guard the steady-state middle only: requests finishing free their
    # slots, and the resulting re-bucketing is warmup work by contract,
    # not a per-step cost.
    steps = max(gen - 2, 1)
    guard = DispatchGuard(
        max_compiles=0 if enforce else None,
        raise_on_sync=enforce,
    )
    with guard:
        for _ in range(steps):
            engine.step()
    engine.drain(max_steps=64 * max(gen, 1))
    return {
        "steps": steps,
        "compiles": guard.compiles,
        "implicit_d2h": guard.implicit_syncs,
        "explicit_syncs": guard.explicit_syncs,
        "enforced": enforce,
        "clean": guard.compiles == 0 and guard.implicit_syncs == 0,
    }


def _measure_trace(
    engine: Engine,
    prompts: list[np.ndarray],
    gens: list[int],
    repeats: int = 3,
) -> dict:
    """Submit a whole trace, drain, fold wall-clock into the stats.
    Best-of-``repeats`` (every program is pre-warmed, so repeats are
    i.i.d.): shields the admission-path comparison from load noise."""
    best: dict | None = None
    for _ in range(repeats):
        engine.reset_stats()
        t0 = time.perf_counter()
        for p, g in zip(prompts, gens):
            engine.submit(p, g)
        finished = engine.drain()
        wall_s = time.perf_counter() - t0
        out = engine.stats_summary()
        out["wall_tok_s"] = round(
            sum(len(f.tokens) for f in finished) / wall_s, 2
        )
        out["wall_s"] = round(wall_s, 4)
        out["requests"] = len(prompts)
        if best is None or out["wall_tok_s"] > best["wall_tok_s"]:
            best = out
    return best


def _measure_prefix_cache(
    cfg, mesh, params, batch: int, smoke: bool, repeats: int
) -> dict:
    """Shared-system-prompt scenario: prefix cache on vs off.

    Both engines serve the identical trace with identical geometry; the
    warmup pass compiles every program *and* (cache-on) populates the
    radix tree, so the measured repeats see steady-state hit rates —
    exactly what a production system serving one system prompt to a
    stream of users looks like. Best-of-``repeats`` by admission time
    (prefill seconds)."""
    page = cfg.attn_block
    sys_pages = 3
    max_len = (sys_pages + 2) * page
    n_req = (2 if smoke else 4) * batch
    rng = np.random.default_rng(5)
    sys_prompt = rng.integers(
        0, cfg.vocab_size, sys_pages * page, dtype=np.int32
    )
    prompts = [
        np.concatenate(
            [
                sys_prompt,
                rng.integers(
                    0,
                    cfg.vocab_size,
                    int(rng.integers(4, page // 2)),
                    dtype=np.int32,
                ),
            ]
        )
        for _ in range(n_req)
    ]
    gens = [int(rng.integers(2, 5)) for _ in range(n_req)]

    results, streams = {}, {}
    for mode, on in (("on", True), ("off", False)):
        eng = Engine(
            cfg,
            mesh,
            engine_cfg=EngineConfig(
                max_slots=batch, max_len=max_len, prefix_cache=on
            ),
            params=params,
        )
        for p, g in zip(prompts, gens):  # warm every program (+ the tree)
            eng.submit(p, g)
        eng.drain()
        best = None
        for _ in range(repeats):
            eng.reset_stats()
            t0 = time.perf_counter()
            for p, g in zip(prompts, gens):
                eng.submit(p, g)
            fins = eng.drain()
            wall = time.perf_counter() - t0
            out = eng.stats_summary()
            out["wall_s"] = round(wall, 4)
            out["wall_tok_s"] = round(
                sum(len(f.tokens) for f in fins) / wall, 2
            )
            if best is None or out["prefill_s"] < best["prefill_s"]:
                best = out
                streams[mode] = {
                    f.uid - fins[0].uid: f.tokens.tolist()
                    for f in sorted(fins, key=lambda f: f.uid)
                }
        results[mode] = best
    # the cache must be a pure optimization: identical token streams
    assert streams["on"] == streams["off"], "prefix cache changed tokens"
    keys = (
        "prefill_s",
        "prefill_tokens",
        "wall_s",
        "wall_tok_s",
        "p95_token_latency_ms",
    )
    row = {m: {k: results[m][k] for k in keys} for m in ("on", "off")}
    pc = results["on"]["prefix_cache"]
    row["on"]["hit_rate"] = pc["hit_rate"]
    row["on"]["hit_tokens"] = pc["hit_tokens"]
    row["on"]["evicted_pages"] = pc["evicted_pages"]
    row["admission_speedup"] = round(
        results["off"]["prefill_s"]
        / max(results["on"]["prefill_s"], 1e-9),
        2,
    )
    row["wall_speedup"] = round(
        results["on"]["wall_tok_s"]
        / max(results["off"]["wall_tok_s"], 1e-9),
        2,
    )
    row["requests"] = n_req
    row["sys_prompt_tokens"] = sys_pages * page
    return row


def _goodput_pair(
    cfg,
    mesh,
    params,
    slots: int,
    max_len: int,
    items: list[workloads.WorkItem],
    *,
    strict: bool = False,
) -> dict:
    """Serve one seeded trace with preemption on vs off (identical
    engines otherwise) and fold each run into a goodput row.

    Calibration first: the "on" engine replays the trace once with
    deadlines unarmed — warming every program including the swap path —
    and its measured seconds-per-step converts the trace's
    step-denominated deadlines into wall-clock ``ScheduleParams``, the
    *same* values for both modes. Token streams are asserted
    bit-identical across modes (preemption must be a pure scheduling
    change); ``strict`` additionally asserts the trace preempted at
    least once and met strictly more deadlines with preemption on."""
    warm_lens = sorted({w.prompt.size for w in items})
    step_s = None
    out: dict = {}
    streams: dict[str, list] = {}
    for mode, on in (("on", True), ("off", False)):
        eng = Engine(
            cfg,
            mesh,
            engine_cfg=EngineConfig(
                max_slots=slots, max_len=max_len, preemption=on
            ),
            params=params,
        )
        _warm_buckets(eng, warm_lens)
        # first unarmed replay warms what _warm_buckets cannot reach
        # (the swap gather/scatter and presence-reseed programs fire on
        # the first preemption); the second measures steady-state
        # seconds-per-step for the deadline conversion
        workloads.replay(eng, items, step_s=None)
        if step_s is None:
            _, wall, steps = workloads.replay(eng, items, step_s=None)
            step_s = wall / max(steps, 1)
        eng.reset_stats()
        fins, wall, steps = workloads.replay(eng, items, step_s=step_s)
        row = workloads.goodput(fins, eng.stats_summary())
        row["wall_s"] = round(wall, 4)
        row["steps"] = steps
        out[mode] = row
        streams[mode] = [
            f.tokens.tolist() for f in sorted(fins, key=lambda f: f.uid)
        ]
    # preemption may only change WHEN things run, never WHAT they emit
    assert streams["on"] == streams["off"], (
        "preemption changed token streams"
    )
    out["attainment_gain"] = round(
        out["on"]["slo_attainment"] - out["off"]["slo_attainment"], 4
    )
    if strict:
        assert out["on"]["preemptions"] > 0, "trace never preempted"
        assert (
            out["on"]["slo_attainment"] > out["off"]["slo_attainment"]
        ), (
            f"preemption did not raise SLO attainment: "
            f"on={out['on']['slo_attainment']} "
            f"off={out['off']['slo_attainment']}"
        )
    return out


def _measure_observability(
    cfg,
    mesh,
    params,
    batch: int,
    prompt_len: int,
    gen: int,
    repeats: int,
    trace_out: str | None = None,
) -> dict:
    """Tracer overhead: the uniform workload on two otherwise-identical
    warm engines, tracing on vs off, measured back-to-back per repeat.
    The committed number is the median *paired* decode-tok/s ratio
    (traced / off), same protocol as prefill-heavy: load noise lands on
    both legs of a pair.  The tracer budget is ~2% steady-state; the
    hard floor here is loose (ratio >= 0.80) because smoke-scale decode
    steps are microseconds and scheduler jitter dominates.  Token
    streams must be bit-identical — tracing is observation only.
    ``trace_out``: export the traced engine's final ring there."""
    max_len = prompt_len + gen + 1
    engines = {}
    for mode, on in (("traced", True), ("off", False)):
        eng = Engine(
            cfg,
            mesh,
            engine_cfg=EngineConfig(
                max_slots=batch, max_len=max_len, trace=on
            ),
            params=params,
        )
        _warm_buckets(eng, [prompt_len])
        engines[mode] = eng
    rng = np.random.default_rng(7)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(batch, prompt_len), dtype=np.int32
    )
    pairs, streams = [], {}
    for _ in range(repeats):
        pair = {}
        for mode, eng in engines.items():
            eng.reset_stats()
            t0 = time.perf_counter()
            for b in range(batch):
                eng.submit(prompts[b], gen)
            fins = eng.drain()
            wall = time.perf_counter() - t0
            out = eng.stats_summary()
            out["wall_s"] = round(wall, 4)
            pair[mode] = out
            streams[mode] = [
                f.tokens.tolist() for f in sorted(fins, key=lambda f: f.uid)
            ]
        assert streams["traced"] == streams["off"], (
            "tracing changed token streams"
        )
        pairs.append(pair)
    ratios = [
        p["traced"]["decode_tok_s"] / max(p["off"]["decode_tok_s"], 1e-9)
        for p in pairs
    ]
    med_i = int(np.argsort(ratios)[len(ratios) // 2])
    ratio = round(sorted(ratios)[len(ratios) // 2], 4)
    assert ratio >= 0.80, (
        f"tracer overhead blew the budget: traced/off decode ratio "
        f"{ratio} (floor 0.80)"
    )
    keys = ("decode_tok_s", "p95_token_latency_ms", "wall_s")
    row = {
        m: {k: pairs[med_i][m][k] for k in keys} for m in ("traced", "off")
    }
    row["traced_vs_off"] = ratio
    row["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)
    row["events_recorded"] = int(engines["traced"].tracer.n_recorded)
    if trace_out:
        row["trace_events_written"] = engines["traced"].export_perfetto(
            trace_out
        )
    return row


def _measure_observability_live(
    cfg,
    mesh,
    params,
    batch: int,
    prompt_len: int,
    gen: int,
    repeats: int,
    smoke: bool,
    listen: str | None = None,
) -> dict:
    """Live-plane overhead + mid-run scrape round-trip.

    The monitored engine carries the whole plane — rolling windows, the
    burn-rate monitor (shed disabled) and per-step memory gauges — vs a
    bare engine, measured as paired repeats (median monitored/off
    decode-tok/s ratio, same protocol as the tracer scenario). The
    committed (non-smoke) floor is 0.95: one window tick + a burn
    evaluation per step must stay inside 5%; the smoke floor is looser
    because microsecond steps amplify scheduler jitter. Token streams
    must be bit-identical — monitoring alone never changes what is
    served.

    With ``listen`` set, one extra monitored run scrapes ``/metrics``
    and ``/healthz`` *mid-decode* (round-tripping ``obs/prom.parse``)
    and asserts the end-of-run ``/vars`` windowed percentiles agree
    with ``stats_summary()`` — the window covers the whole run, so the
    raw-sample percentiles must match to exposition rounding."""
    from repro.obs import SloConfig

    max_len = prompt_len + gen + 1
    monitored_cfg = EngineConfig(
        max_slots=batch,
        max_len=max_len,
        monitor=True,
        slo=SloConfig(
            target=0.99, fast_window_s=5.0, slow_window_s=30.0
        ),
    )
    engines = {}
    for mode, ecfg in (
        ("monitored", monitored_cfg),
        ("off", EngineConfig(max_slots=batch, max_len=max_len)),
    ):
        eng = Engine(cfg, mesh, engine_cfg=ecfg, params=params)
        _warm_buckets(eng, [prompt_len])
        engines[mode] = eng
    rng = np.random.default_rng(17)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(batch, prompt_len), dtype=np.int32
    )
    pairs, streams = [], {}
    for _ in range(repeats):
        pair = {}
        for mode, eng in engines.items():
            eng.reset_stats()
            t0 = time.perf_counter()
            for b in range(batch):
                eng.submit(prompts[b], gen)
            fins = eng.drain()
            wall = time.perf_counter() - t0
            out = eng.stats_summary()
            out["wall_s"] = round(wall, 4)
            pair[mode] = out
            streams[mode] = [
                f.tokens.tolist()
                for f in sorted(fins, key=lambda f: f.uid)
            ]
        assert streams["monitored"] == streams["off"], (
            "live monitoring changed token streams"
        )
        pairs.append(pair)
    ratios = [
        p["monitored"]["decode_tok_s"]
        / max(p["off"]["decode_tok_s"], 1e-9)
        for p in pairs
    ]
    med_i = int(np.argsort(ratios)[len(ratios) // 2])
    ratio = round(sorted(ratios)[len(ratios) // 2], 4)
    floor = 0.80 if smoke else 0.95
    assert ratio >= floor, (
        f"live monitoring blew the budget: monitored/off decode ratio "
        f"{ratio} (floor {floor})"
    )
    keys = ("decode_tok_s", "p95_token_latency_ms", "wall_s")
    row = {
        m: {k: pairs[med_i][m][k] for k in keys}
        for m in ("monitored", "off")
    }
    row["monitored_vs_off"] = ratio
    row["overhead_pct"] = round((1.0 - ratio) * 100.0, 2)

    if listen:
        import json as _json
        import urllib.request

        from repro.obs.http import attach
        from repro.obs.prom import parse as prom_parse

        def _get(url: str) -> str:
            with urllib.request.urlopen(url, timeout=10.0) as r:
                assert r.status == 200, f"{url} -> {r.status}"
                return r.read().decode()

        eng = engines["monitored"]
        srv = attach(eng, listen)
        try:
            eng.reset_stats()
            for b in range(batch):
                eng.submit(prompts[b], gen)
            scraped = None
            step = 0
            while not eng.scheduler.idle or eng._rejected:
                fins = eng.step()
                step += 1
                if step == max(gen // 2, 1):  # scrape mid-decode
                    flat = prom_parse(_get(srv.url + "/metrics"))
                    assert (
                        flat["repro_serve_decode_steps_total"] > 0
                    ), "mid-run exposition missing decode steps"
                    assert _get(srv.url + "/healthz") == "ok\n"
                    scraped = len(flat)
            assert scraped is not None, "run too short to scrape"
            live = _json.loads(_get(srv.url + "/vars"))
            s = eng.stats_summary()
            # the window spans the whole (post-reset) run: /vars raw-
            # sample percentiles must agree with the final summary
            for vk, sk in (
                ("p50_ms", "p50_token_latency_ms"),
                ("p95_ms", "p95_token_latency_ms"),
            ):
                got = live["token_latency_ms"][vk]
                want = s[sk]
                assert abs(got - want) <= max(0.02, 0.01 * want), (
                    f"/vars {vk}={got} disagrees with "
                    f"stats_summary {sk}={want}"
                )
            slo = _json.loads(_get(srv.url + "/slo"))
            assert slo["enabled"] and slo["state"] == "OK"
            row["live_scrape"] = {
                "listen": srv.url,
                "midrun_metric_samples": scraped,
                "vars_token_p50_ms": live["token_latency_ms"]["p50_ms"],
                "summary_token_p50_ms": s["p50_token_latency_ms"],
                "pool_pages": live["memory"]["pool_pages"],
            }
        finally:
            srv.stop()
    return row


def _measure_slo_shed(cfg, mesh, params, slots: int) -> dict:
    """Burn-rate load shed under overload: shed on vs off.

    Preemption is OFF, so the only defense is the queue. A wave of
    long-decode low-priority requests pins every slot (and keeps a deep
    backlog to re-pin any slot that frees), while short deadline'd
    high-priority requests arrive on a steady clock. Without shedding,
    each freed slot is immediately re-pinned by backlog, so the
    interactive tier keeps queueing behind ~full decodes and misses.
    With ``SloConfig(shed=True)`` the first misses drive the monitor
    CRITICAL, the queued background is dropped as structured
    ``REJECT_SHED`` results, and later arrivals land on free slots.
    The headline assert: high-priority SLO attainment strictly higher
    with shedding on, and every shed surfaced as a structured
    rejection (never a silent drop)."""
    from repro.obs import SloConfig
    from repro.serving.request import REJECT_SHED

    page = cfg.attn_block
    max_len = 3 * page
    bg_gen = 2 * page - 1  # fills a slot end-to-end, no capacity finish
    n_bg = 4 * slots
    hi_gap, hi_dl = 6, 10
    # misses only surface when a late request *finishes* (first bg wave
    # boundary), so the interactive stream must outlive the background
    # horizon for the post-CRITICAL shed to protect later arrivals
    n_hi = ((n_bg // slots) * bg_gen) // hi_gap
    rng = np.random.default_rng(23)
    items = [
        workloads.WorkItem(
            arrival_step=0,
            prompt=rng.integers(1, cfg.vocab_size, page).astype(np.int32),
            max_new_tokens=bg_gen,
            priority=0,
        )
        for _ in range(n_bg)
    ]
    items += [
        workloads.WorkItem(
            arrival_step=4 + hi_gap * k,
            prompt=rng.integers(
                1, cfg.vocab_size, int(rng.integers(6, page // 2))
            ).astype(np.int32),
            max_new_tokens=3,
            priority=1,
            deadline_steps=hi_dl,
        )
        for k in range(n_hi)
    ]
    lens = sorted({w.prompt.size for w in items})

    # calibrate seconds-per-step on a bare engine (the monitor changes
    # no compiled program), then arm both modes with the same deadlines
    cal = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(
            max_slots=slots, max_len=max_len, preemption=False
        ),
        params=params,
    )
    _warm_buckets(cal, lens)
    workloads.replay(cal, items, step_s=None)
    _, wall, steps = workloads.replay(cal, items, step_s=None)
    step_s = wall / max(steps, 1)
    # burn windows sized in measured steps: misses must land in both
    # windows the tick they are recorded, and age out ~a bg-gen later
    fast_s = max(8 * step_s, 5e-3)

    def _hi_attainment(fins) -> tuple[int, int]:
        hi = [
            f
            for f in fins
            if f.schedule.priority == 1
            and f.schedule.deadline_s is not None
            and f.reject_reason != REJECT_SHED
        ]
        return sum(1 for f in hi if f.slo_met), len(hi)

    out: dict = {}
    for mode, shed in (("off", False), ("on", True)):
        eng = Engine(
            cfg,
            mesh,
            engine_cfg=EngineConfig(
                max_slots=slots,
                max_len=max_len,
                preemption=False,
                monitor=True,
                slo=SloConfig(
                    target=0.9,
                    fast_window_s=fast_s,
                    slow_window_s=3 * fast_s,
                    warn_burn=2.0,
                    critical_burn=6.0,
                    shed=shed,
                    shed_max_per_tick=2 * slots,
                ),
            ),
            params=params,
        )
        _warm_buckets(eng, lens)
        workloads.replay(eng, items, step_s=None)  # warm, unarmed
        eng.reset_stats()
        fins, wall, steps = workloads.replay(eng, items, step_s=step_s)
        stats = eng.stats_summary()
        sheds = [f for f in fins if f.reject_reason == REJECT_SHED]
        met, n_dl = _hi_attainment(fins)
        out[mode] = {
            "requests": len(fins),
            "hi_with_deadline": n_dl,
            "hi_slo_met": met,
            "hi_attainment": round(met / n_dl, 4) if n_dl else 1.0,
            "sheds": len(sheds),
            "rejected_total": stats["rejected"]["total"],
            "slo_transitions": dict(eng._slo_mon.transitions),
            "wall_s": round(wall, 4),
            "steps": steps,
        }
        if shed:
            assert sheds, "overload under CRITICAL never shed"
            assert all(
                f.finish_reason == "rejected"
                and f.reject_reason == REJECT_SHED
                for f in sheds
            ), "sheds must surface as structured rejections"
            assert out["on"]["slo_transitions"].get("CRITICAL", 0) >= 1
        else:
            assert not sheds and stats["rejected"]["total"] == 0, (
                "shedding disabled must never reject"
            )
    out["hi_attainment_gain"] = round(
        out["on"]["hi_attainment"] - out["off"]["hi_attainment"], 4
    )
    assert out["on"]["hi_attainment"] > out["off"]["hi_attainment"], (
        f"shedding did not raise high-priority attainment: "
        f"on={out['on']['hi_attainment']} "
        f"off={out['off']['hi_attainment']}"
    )
    out["step_s"] = round(step_s, 6)
    return out


def _measure_mesh(smoke: bool) -> dict:
    """Run ``benchmarks.serve_mesh`` in a subprocess and parse its JSON.

    Device count is fixed at the first backend initialization, so the
    simulated 8-device CPU platform must be forced *before* jax imports
    — impossible in this process, which already initialized the default
    platform. The child re-checks the env, so forcing it here keeps the
    bench deterministic no matter which platform the parent grabbed."""
    root = Path(__file__).resolve().parents[1]
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count=8".strip()
        )
    env["PYTHONPATH"] = os.pathsep.join(
        [str(root / "src")]
        + ([env["PYTHONPATH"]] if env.get("PYTHONPATH") else [])
    )
    cmd = [sys.executable, "-m", "benchmarks.serve_mesh"]
    if smoke:
        cmd.append("--smoke")
    proc = subprocess.run(
        cmd, cwd=root, env=env, capture_output=True, text=True
    )
    if proc.returncode != 0:
        raise RuntimeError(
            f"serve_mesh failed (rc={proc.returncode}):\n"
            + proc.stderr[-2000:]
        )
    return json.loads(proc.stdout.strip().splitlines()[-1])


def _measure_goodput(cfg, mesh, params, batch: int, smoke: bool) -> dict:
    """The three scheduling scenarios over seeded workload traces."""
    page = cfg.attn_block
    slots = batch

    # ---- burst: deadline'd high-priority burst on a full pool. The
    # step counts give >2x margin on both sides of the deadline: with
    # preemption the burst's e2e is ~burst_gen + a few steps; without,
    # it queues behind ~background_gen steps.
    bg_gen, burst_at, dl = (40, 8, 22) if smoke else (96, 12, 48)
    burst = workloads.poisson_burst(
        np.random.default_rng(11),
        vocab=cfg.vocab_size,
        page=page,
        n_background=slots,
        n_burst=slots,
        burst_step=burst_at,
        background_gen=bg_gen,
        burst_gen=6,
        deadline_steps=dl,
    )
    rows = {
        "burst": _goodput_pair(
            cfg, mesh, params, slots, 3 * page, burst, strict=True
        )
    }

    # ---- long tail: open-loop Poisson arrivals, interactive tier
    # (priority 1, deadline'd shorts) over a heavy batch tail
    tail = workloads.long_tail(
        np.random.default_rng(12),
        vocab=cfg.vocab_size,
        page=page,
        n=12 if smoke else 32,
        mean_gap_steps=3.0 if smoke else 2.0,
        short_gen=(3, 8),
        heavy_gen=bg_gen,
        deadline_steps=30 if smoke else 40,
    )
    rows["long_tail"] = _goodput_pair(
        cfg, mesh, params, slots, 3 * page, tail
    )

    # ---- chat: multi-turn conversations, prefix cache on — turn 2+
    # prompts extend turn 1's history, so admission hits the
    # decode-written pages the engine indexed when turn 1 finished
    n_turns = 2 if smoke else 3
    mk_convs = lambda seed: workloads.chat_turns(
        np.random.default_rng(seed),
        vocab=cfg.vocab_size,
        n_users=slots,
        n_turns=n_turns,
        user_tokens=page,
        # gen page+1: the answer fills the prompt's last page exactly
        # (written = prompt + gen[:-1]), so whole turns become matchable
        gen=page + 1,
    )
    eng = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(
            max_slots=slots,
            max_len=(2 * n_turns + 1) * page,
            prefix_cache=True,
        ),
        params=params,
    )
    # warm with same-shaped different-token conversations: compiles the
    # partial-prefill buckets the measured turns hit, without seeding
    # the tree with the measured tokens
    workloads.replay_chat(eng, mk_convs(998))
    eng.reset_stats()
    by_turn, wall, _ = workloads.replay_chat(eng, mk_convs(13))
    later = [f for t, fs in by_turn.items() if t >= 1 for f in fs]
    hit = sum(f.prefix_hit_tokens for f in later)
    plen = sum(int(f.prompt.size) for f in later)
    ttft = [f.ttft_s for fs in by_turn.values() for f in fs]
    stats = eng.stats_summary()
    rows["chat"] = {
        "turns": n_turns,
        "users": slots,
        "wall_s": round(wall, 4),
        "turn2plus_hit_rate": round(hit / plen, 4) if plen else 0.0,
        "turn2plus_hit_tokens": hit,
        "decode_indexed_pages": stats["prefix_cache"][
            "decode_indexed_pages"
        ],
        "ttft_p50_ms": workloads._pct(ttft, 50),
        "ttft_p95_ms": workloads._pct(ttft, 95),
    }
    assert rows["chat"]["turn2plus_hit_rate"] > 0.25, (
        "chat turns no longer hit decode-indexed pages: "
        f"{rows['chat']}"
    )
    return rows


def run(
    smoke: bool = False,
    guards: bool = False,
    trace_out: str | None = None,
    listen: str | None = None,
) -> None:
    cfg = registry.get_smoke(ARCH, sparse=True)
    batch, prompt_len, gen, repeats = BATCH, PROMPT_LEN, GEN, 3
    if smoke:
        # seconds-scale dry run of every scenario: tiny model, tiny
        # traces, one repeat, JSON into a temp dir (the real
        # BENCH_serve.json trajectory stays untouched)
        import tempfile

        from benchmarks import common

        common.set_json_dir(tempfile.mkdtemp(prefix="bench_serve_smoke_"))
        cfg = cfg.replace(num_layers=2, vocab_size=256)
        batch, prompt_len, gen, repeats = 2, 8, 4, 1
    mesh = make_local_mesh()
    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(batch, prompt_len), dtype=np.int32
    )

    # ---- seed Server baseline (fixed batch, per-token prefill loop)
    server = Server(cfg, mesh)
    server.generate(prompts[:, :prompt_len], 2)  # warm the decode jit
    t0 = time.perf_counter()
    out = server.generate(prompts, gen)
    server_s = time.perf_counter() - t0
    server_tokens = int(out.size)
    server_tok_s = server_tokens / server_s

    # ---- engine, uniform workload (same requests, shared weights)
    max_len = prompt_len + gen + 1
    engine = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(max_slots=batch, max_len=max_len),
        params=server.params,
    )
    uniform = _measure_uniform(engine, prompts, gen, repeats=repeats)

    # ---- decode-by-sampler: identical workload, fully-loaded sampling
    # on the same (already warm) engine — sampling is fused into the
    # jit'd step, so this should cost within ~10% of greedy decode
    keys = ("decode_tok_s", "p95_token_latency_ms", "p50_token_latency_ms")
    sampled = _measure_uniform(
        engine, prompts, gen, sampling=SAMPLED, repeats=repeats
    )
    by_sampler = {
        "greedy": {k: uniform[k] for k in keys},
        SAMPLED.kind: {k: sampled[k] for k in keys},
        "sampled_vs_greedy": round(
            sampled["decode_tok_s"] / max(uniform["decode_tok_s"], 1e-9),
            4,
        ),
    }

    # ---- per-impl decode comparison: jnp gather path vs the Pallas
    # paged kernel (off TPU the interpreted kernel stands in for it, so
    # the json tracks parity-path numbers on every platform)
    base_impl = engine.paged_impl
    other_impl = "interpret" if base_impl == "gather" else "gather"
    by_impl = {base_impl: {k: uniform[k] for k in keys}}
    engine_o = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(max_slots=batch, max_len=max_len),
        params=server.params,
        paged_impl=other_impl,
    )
    other = _measure_uniform(engine_o, prompts, gen, repeats=repeats)
    by_impl[other_impl] = {k: other[k] for k in keys}

    # ---- dispatch-guard scenario: the steady-state decode loop runs
    # inside repro.analysis.guards.DispatchGuard. Counters are always
    # recorded in the payload; under --guards (and in the --smoke tier-1
    # gate) the guard *raises* on any recompile or implicit D2H sync, so
    # a hot-path regression fails the run instead of just drifting a
    # number.
    dispatch_guard = _measure_guarded(
        engine, prompts, gen, enforce=guards or smoke
    )

    # ---- engine, mixed-length trace with mid-flight arrivals
    engine2 = Engine(
        cfg,
        mesh,
        engine_cfg=EngineConfig(max_slots=batch, max_len=2 * max_len),
        params=server.params,
    )
    rng = np.random.default_rng(1)
    n_req = 2 * batch
    lens = [int(rng.integers(8, 2 * prompt_len)) for _ in range(n_req)]
    gens = [int(rng.integers(max(gen // 2, 1), 2 * gen))
            for _ in range(n_req)]
    # warm every (N, S) bucket the trace can hit, not just prompt-32:
    # otherwise other buckets JIT inside the measured region and pollute
    # the latency percentiles
    _warm_buckets(engine2, lens)
    t0 = time.perf_counter()
    for i in range(n_req // 2):
        engine2.submit(
            rng.integers(0, cfg.vocab_size, lens[i]).astype(np.int32),
            gens[i],
        )
    fins = []
    for _ in range(max(gen // 2, 1)):  # let the first wave progress
        fins += engine2.step()
    for i in range(n_req // 2, n_req):  # late arrivals, admitted mid-flight
        engine2.submit(
            rng.integers(0, cfg.vocab_size, lens[i]).astype(np.int32),
            gens[i],
        )
    fins += engine2.drain()
    mixed_s = time.perf_counter() - t0
    mixed = engine2.stats_summary()
    mixed["wall_tok_s"] = round(
        sum(len(f.tokens) for f in fins) / mixed_s, 2
    )
    mixed["requests"] = n_req

    # ---- prefill-heavy: many short ragged prompts, tiny gens — admission
    # dominates. Batched bucketed admission vs per-request baseline on the
    # identical trace (shared weights, same slots/capacity).
    rng = np.random.default_rng(2)
    ph_n = (2 if smoke else 8) * batch
    ph_prompts = [
        rng.integers(
            0, cfg.vocab_size, int(rng.integers(4, 3 * prompt_len))
        ).astype(np.int32)
        for _ in range(ph_n)
    ]
    ph_gens = [int(rng.integers(2, 5)) for _ in range(ph_n)]
    ph_lens = [p.size for p in ph_prompts]
    # Both engines are built and warmed before any timing, then every
    # repeat measures the two modes back-to-back and the committed
    # speedup is the median of the per-repeat *paired* ratios: a patch
    # of machine load lands on both legs of a pair instead of skewing
    # whichever mode's sequential block it happened to hit (the old
    # per-mode best-of blocks drifted run-to-run for exactly that
    # reason).
    ph_engines = {}
    for mode, batch_cap in (("batched", 0), ("per_request", 1)):
        eng = Engine(
            cfg,
            mesh,
            # 2x slots: admission waves are what this scenario measures
            engine_cfg=EngineConfig(
                max_slots=2 * batch,
                max_len=2 * max_len,
                max_prefill_batch=batch_cap,
            ),
            params=server.params,
        )
        _warm_buckets(eng, ph_lens)
        ph_engines[mode] = eng
    ph_pairs = [
        {
            m: _measure_trace(ph_engines[m], ph_prompts, ph_gens, repeats=1)
            for m in ("batched", "per_request")
        }
        for _ in range(repeats)
    ]
    ph_ratios = [
        p["batched"]["wall_tok_s"]
        / max(p["per_request"]["wall_tok_s"], 1e-9)
        for p in ph_pairs
    ]
    ph = ph_pairs[int(np.argsort(ph_ratios)[len(ph_ratios) // 2])]
    ph_speedup = round(sorted(ph_ratios)[len(ph_ratios) // 2], 2)

    # ---- prefix cache: shared-system-prompt trace, cache on vs off
    prefix = _measure_prefix_cache(
        cfg, mesh, server.params, batch, smoke, repeats
    )

    # ---- observability: tracer on vs off on the uniform workload —
    # proves the span tracer stays inside its overhead budget and (via
    # --trace-out) round-trips a validatable Perfetto timeline
    obs = _measure_observability(
        cfg, mesh, server.params, batch, prompt_len, gen, repeats,
        trace_out=trace_out,
    )

    # ---- observability_live: the full telemetry plane (windows + SLO
    # monitor + memory gauges) vs a bare engine, plus the burn-rate
    # load-shed scenario; --listen adds a mid-run /metrics scrape
    obs_live = _measure_observability_live(
        cfg, mesh, server.params, batch, prompt_len, gen, repeats,
        smoke, listen=listen,
    )
    obs_live["slo_shed"] = _measure_slo_shed(cfg, mesh, server.params, batch)

    # ---- goodput: SLO-aware scheduling scenarios (burst / long-tail /
    # multi-turn chat) over seeded workload traces
    good = _measure_goodput(cfg, mesh, server.params, batch, smoke)

    # ---- mesh: TP decode scaling + DP replica routing on a simulated
    # 8-device host mesh (subprocess — see _measure_mesh). On a TPU host
    # this process holds the chip, and a CPU child's numbers would sit
    # beside chip numbers in one file: the block is left unmeasured.
    dev = jax.devices()[0]
    if dev.platform == "tpu":
        meshrow = {
            "status": "not measured",
            "reason": "simulated-CPU mesh child skipped on a TPU host",
        }
    else:
        meshrow = _measure_mesh(smoke)

    payload = {
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "device_count": len(jax.devices()),
        "config": {
            "arch": ARCH,
            "smoke": True,
            "sparse": True,
            "batch": batch,
            "prompt_len": prompt_len,
            "gen": gen,
            "page": cfg.attn_block,
            "slots": batch,
        },
        "server": {
            "tok_s": round(server_tok_s, 2),
            "total_tokens": server_tokens,
            "wall_s": round(server_s, 4),
        },
        "engine_uniform": uniform,
        "engine_mixed": mixed,
        "engine_prefill_heavy": ph["batched"],
        "prefill_heavy_baseline": ph["per_request"],
        "prefill_heavy_speedup": ph_speedup,
        "decode_by_impl": by_impl,
        "decode_by_sampler": by_sampler,
        "dispatch_guard": dispatch_guard,
        "observability": obs,
        "observability_live": obs_live,
        "prefix_cache": prefix,
        "goodput": good,
        "mesh": meshrow,
        "paged_impl_default": base_impl,
        "speedup_vs_server": round(uniform["tok_s"] / server_tok_s, 2),
    }
    emit_json("BENCH_serve.json", payload)
    emit(
        "serve_engine/uniform",
        1e6 / max(uniform["wall_tok_s"], 1e-9),
        f"tok_s={uniform['tok_s']};server_tok_s={server_tok_s:.2f}"
        f";speedup={payload['speedup_vs_server']}x",
    )
    emit(
        "serve_engine/mixed",
        1e6 * mixed_s / max(mixed["generated_tokens"], 1),
        f"tok_s={mixed['tok_s']};occupancy={mixed['mean_occupancy']}"
        f";p95_ms={mixed['p95_token_latency_ms']}",
    )
    emit(
        "serve_engine/prefill_heavy",
        1e6 / max(ph["batched"]["wall_tok_s"], 1e-9),
        f"wall_tok_s={ph['batched']['wall_tok_s']}"
        f";baseline={ph['per_request']['wall_tok_s']}"
        f";speedup={payload['prefill_heavy_speedup']}x"
        f";req_per_prefill={ph['batched']['mean_prefill_batch']}",
    )
    for impl, row in by_impl.items():
        emit(
            f"serve_engine/decode_{impl}",
            1e6 / max(row["decode_tok_s"], 1e-9),
            f"decode_tok_s={row['decode_tok_s']}"
            f";p95_ms={row['p95_token_latency_ms']}",
        )
    emit(
        "serve_engine/decode_sampled",
        1e6 / max(sampled["decode_tok_s"], 1e-9),
        f"decode_tok_s={sampled['decode_tok_s']}"
        f";greedy_tok_s={uniform['decode_tok_s']}"
        f";sampled_vs_greedy={by_sampler['sampled_vs_greedy']}x",
    )
    emit(
        "serve_engine/dispatch_guard",
        1e6 * dispatch_guard["steps"],
        f"steps={dispatch_guard['steps']}"
        f";compiles={dispatch_guard['compiles']}"
        f";implicit_d2h={dispatch_guard['implicit_d2h']}"
        f";explicit_syncs={dispatch_guard['explicit_syncs']}"
        f";enforced={dispatch_guard['enforced']}",
    )
    emit(
        "serve_engine/observability",
        1e6 / max(obs["traced"]["decode_tok_s"], 1e-9),
        f"traced_tok_s={obs['traced']['decode_tok_s']}"
        f";off_tok_s={obs['off']['decode_tok_s']}"
        f";traced_vs_off={obs['traced_vs_off']}x"
        f";overhead_pct={obs['overhead_pct']}"
        f";events={obs['events_recorded']}",
    )
    emit(
        "serve_engine/observability_live",
        1e6 / max(obs_live["monitored"]["decode_tok_s"], 1e-9),
        f"monitored_tok_s={obs_live['monitored']['decode_tok_s']}"
        f";off_tok_s={obs_live['off']['decode_tok_s']}"
        f";monitored_vs_off={obs_live['monitored_vs_off']}x"
        f";overhead_pct={obs_live['overhead_pct']}",
    )
    shed = obs_live["slo_shed"]
    emit(
        "serve_engine/slo_shed",
        1e6 * (1.0 - shed["on"]["hi_attainment"] + 1e-9),
        f"hi_attainment_on={shed['on']['hi_attainment']}"
        f";hi_attainment_off={shed['off']['hi_attainment']}"
        f";gain={shed['hi_attainment_gain']}"
        f";sheds={shed['on']['sheds']}"
        f";critical_transitions={shed['on']['slo_transitions'].get('CRITICAL', 0)}",
    )
    emit(
        "serve_engine/prefix_cache",
        1e6 * prefix["on"]["prefill_s"],
        f"admission_speedup={prefix['admission_speedup']}x"
        f";hit_rate={prefix['on']['hit_rate']}"
        f";wall_speedup={prefix['wall_speedup']}x",
    )
    for name in ("burst", "long_tail"):
        row = good[name]
        emit(
            f"serve_engine/goodput_{name}",
            1e6 * row["on"]["ttft_p95_ms"],
            f"slo_on={row['on']['slo_attainment']}"
            f";slo_off={row['off']['slo_attainment']}"
            f";preemptions={row['on']['preemptions']}"
            f";swap_out_bytes={row['on']['swap_out_bytes']}",
        )
    emit(
        "serve_engine/goodput_chat",
        1e6 * good["chat"]["ttft_p95_ms"],
        f"turn2plus_hit_rate={good['chat']['turn2plus_hit_rate']}"
        f";decode_indexed_pages={good['chat']['decode_indexed_pages']}",
    )
    top_tp = str(max(int(k) for k in meshrow["by_tp"]))
    emit(
        "serve_engine/mesh",
        1e6 / max(meshrow["by_tp"][top_tp]["decode_tok_s"], 1e-9),
        f"tp{top_tp}_decode_tok_s={meshrow['by_tp'][top_tp]['decode_tok_s']}"
        f";tp1={meshrow['by_tp']['1']['decode_tok_s']}"
        f";streams_equal={meshrow['streams_equal']}"
        f";router_tok_s={meshrow['router']['wall_tok_s']}",
    )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="seconds-scale dry run (tier-1 gate)")
    ap.add_argument("--guards", action="store_true",
                    help="enforce the dispatch guard: raise on any "
                         "recompile or implicit device->host sync in "
                         "the steady-state decode loop (implied by "
                         "--smoke)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write the observability scenario's traced "
                         "engine ring as Perfetto JSON (tier-1 "
                         "round-trips and validates it)")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="serve live telemetry from the monitored "
                         "engine during the observability_live "
                         "scenario and scrape /metrics mid-run "
                         "(port 0 = ephemeral)")
    _args = ap.parse_args()
    run(smoke=_args.smoke, guards=_args.guards, trace_out=_args.trace_out,
        listen=_args.listen)
