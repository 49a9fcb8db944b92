"""Serving launcher: thin CLI over the continuous-batching engine.

CPU-scale demo (smoke configs) and the TPU entry point (full configs via
the production mesh). Requests flow through ``repro.serving.Engine``:
batched bucketed prefill (one jit'd call per same-bucket admission
group) straight into the block-paged KV cache, one jit'd decode step per
token over all slots, admission/eviction per step.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-1.7b --smoke \
      --batch 4 --prompt-len 32 --gen 16

``Server`` below is the pre-engine fixed-batch reference path (prefills
token-by-token through the decode step); it is kept as the numerics
oracle for tests and as the baseline the serving benchmark measures the
engine against.
"""

from __future__ import annotations

import argparse
import dataclasses
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.distributed import sharding
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import (
    make_local_mesh,
    make_production_mesh,
    make_tp_mesh,
)
from repro.models import transformer as T
from repro.serving import (
    Engine,
    EngineConfig,
    SamplingParams,
    ScheduleParams,
)
from repro.serving.router import ReplicaRouter


class Server:
    """Fixed-batch LM server (reference): prefill once, then step the
    decode cache. Superseded by ``repro.serving.Engine`` for serving."""

    def __init__(self, cfg, mesh, *, strategy: str = "fsdp", seed: int = 0):
        self.cfg, self.mesh = cfg, mesh
        st = sharding.Strategy(mesh, strategy)
        self.cfg = cfg = cfg.replace(tp_size=st.tp_size, batch_axes=st.batch)
        with mesh:
            key = jax.random.PRNGKey(seed)
            pshape = jax.eval_shape(lambda k: T.init_model(k, cfg), key)
            psh = sharding.param_shardings(st, pshape)
            self.params = jax.jit(
                lambda k: T.init_model(k, cfg), out_shardings=psh
            )(key)
            self._decode = jax.jit(
                lambda p, c, t, pos: T.decode_step(cfg, p, c, t, pos)
            )
        self.st = st

    def generate(self, prompts: np.ndarray, gen_len: int) -> np.ndarray:
        """prompts: (B, P) int32 -> (B, gen_len) int32."""
        cfg = self.cfg
        b, plen = prompts.shape
        max_len = plen + gen_len + 1
        with self.mesh:
            caches = T.init_cache(cfg, b, max_len)
            # prefill token-by-token through the decode path keeps one code
            # path; a production server would jit T.prefill (we lower it in
            # the dry-run) — here prompt lengths are tiny.
            logits = None
            for i in range(plen):
                logits, caches = self._decode(
                    self.params, caches, jnp.asarray(prompts[:, i]),
                    jnp.asarray(i, jnp.int32),
                )
            out = []
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            for j in range(gen_len):
                out.append(np.asarray(tok))
                logits, caches = self._decode(
                    self.params, caches, tok,
                    jnp.asarray(plen + j, jnp.int32),
                )
                tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return np.stack(out, axis=1)


def _write_obs(served, metrics, args) -> None:
    """Flush observability artifacts after drain: a Perfetto timeline
    (``--trace-out``) and/or a Prometheus snapshot (``--metrics-out``).
    ``served`` is the Engine or ReplicaRouter (both export the same
    ``export_perfetto(path)`` surface)."""
    if args.trace_out:
        n = served.export_perfetto(args.trace_out)
        print(f"wrote {n} trace events to {args.trace_out} "
              "(open at ui.perfetto.dev)")
    if args.metrics_out:
        from repro.obs.prom import write_snapshot

        write_snapshot(args.metrics_out, metrics)
        print(f"wrote Prometheus snapshot to {args.metrics_out}")


def _maybe_listen(served, args):
    """Start the live scrape endpoint (``--listen``) before draining.
    ``served`` is the Engine or ReplicaRouter; returns the running
    ``MetricsServer`` or None."""
    if not args.listen:
        return None
    from repro.obs.http import attach

    server = attach(served, args.listen)
    print(f"live telemetry at {server.url} "
          "(/metrics /healthz /vars /slo)")
    return server


def _shutdown_live(server, engines, args) -> None:
    """Stop the ``--listen`` endpoint and report flight-recorder
    incidents captured during the run."""
    if server is not None:
        server.stop()
    if args.flight_dir:
        n = sum(len(eng._flight.incidents) for eng in engines
                if eng._flight is not None)
        print(f"flight recorder: {n} incident bundle(s) "
              f"under {args.flight_dir}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_NAMES, required=True)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--sparse", action="store_true")
    ap.add_argument("--batch", type=int, default=4,
                    help="number of requests to submit")
    ap.add_argument("--slots", type=int, default=0,
                    help="engine cache slots (default: --batch)")
    ap.add_argument("--max-len", type=int, default=0,
                    help="per-slot KV capacity (default: fits prompt+gen)")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--lookahead", type=int, default=0,
                    help="admission lookahead window K (default: 2*slots)")
    ap.add_argument("--max-prefill-batch", type=int, default=0,
                    help="cap requests per jit'd prefill call (default: "
                         "slots; 1 = per-request admission baseline)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature (0 = greedy, the default)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="keep only the k highest logits (0 = disabled)")
    ap.add_argument("--top-p", type=float, default=1.0,
                    help="nucleus sampling mass (1.0 = disabled)")
    ap.add_argument("--repetition-penalty", type=float, default=1.0,
                    help="penalty on already-seen tokens (1.0 = disabled)")
    ap.add_argument("--seed", type=int, default=0,
                    help="base sampling seed; request b uses seed+b")
    ap.add_argument("--sampler-candidates", type=int, default=64,
                    help="static top-C candidate cap for the fused "
                         "sampler (0 = exact full-vocab; top-k must "
                         "fit under it)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="radix-tree prompt-prefix reuse: admission maps "
                         "cached prefix pages into the new slot and "
                         "prefills only the uncached suffix")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="first N tokens of every synthetic prompt are "
                         "a common system prompt (demos --prefix-cache "
                         "hits; 0 = fully independent prompts)")
    ap.add_argument("--priority", type=int, default=0,
                    help="scheduling priority for the submitted batch "
                         "(higher admits first and may preempt lower)")
    ap.add_argument("--deadline", type=float, default=0.0,
                    help="soft end-to-end deadline in seconds (0 = "
                         "none); reported as SLO attainment")
    ap.add_argument("--max-queue-wait", type=float, default=0.0,
                    help="give up (structured rejection) if not "
                         "admitted within this many seconds (0 = wait "
                         "forever)")
    ap.add_argument("--no-preemption", action="store_true",
                    help="disable priority preemption (host-memory "
                         "page swap)")
    ap.add_argument("--preempt-min-steps", type=int, default=4,
                    help="hysteresis: steps a sequence must run after "
                         "admit/resume before it can be preempted")
    ap.add_argument("--max-skips", type=int, default=64,
                    help="anti-starvation: after this many passes of "
                         "being admitted around, a waiting request "
                         "blocks later admissions until it fits "
                         "(0 disables aging)")
    ap.add_argument("--strategy", choices=["tp", "fsdp"], default="fsdp")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard weights AND the "
                         "paged KV pools over the model axis of a "
                         "(1, tp) device slice (implies --strategy tp)")
    ap.add_argument("--replicas", type=int, default=1,
                    help="data-parallel replicas: N complete engines on "
                         "disjoint (1, tp) slices behind a least-loaded "
                         "router (repro.serving.router)")
    ap.add_argument("--paged-impl", default=None,
                    choices=["gather", "pallas", "interpret"],
                    help="paged decode-attention read (default: pallas on "
                         "TPU, gather elsewhere)")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--legacy-server", action="store_true",
                    help="use the fixed-batch reference Server instead")
    ap.add_argument("--trace", action="store_true",
                    help="enable span tracing (repro.obs) without "
                         "writing a file")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event / Perfetto JSON "
                         "timeline after draining (implies --trace); "
                         "open it at ui.perfetto.dev")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a Prometheus text-exposition snapshot "
                         "of the serving metrics after draining")
    ap.add_argument("--listen", default=None, metavar="HOST:PORT",
                    help="serve live /metrics /healthz /vars /slo over "
                         "HTTP while running (port 0 = ephemeral; "
                         "implies --monitor 30)")
    ap.add_argument("--monitor", type=float, default=0.0, metavar="SECS",
                    help="rolling live-telemetry window in seconds "
                         "(0 = off; feeds /vars and the SLO monitor)")
    ap.add_argument("--slo-target", type=float, default=0.0,
                    help="SLO attainment objective, e.g. 0.99 (0 = "
                         "burn-rate monitor off)")
    ap.add_argument("--slo-fast-window", type=float, default=60.0,
                    help="fast burn-rate window in seconds")
    ap.add_argument("--slo-slow-window", type=float, default=300.0,
                    help="slow burn-rate window in seconds")
    ap.add_argument("--slo-shed", action="store_true",
                    help="shed lowest-priority queued requests while "
                         "the burn-rate state is CRITICAL (structured "
                         "rejections; off = monitor only)")
    ap.add_argument("--flight-dir", default=None, metavar="DIR",
                    help="arm the flight recorder: write incident "
                         "bundles (trace + metrics + config) under DIR "
                         "on step-time spikes, post-warmup compiles and "
                         "SLO CRITICAL transitions")
    args = ap.parse_args()
    use_compile_cache()

    cfg = (
        registry.get_smoke(args.arch, sparse=args.sparse)
        if args.smoke
        else registry.get(args.arch, sparse=args.sparse)
    )
    if not cfg.embed_inputs:
        raise SystemExit(
            f"{args.arch} has a stub modality frontend; serve the backbone "
            "via the dry-run (decode_32k) instead"
        )
    if args.tp > 1 and args.strategy != "tp":
        raise SystemExit(
            "--tp > 1 shards over the model axis: pass --strategy tp"
        )
    if args.production_mesh:
        mesh = make_production_mesh()
    elif args.tp > 1:
        mesh = make_tp_mesh(args.tp)
    else:
        mesh = make_local_mesh()
    rng = np.random.default_rng(0)
    prompts = rng.integers(
        0, cfg.vocab_size, size=(args.batch, args.prompt_len), dtype=np.int32
    )
    if args.shared_prefix:
        n = min(args.shared_prefix, args.prompt_len)
        prompts[:, :n] = prompts[0, :n]  # one system prompt for everyone

    # the paged cache covers attention families; SSM/hybrid state is
    # slot-indexed, not paged — serve those through the reference path
    has_ssm = any(g.kind == "ssm" for g in cfg.layer_groups())
    if has_ssm and not args.legacy_server:
        print(f"{args.arch} has SSM layers: using the fixed-batch Server "
              "(paged engine covers attention families)")
    sp0 = SamplingParams(
        temperature=args.temperature,
        top_k=args.top_k,
        top_p=args.top_p,
        repetition_penalty=args.repetition_penalty,
        seed=args.seed,
    )
    if args.legacy_server or has_ssm:
        if not sp0.is_plain:
            raise SystemExit(
                f"sampler '{sp0.kind}' needs the paged engine (in-jit "
                "sampling); the reference Server path is plain-greedy "
                "only"
            )
        server = Server(cfg, mesh, strategy=args.strategy)
        t0 = time.perf_counter()
        out = server.generate(prompts, args.gen)
        dt = time.perf_counter() - t0
        print(f"generated {out.shape} tokens in {dt:.2f}s "
              f"({args.batch * args.gen / dt:.1f} tok/s)")
        print(out[:2])
        return

    max_len = args.max_len or (args.prompt_len + args.gen + 1)
    slo = None
    if args.slo_target:
        from repro.obs import SloConfig

        slo = SloConfig(
            target=args.slo_target,
            fast_window_s=args.slo_fast_window,
            slow_window_s=args.slo_slow_window,
            shed=args.slo_shed,
        )
    # --listen without an explicit window still needs live aggregation
    # behind /vars; the SLO monitor sizes its own window when set
    monitor = args.monitor if args.monitor else bool(args.listen)
    ecfg = EngineConfig(
        max_slots=args.slots or args.batch,
        max_len=max_len,
        lookahead=args.lookahead or None,
        max_prefill_batch=args.max_prefill_batch,
        sampler_candidates=args.sampler_candidates,
        max_skips=args.max_skips,
        prefix_cache=args.prefix_cache,
        preemption=not args.no_preemption,
        preempt_min_steps=args.preempt_min_steps,
        trace=bool(args.trace or args.trace_out),
        monitor=monitor,
        slo=slo,
        flight_dir=args.flight_dir,
    )
    schedule = ScheduleParams(
        priority=args.priority,
        deadline_s=args.deadline or None,
        max_queue_wait_s=args.max_queue_wait or None,
    )
    if args.replicas > 1:
        router = ReplicaRouter(
            cfg,
            replicas=args.replicas,
            tp=args.tp,
            engine_cfg=ecfg,
            strategy=args.strategy,
            paged_impl=args.paged_impl,
        )
        print(
            f"paged decode impl: {router.engines[0].paged_impl}, "
            f"sampler: {sp0.kind}, "
            f"{args.replicas} replicas x tp={args.tp}"
        )
        for b in range(args.batch):
            router.submit(
                prompts[b],
                args.gen,
                sampling=dataclasses.replace(sp0, seed=args.seed + b),
                schedule=schedule,
            )
        server = _maybe_listen(router, args)
        t0 = time.perf_counter()
        finished = router.drain()
        dt = time.perf_counter() - t0
        total = sum(len(f.tokens) for f in finished)
        s = router.stats_summary()
        per = [int(rep["requests_finished"]) for rep in s["per_replica"]]
        print(
            f"served {len(finished)} requests / {total} tokens in "
            f"{dt:.2f}s ({total / dt:.1f} tok/s end-to-end, "
            f"{s['decode_tok_s']:.1f} tok/s decode fleet-wide, "
            f"p50 {s['p50_token_latency_ms']:.1f}ms "
            f"p95 {s['p95_token_latency_ms']:.1f}ms; "
            f"per-replica finished: {per})"
        )
        _write_obs(router, router.merged_metrics(), args)
        _shutdown_live(server, router.engines, args)
        grid = np.stack(
            [f.tokens for f in sorted(finished, key=lambda f: f.uid)[:2]]
        )
        print(grid)
        return

    engine = Engine(
        cfg,
        mesh,
        strategy=args.strategy,
        engine_cfg=ecfg,
        paged_impl=args.paged_impl,
    )
    print(f"paged decode impl: {engine.paged_impl}, sampler: {sp0.kind}")
    for b in range(args.batch):
        # each request gets its own noise stream via a distinct seed
        engine.submit(
            prompts[b],
            args.gen,
            sampling=dataclasses.replace(sp0, seed=args.seed + b),
            schedule=schedule,
        )
    server = _maybe_listen(engine, args)
    t0 = time.perf_counter()
    finished = engine.drain()
    dt = time.perf_counter() - t0
    s = engine.stats_summary()
    total = sum(len(f.tokens) for f in finished)
    print(
        f"served {len(finished)} requests / {total} tokens in {dt:.2f}s "
        f"({total / dt:.1f} tok/s end-to-end, "
        f"{s['decode_tok_s']:.1f} tok/s decode, "
        f"p50 {s['p50_token_latency_ms']:.1f}ms "
        f"p95 {s['p95_token_latency_ms']:.1f}ms, "
        f"occupancy {s['mean_occupancy']:.2f}, "
        f"{s['mean_prefill_batch']:.1f} req/prefill)"
    )
    pre = s["preemption"]
    if pre["preemptions"] or s["rejected"]["total"] or args.deadline:
        print(
            f"scheduling: {pre['preemptions']} preemptions "
            f"({pre.get('out_bytes', 0)} bytes swapped out, "
            f"{pre.get('in_bytes', 0)} restored), "
            f"{s['rejected']['total']} rejected, "
            f"SLO attainment {s['slo']['attainment']:.0%} "
            f"({s['slo']['met']}/{s['slo']['with_deadline']}), "
            f"ttft p95 {s['ttft_ms']['p95_ms']:.1f}ms"
        )
    if args.prefix_cache:
        pc = s["prefix_cache"]
        print(
            f"prefix cache: {pc['hit_rate']:.0%} hit rate "
            f"({pc['hit_tokens']}/{pc['prompt_tokens']} prompt tokens, "
            f"{pc['hit_pages']} shared pages, "
            f"{pc['inserted_pages']} indexed, {pc['evicted_pages']} "
            f"evicted, {pc['cow_copies']} COW)"
        )
    _write_obs(engine, engine.metrics, args)
    _shutdown_live(server, [engine], args)
    grid = np.stack(
        [f.tokens for f in sorted(finished, key=lambda f: f.uid)[:2]]
    )
    print(grid)


if __name__ == "__main__":
    main()
