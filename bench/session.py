"""A whole run of one cell: set-up, the measured window, the check
against the reference, and the result line.

``run`` takes a ``Cell`` from ``bench.harness.resolve_cell``. Tests call
it on the CPU with ``test=TestOptions(...)`` (small widths, a fixed pool,
the Pallas kernels interpreted, and faults planted in the timed path);
the benchmark's own runs never pass it.
"""

from __future__ import annotations

import dataclasses
import gc
import math
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Callable

from bench import harness
from bench.peaks import peaks_for
from bench.traffic import generator
from bench.weights import LayerWeights, make_params

__all__ = ["TestOptions", "use_compile_cache", "run"]

# traced slice: the last TRACE_SECONDS of the window (at most half of
# it). Stopping the profiler writes the trace and holds the host for
# seconds, so it stops only once the window has closed.
TRACE_SECONDS = 3.0


@dataclasses.dataclass
class TestOptions:
    widths: dict  # configuration-file keys replaced by small ones
    program: dict  # ModelConfig fields for the same (e.g. dtype, blocks)
    n_pages: int
    paged_impl: str = "interpret"
    # faults planted in the timed path: called with the engine (or the
    # router) after set-up, before the window opens
    fault: Callable | None = None


def use_compile_cache(root: Path) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout, or where ``JAX_COMPILATION_CACHE_DIR`` says."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(root / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path


class _Tracer:
    """Profiles the end of the window, from inside the loop: starts
    ``TRACE_SECONDS`` before the close; ``finish`` stops it after."""

    def __init__(self, t_open: float, seconds: float, keep: str | None = None):
        self.start_at = t_open + seconds - min(TRACE_SECONDS, 0.5 * seconds)
        self.keep = keep
        self.dir = keep or tempfile.mkdtemp(prefix="bench-trace-")
        self.state = "before"
        self.begin = self.t0 = self.t1 = None

    def tick(self, now: float) -> None:
        import jax

        if self.state == "before" and now >= self.start_at:
            # starting the profiler holds the loop for seconds: [begin, t0]
            self.begin = now
            jax.profiler.start_trace(self.dir)
            self.t0, self.state = time.perf_counter(), "on"

    def finish(self) -> None:
        import jax

        if self.state == "on":
            self.t1, self.state = time.perf_counter(), "done"
            jax.profiler.stop_trace()


def _readers(cell: harness.Cell, root: Path = harness.ROOT):
    return {m["name"]: harness.metric_reader(m["name"], root) for m in cell.per_layer}


def run(cell: harness.Cell, seed: int, seconds: float, trace: bool, t_start: float,
        test: TestOptions | None = None, control: bool = False,
        rate: float | None = None, trace_dir: str | None = None) -> tuple[dict, dict]:
    """One run. Returns the result line's object and extras for tools
    and tests. Two options serve the tools and never the benchmark's
    own runs: ``control`` puts the fp8 control in the program's place in
    the comparison that decides ``correct``: at each position of the
    same sample, the token the control puts first is judged in place of
    the served one (the limit's upper reading; the program's own widest
    gap goes to the extras), ``rate`` offers a poisson
    mix at another rate (the knee sweep), ``trace_dir`` keeps the trace
    there."""
    import jax

    from repro.launch.mesh import make_tp_mesh
    from repro.models import transformer as T

    conf = {**cell.config, **(test.widths if test else {})}
    arch = cell.arch
    cfg = arch.program_config(conf, smoke=test.program if test else None)
    vocab = conf["vocab_size"]
    mix = cell.mix
    devs = jax.devices()[: cell.chips]
    if cell.replicas > 1:
        devs = harness.router_devices(devs)
    dev = devs[0]
    peaks = peaks_for(dev.device_kind) if not test else None
    # one engine on all the cell's chips, or one per slice of them
    # behind a router; the pool is sized on the first slice
    tp = cell.chips // cell.replicas
    mesh = make_tp_mesh(tp, devs[:tp])
    paged_impl = test.paged_impl if test else None

    # ---- set-up: weights, pool, engine, warm-up ----------------------
    marks = [("start", t_start)]

    def mark(name):
        marks.append((name, time.perf_counter()))

    param_shapes = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.PRNGKey(0))
    if cell.replicas == 1:
        params = make_params(param_shapes, seed, vocab)
        jax.block_until_ready(params)
        mark("weights")
    if test:
        pool = {"n_pages": test.n_pages}
    else:
        pool = harness.size_pool(cfg, mesh, param_shapes, mix, dev, arch)
    mark("pool")
    if cell.replicas == 1:
        server = harness.build_engine(cfg, mesh, params, mix, pool["n_pages"], paged_impl=paged_impl)
        del params
        mark("engine")
    else:
        server = harness.build_router(cfg, devs, cell.replicas, mix, pool["n_pages"], paged_impl=paged_impl)
        mark("router")
        harness.load_weights(server, param_shapes, seed, vocab)
        mark("weights")
    fleet = harness.Fleet(server)
    taken = {e.paged_impl for e in fleet.engines}
    if not test and taken != {"pallas"}:
        raise RuntimeError(f"paged decode took {sorted(taken)}, not the Pallas kernel")
    harness.warm_fleet(fleet, mix)
    mark("warm")
    sched = generator.schedule(mix, seed, seconds, vocab, rate=rate)
    started = harness.admit_in_setup(fleet, sched)
    if started:
        mark("first wave")
    server.reset_stats()  # the counters read are the window's
    if test and test.fault:
        test.fault(server)
    setup_s = time.perf_counter() - t_start
    phases = ", ".join(f"{b[0]} {b[1] - a[1]:.2f} s" for a, b in zip(marks, marks[1:]))
    note = f"pool {pool['n_pages']} pages"
    if "page_bytes" in pool:
        note += f" of {pool['page_bytes']} B"
    if cell.replicas > 1:
        note += f" in each of {cell.replicas} replicas"
    if started:
        note += f"; {sum(r.count > 0 for r in started)} of {len(started)} admitted in set-up"
    print(f"set-up: {phases}; {note}", file=sys.stderr, flush=True)

    # ---- the window ----------------------------------------------------
    t_open = time.perf_counter()
    tracer = _Tracer(t_open, seconds, trace_dir) if trace else None
    win = harness.open_loop(
        fleet, sched, seconds, t_open=t_open, annotate=trace,
        hooks={"tick": tracer.tick} if tracer else None, started=started,
    )
    if tracer:
        tracer.finish()
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0) for d in devs)
    stats = [e.stats for e in fleet.engines]
    first = fleet.engines[0]
    counters = {
        "decode_steps": sum(st.decode_steps for st in stats),
        "decode_tokens": sum(st.generated - st.prefill_requests for st in stats),
        "max_slots": first.ecfg.max_slots,
        # one replica's pool: every replica has the same
        "kv_pool_tokens": (first.kv.n_pages - 1) * first.kv.page,
        # tokens each replica emitted in the window
        "replica_tokens": [st.generated for st in stats],
    }
    del server, fleet, first, stats
    gc.collect()

    # ---- correctness -----------------------------------------------------
    weights = LayerWeights(param_shapes, seed, vocab)
    ref = arch.reference(conf, weights, mix.engine["max_len"])
    ctl = arch.reference(conf, weights, mix.engine["max_len"], precision="fp8") if control else None
    sample = harness.pick_sample(win, seed)
    widest = program_widest = math.inf
    n_tok = 0
    if sample:
        gaps, cgaps = harness.served_gaps(
            ref, [sched.prompts[r.idx] for r in sample], [r.served for r in sample], control=ctl
        )
        n_tok = sum(g.size for g in gaps)
        program_widest = widest = max(float(g.max()) for g in gaps)
        if cgaps is not None:
            # the control in the program's place: its picks are what the
            # comparison judges
            widest = max(float(g.max()) for g in cgaps)
    limit = float(cell.cell["served_logit_gap_limit"])
    correct = bool(sample) and widest <= limit
    compared = {"served_logit_gap_max": {"value": widest, "limit": limit}}

    # ---- metrics -------------------------------------------------------
    work = arch.work(conf, mix.engine["max_len"])
    record = {"window": win, "counters": counters, "work": work, "peaks": peaks, "chips": cell.chips,
              # the seconds in which starting the profiler held the loop
              "profiler_start": (tracer.begin, tracer.t0) if tracer and tracer.t0 else None}
    device = {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": len(jax.devices()),
        "memory_peak_bytes": int(peak),
    }
    result = {"correct": correct}
    failed = sum(1 for r in win.reqs if r.failed)
    attempted = len(win.reqs)
    metrics = {}
    breakdown = None
    if trace:
        from bench.trace import reduce as trace_reduce

        red = trace_reduce.reduce_dir(tracer.dir, tracer.t0, tracer.t1)
        if not tracer.keep:
            shutil.rmtree(tracer.dir, ignore_errors=True)
        # busy seconds averaged over the cell's chips; the per-layer
        # metrics read the busiest chip, and a metric read from the
        # trace reads it against that chip's own work
        busy = [red.busy_by_device.get(d.id, 0.0) for d in devs] if red.busy_by_device else [red.busy_s]
        device["busy_s"] = sum(busy) / len(busy)
        device["window_s"] = red.window_s
        breakdown = {"device_ops": red.top_ops(10), "idle_gaps": red.idle_gaps(10)}
        replica_of = {d.id: i // tp for i, d in enumerate(devs)}
        traced = {**record, "window": win.on(replica_of[red.device])} if red.device in replica_of else record
        if cell.replicas > 1:
            print(f"traced chip: device {red.device} of {sorted(replica_of)}, replica "
                  f"{replica_of.get(red.device)}; busy s by device {red.busy_by_device}", file=sys.stderr)
        readers = _readers(cell)
        for m in cell.per_layer:
            val = readers[m["name"]](traced if m["source"] == "device_trace" else record, red)
            if val is not None:
                metrics[m["name"]] = {"value": val, "unit": m["unit"]}
    else:
        e2e = harness.end_to_end(win, setup_s, [m["name"] for m in cell.end_to_end])
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    result.update(attempted=attempted, failed=failed, metrics=metrics, device=device)
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["_compiles"] = win.compiles
    result["_waiting_at_close"] = win.waiting_at_close
    result["compared"] = compared
    lines = [f"reference sample: {len(sample)} requests, {n_tok} served tokens"]
    lines += [f"compared {k}: {v['value']} (limit {v['limit']})" for k, v in compared.items()]
    extra = {"lines": lines, "record": record, "sample": sample}
    if control:
        extra["program_gap"] = program_widest
    return result, extra
