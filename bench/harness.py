"""One cell of the benchmark: build the engine the way ``launch/serve.py``
does, warm the cell's programs, drive an open loop on the wall clock,
then check the served tokens against the plain reference.

Everything a cell needs is found by name, so a cell, a configuration, an
architecture, an arrival kind or a per-layer metric is added as new
files and entries alone:

- ``BENCHMARK.json`` names the cell's configuration and traffic;
  ``bench/configs/<config>.json``, ``bench/traffic/<traffic>.json`` and
  ``bench/cells/<cell>.json`` hold them;
- the configuration's ``architectures[0]``, in lower case, names its
  module: ``bench/arch/<architecture>.py`` (``Qwen3ForCausalLM`` ->
  ``bench/arch/qwen3forcausallm.py``), which gives the program's
  configuration, the plain reference and the work counts (see
  ``bench.arch``);
- the traffic's ``arrivals.kind`` names ``bench/traffic/arrivals/<kind>.py``
  (see ``bench.traffic.generator``);
- each per-layer metric is read by ``bench/metrics/<metric>.py``.

A cell file may say ``"replicas": R``: the cell then runs
``ReplicaRouter`` over R one-chip replicas of its ``chips`` in place of
one ``Engine`` on all of them, each replica with the pool one chip gets.
"""

from __future__ import annotations

import concurrent.futures
import contextlib
import dataclasses
import json
import math
import re
import time
from pathlib import Path
from types import ModuleType

import numpy as np

from bench import loader
from bench.traffic import generator
from bench.weights import make_params, tree_bytes

ROOT = Path(__file__).resolve().parents[1]
# room on the chip beyond what the hungrier program needs: the runtime's
# own reserve (258 MiB on a v5e), sampling state, page tables
POOL_RESERVE_BYTES = 512 << 20
POOL_TRIES = 4
# reference sample: the longest finished request, then others drawn from
# the seed until this many served tokens are covered
SAMPLE_TOKENS = 384
SAMPLE_MAX_REQUESTS = 8


# ----------------------------------------------------------------------
# Cells
# ----------------------------------------------------------------------


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: generator.Mix
    cell: dict
    end_to_end: list[dict]
    per_layer: list[dict]
    arch: ModuleType  # the configuration's architecture module (bench.arch)
    replicas: int = 1  # engines behind a ReplicaRouter; 1 is one Engine


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def _in_cell(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve_cell(bench: dict, name: str, root: Path = ROOT) -> Cell:
    wl = {w["name"]: w for w in bench["workloads"]}
    if name not in wl:
        raise KeyError(f"unknown workload {name!r}; known: {sorted(wl)}")
    w = wl[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config = json.loads((root / conf["file"]).read_text())
    cell = json.loads((root / "bench" / "cells" / f"{name}.json").read_text())
    chips, replicas = int(w["chips"]), int(cell.get("replicas", 1))
    if replicas < 1 or chips % replicas:
        raise ValueError(f"{name}: {replicas} replicas do not share {chips} chips evenly")
    return Cell(
        name=name,
        chips=chips,
        config=config,
        mix=generator.load_mix(w["traffic"], root / "bench" / "traffic"),
        cell=cell,
        end_to_end=[m for m in bench["end_to_end"] if _in_cell(m, name)],
        per_layer=[m for m in bench["per_layer"] if _in_cell(m, name)],
        arch=arch_module(config, root),
        replicas=replicas,
    )


def arch_module(conf: dict, root: Path = ROOT) -> ModuleType:
    """``bench/arch/<architectures[0] in lower case>.py`` of a
    configuration file."""
    name = conf["architectures"][0].lower()
    if not re.fullmatch(r"[a-z0-9_]+", name):
        raise ValueError(f"architecture {conf['architectures'][0]!r} names no module")
    return loader.load(root / "bench" / "arch" / f"{name}.py")


def metric_reader(name: str, root: Path = ROOT):
    """``read(run, trace)`` of ``bench/metrics/<name>.py``: the metric's
    value, or None where the run offers nothing to read it from."""
    return loader.load(root / "bench" / "metrics" / f"{name}.py").read


# ----------------------------------------------------------------------
# Engine
# ----------------------------------------------------------------------


def prefill_buckets(mix: generator.Mix, page: int) -> list[tuple[int, int]]:
    """Every (N, S) prefill program the mix can make the engine run:
    S the power-of-two page bucket of a prompt length (capped at a
    slot's pages), N a power of two up to ``max_prefill_batch``."""
    eng = mix.engine
    per_seq = eng["max_len"] // page
    lo = -(-mix.prompt_len["min"] // page)
    hi = -(-mix.prompt_len["max"] // page)
    seqs = sorted(
        {min(1 << (n - 1).bit_length(), per_seq) * page for n in range(lo, hi + 1)}
    )
    ns = [1 << i for i in range(eng["max_prefill_batch"].bit_length()) if 1 << i <= eng["max_prefill_batch"]]
    return [(n, s) for n in ns for s in seqs]


def engine_config(mix: generator.Mix, n_pages: int):
    from repro.serving import EngineConfig

    e = mix.engine
    return EngineConfig(
        max_slots=e["max_slots"],
        max_len=e["max_len"],
        max_prefill_batch=e["max_prefill_batch"],
        n_pages=n_pages,
    )


def size_pool(cfg, mesh, param_shapes, mix, device, arch=None) -> dict:
    """Pages for the pool: what the chip holds after the parameters and
    the temporaries of the decode step and the largest prefill program,
    as ``memory_analysis()`` of those programs reports them. Each
    program's need is taken as affine in the pages: estimated from two
    small probe pools, then the programs are compiled at the estimate
    and it is cut until both fit.

    The serving cache the programs are lowered on is the architecture
    module's ``cache_shapes(cfg, n_pages, page, slots)`` where ``arch``
    gives it, else the program's ``init_paged_cache(cfg, n_pages,
    page)``. Whatever that tree holds is counted in each program's need,
    state sized by the slots as well as the pages. A page's bytes are
    the difference of the tree between the two probe sizes, so state
    that does not scale with the pages cancels out of them."""
    import jax
    import jax.numpy as jnp

    from repro.distributed import sharding
    from repro.kernels.ops import default_impl
    from repro.models import transformer as T

    st = sharding.Strategy(mesh, "fsdp")
    c = cfg.replace(tp_size=st.tp_size, batch_axes=st.batch)
    page, e = cfg.attn_block, mix.engine
    per_seq = e["max_len"] // page
    slots = e["max_slots"]
    impl = default_impl()
    sds = jax.ShapeDtypeStruct
    nb, s = max(prefill_buckets(mix, page), key=lambda b: b[0] * b[1])
    i32 = lambda *shape: sds(shape, jnp.int32)  # noqa: E731
    dec = jax.jit(
        lambda p, cc, t, pos, tab: T.decode_step_paged(c, p, cc, t, pos, tab, impl=impl, paged_impl=impl),
        donate_argnums=(1,),
    )
    pre = jax.jit(
        lambda p, t, pl, cc, rows: T.prefill_paged(c, p, t, pl, cc, rows, impl=impl),
        donate_argnums=(3,),
    )

    def pool_shapes(n_pages):
        if hasattr(arch, "cache_shapes"):
            return arch.cache_shapes(c, n_pages, page, slots)
        return jax.eval_shape(lambda: T.init_paged_cache(c, n_pages, page))

    def need(n_pages) -> list[int]:
        """Bytes the device holds while each program runs: the decode
        step's, the largest prefill program's."""
        pool = pool_shapes(n_pages)
        with mesh:
            progs = (
                dec.lower(param_shapes, pool, i32(slots), i32(slots), i32(slots, per_seq)),
                pre.lower(param_shapes, i32(nb, s), i32(nb), pool, i32(nb, s // page)),
            )
            out = []
            for lowered in progs:
                m = lowered.compile().memory_analysis()
                out.append(m.argument_size_in_bytes + m.temp_size_in_bytes
                           + m.output_size_in_bytes - m.alias_size_in_bytes)
        return out

    limit = device.memory_stats()["bytes_limit"]
    budget = limit - POOL_RESERVE_BYTES
    params = tree_bytes(param_shapes)
    lo, hi = per_seq + 1, 2 * per_seq + 1
    page_bytes = (tree_bytes(pool_shapes(hi)) - tree_bytes(pool_shapes(lo))) // (hi - lo)
    # each program's need is affine in the pages: fit both, take the
    # tighter, then correct the estimate by what the compiler reports
    probes = {lo: need(lo), hi: need(hi)}
    lines = [(a, (b - a) / (hi - lo)) for a, b in zip(probes[lo], probes[hi])]
    n = min(int(lo + (budget - a) // slope) for a, slope in lines)
    tries = []
    for _ in range(POOL_TRIES):
        n = max(lo, min(n, slots * per_seq + 1))
        try:
            used = need(n)
        except jax.errors.JaxRuntimeError as err:
            if "RESOURCE_EXHAUSTED" not in str(err):
                raise
            tries.append((n, None))
            n = int(n * 0.95)
            continue
        tries.append((n, max(used)))
        if max(used) <= budget:
            break
        n = min(
            int(lo + (n - lo) * (budget - a) / (u - a)) - 1
            for (a, _), u in zip(lines, used)
            if u > budget
        )
    else:
        raise RuntimeError(f"no pool size fits {budget} bytes: {tries}")
    return {"n_pages": n, "page_bytes": page_bytes, "bytes_limit": limit, "param_bytes": params, "tries": tries,
            "probes": probes}


def build_engine(cfg, mesh, params, mix, n_pages: int, paged_impl=None):
    from repro.serving import Engine

    return Engine(
        cfg, mesh, engine_cfg=engine_config(mix, n_pages), params=params,
        paged_impl=paged_impl,
    )


def router_devices(devices) -> list:
    """``devices`` in the order the router's replicas take them: JAX's
    default device last. A one-device engine creates its pool on the
    default device and moves it to its own with its first decode step,
    in its constructor; so the default device holds one pool at a time
    only where its own replica is built last."""
    import jax

    default = jax.devices()[0]
    return [d for d in devices if d != default] + [d for d in devices if d == default]


def build_router(cfg, devices, replicas: int, mix, n_pages: int, paged_impl=None):
    """``ReplicaRouter`` over ``replicas`` disjoint slices of ``devices``
    (in ``router_devices`` order): each replica an engine with a pool of
    ``n_pages`` and its parameters placed as a one-chip cell's engine
    places them (``fsdp``)."""
    from repro.serving.router import ReplicaRouter

    return ReplicaRouter(
        cfg, replicas=replicas, tp=len(devices) // replicas, engine_cfg=engine_config(mix, n_pages),
        strategy="fsdp", paged_impl=paged_impl, devices=router_devices(devices),
    )


def load_weights(router, param_shapes, seed: int, vocab: int) -> None:
    """Put the seed's weights in every replica of ``router`` in place of
    those the replica made for itself, as a checkpoint load would: made
    on the replica's own chips (``make_params``), placed as its programs
    expect. The replica's own weights are freed first."""
    import jax

    for eng in router.engines:
        shardings = jax.tree.map(lambda a: a.sharding, eng.params)
        eng.params = None
        eng.params = make_params(param_shapes, seed, vocab, shardings=shardings)
    jax.block_until_ready([eng.params for eng in router.engines])


class Fleet:
    """What the open loop drives: one ``Engine``, or a ``ReplicaRouter``
    with its replicas' engines behind it. Requests go through the
    server's ``submit`` and ``step``; each request's replica and its id
    there are the router's placement of it."""

    def __init__(self, server):
        self.server = server
        self.routed = hasattr(server, "engines")
        self.engines = list(server.engines) if self.routed else [server]

    def submit(self, prompt, max_new_tokens: int) -> tuple[int, int, int]:
        """(uid, replica, the replica's own uid)."""
        uid = self.server.submit(prompt, max_new_tokens)
        if not self.routed:
            return uid, 0, uid
        replica, local = self.server._placed[uid]
        return uid, replica, local

    def step(self):
        return self.server.step()

    def running(self):
        """(replica, the replica's uid, state) of every running sequence."""
        return [(i, st.request.uid, st) for i, e in enumerate(self.engines) for st in e.scheduler.active()]

    def waiting(self) -> int:
        return sum(len(e.scheduler.waiting) for e in self.engines)

    def idle(self) -> bool:
        return not any(e.scheduler.active() or e.scheduler.waiting for e in self.engines)


def warm(engine, mix) -> None:
    """Run every prefill program the mix can produce once, through
    ``submit``/``step``, each followed by the decode step on the pool it
    leaves (two-token requests finish in their admission step)."""
    rng = np.random.default_rng(0)
    buckets = prefill_buckets(mix, engine.kv.page)
    for n, s in buckets:
        for _ in range(n):
            engine.submit(rng.integers(0, engine.cfg.vocab_size, size=s, dtype=np.int32), 2)
        done = engine.step()
        if len(done) != n or engine.scheduler.active() or engine.scheduler.waiting:
            raise RuntimeError(f"warm-up of bucket ({n}, {s}) did not finish in one step")
    engine.reset_stats()


def warm_fleet(fleet: Fleet, mix) -> None:
    """``warm`` every replica, the replicas side by side, one thread each:
    each loads or compiles programs for its own chips."""
    if len(fleet.engines) == 1:
        warm(fleet.engines[0], mix)
        return
    with concurrent.futures.ThreadPoolExecutor(len(fleet.engines)) as pool:
        for f in [pool.submit(warm, e, mix) for e in fleet.engines]:
            f.result()


# ----------------------------------------------------------------------
# The open loop
# ----------------------------------------------------------------------


@dataclasses.dataclass
class Req:
    idx: int
    due: float  # absolute perf_counter seconds
    plen: int
    olen: int
    uid: int = -1
    submit: float | None = None
    times: list = dataclasses.field(default_factory=list)  # delivery time per token
    first_step: int | None = None  # harness step that delivered the first token
    finished: object = None  # FinishedRequest
    finish_time: float | None = None
    failed: bool = False
    served: list | None = None  # token ids served by the close (finished or not)
    replica: int = 0  # the replica that serves it
    local: int = -1  # its uid in that replica's engine

    @property
    def count(self) -> int:
        return len(self.times)


@dataclasses.dataclass
class Step:
    start: float
    end: float
    prefill_tokens: int = 0  # prompt tokens of requests admitted in the step
    prefills: list = dataclasses.field(default_factory=list)  # prompt lengths
    decode_positions: list = dataclasses.field(default_factory=list)
    # behind a router: the same step's work on each replica that worked
    parts: dict = dataclasses.field(default_factory=dict)

    def on(self, replica: int) -> "Step":
        return self.parts.setdefault(replica, Step(self.start, self.end))


@dataclasses.dataclass
class Window:
    open: float
    close: float
    reqs: list[Req]
    steps: list[Step]
    compiles: int
    waiting_at_close: int = 0  # requests due but not admitted when the window closed

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def on(self, replica: int) -> "Window":
        """The window as one replica saw it: every request, and each
        step's work on that replica alone (the whole step where the
        steps were not split by replica)."""
        if not any(s.parts for s in self.steps):
            return self
        steps = [s.parts.get(replica, Step(s.start, s.end)) for s in self.steps]
        return dataclasses.replace(self, steps=steps)


def admit_in_setup(fleet: Fleet, sched: generator.Schedule) -> list[Req]:
    """Submit the schedule's first ``sched.in_setup`` requests and take
    one step, which admits as many of them as the pool holds (an
    admission pass plans every waiting request its budget fits) and
    decodes them once. Part of set-up: the tokens it delivers are
    stamped before the window opens, where no metric counts them."""
    if not sched.in_setup:
        return []
    clock = time.perf_counter
    reqs = []
    for i in range(sched.in_setup):
        r = Req(i, math.nan, int(sched.prompt_len[i]), int(sched.output_len[i]))
        r.uid, r.replica, r.local = fleet.submit(sched.prompts[i], r.olen)
        r.submit = clock()
        reqs.append(r)
    by_local = {(r.replica, r.local): r for r in reqs}
    fins = fleet.step()
    t = clock()
    running = fleet.running()
    if fins or not running:
        raise RuntimeError(f"set-up admitted {len(fins)} finished and no running request")
    for replica, local, st in running:
        r = by_local[replica, local]
        r.times = [t] * len(st.generated)
    return reqs


def open_loop(fleet: Fleet, sched: generator.Schedule, seconds: float, *, t_open: float | None = None,
              annotate: bool = False, hooks=None, started: list[Req] | None = None) -> Window:
    """Submit each request when due, step while there is work, sleep
    only while every replica is idle. Tokens are stamped at the end of
    the step that produced them. ``started`` are the schedule's first
    requests, submitted during set-up (``admit_in_setup``). Behind a
    router, each step's work is also kept per replica."""
    import jax

    from repro.analysis.guards import compile_events_total

    span = (lambda name, **kw: jax.profiler.TraceAnnotation(name, **kw)) if annotate else (
        lambda name, **kw: contextlib.nullcontext())
    clock = time.perf_counter
    t_open = clock() if t_open is None else t_open
    close = t_open + seconds
    reqs: list[Req] = list(started or [])
    by_uid: dict[int, Req] = {r.uid: r for r in reqs}
    by_local: dict[tuple[int, int], Req] = {(r.replica, r.local): r for r in reqs}
    pending = [
        Req(i, t_open + float(d), int(p), int(o))
        for i, (d, p, o) in enumerate(zip(sched.due_s, sched.prompt_len, sched.output_len))
    ]
    for r in reqs:
        r.due = pending[r.idx].due
    prompts = sched.prompts
    nxt = len(reqs)
    steps: list[Step] = []
    c0 = compile_events_total()
    hooks = hooks or {}
    while True:
        now = clock()
        if now >= close:
            break
        if "tick" in hooks:
            hooks["tick"](now)
        if nxt < len(pending) and pending[nxt].due <= now:
            with span("submit"):
                while nxt < len(pending) and pending[nxt].due <= now:
                    r = pending[nxt]
                    r.uid, r.replica, r.local = fleet.submit(prompts[nxt], r.olen)
                    r.submit = clock()
                    by_uid[r.uid] = r
                    by_local[r.replica, r.local] = r
                    reqs.append(r)
                    nxt += 1
        if fleet.idle():
            wake = pending[nxt].due if nxt < len(pending) else close
            with span("wait_for_arrival"):
                time.sleep(max(0.0, min(wake, close) - clock()))
            continue
        before = {by_local[i, u].idx: st.pos for i, u, st in fleet.running()}
        t0 = clock()
        with span("step", step=len(steps)):
            fins = fleet.step()
        t1 = clock()
        step = Step(t0, t1)
        counts = {by_local[i, u].idx: (by_local[i, u], len(st.generated)) for i, u, st in fleet.running()}
        for f in fins:
            r = by_uid[f.uid]
            counts[r.idx] = (r, len(f.tokens))
            r.finished, r.finish_time = f, t1
            r.failed = f.finish_reason in ("rejected", "capacity") or f.preemptions > 0
        for idx, (r, n) in counts.items():
            new = n - r.count
            if new <= 0:
                continue
            where = (step, step.on(r.replica)) if fleet.routed else (step,)
            if r.count == 0:
                r.first_step = len(steps)
                for s in where:
                    s.prefills.append(r.plen)
                    s.prefill_tokens += r.plen
                new -= 1
                r.times.append(t1)
            if new > 0:
                # decode tokens: the request's slot sat at ``pos`` before
                # the step, or at ``plen`` when admitted in this step
                p0 = before.get(idx, r.plen)
                for s in where:
                    s.decode_positions.extend(range(p0, p0 + new))
                r.times.extend([t1] * new)
        steps.append(step)
    compiles = compile_events_total() - c0
    for r in reqs:
        if r.finished is not None:
            r.served = [int(t) for t in r.finished.tokens]
    for i, u, st in fleet.running():
        by_local[i, u].served = [int(t) for t in st.generated]
    return Window(t_open, close, reqs, steps, compiles, fleet.waiting())


# ----------------------------------------------------------------------
# End-to-end metrics
# ----------------------------------------------------------------------


def _p(vals, q) -> float:
    return float(np.percentile(np.asarray(vals, np.float64), q)) if len(vals) else float("nan")


def ttft_ms(win: Window) -> list[float]:
    """Due to first token, for every request due in the window; one
    with no first token by the close counts at its wait so far."""
    out = []
    for r in win.reqs:
        if not (win.open <= r.due < win.close):
            continue
        t = r.times[0] if r.times and r.times[0] <= win.close else win.close
        out.append((t - r.due) * 1e3)
    return out


def itl_ms(win: Window) -> list[float]:
    """Gaps between consecutive tokens of a request, for every pair of
    tokens delivered in the window."""
    out = []
    for r in win.reqs:
        ts = r.times
        for a, b in zip(ts, ts[1:]):
            if win.open <= a and b <= win.close:
                out.append((b - a) * 1e3)
    return out


def tokens_in_window(win: Window) -> int:
    return sum(1 for r in win.reqs for t in r.times if win.open <= t <= win.close)


def end_to_end(win: Window, setup_s: float, names: list[str]) -> dict:
    """The named end-to-end metrics: ``setup_s``, ``output_tokens_per_s``,
    and ``ttft_p<q>_ms`` / ``itl_p<q>_ms`` for any percentile ``q``."""
    out = {}
    for name in names:
        m = re.fullmatch(r"(ttft|itl)_p(\d+)_ms", name)
        if m:
            vals = ttft_ms(win) if m.group(1) == "ttft" else itl_ms(win)
            out[name] = _p(vals, int(m.group(2)))
        elif name == "output_tokens_per_s":
            out[name] = tokens_in_window(win) / win.seconds
        elif name == "setup_s":
            out[name] = setup_s
        else:
            raise KeyError(f"no end-to-end metric named {name!r}")
    return out


# ----------------------------------------------------------------------
# Correctness
# ----------------------------------------------------------------------


def pick_sample(win: Window, seed: int) -> list[Req]:
    """Requests for the reference: among those that served tokens by the
    close (finished, or still decoding), the one with the longest
    context, then others in an order drawn from the seed: first the
    first in that order from each replica not yet in the sample, then
    the rest up to ``SAMPLE_TOKENS`` served tokens."""
    done = [r for r in win.reqs if r.served and not r.failed]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.plen + len(r.served), r.idx))
    rest = [r for r in done if r is not longest]
    order = generator.seed_rng(seed, 5).permutation(len(rest))
    out, n = [longest], len(longest.served)
    replicas = {longest.replica}
    for i in order:
        if rest[i].replica not in replicas:
            replicas.add(rest[i].replica)
            out.append(rest[i])
            n += len(rest[i].served)
    for i in order:
        if n >= SAMPLE_TOKENS or len(out) >= SAMPLE_MAX_REQUESTS:
            break
        if any(rest[i] is r for r in out):
            continue
        out.append(rest[i])
        n += len(rest[i].served)
    return out


def served_gaps(ref: Reference, prompts: list, served: list, control: Reference | None = None):
    """Per request, per served token: how far its reference logit lies
    below the reference's best (inf for an id outside the vocabulary).
    With ``control``, also the gaps of the tokens the control puts first
    at the same positions."""
    seqs = [np.concatenate([p, s[:-1]]).astype(np.int32) for p, s in zip(prompts, served)]
    reads = [np.arange(p.size - 1, q.size) for p, q in zip(prompts, seqs)]
    out, ctl = [], []
    lgs = ref.logits_many(seqs, reads)
    picks = [c.argmax(-1) for c in control.logits_many(seqs, reads)] if control else None
    for i, (lg, s) in enumerate(zip(lgs, served)):
        s = np.asarray(s, np.int64)
        best = lg.max(axis=-1)
        rows = np.arange(s.size)
        ok = (s >= 0) & (s < lg.shape[-1])
        got = np.where(ok, lg[rows, np.clip(s, 0, lg.shape[-1] - 1)], -np.inf)
        out.append(best - got)
        if picks is not None:
            ctl.append(best - lg[rows, picks[i]])
    return out, (ctl if control else None)
