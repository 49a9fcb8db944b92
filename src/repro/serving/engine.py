"""Continuous-batching serving engine over the block-paged KV cache.

``Engine.submit()`` enqueues requests; each ``step()`` drains the waiting
queue in one admission pass — the first ``lookahead`` queued requests are
grouped by prefill bucket and each same-bucket group is admitted with ONE
jit'd batched prefill call and ONE host sync (no per-request prefill
loop, and an oversized head-of-queue request no longer blocks smaller
ones behind it) — then runs ONE jit'd decode step over all slots (ragged
per-slot positions, idle slots masked to the trash page), and evicts
finished sequences so their slot and pages are reusable the very next
step. ``drain()`` loops until the queue and slots are empty.

The waiting queue is *priority-ordered* (``ScheduleParams``: priority
desc, earliest soft deadline first within a class, FCFS last), and
admission may **preempt**: when a higher-priority request is blocked on
slots or pages, the engine swaps out the lowest-priority /
longest-remaining running sequence — its private KV pages move to host
memory via an async device→host copy overlapped with the next decode
step, shared/radix-indexed pages are pinned or parked in place, never
copied (``repro.serving.swap``) — and the victim's request re-enters the
queue to resume bit-exactly later. Hysteresis
(``EngineConfig(preempt_min_steps=)``) keeps one burst from thrashing
swap: a sequence must run that many steps after each admit/resume before
it can be victimized.

The decode step is always shaped ``(max_slots,)`` and prefill shapes are
bucketed to power-of-two page counts *and* power-of-two batch sizes
(groups split greedily into exact power-of-two chunks, so every call
fills its compiled program), so the engine compiles a handful of programs
total no matter how ragged the traffic is. Page allocation is trimmed to
the real prompt length — bucket padding never pins real pages.
"""

from __future__ import annotations

import dataclasses
import gc
import threading
import time
import weakref

import jax
import jax.numpy as jnp
import numpy as np

from repro.analysis.guards import compile_events_total, hot_path
from repro.configs.base import ModelConfig
from repro.obs import (
    BurnRateMonitor,
    FlightRecorder,
    MetricsRegistry,
    NullTracer,
    SloConfig,
    SpikeDetector,
    Tracer,
    WindowedView,
)
from repro.obs.slo import CRITICAL
from repro.obs.trace import PROFILER_PREFIX
from repro.distributed import sharding
from repro.models import transformer as T
from repro.serving import sampling as sampling_lib
from repro.serving.kv_cache import PagedKVCache
from repro.serving.prefix import PrefixCache, PrefixStats
from repro.serving.request import (
    REJECT_SHED,
    REJECT_TIMEOUT,
    REJECT_TOO_LARGE,
    FinishedRequest,
    Request,
    ScheduleParams,
    SequenceState,
)
from repro.serving.sampling import SamplingParams
from repro.serving.scheduler import Scheduler
from repro.serving.stats import ServeStats
from repro.serving.swap import SwapManager, SwapStats

__all__ = ["Engine", "EngineConfig"]


class EngineConfig:
    """Serving knobs: ``max_slots`` concurrent sequences, each with
    ``max_len`` tokens of page-granular KV capacity. ``lookahead`` bounds
    how many waiting requests one admission pass may inspect (default
    ``2 * max_slots``): within that window smaller requests may be
    admitted past an oversized head-of-queue one. ``max_prefill_batch``
    caps how many same-bucket requests share one jit'd prefill call
    (0 -> ``max_slots``; 1 reproduces per-request admission, kept as the
    benchmark baseline). ``max_skips`` bounds starvation: a waiting
    request that ``max_skips`` admission passes have admitted *around*
    (lookahead picked later, smaller requests over it) becomes a
    barrier — nothing behind it is admitted until it fits (0 disables
    aging). ``prefix_cache`` turns on radix-tree prefix reuse: admission
    maps cached prompt-prefix pages straight into the new slot's page
    table and prefills only the uncached suffix (``repro.serving.prefix``).

    ``preemption`` lets a blocked higher-priority request swap out a
    running strictly-lower-priority sequence (pages to host memory,
    ``repro.serving.swap``) instead of waiting for it to finish;
    ``preempt_min_steps`` is the hysteresis — a sequence may only be
    victimized after running that many steps since its last
    admit/resume, so a burst preempts once, not every step.

    ``trace`` turns on span tracing (``repro.obs``): True for the
    default ring capacity, an int for an explicit event capacity. Off
    (the default), the engine binds the no-op tracer and does zero
    tracing work. Either way, while a ``jax.profiler`` session runs the
    step's phases (``engine.admission``, ``engine.prefill``,
    ``engine.decode_prepare``, ``engine.decode_step``,
    ``engine.host_sync``, ``engine.finish``, and ``engine.gc`` for a
    garbage collection) appear in its trace.

    ``monitor`` turns on the live telemetry plane (``repro.obs.windows``):
    True for a 30 s rolling window, a float for an explicit window in
    seconds — the engine ticks a ``WindowedView`` once per step and
    samples device-memory gauges, and ``windowed_vars()`` / the
    ``/vars`` endpoint answer over it. ``slo`` attaches a multi-window
    burn-rate monitor (``repro.obs.slo.SloConfig``; implies monitoring,
    and widens the window to cover its slow timescale).  ``flight_dir``
    arms the flight recorder: anomalies (decode-step time exceeding
    ``spike_factor`` times the warm EWMA baseline, post-warmup step
    compiles, SLO CRITICAL transitions) snapshot the tracer ring +
    metrics + config into incident bundles under that directory.  All
    four default off — a bare engine does zero live-plane work."""

    def __init__(
        self,
        max_slots: int = 8,
        max_len: int = 512,
        *,
        lookahead: int | None = None,
        max_prefill_batch: int = 0,
        n_pages: int = 0,
        sampler_candidates: int = 64,
        max_skips: int = 64,
        prefix_cache: bool = False,
        preemption: bool = True,
        preempt_min_steps: int = 4,
        trace: bool | int = False,
        monitor: bool | float = False,
        slo: SloConfig | None = None,
        flight_dir: str | None = None,
        spike_factor: float = 8.0,
    ):
        self.max_slots = max_slots
        self.max_len = max_len
        self.n_pages = n_pages  # 0 -> worst-case pool (see PagedKVCache)
        self.lookahead = (
            lookahead if lookahead is not None else 2 * max_slots
        )
        if self.lookahead < 1:
            raise ValueError("lookahead must be >= 1")
        if max_skips < 0:
            raise ValueError("max_skips must be >= 0 (0 disables aging)")
        self.max_skips = max_skips
        self.prefix_cache = prefix_cache
        self.preemption = preemption
        if trace is not True and trace is not False and int(trace) < 0:
            raise ValueError("trace must be a bool or a capacity >= 0")
        self.trace = trace
        # identity checks, not equality: 1.0 == True in Python, and a
        # 1-second window must not be mistaken for the bool default
        if (
            monitor is not True
            and monitor is not False
            and float(monitor) <= 0
        ):
            raise ValueError(
                "monitor must be a bool or a window in seconds > 0"
            )
        self.monitor = monitor
        if slo is not None and not isinstance(slo, SloConfig):
            raise TypeError("slo must be a repro.obs.SloConfig or None")
        self.slo = slo
        self.flight_dir = flight_dir
        if spike_factor <= 1.0:
            raise ValueError("spike_factor must be > 1")
        self.spike_factor = spike_factor
        if preempt_min_steps < 1:
            raise ValueError("preempt_min_steps must be >= 1")
        self.preempt_min_steps = preempt_min_steps
        self.max_prefill_batch = max_prefill_batch or max_slots
        if not 1 <= self.max_prefill_batch <= max_slots:
            raise ValueError(
                f"max_prefill_batch {self.max_prefill_batch} must be in "
                f"[1, max_slots={max_slots}]"
            )
        # static candidate cap for the fused sampler: the sampled branch
        # draws from the top-C logits (lax.top_k, O(V log C)) instead of
        # full-vocab sorting (O(V log V) — ~100ms/step at 50k vocab).
        # Requests may not ask for top_k beyond it (Engine.submit
        # raises). 0 -> uncapped exact full-vocab semantics.
        self.sampler_candidates = sampler_candidates or None
        if sampler_candidates < 0:
            raise ValueError("sampler_candidates must be >= 0")

    @property
    def monitor_window_s(self) -> float | None:
        """Effective rolling-window retention in seconds (None = the
        live plane is off). ``slo`` implies monitoring and widens the
        window to cover its slow burn timescale."""
        if self.monitor is False and self.slo is None:
            return None
        if self.monitor is True or self.monitor is False:
            w = 30.0
        else:
            w = float(self.monitor)
        if self.slo is not None:
            w = max(w, self.slo.slow_window_s)
        return w

    def rounded(self, page: int) -> "EngineConfig":
        max_len = -(-self.max_len // page) * page
        return EngineConfig(
            self.max_slots,
            max_len,
            lookahead=self.lookahead,
            max_prefill_batch=self.max_prefill_batch,
            n_pages=self.n_pages,
            sampler_candidates=self.sampler_candidates or 0,
            max_skips=self.max_skips,
            prefix_cache=self.prefix_cache,
            preemption=self.preemption,
            preempt_min_steps=self.preempt_min_steps,
            trace=self.trace,
            monitor=self.monitor,
            slo=self.slo,
            flight_dir=self.flight_dir,
            spike_factor=self.spike_factor,
        )


def _next_pow2(n: int) -> int:
    return 1 << (n - 1).bit_length() if n > 1 else 1


def _argmax_first(out):
    """(logits, *rest) -> (argmax token ids, *rest): fuses the greedy
    pick into the plain jit variants so they, too, sync token ids only."""
    logits, *rest = out
    return (jnp.argmax(logits, axis=-1).astype(jnp.int32), *rest)


class _GcWatch:
    """The engine's ``gc.callbacks`` hook: adds each collection's pause
    to the engine's ``ServeStats.gc_pause_s`` and, while a profiler
    session runs, spans it as ``engine.gc`` (stats: generation,
    collected). A collection stops the whole process, so every engine
    in it counts each one. Holds the engine weakly and leaves
    ``gc.callbacks`` when the engine is collected. The span bypasses
    the tracer: a collection can start inside a ring write."""

    def __init__(self, engine: "Engine"):
        self._engine = weakref.ref(engine)
        self._t0: float | None = None
        self._span: jax.profiler.TraceAnnotation | None = None
        gc.callbacks.append(self)
        weakref.finalize(engine, gc.callbacks.remove, self)

    def __call__(self, phase: str, info: dict) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
            if jax.profiler.TraceAnnotation.is_enabled():
                self._span = jax.profiler.TraceAnnotation(
                    PROFILER_PREFIX + "gc", generation=info["generation"]
                )
                self._span.__enter__()
            return
        if self._span is not None:
            self._span.set_metadata(collected=info["collected"])
            self._span.__exit__(None, None, None)
            self._span = None
        engine = self._engine()
        if engine is not None and self._t0 is not None:
            engine.stats.record_gc_pause(time.perf_counter() - self._t0)
        self._t0 = None


class _Plan:
    """One admission pass's outcome: prefill ``groups`` keyed by
    ``(suffix bucket, prefix-page bucket)``, swapped sequences to
    ``resume`` (``(req, pinned pages)`` in priority order), the leftover
    page ``budget``/``free_slots``, and — when a request could not be
    planned for resource reasons — the highest-priority ``blocked``
    request, the preemption trigger."""

    def __init__(self):
        self.groups: dict[tuple[int, int], list] = {}
        self.resumes: list[tuple[Request, list[int]]] = []
        self.blocked: Request | None = None
        self.budget = 0
        self.free_slots = 0


class Engine:
    def __init__(
        self,
        cfg: ModelConfig,
        mesh,
        *,
        engine_cfg: EngineConfig | None = None,
        strategy: str = "fsdp",
        seed: int = 0,
        params=None,
        paged_impl: str | None = None,
    ):
        """``paged_impl`` selects the paged decode-attention read:
        "gather" (portable jnp reference), "pallas" (fused page-pool
        TPU kernel), or "interpret" (the kernel body interpreted, for
        validation). None picks per platform like ``kernels.ops``."""
        self.mesh = mesh
        st = sharding.Strategy(mesh, strategy)
        self.cfg = cfg = cfg.replace(tp_size=st.tp_size, batch_axes=st.batch)
        self.st = st
        ecfg = (engine_cfg or EngineConfig()).rounded(cfg.attn_block)
        self.ecfg = ecfg
        with mesh:
            if params is None:
                key = jax.random.PRNGKey(seed)
                pshape = jax.eval_shape(lambda k: T.init_model(k, cfg), key)
                psh = sharding.param_shardings(st, pshape)
                params = jax.jit(
                    lambda k: T.init_model(k, cfg), out_shardings=psh
                )(key)
            self.params = params
            self.kv = PagedKVCache(
                cfg,
                ecfg.max_slots,
                ecfg.max_len,
                n_pages=ecfg.n_pages,
                strategy=st,
            )
            if paged_impl is None:
                from repro.kernels.ops import default_impl

                paged_impl = (
                    "pallas" if default_impl() == "pallas" else "gather"
                )
            if paged_impl not in ("gather", "pallas", "interpret"):
                raise ValueError(
                    f"unknown paged_impl {paged_impl!r}; expected "
                    "'gather', 'pallas' or 'interpret'"
                )
            from repro.kernels.ops import impl_for_mesh

            # an engine over several devices takes the gather paths for
            # paged decode and the BSR projections alike: a Pallas call
            # has no SPMD partitioning rule (see kernels.ops)
            paged_impl = impl_for_mesh(paged_impl, mesh.size)
            self.paged_impl = paged_impl
            impl = impl_for_mesh(None, mesh.size)
            # Slot-indexed sampling state. The host-side (slots,) param
            # rows are written at admission; each step packs them into
            # device arrays so the sampler runs INSIDE the jit'd step —
            # the jit returns token ids, and sampled decode keeps the
            # greedy baseline's single host sync per step. ``presence``
            # ((slots, V+1) bool, col V absorbs padding) tracks each
            # slot's prompt+generated tokens for the repetition penalty
            # and stays device-resident, threaded through both jits.
            ms = ecfg.max_slots
            self._samp = {
                "temp": np.zeros((ms,), np.float32),
                "top_k": np.zeros((ms,), np.int32),
                "top_p": np.ones((ms,), np.float32),
                "rep": np.ones((ms,), np.float32),
                "key": np.zeros((ms, 2), np.uint32),
                # prompt length per slot: the decode step derives each
                # row's sample index in-jit (idx = pos - plen + 1), so
                # steady-state sampled decode uploads NO per-step
                # sampler state at all
                "plen": np.ones((ms,), np.int32),
            }
            # device copy of the packed rows; params change only at
            # admission, so steady-state sampled decode re-uses the
            # cached arrays instead of re-transferring 6 arrays a step
            self._samp_dev: dict | None = None
            self._presence = jnp.zeros(
                (ms, cfg.padded_vocab + 1), jnp.bool_
            )
            # Two compiled variants per step kind. The *plain* variant
            # (in-jit argmax, no sampler state — greedy traffic's fast
            # path, zero sampling overhead) serves steps where no active
            # request needs noise or the presence buffer; the *sampled*
            # variant fuses the full sampler. Both decode variants are
            # warmed at init so neither compiles mid-traffic. Presence
            # rides as its own (donatable) arg; the small (slots,) param
            # arrays are device-cached between admissions.
            # The programs are named functions, not lambdas: their names
            # are the XLA modules' names in a profiler trace
            # (jit_engine_decode, ...), which is how a trace reduction
            # tells the decode program from the prefill ones.
            def engine_decode(p, c, t, pos, pt):
                return _argmax_first(
                    T.decode_step_paged(
                        cfg, p, c, t, pos, pt, impl=impl,
                        paged_impl=paged_impl,
                    )
                )

            def engine_decode_sampled(p, c, t, pos, pt, samp, pres):
                return T.decode_step_paged(
                    cfg, p, c, t, pos, pt, impl=impl, paged_impl=paged_impl,
                    sampler={
                        **samp,
                        # per-request sample index, derived in-jit: the
                        # request in this slot has emitted pos - plen + 1
                        # tokens (idle slots' values are ignored)
                        "idx": pos - samp["plen"] + 1,
                        "presence": pres,
                    },
                    sampler_candidates=ecfg.sampler_candidates,
                )

            def engine_prefill(p, t, plens, c, rows):
                return _argmax_first(
                    T.prefill_paged(cfg, p, t, plens, c, rows, impl=impl)
                )

            def engine_prefill_sampled(p, t, plens, c, rows, samp, pres):
                return T.prefill_paged(
                    cfg, p, t, plens, c, rows, impl=impl,
                    sampler={**samp, "presence": pres},
                    sampler_candidates=ecfg.sampler_candidates,
                )

            def engine_prefill_pre(p, t, plens, c, rows, prow, plen_pre):
                return _argmax_first(
                    T.prefill_paged(
                        cfg, p, t, plens, c, rows, impl=impl,
                        prefix_rows=prow, prefix_lens=plen_pre,
                    )
                )

            def engine_prefill_pre_sampled(
                p, t, plens, c, rows, prow, plen_pre, ft, fl, samp, pres
            ):
                return T.prefill_paged(
                    cfg, p, t, plens, c, rows, impl=impl,
                    prefix_rows=prow, prefix_lens=plen_pre,
                    full_tokens=ft, full_plens=fl,
                    sampler={**samp, "presence": pres},
                    sampler_candidates=ecfg.sampler_candidates,
                )

            self._decode = jax.jit(engine_decode, donate_argnums=(1,))
            self._decode_sampled = jax.jit(
                engine_decode_sampled, donate_argnums=(1, 6)
            )
            # one wrapper; jax.jit specializes per (N, S) bucket shape
            self._prefill = jax.jit(engine_prefill, donate_argnums=(3,))
            self._prefill_sampled = jax.jit(
                engine_prefill_sampled, donate_argnums=(3, 6)
            )
            # cache-aware partial-prefill variants: tokens/plens carry
            # only the uncached suffix, (pre_rows, pre_lens) map the
            # shared prefix pages in. Specialized per (N, S_suffix,
            # P_prefix) bucket; miss-only groups take the plain
            # variants above, so cache-off traffic compiles nothing new.
            self._prefill_pre = jax.jit(
                engine_prefill_pre, donate_argnums=(3,)
            )
            self._prefill_pre_sampled = jax.jit(
                engine_prefill_pre_sampled, donate_argnums=(3, 10)
            )
            # presence rebuild for a *resumed* fancy sequence: one jit'd
            # scatter of its prompt+generated tokens (padded with the
            # absorb column V) into the new slot's row — equivalent to
            # the presence the running sequence had accumulated
            npad = cfg.padded_vocab
            self._seed_presence = jax.jit(
                lambda pres, slot, toks: pres.at[slot].set(False)
                .at[slot, toks]
                .set(True),
                donate_argnums=(0,),
            )
            self._presence_pad = npad  # absorb column for padding
            # One throwaway all-idle decode step (every slot masked to the
            # trash page): compiles the decode program up front AND leaves
            # the pools with the aval/layout the decode step produces —
            # the steady state every later program sees. Without this,
            # each prefill bucket compiled against freshly-initialized
            # pools is compiled a SECOND time at serving time, a
            # multi-hundred-ms hiccup per bucket mid-traffic.
            zeros = jnp.zeros((ecfg.max_slots,), jnp.int32)
            table0 = jnp.zeros_like(jnp.asarray(self.kv.page_table))
            _, self.kv.buffers = self._decode(
                self.params, self.kv.buffers, zeros, zeros, table0
            )
            _, self.kv.buffers, self._presence = self._decode_sampled(
                self.params,
                self.kv.buffers,
                zeros,
                zeros,
                table0,
                self._decode_sampler(),
                self._presence,
            )
        # Observability: one shared metrics registry (ServeStats /
        # SwapStats / PrefixStats are views over it; `repro.obs.prom`
        # renders it) and a span tracer. With trace off the engine
        # binds the no-op tracer — call sites below stay branch-free
        # and cost one no-op call each.
        self.metrics = MetricsRegistry()
        if ecfg.trace:
            self.tracer = Tracer(
                capacity=(1 << 16) if ecfg.trace is True else int(ecfg.trace)
            )
        else:
            self.tracer = NullTracer()
        self._intern_trace_ids()
        self.scheduler = Scheduler(
            ecfg.max_slots,
            on_event=self._sched_event if self.tracer.enabled else None,
        )
        self.stats = ServeStats(self.metrics)
        _GcWatch(self)
        # radix-tree prefix cache: parked pages reuse free pool space
        # opportunistically and are evicted (LRU) the moment the
        # allocator wants them back — admission is never blocked
        self._prefix = (
            PrefixCache(self.kv, metrics=self.metrics)
            if ecfg.prefix_cache
            else None
        )
        # host-memory page swap for preemption (always constructed: the
        # machinery is inert until a preemption actually fires)
        self.swap = SwapManager(
            self.kv,
            page_in_tree=(
                self._prefix.page_in_tree if self._prefix else None
            ),
            metrics=self.metrics,
        )
        # uid -> (SequenceState, SwapRecord) for swapped-out sequences;
        # their Requests sit back in the scheduler's waiting queue and
        # resume (swap-in) instead of prefilling when re-admitted
        self._swapped: dict[int, tuple[SequenceState, object]] = {}
        # swap records whose device→host staging copy is still in
        # flight; finalized right after the next decode step
        self._pending_swaps: list = []
        # structured rejections awaiting delivery by the next step()
        self._rejected: list[FinishedRequest] = []
        # slot -> total pages its sequence may ever need (prompt + decode
        # growth). Only pages_for_len(plen) are allocated at admission;
        # the remainder is a *reservation* the admission budget must not
        # hand out twice, or an oversubscribed pool would exhaust
        # mid-decode (alloc_upto raises, losing every in-flight request).
        self._page_need: dict[int, int] = {}
        # slot -> unconsumed COW-page reservations. A prefix-hit slot
        # maps shared/radix-indexed pages it must not write into; if a
        # write ever lands there (sub-page matching, forking),
        # _ensure_writable needs ONE fresh page for the split. That page
        # is budgeted at admission (folded into _page_need) — without
        # it, an oversubscribed pool can be legitimately dry when the
        # split fires and cow_page raises mid-decode, killing every
        # in-flight request.
        self._cow_reserve: dict[int, int] = {}
        # slots whose active request needs the sampled step variant
        # (noise or presence state); empty set -> plain fast path
        self._fancy_slots: set[int] = set()
        self._uid = 0
        self._step_idx = 0
        # ---- live telemetry plane (all opt-in; see EngineConfig) -----
        window_s = ecfg.monitor_window_s
        # re-evaluates self.metrics every tick, so the view follows
        # reset_stats()'s registry swap (window restarts from zero)
        self._window = (
            WindowedView(lambda: self.metrics, window_s=window_s)
            if window_s is not None
            else None
        )
        # serializes window ticks/reads between the step loop and the
        # /vars scrape thread (MetricsServer handlers)
        self._obs_lock = threading.Lock()
        self._slo_mon = (
            BurnRateMonitor(self._window, ecfg.slo)
            if ecfg.slo is not None
            else None
        )
        self._flight = (
            FlightRecorder(ecfg.flight_dir) if ecfg.flight_dir else None
        )
        # the spike detector exists only to feed the flight recorder
        self._spike = (
            SpikeDetector(factor=ecfg.spike_factor)
            if self._flight is not None
            else None
        )
        # compile-trip captures arm only after the first clean
        # (zero-compile) step: warmup-adjacent compiles — fresh prefill
        # buckets, the sampled variants — are expected, not incidents
        self._flight_armed = False
        # backend allocator introspection, probed once: platforms
        # without memory_stats (CPU) silently report 0 bytes
        self._device_memory_stats = None
        if self._window is not None:
            try:
                dev = np.asarray(self.mesh.devices).flat[0]
                fn = getattr(dev, "memory_stats", None)
                if fn is not None and fn():
                    self._device_memory_stats = fn
            except Exception:
                self._device_memory_stats = None

    # ---- observability -----------------------------------------------
    def _intern_trace_ids(self) -> None:
        """Resolve every track/name id the engine will ever record —
        hot-path tracer calls then do no string work at all. (The
        NullTracer returns 0 for every ring-only name.) The phase
        spans name the stats their end call's a0/a1 carry into a
        profiler trace."""
        tr = self.tracer
        self._tk_admission = tr.track("engine:admission")
        self._tk_prefill = tr.track("engine:prefill")
        self._tk_decode = tr.track("engine:decode")
        self._tk_sync = tr.track("engine:host_sync")
        self._tk_queue = tr.track("queue")
        self._tk_slot = [
            tr.track(f"slot{i}") for i in range(self.ecfg.max_slots)
        ]
        self._nm_admission = tr.name("admission", ("admitted", "compiles"))
        self._nm_prefill = tr.name("prefill", ("s", "n"))
        self._nm_decode_prepare = tr.name("decode_prepare", ("active",))
        self._nm_decode_step = tr.name("decode_step", ("active", "compiles"))
        self._nm_host_sync = tr.name("host_sync", ("active",))
        self._nm_finish = tr.name("finish", ("finished",))
        self._nm_queued = tr.name("queued")
        self._nm_decode = tr.name("decode")
        self._nm_finished = tr.name("finished")
        self._nm_rejected = tr.name("rejected")
        self._nm_swap_out = tr.name("swap_out")
        self._nm_swap_in = tr.name("swap_in")
        self._nm_preempt = tr.name("preempt")
        self._nm_cow = tr.name("cow_split")
        self._nm_prefix_match = tr.name("prefix_match")
        # counter lanes (Perfetto "C" samples, one set per step): pool
        # occupancy, queue depth, running slots render as counter tracks
        # under the span lanes
        self._tk_counters = tr.track("counters")
        self._nm_ctr_live = tr.name("pool_live_pages")
        self._nm_ctr_queue = tr.name("queue_depth")
        self._nm_ctr_running = tr.name("running_slots")
        # scheduler queue-lifecycle instants (see _sched_event)
        self._sched_names = {
            kind: tr.name(kind)
            for kind in ("submit", "admit", "resume", "remove")
        }

    def _sched_event(self, kind: str, req: Request) -> None:
        """Scheduler hook -> queue-track instants (only bound when
        tracing is on, so the off path pays nothing)."""
        self.tracer.instant(self._tk_queue, self._sched_names[kind], req.uid)

    # ---- request intake ----------------------------------------------
    def submit(
        self,
        prompt: np.ndarray,
        max_new_tokens: int,
        *,
        eos_id: int | None = None,
        sampling: SamplingParams | None = None,
        schedule: ScheduleParams | None = None,
    ) -> int:
        """Enqueue one request; returns its uid. ``sampling`` attaches
        per-request decoding knobs (default: exact greedy); ``schedule``
        attaches scheduling knobs (priority / soft deadline / max queue
        wait; default: best-effort FCFS).

        A request that could *never* fit the engine's geometry is not
        an exception: it finishes with ``finish_reason "rejected"`` /
        ``reject_reason REJECT_TOO_LARGE``, delivered by the next
        ``step()`` — callers distinguish it from a queue-wait timeout
        (``REJECT_TIMEOUT``) by the reason enum."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        schedule = schedule or ScheduleParams()
        self._uid += 1
        req = Request(
            self._uid,
            prompt,
            max_new_tokens,
            eos_id=eos_id,
            sampling=sampling or SamplingParams(),
            schedule=schedule,
            submit_s=time.perf_counter(),
        )
        cap = self.ecfg.sampler_candidates
        if (
            sampling is not None
            and cap
            and not sampling.is_greedy  # greedy rows never consult top_k
            and sampling.top_k > cap
        ):
            raise ValueError(
                f"top_k {sampling.top_k} exceeds the engine's sampler "
                f"candidate cap {cap} "
                "(EngineConfig(sampler_candidates=...))"
            )
        lifetime = self.kv.pages_for_len(
            min(prompt.size + max_new_tokens - 1, self.ecfg.max_len)
        )
        if (
            prompt.size > self.ecfg.max_len
            or lifetime > self.kv.n_pages - 1
        ):
            # structured rejection for what could never admit: with
            # aging on, an impossible request would eventually barrier
            # the queue forever
            self._rejected.append(self._reject(req, REJECT_TOO_LARGE))
            return self._uid
        self.scheduler.submit(req)
        return self._uid

    def _reject(self, req: Request, reason: str) -> FinishedRequest:
        self.stats.record_reject(
            reason,
            # shed requests are excluded from SLO accounting: shedding
            # is the burn-rate monitor's own *response* to misses, and
            # counting the sheds as new misses would latch CRITICAL
            had_deadline=(
                req.schedule.deadline_s is not None
                and reason != REJECT_SHED
            ),
        )
        self.tracer.instant(self._tk_queue, self._nm_rejected, req.uid)
        return FinishedRequest(
            uid=req.uid,
            prompt=req.prompt,
            tokens=np.zeros((0,), np.int32),
            finish_reason="rejected",
            reject_reason=reason,
            admit_step=-1,
            finish_step=self._step_idx,
            schedule=req.schedule,
        )

    def _expire_waiting(self, finished: list[FinishedRequest]) -> None:
        """Queue-wait timeouts: a never-admitted request whose
        ``max_queue_wait_s`` has elapsed gives up with a structured
        rejection. Swapped-out sequences are exempt — they have already
        run; their re-queued request always resumes eventually."""
        now = time.perf_counter()
        for req in list(self.scheduler.waiting):
            wait = req.schedule.max_queue_wait_s
            if (
                wait is not None
                and req.uid not in self._swapped
                and now - req.submit_s > wait
            ):
                self.scheduler.remove(req)
                finished.append(self._reject(req, REJECT_TIMEOUT))

    # ---- sampler packing ---------------------------------------------
    def _bind_sampler(
        self, slot: int, sp: SamplingParams, plen: int
    ) -> None:
        """Write one request's sampling params into its slot's rows.
        The PRNG base key depends only on the request's seed — never on
        the slot, step, or co-batched requests — so seeded runs are
        reproducible under any admission order."""
        self._samp["temp"][slot] = sp.temperature
        self._samp["top_k"][slot] = sp.top_k
        self._samp["top_p"][slot] = sp.top_p
        self._samp["rep"][slot] = sp.repetition_penalty
        self._samp["key"][slot] = sampling_lib.base_key_data(sp.seed)
        self._samp["plen"][slot] = plen
        self._samp_dev = None  # rows changed: repack at next use
        if sp.is_plain:
            self._fancy_slots.discard(slot)
        else:
            self._fancy_slots.add(slot)

    @hot_path
    def _decode_sampler(self) -> dict:
        """The slot-indexed sampling state for decode steps. Fully
        device-cached between admissions — the per-request sample index
        is derived in-jit from the step's positions (idx = pos - plen +
        1), so steady-state sampled decode transfers nothing."""
        if self._samp_dev is None:
            self._samp_dev = {
                k: jnp.asarray(v) for k, v in self._samp.items()
            }
        return self._samp_dev

    def _prefill_sampler(self, states: list[SequenceState]) -> dict:
        """Pack per-request sampling params for one admission group
        (sample index 0: the first emitted token)."""
        rows = [st_.slot for st_ in states]
        samp = {
            k: jnp.asarray(v[rows]) for k, v in self._samp.items()
        }
        samp["idx"] = jnp.zeros((len(rows),), jnp.int32)
        samp["slots"] = jnp.asarray(np.asarray(rows, np.int32))
        return samp

    # ---- prefill -----------------------------------------------------
    def _bucket(self, plen: int) -> int:
        """Pad prompt lengths to power-of-two page counts: a handful of
        compiled prefill programs serve every prompt length."""
        nb = min(
            _next_pow2(self.kv.pages_for_len(plen)), self.kv.pages_per_seq
        )
        return nb * self.kv.page

    def _batch_bucket(self, n: int) -> int:
        """Pad admission-group sizes to powers of two (capped at
        ``max_slots``): with S also bucketed, the engine compiles
        O(log slots * log lengths) prefill programs total."""
        return min(_next_pow2(n), self.ecfg.max_slots)

    def _pre_bucket(self, n_pages: int) -> int:
        """Pad prefix-hit page counts to powers of two: partial-prefill
        programs stay O(log) per axis like every other bucket (0 = miss
        -> the plain non-prefix program)."""
        if n_pages == 0:
            return 0
        return min(_next_pow2(n_pages), self.kv.pages_per_seq)

    def _lifetime_pages(self, req) -> int:
        """Worst-case pages a request can ever touch, capped at slot
        capacity. The last generated token is returned but never written
        back (no decode step follows it), so the final write position is
        ``plen + max_new_tokens - 2``."""
        return self.kv.pages_for_len(
            min(req.prompt.size + req.max_new_tokens - 1, self.ecfg.max_len)
        )

    def _alloc(self, slot: int, pos: int) -> None:
        """Grow ``slot`` to cover ``pos``, evicting LRU parked prefix
        pages into the free list first if the allocator would otherwise
        run dry — parked pages are opportunistic and never block a live
        sequence."""
        if self._prefix is not None:
            need = pos // self.kv.page + 1 - self.kv.pages_owned(slot)
            if need > self.kv.free_pages:
                self._prefix.ensure_free(need)
        self.kv.alloc_upto(slot, pos)

    def _ensure_writable(self, slot: int, pos: int) -> None:
        """Copy-on-write guard: a slot must exclusively own the page its
        next token writes into. A shared page (mapped into another slot)
        or a radix-indexed page (its bytes are the tree key's value —
        writing would corrupt future hits) is first replaced by a fresh
        page with a jit'd device-side copy. Page-granular prefix hits
        only ever share *full* pages behind the write position, so this
        fires on sub-page matching or sequence forking — it is the
        invariant, not a hot path.

        The fresh page comes out of the slot's COW reservation
        (``_cow_reserve``, budgeted at prefix-hit admission): the split
        replaces a mapping rather than growing the sequence, so the
        slot's remaining lifetime draw shrinks by one — consuming the
        reservation keeps ``_reserved_pages`` exact and guarantees the
        pool is never dry here even when fully oversubscribed."""
        if self._prefix is None:
            return
        li = pos // self.kv.page
        if li >= self.kv.pages_owned(slot):
            return
        p = int(self.kv.page_table[slot, li])
        if self.kv.refcount(p) > 1 or self._prefix.page_in_tree(p):
            self._prefix.ensure_free(1)
            self.kv.cow_page(slot, li, keep=self._prefix.page_in_tree)
            if self._cow_reserve.get(slot, 0) > 0:
                self._cow_reserve[slot] -= 1
                self._page_need[slot] -= 1
            self.stats.record_cow()
            self.tracer.instant(self._tk_slot[slot], self._nm_cow, p)

    def _reserved_pages(self) -> int:
        """Pages promised to active sequences for decode growth but not
        yet allocated."""
        return sum(
            max(0, need - self.kv.pages_owned(slot))
            for slot, need in self._page_need.items()
        )

    def _match_and_pin(self, req) -> tuple[list[int], int]:
        """Walk the radix tree for ``req``'s prompt and pin every hit
        page (parked pages become live, live pages gain a reference), so
        nothing this plan relies on can be evicted or freed before the
        admission lands. Returns (pinned pages, admission cost in
        pages): fresh pages the request still needs, plus the parked
        pages the pin just consumed from the evictable budget.

        Works for *resumes* too: a swapped-out sequence's resident
        prefix (its swap pins keep the shared pages live, so the tree
        still maps them) comes back through the same walk, and the cost
        formula — lifetime minus resident — prices exactly the fresh
        pages the restore plus future decode growth still need.

        A hit also costs one *COW reserve* page (see ``_cow_reserve``):
        the shared pages the slot adopts are write-protected, and a
        future split must never find the pool dry. A pool-filling
        request (lifetime == every allocatable page) physically cannot
        carry the extra page, so it declines the hit and prefills fresh
        — a miss shares nothing, so it needs no reserve. Resumes are
        exempt from declining: their pinned shared pages were never
        copied to host, so the re-match MUST adopt them."""
        if self._prefix is None:
            return [], self._lifetime_pages(req)
        pages = self._prefix.match(req.prompt)
        lifetime = self._lifetime_pages(req)
        if (
            pages
            and req.uid not in self._swapped
            and lifetime + 1 > self.kv.n_pages - 1
        ):
            return [], lifetime
        parked = 0
        for p in pages:
            if self.kv.is_cached(p):
                self.kv.take_cached(p)
                parked += 1
            else:
                self.kv.incref(p)
        reserve = 1 if pages else 0
        return pages, lifetime - len(pages) + parked + reserve

    def _unpin(self, pages: list[int]) -> None:
        for p in pages:
            self.kv.unpin(p)

    def _plan_admission(self) -> _Plan:
        """One bounded-lookahead pass over the waiting queue (priority
        order): group the first ``lookahead`` requests into same-bucket
        prefill waves — or resume entries for swapped-out sequences —
        that fit the current slot and page budget. A request whose pages
        don't fit is *skipped* (not blocking): later, smaller requests
        in the window may still be admitted this step — unless the
        skipped request has already been admitted around ``max_skips``
        times, in which case the pass stops at it (anti-starvation
        barrier). The budget covers each request's whole lifetime
        (prompt + decode growth), so admission can never oversubscribe
        into a mid-decode out-of-pages crash; with the prefix cache on
        it counts only *uncached* pages (hit pages are shared, parked
        pages are already resident) plus every parked page as evictable
        headroom.

        The highest-priority request that could not be planned is
        reported as ``plan.blocked`` — ``step()`` hands it to the
        preemption path."""
        plan = _Plan()
        plan.free_slots = self.scheduler.num_free_slots
        plan.budget = self.kv.free_pages - self._reserved_pages()
        if self._prefix is not None:
            plan.budget += self._prefix.evictable_pages()
        skipped: list[tuple[int, Request]] = []
        last_planned = -1
        for wi, req in enumerate(
            self.scheduler.peek_admissible(self.ecfg.lookahead)
        ):
            if plan.free_slots == 0:
                if plan.blocked is None:
                    plan.blocked = req
                break
            pages, cost = self._match_and_pin(req)
            if cost > plan.budget:
                self._unpin(pages)
                skipped.append((wi, req))
                if plan.blocked is None:
                    plan.blocked = req
                if (
                    self.ecfg.max_skips
                    and self.scheduler.skip_count(req) >= self.ecfg.max_skips
                ):
                    break  # starved request: stop admitting around it
                continue
            if req.uid in self._swapped:
                plan.resumes.append((req, pages))
            else:
                suffix = req.prompt.size - len(pages) * self.kv.page
                key = (self._bucket(suffix), self._pre_bucket(len(pages)))
                plan.groups.setdefault(key, []).append((req, pages))
            plan.free_slots -= 1
            plan.budget -= cost
            last_planned = wi
        # a request ages only when this pass admitted *around* it
        # (someone behind it in the window got a slot)
        self.scheduler.note_skips(
            [req for wi, req in skipped if wi < last_planned]
        )
        return plan

    def _unplan(self, plan: _Plan) -> None:
        """Drop every pin a plan holds (it is being recomputed after a
        preemption changed the resource picture)."""
        for plans in plan.groups.values():
            for _, pages in plans:
                self._unpin(pages)
        for _, pages in plan.resumes:
            self._unpin(pages)

    # ---- preemption --------------------------------------------------
    def _swap_pin_len(self, state: SequenceState) -> int:
        """How many of a victim's leading pages swap-out would pin in
        place (shared with other slots) rather than copy — capped at the
        radix match limit so a resume's re-match always covers them."""
        owned = self.kv.owned_pages(state.slot)
        cap = min((state.plen - 1) // self.kv.page, len(owned))
        n = 0
        while n < cap and self.kv.refcount(owned[n]) > 1:
            n += 1
        return n

    def _maybe_preempt(self, plan: _Plan) -> bool:
        """Try to unblock ``plan.blocked`` by swapping out running
        sequences of *strictly lower* priority (lowest priority first,
        longest-remaining first within a class). Hysteresis: only
        sequences that have run ``preempt_min_steps`` since their last
        admit/resume are candidates, so one burst cannot thrash swap.
        Victims are only swapped if they collectively unblock the
        request — pointless preemptions are never issued. Returns True
        if anything was swapped (the caller re-plans)."""
        req = plan.blocked
        if req is None or not self.ecfg.preemption:
            return False
        pr = req.schedule.priority
        cands = [
            st_
            for st_ in self.scheduler.active()
            if st_.request.schedule.priority < pr
            and self._step_idx - st_.resume_step
            >= self.ecfg.preempt_min_steps
        ]
        if not cands:
            return False
        cands.sort(
            key=lambda st_: (
                st_.request.schedule.priority,
                -st_.remaining,
            )
        )
        pages, cost = self._match_and_pin(req)
        self._unpin(pages)
        budget, free_slots = plan.budget, plan.free_slots
        victims: list[SequenceState] = []
        for v in cands:
            if free_slots >= 1 and cost <= budget:
                break
            # swapping v frees its private pages (copied or parked) and
            # releases its unallocated decode-growth reservation; only
            # its pinned shared prefix stays unavailable
            need = self._page_need.get(
                v.slot, self.kv.pages_owned(v.slot)
            )
            budget += need - self._swap_pin_len(v)
            free_slots += 1
            victims.append(v)
        if free_slots < 1 or cost > budget:
            return False  # even every candidate would not unblock it
        for v in victims:
            self._preempt(v)
        return True

    def _preempt(self, state: SequenceState) -> None:
        """Swap one running sequence out to host memory and re-queue its
        request for a later bit-exact resume."""
        slot = state.slot
        uid = state.request.uid
        # close the slot's decode span before its pages move; a1=1 marks
        # the close as a preemption, not a finish
        self.tracer.end(self._tk_slot[slot], self._nm_decode, uid, 1)
        self.tracer.instant(self._tk_slot[slot], self._nm_preempt, uid)
        record = self.swap.swap_out(
            slot, max_pin=(state.plen - 1) // self.kv.page
        )
        self.tracer.instant(
            self._tk_slot[slot], self._nm_swap_out, uid, record.n_host
        )
        self.scheduler.evict(slot)
        self._page_need.pop(slot, None)
        self._cow_reserve.pop(slot, None)
        self._fancy_slots.discard(slot)
        state.preemptions += 1
        self._swapped[state.request.uid] = (state, record)
        self._pending_swaps.append(record)
        self.scheduler.submit(state.request)
        self.stats.record_preemption()

    def _resume(self, req: Request, pages: list[int]) -> SequenceState:
        """Swap a preempted sequence back in: adopt the re-matched
        resident prefix (``pages``, pinned by the plan), allocate fresh
        pages for the rest, scatter the host copies, and rebind the
        slot-indexed sampler/presence state. The token stream continues
        bit-exactly: KV bytes round-trip unchanged, and the sampler's
        noise depends only on (seed, sample index)."""
        state, record = self._swapped.pop(req.uid)
        assert self.scheduler.resume(state, request=req) is not None
        slot = state.slot
        self.tracer.instant(
            self._tk_slot[slot], self._nm_swap_in, req.uid, record.n_host
        )
        self.tracer.begin(self._tk_slot[slot], self._nm_decode, req.uid)
        reserve = 1 if pages else 0
        self._page_need[slot] = self._lifetime_pages(req) + reserve
        self._cow_reserve[slot] = reserve
        self._bind_sampler(slot, req.sampling, state.plen)
        if pages:
            self.kv.adopt(slot, pages)
        self._alloc(slot, record.n_logical * self.kv.page - 1)
        self.swap.swap_in(record, slot, n_resident=len(pages))
        state.resume_step = self._step_idx
        state.prefix_hit_tokens = max(
            state.prefix_hit_tokens, len(pages) * self.kv.page
        )
        if not req.sampling.is_plain:
            # rebuild the slot's presence row: prompt + generated so
            # far, padded into the absorb column
            toks = np.full(
                (self.ecfg.max_len + 1,), self._presence_pad, np.int32
            )
            seen = np.concatenate(
                [req.prompt, np.asarray(state.generated, np.int32)]
            )[: self.ecfg.max_len + 1]
            toks[: seen.size] = seen
            with self.mesh:
                self._presence = self._seed_presence(
                    self._presence,
                    jnp.asarray(slot, jnp.int32),
                    jnp.asarray(toks),
                )
        self.stats.record_resume()
        return state

    # ---- admission ---------------------------------------------------
    def _admit_group(
        self, plans: list, s: int, npre: int
    ) -> list[SequenceState]:
        """Admit one same-bucket group: ONE jit'd ``prefill_paged`` call
        over tokens (N, S) and ONE host sync for all N requests. Page
        allocation is trimmed to each real prompt — bucket-padding keys
        scatter to the trash page.

        ``plans`` carries ``(req, pinned prefix pages)`` pairs sharing
        the ``(S suffix, npre prefix-page)`` bucket: hit pages are
        adopted straight into the slot's page table (the plan's pin
        becomes the slot's reference) and only the uncached suffix is
        prefilled, attending the prefix through the page table. The hit
        pages are re-indexed in the radix tree only *after* the call's
        host sync — a same-wave duplicate prompt must never read pages
        its own program is still writing."""
        nb = len(plans)
        # step()'s greedy chunking hands over exact power-of-two groups,
        # so every call fills its compiled (N, S) program — no batch rows
        # are ever padded
        assert nb == self._batch_bucket(nb)
        n_pages = s // self.kv.page
        tokens = np.zeros((nb, s), np.int32)
        plens = np.empty((nb,), np.int32)
        rows = np.zeros((nb, n_pages), np.int32)
        pre_rows = np.zeros((nb, max(npre, 1)), np.int32)
        pre_lens = np.zeros((nb,), np.int32)
        # full prompts ride along only for the sampled variant's
        # presence seeding (cached prefix tokens count for the
        # repetition penalty); shape is static per group bucket
        full_tokens = np.zeros((nb, npre * self.kv.page + s), np.int32)
        full_plens = np.empty((nb,), np.int32)
        states: list[SequenceState] = []
        t_admit = time.perf_counter()
        for i, (req, pages) in enumerate(plans):
            state = self.scheduler.admit(self._step_idx, request=req)
            assert state is not None
            state.resume_step = self._step_idx
            hit = len(pages) * self.kv.page
            state.prefix_hit_tokens = hit
            # queue wait: submit -> this admission pass. The tracer gets
            # it as an X span on the queue track (start = submit time,
            # same perf_counter clock the ns stamps use).
            wait = t_admit - req.submit_s
            self.stats.record_queue_wait(wait)
            self.tracer.complete(
                self._tk_queue,
                self._nm_queued,
                int(req.submit_s * 1e9),
                int(wait * 1e9),
                req.uid,
            )
            if pages:
                self.tracer.instant(
                    self._tk_slot[state.slot],
                    self._nm_prefix_match,
                    req.uid,
                    len(pages),
                )
            # a prefix hit carries one extra budgeted page: the COW
            # reserve for a future split of an adopted shared page
            reserve = 1 if pages else 0
            self._page_need[state.slot] = (
                self._lifetime_pages(req) + reserve
            )
            self._cow_reserve[state.slot] = reserve
            self._bind_sampler(state.slot, req.sampling, state.plen)
            if pages:
                self.kv.adopt(state.slot, pages)
            self._alloc(state.slot, state.plen - 1)
            suffix = req.prompt[hit:]
            tokens[i, : suffix.size] = suffix
            plens[i] = suffix.size
            rows[i] = self.kv.suffix_row(
                state.slot, len(pages), state.plen, n_pages
            )
            pre_rows[i, : len(pages)] = pages
            pre_lens[i] = hit
            full_tokens[i, : state.plen] = req.prompt
            full_plens[i] = state.plen
            self.stats.record_prefix_lookup(hit, state.plen, len(pages))
            states.append(state)
        t0 = time.perf_counter()
        t0_ns = self.tracer.begin(
            self._tk_prefill, self._nm_prefill, s, nb
        )
        with self.mesh:
            # first token picked inside the jit either way: one host
            # sync of N ints. A group of plain (greedy, no-penalty)
            # requests takes the argmax variant and skips all sampler
            # state; one fancy request in the group switches the whole
            # group to the fused-sampler variant (its plain peers still
            # get exact argmax via their temp=0 rows). Miss-only groups
            # (npre == 0) take the plain non-prefix programs — identical
            # to cache-off serving.
            fancy = any(not req.sampling.is_plain for req, _ in plans)
            if npre and fancy:
                toks_dev, self.kv.buffers, self._presence = (
                    self._prefill_pre_sampled(
                        self.params,
                        jnp.asarray(tokens),
                        jnp.asarray(plens),
                        self.kv.buffers,
                        jnp.asarray(rows),
                        jnp.asarray(pre_rows),
                        jnp.asarray(pre_lens),
                        jnp.asarray(full_tokens),
                        jnp.asarray(full_plens),
                        self._prefill_sampler(states),
                        self._presence,
                    )
                )
            elif npre:
                toks_dev, self.kv.buffers = self._prefill_pre(
                    self.params,
                    jnp.asarray(tokens),
                    jnp.asarray(plens),
                    self.kv.buffers,
                    jnp.asarray(rows),
                    jnp.asarray(pre_rows),
                    jnp.asarray(pre_lens),
                )
            elif fancy:
                toks_dev, self.kv.buffers, self._presence = (
                    self._prefill_sampled(
                        self.params,
                        jnp.asarray(tokens),
                        jnp.asarray(plens),
                        self.kv.buffers,
                        jnp.asarray(rows),
                        self._prefill_sampler(states),
                        self._presence,
                    )
                )
            else:
                toks_dev, self.kv.buffers = self._prefill(
                    self.params,
                    jnp.asarray(tokens),
                    jnp.asarray(plens),
                    self.kv.buffers,
                    jnp.asarray(rows),
                )
            # admission-time sync: one batched fetch per prefill group
            toks = jax.device_get(toks_dev)
        dt = time.perf_counter() - t0
        self.tracer.end(self._tk_prefill, self._nm_prefill, s, nb)
        self.stats.record_host_sync()
        now = time.perf_counter()
        self.stats.record_prefill(
            int(plens.sum()),
            dt,
            emitted=len(states),
            batch=len(states),
            bucket=(nb, s),
        )
        dur_ns = int(dt * 1e9)
        for i, state in enumerate(states):
            state.generated.append(int(toks[i]))
            state.pos = state.plen
            state.first_token_s = now
            self.stats.record_ttft(now - state.request.submit_s)
            # per-slot lifecycle: the prefill interval, then the decode
            # span that stays open until finish (or preemption)
            self.tracer.complete(
                self._tk_slot[state.slot], self._nm_prefill, t0_ns,
                dur_ns, s, nb,
            )
            self.tracer.begin(
                self._tk_slot[state.slot],
                self._nm_decode,
                state.request.uid,
            )
            if self._prefix is not None:
                # index the prompt's full pages (hits refresh their LRU
                # tick; new full pages — suffix included — become
                # matchable the moment their contents are synced)
                self._prefix.insert(
                    state.request.prompt,
                    self.kv.page_table[state.slot],
                )
        return states

    # ---- stepping ----------------------------------------------------
    @hot_path
    def step(self) -> list[FinishedRequest]:
        """One scheduler iteration: admit (batched, possibly after
        preempting) -> resume swapped sequences -> decode -> evict.

        Same-bucket groups are split greedily into power-of-two chunks
        (4 -> one call of 4; 3 -> 2+1) capped at ``max_prefill_batch``:
        every chunk exactly fills its compiled (N, S) program, so batching
        never pays for padded batch rows."""
        finished: list[FinishedRequest] = list(self._rejected)
        self._rejected.clear()
        self._expire_waiting(finished)
        # compile correlation: each phase span carries the backend
        # compiles observed while it ran (a1 of its E event) — 0 after
        # warmup, the DispatchGuard invariant made continuously visible
        c0 = compile_events_total()
        tr = self.tracer
        tr.begin(self._tk_admission, self._nm_admission)
        plan = self._plan_admission()
        if self._maybe_preempt(plan):
            # the resource picture changed: recompute the whole pass so
            # the blocked high-priority request plans first
            self._unplan(plan)
            plan = self._plan_admission()
        n_admitted = len(plan.resumes)
        for req, pages in plan.resumes:
            self._resume(req, pages)
        cap = self.ecfg.max_prefill_batch
        for (s, npre), plans in plan.groups.items():
            i = 0
            while i < len(plans):
                n = 1 << (min(len(plans) - i, cap).bit_length() - 1)
                for state in self._admit_group(plans[i : i + n], s, npre):
                    n_admitted += 1
                    if state.done:  # max_new_tokens == 1 or instant EOS
                        finished.append(self._finish(state))
                i += n
        c1 = compile_events_total()
        tr.end(self._tk_admission, self._nm_admission, n_admitted, c1 - c0)

        # a prompt that already fills its slot cannot take a decode step
        for st_ in list(self.scheduler.active()):
            if st_.pos >= self.ecfg.max_len:
                finished.append(self._finish(st_, reason="capacity"))

        active = self.scheduler.active()
        decode_dt: float | None = None
        if active:
            tr.begin(self._tk_decode, self._nm_decode_prepare)
            tokens = np.zeros((self.ecfg.max_slots,), np.int32)
            positions = np.zeros((self.ecfg.max_slots,), np.int32)
            for st_ in active:
                self._ensure_writable(st_.slot, st_.pos)
                self._alloc(st_.slot, st_.pos)
                tokens[st_.slot] = st_.generated[-1]
                positions[st_.slot] = st_.pos
            t0 = time.perf_counter()
            with self.mesh:
                inputs = (
                    jnp.asarray(tokens),
                    jnp.asarray(positions),
                    self.kv.device_table(),
                )
                samp = self._decode_sampler() if self._fancy_slots else None
            tr.end(self._tk_decode, self._nm_decode_prepare, len(active))
            tr.begin(self._tk_decode, self._nm_decode_step, len(active))
            with self.mesh:
                # token picked inside the jit'd step either way: the one
                # host sync fetches (slots,) ids. All-plain traffic takes
                # the argmax variant (zero sampling overhead); any fancy
                # active slot switches the step to the fused sampler.
                if samp is not None:
                    toks_dev, self.kv.buffers, self._presence = (
                        self._decode_sampled(
                            self.params,
                            self.kv.buffers,
                            *inputs,
                            samp,
                            self._presence,
                        )
                    )
                else:
                    toks_dev, self.kv.buffers = self._decode(
                        self.params, self.kv.buffers, *inputs
                    )
                # THE one sanctioned host sync per decode step: a single
                # batched (slots,) fetch of every active slot's next
                # token. Everything downstream (EOS checks, finish
                # bookkeeping) reads this numpy row, never the device.
                tr.begin(self._tk_sync, self._nm_host_sync)
                nxt = jax.device_get(toks_dev)  # jaxlint: disable=JL001 -- the one batched per-step fetch of the next-token row
                tr.end(self._tk_sync, self._nm_host_sync, len(active))
            dt = decode_dt = time.perf_counter() - t0
            tr.end(
                self._tk_decode,
                self._nm_decode_step,
                len(active),
                compile_events_total() - c1,
            )
            self.stats.record_host_sync()
            self.stats.record_decode_step(
                len(active), self.ecfg.max_slots, dt
            )
        tr.begin(self._tk_decode, self._nm_finish)
        for st_ in active:  # empty unless the decode step ran
            st_.pos += 1
            st_.generated.append(int(nxt[st_.slot]))
            if st_.done:
                finished.append(self._finish(st_))
            elif st_.pos >= self.ecfg.max_len:
                finished.append(self._finish(st_, reason="capacity"))
        # the decode step has been overlapping any in-flight swap-out
        # transfers; land them on the host and drop the device staging
        for record in self._pending_swaps:
            self.swap.finalize(record)
        self._pending_swaps.clear()
        step_compiles = compile_events_total() - c0
        self.stats.record_step_compiles(step_compiles)
        self._step_idx += 1
        self._observe_step(decode_dt, len(active), step_compiles)
        tr.end(self._tk_decode, self._nm_finish, len(finished))
        return finished

    # ---- live telemetry (end of step, host-side) ---------------------
    def _observe_step(
        self,
        decode_dt: float | None,
        n_active: int,
        step_compiles: int,
    ) -> None:
        """End-of-step observability hook — after the step's one
        sanctioned sync, never inside a jit'd program. With tracing,
        monitoring and the flight recorder all off this is three no-op
        tracer calls and an early return (the NullTracer makes no
        clock calls — the zero-obs-work invariant the tests assert)."""
        tr = self.tracer
        kv = self.kv
        live = kv.n_pages - kv.free_pages - kv.cached_pages
        tr.counter(self._tk_counters, self._nm_ctr_live, live)
        tr.counter(
            self._tk_counters,
            self._nm_ctr_queue,
            len(self.scheduler.waiting),
        )
        tr.counter(self._tk_counters, self._nm_ctr_running, n_active)
        if self._flight is not None:
            if step_compiles == 0:
                self._flight_armed = True
            elif self._flight_armed:
                # post-warmup compile: the DispatchGuard invariant
                # tripped mid-traffic — snapshot what led up to it
                self._capture_incident(
                    "dispatch_guard_trip",
                    {"step_compiles": step_compiles},
                )
            if self._spike is not None and decode_dt is not None:
                baseline = self._spike.baseline
                if self._spike.observe(decode_dt):
                    self._capture_incident(
                        "step_time_spike",
                        {
                            "decode_step_s": decode_dt,
                            "baseline_s": baseline,
                            "factor": self.ecfg.spike_factor,
                        },
                    )
        if self._window is None:
            return
        self._sample_memory(live)
        with self._obs_lock:
            self._window.tick()
            status = (
                self._slo_mon.evaluate()
                if self._slo_mon is not None
                else None
            )
        if status is None:
            return
        self.stats.record_slo_state(
            status["state_code"], status["fast_burn"], status["slow_burn"]
        )
        if status["state"] == CRITICAL and self.ecfg.slo.shed:
            self._shed_queued(self.ecfg.slo.shed_max_per_tick)
        if status["transitioned_to"] == CRITICAL and self._flight is not None:
            self._capture_incident("slo_critical", {"slo": status})

    def _sample_memory(self, live_pages: int) -> None:
        """Per-step device-memory gauges: pool occupancy/fragmentation,
        COW reserve, host-swap residency, and the backend allocator's
        bytes-in-use where the platform exposes them."""
        host_bytes = sum(
            rec.n_host for _, rec in self._swapped.values()
        ) * self.swap.page_bytes
        dev_bytes = 0
        if self._device_memory_stats is not None:
            try:
                dev_bytes = int(
                    self._device_memory_stats().get("bytes_in_use", 0)
                )
            except Exception:  # backend stopped cooperating: disable
                self._device_memory_stats = None
        self.stats.record_memory(
            n_pages=self.kv.n_pages,
            live_pages=live_pages,
            cached_pages=self.kv.cached_pages,
            reserved_pages=self._reserved_pages(),
            cow_reserve_pages=sum(self._cow_reserve.values()),
            host_swap_bytes=host_bytes,
            device_bytes_in_use=dev_bytes,
        )

    def _shed_queued(self, max_n: int) -> int:
        """CRITICAL-state load shed: reject up to ``max_n`` waiting
        requests from the lowest priority class present, newest-queued
        first (within a class the queue is deadline-then-FCFS ordered,
        so the tail is the least urgent). Swapped-out sequences are
        exempt — they already hold device work and always resume.
        Sheds surface as structured ``REJECT_SHED`` results delivered
        by the next ``step()``, never silent drops."""
        cands = [
            r
            for r in self.scheduler.waiting
            if r.uid not in self._swapped
        ]
        if not cands:
            return 0
        lowest = min(r.schedule.priority for r in cands)
        shed = [r for r in cands if r.schedule.priority == lowest]
        shed = shed[-max_n:][::-1]
        for req in shed:
            self.scheduler.remove(req)
            self._rejected.append(self._reject(req, REJECT_SHED))
        return len(shed)

    def _capture_incident(self, kind: str, context: dict) -> str | None:
        path = self._flight.capture(
            kind,
            tracer=self.tracer,
            metrics=self.metrics,
            config=self._config_dict(),
            context={**context, "step": self._step_idx},
        )
        if path is not None:
            self.stats.record_flight_incident(kind)
        return path

    def _config_dict(self) -> dict:
        e = self.ecfg
        return {
            "max_slots": e.max_slots,
            "max_len": e.max_len,
            "n_pages": self.kv.n_pages,
            "lookahead": e.lookahead,
            "max_prefill_batch": e.max_prefill_batch,
            "prefix_cache": e.prefix_cache,
            "preemption": e.preemption,
            "paged_impl": self.paged_impl,
            "spike_factor": e.spike_factor,
            "slo": dataclasses.asdict(e.slo) if e.slo else None,
        }

    def windowed_vars(self, span_s: float | None = None) -> dict:
        """Live rolling-window stats (the ``/vars`` endpoint). Safe to
        call from the scrape thread: ticks and reads under the obs
        lock, so it never races the step loop's own tick. Percentiles
        come from the window's retained raw samples — a window covering
        the whole run agrees exactly with ``stats_summary()``."""
        if self._window is None:
            return {"enabled": False}
        with self._obs_lock:
            w = self._window
            w.tick()

            def pcts(name: str) -> dict:
                return {
                    f"p{q}_ms": round(
                        w.percentile(name, q, span_s) * 1e3, 3
                    )
                    for q in (50, 95, 99)
                }

            out = {
                "enabled": True,
                "window_s": w.window_s,
                "covered_s": round(w.covered_s, 3),
                "ttft_ms": pcts("repro_serve_ttft_seconds"),
                "queue_wait_ms": pcts("repro_serve_queue_wait_seconds"),
                "token_latency_ms": pcts(
                    "repro_serve_step_latency_seconds"
                ),
                "tok_s": round(
                    w.rate("repro_serve_generated_tokens_total", span_s),
                    2,
                ),
                "admitted_per_s": round(
                    w.rate("repro_serve_prefill_requests_total", span_s),
                    3,
                ),
                "finished_per_s": round(
                    w.rate(
                        "repro_serve_requests_finished_total", span_s
                    ),
                    3,
                ),
                "rejected_per_s": round(
                    w.rate("repro_serve_rejected_total", span_s), 3
                ),
                "queue_depth": len(self.scheduler.waiting),
                "running_slots": len(self.scheduler.active()),
                "memory": {
                    "pool_pages": w.gauge("repro_mem_pool_pages"),
                    "live_pages": w.gauge("repro_mem_pool_live_pages"),
                    "cached_pages": w.gauge(
                        "repro_mem_pool_cached_pages"
                    ),
                    "reserved_pages": w.gauge(
                        "repro_mem_pool_reserved_pages"
                    ),
                    "fragmentation": w.gauge(
                        "repro_mem_pool_fragmentation_ratio"
                    ),
                    "host_swap_bytes": w.gauge(
                        "repro_mem_host_swap_bytes"
                    ),
                    "device_bytes_in_use": w.gauge(
                        "repro_mem_device_bytes_in_use"
                    ),
                },
            }
            if self._slo_mon is not None:
                out["slo"] = dict(self._slo_mon.last)
            return out

    def window_samples(
        self, name: str, span_s: float | None = None
    ) -> list[float]:
        """Raw window samples for one histogram, read under the obs
        lock (``ReplicaRouter`` merges these for fleet percentiles)."""
        if self._window is None:
            return []
        with self._obs_lock:
            self._window.tick()
            return self._window.samples(name, span_s)

    def slo_state(self) -> dict:
        """Read-only burn-rate status (the ``/slo`` endpoint). The step
        loop is the only *evaluator* — a scrape returns the retained
        ``last`` result and can never consume a state transition."""
        if self._slo_mon is None:
            return {"enabled": False}
        return {"enabled": True, **self._slo_mon.last}

    def _finish(
        self, state: SequenceState, *, reason: str | None = None
    ) -> FinishedRequest:
        # Early-finish reclamation: pages the lifetime budget reserved
        # but the sequence never touched (EOS before max_new_tokens) go
        # straight back to the admission budget — popping the need entry
        # releases the reservation, freeing the slot returns the
        # allocated pages — and are counted for the stats.
        need = self._page_need.pop(state.slot, 0)
        self._cow_reserve.pop(state.slot, None)
        reclaimed = max(0, need - self.kv.pages_owned(state.slot))
        # close the slot's decode span (opened at admission/resume)
        self.tracer.end(
            self._tk_slot[state.slot],
            self._nm_decode,
            state.request.uid,
            len(state.generated),
        )
        self.tracer.instant(
            self._tk_slot[state.slot], self._nm_finished, state.request.uid
        )
        if self._prefix is not None:
            # index the decode-written pages too (full blocks only): the
            # next turn of a multi-turn conversation prompts with this
            # sequence's history and hits these pages. The last generated
            # token is returned but never written back, so the indexed
            # content is prompt + generated[:-1].
            written = np.concatenate(
                [
                    state.request.prompt,
                    np.asarray(state.generated[:-1], np.int32),
                ]
            )
            self.stats.record_decode_indexed(
                self._prefix.insert(
                    written, self.kv.page_table[state.slot]
                )
            )
        self.scheduler.evict(state.slot)
        # radix-indexed pages are parked (refcount 0, device-resident)
        # instead of freed: a future prompt sharing the prefix maps them
        # straight back in, and eviction reclaims them on demand
        self.kv.free_slot(
            state.slot,
            keep=None if self._prefix is None else self._prefix.page_in_tree,
        )
        self._fancy_slots.discard(state.slot)
        if reclaimed:
            self.stats.record_reclaimed(reclaimed)
        if reason is None:
            eos = state.request.eos_id
            reason = (
                "eos"
                if eos is not None and state.generated[-1] == eos
                else "length"
            )
        now = time.perf_counter()
        req = state.request
        fin = FinishedRequest(
            uid=req.uid,
            prompt=req.prompt,
            tokens=np.asarray(state.generated, np.int32),
            finish_reason=reason,
            admit_step=state.admit_step,
            finish_step=self._step_idx,
            prefix_hit_tokens=state.prefix_hit_tokens,
            preemptions=state.preemptions,
            ttft_s=(
                state.first_token_s - req.submit_s
                if state.first_token_s is not None
                else None
            ),
            e2e_s=now - req.submit_s,
            schedule=req.schedule,
        )
        self.stats.record_finish(
            kind=req.sampling.kind,
            tokens=len(state.generated),
            slo_met=fin.slo_met,
        )
        return fin

    def drain(self, max_steps: int | None = None) -> list[FinishedRequest]:
        """Step until every submitted request has finished (including
        structured rejections awaiting delivery)."""
        out: list[FinishedRequest] = []
        steps = 0
        while not self.scheduler.idle or self._rejected:
            out.extend(self.step())
            steps += 1
            if (
                max_steps is not None
                and steps >= max_steps
                and not self.scheduler.idle
            ):
                raise RuntimeError(
                    f"drain did not converge in {max_steps} steps"
                )
        return out

    def reset_stats(self) -> None:
        """Zero the per-run counters (benchmark repeats); the radix
        tree's contents survive — only the numbers reset.

        The metrics registry and the tracer ring reset *atomically*
        (both or neither): a fresh registry is built and every stats
        view rebinds to it, and the tracer closes any open spans (they
        are counted as truncated, and their pending ``end()`` calls
        become no-ops) before clearing its ring — a mid-traffic reset
        never leaks a dangling span into the next export."""
        reg = MetricsRegistry()
        self.stats = ServeStats(reg)
        self.swap.stats = SwapStats(reg)
        if self._prefix is not None:
            self._prefix.stats = PrefixStats(reg)
        self.metrics = reg
        self.tracer.reset()

    def stats_summary(self) -> dict:
        out = self.stats.summary()
        out["preemption"].update(self.swap.stats.snapshot())
        if self._prefix is not None:
            out["prefix_cache"].update(self._prefix.stats.snapshot())
            out["prefix_cache"]["enabled"] = True
            out["prefix_cache"]["cached_pages"] = self.kv.cached_pages
            # keep the prom gauge in step with the pool
            self._prefix.stats.set_cached_pages(self.kv.cached_pages)
        return out

    def export_perfetto(self, path: str) -> int:
        """Write this engine's trace ring as Chrome trace-event JSON
        (requires ``EngineConfig(trace=...)``)."""
        return self.tracer.export_perfetto(path)
