"""The engine's side of a profiler trace: its phase spans, its programs
by name, and the named scope each device operation ran under. Three
per-layer readings come from them, with the splits behind them.

What the program puts in a trace (``repro.obs.trace``,
``repro.serving.engine``, ``repro.models``):

- host spans ``engine.admission``, ``engine.prefill`` (stats ``s``,
  ``n``), ``engine.decode_prepare``, ``engine.decode_step``,
  ``engine.host_sync``, ``engine.finish`` and ``engine.gc``, on the
  profiler's clock, nested in the harness's ``step`` span;
- programs named ``jit_engine_decode``, ``jit_engine_decode_sampled``,
  ``jit_engine_prefill``, ... (an execution on a TPU is named
  ``jit_engine_decode(<program id>)``);
- named scopes in each operation's metadata (its ``tf_op`` stat, e.g.
  ``jit(engine_decode)/layer_scan/while/body/attn_proj/dot_general``):
  ``layer_scan`` around the layer scan, and inside a block
  ``attn_proj``, ``kv_write``, ``paged_attention``, ``mlp``, ``norm``;
  ``lm_head`` around the logits.

``jax.profiler.ProfileData`` gives events but not the metadata of a
device operation, so the scopes are read from the ``.xplane.pb`` itself
(``_op_scopes``). A trace of a program without these spans, names or
scopes (the harness's own spans only) reads as None.

The profiler puts the device's events on the host's clock once per
session, and on a v5e that was off by about a millisecond in some
sessions: decode programs starting before the span that dispatched
them. ``read_file`` shifts the device's events by the least amount that
makes the trace causal (``_clock_offset``) before idle time is split by
phase; a trace no shift can make causal splits none. Idle time is split
by its overlap with the spans, not by where a gap's midpoint falls: one
gap between steps straddles several phases.

    python -m bench.trace.engine <trace dir>   # one JSON object
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import json
import os
import re
import sys

import numpy as np

from bench.trace.reduce import CONTAINERS, _device_events, _kind, _stats, _union

__all__ = [
    "EngineTrace", "read_file", "read_dir", "scope_of",
    "first_token_hold_p90_ms", "engine_host_ms_per_step", "decode_kv_pool_ms",
    "idle_ms_by_phase", "decode_ms_by_scope", "decode_nesting", "summary",
]

PREFIX = "engine."
DECODE = "jit_engine_decode"  # and jit_engine_decode_sampled
BLOCK_SCOPES = ("attn_proj", "kv_write", "paged_attention", "mlp", "norm", "lm_head")
# the KV pool's own traffic: the scan slicing each layer's pool out of
# the stacked one and writing it back, and the new token's write
POOL_SCOPES = ("layer_scan", "kv_write")


@dataclasses.dataclass
class Span:
    name: str
    start: float  # ns, trace clock
    end: float
    stats: dict


@dataclasses.dataclass
class Run:
    """One execution of a compiled program."""
    module: str  # without the program id: ``jit_engine_decode``
    start: float
    end: float


@dataclasses.dataclass
class Op:
    start: float
    dur: float
    kind: str  # HLO instruction kind: ``copy``, ``fusion``, ...
    run: int  # index into ``EngineTrace.runs``, -1 if none
    scope: str | None  # ``scope_of`` its metadata; None where it has none


@dataclasses.dataclass
class EngineTrace:
    spans: list[Span]  # engine.* host spans
    steps: list[tuple[float, float]]  # the harness's ``step`` spans
    runs: list[Run]  # device times shifted by ``clock_offset``
    ops: list[Op]  # likewise
    busy: list[tuple[float, float]]  # union of the device's operations
    # ns added to the device's times (``_clock_offset``); None where no
    # shift makes the trace causal, and the times are left as recorded
    clock_offset: float | None = 0.0

    def decode_runs(self) -> list[int]:
        """Executions of the decode program, one per decode step: what
        the per-step readings divide by."""
        return [i for i, r in enumerate(self.runs) if r.module.startswith(DECODE)]

    def idle_gaps(self) -> list[tuple[float, float]]:
        return [(b0, a1) for (_, b0), (a1, _) in zip(self.busy, self.busy[1:])]


def _phases(spans: list[Span]) -> list[tuple[float, float, str]]:
    """The host's timeline cut where any span starts or ends, each piece
    named by the innermost span over it (spans nest: the last to start
    that has not ended), ``harness`` where none is."""
    starts = [s.start for s in spans]
    cuts = sorted({t for s in spans for t in (s.start, s.end)})
    out = []
    for a, b in zip(cuts, cuts[1:]):
        i = bisect.bisect_right(starts, (a + b) / 2) - 1
        while i >= 0 and spans[i].end < (a + b) / 2:
            i -= 1
        out.append((a, b, spans[i].name if i >= 0 else "harness"))
    return out


def scope_of(tf_op: str | None) -> str | None:
    """The innermost scope the model names in an operation's metadata:
    the last block scope on its path, else ``layer_scan``, else None."""
    if not tf_op:
        return None
    parts = tf_op.rstrip(":").split("/")
    for p in reversed(parts):
        if p in BLOCK_SCOPES:
            return p
    return "layer_scan" if "layer_scan" in parts else None


# ---- the raw trace ------------------------------------------------------
# A varint walk over the protobuf wire format of XSpace, reading only the
# event metadata of device planes: XSpace.planes = 1; XPlane.name = 2,
# .event_metadata = 4 (map entries: key = 1, value = 2), .stat_metadata = 5;
# XEventMetadata.name = 2, .stats = 5; XStatMetadata.name = 2;
# XStat.metadata_id = 1, .uint64_value = 3, .int64_value = 4,
# .str_value = 5, .ref_value = 7 (tsl/profiler/protobuf/xplane.proto).


def _varint(buf, i: int) -> tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf):
    """(field number, value) of one message: an int for a varint, a
    memoryview for a length-delimited field; fixed-width fields skipped."""
    i = 0
    while i < len(buf):
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            val, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            val, i = buf[i:i + n], i + n
        elif wire in (1, 5):
            i += 8 if wire == 1 else 4
            continue
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, val


def _entry_value(entry):
    return next((v for n, v in _fields(entry) if n == 2), b"")


def _op_scopes(data: bytes) -> dict[tuple[str, str], str]:
    """(program id, operation name) -> ``tf_op`` stat, over every device
    plane's event metadata."""
    out = {}
    buf = memoryview(data)
    for num, plane in _fields(buf):
        if num != 1:
            continue
        fields = list(_fields(plane))
        name = next((bytes(v).decode() for n, v in fields if n == 2), "")
        if not name.startswith("/device:"):
            continue
        stat_names = {}
        for n, entry in fields:
            if n == 5:
                sm = dict(_fields(_entry_value(entry)))
                stat_names[sm.get(1, 0)] = bytes(sm.get(2, b"")).decode()
        for n, entry in fields:
            if n != 4:
                continue
            op_name, stats = "", {}
            for m, v in _fields(_entry_value(entry)):
                if m == 2:
                    op_name = bytes(v).decode(errors="replace")
                elif m == 5:
                    st = dict(_fields(v))
                    key = stat_names.get(st.get(1))
                    if 5 in st:
                        stats[key] = bytes(st[5]).decode(errors="replace")
                    elif 7 in st:
                        stats[key] = stat_names.get(st[7], "")
                    elif 3 in st or 4 in st:
                        stats[key] = str(st.get(3, st.get(4)))
            if "tf_op" in stats:
                out[(stats.get("program_id", ""), op_name)] = stats["tf_op"]
    return out


def _engine_spans(pd) -> tuple[list[Span], list[tuple[float, float]]]:
    spans, steps = [], []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name.startswith(PREFIX):
                    spans.append(Span(name, ev.start_ns, ev.start_ns + ev.duration_ns, _stats(ev)))
                elif name == "step":
                    steps.append((ev.start_ns, ev.start_ns + ev.duration_ns))
    return sorted(spans, key=lambda s: s.start), sorted(steps)


def _dispatches(spans: list[Span], runs: list[Run]):
    """(run, its ``engine.decode_step`` span, that span's
    ``engine.host_sync``) for each decode execution: the span that
    starts nearest the run, if within its own length of it."""
    steps = [s for s in spans if s.name == PREFIX + "decode_step"]
    syncs = [s for s in spans if s.name == PREFIX + "host_sync"]
    for r in runs:
        if not r.module.startswith(DECODE) or not steps:
            continue
        step = min(steps, key=lambda s: abs(s.start - r.start))
        sync = next((h for h in syncs if step.start <= h.start and h.end <= step.end), None)
        if sync is not None and abs(step.start - r.start) <= step.end - step.start:
            yield r, step, sync


def _clock_offset(spans: list[Span], runs: list[Run]) -> float | None:
    """The least shift (ns) of the device's times under which no decode
    program starts before its ``engine.decode_step`` span or ends after
    that span's ``engine.host_sync`` returned; None if no shift does."""
    lo, hi = -float("inf"), float("inf")
    for r, step, sync in _dispatches(spans, runs):
        lo, hi = max(lo, step.start - r.start), min(hi, sync.end - r.end)
    if lo > hi:
        return None
    return max(lo, min(0.0, hi)) if lo > -float("inf") else 0.0


def read_file(path: str) -> EngineTrace:
    from jax.profiler import ProfileData

    with open(path, "rb") as f:
        data = f.read()
    pd = ProfileData.from_serialized_xspace(data)
    scopes = _op_scopes(data)
    raw_ops, raw_mods, _, _ = _device_events(pd)
    mods = sorted(raw_mods)
    runs, pids = [], []
    for a, dur, name in mods:
        m = re.fullmatch(r"(.*)\((\d+)\)", name)
        runs.append(Run(m.group(1) if m else name, a, a + dur))
        pids.append(m.group(2) if m else "")
    spans, steps = _engine_spans(pd)
    offset = _clock_offset(spans, runs)
    shift = offset or 0.0
    for r in runs:
        r.start, r.end = r.start + shift, r.end + shift
    starts = [r.start for r in runs]
    ops = []
    for start, dur, _, name in raw_ops:
        start += shift
        i = bisect.bisect_right(starts, start) - 1
        run = i if i >= 0 and start <= runs[i].end else -1
        tf_op = scopes.get((pids[run], name)) if run >= 0 else None
        ops.append(Op(start, dur, _kind(name), run, scope_of(tf_op)))
    busy = _union([(op.start, op.start + op.dur) for op in ops])
    return EngineTrace(spans, steps, runs, ops, busy, offset)


def read_dir(path: str) -> EngineTrace:
    """The newest trace under ``path``."""
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    return read_file(max(files, key=os.path.getmtime))


# ---- readings -------------------------------------------------------------


def first_token_hold_p90_ms(et: EngineTrace) -> float | None:
    """p90 over requests of how long a request's first token waits on
    the host: from the end of its ``engine.prefill`` span (the token is
    on the host after the prefill's sync) to the end of the harness
    ``step`` that holds it (when the step returns it). Each span counts
    once per request it admitted (its ``n``)."""
    holds, weights = [], []
    for s in et.spans:
        if s.name != PREFIX + "prefill":
            continue
        step = next((b for a, b in et.steps if a <= s.start and s.end <= b), None)
        if step is not None:
            holds.append((step - s.end) / 1e6)
            weights.append(int(s.stats.get("n", 1)))
    if not holds:
        return None
    return float(np.percentile(np.repeat(holds, weights), 90))


def idle_ms_by_phase(et: EngineTrace) -> dict[str, float]:
    """Device-idle ms per traced decode step, by what the host was in
    meanwhile: the innermost engine span, or ``harness`` outside them.
    A gap between two device operations is split where the host moved
    from one span to the next. Empty where the device's clock cannot be
    put on the host's."""
    n = len(et.decode_runs())
    if not n or et.clock_offset is None:
        return {}
    phases = _phases(et.spans)
    starts = [a for a, _, _ in phases]
    out = collections.Counter()
    for g0, g1 in et.idle_gaps():
        out["harness"] += (g1 - g0) / 1e6
        i = max(bisect.bisect_right(starts, g0) - 1, 0)
        while i < len(phases) and phases[i][0] < g1:
            a, b, name = phases[i]
            cut = (min(b, g1) - max(a, g0)) / 1e6
            if cut > 0 and name != "harness":
                out[name] += cut
                out["harness"] -= cut
            i += 1
    return {k: v / n for k, v in out.most_common() if v > 0}


def engine_host_ms_per_step(et: EngineTrace) -> float | None:
    """Device-idle ms per traced decode step while the engine's host code
    runs: idle time under an ``engine.*`` span other than
    ``engine.host_sync`` (there the host waits for the device)."""
    if not et.spans or not et.decode_runs() or et.clock_offset is None:
        return None
    by = idle_ms_by_phase(et)
    return sum(v for k, v in by.items() if k.startswith(PREFIX) and k != PREFIX + "host_sync")


def decode_ms_by_scope(et: EngineTrace) -> dict[str, float]:
    """Device ms per traced decode step of the decode program's
    operations, by scope (``unscoped`` where the metadata names none);
    loops and calls, which hold other operations, are left out."""
    runs = set(et.decode_runs())
    out = collections.Counter()
    for op in et.ops:
        if op.run in runs and op.kind not in CONTAINERS:
            out[op.scope or "unscoped"] += op.dur / 1e6
    return {k: v / len(runs) for k, v in out.most_common()} if runs else {}


def decode_kv_pool_ms(et: EngineTrace) -> float | None:
    """Device ms per traced decode step that the decode program spends
    on the KV pool itself: operations under ``layer_scan`` but in no
    block scope (the scan slicing each layer's pool out and writing it
    back) and under ``kv_write``. Leaves out what carries no scope:
    copies the compiler adds with no metadata, such as whole-pool layout
    copies outside the scan (``decode_ms_by_scope`` shows them as
    ``unscoped``)."""
    by = decode_ms_by_scope(et)
    if not by or set(by) == {"unscoped"}:
        return None
    return sum(by.get(s, 0.0) for s in POOL_SCOPES)


def decode_nesting(et: EngineTrace) -> dict[str, int]:
    """How the decode program's executions sit in the engine's spans as
    recorded (before ``clock_offset``): each should start inside its
    ``engine.decode_step`` span and end before that span's
    ``engine.host_sync`` returns."""
    shift = et.clock_offset or 0.0
    raw = [Run(r.module, r.start - shift, r.end - shift) for r in et.runs]
    out = collections.Counter(runs=len(et.decode_runs()))
    for r, step, sync in _dispatches(et.spans, raw):
        out["start_in_decode_step"] += step.start <= r.start <= step.end
        out["end_before_host_sync_end"] += r.end <= sync.end
    return dict(out)


def summary(et: EngineTrace) -> dict:
    """The three readings and the splits behind them."""
    runs = set(et.decode_runs())
    unscoped = collections.Counter()
    for op in et.ops:
        if op.run in runs and op.scope is None and op.kind not in CONTAINERS:
            unscoped[op.kind] += op.dur / 1e6 / len(runs)
    return {
        "first_token_hold_p90_ms": first_token_hold_p90_ms(et),
        "engine_host_ms_per_step": engine_host_ms_per_step(et),
        "decode_kv_pool_ms": decode_kv_pool_ms(et),
        "decode_steps": len(runs),
        "clock_offset_ms": None if et.clock_offset is None else et.clock_offset / 1e6,
        "idle_ms_by_phase": idle_ms_by_phase(et),
        "decode_ms_by_scope": decode_ms_by_scope(et),
        "unscoped_ms_by_kind": dict(unscoped.most_common(10)),
        "nesting": decode_nesting(et),
    }


if __name__ == "__main__":
    print(json.dumps(summary(read_dir(sys.argv[1]))))
