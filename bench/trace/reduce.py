"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
metrics read.

What a trace offers, and what is read from it:

- device operations: the events of the busiest TPU device plane's ``XLA Ops``
  line, each inside one execution of a compiled program (its ``XLA
  Modules`` line). On a host without an accelerator, the events of the
  host plane that carry an ``hlo_module`` stat stand in (tests only).
- the harness's own host spans (``jax.profiler.TraceAnnotation``):
  ``step`` (with the harness's step index as a stat), ``submit`` and
  ``wait_for_arrival``.

Device time is attributed to the step whose span contains the
operation's start; each step waits for its device work, so a step's
operations fall inside its span. A program execution is the decode
program when it runs the paged decode kernel; every other program run
inside a step is a prefill. Kernels are told apart by the jitted
function that wraps each ``pallas_call``, which the operation's
metadata names: ``paged_decode_attention_pallas``, ``bsr_matmul_pallas``
and ``block_sparse_attention_pallas``.
"""

from __future__ import annotations

import bisect
import collections
import dataclasses
import glob
import os
import re

__all__ = ["Reduced", "StepDevice", "reduce_dir", "reduce_file", "KERNELS"]

KERNELS = {
    "paged_decode": "paged_decode_attention_pallas",
    "bsr_matmul": "bsr_matmul_pallas",
    "bsr_attention": "block_sparse_attention_pallas",
}
HOST_SPANS = ("step", "submit", "wait_for_arrival")
CONTAINERS = ("while", "conditional", "call", "cond")


@dataclasses.dataclass
class Op:
    start: float  # ns, trace clock
    dur: float
    name: str
    module: int  # index of the program execution that ran it, -1 if none
    kernel: str | None


@dataclasses.dataclass
class StepDevice:
    decode_ms: float = 0.0  # decode program executions
    prefill_ms: float = 0.0  # other program executions
    paged_ms: float = 0.0  # paged decode kernel
    bsr_decode_ms: float = 0.0  # BSR matmul kernel inside the decode program


@dataclasses.dataclass
class Reduced:
    window_s: float
    busy_s: float
    steps: dict[int, StepDevice]
    ops: list[Op]
    gaps: list[tuple[str, float]]  # (host span the gap falls in, seconds)
    # the device number of the plane read (None on a host without an
    # accelerator), and the busy seconds of every device plane
    device: int | None = None
    busy_by_device: dict[int, float] = dataclasses.field(default_factory=dict)

    def top_ops(self, n: int) -> list[list]:
        """The device operations that took most time, by kind; loops and
        calls, which hold other operations, are left out."""
        tot = collections.Counter()
        for op in self.ops:
            if op.name.split("/")[-1] not in CONTAINERS:
                tot[op.name] += op.dur / 1e9
        return [[k, v] for k, v in tot.most_common(n)]

    def idle_gaps(self, n: int) -> list[list]:
        return [[k, v] for k, v in sorted(self.gaps, key=lambda g: -g[1])[:n]]


def _stats(ev) -> dict:
    try:
        return {str(k): v for k, v in ev.stats}
    except Exception:  # a stat of a type the reader cannot convert
        return {}


def _text(ev, stats: dict) -> str:
    return " ".join([ev.name] + [str(v) for v in stats.values()])


def _kind(name: str) -> str:
    """An operation's kind: its HLO instruction name without the
    instance number (``%copy.175 = bf16[...] copy(...)`` -> ``copy``)."""
    m = re.match(r"%?([A-Za-z_][\w-]*?)(?:[._]\d+)*(?:\s|=|$)", name)
    return m.group(1) if m else name[:40]


def _union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def _busy_ns(ops) -> float:
    return sum(b - a for a, b in _union([(o[0], o[0] + o[1]) for o in ops]))


def _device_events(pd):
    """(ops, modules, device, busy): raw (start, dur, text, name) tuples of
    the busiest device plane's operations and program executions, that
    plane's device number (``/device:TPU:<n>``), and the busy seconds of
    every device plane by its number. On a host without an accelerator
    the device is None and the busy seconds are empty."""
    best, busy_by_device = None, {}
    for plane in pd.planes:
        if not plane.name.startswith("/device:"):
            continue
        lines = {line.name: line for line in plane.lines}
        if "XLA Ops" not in lines:
            continue
        ops = []
        for ev in lines["XLA Ops"].events:
            st = _stats(ev)
            ops.append((ev.start_ns, ev.duration_ns, _text(ev, st), ev.name))
        mods = []
        for ev in lines.get("XLA Modules", None).events if "XLA Modules" in lines else []:
            mods.append((ev.start_ns, ev.duration_ns, ev.name))
        m = re.search(r":(\d+)$", plane.name)
        device = int(m.group(1)) if m else None
        if device is not None:
            busy_by_device[device] = _busy_ns(ops) / 1e9
        busy = sum(o[1] for o in ops)
        if best is None or busy > best[2]:
            best = (ops, mods, busy, device)
    if best is not None:
        return best[0], best[1], best[3], busy_by_device
    # no device plane: the host's compiled-program events (CPU tests)
    ops, mods = [], {}
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            for ev in line.events:
                st = _stats(ev)
                if "hlo_module" in st and ev.duration_ns > 0:
                    ops.append((ev.start_ns, ev.duration_ns, _text(ev, st), ev.name))
                    key = (st["hlo_module"], st.get("run_id"))
                    a, b = mods.get(key, (ev.start_ns, ev.start_ns + ev.duration_ns))
                    mods[key] = (min(a, ev.start_ns), max(b, ev.start_ns + ev.duration_ns))
    return ops, [(a, b - a, str(k[0])) for k, (a, b) in mods.items()], None, {}


def _host_spans(pd) -> list[tuple[str, float, float, dict]]:
    out = []
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                name = ev.name.split("#", 1)[0]
                if name in HOST_SPANS:
                    out.append((name, ev.start_ns, ev.start_ns + ev.duration_ns, _stats(ev)))
    return out


def reduce_file(path: str, window_s: float | None = None) -> Reduced:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    raw_ops, raw_mods, device, busy_by_device = _device_events(pd)
    mods = sorted(raw_mods)
    starts = [m[0] for m in mods]

    def module_of(t: float) -> int:
        i = bisect.bisect_right(starts, t) - 1
        return i if i >= 0 and t <= mods[i][0] + mods[i][1] else -1

    ops = []
    for start, dur, text, name in raw_ops:
        kernel = next((k for k, fn in KERNELS.items() if fn in text), None)
        ops.append(Op(start, dur, "", module_of(start), kernel))
    # a program (by name) is the decode program when any of its runs
    # executes the paged decode kernel
    decode_names = {mods[op.module][2] for op in ops if op.kernel == "paged_decode" and op.module >= 0}
    decode_runs = {i for i, m in enumerate(mods) if m[2] in decode_names}
    for op, (_, _, _, name) in zip(ops, raw_ops):
        where = "decode" if op.module in decode_runs else ("prefill" if op.module >= 0 else "other")
        op.name = f"{where}/{op.kernel or _kind(name)}"

    spans = _host_spans(pd)
    steps: dict[int, StepDevice] = {}
    # each ``step`` span carries the harness's step index; spans without
    # one are numbered in order
    step_spans = sorted((a, b, st.get("step")) for n, a, b, st in spans if n == "step")
    step_spans = [(a, b, i if k is None else int(k)) for i, (a, b, k) in enumerate(step_spans)]
    sstarts = [s[0] for s in step_spans]

    def step_of(t: float) -> int | None:
        i = bisect.bisect_right(sstarts, t) - 1
        if i >= 0 and t <= step_spans[i][1] and step_spans[i][2] >= 0:
            return step_spans[i][2]
        return None

    for i, (a, dur, _) in enumerate(mods):
        s = step_of(a)
        if s is None:
            continue
        sd = steps.setdefault(s, StepDevice())
        if i in decode_runs:
            sd.decode_ms += dur / 1e6
        else:
            sd.prefill_ms += dur / 1e6
    for op in ops:
        s = step_of(op.start)
        if s is None:
            continue
        sd = steps.setdefault(s, StepDevice())
        if op.kernel == "paged_decode":
            sd.paged_ms += op.dur / 1e6
        elif op.kernel == "bsr_matmul" and op.module in decode_runs:
            sd.bsr_decode_ms += op.dur / 1e6

    busy = _union([(op.start, op.start + op.dur) for op in ops])
    busy_s = sum(b - a for a, b in busy) / 1e9
    if window_s is None:
        env = {}
        for plane in pd.planes:
            if plane.name == "Task Environment":
                env = {str(k): v for k, v in plane.stats}
        window_s = (float(env["profile_stop_time"]) - float(env["profile_start_time"])) / 1e9
    gaps = []
    hs = sorted((a, b, n) for n, a, b, _ in spans)
    for (_, b0), (a1, _) in zip(busy, busy[1:]):
        mid = (b0 + a1) / 2
        name = next((n for a, b, n in hs if a <= mid <= b), "harness")
        gaps.append((name, (a1 - b0) / 1e9))
    return Reduced(window_s=window_s, busy_s=busy_s, steps=steps, ops=ops, gaps=gaps,
                   device=device, busy_by_device=busy_by_device)


def reduce_dir(path: str, t0: float | None = None, t1: float | None = None) -> Reduced:
    """The newest trace under ``path``; the window is ``t1 - t0`` (host
    seconds) when given, else the profile's own start and stop."""
    files = glob.glob(os.path.join(path, "**", "*.xplane.pb"), recursive=True)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {path}")
    window = (t1 - t0) if t0 is not None and t1 is not None else None
    return reduce_file(max(files, key=os.path.getmtime), window)
