"""The engine's spans on the profiler's clock.

While a ``jax.profiler`` session runs, the engine's step phases appear
in its trace as ``engine.<phase>`` host spans carrying their stats,
nested in the caller's span, beside its programs, which are named
(``jit_engine_decode``, ``jit_engine_prefill``). On the CPU the host
runs the programs, so their operations stand in for the device's.
With no profiler running the tracers make no annotation at all.
"""

import gc
import glob
import weakref

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.obs.trace as trace_mod
from repro.configs import registry
from repro.launch.mesh import make_local_mesh
from repro.obs import NullTracer, Tracer
from repro.obs.prom import parse, render
from repro.serving import Engine, EngineConfig
from repro.serving import engine as engine_mod

PHASES = (
    "engine.admission",
    "engine.prefill",
    "engine.decode_prepare",
    "engine.decode_step",
    "engine.host_sync",
    "engine.finish",
)


def _engine(**kw):
    cfg = registry.get_smoke("qwen3-1.7b").replace(num_layers=2, vocab_size=128)
    return Engine(
        cfg, make_local_mesh(), engine_cfg=EngineConfig(max_slots=2, max_len=64, **kw)
    )


def _host_events(path):
    """(name, start, end, stats) of every host event in the trace."""
    pd = ProfileData.from_file(glob.glob(f"{path}/**/*.xplane.pb", recursive=True)[0])
    out = []
    for plane in pd.planes:
        if plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    stats = {str(k): v for k, v in ev.stats}
                    out.append((ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, stats))
    return out


@pytest.fixture(scope="module", params=[False, True], ids=["trace_off", "trace_on"])
def traced(request, tmp_path_factory):
    """Two engine steps under a profiler, each in a caller's ``step``
    span: the first admits a request (prefill, decode), the second
    decodes and finishes it. Then a collection."""
    eng = _engine(trace=request.param)
    rng = np.random.default_rng(0)
    eng.submit(rng.integers(1, 127, 12).astype(np.int32), 2)
    eng.drain(max_steps=10)  # compiles the prefill bucket outside the trace
    path = tmp_path_factory.mktemp("profile")
    eng.submit(rng.integers(1, 127, 12).astype(np.int32), 3)
    with jax.profiler.trace(str(path)):
        for i in range(2):
            with jax.profiler.TraceAnnotation("step", step=i):
                eng.step()
        gc.collect()
    return eng, _host_events(path)


def test_phases_nest_in_the_callers_span_with_their_stats(traced):
    _, events = traced
    steps = [(a, b) for n, a, b, _ in events if n == "step"]
    assert len(steps) == 2
    spans = [(n, a, b, st) for n, a, b, st in events if n in PHASES]
    assert {n for n, *_ in spans} == set(PHASES)
    for name, a, b, _ in spans:
        assert any(s0 <= a <= b <= s1 for s0, s1 in steps), name
    by = {n: st for n, _, _, st in spans}
    assert by["engine.prefill"] == {"s": 64, "n": 1}
    assert by["engine.admission"]["compiles"] == 0
    assert by["engine.decode_step"]["active"] == 1
    assert by["engine.host_sync"] == {"active": 1}
    assert by["engine.decode_prepare"] == {"active": 1}
    assert "finished" in by["engine.finish"]
    # host_sync sits inside decode_step; prepare and finish beside it
    d0, d1 = next((a, b) for n, a, b, _ in spans if n == "engine.decode_step")
    h0, h1 = next((a, b) for n, a, b, _ in spans if n == "engine.host_sync")
    assert d0 <= h0 <= h1 <= d1
    p1 = next(b for n, a, b, _ in spans if n == "engine.decode_prepare")
    f0 = next(a for n, a, b, _ in spans if n == "engine.finish")
    assert p1 <= d0 and d1 <= f0


def test_programs_are_named(traced):
    _, events = traced
    modules = {st.get("hlo_module") for *_, st in events}
    assert {"jit_engine_decode", "jit_engine_prefill"} <= modules
    assert not any(m and "lambda" in str(m) for m in modules)


def test_decode_runs_inside_decode_step_and_ends_before_host_sync(traced):
    _, events = traced
    runs = {}
    for _, a, b, st in events:
        if st.get("hlo_module") == "jit_engine_decode":
            r = runs.setdefault(st["run_id"], [a, b])
            r[0], r[1] = min(r[0], a), max(r[1], b)
    steps = [(a, b) for n, a, b, _ in events if n == "engine.decode_step"]
    syncs = [(a, b) for n, a, b, _ in events if n == "engine.host_sync"]
    assert len(runs) == len(steps) == 2
    for a, b in runs.values():
        s0, s1 = next(s for s in steps if s[0] <= a <= s[1])
        sync = next(s for s in syncs if s0 <= s[0] and s[1] <= s1)
        assert b <= sync[1]


def test_gc_is_a_span_and_a_counter(traced):
    eng, events = traced
    gcs = [st for n, _, _, st in events if n == "engine.gc"]
    assert gcs and all({"generation", "collected"} <= set(st) for st in gcs)
    assert eng.stats.gc_pause_s > 0
    prom = parse(render(eng.metrics))
    assert prom["repro_serve_gc_pause_seconds_total"] == pytest.approx(eng.stats.gc_pause_s)


def test_gc_hook_leaves_with_its_engine():
    eng = _engine()
    hooks = [cb for cb in gc.callbacks if isinstance(cb, engine_mod._GcWatch) and cb._engine() is eng]
    assert len(hooks) == 1
    ref = weakref.ref(eng)
    del eng
    gc.collect()
    assert ref() is None and hooks[0] not in gc.callbacks


@pytest.mark.parametrize("tracer_cls", [Tracer, NullTracer])
def test_no_annotation_without_a_profiler(monkeypatch, tmp_path, tracer_cls):
    made = []

    class Counting(trace_mod.TraceAnnotation):
        def __init__(self, name, **kw):
            made.append(name)
            super().__init__(name, **kw)

    monkeypatch.setattr(trace_mod, "TraceAnnotation", Counting)
    tr = tracer_cls()
    tk = tr.track("engine:decode")
    phase = tr.name("decode_step", ("active", "compiles"))
    ring_only = tr.name("decode")
    for _ in range(3):
        tr.begin(tk, phase)
        tr.begin(tk, ring_only)
        tr.end(tk, ring_only)
        tr.end(tk, phase, 4, 0)
    assert made == []
    with jax.profiler.trace(str(tmp_path)):
        tr.begin(tk, ring_only)
        tr.end(tk, ring_only)
        tr.begin(tk, phase)
        tr.end(tk, phase, 4, 0)
    assert made == ["engine.decode_step"]
    spans = [(n, st) for n, _, _, st in _host_events(tmp_path) if n.startswith("engine.")]
    assert spans == [("engine.decode_step", {"active": 4, "compiles": 0})]
