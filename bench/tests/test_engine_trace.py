"""The engine's side of a profiler trace (``bench.trace.engine``): its
spans, its named programs and the scopes of the decode program's
operations, read into the three engine-side readings. Checked on a
small synthetic trace with known values, on two recorded v5e traces
(one from before the spans and scopes, which reads as None, and one of
``pixelfly-chat`` with them), and on a CPU trace of a small engine."""

import gzip
import shutil

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench.trace import engine as E
from bench.trace import reduce as R

MS = 1_000_000  # ns

# (start ms, end ms, name, stats) on the host's python thread
HOST = [
    (0, 100, "step", {"step": 0}),
    (1, 40, "engine.admission", {"admitted": 2, "compiles": 0}),
    (2, 30, "engine.prefill", {"s": 256, "n": 2}),
    (41, 45, "engine.decode_prepare", {"active": 2}),
    (46, 95, "engine.decode_step", {"active": 2, "compiles": 0}),
    (50, 95, "engine.host_sync", {"active": 2}),
    (96, 99, "engine.finish", {"finished": 0}),
    (100, 200, "step", {"step": 1}),
    (100.5, 100.9, "engine.admission", {"admitted": 1, "compiles": 0}),
    (100.6, 100.8, "engine.prefill", {"s": 128, "n": 1}),
    (101, 110, "engine.decode_prepare", {"active": 3}),
    (111, 195, "engine.decode_step", {"active": 3, "compiles": 0}),
    (112, 195, "engine.host_sync", {"active": 3}),
    (196, 199, "engine.finish", {"finished": 1}),
]
SCAN = "jit(engine_decode)/layer_scan/while/body/"
# program executions (start, end, module, program id), then operations
# (start, end, HLO text, tf_op)
RUNS = [
    (5, 28, "jit_engine_prefill", 9),
    (47, 94, "jit_engine_decode", 7),
    (112, 190, "jit_engine_decode", 7),
]
OPS = [
    (5, 28, "%fusion.1 = bf16[2,256,2048] fusion(x)", "jit(engine_prefill)/layer_scan/while/body/mlp/dot:"),
    (47, 94, "%while.3 = (s32[]) while(t)", "jit(engine_decode)/layer_scan/while:"),
    (47, 60, "%constant_dynamic-slice_fusion.1 = bf16[2] fusion(p)", SCAN + "dynamic_slice:"),
    (60, 62, "%scatter.2 = bf16[2] scatter(p)", SCAN + "closed_call/kv_write/scatter:"),
    (62, 80, "%paged_decode_attention_pallas.4 = bf16[2] custom-call(q)",
     SCAN + "closed_call/paged_attention/jit(paged_decode_attention_pallas)/pallas_call:"),
    (80, 88, "%copy.5 = bf16[28,8] copy(p)", None),
    (90, 94, "%fusion.6 = bf16[2] fusion(o)", SCAN + "closed_call/attn_proj/dot_general:"),
    (112, 150, "%constant_dynamic-slice_fusion.1 = bf16[2] fusion(p)", SCAN + "dynamic_slice:"),
    (152, 190, "%paged_decode_attention_pallas.4 = bf16[2] custom-call(q)",
     SCAN + "closed_call/paged_attention/jit(paged_decode_attention_pallas)/pallas_call:"),
]


def _xspace(host, runs, ops) -> bytes:
    """A serialized XSpace of one host thread and one TPU's program and
    operation lines, with each operation's metadata as a TPU trace
    carries it (``tf_op`` and ``program_id`` stats)."""
    stat_ids = {}

    def sid(name):
        return stat_ids.setdefault(name, len(stat_ids) + 1)

    def stat_meta():
        return "".join(f'stat_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n'
                       for n, i in stat_ids.items())

    def event(mid, a, b, stats=""):
        return f"events {{ metadata_id: {mid} offset_ps: {int(a * MS * 1000)} duration_ps: {int((b - a) * MS * 1000)} {stats} }}\n"

    host_meta, host_events = {}, ""
    for a, b, name, stats in host:
        mid = host_meta.setdefault(name, len(host_meta) + 1)
        st = "".join(f"stats {{ metadata_id: {sid(k)} int64_value: {v} }} " for k, v in stats.items())
        host_events += event(mid, a, b, st)
    host_plane = (
        'planes { id: 1 name: "/host:CPU" lines { id: 1 name: "python" timestamp_ns: 0\n'
        + host_events + "}\n"
        + "".join(f'event_metadata {{ key: {i} value {{ id: {i} name: "{n}" }} }}\n' for n, i in host_meta.items())
        + stat_meta() + "}\n"
    )
    stat_ids.clear()
    dev_meta, mod_events, op_events = [], "", ""
    for a, b, module, pid in runs:
        dev_meta.append(f'{module}({pid})')
        mod_events += event(len(dev_meta), a, b)
    for a, b, text, tf_op in ops:
        pid = next(p for s, e, _, p in runs if s <= a <= e)
        st = f"stats {{ metadata_id: {sid('program_id')} uint64_value: {pid} }} "
        if tf_op:
            st += f'stats {{ metadata_id: {sid("tf_op")} str_value: "{tf_op}" }} '
        dev_meta.append((text, st))
        op_events += event(len(dev_meta), a, b)
    metas = ""
    for i, m in enumerate(dev_meta, 1):
        name, st = (m, "") if isinstance(m, str) else m
        metas += f'event_metadata {{ key: {i} value {{ id: {i} name: "{name}" {st} }} }}\n'
    dev_plane = (
        'planes { id: 2 name: "/device:TPU:0"\n'
        f'lines {{ id: 1 name: "XLA Modules" timestamp_ns: 0\n{mod_events}}}\n'
        f'lines {{ id: 2 name: "XLA Ops" timestamp_ns: 0\n{op_events}}}\n'
        + metas + stat_meta() + "}\n"
    )
    return ProfileData.text_proto_to_serialized_xspace(host_plane + dev_plane)


def _read(tmp_path, host=HOST, runs=RUNS, ops=OPS) -> E.EngineTrace:
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_xspace(host, runs, ops))
    return E.read_dir(str(tmp_path))


def test_scope_of():
    assert E.scope_of(SCAN + "closed_call/attn_proj/jit(bsr_matmul_pallas)/pallas_call:") == "attn_proj"
    assert E.scope_of(SCAN + "dynamic_update_slice:") == "layer_scan"
    assert E.scope_of("jit(engine_decode)/lm_head/dot_general:") == "lm_head"
    assert E.scope_of("jit(<lambda>)/while/body/dynamic_slice:") is None
    assert E.scope_of(None) is None


def test_synthetic_trace_reads_known_values(tmp_path):
    et = _read(tmp_path)
    assert [s.name for s in et.spans][:3] == ["engine.admission", "engine.prefill", "engine.decode_prepare"]
    assert et.spans[1].stats == {"s": 256, "n": 2}
    assert [et.runs[i].module for i in et.decode_runs()] == ["jit_engine_decode"] * 2
    # prefill holds: 70 ms (two requests), 99.2 ms (one)
    assert E.first_token_hold_p90_ms(et) == pytest.approx(np.percentile([70, 70, 99.2], 90))
    # idle 28-47, 94-112 and 150-152 ms (none inside the while loop,
    # which holds its body), split where the host moved between spans
    by = {"engine.admission": 10.2, "engine.prefill": 2.2, "engine.decode_prepare": 13.0,
          "engine.decode_step": 2.0, "engine.host_sync": 3.0, "engine.finish": 3.0, "harness": 5.6}
    assert E.idle_ms_by_phase(et) == pytest.approx({k: v / 2 for k, v in by.items()})
    assert E.engine_host_ms_per_step(et) == pytest.approx(15.2)
    assert et.clock_offset == 0.0
    # per decode step; the while loop holding the body is left out
    assert E.decode_ms_by_scope(et) == pytest.approx(
        {"layer_scan": 25.5, "paged_attention": 28.0, "unscoped": 4.0, "attn_proj": 2.0, "kv_write": 1.0}
    )
    assert E.decode_kv_pool_ms(et) == pytest.approx(26.5)
    assert E.decode_nesting(et) == {"runs": 2, "start_in_decode_step": 2, "end_before_host_sync_end": 2}
    s = E.summary(et)
    assert s["unscoped_ms_by_kind"] == pytest.approx({"copy": 4.0})
    assert s["decode_steps"] == 2


def _shifted(runs, ops, dt):
    return ([(a + dt, b + dt, m, p) for a, b, m, p in runs],
            [(a + dt, b + dt, t, s) for a, b, t, s in ops])


def test_device_clock_put_on_the_hosts(tmp_path):
    """Device events recorded 1.5 ms early (programs starting before
    the span that dispatched them) are moved by the least shift that
    makes the trace causal; one that no shift can is split by nothing."""
    et = _read(tmp_path, HOST, *_shifted(RUNS, OPS, -1.5))
    assert et.clock_offset == pytest.approx(0.5 * MS)
    assert E.decode_nesting(et) == {"runs": 2, "start_in_decode_step": 0, "end_before_host_sync_end": 2}
    assert sum(E.idle_ms_by_phase(et).values()) == pytest.approx(39 / 2)
    assert E.decode_kv_pool_ms(et) == pytest.approx(26.5)
    # the second decode program outlasts its span: no shift fits
    runs = RUNS[:2] + [(105, 200, "jit_engine_decode", 7)]
    d = tmp_path / "late"
    d.mkdir()
    et = _read(d, HOST, runs, OPS)
    assert et.clock_offset is None
    assert E.idle_ms_by_phase(et) == {} and E.engine_host_ms_per_step(et) is None
    assert E.decode_kv_pool_ms(et) == pytest.approx(26.5)
    assert E.first_token_hold_p90_ms(et) is not None


STEPS_ONLY = [h for h in HOST if h[2] == "step"]
LAMBDAS = [(a, b, "jit__lambda", p) for a, b, _, p in RUNS]
BARE = [(a, b, t, None) for a, b, t, _ in OPS]


@pytest.mark.parametrize("host, runs, ops, reads", [
    # the parent's trace: the harness's spans, unnamed programs, no scopes
    (STEPS_ONLY, LAMBDAS, BARE, (False, False, False)),
    (STEPS_ONLY, RUNS, OPS, (False, False, True)),
    (HOST, LAMBDAS, OPS, (True, False, False)),
    (HOST, RUNS, BARE, (True, True, False)),
], ids=["parent", "no_spans", "no_names", "no_scopes"])
def test_readers_return_none_without_what_they_read(tmp_path, host, runs, ops, reads):
    et = _read(tmp_path, host, runs, ops)
    got = (E.first_token_hold_p90_ms(et), E.engine_host_ms_per_step(et), E.decode_kv_pool_ms(et))
    assert tuple(v is not None for v in got) == reads


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    path = tmp_path_factory.mktemp("trace") / "slice.xplane.pb"
    with gzip.open(R.__file__.rsplit("/", 1)[0] + "/testdata/pixelfly_decode_steps.xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_recorded_v5e_trace_metadata_and_no_engine_readings(recorded):
    scopes = E._op_scopes(open(recorded, "rb").read())
    kernel = [v for (_, n), v in scopes.items() if n.startswith("%paged_decode_attention_pallas")]
    assert kernel == ["jit(<lambda>)/while/body/closed_call/jit(paged_decode_attention_pallas)/pallas_call:"]
    et = E.read_file(recorded)
    red = R.reduce_file(recorded)
    assert len(et.ops) == len(red.ops)
    assert sum(b - a for a, b in et.busy) / 1e9 == pytest.approx(red.busy_s)
    assert not et.spans and not et.decode_runs()
    assert E.first_token_hold_p90_ms(et) is None
    assert E.engine_host_ms_per_step(et) is None
    assert E.decode_kv_pool_ms(et) is None


@pytest.fixture(scope="module")
def chat_trace(tmp_path_factory):
    """A traced window of ``pixelfly-chat`` on one v5e with this
    program: 20 decode steps, two prefill groups, every engine span."""
    path = tmp_path_factory.mktemp("chat") / "chat.xplane.pb"
    with gzip.open(R.__file__.rsplit("/", 1)[0] + "/testdata/pixelfly_chat_engine_spans.xplane.pb.gz", "rb") as src, \
            open(path, "wb") as dst:
        shutil.copyfileobj(src, dst)
    return str(path)


def test_v5e_chat_trace(chat_trace):
    et = E.read_file(chat_trace)
    assert len(et.decode_runs()) == 20
    assert {et.runs[i].module for i in et.decode_runs()} == {"jit_engine_decode"}
    # this session put the device's events about 1.1 ms early: every
    # decode program starts before its span as recorded
    assert 0.5 * MS < et.clock_offset < 2 * MS
    assert E.decode_nesting(et) == {"runs": 20, "start_in_decode_step": 0, "end_before_host_sync_end": 20}
    by = E.decode_ms_by_scope(et)
    assert sum(by.values()) == pytest.approx(130.4, abs=0.5)
    assert set(by) == {"layer_scan", "kv_write", "paged_attention", "mlp", "lm_head", "attn_proj", "norm", "unscoped"}
    # what carries no scope: the two whole-pool layout copies after the loop
    assert E.summary(et)["unscoped_ms_by_kind"]["copy"] == pytest.approx(by["unscoped"], rel=0.01)
    assert E.decode_kv_pool_ms(et) == pytest.approx(by["layer_scan"] + by["kv_write"])
    assert 80 < E.decode_kv_pool_ms(et) < 95
    assert 100 < E.first_token_hold_p90_ms(et) < 200
    assert 0 < E.engine_host_ms_per_step(et) < 5
    # the harness's reduction reads the same trace as before the spans
    red = R.reduce_file(chat_trace)
    assert {"paged_decode", "bsr_matmul"} <= {op.kernel for op in red.ops}
    assert sum(b - a for a, b in et.busy) / 1e9 == pytest.approx(red.busy_s)


def test_cpu_trace_of_a_small_engine(tmp_path):
    """The program's spans and programs read back: on the CPU the host
    runs the programs and their operations carry no scopes."""
    from repro.configs import registry
    from repro.launch.mesh import make_local_mesh
    from repro.serving import Engine, EngineConfig

    cfg = registry.get_smoke("qwen3-1.7b").replace(num_layers=2, vocab_size=128)
    eng = Engine(cfg, make_local_mesh(), engine_cfg=EngineConfig(max_slots=2, max_len=64))
    rng = np.random.default_rng(1)
    eng.submit(rng.integers(1, 127, 12).astype(np.int32), 2)
    eng.drain(max_steps=10)
    eng.submit(rng.integers(1, 127, 12).astype(np.int32), 3)
    with jax.profiler.trace(str(tmp_path)):
        for i in range(2):
            with jax.profiler.TraceAnnotation("step", step=i):
                eng.step()
    et = E.read_dir(str(tmp_path))
    assert E.decode_nesting(et) == {"runs": 2, "start_in_decode_step": 2, "end_before_host_sync_end": 2}
    assert E.first_token_hold_p90_ms(et) > 0
    assert E.engine_host_ms_per_step(et) is not None
    assert E.decode_kv_pool_ms(et) is None
