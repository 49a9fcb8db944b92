"""``BENCHMARK.json`` and every file it names: names, units and keys as
the benchmark's contract has them, every per-layer metric's cells
reporting the end-to-end metric it moves, and a new configuration,
traffic mix, per-layer metric, architecture and arrival kind added as
new files and entries alone."""

import json
import re
import shutil

import numpy as np
import pytest

from bench import harness
from bench.traffic import generator

ROOT = harness.ROOT
BENCH = harness.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
WIDTH = re.compile(r"(_dim|_rank)$|^hidden_size$|intermediate|latent|state_size|proj|head_dim|expan|experts_per_tok")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}


def _line(s):
    return isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s and "\t" not in s


def test_top_level_keys():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16
    for p in BENCH["paths"]:
        assert PATH.match(p) and not p.startswith("/") and ".." not in p.split("/")
        assert (ROOT / p).is_dir()
    cmd = BENCH["command"]
    assert 1 <= len(cmd) <= 32 and all(_line(w) for w in cmd)
    for w in cmd[1:]:
        if "/" in w:
            assert any(w.startswith(p.rstrip("/") + "/") for p in BENCH["paths"])
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    # a full check at 24 cells fits its time
    rs = BENCH["run_seconds"]
    assert (2 + 14 * 24) * (rs + 60) + 24 * 2 * 90 + 1200 <= 43200
    assert len(json.dumps(BENCH)) <= 64 * 1024


def test_names_units_and_entry_keys():
    seen = set()
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert NAME.match(c["name"]) and _line(c["source"]) and _line(c["why"])
        assert len(c["reduced"]) <= 16 and all(NAME.match(k) and not WIDTH.search(k) for k in c["reduced"])
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert NAME.match(w["name"]) and NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert w["chips"] in (1, 4) and _line(w["why"])
    assert len({(w["config"], w["traffic"]) for w in BENCH["workloads"]}) == len(BENCH["workloads"])
    four = sum(w["chips"] == 4 for w in BENCH["workloads"])
    assert four <= max(1, len(BENCH["workloads"]) // 2)
    for m in BENCH["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25
    for m in BENCH["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}
        assert m["source"] in SOURCES and _line(m["layer"])
    for m in BENCH["end_to_end"] + BENCH["per_layer"]:
        assert NAME.match(m["name"]) and UNIT.match(m["unit"]) and m["better"] in ("lower", "higher")
        assert m["name"] not in seen
        seen.add(m["name"])
    for group in ("configs", "workloads"):
        names = [x["name"] for x in BENCH[group]]
        assert len(names) == len(set(names))


def test_every_named_file_loads():
    for c in BENCH["configs"]:
        assert c["file"].startswith("bench/") and PATH.match(c["file"])
        conf = json.loads((ROOT / c["file"]).read_text())
        assert conf["name"] == c["name"] and conf["source"] == c["source"]
        assert conf["reduced"] == c["reduced"]
        arch = harness.arch_module(conf)
        assert all(callable(getattr(arch, f)) for f in ("program_config", "reference", "work"))
    for w in BENCH["workloads"]:
        cell = harness.resolve_cell(BENCH, w["name"])
        assert cell.cell["served_logit_gap_limit"] > 0
        s = generator.schedule(cell.mix, 1, BENCH["run_seconds"], 1000)
        assert len(s) > 0
    for m in BENCH["per_layer"]:
        assert callable(harness.metric_reader(m["name"]))


def test_every_cell_reports_what_its_metrics_move():
    e2e = {m["name"]: m for m in BENCH["end_to_end"]}
    assert "setup_s" in e2e and "workloads" not in e2e["setup_s"]
    cells = [w["name"] for w in BENCH["workloads"]]

    def reports(metric, cell):
        return "workloads" not in metric or cell in metric["workloads"]

    for cell in cells:
        assert sum(reports(m, cell) for m in BENCH["end_to_end"]) >= 2
        assert any(reports(m, cell) for m in BENCH["per_layer"])
    for m in BENCH["per_layer"]:
        assert m["moves"] in e2e
        for cell in m.get("workloads", cells):
            assert cell in cells and reports(e2e[m["moves"]], cell), (m["name"], cell)
    layers = {m["layer"] for m in BENCH["per_layer"]}
    for m in BENCH["per_layer"]:
        if "roofline" in m["name"] or "mfu" in m["name"]:
            assert m["unit"] == "%"
    assert layers


def test_a_cell_is_added_by_files_and_entries_alone(tmp_path):
    """A new configuration, traffic mix, cell and per-layer metric: new
    files and new entries in a copy of the benchmark, no file edited."""
    for d in ("configs", "traffic", "cells", "metrics", "arch"):
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d)
    bench = json.loads(json.dumps(BENCH))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    conf = json.loads((ROOT / "bench/configs/qwen3-1.7b-dense.json").read_text())
    conf["name"] = "qwen3-1.7b-dense-copy"
    (tmp_path / "bench/configs/qwen3-1.7b-dense-copy.json").write_text(json.dumps(conf))
    mix = json.loads((ROOT / "bench/traffic" / f"{BENCH['workloads'][0]['traffic']}.json").read_text())
    mix["arrivals"] = {"kind": "poisson", "rate_per_s": 1.5}
    (tmp_path / "bench/traffic/slow-chat.json").write_text(json.dumps(mix))
    (tmp_path / "bench/cells/copy-slow-chat.json").write_text(json.dumps({"served_logit_gap_limit": 0.1}))
    (tmp_path / "bench/metrics/requests_due.py").write_text(
        "def read(run, trace):\n    return len(run['window'].reqs)\n"
    )
    bench["configs"].append({"name": conf["name"], "source": conf["source"],
                             "file": "bench/configs/qwen3-1.7b-dense-copy.json", "reduced": [], "why": "a copy"})
    bench["workloads"].append({"name": "copy-slow-chat", "config": conf["name"], "traffic": "slow-chat",
                               "chips": 1, "why": "a slower chat"})
    bench["per_layer"].append({"name": "requests_due", "unit": "requests", "better": "higher",
                               "source": "host_clock", "layer": "load generator", "moves": "output_tokens_per_s",
                               "workloads": ["copy-slow-chat"]})
    cell = harness.resolve_cell(bench, "copy-slow-chat", root=tmp_path)
    assert cell.config["name"] == conf["name"] and cell.mix.arrivals["rate_per_s"] == 1.5
    assert [m["name"] for m in cell.per_layer][-1] == "requests_due"
    s = generator.schedule(cell.mix, 5, 10.0, 100)
    assert len(s) == 15

    class W:
        reqs = list(range(len(s)))

    assert harness.metric_reader("requests_due", tmp_path)({"window": W}, None) == 15
    after = {p: p.read_bytes() for p in before}
    assert after == before
    assert np.all(np.diff(s.due_s) >= 0)


TOY_ARCH = """
\"\"\"A toy architecture for the tests: the token embedding, two residual
SwiGLU layers, logits against the embedding.\"\"\"

import jax
import jax.numpy as jnp
import numpy as np


def program_config(conf, *, smoke=None):
    from repro.configs import registry

    return registry.get_smoke("qwen3-1.7b").replace(
        num_layers=2, vocab_size=conf["vocab_size"], dtype="float32", **(smoke or {}))


class Reference:
    def __init__(self, weights, vocab):
        self.weights, self.vocab = weights, vocab

    def logits(self, tokens, read):
        return self.logits_many([tokens], [read])[0]

    def logits_many(self, seqs, reads):
        emb = self.weights.top()["embed/tok"][: self.vocab]
        layers = [self.weights.layer(i) for i in range(2)]
        out = []
        for tokens, read in zip(seqs, reads):
            x = emb[jnp.asarray(tokens)]
            for w in layers:
                x = x + (jax.nn.silu(x @ w["mlp/wg/w"]) * (x @ w["mlp/wu/w"])) @ w["mlp/wd/w"]
            out.append(np.asarray(x[jnp.asarray(read)] @ emb.T))
        return out


def reference(conf, weights, max_len, *, precision="f32"):
    return Reference(weights, conf["vocab_size"])


class Work:
    def __init__(self, conf):
        self.per_token = 2 * 3 * 2 * conf["hidden_size"] * conf["intermediate_size"]

    def prefill_flops(self, n):
        return self.per_token * n

    def decode_flops(self, p):
        return self.per_token


def work(conf, max_len):
    return Work(conf)
"""

BURST_ARRIVALS = """
\"\"\"Bursts of ``size`` requests every ``every_s`` seconds.\"\"\"

import numpy as np

from bench.traffic.generator import SCHEDULE_SEED, Schedule, draw_prompts, lengths, seed_rng


def schedule(mix, seed, seconds, vocab):
    a = mix.arrivals
    starts = np.arange(0.0, seconds, a["every_s"])
    due = np.repeat(starts, a["size"])
    plen = lengths(mix.prompt_len, due.size, seed_rng(SCHEDULE_SEED, 1))
    olen = lengths(mix.output_len, due.size, seed_rng(SCHEDULE_SEED, 2))
    return Schedule(due, plen, olen, draw_prompts(plen, seed, vocab))
"""


def test_an_architecture_and_an_arrival_kind_are_added_by_files_alone(tmp_path):
    """A configuration whose architecture is a module of its own and a mix
    whose arrival kind is a module of its own: new files and entries in a
    copy of the benchmark resolve to a cell whose schedule, program
    configuration, reference and work counts the harness builds, with no
    file edited."""
    import jax

    from bench.weights import LayerWeights

    for d in ("configs", "traffic", "cells", "metrics", "arch"):
        shutil.copytree(ROOT / "bench" / d, tmp_path / "bench" / d,
                        ignore=shutil.ignore_patterns("__pycache__"))
    bench = json.loads(json.dumps(BENCH))
    before = {p: p.read_bytes() for p in (tmp_path / "bench").rglob("*") if p.is_file()}
    conf = {"name": "toy-2l", "source": "https://example.org/toy-2l", "architectures": ["ToyTwoLayerLM"],
            "hidden_size": 256, "intermediate_size": 512, "vocab_size": 96, "reduced": []}
    (tmp_path / "bench/configs/toy-2l.json").write_text(json.dumps(conf))
    (tmp_path / "bench/arch/toytwolayerlm.py").write_text(TOY_ARCH)
    (tmp_path / "bench/traffic/arrivals/burst.py").write_text(BURST_ARRIVALS)
    mix = json.loads((ROOT / "bench/traffic" / f"{BENCH['workloads'][0]['traffic']}.json").read_text())
    mix["arrivals"] = {"kind": "burst", "every_s": 2.5, "size": 3}
    (tmp_path / "bench/traffic/chat-bursts.json").write_text(json.dumps(mix))
    (tmp_path / "bench/cells/toy-bursts.json").write_text(json.dumps({"served_logit_gap_limit": 0.1}))
    bench["configs"].append({"name": "toy-2l", "source": conf["source"], "file": "bench/configs/toy-2l.json",
                             "reduced": [], "why": "a toy"})
    bench["workloads"].append({"name": "toy-bursts", "config": "toy-2l", "traffic": "chat-bursts",
                               "chips": 1, "why": "bursty chat on a toy"})
    cell = harness.resolve_cell(bench, "toy-bursts", root=tmp_path)
    assert cell.arch.__file__ == str(tmp_path / "bench/arch/toytwolayerlm.py")

    s = generator.schedule(cell.mix, 5, 10.0, conf["vocab_size"])
    np.testing.assert_array_equal(s.due_s, np.repeat([0.0, 2.5, 5.0, 7.5], 3))
    assert all(int(p.max()) < conf["vocab_size"] for p in s.prompts)

    from repro.models import transformer as T

    cfg = cell.arch.program_config(cell.config)
    shapes = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.PRNGKey(0))
    ref = cell.arch.reference(cell.config, LayerWeights(shapes, 5, conf["vocab_size"]), 320)
    lg = ref.logits(s.prompts[0], np.arange(s.prompts[0].size))
    assert lg.shape == (s.prompts[0].size, conf["vocab_size"]) and np.isfinite(lg).all()

    # the metrics read the counts the module defines, and nothing for one
    # it lacks
    work = cell.arch.work(cell.config, 320)
    steps = [harness.Step(0.0, 0.5, prefills=[100], prefill_tokens=100), harness.Step(0.5, 1.0, decode_positions=[100])]
    win = harness.Window(0.0, 2.0, [], steps, 0)

    class Peaks:
        flops, hbm_bytes = 1e9, 1e9

    class Trace:
        steps = {1: type("S", (), {"paged_ms": 1.0, "bsr_decode_ms": 1.0})()}

    run = {"window": win, "work": work, "peaks": Peaks, "chips": 1}
    assert harness.metric_reader("mfu", tmp_path)(run, None) == pytest.approx(100 * work.per_token * 101 / 1e9)
    assert harness.metric_reader("paged_decode_roofline", tmp_path)(run, Trace) is None
    assert harness.metric_reader("bsr_matmul_roofline", tmp_path)(run, Trace) is None
    after = {p: p.read_bytes() for p in before}
    assert after == before
