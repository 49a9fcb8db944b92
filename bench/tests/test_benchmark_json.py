"""``BENCHMARK.json`` and every file it names: names, units and keys as
the benchmark's contract has them, every per-layer metric's cells
reporting the end-to-end metric it moves, and a new configuration,
traffic mix and per-layer metric added as new files and entries alone."""

import json
import re
import shutil

import numpy as np

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
        harness.model_spec(conf)
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
    for d in ("configs", "traffic", "cells", "metrics"):
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
