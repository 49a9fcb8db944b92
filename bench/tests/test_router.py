"""A cell behind ``ReplicaRouter``: four one-device replicas on a host
with four forced CPU devices (``bench/tests/router_runs.py``, in a
process of its own), through set-up, the open-loop window and the check
against the reference, untraced and traced; with a fault planted in one
replica; and the router's streams against one engine's."""

import json
import os
import subprocess
import sys

import pytest

from bench import harness

ROOT = harness.ROOT
REPLICAS = 4


@pytest.fixture(scope="module")
def runs(tmp_path_factory):
    out = tmp_path_factory.mktemp("router") / "runs.json"
    env = {
        **os.environ,
        "JAX_PLATFORMS": "cpu",
        "XLA_FLAGS": f"--xla_force_host_platform_device_count={REPLICAS}",
        "PYTHONPATH": os.pathsep.join([str(ROOT), str(ROOT / "src")]),
    }
    p = subprocess.run([sys.executable, "-m", "bench.tests.router_runs", str(out)], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=1000)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(out.read_text())


def test_four_devices(runs):
    assert runs["devices"] == REPLICAS


@pytest.mark.parametrize("kind", ["untraced", "traced"])
def test_router_cell_is_correct_with_every_replica_serving(runs, kind):
    got = runs[kind]
    assert got["correct"] and got["gap"] <= got["limit"]
    assert got["compiles"] == 0  # every replica's programs were warmed in set-up
    c = got["counters"]
    assert len(c["replica_tokens"]) == REPLICAS and min(c["replica_tokens"]) > 0
    assert got["served_replicas"] == got["sample_replicas"] == list(range(REPLICAS))
    # each step's work, split by replica, adds up to the step's
    parts = got["parts"]
    assert sorted(parts) == [str(r) for r in range(REPLICAS)]
    assert sum(p[0] for p in parts.values()) == got["prefill_tokens"]
    assert sum(p[1] for p in parts.values()) == got["decode_tokens"]
    assert c["decode_tokens"] >= got["decode_tokens"] > 0
    # one replica's pool, as a one-chip cell's
    assert c["kv_pool_tokens"] > 0 and c["max_slots"] == 4


def test_router_end_to_end_metrics(runs):
    m = runs["untraced"]["metrics"]
    assert set(m) == {"ttft_p90_ms", "itl_p95_ms", "output_tokens_per_s", "setup_s"}
    assert all(v > 0 for v in m.values())


def test_router_traced_line(runs):
    """The traced line reads the spread over replicas from the
    replicas' own counters, the fleet's occupancy and one replica's
    pool, and the device's busy seconds averaged over the chips."""
    got = runs["traced"]
    m = got["metrics"]
    toks = got["counters"]["replica_tokens"]
    mean = sum(toks) / len(toks)
    assert m["replica_tokens_spread"] == pytest.approx(100 * (max(toks) - min(toks)) / mean)
    c = got["counters"]
    assert m["decode_batch_occupancy"] == pytest.approx(100 * c["decode_tokens"] / (c["decode_steps"] * 4))
    assert m["kv_pool_tokens"] == c["kv_pool_tokens"]
    assert 0 < got["device"]["busy_s"] <= got["device"]["window_s"]


@pytest.mark.parametrize("fault", ["token_altered", "state_unchanged", "other_weights"])
def test_fault_in_one_replica_is_not_correct(runs, fault):
    """A fault in the last replica alone: the sample holds a request of
    every replica that served, so the check sees it."""
    got = runs["faults"][fault]
    assert not got["correct"]
    assert got["gap"] > got["limit"]
    assert REPLICAS - 1 in got["sample_replicas"]


def test_router_streams_equal_one_engine_on_the_same_weights(runs):
    s = runs["streams"]
    assert s["router"] == s["engine"]
    # least-loaded routing put the requests on every replica
    assert all(n > 0 for n in s["served_by"])


def test_each_replica_keeps_its_pool_and_weights_on_its_own_device(runs):
    """The replicas take the devices with JAX's default device last
    (``harness.router_devices``), and once built each keeps its pool and
    the seed's weights on the device of its own mesh."""
    placed = runs["streams"]["placement"]
    assert [p["mesh"] for p in placed] == [[1], [2], [3], [0]]
    for p in placed:
        assert p["pool"] == p["params"] == p["mesh"]
