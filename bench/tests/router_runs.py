"""Runs of a router cell on a host with four forced CPU devices, for
``test_router.py``, which starts this module in a process of its own
(the device count is fixed when JAX starts):

    XLA_FLAGS=--xla_force_host_platform_device_count=4 JAX_PLATFORMS=cpu \\
        python -m bench.tests.router_runs OUT.json

The ``pixelfly-chat-x4`` cell at small widths, ``ReplicaRouter`` over
four one-device replicas, at a rate that keeps every replica serving:
a run untraced and one traced, runs with a fault planted in one
replica, and the router's greedy streams against one engine's on the
same weights. Writes one JSON object of readings to ``OUT.json``.
"""

from __future__ import annotations

import json
import sys
import tempfile
import time

import jax
import numpy as np

from bench import harness, session
from bench.tests import smoke
from bench.tests.test_faults import alter_tokens, freeze_state
from bench.weights import make_params

REPLICAS = 4
SEED = 2**31 + 4321


def cell():
    """The benchmark's ``pixelfly-chat-x4`` (its replicas and metric
    lists) under the cut-down chat mix, at a rate that keeps every
    replica serving."""
    c = smoke.cell("pixelfly-chat-x4", "chat")
    assert c.replicas == c.chips == REPLICAS
    return smoke.replace_mix(c, arrivals={"kind": "poisson", "rate_per_s": 24.0})


def other_weights(router):
    """The last replica serves weights of another seed."""
    eng = router.engines[-1]
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), eng.params)
    shardings = jax.tree.map(lambda a: a.sharding, eng.params)
    eng.params = make_params(shapes, SEED + 1, eng.cfg.vocab_size, shardings=shardings)


FAULTS = {
    "token_altered": lambda router: alter_tokens(router.engines[-1]),
    "state_unchanged": lambda router: freeze_state(router.engines[-1]),
    "other_weights": other_weights,
}


def one_run(c, trace=False, fault=None):
    result, extra = session.run(c, SEED, 3.0, trace, time.perf_counter(), test=smoke.options(c, fault=fault))
    win = extra["record"]["window"]
    parts = {}
    for s in win.steps:
        for r, p in s.parts.items():
            q = parts.setdefault(r, [0, 0])
            q[0] += p.prefill_tokens
            q[1] += len(p.decode_positions)
    return {
        "correct": result["correct"],
        "compiles": result["_compiles"],
        "metrics": {k: v["value"] for k, v in result["metrics"].items()},
        "gap": result["compared"]["served_logit_gap_max"]["value"],
        "limit": result["compared"]["served_logit_gap_max"]["limit"],
        "counters": extra["record"]["counters"],
        "sample_replicas": sorted({r.replica for r in extra["sample"]}),
        "served_replicas": sorted({r.replica for r in win.reqs if r.served}),
        "prefill_tokens": sum(s.prefill_tokens for s in win.steps),
        "decode_tokens": sum(len(s.decode_positions) for s in win.steps),
        "parts": {str(r): q for r, q in sorted(parts.items())},
        "device": result["device"],
    }


def streams():
    """Greedy streams of a router whose replicas load the seed's weights
    (``harness.load_weights``) and of one engine on the same weights."""
    from repro.launch.mesh import make_tp_mesh
    from repro.models import transformer as T

    c = cell()
    o = smoke.options(c)
    conf = {**c.config, **o.widths}
    cfg = c.arch.program_config(conf, smoke=o.program)
    shapes = jax.eval_shape(lambda k: T.init_model(k, cfg), jax.random.PRNGKey(0))
    rng = np.random.default_rng(3)
    prompts = [rng.integers(0, conf["vocab_size"], n, dtype=np.int32) for n in (40, 100, 17, 200, 64, 129, 90, 33)]
    outs = {}
    for kind in ("router", "engine"):
        if kind == "router":
            server = harness.build_router(cfg, jax.devices()[:REPLICAS], REPLICAS, c.mix, o.n_pages,
                                          paged_impl=o.paged_impl)
            harness.load_weights(server, shapes, SEED, conf["vocab_size"])
            # where each replica keeps its pool and weights once built
            outs["placement"] = [
                {"mesh": [d.id for d in e.mesh.devices.flat],
                 "pool": sorted({d.id for a in jax.tree.leaves(e.kv.buffers) for d in a.devices()}),
                 "params": sorted({d.id for a in jax.tree.leaves(e.params) for d in a.devices()})}
                for e in server.engines]
        else:
            params = make_params(shapes, SEED, conf["vocab_size"])
            server = harness.build_engine(cfg, make_tp_mesh(1), params, c.mix, o.n_pages, paged_impl=o.paged_impl)
        uids = [server.submit(p, 12) for p in prompts]
        done = {}
        while len(done) < len(uids):
            for f in server.step():
                done[f.uid] = [int(t) for t in f.tokens]
        outs[kind] = [done[u] for u in uids]
        if kind == "router":
            outs["served_by"] = [e.stats.generated for e in server.engines]
        del server
    return outs


def main(out: str) -> int:
    with tempfile.TemporaryDirectory() as cache:
        # the replicas' programs compile once across the runs below
        jax.config.update("jax_compilation_cache_dir", cache)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
        c = cell()
        got = {"devices": len(jax.devices())}
        got["untraced"] = one_run(c)
        got["traced"] = one_run(c, trace=True)
        got["faults"] = {name: one_run(c, fault=f) for name, f in FAULTS.items()}
        got["streams"] = streams()
    with open(out, "w") as f:
        json.dump(got, f)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
