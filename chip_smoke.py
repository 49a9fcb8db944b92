#!/usr/bin/env python3
"""Smoke check that the serving engine runs on a TPU.

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # the four-chip serving paths only

One chip: the Pallas kernels of the main path against their portable
``gather`` references at qwen3-1.7b widths in bf16, then the pixelfly
sparse twin and the dense twin of qwen3-1.7b at full width (28 layers,
d_model 2048, random weights from a fixed seed), each built the way
``repro.launch.serve`` builds it (registry, local mesh, ``EngineConfig``,
``Engine``) and served 8 requests of 512 prompt tokens and 32 new tokens
through ``submit``/``drain``.

Four chips: the same greedy traffic through ``ReplicaRouter(replicas=4,
tp=1)``, whose streams must equal a one-device engine's, and through one
``tp=4`` engine, which must complete (its agreement with the one-device
streams is printed, not gated: bf16 all-reduces change summation order).

Lines before the last are smoke output, not benchmark numbers. The last
line is one JSON object naming the device. Without a TPU, or when any
check fails, the script exits non-zero and prints no result.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent
SEED = 0
SLOTS = 8
PROMPT_LEN = 512
GEN = 32
ARCH = "qwen3-1.7b"
ATOL_BF16 = 1e-2  # tests/test_kernels_paged.py's bf16 tolerance


class SmokeFailure(RuntimeError):
    """A check of the smoke run did not hold."""


def check(cond: bool, msg: str) -> None:
    if not cond:
        raise SmokeFailure(msg)


def log(msg: str) -> None:
    print(f"smoke: {msg}", flush=True)


class CompileClock:
    """Counts JAX's backend-compile events and their seconds. The event
    spans the persistent-cache lookup, so a program loaded from the
    cache counts too, with the seconds the load took."""

    def __init__(self):
        import jax

        self.n = 0
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event: str, duration: float, **_kw) -> None:
        if "backend_compile" in event:
            self.n += 1
            self.seconds += duration

    def mark(self) -> tuple[int, float]:
        return self.n, self.seconds


def make_requests(vocab: int, *, sampled: bool) -> list:
    """(prompt, SamplingParams or None) x SLOTS from ``SEED``: requests 0
    and 1 share one prompt and are greedy; with ``sampled`` request 2 is
    seeded-sampled."""
    import numpy as np

    from repro.serving import SamplingParams

    rng = np.random.default_rng(SEED)
    prompts = rng.integers(0, vocab, size=(SLOTS, PROMPT_LEN), dtype=np.int32)
    prompts[1] = prompts[0]
    reqs = [(p, None) for p in prompts]
    if sampled:
        reqs[2] = (
            prompts[2],
            SamplingParams(temperature=0.8, top_k=40, seed=SEED + 2),
        )
    return reqs


def serve(engine, reqs) -> tuple[list[list[int]], float]:
    """Submit ``reqs`` in order and drain; streams in submit order."""
    t0 = time.perf_counter()
    uids = [engine.submit(p, GEN, sampling=sp) for p, sp in reqs]
    fins = {f.uid: f for f in engine.drain()}
    wall = time.perf_counter() - t0
    check(set(fins) == set(uids), f"finished {sorted(fins)} != {uids}")
    return [fins[u].tokens.tolist() for u in uids], wall


def check_streams(streams: list[list[int]], vocab: int, what: str) -> None:
    for i, s in enumerate(streams):
        check(len(s) == GEN, f"{what}: request {i} gave {len(s)} tokens")
        check(
            all(0 <= t < vocab for t in s),
            f"{what}: request {i} has a token outside [0, {vocab})",
        )
    check(streams[0] == streams[1], f"{what}: identical greedy requests differ")


def step_compiles(engines) -> int:
    return sum(
        e.stats.summary()["dispatch_guard"]["step_compiles"] for e in engines
    )


def warm_then_serve(engine, reqs, clock, what: str, engines=None) -> dict:
    """Serve ``reqs`` twice: the first pass compiles whatever the engine
    did not warm at construction, the second must compile nothing and
    repeat the first pass's streams."""
    engines = engines or [engine]
    c0 = clock.mark()
    warm, warm_s = serve(engine, reqs)
    c1 = clock.mark()
    for e in engines:
        e.reset_stats()
    steady, steady_s = serve(engine, reqs)
    n = step_compiles(engines)
    check(n == 0, f"{what}: {n} compiles after warm-up")
    check(steady == warm, f"{what}: streams changed between passes")
    return {
        "streams": steady,
        "warm_pass_s": warm_s,
        "warm_pass_compiles": c1[0] - c0[0],
        "warm_pass_compile_s": c1[1] - c0[1],
        "steady_pass_s": steady_s,
    }


def peak_bytes(device) -> int | None:
    stats = device.memory_stats()
    return None if stats is None else stats.get("peak_bytes_in_use")


def tree_bytes(tree) -> int:
    import jax

    return sum(a.nbytes for a in jax.tree.leaves(tree))


# ----------------------------------------------------------------------
# Kernels
# ----------------------------------------------------------------------


def check_kernels(cfg) -> None:
    """Pallas paged decode and BSR matmul against the gather paths run on
    the same values upcast to float32, at ``cfg``'s widths in bf16."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core.pixelfly import init_linear
    from repro.kernels import ops
    from repro.models import layers as L

    rng = np.random.default_rng(SEED)
    hk, d = cfg.num_kv_heads, cfg.head_dim
    g = cfg.num_heads // hk
    page, b = cfg.attn_block, SLOTS
    pps = (PROMPT_LEN + GEN) // page + 1  # pages per slot
    n_pages = b * pps + 1

    def bf16(shape):
        return jnp.asarray(rng.standard_normal(shape), jnp.bfloat16)

    # pools stacked over two layers, as the layer scan passes them; the
    # reads below take layer 1
    k, v = bf16((2, n_pages, hk, page, d)), bf16((2, n_pages, hk, page, d))
    k, v = k.at[:, 0].set(1e4), v.at[:, 0].set(-1e4)  # poisoned trash page
    q = bf16((b, 1, hk, g, d))
    table = rng.permutation(np.arange(1, n_pages))[: b * pps].reshape(b, pps)
    table[1] = 0  # idle slot: all-trash row at position 0
    table[b - 1, 1:] = 0  # partially allocated row
    pos = rng.integers(0, pps * page, b)
    pos[1], pos[b - 1] = 0, int(rng.integers(0, page))
    table, pos = jnp.asarray(table, jnp.int32), jnp.asarray(pos, jnp.int32)
    f32 = lambda a: a.astype(jnp.float32)  # noqa: E731

    def compare(name, got, want):
        got = f32(got)
        err = float(jnp.max(jnp.abs(got - want)))
        log(f"kernel {name}: max |pallas - gather| = {err:.3e}")
        np.testing.assert_allclose(
            np.asarray(got), np.asarray(want), atol=ATOL_BF16, rtol=ATOL_BF16
        )

    kw = {"sm_scale": d**-0.5}
    sparse_kw = {
        **kw,
        "local_blocks": cfg.attn_local_blocks,
        "global_blocks": cfg.attn_global_blocks,
    }
    for name, fn, extra in (
        ("paged decode dense", L.paged_decode_attention_jnp, kw),
        ("paged decode sparse", L.paged_sparse_decode_attention_jnp,
         sparse_kw),
    ):
        compare(
            name,
            fn(q, k, v, 1, table, pos, impl="pallas", **extra),
            fn(f32(q), f32(k), f32(v), 1, table, pos, impl="gather", **extra),
        )

    spec = L.AttnSpec(cfg).wq
    check(spec.sparse, "BSR kernel check needs the sparse twin's config")
    params = init_linear(jax.random.PRNGKey(SEED), spec)
    cols = jnp.asarray(spec.pattern().cols)
    for rows in (SLOTS, SLOTS * PROMPT_LEN):  # decode and prefill batches
        x, blocks = bf16((rows, cfg.d_model)), params["blocks"]
        compare(
            f"bsr_matmul rows={rows}",
            ops.bsr_matmul(x, blocks, cols, impl="pallas"),
            ops.bsr_matmul(f32(x), f32(blocks), cols, impl="gather"),
        )


# ----------------------------------------------------------------------
# Phases
# ----------------------------------------------------------------------


def engine_config():
    from repro.serving import EngineConfig

    # the serve CLI's sizing: prompt + generation + 1, rounded up to pages
    return EngineConfig(max_slots=SLOTS, max_len=PROMPT_LEN + GEN + 1)


def twin_phase(cfg, mesh, clock) -> tuple:
    """Build one engine as ``launch/serve.py`` does and serve the mixed
    traffic twice. Returns (engine, report)."""
    from repro.serving import Engine

    c0 = clock.mark()
    t0 = time.perf_counter()
    engine = Engine(cfg, mesh, engine_cfg=engine_config(), seed=SEED)
    init_s = time.perf_counter() - t0
    c1 = clock.mark()
    reqs = make_requests(cfg.vocab_size, sampled=True)
    rep = warm_then_serve(engine, reqs, clock, cfg.name)
    check_streams(rep["streams"], cfg.vocab_size, cfg.name)
    rep.update(
        init_s=init_s,
        init_compiles=c1[0] - c0[0],
        init_compile_s=c1[1] - c0[1],
        param_bytes=tree_bytes(engine.params),
        pool_pages=engine.kv.n_pages,
        pool_bytes=tree_bytes(engine.kv.buffers),
    )
    return engine, rep


def report(name: str, rep: dict, device) -> None:
    keys = (
        "param_bytes", "pool_pages", "pool_bytes", "init_s", "init_compiles",
        "init_compile_s", "warm_pass_s", "warm_pass_compiles",
        "warm_pass_compile_s", "steady_pass_s",
    )
    fields = ", ".join(f"{k}={rep[k]}" for k in keys if k in rep)
    log(f"{name}: {fields}, peak_bytes_in_use={peak_bytes(device)}")


def one_chip(clock) -> None:
    import jax

    from repro.configs import registry
    from repro.launch.mesh import make_local_mesh

    dev = jax.devices()[0]
    sparse_cfg = registry.get(ARCH, sparse=True)
    t0 = time.perf_counter()
    check_kernels(sparse_cfg)
    log(
        f"kernels: wall_s={time.perf_counter() - t0}, "
        f"peak_bytes_in_use={peak_bytes(dev)}"
    )
    for name, cfg in (
        ("sparse twin", sparse_cfg),
        ("dense twin", registry.get(ARCH)),
    ):
        engine, rep = twin_phase(cfg, make_local_mesh(), clock)
        check(
            engine.paged_impl == "pallas",
            f"{name}: paged decode took {engine.paged_impl!r}, not the kernel",
        )
        report(name, rep, dev)
        del engine
        gc.collect()


def four_chips(clock) -> None:
    """The serving paths that exist only across chips, and the one-device
    engine they are compared with. Greedy traffic, and prefill groups
    of 2 everywhere, so the router's replicas and the one-device engine
    run programs of the same shapes."""
    import jax

    from repro.configs import registry
    from repro.launch.mesh import make_tp_mesh
    from repro.serving import Engine, EngineConfig
    from repro.serving.router import ReplicaRouter

    devs = jax.devices()
    check(len(devs) >= 4, f"--chips 4 needs 4 devices, have {len(devs)}")
    cfg = registry.get(ARCH, sparse=True)
    reqs = make_requests(cfg.vocab_size, sampled=False)
    ecfg = EngineConfig(
        max_slots=SLOTS, max_len=PROMPT_LEN + GEN + 1, max_prefill_batch=2
    )

    def build(what, fn):
        t0 = time.perf_counter()
        out = fn()
        log(f"{what}: init_s={time.perf_counter() - t0}")
        return out

    def engine(tp):
        mesh = make_tp_mesh(tp)
        return Engine(cfg, mesh, strategy="tp", engine_cfg=ecfg, seed=SEED)

    base_eng = build("one-device engine", lambda: engine(1))
    base = warm_then_serve(base_eng, reqs, clock, "one-device engine")
    check_streams(base["streams"], cfg.vocab_size, "one-device engine")
    report("one-device engine", base, devs[0])
    del base_eng
    gc.collect()

    router = build(
        "router",
        lambda: ReplicaRouter(
            cfg, replicas=4, tp=1, engine_cfg=ecfg, seed=SEED
        ),
    )
    rep = warm_then_serve(
        router, reqs, clock, "router", engines=router.engines
    )
    per = [e.stats.finished for e in router.engines]
    check(all(n > 0 for n in per), f"router left a replica idle: {per}")
    check(
        rep["streams"] == base["streams"],
        "4-replica router streams differ from the one-device engine",
    )
    report(f"router 4x1 (finished per replica {per})", rep, devs[0])
    del router
    gc.collect()

    tp_eng = build("tp=4 engine", lambda: engine(4))
    rep = warm_then_serve(tp_eng, reqs, clock, "tp=4 engine")
    check_streams(rep["streams"], cfg.vocab_size, "tp=4 engine")
    same = sum(
        a == b
        for s, t in zip(rep["streams"], base["streams"])
        for a, b in zip(s, t)
    )
    log(
        f"tp=4 engine: paged_impl={tp_eng.paged_impl}, tokens agreeing with "
        f"the one-device engine: {same}/{SLOTS * GEN} (not gated)"
    )
    report("tp=4 engine", rep, devs[0])


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument(
        "--chips", type=int, choices=(1, 4), default=1,
        help="4: run only the router and tp=4 paths (needs 4 chips)",
    )
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print(f"chip_smoke: no sources under {ROOT / 'src'}", file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
            file=sys.stderr,
        )
        return 1

    from repro.launch.compile_cache import use_compile_cache

    log(f"compile cache: {use_compile_cache()}")
    log(f"device_kind={dev.device_kind}, devices={len(jax.devices())}")
    clock = CompileClock()
    t0 = time.perf_counter()
    if args.chips == 4:
        four_chips(clock)
    else:
        one_chip(clock)
    log(
        f"total wall_s={time.perf_counter() - t0}, compiles={clock.n}, "
        f"compile_s={clock.seconds}"
    )
    result = {
        "ok": True,
        "device": {
            "platform": dev.platform,
            "kind": dev.device_kind,
            "count": len(jax.devices()),
        },
    }
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
