import os

os.environ["XLA_FLAGS"] = (
    "--xla_force_host_platform_device_count=512 "
    + os.environ.get("XLA_FLAGS", "")
).strip()

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this:
  1. builds the production mesh (16x16 single pod / 2x16x16 multi-pod),
  2. lowers the right step function (train_step / prefill_step /
     serve_step) with full in/out shardings over ShapeDtypeStructs,
  3. compiles it, prints ``memory_analysis()`` (fits-per-device proof) and
     ``cost_analysis()``,
  4. runs the HLO roofline walker (repro.analysis.roofline) and emits the
     three roofline terms + MODEL_FLOPS ratio as JSON.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-1.7b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all --multi-pod --json out.json
"""

import argparse
import json
import sys
import time
import traceback

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.analysis import roofline
from repro.configs import registry
from repro.configs.base import SHAPES, ModelConfig, ShapeSpec
from repro.distributed import sharding
from repro.launch import specs as specs_lib
from repro.launch.mesh import make_production_mesh
from repro.models import transformer as T
from repro.training.loop import make_train_step
from repro.training.optimizer import OptConfig


def _prep_cfg(cfg: ModelConfig, mesh, st: sharding.Strategy) -> ModelConfig:
    """Launcher-side knobs that depend on the mesh + strategy."""
    dp = mesh.size // (mesh.shape.get("model", 1) if st.kind == "tp" else 1)
    cfg = cfg.replace(tp_size=st.tp_size, batch_axes=st.batch)
    if cfg.moe_num_experts:
        cfg = cfg.replace(moe_routing_groups=dp)
    return cfg


def pick_microbatches(
    cfg: ModelConfig,
    shape: ShapeSpec,
    mesh,
    st: sharding.Strategy,
    *,
    target_tokens_per_device: int | None = None,
) -> int:
    """Gradient-accumulation factor: bound live activations to
    ~target tokens/device/microbatch. k must divide the per-data-shard
    batch so every microbatch stays evenly sharded."""
    if shape.kind != "train":
        return 1
    if target_tokens_per_device is None:
        target_tokens_per_device = int(
            os.environ.get("REPRO_MB_TARGET_TOKENS", 4096)
        )
    dp = 1
    for a in st.batch:
        dp *= mesh.shape[a]
    dp = min(dp, shape.global_batch)
    per_dev = shape.global_batch * shape.seq_len // dp
    want = max(1, -(-per_dev // target_tokens_per_device))
    per_shard_batch = max(1, shape.global_batch // dp)
    k = 1
    for d in range(1, per_shard_batch + 1):
        if per_shard_batch % d == 0 and d <= want:
            k = d
    return k


def lower_cell(
    cfg: ModelConfig,
    shape: ShapeSpec,
    mesh,
    *,
    strategy: str = "tp",
    opt_cfg: OptConfig = OptConfig(),
):
    """Returns the lowered computation for one cell."""
    st = sharding.Strategy(mesh, strategy)
    cfg = _prep_cfg(cfg, mesh, st)
    batch_in = specs_lib.input_specs(cfg, shape)

    with mesh:
        if shape.kind == "train":
            state = specs_lib.state_specs(cfg, opt_cfg)
            state_sh = {
                "params": sharding.param_shardings(st, state["params"]),
                "opt": {
                    "step": NamedSharding(mesh, P()),
                    "mu": sharding.param_shardings(st, state["opt"]["mu"]),
                    "nu": sharding.param_shardings(st, state["opt"]["nu"]),
                },
            }
            batch_sh = sharding.named(st, sharding.batch_specs(st, batch_in))
            k = pick_microbatches(cfg, shape, mesh, st)
            fn = make_train_step(cfg, opt_cfg, microbatches=k)
            lowered = jax.jit(
                fn,
                in_shardings=(state_sh, batch_sh),
                out_shardings=(state_sh, None),
                donate_argnums=(0,),
            ).lower(state, batch_in)
            return lowered

        params = specs_lib.params_specs(cfg)
        params_sh = sharding.param_shardings(st, params)
        if shape.kind == "prefill":
            batch_sh = sharding.named(st, sharding.batch_specs(st, batch_in))

            def prefill_step(p, batch):
                logits, caches = T.prefill(cfg, p, batch)
                return logits, caches

            lowered = jax.jit(
                prefill_step, in_shardings=(params_sh, batch_sh)
            ).lower(params, batch_in)
            return lowered

        # decode
        caches = specs_lib.decode_cache_specs(cfg, shape)
        cache_sh = sharding.named(st, sharding.cache_specs(st, caches))
        tok_sh = sharding.named(
            st, sharding.batch_specs(st, batch_in)["tokens"]
        )

        def serve_step(p, c, tokens, pos):
            return T.decode_step(cfg, p, c, tokens, pos)

        lowered = jax.jit(
            serve_step,
            in_shardings=(
                params_sh,
                cache_sh,
                tok_sh,
                NamedSharding(mesh, P()),
            ),
            out_shardings=(None, cache_sh),
            donate_argnums=(1,),
        ).lower(
            params, caches, batch_in["tokens"], batch_in["pos"]
        )
        return lowered


def run_cell(
    arch: str,
    shape_name: str,
    *,
    multi_pod: bool = False,
    sparse: bool = True,
    density: float | None = None,
    verbose: bool = True,
    strategy: str | None = None,
    overrides: dict | None = None,
) -> dict:
    shape = SHAPES[shape_name]
    cfg = registry.get(arch, sparse=sparse, density=density, **(overrides or {}))
    mesh = make_production_mesh(multi_pod=multi_pod)
    n_chips = mesh.size
    strategy = strategy or registry.DEFAULT_STRATEGY.get(arch, "tp")

    t0 = time.time()
    lowered = lower_cell(cfg, shape, mesh, strategy=strategy)
    t_lower = time.time() - t0
    t0 = time.time()
    compiled = lowered.compile()
    t_compile = time.time() - t0

    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    cost = roofline.analyze_hlo(hlo)
    # the production mesh is a v5e pod (make_production_mesh)
    terms = roofline.roofline_terms(cost, roofline.peaks_for("TPU v5 lite"))

    n_tokens = (
        shape.global_batch * shape.seq_len
        if shape.kind in ("train", "prefill")
        else shape.global_batch
    )
    mflops = roofline.model_flops(
        cfg, n_tokens, backward=(shape.kind == "train")
    )
    hlo_flops_global = cost.flops * n_chips
    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "strategy": strategy,
        "sparse": sparse,
        "density": cfg.sparse_density if sparse else 1.0,
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
        "argument_bytes_per_device": getattr(mem, "argument_size_in_bytes", None),
        "output_bytes_per_device": getattr(mem, "output_size_in_bytes", None),
        "xla_cost_flops_per_device": ca.get("flops"),
        "hlo_flops_per_device": cost.flops,
        "hlo_bytes_per_device": cost.bytes_accessed,
        "collective_bytes_per_device": cost.total_collective_bytes,
        "collective_breakdown": cost.collective_bytes,
        **terms,
        "model_flops_global": mflops,
        "useful_flops_ratio": (
            mflops / hlo_flops_global if hlo_flops_global else 0.0
        ),
    }
    if verbose:
        print(f"== {arch} x {shape_name} [{result['mesh']}] "
              f"{'pixelfly' if sparse else 'dense'} ==")
        print(f"  lower {t_lower:.1f}s compile {t_compile:.1f}s")
        print(f"  memory_analysis: {mem}")
        print(f"  cost_analysis flops/device (XLA, loop-bodies-once): {ca.get('flops')}")
        print(f"  walker flops/device {cost.flops:.3e}  bytes {cost.bytes_accessed:.3e}  "
              f"collective {cost.total_collective_bytes:.3e}")
        print(f"  terms: compute {terms['compute_s']*1e3:.2f}ms  "
              f"memory {terms['memory_s']*1e3:.2f}ms  "
              f"collective {terms['collective_s']*1e3:.2f}ms  "
              f"-> {terms['bottleneck']}-bound")
        print(f"  MODEL_FLOPS/HLO_FLOPS (useful ratio): {result['useful_flops_ratio']:.3f}")
    return result


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_NAMES)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--dense", action="store_true", help="dense baseline (no pixelfly)")
    ap.add_argument("--strategy", choices=["tp", "fsdp"], default=None)
    ap.add_argument("--density", type=float, default=None)
    ap.add_argument("--json", default=None)
    args = ap.parse_args()

    cells: list[tuple[str, str]] = []
    if args.all:
        for a in registry.ARCH_NAMES:
            for sh in registry.shapes_for(a, sparse=not args.dense):
                cells.append((a, sh.name))
    else:
        if not args.arch or not args.shape:
            ap.error("--arch and --shape (or --all) required")
        cells = [(args.arch, args.shape)]

    meshes = [args.multi_pod]
    if args.both_meshes:
        meshes = [False, True]

    results, failures = [], []
    for arch, sh in cells:
        for mp in meshes:
            try:
                results.append(
                    run_cell(
                        arch, sh, multi_pod=mp,
                        sparse=not args.dense, density=args.density,
                        strategy=args.strategy,
                    )
                )
            except Exception as e:  # noqa: BLE001 - report and continue
                traceback.print_exc()
                failures.append({"arch": arch, "shape": sh, "multi_pod": mp,
                                 "error": repr(e), "ok": False})
    if args.json:
        with open(args.json, "w") as f:
            json.dump(results + failures, f, indent=1)
    print(f"\n{len(results)} cells OK, {len(failures)} failed")
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
