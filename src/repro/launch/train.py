"""Training launcher.

CPU-scale real training (runs here) and the entry point a TPU cluster
would use (same code path; the mesh and strategy come from flags).

  PYTHONPATH=src python -m repro.launch.train --arch smollm-360m --smoke \
      --steps 50 --batch 8 --seq 128 --ckpt-dir /tmp/ck
"""

from __future__ import annotations

import argparse

import jax

from repro.configs import registry
from repro.launch.compile_cache import use_compile_cache
from repro.launch.mesh import make_local_mesh, make_production_mesh
from repro.training.data import EmbedsWrapper, SyntheticLM, TextFileLM
from repro.training.loop import TrainConfig, Trainer
from repro.training.optimizer import OptConfig


def build_data(cfg, args):
    if args.data_file:
        src = TextFileLM(args.data_file, args.seq, args.batch, seed=args.seed)
    else:
        src = SyntheticLM(
            min(cfg.vocab_size, 512) if args.smoke else cfg.vocab_size,
            args.seq,
            args.batch,
            seed=args.seed,
        )
    if not cfg.embed_inputs:
        src = EmbedsWrapper(
            src, cfg.d_model, n_pos_streams=len(cfg.mrope_sections)
        )
    return src


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=registry.ARCH_NAMES, required=True)
    ap.add_argument("--smoke", action="store_true", help="reduced config")
    ap.add_argument("--sparse", action="store_true", help="pixelfly model")
    ap.add_argument("--density", type=float, default=None)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data-file", default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--strategy", choices=["tp", "fsdp"], default="fsdp")
    ap.add_argument("--production-mesh", action="store_true")
    ap.add_argument("--compress-grads", action="store_true")
    args = ap.parse_args()
    use_compile_cache()

    if args.smoke:
        cfg = registry.get_smoke(args.arch, sparse=args.sparse)
    else:
        cfg = registry.get(args.arch, sparse=args.sparse, density=args.density)
    if args.density is not None:
        cfg = cfg.replace(sparse_density=args.density)

    mesh = (
        make_production_mesh()
        if args.production_mesh
        else make_local_mesh()
    )
    opt = OptConfig(
        lr=args.lr, total_steps=args.steps, warmup_steps=max(1, args.steps // 10),
        compress_grads=args.compress_grads,
    )
    data = build_data(cfg, args)
    trainer = Trainer(
        cfg,
        opt,
        data,
        mesh,
        TrainConfig(
            steps=args.steps, ckpt_dir=args.ckpt_dir,
            ckpt_every=args.ckpt_every, seed=args.seed,
        ),
        strategy=args.strategy,
    )
    hist = trainer.run()
    if hist:
        print(
            f"final loss {hist[-1]['loss']:.4f} after {trainer.step} steps "
            f"({trainer.straggler_events} straggler events)"
        )
    trainer.checkpoint()


if __name__ == "__main__":
    main()
