"""`python -m repro.launch.train` — the paper's §5.2.4 job-script payload.

Trains a (reduced or full) architecture on the synthetic LM pipeline on
whatever devices exist.  On a real pod each host runs this under the
scheduler; in the container it runs single-process (use --reduced for CPU).
"""
from __future__ import annotations

import argparse

from repro.configs import (
    ARCH_IDS, INPUT_SHAPES, default_run_config, get_config,
    get_reduced_config, shape_for,
)
from repro.configs.base import InputShape, RunConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.monitoring import MetricsRegistry
from repro.optim import OptimizerConfig
from repro.training import Trainer, TrainerConfig


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="repro.launch.train")
    ap.add_argument("--arch", default="stablelm-3b", choices=ARCH_IDS)
    ap.add_argument("--shape", default="train_4k",
                    choices=list(INPUT_SHAPES))
    ap.add_argument("--reduced", action="store_true",
                    help="reduced config + small batch (CPU smoke)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=0,
                    help="override sequence length")
    ap.add_argument("--batch", type=int, default=0,
                    help="override global batch")
    ap.add_argument("--data", type=int, default=1, help="data mesh axis")
    ap.add_argument("--model", type=int, default=1, help="model mesh axis")
    ap.add_argument("--strategy", default="fsdp_tp",
                    choices=["dp", "tp", "fsdp", "fsdp_tp"])
    ap.add_argument("--zero", type=int, default=3, choices=[1, 2, 3])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="")
    ap.add_argument("--ckpt-every", type=int, default=0)
    args = ap.parse_args(argv)

    enable_compile_cache()
    cfg = (get_reduced_config(args.arch) if args.reduced
           else get_config(args.arch))
    base = INPUT_SHAPES[args.shape]
    shape = InputShape(
        base.name,
        args.seq_len or (256 if args.reduced else base.seq_len),
        args.batch or (8 if args.reduced else base.global_batch),
        base.kind)
    assert shape.kind == "train", "use repro.launch.serve for decode shapes"
    cfg = shape_for(cfg, shape)
    mesh = make_mesh(args.data, args.model)
    run = RunConfig(strategy=args.strategy, zero_stage=args.zero,
                    microbatches=args.microbatches, remat="layer")
    opt = OptimizerConfig(peak_lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                          decay_steps=args.steps)
    tcfg = TrainerConfig(steps=args.steps, ckpt_dir=args.ckpt_dir or None,
                         ckpt_every=args.ckpt_every)
    metrics = MetricsRegistry()
    print(f"training {cfg.name} ({cfg.param_count():,} params) on "
          f"mesh {dict(mesh.shape)} strategy={args.strategy} "
          f"zero={args.zero}")
    trainer = Trainer(cfg, run, mesh, shape, opt, tcfg, metrics)
    with mesh:
        trainer.train()
    print("\n== metrics ==")
    print(metrics.dashboard())
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
