"""Training launcher.

  python -m repro.launch.train --arch llama3-8b --steps 100 --reduced
  python -m repro.launch.train --arch internlm2-1.8b --seq 256 --batch 8

Runs the full training stack on the available devices: diffusion-scheduled
data pipeline, jitted train step (the same one the multi-pod dry-run lowers),
async checkpointing, heartbeat/straggler monitoring.  ``--reduced`` swaps in
the architecture's smoke-test dims (CPU-friendly); full dims on a real TPU
slice pick up the production shardings via ``--mesh``.
"""

from __future__ import annotations

import argparse

import jax

from ..configs import get_arch
from ..configs.base import ShapeConfig
from ..models.sharding import ShardCtx
from ..optim.adamw import AdamWConfig
from ..runtime.train_loop import TrainConfig, Trainer
from .compile_cache import use_compile_cache
from .mesh import make_ctx, make_host_mesh


def main() -> None:
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="smoke-test dims (CPU)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--hosts", type=int, default=4)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_train")
    ap.add_argument("--mesh", default="none",
                    help="'none' (single device) | 'host' (all local devices)")
    args = ap.parse_args()

    cfg = get_arch(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeConfig("train", "train", args.seq, args.batch)
    ctx = ShardCtx() if args.mesh == "none" else make_ctx(make_host_mesh())

    trainer = Trainer(
        cfg, shape,
        TrainConfig(total_steps=args.steps, log_every=max(1, args.steps // 10),
                    checkpoint_every=max(10, args.steps // 4),
                    checkpoint_dir=args.ckpt_dir, num_hosts=args.hosts,
                    opt=AdamWConfig(lr=args.lr)),
        ctx=ctx,
    )
    res = trainer.run()
    print(f"done: {res.steps_run} steps, final loss {res.final_loss:.4f}, "
          f"pipeline hit-rate {res.pipeline_hit_rate:.0%}, wall {res.wall_s:.0f}s")


if __name__ == "__main__":
    main()
