"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

Runs the fault-tolerant Trainer on the deterministic token stream.  On a
real pod this process runs per-host under the same mesh the dry-run proved;
on this container use ``--reduced`` for a CPU-sized twin.

Examples:
  PYTHONPATH=src python -m repro.launch.train --arch qwen3-14b --reduced \
      --steps 50 --batch 8 --seq 64
"""
from __future__ import annotations

import argparse

import jax.numpy as jnp

from repro.configs import ARCH_IDS, get_config
from repro.data import TokenStream
from repro.distributed.sharding import make_constrainer
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_production_mesh
from repro.runtime.trainer import Trainer, TrainerConfig


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS, required=True)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU-sized config (smoke twin)")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8, help="per-host batch")
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--production-mesh", action="store_true",
                    help="build the 16x16 mesh (needs 256 devices)")
    args = ap.parse_args()

    cfg = get_config(args.arch, reduced=args.reduced)
    mesh = constrain = None
    if args.production_mesh:
        mesh = make_production_mesh()
        constrain = make_constrainer(cfg, mesh)

    stream = TokenStream(vocab_size=cfg.vocab_size, batch_size=args.batch,
                         seq_len=args.seq)
    trainer = Trainer(
        cfg,
        TrainerConfig(ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                      lr=args.lr, warmup_steps=max(args.steps // 10, 1),
                      total_steps=args.steps,
                      compute_dtype=jnp.float32 if args.reduced
                      else jnp.bfloat16),
        lambda step: stream.batch(step),
        mesh=mesh, constrain=constrain)
    out = trainer.run(args.steps)
    losses = out["losses"]
    print(f"finished at step {out['final_step']}: "
          f"loss {losses[0]:.4f} → {losses[-1]:.4f}; "
          f"recoveries={out['recoveries']} stragglers={out['stragglers']}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
