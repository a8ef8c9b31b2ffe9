"""Training launcher: ``python -m repro.launch.train --arch <id> [...]``.

CPU-scale driver over the production step functions: smoke-sized variants
train locally; full configs are for the dry-run (this driver will also
run them under a mesh if you have the hardware).
"""
from __future__ import annotations

import argparse

import jax

from repro.configs import ARCH_IDS, get_config
from repro.data.synthetic import lm_batches
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_mesh
from repro.optim.adamw import AdamWConfig
from repro.train.loop import train


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="bmoe-paper", choices=list(ARCH_IDS))
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--full", action="store_true",
                    help="use the full (not smoke) config — needs a mesh")
    ap.add_argument("--mesh", default=None,
                    help="'data,model' sizes, e.g. '2,4' (needs devices)")
    args = ap.parse_args()

    enable_compile_cache()
    cfg = get_config(args.arch, smoke=not args.full)
    mesh = None
    if args.mesh:
        d, m = (int(x) for x in args.mesh.split(","))
        mesh = make_mesh((d, m), ("data", "model"))
    print(f"[train] arch={cfg.name} smoke={not args.full} "
          f"steps={args.steps} devices={len(jax.devices())}")
    batches = lm_batches(cfg.vocab_size, args.batch, args.seq, seed=0)
    _, history = train(
        cfg, batches, steps=args.steps, mesh=mesh,
        opt_cfg=AdamWConfig(lr=args.lr, warmup_steps=10,
                            total_steps=args.steps),
        log_every=max(args.steps // 10, 1),
        callback=lambda m: print(
            f"  step {m['step']:5d} loss={m['loss']:.4f} "
            f"grad_norm={m['grad_norm']:.3f} ({m['wall_s']:.0f}s)"))
    print(f"[train] done: loss {history[0]['loss']:.3f} -> "
          f"{history[-1]['loss']:.3f}")


if __name__ == "__main__":
    main()
