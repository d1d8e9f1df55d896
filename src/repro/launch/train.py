"""Training launcher: ``python -m repro.launch.train --arch <id> ...``.

Runs the Trainer on whatever devices exist (CPU here, a TPU slice in
production — the same code path: mesh + rules + jitted step).  Smoke-scale
by default; ``--full`` selects the assigned full config (only sensible on
real hardware).
"""
from __future__ import annotations

import argparse

from ..core.transprecision import PRESETS
from ..configs import get_config
from ..optim import AdamWConfig
from ..train import Trainer, TrainerConfig
from .compile_cache import enable_compile_cache


def main():
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="paper-edge")
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--policy", default="bf16", choices=sorted(PRESETS))
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    args = ap.parse_args()

    cfg = get_config(args.arch, smoke=not args.full)
    tcfg = TrainerConfig(steps=args.steps, global_batch=args.batch,
                         seq_len=args.seq, checkpoint_dir=args.ckpt_dir,
                         checkpoint_every=args.ckpt_every)
    opt = AdamWConfig(lr=args.lr, total_steps=args.steps,
                      warmup_steps=max(1, args.steps // 10))
    trainer = Trainer(cfg, tcfg, opt, policy=args.policy)
    out = trainer.run()
    print("final:", out["metrics"])


if __name__ == "__main__":
    main()
