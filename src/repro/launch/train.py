"""End-to-end training driver.

  PYTHONPATH=src python -m repro.launch.train --arch qwen1_5_0_5b \
      --steps 200 --batch 8 --seq 256 --scale 100m --ckpt-dir /tmp/ck

``--scale`` picks the config (``repro.configs.scaled_config``): ``smoke``
(~1M params), ``100m`` (a same-family ~100M-param reduction) or ``full``
(the published widths).
"""

from __future__ import annotations

import argparse
import dataclasses

import jax

from repro.configs import ARCH_IDS, SCALES, scaled_config
from repro.data import ByteFileSource, SyntheticLMSource
from repro.launch.cache import use_compile_cache
from repro.optim import AdamWConfig
from repro.runtime import Trainer, TrainerConfig


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1_5_0_5b", choices=ARCH_IDS)
    ap.add_argument("--scale", default="100m", choices=SCALES)
    ap.add_argument("--steps", type=int, default=200)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=256)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--data", default=None, help="path for byte-level data")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    use_compile_cache()
    cfg = scaled_config(args.arch, args.scale)
    if args.data:
        src = ByteFileSource(args.data, seq_len=args.seq, global_batch=args.batch,
                             seed=args.seed)
        cfg = dataclasses.replace(cfg, vocab=256)
    else:
        src = SyntheticLMSource(vocab=cfg.vocab, seq_len=args.seq,
                                global_batch=args.batch, seed=args.seed,
                                branching=4)
    n_params = cfg.n_params
    print(f"arch={cfg.name} family={cfg.family} params~{n_params/1e6:.1f}M "
          f"devices={jax.device_count()}")
    tcfg = TrainerConfig(
        adamw=AdamWConfig(lr=args.lr), warmup=min(50, args.steps // 10 + 1),
        total_steps=args.steps, ckpt_dir=args.ckpt_dir,
        ckpt_every=args.ckpt_every)
    trainer = Trainer(cfg, tcfg)
    trainer.fit(src, steps=args.steps, rng=jax.random.PRNGKey(args.seed))
    losses = [m["loss"] for m in trainer.metrics_log if "loss" in m]
    k = max(1, min(10, len(losses) // 5))
    print(f"loss: first{k}={sum(losses[:k])/k:.4f} "
          f"last{k}={sum(losses[-k:])/k:.4f} steps={len(losses)}")


if __name__ == "__main__":
    main()
