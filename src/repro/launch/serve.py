"""End-to-end serving driver: batched generation over a request queue.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen1_5_0_5b \
      --requests 8 --max-new 16 [--scale full]

``--scale`` picks the same config family as launch/train.py: ``smoke``
(the default, CPU-sized), ``100m`` or ``full`` (the published widths).
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import ARCH_IDS, SCALES, scaled_config
from repro.launch.cache import use_compile_cache
from repro.models import get_family
from repro.runtime.server import Request, Server


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1_5_0_5b", choices=ARCH_IDS)
    ap.add_argument("--scale", default="smoke", choices=SCALES)
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--batch-slots", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--ckpt-dir", default=None, help="restore params from checkpoint")
    args = ap.parse_args()

    use_compile_cache()
    cfg = scaled_config(args.arch, args.scale)
    fam = get_family(cfg)
    params = fam.init(jax.random.PRNGKey(0), cfg)
    if args.ckpt_dir:
        from repro.checkpoint import CheckpointManager

        restored = CheckpointManager(args.ckpt_dir).restore(params)
        if restored:
            params = restored[0]
            print(f"restored params from step {restored[1]}")
    server = Server(cfg, params, max_len=args.prompt_len + args.max_new + 1,
                    temperature=args.temperature)
    rng = jax.random.PRNGKey(1)
    reqs = []
    for i in range(args.requests):
        rng, k = jax.random.split(rng)
        prompt = list(map(int, jax.random.randint(k, (args.prompt_len,), 0, cfg.vocab)))
        reqs.append(Request(prompt=prompt, max_new=args.max_new))
    t0 = time.perf_counter()
    done = server.serve(reqs, batch_slots=args.batch_slots)
    dt = time.perf_counter() - t0
    total_tokens = sum(len(r.out) for r in done)
    print(f"served {len(done)} requests, {total_tokens} tokens in {dt:.2f}s "
          f"({total_tokens/dt:.1f} tok/s on {jax.device_count()} host device(s))")
    for r in done[:3]:
        print(f"  prompt={r.prompt[:4]}... -> {r.out}")


if __name__ == "__main__":
    main()
