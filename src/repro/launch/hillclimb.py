"""Perf hill-climb driver: re-lower chosen cells with candidate changes.

Each variant is one hypothesis from the §Perf log; results append to
results/dryrun.json under the variant name and the report compares them to
the baseline.

  PYTHONPATH=src python -m repro.launch.hillclimb --cell phi3_5_moe:train_4k \
      --variant attn_chunk_512
"""

import argparse
import dataclasses
import os

from repro.configs import get_config
from repro.launch.dryrun import append_result, run_cell

# variant name -> (cfg transform, build_opts)
VARIANTS = {
    # memory-term levers
    "attn_chunk_512": (lambda c: dataclasses.replace(c, attn_q_chunk=512), {}),
    "attn_chunk_1024": (lambda c: dataclasses.replace(c, attn_q_chunk=1024), {}),
    "loss_chunk_256": (lambda c: dataclasses.replace(c, loss_chunk=256), {}),
    "loss_chunk_512": (lambda c: dataclasses.replace(c, loss_chunk=512), {}),
    "attn512_loss256": (lambda c: dataclasses.replace(
        c, attn_q_chunk=512, loss_chunk=256), {}),
    "cap_factor_1": (lambda c: dataclasses.replace(c, capacity_factor=1.0), {}),
    "bf16_attn": (lambda c: dataclasses.replace(c, attn_bf16_logits=True), {}),
    "no_remat": (lambda c: dataclasses.replace(c, remat=False), {}),
    "moe_token_shard": (lambda c: dataclasses.replace(c, moe_token_shard=True), {}),
    "moe_shard_cap1": (lambda c: dataclasses.replace(
        c, moe_token_shard=True, capacity_factor=1.0), {}),
    "bf16_attn_loss256": (lambda c: dataclasses.replace(
        c, attn_bf16_logits=True, loss_chunk=256), {}),
    "bf16_attn_noremat": (lambda c: dataclasses.replace(
        c, attn_bf16_logits=True, remat=False), {}),
    "attn512_noremat": (lambda c: dataclasses.replace(
        c, attn_q_chunk=512, remat=False), {}),
    # collective-term levers
    "align_decode": (lambda c: c, {"align_decode_cache": True}),
    "sp_prefill": (lambda c: c, {"seq_parallel": True}),
    "no_sp": (lambda c: c, {"seq_parallel": False}),
    "no_zero1": (lambda c: c, {"zero1": False}),
    # combos
    "align_decode_attn512": (lambda c: dataclasses.replace(c, attn_q_chunk=512),
                             {"align_decode_cache": True}),
    "align_bf16": (lambda c: dataclasses.replace(c, attn_bf16_logits=True),
                   {"align_decode_cache": True}),
    "moe_shard_sp": (lambda c: dataclasses.replace(c, moe_token_shard=True),
                     {"seq_parallel": True}),
    "bf16_attn_sp_moe": (lambda c: dataclasses.replace(
        c, attn_bf16_logits=True, moe_token_shard=True), {"seq_parallel": True}),
    "sp_prefill_attn512": (lambda c: dataclasses.replace(c, attn_q_chunk=512),
                           {"seq_parallel": True}),
}


def main():
    # the placeholder production mesh needs 512 host devices; set before
    # JAX creates its backends, and only when run as a script
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--cell", required=True, help="arch:shape")
    ap.add_argument("--variant", required=True, choices=list(VARIANTS))
    ap.add_argument("--scan-memory", action="store_true",
                    help="also run the scanned pass for memory analysis")
    args = ap.parse_args()
    arch, shape = args.cell.split(":")
    transform, build_opts = VARIANTS[args.variant]
    cfg = transform(get_config(arch))
    rec = run_cell(arch, shape, cfg_override=cfg, build_opts=build_opts,
                   variant=args.variant, unroll=True)
    append_result(rec)
    if args.scan_memory:
        rec2 = run_cell(arch, shape, cfg_override=cfg, build_opts=build_opts,
                        variant=f"{args.variant}-scan", unroll=False)
        append_result(rec2)


if __name__ == "__main__":
    main()
