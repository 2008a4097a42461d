"""Multi-pod dry-run driver.

For every (architecture x applicable shape) cell:
  * build the step function + ShapeDtypeStruct inputs (launch/steps.py),
  * ``jax.jit(step, in_shardings=...).lower(...).compile()`` on the
    production mesh — (16, 16) single-pod and (2, 16, 16) multi-pod,
  * record memory_analysis / cost_analysis / collective-bytes (roofline).

Results are appended incrementally to results/dryrun.json so the sweep is
resumable.  Usage:

  PYTHONPATH=src python -m repro.launch.dryrun --arch yi_6b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multipod-only-train]
"""

import argparse
import dataclasses
import json
import os
import pathlib
import time
import traceback

import jax

from repro.configs import ARCH_IDS, get_config
from repro.launch.mesh import make_production_mesh
from repro.launch.roofline import extract
from repro.launch.shapes import SHAPES, applicable
from repro.launch.steps import build_cell

RESULTS = pathlib.Path(__file__).resolve().parents[3] / "results"


def _layer_period(cfg) -> int:
    if cfg.family == "transformer" and cfg.local_global_ratio:
        return cfg.local_global_ratio + 1
    if cfg.family == "rglru_hybrid":
        return len(cfg.block_pattern or ("rec", "rec", "attn"))
    return 1


def _extrapolation_depths(cfg) -> tuple[int, int] | None:
    """(L1, L2) reduced depths for the affine roofline pass, or None for
    full unroll.  Valid because the unrolled HLO cost is affine in the
    number of layer periods: cost(L) = base + (L/p) * period_cost."""
    p = _layer_period(cfg)
    L = cfg.n_layers
    if cfg.family in ("whisper",) or L % p:
        return None  # small or non-periodic tail (recurrentgemma 26 = 8*3+2)
    # Anchors: collective bytes extrapolate exactly (<0.1% error, validated
    # vs full unroll); FLOPs within ~2%; "bytes accessed" within ~15%
    # (fusion at the loss/embed boundary is not perfectly layer-affine).
    if cfg.family == "rwkv6":
        # each rwkv layer unrolls its 64-step chunk loop too: keep anchors
        # shallow or the autodiff'd HLO explodes
        l1, l2 = 1, 2
    else:
        l1, l2 = (p, 2 * p) if p >= 4 else (4 * p, 8 * p)
    if l2 >= L:
        return None
    return l1, l2


def _costs_of(rec: dict) -> dict:
    keys = ("hlo_flops", "hlo_bytes", "coll_bytes")
    out = {k: rec[k] for k in keys}
    out["coll_breakdown"] = dict(rec["coll_breakdown"])
    return out


def _affine(c1: dict, c2: dict, n1: float, n2: float, n: float) -> dict:
    def ext(a, b):
        per = (b - a) / (n2 - n1)
        return a + per * (n - n1)

    out = {k: ext(c1[k], c2[k]) for k in ("hlo_flops", "hlo_bytes", "coll_bytes")}
    keys = set(c1["coll_breakdown"]) | set(c2["coll_breakdown"])
    out["coll_breakdown"] = {
        k: ext(c1["coll_breakdown"].get(k, 0), c2["coll_breakdown"].get(k, 0))
        for k in keys}
    return out


def run_cell(arch: str, shape: str, multi_pod: bool = False,
             variant: str = "baseline", cfg_override=None, verbose: bool = True,
             mesh=None, mesh_name: str | None = None, unroll: bool = True,
             build_opts: dict | None = None):
    """Two lowering modes (see EXPERIMENTS.md §Dry-run):

    * ``unroll=True`` — exact roofline accounting: XLA cost_analysis counts
      lax.scan bodies ONCE, so FLOPs/bytes/collectives are only correct when
      layer loops are unrolled.  Buffer-assignment "temp" memory is
      pessimistic in this mode (the scheduler keeps more unrolled buffers
      alive than the scanned program would).
    * ``unroll=False`` — the production lowering (scanned layers): proves
      shardability/compile and gives the realistic per-device memory.
    """
    cfg = cfg_override or get_config(arch)
    cfg = dataclasses.replace(cfg, scan_layers=not unroll)
    ok, why = applicable(cfg, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": "multi" if multi_pod else "single",
                "variant": variant, "status": "skipped", "reason": why}
    if mesh is None:
        mesh = make_production_mesh(multi_pod=multi_pod)
        mesh_name = "2x16x16" if multi_pod else "16x16"
    mesh_name = mesh_name or "x".join(map(str, mesh.devices.shape))
    cell = SHAPES[shape]
    t0 = time.time()

    def compile_once(cfg_i):
        with jax.set_mesh(mesh):
            built = build_cell(cfg_i, shape, mesh, **(build_opts or {}))
            jitted = jax.jit(built["step_fn"],
                             in_shardings=built["in_shardings"],
                             out_shardings=built.get("out_shardings"),
                             donate_argnums=built["donate"])
            lowered = jitted.lower(*built["specs"])
            compiled = lowered.compile()
            hlo = compiled.as_text()
            roof = extract(arch, shape, mesh_name, mesh.devices.size, compiled,
                           hlo, cfg, built["kind"], cell.seq_len,
                           cell.global_batch)
            mem = compiled.memory_analysis()
        return roof, mem

    try:
        depths = _extrapolation_depths(cfg) if unroll else None
        if depths is None:
            roof, mem = compile_once(cfg)
            rec = roof.to_dict()
            rec["method"] = "unrolled-full" if unroll else "scanned"
        else:
            L1, L2 = depths
            roof1, _ = compile_once(dataclasses.replace(cfg, n_layers=L1))
            roof2, mem = compile_once(dataclasses.replace(cfg, n_layers=L2))
            ext = _affine(_costs_of(roof1.to_dict()), _costs_of(roof2.to_dict()),
                          L1, L2, cfg.n_layers)
            roof2.hlo_flops = ext["hlo_flops"]
            roof2.hlo_bytes = ext["hlo_bytes"]
            roof2.coll_bytes = ext["coll_bytes"]
            roof2.coll_breakdown = ext["coll_breakdown"]
            rec = roof2.to_dict()
            rec["method"] = f"unrolled-affine(L={L1},{L2})"
        rec.update({
            "variant": variant,
            "status": "ok",
            "compile_s": round(time.time() - t0, 1),
            "memory_analysis": {
                k: int(getattr(mem, k, 0)) for k in
                ("temp_size_in_bytes", "argument_size_in_bytes",
                 "output_size_in_bytes", "alias_size_in_bytes",
                 "generated_code_size_in_bytes")
            },
        })
        if verbose:
            print(f"[{mesh_name}] {arch} x {shape} ({variant}): OK "
                  f"({rec['compile_s']}s) bottleneck={rec['bottleneck']} "
                  f"frac={rec['roofline_fraction']:.3f} "
                  f"bytes/dev={rec['bytes_per_device']/2**30:.2f}GiB", flush=True)
        return rec
    except Exception as e:  # noqa: BLE001 — a failing cell is a recorded bug
        if verbose:
            print(f"[{mesh_name}] {arch} x {shape} ({variant}): FAIL {e}", flush=True)
            traceback.print_exc()
        return {"arch": arch, "shape": shape, "mesh": mesh_name,
                "variant": variant, "status": "fail", "error": str(e)[:2000],
                "compile_s": round(time.time() - t0, 1)}


def append_result(rec: dict, path: pathlib.Path | None = None):
    path = path or (RESULTS / "dryrun.json")
    path.parent.mkdir(parents=True, exist_ok=True)
    records = json.loads(path.read_text()) if path.exists() else []
    records = [r for r in records
               if not (r["arch"] == rec["arch"] and r["shape"] == rec["shape"]
                       and r["mesh"] == rec["mesh"]
                       and r.get("variant", "baseline") == rec.get("variant", "baseline"))]
    records.append(rec)
    path.write_text(json.dumps(records, indent=1))


def _done(records, arch, shape, mesh, variant) -> bool:
    return any(r["arch"] == arch and r["shape"] == shape and r["mesh"] == mesh
               and r.get("variant") == variant and r.get("status") != "fail"
               for r in records)


def main():
    # the placeholder production mesh needs 512 host devices; set before
    # JAX creates its backends, and only when run as a script
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=ARCH_IDS)
    ap.add_argument("--shape", choices=list(SHAPES))
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--no-multipod", action="store_true")
    ap.add_argument("--no-resume", action="store_true")
    ap.add_argument("--out", default=None)
    args = ap.parse_args()

    out = pathlib.Path(args.out) if args.out else (RESULTS / "dryrun.json")
    existing = json.loads(out.read_text()) if (out.exists() and not args.no_resume) else []
    archs = ARCH_IDS if (args.all or not args.arch) else [args.arch]
    shapes = list(SHAPES) if (args.all or not args.shape) else [args.shape]
    # passes per cell: scanned single-pod (compile/memory), unrolled
    # single-pod (roofline), scanned multi-pod (pod-axis proof)
    jobs = []
    for a in archs:
        for s in shapes:
            jobs.append((a, s, False, False, "compile-scan"))
            jobs.append((a, s, False, True, "baseline"))
            if not args.no_multipod:
                jobs.append((a, s, True, False, "compile-scan"))
    for a, s, mp, unroll, variant in jobs:
        mesh_name = "2x16x16" if mp else "16x16"
        if _done(existing, a, s, mesh_name, variant):
            print(f"[{mesh_name}] {a} x {s} ({variant}): cached, skip")
            continue
        rec = run_cell(a, s, multi_pod=mp, unroll=unroll, variant=variant)
        append_result(rec, out)


if __name__ == "__main__":
    main()
