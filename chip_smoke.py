#!/usr/bin/env python3
"""Bring-up smoke run on the TPU, through the entry points users call.

  python chip_smoke.py              # one chip: serve, train, Pallas kernels
  python chip_smoke.py --chips 4    # four chips: collectives, SUMMA, sharded step

One chip: Qwen1.5-0.5B at its published width (24 layers, d=1024, vocab
151936) with random weights made from ``--seed`` is served through
``runtime.server.Server`` (8 requests, prompt 128, 32 new tokens, 4 slots)
and checked for prefill-vs-decode consistency, then trained through
``runtime.trainer.Trainer`` on ``SyntheticLMSource`` (batch 4 x seq 1024,
10 steps); each of the five Pallas kernels (the N-way reduction under add,
max and and) is compiled to Mosaic at the model widths of
``kernels/widths.py`` and compared with ``kernels/ref.py``.

Four chips (``--chips 4``) runs only what exists across chips: every
collective schedule of ``core/schedules.py`` against ``native`` and NumPy
on a 4-device mesh at the paper's 1-32 KiB sizes, ``summa_sharded`` native
against ring on a 2x2 mesh, and four full-width Qwen train steps with 4-way
model sharding against one chip: the losses and the weights' update.

Everything runs in this one process: a chip belongs to one process, so no
child touches JAX.  Each phase prints its compile seconds (lowering and
XLA compile), the rest of its wall time, and the device's peak
memory so far; no rate or utilisation.  The last line of stdout is one
JSON object ``{"ok": true, "device": {...}}``.  A run that finds no TPU, or
in which any check fails, exits non-zero without that line.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys
import time

ROOT = pathlib.Path(__file__).resolve().parent
ARCH = "qwen1_5_0_5b"

# serving: 2 waves of 4 slots
N_REQUESTS, PROMPT_LEN, MAX_NEW, SLOTS = 8, 128, 32, 4
# prefill(x[:S]) + decode(x[S]) vs prefill(x[:S+1]) at full width in bf16:
# the two paths round differently through 24 layers; logits are O(1)
# (tied embeddings, std 0.02 x sqrt(1024)), so allow 0.1 absolute plus 2%.
CONSISTENCY_ATOL, CONSISTENCY_RTOL = 0.1, 0.02
# training; the data's Markov chain runs over the first DATA_VOCAB token
# ids, so ten steps can show the loss falling (over all 151936 ids the
# chain's bigrams are each seen about once in ten steps)
TRAIN_BATCH, TRAIN_SEQ, TRAIN_STEPS, TRAIN_LR, DATA_VOCAB = 4, 1024, 10, 1e-3, 8192
# 4-way model-sharded training vs one chip, from the same weights on the same
# batches.  The schedule's lr is 0 at step 0, so steps 1-3 update the weights
# and the last two losses come after updates.  Compared: every loss, and the
# weights' whole update, |dW_4chips - dW_1chip| / |dW_1chip| over all
# parameters (1.0 if the sharded update were missing).  Only the order of
# bf16/f32 partial sums differs.  On a v5e the gaps were 2.95e-3 (loss) and
# 0.059 (update); the limits are about 3.4 times those.  Without the
# sharded update the last loss would stay near the initial 12.13 against
# 11.80, and the update gap would be 1.0.
SHARDED_STEPS = 4
SHARDED_LOSS_ATOL, SHARDED_UPDATE_RTOL = 1e-2, 0.2
COLLECTIVE_KIB = (1, 2, 4, 8, 16, 32)


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


class CompileClock:
    """Sums JAX's lowering and backend-compile durations (tracing is left
    out: nested jits report their traces inside the outer one's)."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.seconds = 0.0
        self._jax = jax
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.seconds += duration

    def close(self):
        self._jax.monitoring.unregister_event_duration_listener(self._on_event)


class Phase:
    """Times one phase; prints compile/run seconds and peak device memory."""

    def __init__(self, name: str, clock: CompileClock, devices):
        self.name, self.clock, self.devices = name, clock, devices

    def __enter__(self):
        self.t0, self.c0 = time.perf_counter(), self.clock.seconds
        print(f"phase={self.name} start", flush=True)
        return self

    def __exit__(self, exc_type, *_):
        if exc_type is not None:
            return False  # a failed phase fails the run
        wall = time.perf_counter() - self.t0
        comp = self.clock.seconds - self.c0
        peaks = [(d.memory_stats() or {}).get("peak_bytes_in_use", -1)
                 for d in self.devices]
        print(f"phase={self.name} ok compile_s={comp} run_s={wall - comp} "
              f"wall_s={wall} peak_bytes_in_use={max(peaks)}", flush=True)
        return False


def rel_err(out, expected) -> float:
    import numpy as np

    out = np.asarray(out, np.float64)
    expected = np.asarray(expected, np.float64)
    return float(np.max(np.abs(out - expected)) / max(np.max(np.abs(expected)), 1e-30))


# ---------------------------------------------------------------------------
# one chip
# ---------------------------------------------------------------------------


def serve_phase(jax, cfg, seed: int) -> None:
    import numpy as np

    from repro.models import get_family
    from repro.runtime.server import Request, Server

    fam = get_family(cfg)
    params = jax.jit(lambda k: fam.init(k, cfg))(jax.random.PRNGKey(seed))
    server = Server(cfg, params, max_len=PROMPT_LEN + MAX_NEW)
    rng = np.random.default_rng(seed)
    reqs = [Request(prompt=rng.integers(0, cfg.vocab, PROMPT_LEN).tolist(),
                    max_new=MAX_NEW) for _ in range(N_REQUESTS)]
    done = server.serve(reqs, batch_slots=SLOTS)
    for i, r in enumerate(done):
        check(r.done and len(r.out) == MAX_NEW,
              f"request {i}: {len(r.out)} tokens, want {MAX_NEW}")
        check(all(0 <= t < cfg.vocab for t in r.out), f"request {i}: token outside vocab")
    print(f"serve: {len(done)} requests x {MAX_NEW} tokens, all inside vocab {cfg.vocab}; "
          f"first: {done[0].out[:8]}", flush=True)

    # prefill-vs-decode consistency (tests/test_arch_smoke.py) at full width
    tokens = jax.numpy.asarray(rng.integers(0, cfg.vocab, (SLOTS, PROMPT_LEN + 1)),
                               jax.numpy.int32)
    full, _ = jax.jit(lambda p, t: fam.prefill(p, t, cfg, max_len=PROMPT_LEN + 1))(
        params, tokens)
    _, cache = jax.jit(lambda p, t: fam.prefill(p, t, cfg, max_len=PROMPT_LEN + 1))(
        params, tokens[:, :PROMPT_LEN])
    dec, _ = jax.jit(lambda p, c, t: fam.decode_step(p, c, t, PROMPT_LEN, cfg))(
        params, cache, tokens[:, PROMPT_LEN:])
    full, dec = np.asarray(full), np.asarray(dec)
    check(np.all(np.isfinite(full)) and np.all(np.isfinite(dec)), "non-finite logits")
    diff = float(np.max(np.abs(dec - full)))
    top1 = float(np.mean(dec[:, :cfg.vocab].argmax(-1) == full[:, :cfg.vocab].argmax(-1)))
    print(f"serve: prefill-vs-decode max|diff|={diff} max|logit|={float(np.max(np.abs(full)))} "
          f"top1_agree={top1} (atol {CONSISTENCY_ATOL}, rtol {CONSISTENCY_RTOL})", flush=True)
    check(bool(np.all(np.abs(dec - full) <= CONSISTENCY_ATOL + CONSISTENCY_RTOL * np.abs(full))),
          "prefill and decode logits disagree beyond the bf16 tolerance")


def train_losses(jax, cfg, seed: int, steps: int, warmup: int, mesh=None, policy=None):
    """Runs ``Trainer.fit``; returns (losses, params)."""
    import numpy as np

    from repro.data import SyntheticLMSource
    from repro.models.common import REPLICATED
    from repro.optim import AdamWConfig
    from repro.runtime import Trainer, TrainerConfig

    src = SyntheticLMSource(vocab=min(DATA_VOCAB, cfg.vocab), seq_len=TRAIN_SEQ,
                            global_batch=TRAIN_BATCH, seed=seed, branching=4)
    tcfg = TrainerConfig(adamw=AdamWConfig(lr=TRAIN_LR), warmup=warmup,
                         total_steps=steps)
    trainer = Trainer(cfg, tcfg, policy=policy or REPLICATED, mesh=mesh)
    params, _ = trainer.fit(src, steps=steps, rng=jax.random.PRNGKey(seed), resume=False)
    losses = [m["loss"] for m in trainer.metrics_log if "loss" in m]
    check(len(losses) == steps, f"{len(losses)} losses for {steps} steps")
    check(all(np.isfinite(losses)), f"non-finite loss: {losses}")
    return losses, params


def train_phase(jax, cfg, seed: int) -> None:
    losses, _ = train_losses(jax, cfg, seed, TRAIN_STEPS, warmup=2)
    print(f"train: batch {TRAIN_BATCH} x seq {TRAIN_SEQ}, losses {losses}", flush=True)
    check(losses[-1] < losses[0], f"loss did not fall: {losses[0]} -> {losses[-1]}")


def kernel_phase(jax, seed: int) -> None:
    from repro.kernels.widths import MODEL_WIDTH

    keys = jax.random.split(jax.random.PRNGKey(seed), len(MODEL_WIDTH))
    for key, (name, case) in zip(keys, MODEL_WIDTH.items()):
        args = case.make_inputs(key)
        # the kernel's own default picks Mosaic on a TPU backend
        t0 = time.perf_counter()
        compiled = jax.jit(case.kernel).lower(*args).compile()
        compile_s = time.perf_counter() - t0
        check("tpu_custom_call" in compiled.as_text(),
              f"{name}: no tpu_custom_call in the compiled program (interpreted?)")
        t0 = time.perf_counter()
        out = jax.block_until_ready(compiled(*args))
        run_s = time.perf_counter() - t0
        with jax.default_matmul_precision("highest"):
            expected = jax.jit(case.reference)(*args)
        err = rel_err(out, expected)
        print(f"kernel={name} shapes={[tuple(a.shape) for a in args]} mosaic=yes "
              f"compile_s={compile_s} run_s={run_s} max_err/max_ref={err} (tol {case.tol})",
              flush=True)
        check(out.shape == expected.shape and err <= case.tol,
              f"{name}: error {err} > {case.tol}")


# ---------------------------------------------------------------------------
# four chips
# ---------------------------------------------------------------------------


def on_all(out, devices, what: str) -> list[int]:
    """Checks that ``out`` has a shard on every device; returns their ids."""
    held = sorted(s.device.id for s in out.addressable_shards)
    check(held == sorted(d.id for d in devices),
          f"{what}: shards on {held}, want all of {sorted(d.id for d in devices)}")
    return held


def collectives_phase(jax, devices, seed: int) -> None:
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core import schedules as sched

    n, cols, root = len(devices), 64, 1
    mesh = jax.make_mesh((n,), ("x",), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,))

    def spmd(body, x):
        fn = jax.shard_map(body, mesh=mesh, in_specs=P("x"), out_specs=P("x"),
                           check_vma=False)
        return jax.jit(fn)(x)

    ops = {
        "broadcast": lambda s: lambda v: sched.broadcast(v, "x", root=root, schedule=s,
                                                          chunks=2),
        "all_reduce": lambda s: lambda v: sched.all_reduce(v, "x", schedule=s),
        "reduce_scatter": lambda s: lambda v: sched.reduce_scatter(v, "x", schedule=s),
    }
    rng = np.random.default_rng(seed)
    checked = 0
    for kib in COLLECTIVE_KIB:
        rows = kib * 1024 // (4 * cols)  # one f32 (rows, cols) buffer per device
        host = rng.standard_normal((n * rows, cols)).astype(np.float32)
        x = jax.device_put(host, NamedSharding(mesh, P("x")))
        per = host.reshape(n, rows, cols).astype(np.float64)
        expected = {
            "broadcast": np.tile(per[root], (n, 1)),
            "all_reduce": np.tile(per.sum(0), (n, 1)),
            "reduce_scatter": per.sum(0),  # device i keeps rows i*rows/n...
        }
        for op, make in ops.items():
            native = np.asarray(spmd(make("native"), x), np.float64)
            for s in sched.SCHEDULES:
                out = spmd(make(s), x)
                on_all(out, devices, f"{op}/{s}/{kib}KiB")
                got = np.asarray(out, np.float64)
                check(rel_err(got, expected[op]) <= 1e-6 and rel_err(got, native) <= 1e-6,
                      f"{op}/{s} at {kib} KiB disagrees with native or NumPy")
                checked += 1
    for s in sched.SCHEDULES:
        out = spmd(lambda v, s=s: v * 0 + sched.barrier("x", schedule=s).astype(v.dtype),
                   jax.device_put(np.zeros((n, 128), np.float32), NamedSharding(mesh, P("x"))))
        on_all(out, devices, f"barrier/{s}")
        check(bool(np.all(np.asarray(out) == n)), f"barrier/{s}: not every member arrived")
        checked += 1
    print(f"collectives: {checked} (op, schedule, size) cases agree with native and "
          f"NumPy, each output sharded over devices {on_all(out, devices, 'barrier')}; "
          f"sizes {COLLECTIVE_KIB} KiB per device", flush=True)


def summa_phase(jax, devices, seed: int) -> None:
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.summa import summa_sharded

    mesh = jax.make_mesh((2, 2), ("row", "col"), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    rng = np.random.default_rng(seed)
    a_host, b_host = (rng.standard_normal((1024, 1024)).astype(np.float32) for _ in range(2))
    spec = NamedSharding(mesh, P("row", "col"))
    a, b = jax.device_put(a_host, spec), jax.device_put(b_host, spec)
    expected = a_host.astype(np.float64) @ b_host.astype(np.float64)
    outs = {}
    with jax.set_mesh(mesh), jax.default_matmul_precision("highest"):
        for s in ("native", "ring"):
            out = jax.jit(lambda x, y, s=s: summa_sharded(x, y, mesh, "row", "col",
                                                          schedule=s))(a, b)
            held = on_all(out, devices, f"summa/{s}")
            outs[s] = np.asarray(out, np.float64)
            check(rel_err(outs[s], expected) <= 1e-4, f"summa/{s} disagrees with NumPy")
    err = rel_err(outs["ring"], outs["native"])
    check(err <= 1e-5, f"summa ring vs native: {err}")
    print(f"summa: 1024^2 f32 on 2x2 (shards on devices {held}), native and ring agree "
          f"with NumPy; ring vs native max_err/max={err}", flush=True)


def sharded_train_phase(jax, cfg, devices, seed: int) -> None:
    import numpy as np

    from repro.launch.steps import make_policy
    from repro.models import get_family

    # the weights both runs start from, made eagerly as Trainer.init_state does
    init = get_family(cfg).init(jax.random.PRNGKey(seed), cfg)
    ref_losses, ref_params = train_losses(jax, cfg, seed, SHARDED_STEPS, warmup=1)
    mesh = jax.make_mesh((1, len(devices)), ("data", "model"), devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    policy = make_policy(cfg, mesh)
    with jax.set_mesh(mesh):
        losses, params = train_losses(jax, cfg, seed, SHARDED_STEPS, warmup=1, mesh=mesh,
                                      policy=policy)
    wq = params["layers"]["attn"]["wq"]
    held = on_all(wq, devices, "sharded params")
    shard_shapes = sorted({tuple(s.data.shape) for s in wq.addressable_shards})
    # the update the steps made, over every parameter; the worst leaf is
    # printed too, where a bf16 weight with no update (a norm scale at 1.0)
    # counts only if the sharded run moved it
    gap2 = upd2 = 0.0
    worst = ("", 0.0)
    leaves = [jax.tree_util.tree_flatten_with_path(t)[0] for t in (init, ref_params, params)]
    for (path, w0), (_, w1), (_, w4) in zip(*leaves):
        w0 = np.asarray(w0, np.float64)
        d1, d4 = np.asarray(w1, np.float64) - w0, np.asarray(w4, np.float64) - w0
        g2, u2 = float(np.sum((d4 - d1) ** 2)), float(np.sum(d1 ** 2))
        gap2, upd2 = gap2 + g2, upd2 + u2
        leaf_gap = (g2 / u2) ** 0.5 if u2 > 0 else (0.0 if g2 == 0 else float("inf"))
        if leaf_gap >= worst[1]:
            worst = (jax.tree_util.keystr(path), leaf_gap)
    update_gap = (gap2 / upd2) ** 0.5 if upd2 > 0 else float("inf")
    loss_gap = max(abs(a - b) for a, b in zip(losses, ref_losses))
    print(f"sharded train: wq {tuple(wq.shape)} held as {shard_shapes} on devices "
          f"{held}; losses {losses} vs one-chip {ref_losses} (max gap {loss_gap}, "
          f"atol {SHARDED_LOSS_ATOL}); update |dW_4 - dW_1|/|dW_1|={update_gap} "
          f"(|dW_1|={upd2 ** 0.5}, rtol {SHARDED_UPDATE_RTOL}; worst leaf "
          f"{worst[0]} {worst[1]})", flush=True)
    check(shard_shapes == [(wq.shape[0], wq.shape[1], wq.shape[2] // len(devices))],
          f"wq not split 4 ways on its output dim: {shard_shapes}")
    check(upd2 > 0, "the one-chip steps left the weights unchanged")
    check(update_gap <= SHARDED_UPDATE_RTOL,
          f"sharded update differs from one chip's: {update_gap}")
    check(loss_gap <= SHARDED_LOSS_ATOL, f"sharded losses {losses} vs one-chip {ref_losses}")


# ---------------------------------------------------------------------------


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4))
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()

    if not (ROOT / "src" / "repro").is_dir():
        fail(f"no src/repro beside {pathlib.Path(__file__).name}: run it from a checkout")
    sys.path.insert(0, str(ROOT / "src"))

    import jax

    devices = jax.devices()
    dev = devices[0]
    print(f"device: platform={dev.platform} kind={dev.device_kind} count={len(devices)}",
          flush=True)
    check(dev.platform == "tpu", f"no TPU: JAX's first device is {dev.platform}")
    check(len(devices) >= args.chips, f"--chips {args.chips} but {len(devices)} devices")
    devices = devices[:args.chips]

    from repro.configs import scaled_config
    from repro.launch.cache import use_compile_cache

    use_compile_cache()
    cfg = scaled_config(ARCH, "full")
    print(f"model: {cfg.name} layers={cfg.n_layers} d={cfg.d_model} vocab={cfg.vocab} "
          f"params~{cfg.n_params / 1e6:.0f}M", flush=True)
    clock = CompileClock(jax)
    if args.chips == 1:
        with Phase("serve", clock, devices):
            serve_phase(jax, cfg, args.seed)
        with Phase("train", clock, devices):
            train_phase(jax, cfg, args.seed)
        with Phase("kernels", clock, devices):
            kernel_phase(jax, args.seed)
    else:
        with Phase("collectives", clock, devices):
            collectives_phase(jax, devices, args.seed)
        with Phase("summa", clock, devices):
            summa_phase(jax, devices, args.seed)
        with Phase("sharded_train", clock, devices):
            sharded_train_phase(jax, cfg, devices, args.seed)
    clock.close()
    print(json.dumps({"ok": True, "device": {"platform": dev.platform,
                                              "kind": dev.device_kind,
                                              "count": len(devices)}}), flush=True)


if __name__ == "__main__":
    main()
