"""Runner of ``kind: "train"`` mixes: the program's training step on one chip.

The program's ``ModelConfig`` is ``repro.configs.get_config(<program_config>)``
(``scaled_config`` at ``program_scale``, where a test's file gives one), with
the configuration file's published keys checked against it: a width or
constant that differs stops the run.  Only depth may be cut: where the file
lists ``num_hidden_layers`` under ``reduced``, the layer count is set from
the file; any other published key listed there stops the run as well.  The model path is the program's own: ``Trainer.init_state`` builds the optimizer
state and ``Trainer``'s jitted step (``Trainer._step_fn``) trains, each step
as ``Trainer.fit`` runs it: the host batch of that step, ``jnp.asarray``, the
step, and the loss read back.  The weights are the benchmark's, drawn from
the seed on the device in one jitted call (the reference may take nothing the
program made), in the program's own tree and type.

Set-up drives the first ``checked_steps`` steps through that same call, and
keeps what the comparison needs: their losses, the first gradient as the
optimizer holds it (its first moment over 1 - b1) and the parameters before
the next step.  The window goes on from there.  ``train_tokens_per_s`` is the
tokens of the steps completed in the window over the window.  The notes also
give the compiled step's ``memory_analysis()`` and the chip's
``memory_stats()`` after set-up and after the window: ``memory_peak_bytes``
reads one key of the latter.

After the window the program's state is freed and the checked steps are
compared with the plain reference (``reference/<reference>.py``), worst leaf
first; each layer's slice of a stacked parameter is a leaf of its own:

- ``loss_gap``: the largest |program - reference| of a checked step's loss;
- ``grad_gap``: |norm of the program's first gradient - the reference's|,
  over the larger of the reference's norm of that leaf and of the median leaf;
- ``grad_diff``: the norm of their difference, over the same;
- ``change_gap``: as ``grad_gap``, for the change of the parameters over the
  checked steps, leaving out the leaves whose reference gradient is under a
  thousandth of the median leaf's (they move by round-off alone, as a bias
  that softmax cancels would).
"""

from __future__ import annotations

import dataclasses
import math
import time

import numpy as np

from chipbench import flops
from chipbench.clock import span
from chipbench.tokens import markov_batch

# The control: the reference with its matrix products in float8 (e4m3).
CONTROL = "fp8"
# Published keys of a configuration file and the ModelConfig fields they set.
PUBLISHED = {
    "num_hidden_layers": "n_layers",
    "hidden_size": "d_model",
    "num_attention_heads": "n_heads",
    "num_key_value_heads": "n_kv_heads",
    "intermediate_size": "d_ff",
    "vocab_size": "vocab",
    "rms_norm_eps": "norm_eps",
    "rope_theta": "rope_theta",
    "tie_word_embeddings": "tie_embeddings",
    "qkv_bias": "qkv_bias",
}
# The published keys a file may cut under ``reduced``: depth alone.
CUTTABLE = {"num_hidden_layers"}
# What the runner needs the program's configuration to be, beside the keys above.
ARCHITECTURE = {"family": "transformer", "n_experts": 0, "attn_window": 0,
                "local_global_ratio": 0}
# Below this share of the median leaf's reference gradient, a leaf moves by round-off.
STILL = 1e-3
# The figures of the compiled step's ``memory_analysis()`` that the notes give.
MEMORY_FIGURES = ("argument_size_in_bytes", "output_size_in_bytes", "alias_size_in_bytes",
                  "temp_size_in_bytes", "generated_code_size_in_bytes")


def program_config(cfg: dict, base):
    """``base`` (a ``ModelConfig``) with the file's published keys checked and
    its depth set where ``reduced`` cuts it; raises on any other difference."""
    settable = set(cfg.get("reduced", ()))
    changes, wrong = {}, []
    for key in sorted(settable & set(PUBLISHED) - CUTTABLE):
        wrong.append(f"{key}: listed under reduced, but only depth may be cut")
    for key, field in PUBLISHED.items():
        want, have = cfg[key], getattr(base, field)
        if want == have:
            continue
        if key in settable:
            changes[field] = want
        else:
            wrong.append(f"{key}: the file states {want!r}, the program's {field} is {have!r}")
    head_dim = cfg["hidden_size"] // cfg["num_attention_heads"]
    if base.head_dim != head_dim:
        wrong.append(f"head_dim: {head_dim} published, the program's is {base.head_dim}")
    for field, want in ARCHITECTURE.items():
        if getattr(base, field) != want:
            wrong.append(f"{field}: the runner needs {want!r}, the program's is "
                         f"{getattr(base, field)!r}")
    if wrong:
        raise ValueError("the program's configuration differs from the file: " + "; ".join(wrong))
    return dataclasses.replace(base, **changes)


def reference_layout(tree: dict, vocab: int) -> dict:
    """The program's parameter tree as the reference lays it out: the
    vocabulary's rows of the embedding, and each layer's arrays in one flat
    dict of stacked arrays."""
    layers = tree["layers"]
    flat = {"norm1": layers["norm1"], "norm2": layers["norm2"]}
    flat.update(layers["attn"])
    flat.update(layers["mlp"])
    out = {"embed": tree["embed"][:vocab], "final_norm": tree["final_norm"], "layers": flat}
    if "lm_head" in tree:
        out["lm_head"] = tree["lm_head"][:vocab]
    return out


def leaves(tree: dict) -> dict:
    """Name -> array, one per published tensor: each layer's slice apart."""
    out = {k: np.asarray(v) for k, v in tree.items() if k != "layers"}
    for k, v in tree["layers"].items():
        for i in range(v.shape[0]):
            out[f"layers.{i}.{k}"] = np.asarray(v[i])
    return out


def _norm(a, b=None) -> float:
    """The norm of ``a`` (or of ``a - b``), summed in float64 by pieces."""
    a = np.asarray(a).reshape(-1)
    b = None if b is None else np.asarray(b).reshape(-1)
    step, total = 1 << 24, 0.0
    for i in range(0, a.size, step):
        d = a[i:i + step].astype(np.float64)
        if b is not None:
            d -= b[i:i + step]
        total += float(np.dot(d, d))
    return math.sqrt(total)


def compare(prog: dict, ref: dict) -> dict:
    """The numbers a run is judged by (module docstring)."""
    loss_gap = max(abs(p - r) for p, r in zip(prog["losses"], ref["losses"], strict=True))
    pg, rg = leaves(prog["grad1"]), leaves(ref["grad1"])
    pc, rc = leaves(prog["change"]), leaves(ref["change"])
    rg_norm = {k: _norm(v) for k, v in rg.items()}
    g_med = float(np.median(list(rg_norm.values())))
    grad_gap = grad_diff = 0.0
    for k, r in rg_norm.items():
        base = max(r, g_med)
        grad_gap = max(grad_gap, abs(_norm(pg[k]) - r) / base)
        grad_diff = max(grad_diff, _norm(pg[k], rg[k]) / base)
    moving = [k for k in rc if rg_norm[k] >= STILL * g_med]
    rc_norm = {k: _norm(rc[k]) for k in moving}
    c_med = float(np.median(list(rc_norm.values())))
    change_gap = max(abs(_norm(pc[k]) - r) / max(r, c_med) for k, r in rc_norm.items())
    return {"loss_gap": loss_gap, "grad_gap": grad_gap, "grad_diff": grad_diff,
            "change_gap": change_gap}


def _key(seed: int):
    """A threefry key from all 64 bits of the seed."""
    return np.array([(seed >> 32) & 0xFFFFFFFF, seed & 0xFFFFFFFF], np.uint32)


def _weights(jax, shapes, key_data):
    """The benchmark's weights in the program's tree: matrices and embedding
    N(0, 0.02) (``initializer_range``), biases and norm offsets N(0, 0.02) so
    that they take part; one key per leaf, folded from its position."""
    leaves, treedef = jax.tree.flatten(shapes)
    out = [(0.02 * jax.random.normal(jax.random.fold_in(key_data, i), s.shape)).astype(s.dtype)
           for i, s in enumerate(leaves)]
    return jax.tree.unflatten(treedef, out)


class Runner:
    def __init__(self, cell, seed: int, devices, jax, limits: dict):
        self.cell, self.seed, self.devices, self.jax = cell, seed, devices, jax
        self.cfg, self.traffic, self.limits = cell.config, cell.traffic, limits
        self.failed = 0
        self.times = []                # (batch, whole step) seconds of each step
        self.wrap_step = None          # a test's fault, planted under the timed step
        self.wrap_model_config = None  # a test's fault, planted in the program's configuration

    # -- traffic ------------------------------------------------------------

    def batch(self, step: int) -> dict:
        tr = self.traffic
        return markov_batch(self.seed, step, tr["batch"], tr["seq_len"],
                            self.cfg["vocab_size"], tr["branching"])

    def _step(self, step: int) -> float:
        jnp = self.jax.numpy
        t0 = time.perf_counter()
        with span("batch"):
            batch = {k: jnp.asarray(v) for k, v in self.batch(step).items()}
        t1 = time.perf_counter()
        with span("step"):
            self.params, self.opt, self.err, metrics = self.step_fn(
                self.params, self.opt, batch, self.err)
        with span("loss"):
            loss = float(metrics["loss"])
        self.times.append((t1 - t0, time.perf_counter() - t0))
        if not math.isfinite(loss):
            self.failed += 1
        return loss

    # -- set-up, window, release -----------------------------------------------

    def setup(self) -> None:
        jax = self.jax
        from repro.configs import scaled_config
        from repro.optim import AdamWConfig
        from repro.runtime.trainer import Trainer, TrainerConfig

        base = scaled_config(self.cfg["program_config"], self.cfg.get("program_scale", "full"))
        model_cfg = program_config(self.cfg, base)
        if self.wrap_model_config is not None:
            model_cfg = self.wrap_model_config(model_cfg)
        opt = self.traffic["optimizer"]
        tcfg = TrainerConfig(
            adamw=AdamWConfig(lr=opt["lr"], b1=opt["b1"], b2=opt["b2"], eps=opt["eps"],
                              weight_decay=opt["weight_decay"], grad_clip=opt["grad_clip"]),
            warmup=opt["warmup"], total_steps=opt["total_steps"])
        if opt["min_lr_ratio"] != 0.1:  # warmup_cosine's own floor, which Trainer does not set
            raise ValueError("the program's schedule ends at 0.1 of lr")
        self.trainer = Trainer(model_cfg, tcfg)
        self.step_fn = self.trainer._step_fn
        if self.wrap_step is not None:
            self.step_fn = self.wrap_step(self.step_fn)

        program_key = jax.random.PRNGKey(0)
        shapes = jax.eval_shape(self.trainer.init_state, program_key)[0]
        self.make_weights = jax.jit(lambda k: _weights(jax, shapes, k))
        program_params, self.opt, self.err = jax.jit(self.trainer.init_state)(program_key)
        jax.tree.map(lambda a: a.delete(), program_params)
        self.params = self.make_weights(_key(self.seed))

        b1 = opt["b1"]
        self.losses = []
        for step in range(self.traffic["checked_steps"]):
            self.losses.append(self._step(step))
            if step == 0:
                self.grad1 = jax.device_get(self.opt["m"])
                self.grad1 = jax.tree.map(lambda m: m / np.float32(1 - b1), self.grad1)
        self.params_after = jax.device_get(self.params)
        self.next_step = self.traffic["checked_steps"]
        self.memory_notes = self._memory_notes()
        self.flops_per_step = flops.train_step_flops(self.cfg, self.traffic["batch"],
                                                     self.traffic["seq_len"])

    def _memory_notes(self) -> list[str]:
        """The compiled step's own account of its memory, beside the device's
        allocator statistics, which ``memory_peak_bytes`` reads one key of."""
        jnp = self.jax.numpy
        batch = {k: jnp.asarray(v) for k, v in self.batch(0).items()}
        compiled = self.trainer._step_fn.lower(self.params, self.opt, batch, self.err).compile()
        analysis = compiled.memory_analysis()
        figures = {f: getattr(analysis, f, None) for f in MEMORY_FIGURES}
        return [f"the compiled step's memory_analysis: {figures}",
                f"device memory_stats after set-up: {self.devices[0].memory_stats()}"]

    def window(self, seconds: float, trace_dir=None) -> dict:
        jax = self.jax
        tr = self.traffic
        steps = tr["trace_steps"] if trace_dir is not None else None
        if trace_dir is not None:
            jax.profiler.start_trace(str(trace_dir))
        done = 0
        self.times.clear()
        t0 = time.perf_counter()
        with span("window"):
            while True:
                self._step(self.next_step)
                self.next_step += 1
                done += 1
                elapsed = time.perf_counter() - t0
                if (steps is not None and done >= steps) or (steps is None and elapsed >= seconds):
                    break
        if trace_dir is not None:
            jax.profiler.stop_trace()
        tokens = done * tr["batch"] * tr["seq_len"]
        batch_s, step_s = ([float(q) for q in np.quantile([t[i] for t in self.times], [0, 0.5, 1])]
                           for i in (0, 1))
        return {
            "attempted": done,
            "failed": self.failed,
            "train_tokens_per_s": tokens / elapsed,
            "steps": done,
            "flops_per_step": self.flops_per_step,
            "notes": [f"window: {done} steps of {tr['batch']} x {tr['seq_len']} tokens in "
                      f"{elapsed} s, {elapsed / done} s a step",
                      f"step seconds, least / median / most: {step_s}; of which the "
                      f"host batch: {batch_s}",
                      f"checked steps' losses: {self.losses}",
                      f"model FLOPs a step: {self.flops_per_step}",
                      "generator lateness: none, a closed loop (each step waits for the last)",
                      *self.memory_notes,
                      f"device memory_stats after the window: {self.devices[0].memory_stats()}"],
        }

    def release(self) -> None:
        jax = self.jax
        for tree in (self.params, self.opt, self.err):
            jax.tree.map(lambda a: a.delete(), tree)
        self.params = self.opt = self.err = None
        w0 = self.make_weights(_key(self.seed))
        before = jax.device_get(w0)
        jax.tree.map(lambda a: a.delete(), w0)
        change = jax.tree.map(lambda a, b: a.astype(np.float32) - b.astype(np.float32),
                              self.params_after, before)
        vocab = self.cfg["vocab_size"]
        self.readings = {"losses": self.losses,
                         "grad1": reference_layout(self.grad1, vocab),
                         "change": reference_layout(change, vocab)}
        self.grad1 = self.params_after = None

    def reference(self, precision: str = "exact") -> dict:
        ref = self.cell.reference()
        w0 = reference_layout(self.make_weights(_key(self.seed)), self.cfg["vocab_size"])
        batches = [self.batch(k) for k in range(self.traffic["checked_steps"])]
        return ref.train(w0, batches, self.traffic["optimizer"], self.cfg, precision)

    def check(self) -> dict:
        numbers = compare(self.readings, self.reference())
        return {k: {"value": v, "limit": self.limits[k]} for k, v in numbers.items()
                if k in self.limits}
