"""The train runner on the CPU at a small size: a run is correct, a run with
its timed path broken is not, and its yardsticks (tokens, FLOPs, metric
readers, the program's configuration) say what they state.

No cell of ``BENCHMARK.json`` runs the train runner yet, so the tests build
one here: the ``train-4k`` mix over the program's Qwen1.5 smoke configuration
(float32), at 2 x 32 tokens a step.  The runs drive the whole of a run but the
look for a chip.
"""

import argparse
import dataclasses
import gzip
import json
import pathlib

import numpy as np
import pytest

from chipbench import bench, device, flops, trace
from chipbench.tokens import markov_batch

SEED = 2**33 + 91
DATA = pathlib.Path(__file__).resolve().parent / "data"
# The program's smoke configuration of Qwen1.5-0.5B, stated as a file states it.
SMOKE = {"name": "qwen1.5-0.5b-smoke", "reference": "dense_lm",
         "program_config": "qwen1.5-0.5b", "program_scale": "smoke",
         "num_hidden_layers": 2, "hidden_size": 64, "num_attention_heads": 4,
         "num_key_value_heads": 4, "intermediate_size": 128, "vocab_size": 256,
         "rms_norm_eps": 1e-06, "rope_theta": 10000.0, "tie_word_embeddings": True,
         "qkv_bias": True, "initializer_range": 0.02, "reduced": []}
# Qwen1.5-0.5B's published widths (huggingface.co/Qwen/Qwen1.5-0.5B, config.json).
QWEN = dict(SMOKE, num_hidden_layers=24, hidden_size=1024, num_attention_heads=16,
            num_key_value_heads=16, intermediate_size=2816, vocab_size=151936,
            rope_theta=1000000.0)
# float32 against float32 at this size: the clean run reads under 1e-6 on each
# number; the fp8 control 9.4e-4, 7.4e-3, 0.089 and 3.3e-3, and each fault more
# than 1e-2 on at least one (``test_train_control_reads_far_above_the_program``
# prints the program's and the control's).
LIMITS = {"loss_gap": 1e-4, "grad_gap": 1e-3, "grad_diff": 1e-2, "change_gap": 1e-2}
METRIC = {"source": "host_clock", "better": "higher"}


def smoke_cell():
    traffic = json.loads((bench.HERE / "traffic" / "train-4k.json").read_text())
    return bench.Cell(
        name="qwen1.5-0.5b-smoke.train-4k", chips=1,
        config_name=SMOKE["name"], config=SMOKE,
        traffic_name="train-4k", traffic=dict(traffic, seq_len=32, trace_steps=1),
        end_to_end=({"name": "setup_s", "unit": "s", **METRIC},
                    {"name": "train_tokens_per_s", "unit": "tokens/s", **METRIC}),
        per_layer=({"name": "mfu.train", "unit": "%", **METRIC},
                   {"name": "idle_share.train", "unit": "%", **METRIC}))


@pytest.fixture(scope="module")
def train_runs():
    """The clean run, each planted fault and the control, in one process."""
    import jax

    from chipbench import run as bench_run
    from chipbench.faults import FAULTS
    from chipbench.study import readings

    cell = smoke_cell()
    args = argparse.Namespace(workload=cell.name, seed=SEED, seconds=0.2, trace=0)
    out = {"clean": bench_run.run(args, cell=cell, limits=LIMITS, require_chip=False)}
    for name, hook in FAULTS["train"].items():
        out[name] = bench_run.run(args, cell=cell, limits=LIMITS, require_chip=False,
                                  runner_hook=hook)
    out["program"], out["control"] = readings(cell, SEED, jax, jax.devices()[:1], control=True)
    return out


def test_train_run_is_correct_when_nothing_is_broken(train_runs):
    r = train_runs["clean"]
    assert r["correct"] is True and r["failed"] == 0 and r["attempted"] > 0
    assert r["metrics"]["train_tokens_per_s"]["value"] > 0
    assert set(r["checks"]) == {"loss_gap", "grad_gap", "grad_diff", "change_gap"}


@pytest.mark.parametrize("fault", ["frozen", "half_batch", "padded_vocab"])
def test_train_fault_is_caught(train_runs, fault):
    assert train_runs[fault]["correct"] is False


def test_train_control_reads_far_above_the_program(train_runs):
    prog, ctrl = train_runs["program"]["numbers"], train_runs["control"]["numbers"]
    print("program", prog, "control", ctrl)
    assert ctrl["grad_diff"] > 100 * prog["grad_diff"]
    assert all(prog[k] < limit / 10 for k, limit in LIMITS.items())
    assert any(ctrl[k] > limit for k, limit in LIMITS.items())


def test_flops_by_hand():
    # smoke: 2 layers of 4 x 64^2 + 3 x 64 x 128 matrix weights, a 256 x 64
    # head; attention 2 layers x 2 products x 2 x 64 x 32/2 keys a token
    assert flops.matmul_params(SMOKE) == 2 * (4 * 64 * 64 + 3 * 64 * 128) + 256 * 64
    assert flops.attention_flops_per_token(SMOKE, 32) == 2 * 2 * 2 * 64 * 16
    assert flops.train_step_flops(SMOKE, 2, 32) == 64 * 3 * (2 * 98304 + 8192)
    # Qwen1.5-0.5B at 2 x 4096: 0.928 GFLOP a token of matrices, 0.201 of attention, x3
    assert flops.matmul_params(QWEN) == 24 * (4 * 1024**2 + 3 * 1024 * 2816) + 151936 * 1024
    assert flops.train_step_flops(QWEN, 2, 4096) == 8192 * 3 * (2 * 463863808 + 201326592)


def test_readers_on_a_recorded_training_step():
    """A recorded step of the train cell at its own size: 1.411 s window, 1.335 s busy."""
    recorded = json.load(gzip.open(DATA / "train-4k-one-step.json.gz"))
    step_flops = 27.74e12
    record = {"trace": recorded["trace"], "reduced": trace.reduce(recorded["trace"]),
              "peaks": device.PEAKS["TPU v5 lite"], "steps": 1, "flops_per_step": step_flops}
    mfu = bench.metric_reader("mfu.train")(record)
    assert mfu == pytest.approx(100 * step_flops / (1.410912953 * 197e12))
    assert bench.metric_reader("idle_share.train")(record) == pytest.approx(
        100 * (1 - 1.335395118 / 1.410912953), rel=1e-6)
    assert bench.metric_reader("mfu.train")(dict(record, peaks={})) is None


def test_markov_batch_is_seeded_and_follows_its_chain():
    a = markov_batch(SEED, 7, 2, 64, 1000, 4)
    assert np.array_equal(a["tokens"], markov_batch(SEED, 7, 2, 64, 1000, 4)["tokens"])
    assert not np.array_equal(a["tokens"], markov_batch(SEED, 8, 2, 64, 1000, 4)["tokens"])
    assert np.array_equal(a["tokens"][:, 1:], a["labels"][:, :-1])
    assert a["tokens"].min() >= 0 and a["labels"].max() < 1000
    prev = a["tokens"].astype(np.int64)
    succ = [(prev * 2654435761 + j * 40503 + 17) % 1000 for j in range(4)]
    assert np.all(np.any([a["labels"] == s for s in succ], axis=0))


def test_program_config_sets_only_what_the_file_lists():
    from repro.configs import get_config

    runner = smoke_cell().runner()
    program = dataclasses.replace(get_config("qwen1.5-0.5b"), rope_theta=1e6)
    model = runner.program_config(dict(QWEN, num_hidden_layers=4,
                                       reduced=["num_hidden_layers"]), program)
    assert (model.n_layers, model.rope_theta, model.d_ff) == (4, 1e6, 2816)
    with pytest.raises(ValueError, match="intermediate_size"):
        runner.program_config(dict(QWEN, intermediate_size=2048), program)
    with pytest.raises(ValueError, match="rope_theta"):
        runner.program_config(QWEN, dataclasses.replace(program, rope_theta=1e4))
    with pytest.raises(ValueError, match="only depth may be cut"):
        runner.program_config(dict(QWEN, reduced=["rope_theta"]),
                              dataclasses.replace(program, rope_theta=1e4))
