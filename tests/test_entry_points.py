"""Entry-point plumbing: config scales, the compile cache, sharding
constraints that refuse to fail quietly, and benchmark children pinned to
the CPU."""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import PartitionSpec as P

from repro.configs import SCALES, get_config, get_smoke_config, scaled_config
from repro.launch import cache
from repro.sharding import constrain


# -- scaled_config ------------------------------------------------------------


@pytest.mark.parametrize("scale", SCALES)
def test_scaled_config_shared_by_serve_and_train(scale):
    from repro.launch import serve, train

    cfg = scaled_config("qwen1_5_0_5b", scale)
    assert serve.scaled_config is train.scaled_config is scaled_config
    if scale == "full":
        assert cfg == get_config("qwen1_5_0_5b")
        assert (cfg.n_layers, cfg.d_model, cfg.vocab) == (24, 1024, 151936)
    elif scale == "smoke":
        assert cfg == get_smoke_config("qwen1_5_0_5b")
    else:
        assert cfg.d_model == 512 and cfg.family == "transformer"


def test_scaled_config_rejects_unknown_scale():
    with pytest.raises(ValueError, match="unknown scale"):
        scaled_config("qwen1_5_0_5b", "huge")


def test_serve_entry_point_smoke(monkeypatch, tmp_path, capsys):
    """``launch/serve.py --scale smoke`` serves; with the cache directory set
    in the environment the helper changes no JAX setting."""
    from repro.launch import serve

    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setattr(sys, "argv", ["serve", "--scale", "smoke", "--requests", "2",
                                      "--max-new", "3", "--prompt-len", "4"])
    serve.main()
    out = capsys.readouterr().out
    assert f"compile cache: {tmp_path} (from {cache.ENV_VAR})" in out
    assert "served 2 requests, 6 tokens" in out
    assert jax.config.jax_compilation_cache_dir == before


# -- compile cache ------------------------------------------------------------


def test_compile_cache_honours_environment(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(cache.ENV_VAR, str(tmp_path / "from_env"))
    assert cache.use_compile_cache() == (str(tmp_path / "from_env"), True)
    assert jax.config.jax_compilation_cache_dir == before  # left to JAX


def test_compile_cache_defaults_to_fixed_in_checkout_path(monkeypatch):
    from jax.experimental.compilation_cache import compilation_cache

    monkeypatch.delenv(cache.ENV_VAR, raising=False)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    before = jax.config.jax_compilation_cache_dir
    try:
        path, from_env = cache.use_compile_cache()
        assert not from_env
        assert path == os.path.join(repo, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
        assert cache.use_compile_cache()[0] == path  # same path on every call
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
        compilation_cache.reset_cache()


# -- constrain ------------------------------------------------------------------


def test_constrain_is_identity_without_a_mesh():
    x = jnp.ones((4, 8))
    assert constrain(x, P("data", None)) is x
    assert constrain(x, P("no_such_axis")) is x
    assert constrain(x, None) is x


def test_constrain_raises_on_a_bad_spec_under_a_mesh():
    mesh = jax.make_mesh((1,), ("data",), axis_types=(jax.sharding.AxisType.Auto,))
    x = jnp.ones((4, 8))
    with jax.set_mesh(mesh):
        ok = jax.jit(lambda v: constrain(v, P("data", None)))(x)
        assert ok.shape == x.shape
        with pytest.raises(ValueError, match="model"):
            jax.jit(lambda v: constrain(v, P("model", None)))(x)


# -- no global state at import ---------------------------------------------------


def test_dryrun_and_hillclimb_set_no_flags_at_import():
    code = ("import os, repro.launch.dryrun, repro.launch.hillclimb; "
            "print(os.environ.get('XLA_FLAGS'))")
    env = {k: v for k, v in os.environ.items() if k != "XLA_FLAGS"}
    env["JAX_PLATFORMS"] = "cpu"
    out = subprocess.run([sys.executable, "-c", code], capture_output=True, text=True,
                         timeout=300, env=env, check=True).stdout
    assert out.strip() == "None"


# -- benchmark children -------------------------------------------------------------


@pytest.mark.parametrize("module", ["benchmarks.bench_summa", "benchmarks.bench_schedule_hlo"])
def test_bench_child_is_pinned_to_cpu_and_its_failure_fails(module, monkeypatch):
    import importlib

    bench = importlib.import_module(module)
    seen = {}

    def failed_child(cmd, **kw):
        seen["env"] = kw["env"]
        return subprocess.CompletedProcess(cmd, 1, stdout="", stderr="boom")

    monkeypatch.setattr(bench.subprocess, "run", failed_child)
    with pytest.raises(RuntimeError, match="rc=1"):
        bench.rows()
    assert seen["env"]["JAX_PLATFORMS"] == "cpu"
