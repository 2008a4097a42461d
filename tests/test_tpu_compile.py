"""Compile the main path for a described TPU v5e, with no chip attached.

The TPU compiler is installed with JAX and compiles for a chip that is
described, not present: what it refuses here (a block that breaks the
tiling rules, a primitive Mosaic cannot lower, a program that does not fit
the chip's memory) would be refused on the chip.  Nothing runs, so these
tests say nothing about results or time.

The topology is described inside a module-scoped fixture, never at import:
only one process at a time may load the TPU library, and under pytest-xdist
every worker imports this file while only the worker given it runs it.
Keep every such compile in this one file.
"""

from functools import partial

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import scaled_config
from repro.kernels.widths import MODEL_WIDTH
from repro.models import get_family

V5E_HBM_BYTES = 16 * 2 ** 30


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")  # else the compiler logs outside the checkout
        try:
            desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
        except Exception as e:  # noqa: BLE001 — any failure to describe means no TPU compiler
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        yield desc


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _sds(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# The host backend is the CPU, so ``interpret=False`` forces the Mosaic
# lowering a TPU backend would choose.
@pytest.mark.parametrize("name", list(MODEL_WIDTH))
def test_kernel_compiles_to_mosaic_at_model_width(name, one_chip):
    case = MODEL_WIDTH[name]
    shapes = jax.eval_shape(case.make_inputs, jax.random.PRNGKey(0))
    args = [_sds(s.shape, s.dtype, one_chip) for s in shapes]
    compiled = jax.jit(partial(case.kernel, interpret=False)).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()


def _bytes_on_chip(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def test_qwen_full_width_train_step_fits_one_chip(one_chip):
    from repro.optim import AdamWConfig
    from repro.runtime import Trainer, TrainerConfig

    cfg = scaled_config("qwen1_5_0_5b", "full")
    fam = get_family(cfg)
    shapes = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), shapes)
    moment = jax.tree.map(lambda s: _sds(s.shape, jnp.float32, one_chip), shapes)
    opt = {"step": _sds((), jnp.int32, one_chip), "m": moment, "v": moment}
    batch = {k: _sds((4, 1024), jnp.int32, one_chip) for k in ("tokens", "labels")}
    err = jax.tree.map(lambda s: _sds((1,), jnp.float32, one_chip), shapes)

    trainer = Trainer(cfg, TrainerConfig(adamw=AdamWConfig(lr=1e-3), warmup=2,
                                         total_steps=10))
    compiled = trainer._step_fn.lower(params, opt, batch, err).compile()
    assert 0 < _bytes_on_chip(compiled) < V5E_HBM_BYTES


def test_qwen_full_width_serve_steps_compile(one_chip):
    from repro.runtime.server import Server

    cfg = scaled_config("qwen1_5_0_5b", "full")
    fam = get_family(cfg)
    shapes = jax.eval_shape(lambda: fam.init(jax.random.PRNGKey(0), cfg))
    params = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), shapes)
    prompt = _sds((4, 128), jnp.int32, one_chip)
    server = Server(cfg, params=None, max_len=160)
    prefill = server._prefill.lower(params, prompt).compile()
    cache = jax.eval_shape(lambda p, t: fam.prefill(p, t, cfg, max_len=160),
                           shapes, prompt)[1]
    cache = jax.tree.map(lambda s: _sds(s.shape, s.dtype, one_chip), cache)
    decode = server._decode.lower(params, cache, _sds((4, 1), jnp.int32, one_chip),
                                  _sds((), jnp.int32, one_chip)).compile()
    for compiled in (prefill, decode):
        assert 0 < _bytes_on_chip(compiled) < V5E_HBM_BYTES
