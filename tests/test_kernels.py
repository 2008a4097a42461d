"""Pallas kernels vs pure-jnp oracles: shape/dtype sweeps + hypothesis.

Off the TPU the kernels' ``interpret=None`` default resolves to the Pallas
interpreter; on a TPU the same pallas_call lowers to Mosaic
(tests/test_tpu_compile.py compiles them for a described v5e).
"""

import inspect

import jax
import jax.numpy as jnp
import numpy as np
import pytest
pytest.importorskip("hypothesis")
from hypothesis import given, settings, strategies as st

from repro.kernels import ref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.gemm import gemm
from repro.kernels.reduce_nway import reduce_nway
from repro.kernels.rglru import rglru_scan
from repro.kernels.rwkv6 import wkv


def _rand(key, shape, dtype):
    return (jax.random.normal(jax.random.PRNGKey(key), shape) * 0.5).astype(dtype)


# -- GEMM --------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("shape", [(32, 32, 32), (64, 32, 16), (16, 48, 64)])
def test_gemm_matches_ref(shape, dtype):
    M, K, N = shape
    a, b = _rand(0, (M, K), dtype), _rand(1, (K, N), dtype)
    out = gemm(a, b, bm=16, bn=16, bk=16)
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.gemm_ref(a, b), np.float32),
                               rtol=tol, atol=tol)


def test_gemm_accumulate_epilogue():
    """The DCA analogue: C_out = C_in + A @ B reduced by the consumer."""
    a, b = _rand(0, (32, 32), jnp.float32), _rand(1, (32, 32), jnp.float32)
    c = _rand(2, (32, 32), jnp.float32)
    out = gemm(a, b, c, bm=16, bn=16, bk=16, accumulate=True)
    np.testing.assert_allclose(np.asarray(out),
                               np.asarray(ref.gemm_ref(a, b, c, accumulate=True)),
                               rtol=1e-5, atol=1e-5)


@given(
    m=st.sampled_from([16, 32, 48]),
    k=st.sampled_from([16, 32]),
    n=st.sampled_from([16, 32]),
    bk=st.sampled_from([8, 16]),
)
@settings(max_examples=10, deadline=None)
def test_gemm_property_tilings(m, k, n, bk):
    a, b = _rand(3, (m, k), jnp.float32), _rand(4, (k, n), jnp.float32)
    out = gemm(a, b, bm=16, bn=16, bk=bk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.gemm_ref(a, b)),
                               rtol=1e-5, atol=1e-5)


# -- N-way reduction ----------------------------------------------------------


@pytest.mark.parametrize("op,dtype", [("add", jnp.float32), ("max", jnp.float32),
                                      ("and", jnp.int32)])
def test_reduce_nway(op, dtype):
    if dtype == jnp.int32:
        x = jax.random.randint(jax.random.PRNGKey(0), (5, 256), 0, 2).astype(dtype)
    else:
        x = _rand(0, (5, 256), dtype)
    out = reduce_nway(x, op=op, bs=128)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(ref.reduce_nway_ref(x, op), np.float32),
                               rtol=1e-5, atol=1e-5)


def test_reduce_nway_lsb_and_barrier_semantics():
    """LsbAnd: result is 1 iff every participant has arrived (bit set)."""
    arrived = jnp.ones((8, 128), jnp.int32)
    missing = arrived.at[3].set(0)
    assert int(reduce_nway(arrived, op="and", bs=128)[0]) == 1
    assert int(reduce_nway(missing, op="and", bs=128)[0]) == 0


# -- flash attention -----------------------------------------------------------


@pytest.mark.parametrize("window", [0, 32])
@pytest.mark.parametrize("S,d", [(128, 32), (256, 16)])
def test_flash_attention(S, d, window):
    q, k, v = (_rand(i, (4, S, d), jnp.float32) for i in range(3))
    out = flash_attention(q, k, v, window=window, bq=64, bkv=64)
    expected = ref.flash_attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


@given(bq=st.sampled_from([32, 64, 128]), bkv=st.sampled_from([32, 64]))
@settings(max_examples=6, deadline=None)
def test_flash_attention_block_shape_invariance(bq, bkv):
    q, k, v = (_rand(i + 10, (2, 128, 16), jnp.float32) for i in range(3))
    out = flash_attention(q, k, v, bq=bq, bkv=bkv)
    expected = ref.flash_attention_ref(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-4, atol=2e-4)


# -- RG-LRU scan ---------------------------------------------------------------


@pytest.mark.parametrize("chunk", [32, 64, 128])
def test_rglru_scan(chunk):
    B, S, W = 2, 128, 16
    a = jax.nn.sigmoid(_rand(0, (B, S, W), jnp.float32))  # decay in (0,1)
    b = _rand(1, (B, S, W), jnp.float32)
    out = rglru_scan(a, b, chunk=chunk)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref.rglru_scan_ref(a, b)),
                               rtol=1e-4, atol=1e-4)


def test_rglru_matches_model_associative_scan():
    from repro.models.rglru import _lru_scan

    B, S, W = 2, 64, 8
    a = jax.nn.sigmoid(_rand(2, (B, S, W), jnp.float32))
    b = _rand(3, (B, S, W), jnp.float32)
    np.testing.assert_allclose(np.asarray(rglru_scan(a, b, chunk=32)),
                               np.asarray(_lru_scan(a, b)), rtol=1e-4, atol=1e-4)


# -- RWKV-6 WKV ----------------------------------------------------------------


@pytest.mark.parametrize("chunk,S", [(16, 64), (32, 64), (64, 128)])
def test_wkv_matches_sequential_ref(chunk, S):
    BH, hd = 3, 16
    r, k, v = (_rand(i, (BH, S, hd), jnp.float32) for i in range(3))
    logw = -jnp.exp(jnp.clip(_rand(3, (BH, S, hd), jnp.float32) - 2.0, -8, 1))
    u = _rand(4, (BH, hd), jnp.float32)
    out = wkv(r, k, v, logw, u, chunk=chunk)
    expected = ref.wkv_ref(r, k, v, logw, u)
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected),
                               rtol=2e-3, atol=2e-3)


def test_wkv_matches_model_chunked():
    from repro.models.rwkv6 import chunked_wkv

    B, S, H, hd = 2, 64, 2, 16
    r, k, v = (_rand(i + 20, (B, S, H, hd), jnp.float32) for i in range(3))
    logw = -jnp.exp(jnp.clip(_rand(23, (B, S, H, hd), jnp.float32) - 2.0, -8, 1))
    u = _rand(24, (H, hd), jnp.float32)
    out_model, _ = chunked_wkv(r, k, v, logw, u, jnp.zeros((B, H, hd, hd)))
    rk = r.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kk = k.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    vk = v.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    lw = logw.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    uu = jnp.tile(u, (B, 1))
    out_k = wkv(rk, kk, vk, lw, uu, chunk=32)
    out_k = out_k.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
    np.testing.assert_allclose(np.asarray(out_k), np.asarray(out_model),
                               rtol=2e-3, atol=2e-3)


# -- backend-chosen interpret mode and the shapes Mosaic once refused -----------

KERNELS = {"flash_attention": flash_attention, "gemm": gemm,
           "reduce_nway": reduce_nway, "rglru_scan": rglru_scan, "wkv": wkv}


@pytest.mark.parametrize("name", sorted(KERNELS))
def test_interpret_default_is_chosen_by_backend(name):
    from repro.kernels.ops import default_interpret

    assert inspect.signature(KERNELS[name]).parameters["interpret"].default is None
    assert default_interpret() == (jax.default_backend() != "tpu")


# the model-width cases of kernels/widths.py at the shapes Mosaic once
# refused, run by the interpreter with element-wise tolerances:
#   rglru_scan  width 2560 (RecurrentGemma-2B), chunk 128: the in-kernel
#               associative_scan made a zero-size slice
#   wkv         BH=40 heads (RWKV6-3B), hd=64: the (1, hd) block of u broke
#               the second-minor tiling rule
#   reduce_nway 4 x 8192 f32 with bs=512: the 1-D (512,) out block did not
#               match the TPU layout of f32[8192]
ONCE_REFUSED = {"rglru_scan": ("rglru_scan", 1e-4), "wkv": ("wkv", 2e-3),
                "reduce_nway": ("reduce_nway_add", 1e-5)}


@pytest.mark.parametrize("name", list(ONCE_REFUSED))
def test_kernel_at_once_refused_shape(name):
    from repro.kernels.widths import MODEL_WIDTH

    case, tol = MODEL_WIDTH[ONCE_REFUSED[name][0]], ONCE_REFUSED[name][1]
    args = case.make_inputs(jax.random.PRNGKey(3))
    out, expected = case.kernel(*args), case.reference(*args)
    assert out.shape == expected.shape
    np.testing.assert_allclose(np.asarray(out), np.asarray(expected), rtol=tol, atol=tol)
