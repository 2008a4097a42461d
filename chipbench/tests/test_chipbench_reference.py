"""The plain reference of the collective mix against results worked by hand."""

import numpy as np
import pytest

from chipbench.reference import collectives as ref

# four members, two rows of one column each: member i holds [i, 10 + i]
X = np.arange(4, dtype=np.float64)[:, None, None] + np.array([[0.0], [10.0]])
SUM = np.array([[6.0], [46.0]])  # 0+1+2+3, 10+11+12+13


def test_broadcast_copies_the_root():
    out = ref.apply("broadcast", X, root=1)
    assert np.array_equal(out, np.stack([X[1]] * 4))


def test_all_reduce_gives_everyone_the_mean():
    out = ref.apply("all_reduce", X, root=1)
    assert np.array_equal(out, np.stack([SUM / 4] * 4))


def test_reduce_scatter_gives_member_i_block_i_repeated():
    x = np.arange(32, dtype=np.float64).reshape(4, 8, 1)  # 8 rows: 4 blocks of 2
    out = ref.apply("reduce_scatter", x, root=0)
    total = x.sum(axis=0) / 4
    for i in range(4):
        assert np.array_equal(out[i], np.concatenate([total[2 * i:2 * i + 2]] * 4))


@pytest.mark.parametrize("op", ["broadcast", "all_reduce", "reduce_scatter"])
def test_settled_is_a_fixed_point(op):
    x = np.random.default_rng(0).integers(-4096, 4096, size=(4, 8, 3)).astype(np.float64)
    once = ref.settled(op, x, root=1)
    assert np.array_equal(ref.apply(op, once, root=1), once)


def test_bf16_control_rounds_what_float32_keeps():
    x = np.full((4, 4, 1), 4095.0)
    x[0] = 1.0
    exact = ref.apply("all_reduce", x, root=0)
    assert not np.array_equal(ref.apply("all_reduce", x, root=0, precision="bf16"), exact)
