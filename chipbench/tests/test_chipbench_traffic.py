"""The traffic generator is a function of the seed, and draws what it states."""

import numpy as np

from chipbench.generate import payload

BIG = 2**33 + 12345


def test_payload_is_integers_in_range_and_seeded():
    x = payload(BIG, 3, (16, 64), 4096)
    assert x.dtype == np.float32 and np.array_equal(x, np.round(x))
    assert x.min() >= -4096 and x.max() < 4096
    assert np.array_equal(x, payload(BIG, 3, (16, 64), 4096))
    assert not np.array_equal(x, payload(BIG, 4, (16, 64), 4096))
    assert abs(float(x.mean())) < 4096 * 0.1  # uniform around 0
