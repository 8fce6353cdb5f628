"""The plain reference follows the configuration it is given: its integer
softmax, written from the paper's Alg. 1, gives the program's codes bit for
bit, and its forward pass agrees with the program's float path."""

import os
import sys

os.environ.setdefault("JAX_PLATFORMS", "cpu")
HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]

import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402
import pytest  # noqa: E402

from chipbench import check, spec  # noqa: E402

REF = check.load_reference(spec.BENCH_DIR, "decoder")


@pytest.mark.parametrize("length", [1, 7, 300, 4500])
@pytest.mark.parametrize("point", [(6, 16, -7.0), (4, 8, -4.0), (8, 20, -7.0)])
def test_integer_softmax_matches_the_program_bit_for_bit(length, point):
    from repro.core.alg1 import int_softmax_block
    from repro.core.precision import PrecisionConfig

    m, n, t_c = point
    rng = np.random.default_rng(length)
    s = jnp.asarray(rng.normal(0, 3, (2, 8, length)).astype(np.float32))
    valid = jnp.asarray(rng.random((1, 8, length)) < 0.9).at[..., 0].set(True)
    got = REF.alg1_softmax(s, valid, {"M": m, "N": n, "T_C": t_c})
    want = int_softmax_block(s, jnp.broadcast_to(valid, s.shape),
                             PrecisionConfig(M=m, N=n, T_C=t_c))
    assert np.array_equal(np.asarray(got), np.asarray(want))


def test_integer_softmax_sums_to_about_one_and_ignores_masked_keys():
    s = jnp.asarray([[[0.0, -1.0, -2.0, 50.0]]], jnp.float32)
    valid = jnp.asarray([[[True, True, True, False]]])
    p = np.asarray(REF.alg1_softmax(s, valid, {"M": 6, "N": 16,
                                                "T_C": -7.0}))
    assert p[0, 0, 3] == 0.0
    assert abs(p.sum() - 1.0) < 1e-3
    assert p[0, 0, 0] > p[0, 0, 1] > p[0, 0, 2] > 0
