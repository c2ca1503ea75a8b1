"""Comparing the states of two XLA programs that run the same training
steps (the fused superstep scan and the per-dispatch loop)."""

import jax
import numpy as np

# of a leaf's largest magnitude: ten times the largest difference seen
# between the two programs on this image (8.8e-7 of a leaf's largest, in an
# Adam moment; 2 ulp of a float32 loss)
BOUND = 1e-5


def assert_same_steps(got, want):
    """``got`` and ``want`` (pytrees of arrays) came out of the same
    optimizer steps.  Integer and boolean leaves (step counters, schedule
    counts) are equal; a float leaf's elements differ by at most ``BOUND``
    of the leaf's largest magnitude.

    That two compiled programs round alike is no contract of the compiler
    (the scan fuses what the single step does not), so floats are not held
    bit for bit, and not element by element either: an element that is
    the small difference of large terms is off by 2e-3 of itself while the
    leaf is right to seven digits.  The bound still catches what these
    comparisons are for: a skipped micro-batch, a lost accumulation or a
    schedule count off by one moves a leaf by the order of the learning
    rate (1e-3 of its scale and up at the tests' sizes), not by
    rounding."""
    got, want = jax.tree.leaves(got), jax.tree.leaves(want)
    assert len(got) == len(want)
    for a, b in zip(got, want):
        a, b = np.asarray(a), np.asarray(b)
        assert a.dtype == b.dtype and a.shape == b.shape
        if np.issubdtype(a.dtype, np.floating):
            np.testing.assert_allclose(
                a, b, rtol=0, atol=BOUND * float(np.abs(b).max()))
        else:
            np.testing.assert_array_equal(a, b)
