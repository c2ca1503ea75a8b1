"""perf/lib/reference.py against the program's model (XLA implementations,
float32 end to end) at a tiny size: two implementations written apart must
agree to float32 rounding."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from perf.lib import reference

TINY = dict(num_tokens=256, dim=48, depth=3, heads=2, dim_head=16,
            window_size=16, seq_len=64, ff_mult=4, ff_glu=True,
            global_mlp_depth=2, shift_tokens=True)


@pytest.fixture(scope="module")
def model_and_params():
    from progen_tpu.core.precision import make_policy
    from progen_tpu.models import ProGen, ProGenConfig
    from progen_tpu.parallel import unbox

    model = ProGen(config=ProGenConfig(**TINY), policy=make_policy(False))
    toks = jnp.zeros((1, TINY["seq_len"]), jnp.int32)
    params = unbox(jax.jit(model.init)(jax.random.key(3), toks))
    # the spatial weights start near 1e-6: scale them up so that the gate's
    # token mixing is a visible part of the comparison
    for i in range(TINY["depth"]):
        sgu = params["params"][f"ff{i}"].get("sgu")
        if sgu is not None:
            sgu["spatial_weights"] = sgu["spatial_weights"] * 3e4
    return model, params


@pytest.mark.parametrize("length", [64, 32])
def test_forward_matches_the_program(model_and_params, length):
    model, params = model_and_params
    rng = np.random.default_rng(0)
    tokens = rng.integers(0, 256, (3, length)).astype(np.int32)
    want = np.asarray(model.apply(params, jnp.asarray(tokens)))
    got = np.asarray(reference.forward(params["params"], tokens, TINY))
    assert got.shape == want.shape == (3, length, 256)
    np.testing.assert_allclose(got, want, rtol=2e-4, atol=2e-4)


def test_loss_matches_the_program(model_and_params):
    from progen_tpu.train.loss import batch_loss

    model, params = model_and_params
    rng = np.random.default_rng(1)
    batch = np.zeros((4, TINY["seq_len"] + 1), np.int32)
    for i, n in enumerate((10, 30, 63, 64)):
        batch[i, 1:1 + n] = rng.integers(1, 256, n)
    logits = model.apply(params, jnp.asarray(batch[:, :-1]))
    want = float(batch_loss(logits, jnp.asarray(batch[:, 1:])))
    got = float(reference.loss(params["params"], batch, TINY))
    assert got == pytest.approx(want, rel=1e-5)


def test_first_window_sees_the_zero_window():
    """The phantom keys of window 0 carry weight: one query, one real key
    with logit 0 -> the real value gets 1 / (wsz + 1) of the mass."""
    wsz = 4
    q = jnp.zeros((1, 1, wsz, 2))
    v = jnp.ones((1, 1, wsz, 2))
    out = np.asarray(reference.window_attention(q, q, v, wsz))
    np.testing.assert_allclose(out[0, 0, 0], 1.0 / (wsz + 1), rtol=1e-6)
    np.testing.assert_allclose(out[0, 0, 3], 4.0 / (wsz + 4), rtol=1e-6)
