"""The draw of a block-diffusion step (``decode/sampler.py``): the tokens of
``gumbel_topk_sample_batched`` bit for bit, the drawn token's probability
under the filtered distribution, and the two remasking rules on planted
confidences."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.decode import sampler

B, V = 12, 97


def _rows(seed=0):
    ks = jax.random.split(jax.random.key(seed), 3)
    logits = jax.random.normal(ks[0], (B, V)) * 3
    keys = jax.random.split(ks[1], B)
    top_k = jnp.asarray([0, 1, 5, 25, 97, 200, 5, 5, 0, 3, 25, 2])
    temp = jnp.asarray([1.0, 1.0, 0.7, 1.0, 2.0, 1.0, 0.0, 0.0, 0.0, 1.3,
                        1.0, 1.0])
    mask = jax.random.bernoulli(ks[2], 0.8, (B, V)).at[:, 3].set(True)
    return keys, logits, top_k, temp, mask


@pytest.mark.parametrize("masked", [False, True])
def test_tokens_are_the_batched_draws_bit_for_bit(masked):
    keys, logits, top_k, temp, mask = _rows()
    mask = mask if masked else None
    want = sampler.gumbel_topk_sample_batched(keys, logits, top_k, temp,
                                              mask=mask)
    got, conf = jax.jit(sampler.gumbel_topk_sample_with_confidence)(
        keys, logits, top_k, temp, mask)
    np.testing.assert_array_equal(got, want)
    assert conf.dtype == jnp.float32 and conf.shape == (B,)
    assert bool(((conf > 0) & (conf <= 1)).all())


def test_confidence_is_a_softmax_over_the_kept_logits():
    keys, logits, top_k, temp, mask = _rows(1)
    tokens, conf = sampler.gumbel_topk_sample_with_confidence(
        keys, logits, top_k, temp, mask)
    logits, mask = np.asarray(logits, np.float64), np.asarray(mask)
    for i in range(B):
        t = float(temp[i]) or 1.0       # a greedy row reads temperature 1
        row = np.where(mask[i], logits[i], -np.inf) / t
        k = int(top_k[i])
        if 0 < k < V:
            row = np.where(row >= np.sort(row)[-k], row, -np.inf)
        p = np.exp(row - row.max())
        p /= p.sum()
        assert mask[i, int(tokens[i])]
        np.testing.assert_allclose(float(conf[i]), p[int(tokens[i])],
                                   rtol=2e-5)
        if float(temp[i]) == 0.0:
            assert int(tokens[i]) == int(np.argmax(row))
            np.testing.assert_allclose(float(conf[i]), p.max(), rtol=2e-5)


def test_the_static_rule_counts_per_forward():
    assert sampler.transfer_counts(4, 2) == (2, 2)
    assert sampler.transfer_counts(4, 4) == (1, 1, 1, 1)
    assert sampler.transfer_counts(4, 3) == (2, 1, 1)
    assert sampler.transfer_counts(8, 3) == (3, 3, 2)
    assert sampler.transfer_counts(4, 1) == (4,)


def _take(conf, masked, count, threshold=None):
    return np.asarray(sampler.confident_positions(
        jnp.asarray(conf, jnp.float32), jnp.asarray(masked, bool),
        jnp.asarray(count, jnp.int32), threshold)).tolist()


def test_static_rule_on_planted_confidences():
    masked = [[True] * 4] * 3
    conf = [[0.1, 0.4, 0.3, 0.2],       # the two highest
            [0.5, 0.5, 0.5, 0.5],       # ties go to the lower index
            [0.2, 0.9, 0.9, 0.1]]
    assert _take(conf, masked, [2, 2, 1]) == [
        [False, True, True, False], [True, True, False, False],
        [False, True, False, False]]


def test_a_block_with_prompt_tokens_never_redraws_them():
    # the held positions carry the HIGHEST confidences: they are not taken,
    # and a row with fewer masked positions than the count takes them all
    conf = [[0.9, 0.8, 0.1, 0.2], [0.9, 0.9, 0.9, 0.3], [0.5] * 4]
    masked = [[False, False, True, True], [False, False, False, True],
              [False] * 4]
    assert _take(conf, masked, [1, 2, 2]) == [
        [False, False, False, True], [False, False, False, True],
        [False] * 4]
    assert _take(conf, masked, [1, 2, 2], 0.05) == [
        [False, False, True, True], [False, False, False, True], [False] * 4]


def test_dynamic_rule_takes_all_over_the_threshold_and_never_fewer():
    masked = [[True] * 4] * 3
    conf = [[0.95, 0.2, 0.91, 0.97],    # three pass
            [0.3, 0.2, 0.1, 0.25],      # a threshold nothing passes
            [0.9, 0.95, 0.1, 0.2]]      # exactly AT the threshold: not over
    assert _take(conf, masked, [1, 1, 1], 0.9) == [
        [True, False, True, True], [True, False, False, False],
        [False, True, False, False]]
    assert _take(conf, masked, [2, 2, 2], 0.9)[1] == [
        True, False, False, True]
