"""Serving subsystem tests: one-pass prefill, chunked early-exit decode,
continuous-batching engine.

The load-bearing ones:

* prefill parity — ONE parallel forward must leave byte-for-byte the
  same decode state a sequential teacher-forced scan leaves (up to f32
  reduction order), for RAGGED prime lengths in one padded batch;
* chunked = full — the chunked sampler must be BIT-identical to
  ``make_sampler`` (same key-split schedule), and stop within one chunk
  of the last live row when every row hits EOS;
* engine determinism — a request's output depends only on (params,
  prime, seed, knobs), never on slot assignment, chunk size, or what
  else is in flight.
"""

import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from progen_tpu.core.precision import make_policy
from progen_tpu.decode import (
    ProGenDecodeStep,
    Request,
    ServingEngine,
    gumbel_topk_sample,
    gumbel_topk_sample_batched,
    init_caches,
    make_chunked_sampler,
    make_prefiller,
    make_sampler,
    pad_prime_length,
    teacher_forced_logits,
)
from progen_tpu.models import ProGen, ProGenConfig
from progen_tpu.parallel import unbox

pytestmark = pytest.mark.serving

CFG = ProGenConfig(
    num_tokens=32, dim=16, seq_len=24, depth=3, window_size=4,
    global_mlp_depth=1, heads=2, dim_head=8, ff_mult=2,
)


@pytest.fixture(scope="module")
def trained():
    policy = make_policy(False)  # f32 end to end: parity mode
    model = ProGen(config=CFG, policy=policy)
    tokens = jnp.zeros((2, CFG.seq_len), jnp.int32)
    params = unbox(model.init(jax.random.key(7), tokens))
    return model, params, policy


@pytest.fixture(scope="module")
def eos_params(trained):
    """Params whose to_logits bias makes EOS (token 0) win every argmax."""
    _, params, _ = trained
    bias = params["params"]["to_logits"]["bias"]
    return {"params": {
        **params["params"],
        "to_logits": {**params["params"]["to_logits"],
                      "bias": bias.at[0].add(1e4)},
    }}


def test_pad_prime_length():
    assert pad_prime_length(1, 4, 24) == 4
    assert pad_prime_length(5, 4, 24) == 8
    assert pad_prime_length(24, 4, 24) == 24
    # bucketed: windows round to powers of two, capped at seq_len
    assert pad_prime_length(5, 4, 64, bucket=True) == 8
    assert pad_prime_length(9, 4, 64, bucket=True) == 16
    assert pad_prime_length(17, 4, 24, bucket=True) == 24
    with pytest.raises(ValueError):
        pad_prime_length(0, 4, 24)
    with pytest.raises(ValueError):
        pad_prime_length(25, 4, 24)


def test_prefill_matches_sequential_priming(trained):
    """One padded parallel prefill over RAGGED lengths == each row
    teacher-forced through the sequential decode step."""
    _, params, policy = trained
    lengths = [5, 8, 1]
    p_pad = pad_prime_length(max(lengths), CFG.window_size, CFG.seq_len)
    rng = np.random.default_rng(0)
    toks = np.zeros((len(lengths), p_pad), np.int32)
    for b, p in enumerate(lengths):
        toks[b, :p] = rng.integers(1, CFG.num_tokens, p)

    prefill = make_prefiller(CFG, policy)
    last_logits, caches = prefill(params, jnp.asarray(toks),
                                  jnp.asarray(lengths), CFG.seq_len)

    step = ProGenDecodeStep(config=CFG, policy=policy)
    for b, p in enumerate(lengths):
        ref = init_caches(CFG, 1, policy, decode_len=CFG.seq_len)
        logits = None
        for t in range(p):
            logits, ref = step.apply(params, jnp.asarray(toks[b:b + 1, t]),
                                     t, ref)
        np.testing.assert_allclose(np.asarray(last_logits[b]),
                                   np.asarray(logits[0], np.float32),
                                   rtol=1e-5, atol=1e-5)
        got = jax.tree.map(lambda x: np.asarray(x[b]), caches)
        want = jax.tree.map(lambda x: np.asarray(x[0]), ref)
        jax.tree.map(
            lambda g, w: np.testing.assert_allclose(g, w, rtol=1e-5,
                                                    atol=1e-5),
            got, want)


def test_prefill_logits_match_teacher_forcing(trained):
    """The prefill forward's per-position logits agree with the decode
    oracle at the harvested position."""
    _, params, policy = trained
    p = 8
    rng = np.random.default_rng(1)
    toks = jnp.asarray(rng.integers(1, CFG.num_tokens, (2, p)), jnp.int32)
    want = teacher_forced_logits(CFG, params, toks, policy)[:, p - 1]

    prefill = make_prefiller(CFG, policy)
    last_logits, _ = prefill(params, toks, jnp.full((2,), p, jnp.int32),
                             CFG.seq_len)
    np.testing.assert_allclose(np.asarray(last_logits), np.asarray(want),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("chunk_size", [3, 8])
def test_chunked_sampler_matches_full_scan(trained, chunk_size):
    """Same key, same knobs -> the chunked sampler's output is BIT-equal
    to ``make_sampler`` (identical key-split schedule)."""
    _, params, policy = trained
    rng = np.random.default_rng(2)
    prime = jnp.asarray(rng.integers(1, CFG.num_tokens, (2, 5)), jnp.int32)
    full = make_sampler(CFG, policy)
    chunked = make_chunked_sampler(CFG, policy, chunk_size=chunk_size)
    for top_k, temp in [(8, 0.9), (None, 1.0), (None, 0.0)]:
        key = jax.random.key(11)
        a = full(params, key, prime, length=20, top_k=top_k,
                 temperature=temp, add_bos=True)
        b = chunked(params, key, prime, length=20, top_k=top_k,
                    temperature=temp, add_bos=True)
        assert jnp.array_equal(a, b), (top_k, temp)


def test_chunked_sampler_early_exit(trained, eos_params):
    """All rows hitting EOS immediately stops the host loop within one
    chunk — and the output still equals the full scan's."""
    _, params, policy = trained
    prime = jnp.asarray([[3, 4], [5, 6]], jnp.int32)
    full = make_sampler(CFG, policy)
    chunked = make_chunked_sampler(CFG, policy, chunk_size=4)
    key = jax.random.key(3)
    a = full(eos_params, key, prime, length=CFG.seq_len, top_k=None,
             temperature=0.0, add_bos=True)
    b = chunked(eos_params, key, prime, length=CFG.seq_len, top_k=None,
                temperature=0.0, add_bos=True)
    assert jnp.array_equal(a, b)
    # every row is double-zero by position ~4; without early exit the
    # loop would run ceil((24-3)/4) = 6 chunks
    assert chunked.last_num_chunks <= 2


def _mk_requests(n, *, seed=0, max_new=8, collect=None):
    rng = np.random.default_rng(seed)
    reqs = []
    for i in range(n):
        p = int(rng.integers(1, 9))
        reqs.append(Request(
            uid=i, tokens=rng.integers(1, CFG.num_tokens, p).tolist(),
            max_new_tokens=max_new, top_k=8, temperature=0.9, seed=100 + i,
            on_complete=(collect.append if collect is not None else None),
        ))
    return reqs


def _run_engine(params, policy, reqs, **kw):
    eng = ServingEngine(CFG, params, policy=policy, **kw)
    for r in reqs:
        eng.submit(r)
    comps = eng.run_until_idle(max_chunks=300)
    return eng, {c.uid: (c.tokens.tolist(), c.finish_reason) for c in comps}


def test_engine_deterministic_across_slots_and_chunks(trained):
    """Outputs depend only on (params, prime, seed, knobs): fewer slots
    than requests (slot reuse) and a different chunk size give identical
    completions."""
    _, params, policy = trained
    _, a = _run_engine(params, policy, _mk_requests(7), num_slots=3,
                       chunk_size=4)
    _, b = _run_engine(params, policy, _mk_requests(7), num_slots=7,
                       chunk_size=5)
    assert set(a) == set(range(7))
    assert a == b


def test_engine_completion_callbacks_and_lengths(trained):
    _, params, policy = trained
    got = []
    reqs = _mk_requests(5, max_new=6, collect=got)
    eng, by_uid = _run_engine(params, policy, reqs, num_slots=2,
                              chunk_size=3)
    assert sorted(c.uid for c in got) == list(range(5))
    for c in got:
        assert 1 <= len(c.tokens) <= 6
        if c.finish_reason == "eos":
            assert c.tokens[-1] == 0
        else:
            assert c.finish_reason == "length"
        assert c.latency >= 0.0
    assert eng.num_active == 0 and eng.pending == 0


def test_engine_all_eos_terminates_without_decode_chunks(trained,
                                                         eos_params):
    """EOS-dominant params: every request finishes at its FIRST sampled
    token (drawn at admission), so the engine drains with zero decode
    chunks — the early-exit cost bound at its extreme."""
    _, params, policy = trained
    reqs = [Request(uid=i, tokens=[3, 4, 5], max_new_tokens=10,
                    top_k=None, temperature=0.0, seed=i)
            for i in range(3)]
    eng, by_uid = _run_engine(eos_params, policy, reqs, num_slots=2,
                              chunk_size=4)
    assert eng.chunks_run == 0
    for toks, reason in by_uid.values():
        assert toks == [0] and reason == "eos"


def test_engine_greedy_matches_chunked_sampler(trained):
    """A single greedy request through the engine reproduces the chunked
    sampler's continuation for the same prime."""
    _, params, policy = trained
    prime = [7, 9, 2, 4]
    length = 16
    chunked = make_chunked_sampler(CFG, policy, chunk_size=4)
    want = np.asarray(chunked(params, jax.random.key(0),
                              jnp.asarray([prime], jnp.int32),
                              length=length, top_k=None, temperature=0.0))
    want_tail = want[0, len(prime):]
    want_tail = want_tail[:np.argmax(want_tail == 0) + 1
                          if (want_tail == 0).any() else len(want_tail)]

    eng, by_uid = _run_engine(
        params, policy,
        [Request(uid=0, tokens=prime, max_new_tokens=length - len(prime),
                 top_k=None, temperature=0.0, seed=0)],
        num_slots=1, chunk_size=4, max_len=length)
    got = np.asarray(by_uid[0][0])
    n = min(len(got), len(want_tail))
    assert n > 0
    np.testing.assert_array_equal(got[:n], want_tail[:n])


def test_engine_rejects_oversized_prime(trained):
    _, params, policy = trained
    eng = ServingEngine(CFG, params, policy=policy, num_slots=1,
                        chunk_size=2, max_len=8)
    with pytest.raises(ValueError):
        eng.submit(Request(uid=0, tokens=list(range(1, 9)),
                           max_new_tokens=4))
    with pytest.raises(ValueError):
        eng.submit(Request(uid=1, tokens=[], max_new_tokens=4))


def test_engine_tp2_sharded_smoke(trained, devices8):
    """The engine runs SPMD over a tensor-parallel mesh: params stay
    sharded, caches carry the tp layout, and two identical runs agree."""
    from progen_tpu.core import MeshConfig, make_mesh
    from progen_tpu.parallel.sharding import param_shardings

    model, params, policy = trained
    mesh = make_mesh(MeshConfig(data=1, fsdp=4, tensor=2), devices=devices8)
    strategies = ("fsdp", "tp")
    tokens = jnp.zeros((2, CFG.seq_len), jnp.int32)
    shardings = param_shardings(model, tokens, mesh, strategies)["params"]

    def run():
        return _run_engine(
            params, policy, _mk_requests(4, max_new=5), num_slots=2,
            chunk_size=3, mesh=mesh, strategies=strategies,
            params_shardings=shardings)[1]

    a = run()
    b = run()
    assert set(a) == set(range(4))
    assert a == b
    for toks, reason in a.values():
        assert all(0 <= t < CFG.num_tokens for t in toks)


def test_gumbel_topk_bf16_tiny_temperature():
    """bf16 logits with a tiny temperature must not overflow to NaN/inf:
    the sampler casts to f32 BEFORE scaling and top-k masking."""
    logits = jnp.asarray([[10.0, 9.0, -5.0, -400.0]], jnp.bfloat16)
    for temp in (1e-3, 1e-6):
        out = gumbel_topk_sample(jax.random.key(0), logits, top_k=2,
                                 temperature=temp)
        assert int(out[0]) == 0  # tiny temperature == argmax
    keys = jnp.stack([jax.random.key(0)])
    out = gumbel_topk_sample_batched(
        keys, logits, jnp.asarray([2], jnp.int32),
        jnp.asarray([1e-6], jnp.float32))
    assert int(out[0]) == 0


def test_gumbel_topk_batched_matches_scalar():
    """Per-row knobs reduce to the scalar sampler when rows share them."""
    rng = np.random.default_rng(5)
    logits = jnp.asarray(rng.normal(size=(3, 16)), jnp.float32)
    keys = jax.vmap(jax.random.key)(jnp.arange(3, dtype=jnp.uint32))
    got = gumbel_topk_sample_batched(
        keys, logits, jnp.full((3,), 4, jnp.int32),
        jnp.full((3,), 0.7, jnp.float32))
    for b in range(3):
        want = gumbel_topk_sample(keys[b], logits[b:b + 1], top_k=4,
                                  temperature=0.7)
        assert int(got[b]) == int(want[0])


@pytest.mark.slow
def test_sample_cli_serve_e2e(tmp_path):
    """`sample.py --serve`: checkpoint -> engine -> printed completions."""
    from progen_tpu.checkpoint import CheckpointStore
    from progen_tpu.train import make_optimizer, make_train_functions

    model = ProGen(config=CFG, policy=make_policy(False))
    sample_toks = jnp.zeros((2, CFG.seq_len), jnp.int32)
    fns = make_train_functions(model, make_optimizer(1e-3), sample_toks)
    state = fns.init_state(jax.random.key(0))
    store = CheckpointStore(str(tmp_path / "ckpts"))
    store.save(0, state, next_seq_index=0, model_config=CFG.to_dict(),
               run_id="serve-e2e")
    store.close()

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "sample.py"),
         "--serve", "--checkpoint_path", str(tmp_path / "ckpts"),
         "--prime", "AB|CD|E", "--seq_len", "16", "--slots", "2",
         "--chunk", "4", "--top_k", "8"],
        capture_output=True, text=True, timeout=600, env=env, cwd=repo,
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    # one completion block per prime, each stamped with its finish reason
    assert proc.stdout.count("*" * 40) == 3, proc.stdout
    assert ("eos" in proc.stdout) or ("length" in proc.stdout)


def test_bench_emits_json_error_record_when_backend_unavailable():
    """Turned round (PR 21): bench.py's only output is a device rate, so
    without a usable TPU it exits NON-zero, names the platform it found,
    and prints no record — an rc-0 error record hid the missing chip from
    whoever ran it."""
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env.update(JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run(
        [sys.executable, os.path.join(repo, "bench.py")],
        capture_output=True, text=True, timeout=120, env=env, cwd=repo,
    )
    assert proc.returncode != 0, proc.stdout[-2000:]
    assert "needs a TPU" in proc.stderr, proc.stderr[-2000:]
    assert "platform 'cpu'" in proc.stderr, proc.stderr[-2000:]
    assert not proc.stdout.strip(), proc.stdout[-2000:]
