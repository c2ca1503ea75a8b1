"""First-class served workloads: constrained infilling, embeddings, and
multi-tenant batched LoRA (ROADMAP item 5).

The engine-level invariants, each against the same tiny model:

* an ALL-PASS logit mask is bit-identical to no mask at all — dense and
  paged, greedy and sampled (the mask path costs nothing when unused);
* a scaffold-constrained request NEVER emits a masked token, and frozen
  interior positions are forced regardless of key/top-k/temperature;
* a zero-adapter LoRA tenant is bit-identical to the bankless engine,
  tenants batch together in one decode chunk, and paged == dense;
* the embeddings endpoint matches the standalone embedder bit-exactly
  and leaves concurrent generate traffic undisturbed;
* masks/tenants/embed queues survive the snapshot and wire round-trips.
"""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from progen_tpu.decode.engine import Request, ServingEngine
from progen_tpu.decode.handoff import request_from_wire, request_to_wire
from progen_tpu.decode.sampler import (
    apply_logit_mask,
    gumbel_topk_sample,
    gumbel_topk_sample_batched,
)
from progen_tpu.models.progen import ProGen, ProGenConfig
from progen_tpu.workloads import (
    ScaffoldSpec,
    make_embedder,
    mask_from_wire,
    mask_to_wire,
    random_lora_bank,
)
from tests.parity import assert_same_steps

pytestmark = pytest.mark.workloads

CFG = ProGenConfig(num_tokens=32, dim=16, depth=2, seq_len=64,
                   window_size=8, heads=2, dim_head=8, ff_mult=2)


@pytest.fixture(scope="module")
def params():
    model = ProGen(config=CFG)
    return model.init(jax.random.key(0),
                      jnp.zeros((1, CFG.seq_len), jnp.int32))


def mk_engine(params, **kw):
    return ServingEngine(CFG, params, num_slots=4, max_len=32,
                         chunk_size=4, **kw)


def make_requests(n=4, mnt=8):
    return [Request(uid=f"r{i}", tokens=[1 + (i % 5), 2, 3 + i % 3],
                    max_new_tokens=mnt, top_k=4 if i % 2 else None,
                    temperature=0.9, seed=100 + i) for i in range(n)]


def completions(comps):
    return {c.uid: (c.prime.tolist(), c.tokens.tolist(), c.finish_reason)
            for c in comps}


@pytest.fixture(scope="module")
def dense_base(params):
    eng = mk_engine(params)
    for r in make_requests():
        eng.submit(r)
    return completions(eng.run_until_idle())


@pytest.fixture(scope="module")
def bank():
    return random_lora_bank(CFG, num_tenants=4, rank=2, seed=3, scale=0.5)


@pytest.fixture(scope="module")
def scaffold():
    return ScaffoldSpec(template=[1, 2, None, 7, None, (5, 6), 9],
                        vocab=CFG.num_tokens,
                        alphabet=[3, 4, 5, 6, 7, 8, 9, 10])


@pytest.fixture(scope="module")
def lora_multi(params, bank):
    eng = mk_engine(params, lora_bank=bank)
    for i, r in enumerate(make_requests()):
        r.tenant = i % 4
        eng.submit(r)
    return completions(eng.run_until_idle())


# ---------------------------------------------------------------- sampler

def test_apply_logit_mask_all_pass_bit_identity():
    """The satellite contract: one shared masking idiom, and an all-true
    mask returns the logits bit-identically through BOTH samplers."""
    key = jax.random.key(11)
    logits = jax.random.normal(jax.random.key(5), (4, CFG.num_tokens),
                               jnp.float32)
    allpass = jnp.ones((4, CFG.num_tokens), bool)
    assert np.array_equal(np.asarray(apply_logit_mask(logits, allpass)),
                          np.asarray(logits))

    plain = gumbel_topk_sample(key, logits, 5, 0.8)
    masked = gumbel_topk_sample(key, logits, 5, 0.8, mask=allpass)
    assert np.array_equal(np.asarray(plain), np.asarray(masked))

    keys = jax.random.split(jax.random.key(13), 4)
    top_k = jnp.asarray([0, 3, 5, 0], jnp.int32)
    temp = jnp.asarray([0.0, 1.0, 0.7, 1.3], jnp.float32)
    plain_b = gumbel_topk_sample_batched(keys, logits, top_k, temp)
    masked_b = gumbel_topk_sample_batched(keys, logits, top_k, temp,
                                          mask=allpass)
    assert np.array_equal(np.asarray(plain_b), np.asarray(masked_b))


def test_sampler_never_escapes_mask():
    allowed = np.zeros((1, CFG.num_tokens), bool)
    allowed[0, [3, 5, 9]] = True
    logits = jax.random.normal(jax.random.key(2), (1, CFG.num_tokens),
                               jnp.float32)
    for seed in range(20):
        tok = int(gumbel_topk_sample(jax.random.key(seed), logits, None,
                                     1.5, mask=jnp.asarray(allowed))[0])
        assert tok in (3, 5, 9)
    # greedy row through the batched sampler obeys the mask too
    keys = jax.random.split(jax.random.key(0), 1)
    tok = int(gumbel_topk_sample_batched(
        keys, logits, jnp.zeros((1,), jnp.int32),
        jnp.zeros((1,), jnp.float32), mask=jnp.asarray(allowed))[0])
    assert tok in (3, 5, 9)


# ----------------------------------------------------------- scaffold API

def test_scaffold_spec_validation():
    with pytest.raises(ValueError):
        ScaffoldSpec(template=[None, 3], vocab=8)   # free prime position
    with pytest.raises(ValueError):
        ScaffoldSpec(template=[1, 2, 3], vocab=8)   # fully frozen
    with pytest.raises(ValueError):
        ScaffoldSpec(template=[1], vocab=8)         # nothing to infill
    with pytest.raises(ValueError):
        ScaffoldSpec(template=[1, ()], vocab=8)     # empty allowed set
    with pytest.raises(ValueError):
        ScaffoldSpec(template=[1, 99], vocab=8)     # token outside vocab


def test_scaffold_spec_mask_and_kwargs(scaffold):
    assert scaffold.prime() == [1, 2]
    assert scaffold.max_new_tokens == 5
    m = scaffold.logit_mask()
    assert m.shape == (5, CFG.num_tokens)
    assert m[1].sum() == 1 and m[1, 7]          # interior frozen: one-hot
    assert set(np.flatnonzero(m[3])) == {5, 6}  # explicit allowed set
    assert set(np.flatnonzero(m[0])) == set(range(3, 11))  # alphabet
    kw = scaffold.request_kwargs()
    assert kw["tokens"] == [1, 2] and kw["max_new_tokens"] == 5
    full = scaffold.full_mask(16)
    assert full.shape == (16, CFG.num_tokens)
    assert np.array_equal(full[2:7], m) and full[:2].all() and full[7:].all()


def test_mask_wire_roundtrip(scaffold):
    m = scaffold.logit_mask()
    rows = mask_to_wire(m)
    assert np.array_equal(mask_from_wire(rows, CFG.num_tokens), m)
    # the common case costs zero bytes on the wire
    assert mask_to_wire(np.ones((4, CFG.num_tokens), bool)) is None
    assert mask_to_wire(None) is None and mask_from_wire(None, 8) is None


def test_request_wire_roundtrip(scaffold):
    r = Request(uid="w", seed=5, top_k=3, temperature=0.7, tenant=2,
                **scaffold.request_kwargs())
    d = request_to_wire(r, now=0.0)
    r2 = request_from_wire(d, now=0.0, vocab=CFG.num_tokens)
    assert (r2.uid, list(r2.tokens), r2.max_new_tokens, r2.top_k,
            r2.temperature, r2.seed, r2.tenant) == (
        "w", [1, 2], 5, 3, 0.7, 5, 2)
    assert np.array_equal(r2.logit_mask, r.logit_mask)
    # all-pass masks and tenant 0 never travel
    plain = Request(uid="p", tokens=[1], max_new_tokens=2,
                    logit_mask=np.ones((2, CFG.num_tokens), bool))
    d = request_to_wire(plain, now=0.0)
    assert "logit_mask" not in d or d["logit_mask"] is None
    assert "tenant" not in d


# --------------------------------------------------------- engine: infill

def test_all_pass_mask_bit_identical_dense(params, dense_base):
    eng = mk_engine(params)
    for r in make_requests():
        r.logit_mask = np.ones((r.max_new_tokens, CFG.num_tokens), bool)
        eng.submit(r)
    assert completions(eng.run_until_idle()) == dense_base


def test_all_pass_mask_bit_identical_paged(params):
    base = mk_engine(params, paged=True, num_pages=64, page_size=8)
    for r in make_requests():
        base.submit(r)
    expect = completions(base.run_until_idle())
    eng = mk_engine(params, paged=True, num_pages=64, page_size=8)
    for r in make_requests():
        r.logit_mask = np.ones((r.max_new_tokens, CFG.num_tokens), bool)
        eng.submit(r)
    assert completions(eng.run_until_idle()) == expect


@pytest.mark.parametrize("sampled", [True, False])
def test_scaffold_constraint_enforced(params, scaffold, sampled):
    eng = mk_engine(params)
    kw = (dict(top_k=6, temperature=1.1, seed=42) if sampled
          else dict(top_k=None, seed=0))
    eng.submit(Request(uid="inf", **kw, **scaffold.request_kwargs()))
    (c,) = [c for c in eng.run_until_idle() if c.uid == "inf"]
    gen = c.tokens.tolist()
    m = scaffold.logit_mask()
    for g, t in enumerate(gen[:m.shape[0]]):
        assert m[g, t], f"emitted masked token {t} at generated pos {g}"
    # interior frozen positions are forced (EOS can only cut after them)
    assert gen[1] == 7
    if len(gen) > 3:
        assert gen[3] in (5, 6)
    if len(gen) > 4:
        assert gen[4] == 9


# ----------------------------------------------------------- engine: lora

def test_lora_tenant0_bit_identical(params, bank, dense_base):
    eng = mk_engine(params, lora_bank=bank)
    for r in make_requests():
        r.tenant = 0
        eng.submit(r)
    assert completions(eng.run_until_idle()) == dense_base


def test_lora_multi_tenant_one_batch(lora_multi, dense_base):
    # four slots, tenants 0..3 decoded in the same chunk
    assert lora_multi["r0"] == dense_base["r0"]
    assert any(lora_multi[f"r{i}"] != dense_base[f"r{i}"]
               for i in (1, 2, 3))


def test_lora_paged_matches_dense(params, bank, lora_multi):
    eng = mk_engine(params, lora_bank=bank, paged=True, num_pages=64,
                    page_size=8)
    for i, r in enumerate(make_requests()):
        r.tenant = i % 4
        eng.submit(r)
    assert completions(eng.run_until_idle()) == lora_multi


# ----------------------------------------------------- engine: embeddings

def test_embed_matches_direct_embedder(params, dense_base):
    eng = mk_engine(params)
    got = {}
    for i in range(3):
        eng.submit_embed(Request(
            uid=f"e{i}", tokens=[1 + i, 2, 3, 4 + i], max_new_tokens=1,
            on_complete=lambda c: got.__setitem__(c.uid, c)))
    for r in make_requests(2):
        eng.submit(r)
    comps = eng.run_until_idle()
    embeds = [c for c in comps if c.finish_reason == "embed"]
    assert len(embeds) == 3
    for c in embeds:
        assert c.ok and c.embedding.shape == (CFG.dim,)
        assert c.embedding.dtype == np.float32
    # concurrent generate traffic is undisturbed
    gen = completions([c for c in comps if c.finish_reason != "embed"])
    assert gen["r0"] == dense_base["r0"] and gen["r1"] == dense_base["r1"]
    # bit-exact against the standalone embedder program
    emb = make_embedder(CFG)
    t = np.zeros((1, 8), np.int32)
    t[0, :4] = [1, 2, 3, 4]
    ref = np.asarray(emb(params, t, np.array([4], np.int32)))[0]
    assert np.array_equal(ref, got["e0"].embedding)


def test_sow_final_hidden_mean_pool(params):
    """The model switch behind the embedder: sowed post-norm hiddens,
    mean-pooled over real positions, equal the embedder's output.  Runs
    under an f32 policy — the default bf16 compute rounds differently
    between this eager forward and the embedder's fused program."""
    from progen_tpu.core.precision import make_policy

    policy = make_policy(mixed_precision=False)
    model = ProGen(config=CFG, policy=policy, sow_final_hidden=True)
    t = np.zeros((1, 8), np.int32)
    t[0, :4] = [1, 2, 3, 4]
    _, state = model.apply(params, jnp.asarray(t), mutable=["cache"])
    (hidden,) = state["cache"]["final_hidden"]
    assert hidden.shape == (1, 8, CFG.dim)
    pooled = np.asarray(hidden, np.float32)[0, :4].mean(axis=0)
    emb = make_embedder(CFG, policy=policy)
    ref = np.asarray(emb(params, t, np.array([4], np.int32)))[0]
    np.testing.assert_allclose(pooled, ref, rtol=0, atol=1e-6)

    # the switch defaults OFF: nothing is sown, the carry stays lean
    plain = ProGen(config=CFG, policy=policy)
    _, state = plain.apply(params, jnp.asarray(t), mutable=["cache"])
    assert "final_hidden" not in state.get("cache", {})


# ------------------------------------------------- snapshot / aot / guard

def test_snapshot_roundtrip_mask_tenant_embed(params, bank, scaffold):
    def submit_all(eng):
        eng.submit(Request(uid="snap", seed=7, top_k=3, tenant=2,
                           **scaffold.request_kwargs()))
        eng.submit_embed(Request(uid="esnap", tokens=[1, 2, 3],
                                 max_new_tokens=1))

    src = mk_engine(params, lora_bank=bank)
    submit_all(src)
    snap = src.snapshot()

    restored = mk_engine(params, lora_bank=bank)
    assert restored.restore(snap) == 2
    out_r = restored.run_until_idle()

    fresh = mk_engine(params, lora_bank=bank)
    submit_all(fresh)
    out_f = fresh.run_until_idle()

    assert completions(out_r) == completions(out_f)
    em_r = [c.embedding for c in out_r if c.uid == "esnap"][0]
    em_f = [c.embedding for c in out_f if c.uid == "esnap"][0]
    assert np.array_equal(em_r, em_f)


def test_aot_warmup_with_embed(params, dense_base):
    eng = mk_engine(params)
    info = eng.aot_warmup(max_prime=16, embed=True)
    assert info["programs"] > 0
    for r in make_requests():
        eng.submit(r)
    eng.submit_embed(Request(uid="ew", tokens=[1, 2, 3], max_new_tokens=1))
    out = eng.run_until_idle()
    gen = completions([c for c in out if c.finish_reason != "embed"])
    assert gen == dense_base
    assert [c.uid for c in out if c.finish_reason == "embed"] == ["ew"]


def test_workload_validation_errors(params, bank):
    eng = mk_engine(params)
    with pytest.raises(ValueError):   # tenant without a bank
        eng.submit(Request(uid="x", tokens=[1], max_new_tokens=2, tenant=1))
    with pytest.raises(ValueError):   # more mask rows than max_new
        eng.submit(Request(uid="x", tokens=[1], max_new_tokens=2,
                           logit_mask=np.ones((4, CFG.num_tokens), bool)))
    with pytest.raises(ValueError):   # all-False row allows nothing
        eng.submit(Request(uid="x", tokens=[1], max_new_tokens=2,
                           logit_mask=np.zeros((2, CFG.num_tokens), bool)))
    with pytest.raises(ValueError):   # mask over the wrong vocab
        eng.submit(Request(uid="x", tokens=[1], max_new_tokens=2,
                           logit_mask=np.ones((2, 7), bool)))
    with pytest.raises(ValueError):   # embeds never sample: no masks
        eng.submit_embed(Request(uid="x", tokens=[1], max_new_tokens=1,
                                 logit_mask=np.ones((1, CFG.num_tokens),
                                                    bool)))
    with pytest.raises(ValueError):   # embed needs a non-empty prime
        eng.submit_embed(Request(uid="x", tokens=[], max_new_tokens=1))
    # LoRA composes with disaggregated decode: the handle carries a
    # tenant leaf, and the rolling hot-swap path (docs/SERVING.md §9) ships
    # banks to disaggregated workers
    eng = mk_engine(params, lora_bank=bank, disagg=True)
    assert eng.disagg and eng.lora and eng.num_tenants > 1


# ---------------------------------------------------------- lora training

def test_lora_train_frozen_base_superstep_and_bank():
    """Adapters train through the UNMODIFIED train loop: step 0 is the
    base model bit-exactly, the base never moves, the fused superstep
    path equals sequential steps, and the trained factors convert into a
    serving bank that reproduces the training forward."""
    from progen_tpu.core.precision import make_policy
    from progen_tpu.train.lora import (
        LoRAProGen,
        extract_adapters,
        init_from_base,
        lora_train_functions,
    )
    from progen_tpu.workloads import bank_from_trained, validate_lora_bank

    policy = make_policy(mixed_precision=False)
    rank = 2
    model = LoRAProGen(config=CFG, rank=rank, policy=policy)
    sample = jnp.zeros((2, CFG.seq_len), jnp.int32)
    fns = lora_train_functions(model, sample, learning_rate=1e-2,
                               grad_accum_every=2)
    state = fns.init_state(jax.random.key(0))

    base = ProGen(config=CFG, policy=policy)
    base_params = jax.device_get(
        jax.jit(base.init)(jax.random.key(9), sample)["params"])
    state = state.replace(params=init_from_base(state.params, base_params))

    # step 0: b factors are zero, the wrapper IS the base model
    lora_logits = model.apply({"params": state.params}, sample)
    base_logits = base.apply({"params": base_params}, sample)
    assert np.array_equal(np.asarray(lora_logits), np.asarray(base_logits))

    rng = np.random.default_rng(0)
    K, accum, B = 2, 2, 2
    superbatch = jnp.asarray(
        rng.integers(1, CFG.num_tokens, size=(K, accum, B, CFG.seq_len + 1)),
        jnp.int32)
    frozen_before = jax.device_get(state.params["base"])
    state, metrics = fns.train_multi_step(state, superbatch)
    assert metrics["loss"].shape == (K, accum)
    assert np.all(np.isfinite(np.asarray(metrics["loss"])))

    # the base subtree is BIT-unchanged; the adapters moved
    frozen_after = jax.device_get(state.params["base"])
    for x, y in zip(jax.tree.leaves(frozen_before),
                    jax.tree.leaves(frozen_after)):
        assert np.array_equal(x, y)
    trained = extract_adapters(jax.device_get(state.params), CFG)
    assert any(np.abs(np.asarray(site["b"])).max() > 0
               for layer in trained.values() for site in layer.values())

    # fused superstep == sequential per-step walk (tests/parity.py)
    state2 = fns.init_state(jax.random.key(0))
    state2 = state2.replace(params=init_from_base(state2.params, base_params))
    for kk in range(K):
        for aa in range(accum):
            state2, _ = fns.train_step(state2, superbatch[kk, aa])
    assert_same_steps(state.params, state2.params)

    # trained factors -> serving bank: tenant 1 reproduces the training
    # forward through the engine-side apply_lora path
    serving_bank = bank_from_trained(CFG, rank, [trained])
    assert validate_lora_bank(CFG, serving_bank) == 2
    tokens = jnp.asarray(rng.integers(1, CFG.num_tokens, size=(2, 16)),
                         jnp.int32)
    serve_logits = base.apply(
        {"params": state.params["base"]}, tokens,
        jax.tree.map(jnp.asarray, serving_bank), jnp.ones((2,), jnp.int32))
    train_logits = model.apply({"params": state.params}, tokens)
    np.testing.assert_allclose(np.asarray(serve_logits),
                               np.asarray(train_logits), rtol=0, atol=1e-6)
