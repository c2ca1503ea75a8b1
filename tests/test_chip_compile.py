"""Compile the main path's Pallas kernels for a described TPU v5e chip.

Every other test of these kernels runs them under the Pallas interpreter,
which accepts block shapes and memory footprints the chip's compiler
refuses.  The TPU compiler is installed in the CPU sandbox and compiles
for a chip that is described, not attached — nothing runs, so this file
says nothing about results or speed (``chip_smoke.py`` does, on the
chip); it says that the compiler takes each kernel, ``interpret=False``,
at ProGen-small and ProGen-base widths.

The topology is described inside a module-scoped fixture of THIS file and
nowhere else: only one process may load the TPU library, so the call must
not happen while any module is imported, and these tests must stay in one
file (a second file can land on another xdist worker, where its fixture
would skip).
"""

import json
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import SingleDeviceSharding

from progen_tpu.models.configs import BASE, SMALL

# batch sizes are the ones the configs are run at on one chip; they do
# not enter any block shape
WIDTHS = {"small": (SMALL, 8), "base": (BASE, 2)}
PAGE_SIZE = 16  # the engine's default


@pytest.fixture(scope="module")
def topo():
    import os

    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def shape(topo):
    """``shape(dims, dtype)`` -> abstract array placed on one described
    chip (there is no device to hold a real one)."""
    one_chip = SingleDeviceSharding(topo.devices[0])

    def make(dims, dtype):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    return make


@pytest.fixture(scope="module")
def no_persistent_cache():
    """A compile for a described chip is written to the persistent cache
    but cannot be read back without the chip; keep these out of it."""
    from jax.experimental.compilation_cache import compilation_cache

    before = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", before)
    compilation_cache.reset_cache()


def _assert_kernel_compiles(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"


def _buffers_of(text, kind):
    """The instructions of a compiled program whose RESULT holds ``kind``
    and is a buffer of its own — a fusion's, a loop's, a copy's, a
    kernel's (what a fused computation passes along inside is not)."""
    hits = []
    for line in text.splitlines():
        _, sep, rest = line.partition(" = ")
        op = re.search(r" (fusion|while|copy|custom-call)\(", rest)
        if sep and op and kind in re.sub(r"\{[^{}]*\}", "",
                                         rest[:op.start()]):
            hits.append(line.strip())
    return hits


def _grad_of(fn, nargs):
    return jax.grad(lambda *a: fn(*a).astype(jnp.float32).sum(),
                    argnums=tuple(range(nargs)))


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_windowed_attention_compiles_for_v5e(shape, no_persistent_cache,
                                             width, direction):
    from progen_tpu.ops.pallas_attention import pallas_local_attention

    cfg, batch = WIDTHS[width]
    q = shape((batch, cfg.heads, cfg.seq_len, cfg.dim_head), jnp.bfloat16)

    def fn(q, k, v):
        return pallas_local_attention(q, k, v, cfg.window_size,
                                      interpret=False)

    _assert_kernel_compiles(fn if direction == "fwd" else _grad_of(fn, 3),
                            q, q, q)


@pytest.mark.parametrize("direction", ["fwd", "bwd"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_blocked_sgu_compiles_for_v5e(shape, no_persistent_cache, width,
                                      direction):
    from progen_tpu.ops.pallas_sgu import pallas_spatial_gate

    cfg, batch = WIDTHS[width]
    n, d = cfg.seq_len, cfg.dim * cfg.ff_mult // 2
    x = shape((batch, n, d), jnp.bfloat16)
    w = shape((n, n), jnp.bfloat16)
    b = shape((n, 1), jnp.bfloat16)

    def fn(res, gate, w, b):
        return pallas_spatial_gate(res, gate, w, b, interpret=False)

    _assert_kernel_compiles(fn if direction == "fwd" else _grad_of(fn, 4),
                            x, x, w, b)


def test_short_sgu_pads_to_chip_tiles(shape, no_persistent_cache):
    """A prefill shorter than two tiles still compiles: it pads up to the
    128-wide tiles instead of taking one the compiler refuses."""
    from progen_tpu.ops.pallas_sgu import pallas_spatial_gate

    x = shape((2, 96, 2048), jnp.bfloat16)
    _assert_kernel_compiles(
        lambda r, g, w, b: pallas_spatial_gate(r, g, w, b, interpret=False),
        x, x, shape((96, 96), jnp.bfloat16), shape((96, 1), jnp.bfloat16))


@pytest.mark.parametrize("gate_pages", ["bf16", "q8"])
@pytest.mark.parametrize("width", list(WIDTHS))
def test_paged_gate_mix_compiles_for_v5e(shape, no_persistent_cache, width,
                                         gate_pages):
    """The decode-side kernel has no backward.  ``q8`` is int8 weights +
    int8 gate pages with their scale operands."""
    from progen_tpu.ops.pallas_paged_attention import paged_gate_mix

    cfg, _ = WIDTHS[width]
    n, d, batch = cfg.seq_len, cfg.dim * cfg.ff_mult // 2, 8
    pages_per_row = n // PAGE_SIZE
    num_pages = 2 + batch * pages_per_row
    table = shape((batch, pages_per_row), jnp.int32)
    pos = shape((batch,), jnp.int32)
    biases = shape((n, 1), jnp.float32)
    if gate_pages == "bf16":
        def fn(w, b, pool, table, pos):
            return paged_gate_mix(w, b, pool, table, pos, n_rows=n,
                                  impl="pallas", interpret=False)

        args = (shape((n, n), jnp.float32), biases,
                shape((num_pages, PAGE_SIZE, d), jnp.bfloat16), table, pos)
    else:
        def fn(w, b, pool, table, pos, w_scale, pool_scale):
            return paged_gate_mix(w, b, pool, table, pos, n_rows=n,
                                  impl="pallas", interpret=False,
                                  w_scale=w_scale, pool_scale=pool_scale)

        args = (shape((n, n), jnp.int8), biases,
                shape((num_pages, PAGE_SIZE, d), jnp.int8), table, pos,
                shape((n,), jnp.float32),
                shape((num_pages, PAGE_SIZE), jnp.float32))
    _assert_kernel_compiles(fn, *args)


# (cache shape at the slots the benchmark's cells run, arrays in one call)
ROW_WRITES = {
    "small-rings": ((64, 8, 512, 128), 2),
    "small-gate": ((64, 1024, 2048), 1),
    "base-rings": ((16, 12, 1024, 128), 2),
    "base-gate": ((16, 2048, 3072), 1),
    "longcat-latent": ((32, 4096, 576), 1),
    "dsv2-latent": ((64, 3072, 576), 1),
    "trinity-ring": ((64, 4, 2048, 128), 2),
    "trinity-grown": ((64, 4, 9216, 128), 2),
}


@pytest.mark.parametrize("case", list(ROW_WRITES))
def test_row_write_compiles_for_v5e(shape, no_persistent_cache, case):
    """The decode step's cache write (``ops/row_write.py``): a layer's k
    and v rings in one call, the gate cache, and LongCat's latent cache of
    576 lanes, each aliased to its output — no second cache in the
    program's temporaries (at 576 lanes the entry layout the compiler
    prefers differs from the kernel's, with the scatter as with the
    kernel, so that case checks the aliasing alone)."""
    from progen_tpu.ops.row_write import pallas_write_rows

    dims, n = ROW_WRITES[case]
    caches = (shape(dims, jnp.bfloat16),) * n
    updates = (shape(dims[:-2] + dims[-1:], jnp.bfloat16),) * n
    compiled = jax.jit(
        lambda c, u, i: pallas_write_rows(c, u, i, interpret=False),
        donate_argnums=(0,),
    ).lower(caches, updates, shape((dims[0],), jnp.int32)).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text, "kernel did not lower to Mosaic"
    assert "output_to_operand_aliasing" in text
    if dims[-1] % 128 == 0:
        one_cache = 2 * np.prod(dims)
        assert compiled.memory_analysis().temp_size_in_bytes < one_cache


# ---- LongCat-Flash's pieces at published widths (models/longcat.py) ----


def _longcat_shapes(shape, fn, *args):
    """``fn``'s abstract outputs placed on the described chip."""
    return jax.tree.map(lambda a: shape(a.shape, a.dtype),
                        jax.eval_shape(fn, *args))


@pytest.mark.parametrize("tokens", [32, 8192], ids=["decode", "prefill"])
def test_longcat_expert_share_compiles_for_the_chip(shape, tokens,
                                                    no_persistent_cache):
    """The grouped product over held experts (``jax.lax.ragged_dot`` in a
    ``while`` over windows) at 16 experts of 6144 x 2048, for a decode
    batch and for an admission run of 2 x 4096 tokens: the chip's compiler
    takes it and lowers the ragged product to its own kernel."""
    from progen_tpu.models import longcat

    c = longcat.LongCatConfig(num_layers=1, vocab_size=16384,
                              experts_held=16)
    layer = _longcat_shapes(
        shape, lambda k: longcat._init_layer(k, c, jnp.bfloat16),
        jax.random.key(0))
    u = shape((tokens, c.hidden_size), jnp.bfloat16)
    live = shape((tokens,), jnp.bool_)
    _assert_kernel_compiles(
        lambda layer, u, live: longcat.moe_share(u, layer, c, live),
        layer, u, live)


def test_longcat_absorbed_decode_compiles_for_the_chip(shape,
                                                       no_persistent_cache):
    """One absorbed attention step of 32 rows over a 4096-row latent cache
    of 576 numbers (not a multiple of the 128 lanes)."""
    from progen_tpu.models import longcat

    c = longcat.LongCatConfig(num_layers=1, vocab_size=16384,
                              experts_held=16)
    p = _longcat_shapes(
        shape, lambda k: longcat._init_attn(k, c, jnp.bfloat16),
        jax.random.key(0))
    compiled = jax.jit(
        lambda x, pos, cache, p: longcat.mla_decode(x, pos, cache, p, c)
    ).lower(shape((32, c.hidden_size), jnp.bfloat16),
            shape((32,), jnp.int32),
            shape((32, 4096, c.latent_width), jnp.bfloat16), p).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024 ** 3


# ---- DeepSeek-V2's pieces at published widths (models/deepseek_v2.py) ----


@pytest.mark.parametrize("tokens", [64, 4096], ids=["decode", "prefill"])
def test_dsv2_expert_share_compiles_for_the_chip(shape, tokens,
                                                 no_persistent_cache):
    """The group-limited router and the grouped product over 40 held
    experts of 5120 x 1536, for a decode batch of 64 rows and for an
    admission run of 4 x 1024 tokens (windows of 256 and 12,288
    assignments: 1.5 a token expected, twice that held)."""
    from progen_tpu.models import deepseek_v2

    c = deepseek_v2.DeepSeekV2Config(num_hidden_layers=2, vocab_size=25600,
                                     experts_held=40)
    layer = _longcat_shapes(
        shape, lambda k: deepseek_v2._init_layer(k, c, jnp.bfloat16, False),
        jax.random.key(0))
    u = shape((tokens, c.hidden_size), jnp.bfloat16)
    live = shape((tokens,), jnp.bool_)
    _assert_kernel_compiles(
        lambda layer, u, live: deepseek_v2.moe_share(u, layer, c, live),
        layer, u, live)


def test_dsv2_absorbed_decode_compiles_for_the_chip(shape,
                                                    no_persistent_cache):
    """One absorbed attention step of 64 rows and 128 heads over a 3072-row
    latent cache, the YaRN table a constant of the program."""
    from progen_tpu.models import deepseek_v2, latent

    c = deepseek_v2.DeepSeekV2Config(num_hidden_layers=2, vocab_size=25600,
                                     experts_held=40)
    p = _longcat_shapes(
        shape, lambda k: latent.init_attn(k, c, jnp.bfloat16),
        jax.random.key(0))
    compiled = jax.jit(
        lambda x, pos, cache, p: latent.mla_decode(x, pos, cache, p, c)
    ).lower(shape((64, c.hidden_size), jnp.bfloat16),
            shape((64,), jnp.int32),
            shape((64, 3072, c.latent_width), jnp.bfloat16), p).compile()
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * 1024 ** 3


# ---- Trinity's pieces at published widths (models/trinity.py) ----


@pytest.mark.parametrize("tokens", [64, 4 * 8192], ids=["decode", "prefill"])
def test_trinity_expert_share_compiles_for_the_chip(shape, tokens,
                                                    no_persistent_cache):
    """One expert layer's share (sigmoid router with its bias, sort, the
    three ``ragged_dot``s over 16 held experts of width 1024, scatter-add)
    at a decode step's 64 rows and at an admission run's 4 x 8192."""
    from progen_tpu.models import trinity

    c = trinity.TrinityConfig(num_hidden_layers=2, num_dense_layers=1,
                              vocab_size=25024, experts_held=16)
    layer = _longcat_shapes(
        shape, lambda k: trinity._init_layer(k, c, jnp.bfloat16, False),
        jax.random.key(0))
    u = shape((tokens, c.hidden_size), jnp.bfloat16)
    live = shape((tokens,), jnp.bool_)
    _assert_kernel_compiles(
        lambda layer, u, live: trinity.moe_share(u, layer, c, live),
        layer, u, live)


@pytest.mark.parametrize("window,rows", [(2048, 2048), (None, 9216)],
                         ids=["ring", "grown"])
def test_trinity_decode_block_compiles_for_the_chip(shape, window, rows,
                                                    no_persistent_cache):
    """One attention block's decode step of 64 rows over a slot's ring or
    grown keys through the XLA core (scores ``(64, 32, rows)`` float32):
    what a trace under a mesh keeps on the chip; the kernel's own case is
    ``test_gqa_decode_kernel_compiles_for_v5e``."""
    from progen_tpu.models import trinity

    c = trinity.TrinityConfig(num_hidden_layers=2, num_dense_layers=1,
                              vocab_size=25024, experts_held=16)
    block = trinity.KVBlock(c, window)
    p = _longcat_shapes(
        shape, lambda k: trinity._init_attn(k, c, jnp.bfloat16),
        jax.random.key(0))
    kv = shape((64, 4, rows, 128), jnp.bfloat16)
    compiled = jax.jit(block.decode).lower(
        shape((64, c.hidden_size), jnp.bfloat16), shape((64,), jnp.int32),
        {"k": kv, "v": kv}, p).compile()
    # the scores and their softmax, not a second cache
    one_cache = 64 * 4 * rows * 128 * 2
    assert compiled.memory_analysis().temp_size_in_bytes < 2 * one_cache


@pytest.mark.parametrize("bucket", [512, 1024])
def test_mla_prefill_kernel_compiles_for_v5e_at_128_heads(
        shape, no_persistent_cache, bucket):
    """``mla_prefill_fwd`` at an admission run of
    ``serve-dsv2-decode-backlog``: 4 rows, 128 heads, both buckets."""
    from progen_tpu.ops.mla_prefill import pallas_prefill_attention

    r, heads, bf16 = 4, 128, jnp.bfloat16
    _assert_kernel_compiles(
        lambda *a: pallas_prefill_attention(*a, interpret=False),
        shape((r, heads, bucket, 128), bf16),
        shape((r, heads, bucket, 64), bf16),
        shape((r, heads, bucket, 128), bf16),
        shape((r, bucket, 64), bf16),
        shape((r, heads, bucket, 128), bf16),
        shape((r,), jnp.int32))


@pytest.mark.parametrize("bucket", [4096, 8192, 16384])
def test_mla_prefill_kernel_compiles_for_v5e_under_a_keep_mask(
        shape, no_persistent_cache, bucket):
    """``mla_prefill_fwd`` as a full layer of ``serve-dots3-longdoc-backlog``
    calls it: 1 row, 128 heads, the selection as an int8 ``(1, P, P)`` keep
    mask in tiles beside the score tile, each bucket with a segment past the
    first 2,048 rows."""
    from progen_tpu.ops.mla_prefill import pallas_prefill_attention

    r, heads, bf16 = 1, 128, jnp.bfloat16
    _assert_kernel_compiles(
        lambda *a: pallas_prefill_attention(*a, interpret=False),
        shape((r, heads, bucket, 128), bf16),
        shape((r, heads, bucket, 64), bf16),
        shape((r, heads, bucket, 128), bf16),
        shape((r, bucket, 64), bf16),
        shape((r, heads, bucket, 128), bf16),
        shape((r,), jnp.int32),
        shape((r, bucket, bucket), jnp.int8))


@pytest.mark.parametrize("bucket", [512, 1024, 2048, 4096])
def test_mla_prefill_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                             bucket):
    """The prefill's flash kernel (``ops/mla_prefill.py``,
    ``mla_prefill_fwd``) at an admission run of ``serve-longcat-backlog``:
    2 rows, 64 heads of 128 + 64 (v 128), bfloat16, each prefill bucket the
    cell warms, with the tiles the chip path takes."""
    from progen_tpu.ops.mla_prefill import pallas_prefill_attention

    r, heads, bf16 = 2, 64, jnp.bfloat16
    _assert_kernel_compiles(
        lambda *a: pallas_prefill_attention(*a, interpret=False),
        shape((r, heads, bucket, 128), bf16),
        shape((r, heads, bucket, 64), bf16),
        shape((r, heads, bucket, 128), bf16),
        shape((r, bucket, 64), bf16),
        shape((r, heads, bucket, 128), bf16),
        shape((r,), jnp.int32))


@pytest.mark.parametrize("window", [2048, None], ids=["sliding", "full"])
@pytest.mark.parametrize("bucket", [512, 1024, 2048, 4096, 8192])
def test_gqa_prefill_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                             bucket, window):
    """The grouped-query prefill's flash kernel (``ops/gqa.py``,
    ``gqa_prefill_fwd``) at an admission run of
    ``serve-trinity-mixedlen-backlog``: 4 rows, 32 query heads over 4
    key/value heads of 128, bfloat16, each prefill bucket the cell warms,
    a sliding block's window and a full block's none, with the tiles the
    chip path takes."""
    from progen_tpu.ops.gqa import pallas_prefill_attention

    r, heads, kv, d, bf16 = 4, 32, 4, 128, jnp.bfloat16
    _assert_kernel_compiles(
        lambda q, k, v, n: pallas_prefill_attention(
            q, k, v, n, d ** -0.5, window, interpret=False),
        shape((r, bucket, heads * d), bf16), shape((r, kv, bucket, d), bf16),
        shape((r, kv, bucket, d), bf16), shape((r,), jnp.int32))


@pytest.mark.parametrize("slots,heads,max_len", [(64, 128, 3072),
                                                 (32, 64, 4096)],
                         ids=["dsv2", "longcat"])
def test_mla_decode_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                            slots, heads, max_len):
    """The absorbed decode step's attention core (``ops/mla_decode.py``,
    ``mla_decode_fwd``) at the shapes of ``serve-dsv2-decode-backlog`` and
    ``serve-longcat-backlog``: a cache whose last axis (576) is 4.5 lane
    tiles taken whole, with the key tile the chip path takes."""
    from progen_tpu.ops.mla_decode import pallas_decode_attention

    bf16 = jnp.bfloat16
    _assert_kernel_compiles(
        lambda q, c, n: pallas_decode_attention(q, c, n, 512, 192 ** -0.5,
                                                interpret=False),
        shape((slots, heads, 576), bf16), shape((slots, max_len, 576), bf16),
        shape((slots,), jnp.int32))


@pytest.mark.parametrize("slots,heads,rows,d,dv", [
    (64, 32, 2048, 128, 128), (64, 32, 9216, 128, 128),
    (128, 32, 3072, 128, 128), (16, 64, 17408, 192, 128),
    (16, 32, 4096, 128, 64)],
    ids=["trinity-ring", "trinity-grown", "lfm2", "mimo-full", "128-64"])
def test_gqa_decode_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                            slots, heads, rows, d, dv):
    """The grouped-query decode core (``ops/gqa.py``, ``gqa_decode_fwd``)
    at the shapes of ``serve-trinity-mixedlen-backlog`` (a ring and grown
    keys) and ``serve-lfm2-longgen-backlog``: 32 query heads over 4
    key/value heads of 128, bfloat16 — a head's 8 query rows half a
    sublane tile, all four key/value heads in one block —, with the key
    tile the chip path takes; and at TWO WIDTHS: the full layers of
    ``serve-mimo-longdoc-backlog`` (64 query heads over 4 key/value heads,
    keys 192 wide — a block one and a half lane tiles wide, whole — beside
    values of 128, 17 key tiles of 1,024 a slot) and the narrowest pair
    ``decode_lowering`` sends here (values of half a lane tile)."""
    from progen_tpu.ops.gqa import pallas_decode_attention

    bf16 = jnp.bfloat16
    keys, values = (shape((slots, 4, rows, d), bf16),
                    shape((slots, 4, rows, dv), bf16))
    _assert_kernel_compiles(
        lambda q, k, v, n: pallas_decode_attention(q, k, v, n, d ** -0.5,
                                                   interpret=False),
        shape((slots, heads, d), bf16), keys, values, shape((slots,),
                                                            jnp.int32))


@pytest.mark.parametrize("queries,tokens", [(8, 8), (4, 8), (4, 4)],
                         ids=["two-blocks", "last-layer", "one-block"])
def test_gqa_block_decode_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                                  queries, tokens):
    """The block form's core (``ops/gqa.py``, ``gqa_block_decode_fwd``) at
    the shapes of ``serve-sdar-blockdiff-backlog``: 64 slots of 2,560 rows,
    4 key/value heads of 128, bfloat16, with the key tile the chip path
    takes — both blocks' queries (64 query rows a key/value head) and the
    last layer's (32) over the 8 keys of a pending block and the block in
    progress, and the B-wide call of a first block and the direct check (4
    keys, a quarter of a bfloat16 sublane tile, and no mask among them)."""
    from progen_tpu.ops.gqa import pallas_block_decode_attention

    bf16 = jnp.bfloat16
    cache = shape((64, 4, 2560, 128), bf16)
    own = shape((64, 4, tokens, 128), bf16)
    two = tokens > 4
    _assert_kernel_compiles(
        lambda q, k, v, kn, vn, n, lead: pallas_block_decode_attention(
            q, k, v, kn, vn, n, 128 ** -0.5, lead if two else None,
            interpret=False),
        shape((64, queries, 32, 128), bf16), cache, cache, own, own,
        shape((64,), jnp.int32), shape((64,), jnp.bool_))


def _expert_shapes(shape, held, h, inner, gated):
    """The stacked experts' abstract matrices: gate (None without one), up,
    down."""
    bf16 = jnp.bfloat16
    return [shape((held, h, inner), bf16) if gated else None,
            shape((held, h, inner), bf16), shape((held, inner, h), bf16)]


@pytest.mark.parametrize("tokens,h,inner,held,gated", [
    (64, 5120, 1536, 40, True), (32, 6144, 2048, 16, True),
    (64, 2048, 1024, 16, True), (64, 1024, 2688, 128, False)],
    ids=["dsv2", "longcat", "trinity", "nemotron3-two-matrix"])
def test_moe_decode_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                            tokens, h, inner, held, gated):
    """The held experts' decode product (``ops/moe_decode.py``,
    ``moe_decode_fwd``) at a decode call of the expert cells, at the
    published widths and with the inner tile the chip path takes: three
    streamed tiles a step (two for Nemotron-H's experts without a gate, the
    whole inner width of 2688 a step), double-buffered, under the
    ``vmem_limit_bytes`` the call states.  Outside the kernel the call
    keeps only the padded tokens, the listed experts and their routing
    weights."""
    from progen_tpu.ops.moe_decode import pallas_expert_terms

    bf16 = jnp.bfloat16
    fn = jax.jit(lambda u, e, n, wt, wg, wu, wd: pallas_expert_terms(
        u, e, n, wt, wg, wu, wd, interpret=False))
    compiled = fn.lower(
        shape((tokens, h), bf16), shape((held,), jnp.int32),
        shape((), jnp.int32), shape((held, tokens), jnp.float32),
        *_expert_shapes(shape, held, h, inner, gated)).compile()
    assert "tpu_custom_call" in compiled.as_text()
    assert compiled.memory_analysis().temp_size_in_bytes < 4 * tokens * h * 4


@pytest.mark.parametrize("tokens,k,h,inner,held,gated", [
    (256, 8, 2048, 768, 128, True), (512, 8, 2048, 768, 128, True),
    (1024, 8, 2048, 768, 128, True), (1024, 12, 6144, 2048, 16, True),
    (1024, 22, 1024, 2688, 128, False)],
    ids=["sdar-admit-128", "sdar-block-step", "sdar-admit-256",
         "longcat-admit-512", "nemotron3-admit-256-two-matrix"])
def test_moe_grouped_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                             tokens, k, h, inner, held,
                                             gated):
    """The held experts' product over an expert's OWN rows
    (``ops/moe_decode.py``, ``moe_grouped_fwd``) at SDAR's block step (64
    slots x 8 token rows since PR 48: the pending block in front of the
    block in progress), its admissions of 4 x 128 and 4 x 256 — the largest
    in the kernel's range — one step an item, 9.4 MB
    of weights double-buffered — and at LongCat's 2 rows x 512, whose inner
    tile takes eight steps, with the row tile and the work list's static
    bound the chip path takes."""
    from progen_tpu.ops import moe_decode as md

    bf16 = jnp.bfloat16
    rt = md.ROW_TILE
    items = tokens * k // rt + held
    fn = jax.jit(lambda xs, e, n, wt, wg, wu, wd: md.pallas_grouped_terms(
        xs, e, n, wt, wg, wu, wd, row_tile=rt, interpret=False))
    compiled = fn.lower(
        shape((items * rt, h), bf16), shape((items,), jnp.int32),
        shape((), jnp.int32), shape((items * rt,), jnp.float32),
        *_expert_shapes(shape, held, h, inner, gated)).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("tokens,k,router,h,inner,held,gated", [
    (32768, 8, 128, 2048, 1024, 16, True), (8192, 4, 32, 2048, 1792, 32, True),
    (4096, 22, 512, 1024, 2688, 128, False),
    (2048, 6, 160, 5120, 1536, 40, True), (2048, 8, 128, 2048, 768, 128, True),
    (4096, 12, 768, 6144, 2048, 16, True)],
    ids=["trinity-admit-4x8192", "lfm2-admit-8x1024",
         "nemotron3-admit-4x1024-two-matrix", "dsv2-admit-4x512",
         "sdar-admit-4x512", "longcat-admit-2x2048"])
def test_moe_sorted_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                            monkeypatch, tokens, k, router, h,
                                            inner, held, gated):
    """The held experts' product over row tiles of the SORTED rows
    (``ops/moe_decode.py``, ``moe_sorted_fwd``) at an admission run of each
    expert cell, over one window of ``experts.sorted_window`` rows with the
    tiles ``fitted_tile`` gives the chip path: row tiles of 128, Trinity's
    and LFM2's whole expert a step (12.6 and 22 MB, double-buffered),
    DeepSeek-V2's and LongCat's in two and four steps, all under the
    ``vmem_limit_bytes`` the call states."""
    import types

    from progen_tpu.models import experts
    from progen_tpu.ops import moe_decode as md

    bf16 = jnp.bfloat16
    monkeypatch.setattr(md, "_on_tpu", lambda: True)
    c = types.SimpleNamespace(experts_held=held, first_expert=0, moe_topk=k,
                              router_width=router)
    wg, wu, wd = _expert_shapes(shape, held, h, inner, gated)
    tiles = md.fitted_tile(shape((tokens, h), bf16), {
        name: w for name, w in (("wg", wg), ("wu", wu), ("wd", wd))
        if w is not None})
    assert tiles.sorted
    rows = experts.sorted_window(c, tokens, k, tiles.rows)
    fn = jax.jit(lambda xs, wt, lo, hi, wg, wu, wd: md.pallas_sorted_terms(
        xs, wt, lo, hi, wg, wu, wd, row_tile=tiles.rows, tile=tiles.inner,
        interpret=False))
    compiled = fn.lower(
        shape((rows, h), bf16), shape((rows,), jnp.float32),
        shape((held,), jnp.int32), shape((held,), jnp.int32), wg, wu,
        wd).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_trinity_chunk_program_compiles_for_the_chip_and_fits_it(
        shape, no_persistent_cache, monkeypatch):
    """The chunk program of ``serve-trinity-mixedlen-backlog`` over
    ABSTRACT weights (its 64 slots' state is real, on the host, for this
    test alone: 4.3 GB of zeros): 32 steps of 9 layers, the rings and
    grown keys written by ``row_write`` and read by ``gqa_decode_fwd`` up
    to each slot's count, with no float32 score tensor over a whole cache;
    arguments, results and temporaries under the 11.38 GB the cell's file
    states for it."""
    from progen_tpu.decode import sampler
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import trinity
    from progen_tpu.ops import gqa, lowering, moe_decode, row_write

    for module in (row_write, gqa, moe_decode, sampler):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs",
                           "trinity-mini-ep8.json")) as f:
        c = trinity.TrinityConfig.from_dict(json.load(f))
    policy = trinity.bf16_policy()
    params = jax.eval_shape(lambda k: trinity.init_params(c, k, policy),
                            jax.random.key(0))
    eng = ServingEngine(c, params, policy=policy, num_slots=64,
                        chunk_size=32, max_len=9216)

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    compiled = jax.jit(lambda *a: eng._decode_chunk_impl(*a)).lower(
        placed(eng._params), placed(eng.state),
        *placed(eng._layout.chunk_operands())).compile()
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(eng.state))
    assert 4.29e9 < held < 4.31e9
    m = compiled.memory_analysis()
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert 2 * held <= total < 11.4e9, m
    text = compiled.as_text()
    assert "gqa_decode_fwd" in text and "row_write" in text
    assert not _buffers_of(text, "f32[64,4,8,9216]")
    assert not _buffers_of(text, "f32[64,4,8,2048]")


# ---- Granite 4.0-H's whole programs at published widths ----


@pytest.fixture(scope="module")
def granite_engine():
    """The engine of ``serve-granite-chat-backlog`` over ABSTRACT weights
    (its 32 slots' state is real, on the host: 3.1 GB of zeros)."""
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import granite_hybrid as gh

    c, policy = gh.GraniteHybridConfig(), gh.bf16_policy()
    params = jax.eval_shape(lambda k: gh.init_params(c, k, policy),
                            jax.random.key(0))
    return ServingEngine(c, params, policy=policy, num_slots=32,
                         chunk_size=32, max_len=2560)


@pytest.mark.parametrize("program", ["chunk", "admit-1024"])
def test_granite_programs_compile_for_the_chip_and_fit_it(
        shape, granite_engine, program, no_persistent_cache, monkeypatch):
    """All 40 layers, the whole vocabulary, 32 slots of carry, tail and
    grown keys: the chunk program (32 steps of every slot, the carry a
    float32 scan carry) and the admission of 2 rows at the 1024 bucket (4
    chunks of the scan a row), as the chip traces them (the step's key
    writes as ``ops/row_write.py``'s kernel).  Arguments, results and
    temporaries together stay under the chip's 16 GiB: the engine's
    programs do not donate their state, so it is there twice."""
    from progen_tpu.ops import gqa, lowering, row_write

    for module in (row_write, gqa):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    eng = granite_engine

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params, state = placed(eng._params), placed(eng.state)
    s, rows, lay = eng.num_slots, eng.admit_rows, eng._layout
    assert rows == 2
    if program == "chunk":
        # a fresh wrapper: ``jax.jit`` keeps a trace across the patch
        compiled = jax.jit(lambda *a: eng._decode_chunk_impl(*a)).lower(
            params, state, *placed(lay.chunk_operands())).compile()
    else:
        prefill = [shape((rows, 1024), jnp.int32), shape((rows,), jnp.int32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.uint32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.float32),
                   shape(eng._lmask_shape(rows), jnp.bool_)]
        compiled = jax.jit(lambda *a: eng._admit_impl(*a)).lower(
            params, state, shape((s,), jnp.int32), shape((s,), jnp.bool_),
            *prefill, *placed(lay.write_tables(rows))).compile()
    m = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert 6.38e9 < weights < 6.39e9 and 3.1e9 < held < 3.2e9
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert weights + 2 * held <= total < 15.5e9, m
    if program == "chunk":
        assert "tpu_custom_call" in compiled.as_text()      # the key writes


# ---- SDAR's whole programs at published widths ----


@pytest.fixture(scope="module")
def sdar_engine():
    """The engine of ``serve-sdar-blockdiff-backlog`` over ABSTRACT weights
    (its 64 slots' state is real, on the host: 2.0 GB of zeros)."""
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import sdar

    c = sdar.SDARConfig(num_hidden_layers=6, denoising_steps=2,
                        remasking="low_confidence_static")
    policy = sdar.bf16_policy()
    params = jax.eval_shape(lambda k: sdar.init_params(c, k, policy),
                            jax.random.key(0))
    return ServingEngine(c, params, policy=policy, num_slots=64,
                         chunk_size=30, max_len=2560)


@pytest.mark.parametrize("program", ["chunk", "admit-1024"])
def test_sdar_programs_compile_for_the_chip_and_fit_it(
        shape, sdar_engine, program, no_persistent_cache, monkeypatch):
    """6 whole expert layers (all 128 experts of each), the whole
    vocabulary, 64 slots of grown keys: the chunk program (30 forwards of
    64 x 8 positions — each slot's pending block in front of its block in
    progress: the block core as ``gqa_block_decode_fwd``, the pending
    block's withheld write as ``row_block_write``, the draw over the 256 x
    151,936 logits of the
    blocks in progress) and the admission of 4 rows at the 1024 bucket (the
    flash kernel under the block mask), as the chip traces them.
    Arguments, results and temporaries together stay under the chip's
    16 GiB: the engine's programs do not donate their state, so it is there
    twice.  The chunk program for the described chip at PR 48: arguments
    10.746 GB, results 2.024, temporaries 0.621 (0.459 with B positions a
    slot), 13.39 GB together."""
    from progen_tpu.decode import sampler
    from progen_tpu.ops import gqa, lowering, moe_decode, row_write

    for module in (row_write, gqa, moe_decode, sampler):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    eng = sdar_engine

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params, state = placed(eng._params), placed(eng.state)
    s, rows, lay = eng.num_slots, eng.admit_rows, eng._layout
    assert rows == 4
    if program == "chunk":
        compiled = jax.jit(lambda *a: eng._block_chunk_impl(*a)).lower(
            params, state, *placed(lay.chunk_operands())).compile()
    else:
        prefill = [shape((rows, 1024), jnp.int32), shape((rows,), jnp.int32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.uint32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.float32),
                   shape(eng._lmask_shape(rows), jnp.bool_)]
        compiled = jax.jit(lambda *a: eng._admit_impl(*a)).lower(
            params, state, shape((s,), jnp.int32), shape((s,), jnp.bool_),
            *prefill, *placed(lay.write_tables(rows))).compile()
    m = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert 8.72e9 < weights < 8.73e9 and 2.0e9 < held < 2.1e9
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    # an admission draws no token, so the compiler drops the head, the
    # final norm and the last layer's experts from it (1.83 GB it does not
    # even take as arguments): what it computes is the cache
    floor = weights + 2 * held - (0 if program == "chunk" else 1.84e9)
    assert floor <= total < 15.5e9, m
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    for kernel in (("row_block_write", "gqa_block_decode_fwd")
                   if program == "chunk" else ("gqa_prefill_fwd",)):
        assert kernel in text
    # the draw's 32 rounds go by groups of 32 rows whose keys the compiler
    # keeps in the chip's own memory (memory space 1): no uint32 array of
    # the draw's whole shape is left for a loop to read from HBM 32 times
    assert not _buffers_of(text, "u32[256,151936]")
    if program == "chunk":
        loops = [line for line in _buffers_of(text, "u32[32,151936]")
                 if " while(" in line]
        assert len(loops) == 1
        assert "u32[32,151936]{1,0:T(8,128)S(1)}" in loops[0]


# ---- LFM2's whole programs at published widths ----


@pytest.fixture(scope="module")
def lfm2_engine():
    """The engine of ``serve-lfm2-longgen-backlog`` over ABSTRACT weights
    (its 128 slots' state is real, on the host: 2.4 GB of zeros)."""
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import lfm2

    c = lfm2.LFM2Config(num_hidden_layers=12,
                        layer_types=lfm2.LFM2Config().layer_types[:12])
    policy = lfm2.bf16_policy()
    params = jax.eval_shape(lambda k: lfm2.init_params(c, k, policy),
                            jax.random.key(0))
    return ServingEngine(c, params, policy=policy, num_slots=128,
                         chunk_size=32, max_len=3072)


@pytest.mark.parametrize("program", ["chunk", "admit-1024"])
def test_lfm2_programs_compile_for_the_chip_and_fit_it(
        shape, lfm2_engine, program, no_persistent_cache, monkeypatch):
    """The first 12 layers (9 short convolutions, 3 attention; 2 dense, 10
    with all 32 experts), the whole vocabulary, 128 slots of two-row tails
    and grown keys: the chunk program (32 steps of every slot: 128 tokens a
    call is the last size ``moe_decode_fwd`` takes, the key writes
    ``ops/row_write.py``'s kernel) and the admission of 8 rows at the 1024
    bucket (8,192 tokens through ``ragged_dot``), as the chip traces them.
    Arguments, results and temporaries together stay under the chip's 16
    GiB: the engine's programs do not donate their state, so it is there
    twice."""
    from progen_tpu.decode import sampler
    from progen_tpu.ops import gqa, lowering, moe_decode, row_write

    for module in (row_write, gqa, moe_decode, sampler):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    eng = lfm2_engine

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params, state = placed(eng._params), placed(eng.state)
    s, rows, lay = eng.num_slots, eng.admit_rows, eng._layout
    assert rows == 8
    if program == "chunk":
        compiled = jax.jit(lambda *a: eng._decode_chunk_impl(*a)).lower(
            params, state, *placed(lay.chunk_operands())).compile()
    else:
        prefill = [shape((rows, 1024), jnp.int32), shape((rows,), jnp.int32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.uint32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.float32),
                   shape(eng._lmask_shape(rows), jnp.bool_)]
        compiled = jax.jit(lambda *a: eng._admit_impl(*a)).lower(
            params, state, shape((s,), jnp.int32), shape((s,), jnp.bool_),
            *prefill, *placed(lay.write_tables(rows))).compile()
    m = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert 7.85e9 < weights < 7.87e9 and 2.4e9 < held < 2.5e9
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert weights + 2 * held <= total < 15.5e9, m
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if program == "chunk":
        # the chunk's stated peak, the decode core a kernel
        assert total < 12.8e9, m
        assert "moe_decode_fwd" in text and "gqa_decode_fwd" in text
        assert not _buffers_of(text, "f32[128,4,8,3072]")


# ---- Nemotron-H's whole programs at published widths ----


@pytest.fixture(scope="module")
def nemotron_engine():
    """The engine of ``serve-nemotron3-longgen-backlog`` over ABSTRACT
    weights (its 64 slots' state is real, on the host: 1.6 GB of zeros)."""
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import nemotron_h

    c = nemotron_h.NemotronHConfig(
        num_hidden_layers=11, vocab_size=32768, experts_held=128,
        hybrid_override_pattern=nemotron_h.NemotronHConfig(
        ).hybrid_override_pattern[:11])
    policy = nemotron_h.bf16_policy()
    params = jax.eval_shape(lambda k: nemotron_h.init_params(c, k, policy),
                            jax.random.key(0))
    return ServingEngine(c, params, policy=policy, num_slots=64,
                         chunk_size=32, max_len=3072)


@pytest.mark.parametrize("program", ["chunk", "admit-1024"])
def test_nemotron_programs_compile_for_the_chip_and_fit_it(
        shape, nemotron_engine, program, no_persistent_cache, monkeypatch):
    """Stage 0 of 8 (``MEMEMEM*EME``: 5 Mamba-2 mixers of eight groups, 5
    latent expert layers with 128 of 512 two-matrix experts, 1 attention
    layer), a quarter of the vocabulary, 64 slots of float32 carries, tails
    and grown keys: the chunk program (32 steps of every slot: 64 tokens a
    call through ``moe_decode_fwd`` in its two-matrix form) and the
    admission of 4 rows at the 1024 bucket (4,096 tokens through
    ``ragged_dot``, two products a window), as the chip traces them.
    Arguments, results and temporaries together stay under the chip's 16
    GiB: the engine's programs do not donate their state, so it is there
    twice."""
    from progen_tpu.decode import sampler
    from progen_tpu.ops import gqa, lowering, moe_decode, row_write

    for module in (row_write, gqa, moe_decode, sampler):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    eng = nemotron_engine

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params, state = placed(eng._params), placed(eng.state)
    s, rows, lay = eng.num_slots, eng.admit_rows, eng._layout
    assert rows == 4
    if program == "chunk":
        compiled = jax.jit(lambda *a: eng._decode_chunk_impl(*a)).lower(
            params, state, *placed(lay.chunk_operands())).compile()
    else:
        prefill = [shape((rows, 1024), jnp.int32), shape((rows,), jnp.int32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.uint32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.float32),
                   shape(eng._lmask_shape(rows), jnp.bool_)]
        compiled = jax.jit(lambda *a: eng._admit_impl(*a)).lower(
            params, state, shape((s,), jnp.int32), shape((s,), jnp.bool_),
            *prefill, *placed(lay.write_tables(rows))).compile()
    m = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert 9.29e9 < weights < 9.31e9 and 1.5e9 < held < 1.7e9, (weights,
                                                                 held)
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    assert weights + 2 * held <= total < 15.5e9, m
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    if program == "chunk":
        assert "moe_decode_fwd" in text and "gqa_decode_fwd" in text


# ---- MiMo-V2's whole programs at published widths ----


@pytest.fixture(scope="module")
def mimo_engine():
    """The engine of ``serve-mimo-longdoc-backlog`` over ABSTRACT weights
    (its 16 slots' state is real, on the host: 1.5 GB of zeros)."""
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import mimo_v2

    with open(os.path.join(os.path.dirname(__file__), "..", "perf",
                           "configs", "mimo-v2.5-ep16.json")) as f:
        c = mimo_v2.MiMoV2Config.from_dict(json.load(f))
    policy = mimo_v2.bf16_policy()
    params = jax.eval_shape(lambda k: mimo_v2.init_params(c, k, policy),
                            jax.random.key(0))
    return ServingEngine(c, params, policy=policy, num_slots=16,
                         chunk_size=32, max_len=17408)


@pytest.mark.parametrize("program", ["chunk", "admit-16384"])
def test_mimo_programs_compile_for_the_chip_and_fit_it(
        shape, mimo_engine, program, no_persistent_cache, monkeypatch):
    """Layers 0-6 of 48 (the dense layer and one whole period: 5 sliding
    layers of 8 key/value heads under a sink, 2 full ones of 4, keys 192
    wide beside values of 128; 16 of 256 experts), an eighth of the
    vocabulary, 16 slots of 128-row rings and 17,408 grown rows: the chunk
    program (32 steps of every slot: 16 tokens a call through
    ``moe_decode_fwd``, the full layers' core ``gqa_decode_fwd`` at two
    widths since PR 55 — ONE such kernel in the text, the two layers share
    it —, the rings' core the XLA form) and the admission of 1 row at the
    16,384 bucket (through ``moe_sorted_fwd``; the blocked XLA attention,
    no ``gqa_prefill_fwd`` and no decode core), as the chip traces them.
    Arguments, results and temporaries together stay under the chip's 16
    GiB: the engine's programs do not donate their state, so it is there
    twice."""
    from progen_tpu.decode import sampler
    from progen_tpu.ops import gqa, lowering, moe_decode, row_write

    for module in (row_write, gqa, moe_decode, sampler):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    eng = mimo_engine

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params, state = placed(eng._params), placed(eng.state)
    s, rows, lay = eng.num_slots, eng.admit_rows, eng._layout
    assert rows == 1
    if program == "chunk":
        compiled = jax.jit(lambda *a: eng._decode_chunk_impl(*a)).lower(
            params, state, *placed(lay.chunk_operands())).compile()
    else:
        prefill = [shape((rows, 16384), jnp.int32), shape((rows,), jnp.int32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.uint32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.float32),
                   shape(eng._lmask_shape(rows), jnp.bool_)]
        compiled = jax.jit(lambda *a: eng._admit_impl(*a)).lower(
            params, state, shape((s,), jnp.int32), shape((s,), jnp.bool_),
            *prefill, *placed(lay.write_tables(rows))).compile()
    m = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert 6.85e9 < weights < 6.87e9 and 1.4e9 < held < 1.6e9, (weights,
                                                                 held)
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"mimo {program}: weights {weights / 1e9:.2f} GB, state "
          f"{held / 1e9:.2f} GB, temporaries {m.temp_size_in_bytes / 1e9:.2f}"
          f" GB, total {total / 1e9:.2f} GB")
    assert weights + 2 * held <= total < 15.5e9, m
    text = compiled.as_text()
    assert "tpu_custom_call" in text and "gqa_prefill_fwd" not in text
    assert ("gqa_decode_fwd" in text) == (program == "chunk")
    if program == "chunk":
        assert "moe_decode_fwd" in text and "row_write" in text
    else:
        assert "moe_sorted_fwd" in text


# ---- dots3's whole programs at published widths ----


@pytest.fixture(scope="module")
def dots3_engine():
    """The engine of ``serve-dots3-longdoc-backlog`` over ABSTRACT weights
    (its 16 slots' state is real, on the host: 0.85 GB of zeros)."""
    from progen_tpu.decode.engine import ServingEngine
    from progen_tpu.models import dots3

    with open(os.path.join(os.path.dirname(__file__), "..", "perf",
                           "configs", "dots3-note-prev-ep8.json")) as f:
        c = dots3.Dots3Config.from_dict(json.load(f))
    policy = dots3.bf16_policy()
    params = jax.eval_shape(lambda k: dots3.init_params(c, k, policy),
                            jax.random.key(0))
    return ServingEngine(c, params, policy=policy, num_slots=16,
                         chunk_size=32, max_len=17408)


@pytest.mark.parametrize("program", ["chunk", "admit-16384"])
def test_dots3_programs_compile_for_the_chip_and_fit_it(
        shape, dots3_engine, program, no_persistent_cache, monkeypatch):
    """Layers 0-4 of 46 (the dense layer and one whole period: 2 full
    layers of 128 heads over 576-wide latents thinned to 2,048 rows by a
    64-head indexer, 3 sliding ones of 64 heads over a 513-row ring of
    1,088-wide latents; 32 of 256 experts beside a shared one), an eighth of
    the vocabulary, 16 slots of 17,408 rows: the chunk program (32 steps of
    every slot: the indexer's score and ``top_k`` over every slot's rows,
    the gathered 2,048 rows through ``mla_decode_fwd`` — ONE such kernel in
    the text, the two full layers share it, the rings' core the XLA form —,
    16 tokens a call through ``moe_decode_fwd``) and the admission of 1 row
    at the 16,384 bucket (through ``moe_sorted_fwd``; the full layers' core
    ``mla_prefill_fwd`` under the selection of ``ops/dsa.py`` as its keep
    mask, the sliding ones the windowed XLA blocks of ``ops/gqa.py``; no
    decode core), as the chip traces them.  Arguments, results
    and temporaries together stay under the chip's 16 GiB: the engine's
    programs do not donate their state, so it is there twice."""
    from progen_tpu.decode import sampler
    from progen_tpu.ops import (gqa, lowering, mla_decode, mla_prefill,
                                moe_decode, row_write)

    for module in (row_write, gqa, mla_decode, mla_prefill, moe_decode,
                   sampler):
        monkeypatch.setattr(module, "_on_tpu", lambda: True)
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    eng = dots3_engine

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params, state = placed(eng._params), placed(eng.state)
    s, rows, lay = eng.num_slots, eng.admit_rows, eng._layout
    assert rows == 1
    if program == "chunk":
        compiled = jax.jit(lambda *a: eng._decode_chunk_impl(*a)).lower(
            params, state, *placed(lay.chunk_operands())).compile()
    else:
        prefill = [shape((rows, 16384), jnp.int32), shape((rows,), jnp.int32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.uint32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.float32),
                   shape(eng._lmask_shape(rows), jnp.bool_)]
        compiled = jax.jit(lambda *a: eng._admit_impl(*a)).lower(
            params, state, shape((s,), jnp.int32), shape((s,), jnp.bool_),
            *prefill, *placed(lay.write_tables(rows))).compile()
    m = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert 8.17e9 < weights < 8.18e9 and 0.8e9 < held < 0.9e9, (weights,
                                                                 held)
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"dots3 {program}: weights {weights / 1e9:.2f} GB, state "
          f"{held / 1e9:.2f} GB, temporaries {m.temp_size_in_bytes / 1e9:.2f}"
          f" GB, total {total / 1e9:.2f} GB")
    assert weights + 2 * held <= total < 15.5e9, m
    text = compiled.as_text()
    assert "tpu_custom_call" in text
    assert ("mla_prefill_fwd" in text) == (program != "chunk")
    assert ("mla_decode_fwd" in text) == (program == "chunk")
    if program == "chunk":
        assert "moe_decode_fwd" in text and "row_write" in text
    else:
        assert "moe_sorted_fwd" in text
