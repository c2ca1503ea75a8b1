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

import dataclasses
import importlib
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


@pytest.mark.parametrize("bucket", [512, 1024, 2048, 4096, 8192, 16384])
def test_gqa_prefill_kernel_compiles_for_v5e_at_two_widths(
        shape, no_persistent_cache, bucket):
    """``gqa_prefill_fwd`` as a FULL layer of ``serve-mimo-longdoc-backlog``
    admits (PR 62): 1 row, 64 query heads over 4 key/value heads, keys 192
    wide — padded to 256 on the way in — beside values of 128, bfloat16,
    each prefill bucket the cell warms; the output is ``H * 128`` wide."""
    from progen_tpu.ops.gqa import pallas_prefill_attention

    r, heads, kv, d, dv, bf16 = 1, 64, 4, 192, 128, jnp.bfloat16
    fn = jax.jit(lambda q, k, v, n: pallas_prefill_attention(
        q, k, v, n, d ** -0.5, interpret=False))
    args = (shape((r, bucket, heads * d), bf16),
            shape((r, kv, bucket, d), bf16), shape((r, kv, bucket, dv), bf16),
            shape((r,), jnp.int32))
    assert "tpu_custom_call" in fn.lower(*args).compile().as_text()
    assert fn.eval_shape(*args).shape == (r, bucket, heads * dv)


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
    (4096, 12, 768, 6144, 2048, 16, True),
    (16384, 8, 256, 5120, 1536, 32, True),
    (16384, 8, 256, 4096, 2048, 16, True)],
    ids=["trinity-admit-4x8192", "lfm2-admit-8x1024",
         "nemotron3-admit-4x1024-two-matrix", "dsv2-admit-4x512",
         "sdar-admit-4x512", "longcat-admit-2x2048", "dots3-admit-1x16384",
         "mimo-admit-1x16384"])
def test_moe_sorted_kernel_compiles_for_v5e(shape, no_persistent_cache,
                                            monkeypatch, tokens, k, router, h,
                                            inner, held, gated):
    """The held experts' product over row tiles of the SORTED rows
    (``ops/moe_decode.py``, ``moe_sorted_fwd``) at an admission run of each
    expert cell, over one window of ``experts.sorted_window`` rows with the
    tiles ``fitted_tile`` gives the chip path: row tiles of 128, Trinity's
    and LFM2's whole expert a step (12.6 and 22 MB, double-buffered),
    DeepSeek-V2's, dots3's and LongCat's in two and four steps, beside the
    item's terms and its tokens' rows of ``y`` (two float32 ``(128, h)``
    scratches; ``y`` itself in HBM, a row a DMA, the window's token numbers
    prefetched: 64 KB of scalars at Trinity's 16,384 rows), all under the
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
    fn = jax.jit(lambda y, xs, tok, wt, lo, hi, wg, wu, wd:
                 md.pallas_sorted_add(
                     y, xs, tok, wt, lo, hi, wg, wu, wd, row_tile=tiles.rows,
                     tile=tiles.inner, interpret=False), donate_argnums=0)
    compiled = fn.lower(
        shape((tokens, h // md.LANE, md.LANE), jnp.float32),
        shape((rows, h), bf16), shape((rows,), jnp.int32),
        shape((rows,), jnp.float32), shape((held,), jnp.int32),
        shape((held,), jnp.int32), wg, wu, wd).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # ``y`` is added to where it lies: no second copy of it
    m = compiled.memory_analysis()
    assert m.alias_size_in_bytes == tokens * h * 4, m
    assert m.temp_size_in_bytes < 8 << 20, m


# ---- the families' whole programs at published widths ----


def _perf_config(models, config_class, name):
    """The cell's configuration as ``perf/configs/<name>.json`` has it."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "perf", "configs", f"{name}.json")) as f:
        return getattr(models, config_class).from_dict(json.load(f))


def _sdar_draws_by_groups_in_the_chips_own_memory(text):
    """The draw's 32 rounds go by groups of 32 rows whose keys the compiler
    keeps in the chip's own memory (memory space 1): ONE loop reads them."""
    loops = [line for line in _buffers_of(text, "u32[32,151936]")
             if " while(" in line]
    assert len(loops) == 1
    assert "u32[32,151936]{1,0:T(8,128)S(1)}" in loops[0]


def _selection_counts_in_the_chips_own_memory(text):
    """An admission's learned selection at the 16,384 bucket (``ops/dsa.py``
    over ``ops/kth.py``): each of the seven spans past ``top_k`` counts its
    thresholds a block of 128 query rows in loops whose keys the compiler
    keeps in the chip's own memory (memory space 1) through the 32 rounds,
    and no row of scores is sorted."""
    for end in range(4096, 16384 + 1, 2048):
        loops = [line for line in _buffers_of(text, f"u32[128,{end}]")
                 if " while(" in line]
        assert loops, end
        for line in loops:
            assert f"u32[128,{end}]{{1,0:T(8,128)S(1)}}" in line, line
    assert not [line for line in text.splitlines()
                if " sort(" in line and "f32[1,128," in line]


@dataclasses.dataclass(frozen=True)
class Whole:
    """One family's serving cell as its engine's programs are compiled for
    the described chip over ABSTRACT weights (the slots' state is real, on
    the host, for that family's cases alone)."""

    cell: str
    models: str                 # under ``progen_tpu.models``
    config: object              # ``models -> `` the cell's configuration
    engine: dict                # the cell's engine arguments
    admit: tuple | None         # (rows a run, the bucket lowered)
    state: tuple                # bounds on the slots' state, bytes
    weights: tuple | None = None        # bounds on the weights, bytes
    peak: float = 15.5e9        # arguments + results + temporaries, under
    chunk_peak: float | None = None     # the chunk's stated peak
    dropped: float = 0.0        # weights an admission does not even take
    # the modules under ``progen_tpu`` whose ``_on_tpu`` says the chip is there
    ops: tuple = ("ops.row_write", "ops.gqa", "ops.moe_decode",
                  "ops.kth")
    chunk: tuple = ()           # names the chunk program's text must hold
    admission: tuple = ()       # names the admission's text must hold
    never: dict = dataclasses.field(default_factory=dict)   # program -> names
    no_buffers: dict = dataclasses.field(default_factory=dict)  # -> shapes
    chunk_also: object = None   # a further check of the chunk's text
    admit_also: object = None   # a further check of the admission's text


WHOLE_PROGRAMS = {
    # 32 steps of 9 layers, the rings and grown keys written by
    # ``row_write`` and read by ``gqa_decode_fwd`` up to each slot's count,
    # with no float32 score tensor over a whole cache; under the 11.38 GB
    # the cell's file states for it (4.3 GB of zeros on the host)
    "trinity": Whole(
        "serve-trinity-mixedlen-backlog", "trinity",
        lambda m: _perf_config(m, "TrinityConfig", "trinity-mini-ep8"),
        dict(num_slots=64, chunk_size=32, max_len=9216), admit=None,
        state=(4.29e9, 4.31e9), peak=11.4e9,
        chunk=("gqa_decode_fwd", "row_write"),
        no_buffers={"chunk": ("f32[64,4,8,9216]", "f32[64,4,8,2048]")}),
    # all 40 layers, the whole vocabulary, 32 slots of carry, tail and grown
    # keys: the carry a float32 scan carry, the admission 4 chunks of the
    # scan a row, the step's key writes ``ops/row_write.py``'s kernel
    "granite": Whole(
        "serve-granite-chat-backlog", "granite_hybrid",
        lambda m: m.GraniteHybridConfig(),
        dict(num_slots=32, chunk_size=32, max_len=2560), admit=(2, 1024),
        weights=(6.38e9, 6.39e9), state=(3.1e9, 3.2e9),
        ops=("ops.row_write", "ops.gqa"), chunk=("tpu_custom_call",)),
    # 6 whole expert layers (all 128 experts of each), the whole vocabulary,
    # 64 slots of grown keys: 30 forwards of 64 x 8 positions — the block
    # core ``gqa_block_decode_fwd``, the pending block's withheld write
    # ``row_block_write``, the draw over 256 x 151,936 logits — and 4 rows
    # through the flash kernel under the block mask.  An admission draws no
    # token, so the compiler drops the head, the final norm and the last
    # layer's experts from it (1.83 GB it does not even take as arguments).
    # The chunk program at PR 48: arguments 10.746 GB, results 2.024,
    # temporaries 0.621, 13.39 GB together
    "sdar": Whole(
        "serve-sdar-blockdiff-backlog", "sdar",
        lambda m: m.SDARConfig(num_hidden_layers=6, denoising_steps=2,
                               remasking="low_confidence_static"),
        dict(num_slots=64, chunk_size=30, max_len=2560), admit=(4, 1024),
        weights=(8.72e9, 8.73e9), state=(2.0e9, 2.1e9), dropped=1.84e9,
        chunk=("tpu_custom_call", "row_block_write", "gqa_block_decode_fwd"),
        admission=("tpu_custom_call", "gqa_prefill_fwd"),
        no_buffers={"chunk": ("u32[256,151936]",),
                    "admit": ("u32[256,151936]",)},
        chunk_also=_sdar_draws_by_groups_in_the_chips_own_memory),
    # the first 12 layers (9 short convolutions, 3 attention; 2 dense, 10
    # with all 32 experts), the whole vocabulary, 128 slots of two-row tails
    # and grown keys: 128 tokens a call is the last size ``moe_decode_fwd``
    # takes; 8 rows at the 1024 bucket (2.4 GB of zeros on the host)
    "lfm2": Whole(
        "serve-lfm2-longgen-backlog", "lfm2",
        lambda m: m.LFM2Config(num_hidden_layers=12,
                               layer_types=m.LFM2Config().layer_types[:12]),
        dict(num_slots=128, chunk_size=32, max_len=3072), admit=(8, 1024),
        weights=(7.85e9, 7.87e9), state=(2.4e9, 2.5e9), chunk_peak=12.8e9,
        chunk=("tpu_custom_call", "moe_decode_fwd", "gqa_decode_fwd"),
        admission=("tpu_custom_call",),
        no_buffers={"chunk": ("f32[128,4,8,3072]",)}),
    # stage 0 of 8 (``MEMEMEM*EME``), a quarter of the vocabulary, 64 slots
    # of float32 carries, tails and grown keys: 64 tokens a call through
    # ``moe_decode_fwd`` in its two-matrix form; 4 rows at the 1024 bucket
    "nemotron": Whole(
        "serve-nemotron3-longgen-backlog", "nemotron_h",
        lambda m: m.NemotronHConfig(
            num_hidden_layers=11, vocab_size=32768, experts_held=128,
            hybrid_override_pattern=m.NemotronHConfig(
            ).hybrid_override_pattern[:11]),
        dict(num_slots=64, chunk_size=32, max_len=3072), admit=(4, 1024),
        weights=(9.29e9, 9.31e9), state=(1.5e9, 1.7e9),
        chunk=("tpu_custom_call", "moe_decode_fwd", "gqa_decode_fwd"),
        admission=("tpu_custom_call",)),
    # layers 0-6 of 48, an eighth of the vocabulary, 16 slots of 128-row
    # rings and 17,408 grown rows: the full layers' core ``gqa_decode_fwd``
    # at two widths since PR 55, the rings' core the XLA form; 1 row at the
    # 16,384 bucket through ``moe_sorted_fwd``, the full layers' core
    # ``gqa_prefill_fwd`` over keys padded to 256 (PR 62: no float32 score
    # block of theirs is a buffer) and the sliding layers' blocked XLA form
    "mimo": Whole(
        "serve-mimo-longdoc-backlog", "mimo_v2",
        lambda m: _perf_config(m, "MiMoV2Config", "mimo-v2.5-ep16"),
        dict(num_slots=16, chunk_size=32, max_len=17408), admit=(1, 16384),
        weights=(6.85e9, 6.87e9), state=(1.4e9, 1.6e9),
        chunk=("tpu_custom_call", "gqa_decode_fwd", "moe_decode_fwd",
               "row_write"),
        admission=("tpu_custom_call", "moe_sorted_fwd", "gqa_prefill_fwd"),
        never={"chunk": ("gqa_prefill_fwd",), "admit": ("gqa_decode_fwd",)},
        no_buffers={"admit": ("f32[4,16,256", "bf16[4,1,4,16,256,128]")}),
    # layers 0-4 of 46, an eighth of the vocabulary, 16 slots of 17,408
    # rows: the indexer's score and ``top_k`` over every slot's rows, the
    # gathered 2,048 rows through ``mla_decode_fwd``; 1 row at the 16,384
    # bucket: the full layers' core ``mla_prefill_fwd`` under the selection
    # of ``ops/dsa.py`` as its keep mask, the sliding ones the windowed XLA
    # blocks of ``ops/gqa.py``
    "dots3": Whole(
        "serve-dots3-longdoc-backlog", "dots3",
        lambda m: _perf_config(m, "Dots3Config", "dots3-note-prev-ep8"),
        dict(num_slots=16, chunk_size=32, max_len=17408), admit=(1, 16384),
        weights=(8.17e9, 8.18e9), state=(0.8e9, 0.9e9),
        ops=("ops.row_write", "ops.gqa", "ops.mla_decode", "ops.mla_prefill",
             "ops.moe_decode", "ops.kth"),
        chunk=("tpu_custom_call", "mla_decode_fwd", "moe_decode_fwd",
               "row_write"),
        admission=("tpu_custom_call", "mla_prefill_fwd", "moe_sorted_fwd"),
        never={"chunk": ("mla_prefill_fwd",), "admit": ("mla_decode_fwd",)},
        admit_also=_selection_counts_in_the_chips_own_memory),
    # the published layers 2-6 of 78, an eighth of the vocabulary, 16 slots
    # of 17,408 rows in five latent leaves and two indexer leaves (the chunk
    # program 13.32 GB, 2.06 of it the row-major twins of the five latent
    # leaves, which the chip keeps with their rows minor; the admission
    # 14.37: PERF.md section 6, PR 60): the
    # indexers' score and ``top_k`` twice a step, the 2,048 gathered rows
    # through ``mla_decode_fwd`` in all five layers; 1 row at the 16,384
    # bucket: every layer's core ``gqa_prefill_fwd`` over the joined 256-wide
    # heads under a keep mask that two layers compute and three borrow
    "glm52": Whole(
        "serve-glm52-longdoc-backlog", "glm_dsa",
        lambda m: _perf_config(m, "GLMDSAConfig", "glm-5.2-ep16"),
        dict(num_slots=16, chunk_size=32, max_len=17408), admit=(1, 16384),
        weights=(7.76e9, 7.77e9), state=(1.74e9, 1.76e9), chunk_peak=13.4e9,
        ops=("ops.row_write", "ops.gqa", "ops.mla_decode", "ops.moe_decode",
             "ops.kth"),
        chunk=("tpu_custom_call", "mla_decode_fwd", "moe_decode_fwd",
               "row_write"),
        admission=("tpu_custom_call", "gqa_prefill_fwd", "moe_sorted_fwd"),
        never={"chunk": ("gqa_prefill_fwd",),
               "admit": ("mla_decode_fwd", "mla_prefill_fwd")},
        admit_also=_selection_counts_in_the_chips_own_memory),
    # layers 0-7 of 48 (two periods of three delta-rule layers to one gated
    # full-attention layer), 128 of 512 experts, a quarter of the
    # vocabulary, 32 slots: six float32 carries and tails and two grown
    # caches of 17,408 rows of 2 heads of 256 each; the full layers' cores
    # ``gqa_decode_fwd`` / ``gqa_prefill_fwd`` at d = 256, the delta rule's
    # prefill a kernel, its step plain XLA: an admission holds neither a
    # segment's float32 triangles nor an operand transposed chunk-major; 2
    # rows at the 16,384 bucket through ``moe_sorted_fwd``
    "qwen3next": Whole(
        "serve-qwen3next-longdoc-backlog", "qwen3_next",
        lambda m: _perf_config(m, "Qwen3NextConfig",
                               "qwen3-next-80b-a3b-ep4pp6"),
        dict(num_slots=32, chunk_size=32, max_len=17408), admit=(2, 16384),
        weights=(7.33e9, 7.34e9), state=(2.6e9, 2.8e9),
        ops=("ops.row_write", "ops.gqa", "ops.moe_decode", "ops.kth",
             "ops.gdn"),
        chunk=("tpu_custom_call", "gqa_decode_fwd", "moe_decode_fwd",
               "row_write"),
        admission=("tpu_custom_call", "moe_sorted_fwd", "gqa_prefill_fwd",
                   "gdn_prefill_fwd"),
        never={"chunk": ("gqa_prefill_fwd", "gdn_prefill_fwd"),
               "admit": ("gqa_decode_fwd",)},
        no_buffers={"admit": ("f32[32,2,16,2,1,64,64]",
                              "f32[32,2,16,2,64,64]",
                              "bf16[8,32,2,16,64,128]",
                              "bf16[8,32,2,16,2,64,128]")}),
    # the published layers 0 and 37-41 of 42 (five channel-decay delta-rule
    # layers to one gated latent layer), 128 of 512 experts under a SwiGLU
    # limit, a quarter of the vocabulary, 64 slots: five float32 carries and
    # tails and ONE latent leaf of 17,408 rows of 576; the latent layer's
    # cores ``mla_decode_fwd`` / ``mla_prefill_fwd`` at 32 heads, the delta
    # rule's step plain XLA, its prefill the kernel ``kda_prefill_fwd``
    # (PR 66) a row of the admission at a time: an admission holds neither a
    # segment's float32 triangles nor ``q``, ``k``, ``v`` or ``g``
    # transposed chunk-major; 4 rows at the 16,384 bucket through
    # ``moe_sorted_fwd``, 15.38 GB before the kernel
    "ling3": Whole(
        "serve-ling3-longdoc-backlog", "bailing_hybrid",
        lambda m: _perf_config(m, "BailingHybridConfig",
                               "ling-3.0-flash-ep4pp7"),
        dict(num_slots=64, chunk_size=32, max_len=17408), admit=(4, 16384),
        weights=(8.70e9, 8.72e9), state=(1.9e9, 2.1e9), peak=15.39e9,
        ops=("ops.row_write", "ops.mla_decode", "ops.mla_prefill",
             "ops.moe_decode", "ops.kth", "ops.gdn"),
        chunk=("tpu_custom_call", "mla_decode_fwd", "moe_decode_fwd",
               "row_write"),
        admission=("tpu_custom_call", "moe_sorted_fwd", "mla_prefill_fwd",
                   "kda_prefill_fwd"),
        never={"chunk": ("mla_prefill_fwd", "gdn_prefill_fwd",
                         "kda_prefill_fwd"),
               "admit": ("mla_decode_fwd", "gdn_prefill_fwd")},
        no_buffers={"admit": ("f32[32,1,32,1,64,64]", "f32[32,32,64,64]",
                              "bf16[32,1,32,64,64]",
                              "bf16[8,32,1,32,64,128]",
                              "f32[8,32,1,32,64,128]")}),
}

PROGRAMS = [(name, program) for name, row in WHOLE_PROGRAMS.items()
            for program in ("chunk", "admit")
            if program == "chunk" or row.admit]


@pytest.fixture(scope="module")
def whole_engine():
    """``name -> `` the row's engine over abstract weights, ONE family's
    alive at a time: a family's cases follow each other, and the gigabytes
    of zeros its slots' state is on the host go when the next is built."""
    from progen_tpu.decode.engine import ServingEngine

    alive = {}

    def engine_of(name):
        if name not in alive:
            alive.clear()
            row = WHOLE_PROGRAMS[name]
            models = importlib.import_module(
                f"progen_tpu.models.{row.models}")
            config, policy = row.config(models), models.bf16_policy()
            params = jax.eval_shape(
                lambda k: models.init_params(config, k, policy),
                jax.random.key(0))
            alive[name] = ServingEngine(config, params, policy=policy,
                                        **row.engine)
        return alive[name]

    yield engine_of
    alive.clear()


def _compiled_for_the_chip(eng, shape, bucket=None):
    """The engine's chunk program (``bucket`` None) or its admission at
    ``bucket`` compiled for the described chip: the ONE place in ``tests/``
    that reaches the engine's private programs (``_chunk_impl()``, which is
    ``_decode_chunk_impl`` or, for a family that generates by blocks,
    ``_block_chunk_impl``; ``_admit_impl``; ``_layout``; ``_lmask_shape``;
    ``_params``) — ROADMAP D14.  A fresh wrapper each: ``jax.jit`` keeps a
    trace across a patch.  Returns ``(compiled, params, state)``, the last
    two abstract."""

    def placed(tree):
        return jax.tree.map(lambda a: shape(a.shape, a.dtype), tree)

    params, state = placed(eng._params), placed(eng.state)
    s, rows, lay = eng.num_slots, eng.admit_rows, eng._layout
    if bucket is None:
        chunk = eng._chunk_impl()
        compiled = jax.jit(lambda *a: chunk(*a)).lower(
            params, state, *placed(lay.chunk_operands())).compile()
    else:
        prefill = [shape((rows, bucket), jnp.int32), shape((rows,), jnp.int32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.uint32),
                   shape((rows,), jnp.int32), shape((rows,), jnp.float32),
                   shape(eng._lmask_shape(rows), jnp.bool_)]
        compiled = jax.jit(lambda *a: eng._admit_impl(*a)).lower(
            params, state, shape((s,), jnp.int32), shape((s,), jnp.bool_),
            *prefill, *placed(lay.write_tables(rows))).compile()
    return compiled, params, state


@pytest.mark.parametrize("name,program", PROGRAMS, ids=[
    f"{name}-{program}" + (f"-{WHOLE_PROGRAMS[name].admit[1]}"
                           if program == "admit" else "")
    for name, program in PROGRAMS])
def test_a_familys_programs_compile_for_the_chip_and_fit_it(
        shape, whole_engine, name, program, no_persistent_cache, monkeypatch):
    """The chunk program (``chunk_size`` steps of every slot) and the
    admission of one run at the row's bucket, at the widths of the family's
    serving cell (``WHOLE_PROGRAMS``), as the chip traces them.  Arguments,
    results and temporaries together stay under the row's peak (the chip's
    16 GiB unless the cell states less): the engine's programs do not
    donate their state, so it is there twice."""
    from progen_tpu.ops import lowering

    row = WHOLE_PROGRAMS[name]
    for op in row.ops:
        monkeypatch.setattr(importlib.import_module(f"progen_tpu.{op}"),
                            "_on_tpu", lambda: True)
    monkeypatch.setattr(lowering, "on_tpu", lambda: True)
    eng = whole_engine(name)
    if program == "admit":
        assert eng.admit_rows == row.admit[0]
    compiled, params, state = _compiled_for_the_chip(
        eng, shape, row.admit[1] if program == "admit" else None)
    m = compiled.memory_analysis()
    weights = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(params))
    held = sum(a.size * a.dtype.itemsize for a in jax.tree.leaves(state))
    assert row.state[0] < held < row.state[1], held
    if row.weights:
        assert row.weights[0] < weights < row.weights[1], weights
    total = (m.argument_size_in_bytes + m.output_size_in_bytes
             + m.temp_size_in_bytes - m.alias_size_in_bytes)
    print(f"{name} {program}: weights {weights / 1e9:.2f} GB, state "
          f"{held / 1e9:.2f} GB, temporaries {m.temp_size_in_bytes / 1e9:.2f}"
          f" GB, total {total / 1e9:.2f} GB")
    floor = (weights if row.weights else 0) + 2 * held - (
        row.dropped if program == "admit" else 0)
    assert floor <= total < row.peak, m
    if program == "chunk" and row.chunk_peak:
        assert total < row.chunk_peak, m
    text = compiled.as_text()
    for kernel in row.chunk if program == "chunk" else row.admission:
        assert kernel in text, kernel
    for kernel in row.never.get(program, ()):
        assert kernel not in text, kernel
    for buffer in row.no_buffers.get(program, ()):
        assert not _buffers_of(text, buffer), buffer
    also = row.chunk_also if program == "chunk" else row.admit_also
    if also:
        also(text)
