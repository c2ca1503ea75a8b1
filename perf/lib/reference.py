"""ProGen's forward pass and loss in plain ``jax.numpy`` float32.

Written from SURVEY.md section 2.a/2.b, not from ``progen_tpu.models`` or
``progen_tpu.ops`` (nothing is imported from the program): no kernels, no
cache, no mixed precision, a Python loop over attention windows.  The
weights are data and arrive as the nested dict the program's checkpoints
use (``attn{i}/to_qkv/kernel`` ...).  Callers wrap calls in
``jax.default_matmul_precision("highest")``: on a TPU a float32 matmul
otherwise runs in bf16 passes.

Departures from the Haiku original, each shared with the program: batched
``(B, L)`` input instead of ``vmap`` over rows; GELU is the tanh
approximation (``jax.nn.gelu``'s default, which Haiku's model called).
"""

from __future__ import annotations

import math

import jax.numpy as jnp

MASK_VALUE = -1e10
LN_EPS = 1e-5


def layer_norm(x, scale):
    mu = x.mean(-1, keepdims=True)
    var = ((x - mu) ** 2).mean(-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + LN_EPS) * scale


def gelu(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        math.sqrt(2.0 / math.pi) * (x + 0.044715 * x ** 3)))


def linear(x, p):
    y = x @ p["kernel"]
    return y + p["bias"] if "bias" in p else y


def shift_tokens(x):
    """First half of the channels (the larger half when odd) looks one
    position back; position 0 sees zeros."""
    d = x.shape[-1]
    split = d - d // 2
    shifted = jnp.concatenate(
        [jnp.zeros_like(x[:, :1, :split]), x[:, :-1, :split]], axis=1)
    return jnp.concatenate([shifted, x[..., split:]], axis=-1)


def rotary_tables(n, d):
    inv_freq = 1.0 / (10000.0 ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(n, dtype=jnp.float32)[:, None] * inv_freq[None, :]
    ang = jnp.repeat(ang, 2, axis=-1)  # each frequency twice in a row
    return jnp.sin(ang), jnp.cos(ang)


def rotary(x, sin, cos):
    """Interleaved rotation over ``(..., n, d)``: pairs (x0, x1) -> (-x1, x0)."""
    pairs = x.reshape(x.shape[:-1] + (x.shape[-1] // 2, 2))
    rot = jnp.stack([-pairs[..., 1], pairs[..., 0]], axis=-1).reshape(x.shape)
    return x * cos + rot * sin


def window_attention(q, k, v, wsz):
    """``(B, H, n, d)``.  Window ``w``'s queries see the previous window and
    their own, causally.  Window 0's previous window is ``wsz`` zero keys
    that ARE visible (logit 0, value 0): the original pads k and v with a
    zero window and masks nothing of it."""
    b, h, n, d = q.shape
    if n % wsz:
        raise ValueError(f"length {n} is not a multiple of the window {wsz}")
    scale = d ** -0.5
    zeros = jnp.zeros((b, h, wsz, d), q.dtype)
    kp = jnp.concatenate([zeros, k], axis=2)
    vp = jnp.concatenate([zeros, v], axis=2)
    i = jnp.arange(wsz)[:, None]
    j = jnp.arange(2 * wsz)[None, :]
    mask = j <= i + wsz
    outs = []
    for w in range(n // wsz):
        qw = q[:, :, w * wsz:(w + 1) * wsz]
        kw = kp[:, :, w * wsz:(w + 2) * wsz]
        vw = vp[:, :, w * wsz:(w + 2) * wsz]
        sim = jnp.einsum("bhid,bhjd->bhij", qw, kw) * scale
        sim = jnp.where(mask, sim, MASK_VALUE)
        sim = sim - sim.max(-1, keepdims=True)
        p = jnp.exp(sim)
        p = p / p.sum(-1, keepdims=True)
        outs.append(jnp.einsum("bhij,bhjd->bhid", p, vw))
    return jnp.concatenate(outs, axis=2)


def attention_block(x, p, cfg, sin, cos):
    b, n, _ = x.shape
    h, d = cfg["heads"], cfg["dim_head"]
    x = layer_norm(x, p["norm"]["scale"])
    if cfg.get("shift_tokens", True):
        x = shift_tokens(x)
    qkv = linear(x, p["to_qkv"])
    q, k, v = jnp.split(qkv, 3, axis=-1)
    q, k, v = (t.reshape(b, n, h, d).transpose(0, 2, 1, 3) for t in (q, k, v))
    q, k, v = (rotary(t, sin, cos) for t in (q, k, v))  # v too (progen.py:87)
    out = window_attention(q, k, v, cfg["window_size"])
    out = out.transpose(0, 2, 1, 3).reshape(b, n, h * d)
    return linear(out, p["to_out"])


def spatial_gating_unit(x, p):
    n = x.shape[1]
    res, gate = jnp.split(x, 2, axis=-1)
    gate = layer_norm(gate, p["norm"]["scale"])
    w = jnp.tril(p["spatial_weights"])[:n, :n]
    mixed = jnp.einsum("mn,bnd->bmd", w, gate) + p["spatial_biases"][:n]
    return linear(res * mixed, p["proj_out"])


def feed_forward_block(x, p, cfg, use_sgu):
    x = layer_norm(x, p["norm"]["scale"])
    if cfg.get("shift_tokens", True):
        x = shift_tokens(x)
    x = linear(x, p["proj_in"])
    if cfg.get("ff_glu", True) and not use_sgu:
        x, gate = jnp.split(x, 2, axis=-1)
        x = x * gelu(gate)
    else:
        x = gelu(x)
    if use_sgu:
        x = spatial_gating_unit(x, p["sgu"])
    return linear(x, p["proj_out"])


def forward(params, tokens, cfg):
    """``tokens (B, n)`` int -> logits ``(B, n, num_tokens)`` float32.
    ``cfg`` is the configuration file's dict."""
    tokens = jnp.asarray(tokens)
    n = tokens.shape[1]
    x = params["embed"]["embedding"][tokens]
    sin, cos = rotary_tables(n, cfg["dim_head"])
    depth = cfg["depth"]
    for i in range(depth):
        use_sgu = (depth - i) <= cfg["global_mlp_depth"]
        x = x + attention_block(x, params[f"attn{i}"], cfg, sin, cos)
        x = x + feed_forward_block(x, params[f"ff{i}"], cfg, use_sgu)
    x = layer_norm(x, params["norm_out"]["scale"])
    return linear(x, params["to_logits"])


def loss_mask(targets):
    """Every non-pad target plus the FIRST pad (the model learns to emit 0
    as end of sequence)."""
    nonpad = targets != 0
    first_pad = jnp.cumsum(~nonpad, axis=-1) == 1
    return nonpad | first_pad


def loss(params, batch, cfg):
    """Training loss of a ``(B, seq_len + 1)`` batch (BOS column first):
    masked mean NLL inside each row, then the mean over rows."""
    batch = jnp.asarray(batch)
    ids, targets = batch[:, :-1], batch[:, 1:]
    logits = forward(params, ids, cfg)
    logz = jnp.log(jnp.exp(logits - logits.max(-1, keepdims=True)).sum(-1))
    logz = logz + logits.max(-1)
    picked = jnp.take_along_axis(logits, targets[..., None], axis=-1)[..., 0]
    nll = logz - picked
    mask = loss_mask(targets)
    return ((nll * mask).sum(-1) / mask.sum(-1)).mean()
