"""Operations and bytes the ProGen algorithm REQUIRES, from the
configuration's sizes alone.  Nothing here depends on how the program
computes: the causal half of the SGU's spatial matmul and the visible part
of each attention window are charged whatever an implementation executes,
and recomputation (remat) is never charged.

A multiply-add is two operations.  ``cfg`` is a configuration file's dict.
"""

from __future__ import annotations


def _attention_keys_per_query(cfg, n: int) -> float:
    """Mean number of REAL keys a query sees over a length-``n`` row: the
    previous window whole (none for window 0, whose predecessor is the
    zero window) plus its own window up to itself."""
    wsz = cfg["window_size"]
    windows = max(1, n // wsz)
    own = (wsz + 1) / 2.0
    prev = wsz * (windows - 1) / windows
    return own + prev


def forward_flops_per_slot(cfg, n: int | None = None) -> float:
    """Forward operations per token slot of a length-``n`` row (default
    the configuration's ``seq_len``)."""
    n = n or cfg["seq_len"]
    d, h, dh = cfg["dim"], cfg["heads"], cfg["dim_head"]
    inner = h * dh
    hidden = d * cfg.get("ff_mult", 4)
    depth, gmlp = cfg["depth"], cfg["global_mlp_depth"]
    attn = 2 * d * 3 * inner + 2 * inner * d
    attn += 2 * 2 * inner * _attention_keys_per_query(cfg, n)  # QK^T and AV
    glu = 2 * d * hidden * (2 if cfg.get("ff_glu", True) else 1) + 2 * hidden * d
    half = hidden // 2
    sgu = 2 * d * hidden            # proj_in, no GLU doubling
    sgu += 2 * half * (n + 1) / 2   # causal spatial mix, mean row length
    sgu += 2 * half * half          # the unit's own projection
    sgu += 2 * half * d             # proj_out
    head = 2 * d * cfg["num_tokens"]
    return depth * attn + (depth - gmlp) * glu + gmlp * sgu + head


def train_flops_per_slot(cfg, n: int | None = None) -> float:
    """Forward plus backward: the backward pass of a matmul is two."""
    return 3.0 * forward_flops_per_slot(cfg, n)


def param_count(cfg) -> int:
    """Parameters, for the bytes a decode step must stream."""
    d, h, dh = cfg["dim"], cfg["heads"], cfg["dim_head"]
    inner, hidden, n = h * dh, d * cfg.get("ff_mult", 4), cfg["seq_len"]
    depth, gmlp = cfg["depth"], cfg["global_mlp_depth"]
    attn = d + d * 3 * inner + inner * d + d
    glu_in = hidden * (2 if cfg.get("ff_glu", True) else 1)
    glu = d + d * glu_in + glu_in + hidden * d + d
    half = hidden // 2
    sgu = (d + d * hidden + hidden + half + n * n + n
           + half * half + half + half * d + d)
    head = d + d * cfg["num_tokens"] + cfg["num_tokens"]
    embed = cfg["num_tokens"] * d
    return embed + depth * attn + (depth - gmlp) * glu + gmlp * sgu + head


def decode_state_bytes_per_row(cfg, max_len: int, bytes_per=2) -> int:
    """Ring and gate state one decode row holds: two windows of k and v per
    layer, the two token-shift carries per layer, and ``max_len`` gate rows
    of half the hidden width per gMLP layer."""
    d, inner = cfg["dim"], cfg["heads"] * cfg["dim_head"]
    half = d * cfg.get("ff_mult", 4) // 2
    rings = cfg["depth"] * 2 * 2 * cfg["window_size"] * inner
    carries = cfg["depth"] * 2 * d
    gates = cfg["global_mlp_depth"] * max_len * half
    return (rings + carries + gates) * bytes_per


def decode_step_bytes(cfg, rows: int, max_len: int, weight_bytes_per=2) -> int:
    """Bytes one batched decode step must read: every weight once (in the
    compute type) and every live row's state once."""
    return (param_count(cfg) * weight_bytes_per
            + rows * decode_state_bytes_per_row(cfg, max_len))
