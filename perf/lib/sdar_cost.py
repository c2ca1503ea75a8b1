"""Required operations and bytes of SDAR as this chip runs it: the LEAST an
admission's prefill and a forward of the block step must do, from the
configuration's sizes, so that a share of a peak computed from them cannot
read above 100 %.

What is counted and what is not:

* a matrix product of ``m x k`` by ``k x n`` is ``2 m k n`` operations;
  softmax, norms, rotations, the router's top-k, the draw and its confidence
  are not counted;
* an admission's prefill exists for the keys and values it caches and
  nothing else (no token is drawn from it), so of the LAST layer it needs
  the key and value projections only: neither that layer's queries,
  attention, output projection and experts nor the final norm and the head
  are required (the compiler removes them too).  The other layers count
  their four attention matrices, their router, the query-key pairs the
  BLOCK MASK allows — ``n (n + B) / 2`` a row of ``n`` tokens in whole
  blocks, each ``2 * 2 * head_dim`` operations a query head — and the
  assignments to experts that the program's counter saw (8 a token a
  layer; the counter has all layers, so it is scaled by ``(layers - 1) /
  layers``);
* only the prime's WHOLE blocks are prefilled (its last ``P mod B`` tokens
  open the block in progress); padding up to the bucket and unused rows of
  an admission run are the program's waste and are not counted;
* a forward of the block step must read every weight outside the experts
  once — attention and the router of every layer, the head (the embedding
  not: it is a gather of a few rows; norm scales not) —, the three matrices
  of each expert it TOUCHES (the program's counter), of each live row the
  keys and values of its committed rows in every layer (the program's
  ``attn.context_tokens``), and where a row commits, the B rows it writes;
  activations and the logits are not counted;
* what the program moves beyond that — every row of every slot's cache under
  the XLA block core, the float32 logits of every position, the un-donated
  state copied once a chunk — is its waste and is not counted.
"""

from __future__ import annotations

BF16 = 2  # bytes


def attention_params(c: dict) -> int:
    """One attention block's matrices: q, k, v, the output."""
    h, d = c["hidden_size"], c["head_dim"]
    q, kv = c["num_attention_heads"] * d, c["num_key_value_heads"] * d
    return 2 * h * q + 2 * h * kv


def kv_params(c: dict) -> int:
    return 2 * c["hidden_size"] * c["num_key_value_heads"] * c["head_dim"]


def expert_params(c: dict) -> int:
    return 3 * c["hidden_size"] * c["moe_intermediate_size"]


def router_params(c: dict) -> int:
    return c["hidden_size"] * c["num_experts"]


def head_params(c: dict) -> int:
    return c["hidden_size"] * c["vocab_size"]


def params_outside_experts(c: dict) -> int:
    """Every matrix a token passes whatever its routing, head excluded."""
    return c["num_hidden_layers"] * (attention_params(c) + router_params(c))


def total_params(c: dict) -> int:
    """The matrices the chip holds, embedding and head included (norm
    scales left out: 26 thousand beside 4,361 million)."""
    return (params_outside_experts(c)
            + c["num_hidden_layers"] * c["num_experts"] * expert_params(c)
            + 2 * head_params(c))


def kv_bytes_per_row(c: dict) -> int:
    """One token's key and value in one layer's cache."""
    return 2 * c["num_key_value_heads"] * c["head_dim"] * BF16


def attention_pairs(n: int, block: int) -> float:
    """Query-key pairs the block mask allows in a row of ``n`` tokens
    (whole blocks): position ``i`` sees ``(i // B + 1) * B`` keys."""
    return n * (n + block) / 2


def prefill_flops(c: dict, prime_lengths, held_assignments: float) -> float:
    """Operations the prefill of primes of ``prime_lengths`` tokens
    requires, with ``held_assignments`` (token, expert) pairs counted in
    all layers together."""
    b, layers = c["block_length"], c["num_hidden_layers"]
    whole = [n // b * b for n in prime_lengths]
    tokens = float(sum(whole))
    pair = 2 * 2 * c["num_attention_heads"] * c["head_dim"]
    pairs = sum(attention_pairs(n, b) for n in whole)
    return ((layers - 1) * (tokens * 2 * (attention_params(c)
                                          + router_params(c)) + pair * pairs)
            + tokens * 2 * kv_params(c)
            + 2 * expert_params(c) * held_assignments * (layers - 1) / layers)


def forward_terms(c: dict, forwards: float, experts_touched: float,
                  context_tokens: float, commit_forwards: float) -> dict:
    """Bytes ``forwards`` forwards of the block step must move, by what
    they are: ``experts_touched`` is the sum over forwards and layers of
    experts with an assignment, ``context_tokens`` the sum over forwards of
    the live rows' committed lengths, ``commit_forwards`` the (row,
    forward) pairs that wrote a block."""
    layers = c["num_hidden_layers"]
    return {
        "attention": forwards * layers * attention_params(c) * BF16,
        "router": forwards * layers * router_params(c) * BF16,
        "head": forwards * head_params(c) * BF16,
        "experts_touched": experts_touched * expert_params(c) * BF16,
        "committed_rows": context_tokens * layers * kv_bytes_per_row(c),
        "commit_writes": commit_forwards * c["block_length"] * layers
        * kv_bytes_per_row(c),
    }


def forward_bytes(c: dict, forwards: float, experts_touched: float,
                  context_tokens: float, commit_forwards: float) -> float:
    return float(sum(forward_terms(c, forwards, experts_touched,
                                   context_tokens, commit_forwards).values()))
