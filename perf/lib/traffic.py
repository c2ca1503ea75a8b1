"""Traffic and data, from a file of parameters and a seed.

The SCHEDULE — sizes, gaps and their order — belongs to the traffic file:
sizes are the distribution's quantiles, shuffled by the file's
``schedule_seed``.  The run's ``--seed`` draws what does not change the
work: token contents and each request's sampling seed (the runners draw
the weights from it too).  Measured on the chip (PERF.md, PR 23): with the
order left to the run's seed, six seeds spread the serving metrics by over
2 % while two runs of one seed agreed to 0.1 %, so the order was changing
the work and no bound under 10 % could have been kept.
"""

from __future__ import annotations

import math
from statistics import NormalDist

import numpy as np

AMINO = np.frombuffer(b"ACDEFGHIKLMNPQRSTVWY", dtype=np.uint8)


def rng_for(seed: int, stream: str) -> np.random.Generator:
    """Independent generator per named stream of one run's seed."""
    tag = int.from_bytes(stream.encode()[:8].ljust(8, b"\0"), "little")
    return np.random.default_rng([int(seed) & (2 ** 63 - 1), tag])


def quantile_values(dist: dict, n: int) -> np.ndarray:
    """``n`` values at the mid-quantiles ``(i + 0.5) / n`` of ``dist``,
    ascending.  Kinds: ``lognormal`` (median, sigma, min, max; integers),
    ``uniform_int`` (min, max), ``uniform`` (min, max; floats),
    ``exponential`` (mean; floats), ``constant`` (value)."""
    q = (np.arange(n) + 0.5) / n
    kind = dist["kind"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(float(p)) for p in q])
        v = dist["median"] * np.exp(dist["sigma"] * z)
        return np.clip(np.rint(v), dist["min"], dist["max"]).astype(np.int64)
    if kind == "uniform_int":
        lo, hi = dist["min"], dist["max"]
        return np.minimum(lo + np.floor(q * (hi - lo + 1)), hi).astype(np.int64)
    if kind == "uniform":
        return dist["min"] + q * (dist["max"] - dist["min"])
    if kind == "exponential":
        return -np.log1p(-q) * dist["mean"]
    if kind == "constant":
        return np.full(n, dist["value"])
    raise ValueError(f"unknown distribution kind {kind!r}")


def permuted_quantiles(dist: dict, n: int, rng) -> np.ndarray:
    return rng.permutation(quantile_values(dist, n))


def train_records(data: dict, seed: int) -> list[bytes]:
    """``data["records"]`` protein records as the trainer's reader consumes
    them: ``prefix`` + residues.  Lengths are the distribution's quantiles,
    stratified over ``group`` (the micro-batch): consecutive ``group``
    records hold one length from each of ``group`` equal bands, so every
    batch carries nearly the same number of real tokens while the corpus
    keeps the heavy tail.  The file's ``schedule_seed`` orders the lengths;
    the run's seed draws the residues."""
    n, group = int(data["records"]), int(data["group"])
    if n % group:
        raise ValueError(f"records {n} is not a multiple of group {group}")
    order = rng_for(data.get("schedule_seed", 0), "schedule")
    rng = rng_for(seed, "train")
    lengths = quantile_values(data["residues"], n).reshape(group, n // group)
    lengths = np.stack([order.permutation(band) for band in lengths], axis=1)
    prefix = data.get("prefix", "").encode()
    out = []
    for batch in lengths:
        for length in order.permutation(batch):
            out.append(prefix + AMINO[rng.integers(0, len(AMINO), int(length))]
                       .tobytes())
    return out


def record_tokens(records: list[bytes], seq_len: int) -> np.ndarray:
    """Non-pad target tokens each record trains: one per byte, cut at the
    row's length (the end-of-sequence pad that the loss also counts is not
    a real token)."""
    return np.array([min(len(r), seq_len) for r in records], np.int64)


def serve_requests(traffic: dict, seed: int, seconds: float,
                   num_tokens: int) -> list[dict]:
    """The requests of one run, in due order.

    ``traffic["arrivals"]`` is ``{"kind": "open", "rate": r}`` — ``round(r *
    seconds)`` requests whose gaps are the exponential's quantiles in the
    schedule's order, so every run offers the same load — or ``{"kind":
    "backlog", "requests_per_second": k}`` — ``ceil(k * seconds)`` requests
    all due at 0.  ``stagger`` scales the generated lengths of the first
    ``stagger["first"]`` requests by uniform factors so that slots filled
    together do not finish together.  Sizes, gaps and factors are ordered by
    the file's ``schedule_seed``; the run's seed draws the prime's tokens
    and the sampling seed.  Each request: ``uid``, ``due`` (s), ``prime``
    (token ids, never 0), ``max_new``, ``seed``."""
    arr = traffic["arrivals"]
    order = rng_for(traffic.get("schedule_seed", 0), "schedule")
    rng = rng_for(seed, "serve")
    if arr["kind"] == "open":
        n = max(1, round(arr["rate"] * seconds))
        gaps = permuted_quantiles({"kind": "exponential",
                                   "mean": 1.0 / arr["rate"]}, n, order)
        due = np.cumsum(gaps)
    elif arr["kind"] == "backlog":
        n = max(1, math.ceil(arr["requests_per_second"] * seconds))
        due = np.zeros(n)
    else:
        raise ValueError(f"unknown arrivals kind {arr['kind']!r}")
    primes = permuted_quantiles(traffic["prime_tokens"], n, order)
    gens = permuted_quantiles(traffic["generated_tokens"], n, order)
    stagger = traffic.get("stagger")
    if stagger:
        k = min(int(stagger["first"]), n)
        factors = permuted_quantiles(
            {"kind": "uniform", "min": stagger["min"], "max": stagger["max"]},
            k, order)
        gens[:k] = np.maximum(1, np.rint(gens[:k] * factors)).astype(np.int64)
    seeds = rng.integers(0, 2 ** 31 - 1, n)
    return [{
        "uid": i,
        "due": float(due[i]),
        "prime": rng.integers(1, num_tokens, int(primes[i])).tolist(),
        "max_new": int(gens[i]),
        "seed": int(seeds[i]),
    } for i in range(n)]
