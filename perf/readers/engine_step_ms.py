"""Median decode-step time: the benchmark's clock around each
``engine.step()`` that ran a chunk (it ends in the harvest's host fetch, so
the chunk has executed), divided by the chunk's steps.  Admissions that ran
in the same ``step()`` are inside it."""

from perf.lib import stats


def read(obs, metric):
    values = obs["counters"].get("chunk_step_ms")
    return stats.median(values) if values else None
