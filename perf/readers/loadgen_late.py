"""How late the load generator ran: 95th percentile of submit instant minus
due instant, on the benchmark's clock.  A starved generator must not be
read as a fast server."""

from perf.lib import stats


def read(obs, metric):
    late = obs["counters"].get("late_ms")
    return stats.percentile(late, 95) if late else None
