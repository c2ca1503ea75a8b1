"""Mean share of the engine's slots holding a request after each chunk."""


def read(obs, metric):
    values = obs["counters"].get("occupancy")
    return 100.0 * sum(values) / len(values) if values else None
