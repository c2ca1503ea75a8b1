"""Share of the consumed token slots that were padding: an exact count from
the records the benchmark generated."""


def read(obs, metric):
    c = obs["counters"]
    if not c.get("slots"):
        return None
    return 100.0 * (1.0 - c["nonpad_tokens"] / c["slots"])
