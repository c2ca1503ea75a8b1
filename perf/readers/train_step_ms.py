"""Wall time between the window's first and last device sync, per step."""


def read(obs, metric):
    c = obs["counters"]
    return 1e3 * c["window_s"] / c["steps"] if c.get("steps") else None
