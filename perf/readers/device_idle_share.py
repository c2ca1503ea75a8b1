"""1 - union of device-operation intervals over the traced window."""


def read(obs, metric):
    t = obs.get("trace")
    if not t or not t.get("window_s"):
        return None
    return 100.0 * (1.0 - t["busy_s"] / t["window_s"])
