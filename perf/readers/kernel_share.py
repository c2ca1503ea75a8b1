"""Device time of the Pallas custom calls over device busy time, from the
trace (perf/lib/xplane.py decides which operations are custom calls)."""


def read(obs, metric):
    t = obs.get("trace")
    if not t or not t.get("busy_s"):
        return None
    return 100.0 * t["kernel_s"] / t["busy_s"]
