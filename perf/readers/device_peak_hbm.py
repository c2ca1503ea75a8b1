"""``memory_stats()["peak_bytes_in_use"]`` of the fullest chip after the
window, in GiB."""


def read(obs, metric):
    peak = obs.get("memory_peak_bytes")
    return peak / 2 ** 30 if peak else None
