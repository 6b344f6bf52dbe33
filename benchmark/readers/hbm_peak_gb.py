"""Peak bytes in use on the fullest chip after the window, in GB (1e9)."""


def read(r: dict):
    peak = r["memory_peak_bytes"]
    return peak / 1e9 if peak else None
