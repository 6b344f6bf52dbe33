"""1 - union of device op intervals over the traced window, in percent."""


def read(r: dict):
    tr = r.get("trace")
    if not tr or tr["window_s"] <= 0.0:
        return None
    return 100.0 * (1.0 - tr["busy_s"] / tr["window_s"])
