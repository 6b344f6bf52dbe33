"""Device time of the Mosaic custom calls (the Pallas tile kernels) per step."""


def read(r: dict):
    tr = r.get("trace")
    if not tr or not tr["steps"] or tr["kernel_s"] <= 0.0:
        return None
    return 1e3 * tr["kernel_s"] / tr["steps"]
