"""Device time of the step program less its Mosaic custom calls, per step:
the whole-table XLA fusions and the COO overflow path."""


def read(r: dict):
    tr = r.get("trace")
    if not tr or not tr["steps"]:
        return None
    return 1e3 * (tr["step_s"] - tr["kernel_s"]) / tr["steps"]
