"""The tile kernel's share of its roofline: the least time the chip could
take for what the ALGORITHM needs for one block (the configuration's
``roofline.block_work``, over the published peaks of the device kind) over
the kernel's device time per step. Which bound is the larger is printed by
the harness on an earlier line."""


def read(r: dict):
    tr = r.get("trace")
    if not tr or not tr["steps"] or tr["kernel_s"] <= 0.0:
        return None
    return 100.0 * r["least_s_per_step"] / (tr["kernel_s"] / tr["steps"])
