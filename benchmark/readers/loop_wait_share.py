"""Share of the window the pass loop's thread spent blocked on a device
result: the ``wait`` scope AsyncSGD already records in its Timer."""


def read(r: dict):
    t = r["window"]["timers"]
    if "wait" not in t:
        return None
    return 100.0 * t["wait"] / r["window"]["window_s"]
