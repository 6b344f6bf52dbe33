"""The feed's ``put`` busy seconds over blocks, in ms. It may hold waits for
a ring slot, so it is named for what it is, not as an H2D time."""


def read(r: dict):
    t, blocks = r["window"]["timers"], r["window"]["blocks"]
    if "put" not in t or not blocks or t["put"] <= 0.0:
        return None
    return 1e3 * t["put"] / blocks
