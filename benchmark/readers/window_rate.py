"""Examples a second over the whole window: rows of completed steps over the
seconds between the window's two fences."""


def read(r: dict):
    return r["window"]["rows"] / r["window"]["window_s"]
