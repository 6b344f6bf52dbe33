"""Device time of every op of the step program, per step, from the trace."""


def read(r: dict):
    tr = r.get("trace")
    if not tr or not tr["steps"]:
        return None
    return 1e3 * tr["step_s"] / tr["steps"]
