"""Set-up: process start to the first timed step (data, table, compile or
cache load, first steps, warm passes), less the plain reference's own time."""


def read(r: dict):
    return r["setup_s"]
