"""The mesh group feed's ``put`` seconds per step, in ms: what the feed's
transfer thread spent in ``device_put`` of one stacked group (two blocks)
onto its (data, model) sharding, four transfers a group
(``MeshGroupFeed.put_time``, which the mesh pass loop adds to its Timer as
``put``). Read only where the window's steps were mesh dispatches
(``mesh_steps``), so a one-chip feed's ``put`` is never given this name."""


def read(r: dict):
    t = r["window"]["timers"]
    if not t.get("mesh_steps") or not t.get("put"):
        return None
    return 1e3 * t["put"] / t["mesh_steps"]
