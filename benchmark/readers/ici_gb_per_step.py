"""ICI bytes one chip moves for a mesh step, in GB (1e9), as the program
books them: ``MeshTransport.dispatch`` counts each dispatch and the bytes
of its psums by the ring model 2(k-1)/k x payload
(``store.mesh_step_ici_bytes``), and the mesh pass loop adds a part's
change of both to its Timer as ``ici_bytes`` and ``mesh_steps`` (counts,
not seconds). A program without them (no mesh, or a parent commit) leaves
the metric out."""


def bytes_per_step(r: dict):
    t = r["window"]["timers"]
    if not t.get("mesh_steps") or not t.get("ici_bytes"):
        return None
    return t["ici_bytes"] / t["mesh_steps"]


def read(r: dict):
    b = bytes_per_step(r)
    return None if b is None else b / 1e9
