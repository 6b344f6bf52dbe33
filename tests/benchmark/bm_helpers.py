"""Shared by the benchmark's CPU tests: where things are, and a tiny CPU run
of a cell in a child process.

The child has ONE host device (tests/conftest.py gives this process eight,
and the trainer builds its mesh from all it sees) and goes through
``benchmark.run.run_cell(..., need_tpu=False, config_patch=...,
traffic_patch=...)``: function arguments only the tests pass. The script has
no option or variable that lets it run without the chip.
"""

import json
import os
import shutil
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
if REPO not in sys.path:
    sys.path.insert(0, REPO)

TINY_NB = 65536                     # four tiles of buckets


def load(path):
    with open(os.path.join(REPO, path)) as f:
        return json.load(f)


def copy_benchmark(tmp_path: str) -> str:
    """A throw-away checkout root: BENCHMARK.json and benchmark/, as the
    repo has them (the program is found through PYTHONPATH)."""
    root = os.path.join(tmp_path, "checkout")
    os.makedirs(root)
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), root)
    shutil.copytree(os.path.join(REPO, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns(".cache", "__pycache__"))
    return root


def tiny_patches(config_name: str, traffic_name: str):
    """A cell cut to what the interpreter can walk in seconds: widths stay
    (39 fields a row), depth goes (16,384-row blocks, 65,536 buckets)."""
    conf = load(f"benchmark/configs/{config_name}/config.json")
    lines = [c if not c.startswith("num_buckets")
             else f"num_buckets = {TINY_NB}"
             for c in conf["program"]["conf"]]
    config_patch = {"num_buckets": TINY_NB, "subblocks": 2,
                    "block_rows": 16384, "check": {"sample": 4096},
                    "program": {"conf": lines}}
    stream = load(f"benchmark/traffic/{traffic_name}.json")["regime"] \
        == "stream"
    traffic_patch = {"blocks": 4, "files": 1,
                     "ovf_cap": 262144 if stream else 1024}
    return config_patch, traffic_patch


def run_tiny(workload: str, tmp_path, trace: bool = False, seed: int = 5,
             root: str = REPO, prelude: str = "", patches=None,
             seconds: float = 1.0, timeout: int = 900, devices: int = 1,
             control: bool = False):
    """Run the cell in a child; returns (CompletedProcess, result or None).
    ``control`` also prints the lower-precision controls (``--control 1``)."""
    config, traffic = workload.split(".")
    if patches is None:
        patches = tiny_patches(config, traffic)
    # auto picks the split step on the CPU backend: force the chip's fused
    # one, except on a mesh, whose step has the one (split) form
    extra = ("tile_step_kernel=fused",) if devices == 1 else ()
    prog = (
        "import json, sys\n"
        f"sys.path.insert(0, {root!r})\n"
        f"{prelude}\n"
        "from benchmark import run\n"
        f"r = run.run_cell({workload!r}, {seed}, {seconds}, {trace!r}, "
        f"need_tpu=False, root={root!r}, workdir={str(tmp_path)!r}, "
        f"config_patch={patches[0]!r}, traffic_patch={patches[1]!r}, "
        f"extra_conf={extra!r}, control={control!r})\n"
        "print(json.dumps(r))\n")
    flags = (f"--xla_force_host_platform_device_count={devices}"
             if devices > 1 else "")
    env = {**os.environ, "JAX_PLATFORMS": "cpu", "XLA_FLAGS": flags,
           "PYTHONPATH": os.pathsep.join([root, REPO])}
    r = subprocess.run([sys.executable, "-c", prog], capture_output=True,
                       text=True, cwd=root, timeout=timeout, env=env)
    result = None
    if r.returncode == 0:
        result = json.loads(r.stdout.strip().splitlines()[-1])
    return r, result


def overflow_of(keys, num_buckets: int, subblocks: int, ovf_cap: int):
    """The (buckets, rows) that the crec2 writer puts on a block's COO
    overflow list, through the program's own encoder at the writer's
    default cap: what ``TrainSystem.end_data`` reads back from the file."""
    import numpy as np
    from wormhole_tpu.data.crec import default_cap, encode_tile_block
    from wormhole_tpu.ops.tilemm import make_spec
    spec = make_spec(num_buckets, subblocks,
                     default_cap(keys.shape[1], num_buckets))
    _pw, ovf_b, ovf_r, n = encode_tile_block(keys, num_buckets, spec,
                                             ovf_cap)
    assert n <= ovf_cap
    valid = ovf_b != np.uint32(0xFFFFFFFF)
    return ovf_b[valid].astype(np.int64), ovf_r[valid].astype(np.int64)
