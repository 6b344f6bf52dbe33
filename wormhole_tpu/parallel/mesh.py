"""Device-mesh runtime: the TPU replacement for tracker + node roles.

The reference runs scheduler/server/worker *processes* wired by a tracker
(SURVEY.md §1 L6, ``dmlc-core/tracker``). On TPU the equivalent runtime is:
one Python process per host, all devices joined in a ``jax.sharding.Mesh``,
SPMD programs compiled with pjit over named axes. Axis conventions:

- ``data``  — batch/data parallelism (rabit-style BSP reductions ride here)
- ``model`` — parameter/feature sharding (the ps-lite key-range analogue and
  the L-BFGS feature-range partition, lbfgs.h:126-136)

``rank``/``world`` map to ``jax.process_index``/``process_count`` (the rabit
GetRank/GetWorldSize surface); each host reads input part ``rank/world``
exactly like a reference worker.
"""

from __future__ import annotations

import os
from dataclasses import dataclass
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

DATA_AXIS = "data"
MODEL_AXIS = "model"


def parse_mesh_shape(spec: str, num_devices: int) -> Tuple[Tuple[str, int], ...]:
    """Parse "data:4,model:2" → (("data",4),("model",2)); empty = all data."""
    if not spec:
        return ((DATA_AXIS, num_devices),)
    axes = []
    for part in spec.split(","):
        name, _, n = part.partition(":")
        axes.append((name.strip(), int(n)))
    total = int(np.prod([n for _, n in axes]))
    if total != num_devices:
        raise ValueError(f"mesh {spec!r} wants {total} devices, "
                         f"have {num_devices}")
    return tuple(axes)


def derive_mesh_shape(spec: str, model_shards: int = 0,
                      num_devices: Optional[int] = None) -> str:
    """Resolve the ``model_shards`` shorthand: with no explicit
    ``mesh_shape``, a model axis of ``model_shards`` devices and a data
    axis over the rest. An explicit spec always wins (the two knobs are
    alternatives, not composable)."""
    if spec or model_shards <= 1:
        return spec
    n = num_devices if num_devices is not None else len(jax.devices())
    if n % model_shards:
        raise ValueError(f"model_shards {model_shards} does not divide "
                         f"{n} devices")
    return f"{DATA_AXIS}:{n // model_shards},{MODEL_AXIS}:{model_shards}"


def make_mesh(spec: str = "", devices: Optional[Sequence] = None) -> Mesh:
    devices = list(devices) if devices is not None else jax.devices()
    axes = parse_mesh_shape(spec, len(devices))
    names = tuple(a for a, _ in axes)
    shape = tuple(n for _, n in axes)
    dev_array = np.asarray(devices).reshape(shape)
    return Mesh(dev_array, names)


def shard_map_compat(fn, mesh, in_specs, out_specs):
    """``jax.shard_map`` with the replication checker off: the step
    bodies mix per-shard and replicated outputs that the static checker
    cannot prove."""
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=False)


# the persistent compile cache's home when the environment names none:
# one fixed, git-ignored directory at the checkout root. The directory is
# part of what makes a later process find an earlier one's programs, so
# it must never move between runs.
COMPILE_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def _held_to_cpu() -> bool:
    """True when this process is pinned to the CPU backend
    (JAX_PLATFORMS=cpu: the tests, the launcher's simulations)."""
    return (jax.config.jax_platforms or "") == "cpu"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory ("" when it stays off). Every entry point that compiles
    calls this (cold compile of the criteo tile step is minutes,
    PERF.md). ``JAX_COMPILATION_CACHE_DIR`` wins when set — JAX reads
    it itself, so nothing is set in code; otherwise the cache lives at
    ``COMPILE_CACHE_DIR``. A process held to the CPU backend keeps no
    cache of its own: its programs compile in seconds, and XLA's CPU
    loader accepts a cached program built for another machine type
    with no more than a logged error."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    if _held_to_cpu():
        return ""
    jax.config.update("jax_compilation_cache_dir", COMPILE_CACHE_DIR)
    return COMPILE_CACHE_DIR


def require_tpu(what: str) -> None:
    """Fail unless JAX's default backend is a TPU. Runs that ask for
    the chip (chip_smoke.py, bench.py's device-rate phases) call this
    first, so a missing accelerator is an error and never a silent run
    of XLA's CPU backend and the Pallas interpreter."""
    backend = jax.default_backend()
    if backend != "tpu":
        raise RuntimeError(
            f"{what} needs a TPU, but JAX's default backend is "
            f"{backend!r} (no TPU found): refusing to fall back to the "
            "CPU backend and Pallas interpret mode")


def distributed_init() -> None:
    """Join a multi-host job (rabit::Init analogue).

    No-op without cluster env; with COORDINATOR_ADDRESS set (by the mp
    launcher or a pod runtime) calls ``jax.distributed.initialize`` — which
    must happen before anything touches the backend, so this probes the
    already-initialized state via jax's distributed global state, never via
    ``jax.process_count()``."""
    from jax._src import distributed as _dist
    if getattr(_dist.global_state, "client", None) is not None:
        return  # already joined
    coord = os.environ.get("COORDINATOR_ADDRESS")
    if coord:
        jax.distributed.initialize(
            coordinator_address=coord,
            num_processes=int(os.environ.get("NUM_PROCESSES", "1")),
            process_id=int(os.environ.get("PROCESS_ID", "0")))


@dataclass
class MeshRuntime:
    """Bundle of mesh + rank/world + sharding helpers passed to the apps."""

    mesh: Mesh

    @classmethod
    def create(cls, mesh_spec: str = "",
               model_shards: int = 0) -> "MeshRuntime":
        enable_compile_cache()
        distributed_init()
        return cls(mesh=make_mesh(
            derive_mesh_shape(mesh_spec, model_shards)))

    @property
    def rank(self) -> int:
        return jax.process_index()

    @property
    def world(self) -> int:
        return jax.process_count()

    @property
    def num_devices(self) -> int:
        return self.mesh.size

    @property
    def data_axis_size(self) -> int:
        return self.mesh.shape.get(DATA_AXIS, 1)

    @property
    def model_axis_size(self) -> int:
        return self.mesh.shape.get(MODEL_AXIS, 1)

    @property
    def have_model(self) -> bool:
        """True when the mesh really shards parameters: a model axis of
        size > 1. Every mesh step keys its PartitionSpecs off this, so
        the sharded feed (data/crec.MeshGroupFeed) must use the same
        predicate to pre-place groups on the layout the step expects."""
        return self.model_axis_size > 1 and MODEL_AXIS in self.mesh.axis_names

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    def replicated(self) -> NamedSharding:
        return NamedSharding(self.mesh, P())

    def local_part(self, total_parts: int = 0) -> Tuple[int, int]:
        """(part, nparts) for this host's input shard — the reference's
        ``RowBlockIter::Create(uri, rank, world, ...)`` convention."""
        return self.rank, max(self.world, 1)
