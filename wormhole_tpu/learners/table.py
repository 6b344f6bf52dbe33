"""The parameter table's format on the device — the one module that owns it.

A table-backed store holds ``slots`` values a bucket (FTRL: w, z, cg; FM and
wide&deep: w, v_1..v_k and their AdaGrad accumulators). Two forms exist, and this
module is the door between them:

  * stacked — one ``(nb, slots)`` array: what the sparse step, the v1 dense
    steps, FM's and wide&deep's mesh steps, serving, the pager,
    ``save_model``/``load_model`` and the checkpoint read;
  * planar — one float32 ``(T, A_HI, B_LO)`` plane a slot (:class:`PlaneTable`):
    the tile kernels' own layout (ops/tilemm.py), so a tile step hands the
    planes to ``pallas_call`` as they are and gets the next step's state
    back — no slice, no stack, no transpose, no padding lane (the compiler
    lays ``f32[nb, 3]`` out four wide, ``f32[nb, 18]`` twenty-four).

Which stores keep planes: ``ShardedStore`` (learners/store.py), ``FMStore``
(models/fm.py) and ``WideDeepStore`` (models/wide_deep.py), each when it can
see that it can — a float32 table of whole tiles, on one device or, where
the store's own mesh tile step computes on planes (the linear store's does),
on a mesh with whole tiles a MODEL shard (``TableCheckpoint.can_be_planar``).
On a mesh a plane is split over MODEL on its tile axis, which is the key
range the stacked table's rows are split into, and repeated over DATA.
FTRL's in-place kernel updates its three
planes itself, aliased onto its outputs, and so does FM's with its 2(1+k)
(the bfloat16 operand [w, v, Σv²] is put together in VMEM from the w and v
tiles); a block with a COO overflow list takes the kernel that writes the
gradient (FM: a push plane a channel) and ONE elementwise pass over planes
onto the donated state. The dense-tower store runs the split kernel pair
with its tower between: the pull kernel's operand is one op over the w and v
planes, the push kernel's (T, A_HI, ch*B_LO) output is read a lane block a
channel, and the same ONE pass updates its 2(1+k) planes. The linear mesh
step hands plane 0 to the forward kernel of each shard as it stands and
updates the shard's planes in that same ONE pass, after the gradient's psum.
A bfloat16 table, a table without whole tiles a shard, and FM's and
wide&deep's tables on a mesh stay stacked.

Which paths cross: every one in the first list asks the store's
``_stacked()`` (the pager through ``PagedStore._table``), the
single-device tile steps ask ``_tile_table()`` and the mesh tile steps
``_mesh_table()`` (``TableCheckpoint``, shared by the stores); anything
that writes through
``PlaneTable.at`` gets the stacked form as well. A (T, A_HI, B_LO) plane and
the flat ``(nb,)`` column are the same bytes: reshapes between them are
free. Crossing between the FORMS is a pass over the whole table; the store
that crosses counts it (``TableCheckpoint._crossed``, timer scope
``table_cross``). The checkpoint of a one-device store does not cross:
planes are stacked on the host, where the bytes go anyway. A mesh store's
planes are stacked shard by shard on the chips for the writer (counted; the
store keeps its planes), and a restored table is put plane by plane
(``store.put_like``).
"""

from __future__ import annotations

import collections
import functools

import jax
import jax.numpy as jnp
import numpy as np

from wormhole_tpu.ops.tilemm import A_HI, B_LO, TILE


def plane_shape(nb: int) -> tuple:
    """A plane of ``nb`` buckets."""
    return (nb // TILE, A_HI, B_LO)


def split(stacked: jax.Array) -> tuple:
    """The (T, A_HI, B_LO) planes of a stacked ``(nb, slots)`` table, one a
    slot (traceable)."""
    nb, slots = stacked.shape
    return tuple(stacked[:, k].reshape(nb // TILE, A_HI, B_LO)
                 for k in range(slots))


def join(planes) -> jax.Array:
    """The stacked ``(nb, slots)`` table of the planes (traceable; a slice
    of the result folds back to the plane it came from)."""
    return jnp.stack([p.reshape(-1) for p in planes], axis=-1)


def planes_of(table) -> tuple:
    """The float32 planes a tile step computes on: a planar table's own,
    or a stacked table's, sliced inside the step (traceable)."""
    if isinstance(table, PlaneTable):
        return table.planes
    return split(table.astype(jnp.float32))


def table_like(planes, like):
    """``planes`` in the form (and dtype) of the table ``like``."""
    if isinstance(like, PlaneTable):
        return PlaneTable(planes)
    return join(planes).astype(like.dtype)


@jax.jit
def to_stacked(table: "PlaneTable") -> jax.Array:
    """The crossing planes -> ``(nb, slots)``, on the device."""
    return join(table.planes)


@jax.jit
def to_planes(stacked: jax.Array) -> "PlaneTable":
    """The crossing ``(nb, slots)`` -> planes, on the device."""
    return PlaneTable(split(stacked))


# what a device holds of a PlaneTable, as a stacked table's shard says it
TableShard = collections.namedtuple("TableShard", "device index data")


@functools.lru_cache(maxsize=None)
def crossing(planes: bool, sharding=None):
    """The jitted crossing to planes (``planes``) or to ``(nb, slots)``.
    On a mesh the form it makes is placed by ``sharding``, each chip
    writing its own shard; with None it is the one-device crossing."""
    fn = to_planes if planes else to_stacked
    if sharding is None:
        return fn
    return jax.jit(fn.__wrapped__, out_shardings=sharding)


@jax.tree_util.register_pytree_node_class
class PlaneTable:
    """A table as one plane a slot, a pytree of those planes. It answers
    the reads a ``(nb, slots)`` array gets from the code around the stores
    (``shape``, ``dtype``, ``astype``, ``np.asarray``, ``* scalar``, the
    indexings in use, a mesh's ``addressable_shards``) without building
    that array; anything that writes through ``.at`` gets the stacked
    form."""

    def __init__(self, planes):
        self.planes = tuple(planes)

    def tree_flatten(self):
        return self.planes, None

    @classmethod
    def tree_unflatten(cls, _aux, planes):
        return cls(planes)

    @property
    def shape(self) -> tuple:
        return (self.planes[0].size, len(self.planes))

    ndim = 2

    @property
    def dtype(self):
        return self.planes[0].dtype

    @property
    def sharding(self):
        """Where a plane lives, which is where every plane lives: the one
        device, or on a mesh the tile axis split over MODEL (a key range
        a shard, as the stacked table's rows are split)."""
        return self.planes[0].sharding

    @property
    def is_fully_addressable(self) -> bool:
        return self.planes[0].is_fully_addressable

    @property
    def addressable_shards(self) -> list:
        """What each local device holds, as the stacked table's shards
        would say it: ``index`` in ROWS of ``(nb, slots)`` (a plane's
        tile range is a key range), ``data`` the PlaneTable of that
        device's part of every plane, on that device."""
        held = [{s.device: s for s in p.addressable_shards}
                for p in self.planes]
        tiles = self.planes[0].shape[0]
        out = []
        for device, first in held[0].items():
            cut = first.index[0]
            rows = slice((cut.start or 0) * TILE,
                         (tiles if cut.stop is None else cut.stop) * TILE)
            out.append(TableShard(
                device, (rows, slice(None)),
                PlaneTable(h[device].data for h in held)))
        return out

    def astype(self, dtype) -> "PlaneTable":
        if jnp.dtype(dtype) == self.dtype:
            return self
        return PlaneTable(p.astype(dtype) for p in self.planes)

    @property
    def at(self):
        return join(self.planes).at

    def __mul__(self, other) -> "PlaneTable":
        return PlaneTable(p * other for p in self.planes)

    def __array__(self, dtype=None, copy=None):
        # stacked on the host: no (nb, slots) copy on the device
        out = np.stack([np.asarray(p).reshape(-1) for p in self.planes],
                       axis=-1)
        return out if dtype is None else out.astype(dtype, copy=False)

    def __getitem__(self, idx):
        """``[rows]`` and ``[rows, col]`` (``...`` may stand for all rows;
        ``col`` an int, a slice or a traced scalar), as the stacked table
        would answer them."""
        idx = idx if isinstance(idx, tuple) else (idx,)
        if len(idx) > 2:
            raise IndexError(f"a table has two axes, got index {idx!r}")
        rows = slice(None) if idx[0] is Ellipsis else idx[0]
        n = len(self.planes)

        def column(k):
            return self.planes[k].reshape(-1)[rows]

        col = idx[1] if len(idx) == 2 else slice(None)
        if isinstance(col, slice):
            return jnp.stack([column(k) for k in range(n)[col]], axis=-1)
        if isinstance(col, (int, np.integer)):
            return column(col)
        # a traced column: an elementwise select, which fuses into its
        # consumer where a dynamic slice of a stack would build the stack
        return jax.lax.select_n(jnp.clip(col, 0, n - 1),
                                *[column(k) for k in range(n)])
