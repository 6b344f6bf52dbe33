"""Sharded parameter store: the KVWorker/KVServer replacement.

The reference shards the model by key range over server processes and moves
weights/gradients over ZeroMQ (``ps-lite`` ZPush/ZPull, async_sgd.h:84-117).
Here the model is ONE ``(num_buckets, val_len)`` device table sharded over
the ``model`` mesh axis (where the tile kernels step it, on one device or as
the linear mesh step's server shards, kept as one plane a slot in their
layout: learners/table.py); a minibatch's
"pull" is a gather of its unique bucket rows, the "push" a scatter-add of
per-key update deltas — both inside the same jitted train step, so XLA turns
the key exchange into ICI collectives instead of RPC. Keys are hashed into
buckets upstream (Localizer ``num_buckets`` = the FLAGS_max_key hash kernel;
collisions are accepted by design, localizer.h:88-96).

The scatter applies ``new_rows − old_rows`` (a delta add) rather than
writing rows: padded keys carry mask 0 → delta 0, so they are no-ops even
though they alias bucket 0; real keys are unique per batch by construction.

Fixed-point gradient quantization (the FIXING_FLOAT ps-lite filter,
async_sgd.h:144-154) is available for the cross-shard hop: with
``fixed_bytes=1`` gradients quantize to int8 around a per-batch scale before
the scatter, halving-to-quartering the collective bytes.
"""

from __future__ import annotations

import contextlib
import os
import weakref
from dataclasses import dataclass
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from wormhole_tpu.data.feed import SparseBatch
from wormhole_tpu.learners import table as tbl
from wormhole_tpu.learners.handles import FTRLHandle, Handle
from wormhole_tpu.ops import overflow
from wormhole_tpu.ops.loss import create_loss
from wormhole_tpu.ops.spmv import spmv_times, spmv_trans_times
from wormhole_tpu.ops.metrics import accuracy, auc, margin_hist
from wormhole_tpu.parallel.mesh import MODEL_AXIS, MeshRuntime
from wormhole_tpu.utils.timer import Timer


def put_like(template: jax.Array, full: np.ndarray) -> jax.Array:
    """Place a full host-side array like ``template`` — including when the
    template is sharded ACROSS processes (model axis spanning hosts), where
    a plain device_put is illegal: each process contributes its local rows
    via make_array_from_process_local_data. Planes over a mesh
    (learners/table.py) get a column each, to the plane's own shards:
    ``full`` as (nb, val_len) comes to no chip."""
    full = np.asarray(full)
    if (isinstance(template, tbl.PlaneTable)
            and isinstance(template.sharding, NamedSharding)):
        shape = tbl.plane_shape(full.shape[0])
        return tbl.PlaneTable(
            put_like(plane, np.ascontiguousarray(full[:, k]).reshape(shape))
            for k, plane in enumerate(template.planes))
    if getattr(template, "is_fully_addressable", True):
        sharding = getattr(template, "sharding", None)
        if not isinstance(sharding, NamedSharding):
            # the template was an uncommitted local array (single device /
            # replicated-per-process); committing it to its current device
            # would make later mixing with mesh-global batch arrays
            # illegal, so stay uncommitted too
            return jnp.asarray(full)
        return jax.device_put(jnp.asarray(full), sharding)
    parts = {}
    for s in template.addressable_shards:
        start = s.index[0].start or 0
        parts[start] = full[s.index]
    local = np.concatenate([parts[k] for k in sorted(parts)])
    return jax.make_array_from_process_local_data(template.sharding, local)


def _table_sharding(num_buckets: int, runtime: Optional[MeshRuntime],
                    planes: bool = False):
    """Where a (num_buckets, val_len) parameter table lives: rows over the
    ``model`` mesh axis (validating divisibility), or None for the default
    device. ``planes``: where each of its (T, A_HI, B_LO) planes lives,
    the tile axis over ``model``: the same key range a shard."""
    if runtime is None or MODEL_AXIS not in runtime.mesh.axis_names \
            or runtime.model_axis_size <= 1:
        return None
    if num_buckets % runtime.model_axis_size:
        raise ValueError(
            f"num_buckets {num_buckets} not divisible by model axis "
            f"{runtime.model_axis_size}")
    return NamedSharding(runtime.mesh, mesh_table_spec(True, planes))


def factor_table(v0: np.ndarray, runtime: Optional[MeshRuntime],
                 planar: bool):
    """The table ``[w, v_1..v_k, cg_w, cg_v_1..k]`` of an embedding store
    (FMStore, WideDeepStore) from the host's float32 ``(nb, k)`` draw of
    ``v``: ``w`` and the accumulators start at 0. ``planar``: one
    (T, A_HI, B_LO) plane a channel (learners/table.py), the zeros made on
    the device, a buffer each since the tile steps donate them, and the
    ``v`` planes put one by one; else the stacked ``(nb, 2(1+k))`` array,
    placed where the runtime wants it."""
    nb, k = v0.shape
    if not planar:
        slots = np.zeros((nb, 2 * (1 + k)), np.float32)
        slots[:, 1:1 + k] = v0
        sharding = _table_sharding(nb, runtime)
        slots = jnp.asarray(slots)
        return slots if sharding is None else jax.device_put(slots,
                                                             sharding)
    shape = tbl.plane_shape(nb)

    def zeros(n):
        return [jnp.zeros(shape, jnp.float32) for _ in range(n)]

    return tbl.PlaneTable(
        zeros(1) + [jnp.asarray(col.reshape(shape))
                    for col in np.ascontiguousarray(v0.T)]
        + zeros(1 + k))


def _one_device(runtime: Optional[MeshRuntime]) -> bool:
    return runtime is None or runtime.mesh.size == 1


def build_param_table(make, num_buckets: int,
                      runtime: Optional[MeshRuntime], planes: bool = False):
    """``make()`` -> the (num_buckets, val_len) table, built where it is
    to live: with a model axis every chip writes its own shard and nothing
    else. (Built on the default device and placed afterwards, the whole
    table is on one chip first: at 2**29 buckets 8.6 GB beside that chip's
    own 4.3 GB shard, which stays its ``peak_bytes_in_use`` for good.)
    ``planes``: as a :class:`~wormhole_tpu.learners.table.PlaneTable`,
    and never as (num_buckets, val_len)."""
    sharding = _table_sharding(num_buckets, runtime, planes)
    if planes:
        return jax.jit(lambda: tbl.PlaneTable(tbl.split(make())),
                       out_shardings=sharding)()
    if sharding is None:
        return make()
    return jax.jit(make, out_shardings=sharding)()


def mix32(h: jax.Array) -> jax.Array:
    """Finalizing 32-bit mixer — must match ``hashing.mix32_np`` exactly
    (the crec key fold runs on device; the host spec is numpy)."""
    h = h ^ (h >> 16)
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> 13)
    h = h * jnp.uint32(0xC2B2AE35)
    return h ^ (h >> 16)


def zero_grad_push_is_identity(handle: Handle) -> bool:
    """True when a zero-gradient push leaves a slot row unchanged, so the
    fused dense sweep needs no masking: always true for FTRL (w is a pure
    function of z, which g=0 leaves unchanged), and true for the
    direct-update handles without a penalty. For the remaining handles
    (e.g. AdaGrad with L1, whose prox would re-shrink every bucket every
    step) the dense steps keep the old slots wherever the aggregated
    gradient is exactly zero — the touched-bucket mask. So the question
    this answers is "mask or not", NOT whether the handle can use the
    dense paths (they all can).

    To keep "grad == 0" aligned with "no rows touched the bucket", the
    masked steps nudge exactly-zero per-row duals to a signed 1e-30
    (f32 sigmoid saturates to dual == 0.0 for confidently-classified
    rows; without the nudge such rows would stop triggering their
    buckets' L1 prox, unlike the reference's per-received-key apply,
    sgd_server_handle.h:121-140). The residual divergence is a bucket
    whose +-1e-30 contributions cancel exactly — far below update
    precision."""
    from wormhole_tpu.learners.handles import FTRLHandle
    if isinstance(handle, FTRLHandle):
        return True
    return handle.penalty.lambda1 == 0.0 and handle.penalty.lambda2 == 0.0


def _nudge_zero_dual(dual, labels, row_mask):
    """Replace exactly-zero duals of real rows with a signed 1e-30 so
    structural touch survives sigmoid saturation (see
    zero_grad_push_is_identity)."""
    eps = jnp.where(labels > 0.5, jnp.float32(-1e-30), jnp.float32(1e-30))
    return jnp.where((dual == 0.0) & (row_mask > 0), eps, dual)


def masked_push(handle: Handle, s32, grad, t, tau, exact_dense: bool):
    """Full-table handle apply with the touched-bucket mask when a
    zero-grad push is not the identity. The nudge and the mask are only
    correct TOGETHER: every caller must have passed its dual through
    ``_nudge_zero_dual`` before forming ``grad``, or saturated rows
    silently stop triggering their buckets' L1 prox (the bug the pair
    exists to prevent)."""
    new = handle.push(s32, grad, t, tau)
    if not exact_dense:
        new = jnp.where((grad != 0.0)[:, None], new, s32)
    return new


def masked_push_planes(handle: Handle, planes: tuple, grad, t, tau,
                       exact_dense: bool):
    """:func:`masked_push` over the table's planes (learners/table.py),
    one elementwise pass: (new planes, Σ(w_new − w_old)²). ``grad`` is
    shaped like a plane. The same nudge-and-mask contract holds."""
    new = handle.push_planes(planes, grad, t, tau)
    if not exact_dense:
        touched = grad != 0.0
        new = tuple(jnp.where(touched, n, p) for n, p in zip(new, planes))
    d0 = new[0] - planes[0]
    return new, jnp.sum(d0 * d0)


# the FIXING_FLOAT quantizer lives in parallel/filters.py (one
# implementation for the in-jit fixed_bytes path here AND the wire
# codec); _build_step imports quantize_dequantize from there.


# -- shared mesh-step machinery (used by the linear, FM and wide&deep
#    mesh tile steps and the dense mesh step) ------------------------------

def mesh_tile_geometry(rt, spec):
    """(nb_local, spec_local, have_model) for a model-axis-sharded tile
    step: each shard runs the tile kernels over its own tile range."""
    from wormhole_tpu.ops import tilemm
    m = rt.model_axis_size
    if spec.nb % (tilemm.TILE * m):
        raise ValueError(f"nb {spec.nb} not shardable over model axis {m}")
    nb_local = spec.nb // m
    spec_local = tilemm.make_spec(nb_local, spec.subblocks, spec.cap)
    return nb_local, spec_local, rt.have_model


def shard_range_mask(ovb, off, nb_local):
    """(valid, local_idx) of overflow COO buckets owned by this model
    shard: unused slots and out-of-range buckets mask out; idx is
    clamped to 0 where invalid (callers zero the values)."""
    bi = ovb.astype(jnp.int32)
    valid = (ovb != overflow.UNUSED) & (bi >= off) & (bi < off + nb_local)
    return valid, jnp.where(valid, bi - off, 0)


# The mesh step's two list phases, over a list in either of the two forms
# it crosses in (data/crec.MeshGroupFeed). COO: every chip is handed its
# DATA member's whole list and takes the pairs whose bucket its MODEL
# shard owns (shard_range_mask), a slot a pair. Hot (``hot``, the shard's
# TileSpec, says so): the host cut the member's list by owner, so the chip
# is handed its OWN shard's hot form alone, ``ovb`` its distinct listed
# buckets (local ids, whole hot tiles) and ``ovr`` the pairs as rank
# words, and runs it through the hot kernel pair as the one-device step
# does (tilemm.hot_margin_rows / hot_grad_scatter): nothing to mask.
# Jits of their own, so that the device trace keeps their names as an
# op's ``tf_op`` (the profiler keeps the path of an op under a nested
# jit, not under a bare named scope; the hot pair's two kernels are filed
# under them too), and module-level, so that the linear mesh step has one
# definition of each.

@partial(jax.jit, static_argnames=("nb_local", "hot"))
def mesh_ovf_gather(mg, w, ovb, ovr, off, *, nb_local, hot=None):
    """The shard's partial margins with its share of the listed pairs:
    ``w`` gathered unrounded at each owned pair's bucket and added onto
    the pair's row (before the margins' psum over MODEL)."""
    if hot is not None:
        from wormhole_tpu.ops import tilemm
        return mg + tilemm.hot_margin_rows(w, ovb, ovr, hot)
    valid, idx = shard_range_mask(ovb, off, nb_local)
    wv = jnp.where(valid, w[idx], 0.0)
    # scatter-fallback: COO overflow spill, O(ovf_cap)
    return mg.at[ovr.astype(jnp.int32)].add(wv)


@partial(jax.jit, static_argnames=("nb_local", "hot"))
def mesh_ovf_scatter(g, dual, ovb, ovr, off, *, nb_local, hot=None):
    """The shard's gradient with its share of the listed pairs: each
    owned pair's dual gathered unrounded from its row and added into its
    bucket (before the gradient's psum over DATA)."""
    if hot is not None:
        from wormhole_tpu.ops import tilemm
        return tilemm.hot_grad_scatter(g, dual, ovb, ovr, hot)
    valid, idx = shard_range_mask(ovb, off, nb_local)
    dv = jnp.where(valid, dual[ovr.astype(jnp.int32)], 0.0)
    # scatter-fallback: COO overflow spill, O(ovf_cap)
    return g.at[idx].add(dv)


def mesh_metric_sums(objv, num_ex, acc, pos, neg):
    """DATA-axis metric reduction shared by every mesh step: returns
    (objv_g, tot_ex, acc_frac, pos_g, neg_g). acc is a per-shard
    FRACTION; a plain psum would sum D fractions while the harvest
    credits count += 1 per grouped step, so each shard's fraction is
    weighted by its row count (PAD shards contribute 0 rows) and the
    psum'd value is the exact fraction of the grouped step — acc/count
    stays a mean over steps on any mesh geometry."""
    from wormhole_tpu.parallel.mesh import DATA_AXIS
    tot_ex = jax.lax.psum(num_ex, DATA_AXIS)
    acc_frac = (jax.lax.psum(acc * num_ex, DATA_AXIS)
                / jnp.maximum(tot_ex, 1.0))
    return (jax.lax.psum(objv, DATA_AXIS), tot_ex, acc_frac,
            jax.lax.psum(pos, DATA_AXIS), jax.lax.psum(neg, DATA_AXIS))


def mesh_macc_row(objv_g, tot_ex, acc_frac, wdelta2, pos_g, neg_g):
    """The packed on-device metric row every mesh train step
    accumulates: [objv, num_ex, acc, wdelta2, pos[bins], neg[bins]]
    (TableCheckpoint.MACC_LEN layout, consumed by _harvest_macc)."""
    return jnp.concatenate([
        jnp.stack([objv_g, tot_ex, acc_frac, wdelta2]), pos_g, neg_g])


def mesh_table_spec(have_model, planes: bool = False):
    """The spec of the table in a mesh step: its rows over MODEL or, for
    a table kept as ``planes``, each plane's tile axis, which is the same
    key range a shard (a plane is the column's bytes as they lie)."""
    first = MODEL_AXIS if have_model else None
    return P(first, None, None) if planes else P(first, None)


def mesh_step_specs(have_model, planes: bool = False, hot: bool = False):
    """(Pm, Pblk, data_specs) shared by every stacked-group tile mesh
    step (linear/FM/wide&deep): the slots-table spec (with ``planes``
    the spec of each plane of a PlaneTable), the (D,T,SG,N)
    packed-word spec, and the full (slots, pw, labels, ovf_b, ovf_r)
    in_specs prefix; with ``hot`` the last two are the lanes of a group
    whose lists crossed in their hot form A SHARD, (D, M, tiles * TILE)
    ``ovf_u`` and (D, M, vtiles', SG, N') ``ovf_pw``, split over DATA
    and MODEL: a chip holds its own shard's form only. One declaration
    keeps the three step builders and :func:`mesh_group_shardings` (the
    feed's pre-placement layout) from drifting apart."""
    from wormhole_tpu.parallel.mesh import DATA_AXIS
    Pm = mesh_table_spec(have_model, planes)
    model = MODEL_AXIS if have_model else None
    Pblk = (P(DATA_AXIS, MODEL_AXIS, None, None) if have_model
            else P(DATA_AXIS, None, None, None))
    lists = ((P(DATA_AXIS, model, None), P(DATA_AXIS, model, None, None, None))
             if hot else (P(DATA_AXIS, None), P(DATA_AXIS, None)))
    data_specs = (Pm, Pblk, P(DATA_AXIS, None)) + lists
    return Pm, Pblk, data_specs


def mesh_step_ici_bytes(rt: "MeshRuntime", *, margin_elems: int,
                        grad_elems: int = 0, extra_data_elems: int = 0,
                        train: bool = True) -> int:
    """Modeled ICI bytes ONE device moves for a mesh step dispatch —
    the single declaration site of the model (transport's MeshTransport
    books the result into ``comm/bytes_ici``). Every mesh step shares
    the same collective skeleton: margins/pulls psum over MODEL, the
    packed metric row psum over DATA, and (train only) grad/push psum
    over DATA plus the wdelta2 scalar over MODEL. ``extra_data_elems``
    covers model-specific data-axis payloads (wide&deep's MLP grads).
    Each psum is costed at the ring-allreduce 2(k-1)/k·n bound; a
    trivial axis costs zero (XLA elides the collective)."""
    from wormhole_tpu.parallel.transport import ici_ring_bytes
    m = rt.model_axis_size if rt.have_model else 1
    d = rt.data_axis_size
    n = ici_ring_bytes(4 * int(margin_elems), m)
    n += ici_ring_bytes(4 * (TableCheckpoint.MACC_LEN - 1), d)
    if train:
        n += ici_ring_bytes(4 * (int(grad_elems) + int(extra_data_elems)),
                            d)
        n += ici_ring_bytes(4, m)
    return n


def mesh_group_shardings(rt: MeshRuntime, is_tile: bool, hot: bool = False):
    """NamedSharding pytree for ONE D-group, matching the mesh steps'
    in_specs exactly — the layout the sharded feed
    (data/crec.MeshGroupFeed) assembles a group on, so a pre-placed
    group enters shard_map with zero re-layout copies. Tile groups are
    the {pw, labels, ovf_b, ovf_r} dict, or with ``hot`` the {pw,
    labels, ovf_u, ovf_pw} dict of a group whose lists crossed in their
    hot form a shard; v1 groups the (D, block_bytes) u8 array. Chip
    ``(d, m)`` holds ``pw[d, m*T/M:(m+1)*T/M]``, row ``d`` of every COO
    lane and ``[d, m]`` of every hot lane, each a contiguous slice of
    ONE block's arrays, which is what lets the feed send the block's own
    bytes with no stacked copy (``crec.place_mesh_group``)."""
    from wormhole_tpu.parallel.mesh import DATA_AXIS
    lane = rt.sharding(DATA_AXIS, None)
    if not is_tile:
        return lane
    _Pm, Pblk, specs = mesh_step_specs(rt.have_model, hot=hot)
    return {"pw": NamedSharding(rt.mesh, Pblk), "labels": lane,
            **{k: NamedSharding(rt.mesh, spec)
               for k, spec in zip(overflow.names(hot), specs[3:])}}


def mesh_ovf_zeros(D: int, oc: int) -> np.ndarray:
    """Cached all-zero (D, max(oc,1)) u32 overflow stand-in for blocks
    without ovf arrays — allocating it per dispatch put a host memset in
    the mesh hot loop. Callers must not mutate it."""
    key = (D, oc)
    buf = _OVF_ZEROS.get(key)
    if buf is None:
        buf = _OVF_ZEROS[key] = np.zeros((D, max(oc, 1)), np.uint32)
        buf.setflags(write=False)
    return buf


_OVF_ZEROS: dict = {}


# step_kernel's second field when the fused tile step is the in-place one
IN_PLACE = "in place: the FTRL update runs inside the kernel"


@dataclass
class StoreConfig:
    num_buckets: int = 1 << 20
    loss: str = "logit"
    fixed_bytes: int = 0      # 0 = exact; 1 = int8-style quantized grads
    lr_theta: float = 1.0     # staleness weight for DT handles
    param_dtype: str = "float32"  # slots storage dtype; "bfloat16" halves
                                  # table HBM at accumulator-precision cost
                                  # (compute always runs in f32)
    tile_step_kernel: str = "auto"  # auto|fused|split: one-grid fused
                                    # train step vs the two-call split
                                    # oracle (ops/tilemm.py)
    tile_onehot_cache: str = "auto"  # auto|on|off: phase-shared one-hot
                                     # plane cache inside the fused grid
                                     # (auto = VMEM budget model decides;
                                     # ops/tilemm.resolve_step_kernel)


@dataclass(frozen=True)
class TileStep:
    """One variant of a store's one-device tile step, as
    :meth:`TableCheckpoint._tile_step` resolved it, and the parts of the
    step's program that no model owns: what a store's ``_tile_body`` is
    handed to build its half around."""
    spec: object          # the block geometry's TileSpec
    oc: int               # the list's room; 0: the block brings no list
    kind: str             # "train" | "eval"
    fused: bool           # the one-grid kernel (train only), else the pair
    cache: bool           # ... with the phase-shared one-hot cache
    in_place: bool        # ... that updates the table inside the kernel
    objv_fn: object

    def decode(self, block):
        """(pair words, labels, row_mask, list) of a block's arrays, the
        list in the form the block brings it (ops/overflow.py: a form is
        a pytree structure, so each is a program of the step's jit);
        None where the step takes none."""
        lab_u8 = block["labels"]
        row_mask = (lab_u8 != jnp.uint8(255)).astype(jnp.float32)
        labels = jnp.minimum(lab_u8, 1).astype(jnp.float32)
        lst = overflow.of(block) if self.oc else None
        return block["pw"], labels, row_mask, lst

    def metrics(self, margin, labels, row_mask, objv=None):
        """(objv, num_ex, acc, pos, neg) of a step's margins: identical
        ops downstream of the margin buffer in every variant, so the
        fused steps keep the split step's metric bits. ``objv``: the
        loss where the store formed it ahead of its updates."""
        if objv is None:
            objv = self.objv_fn(margin, labels, row_mask)
        num_ex = jnp.sum(row_mask)
        acc = accuracy(labels, margin, row_mask)
        pos, neg = margin_hist(labels, margin, row_mask)
        return objv, num_ex, acc, pos, neg

    def evaluate(self, margin, labels, row_mask):
        """The eval step's outputs from its margins."""
        return (*self.metrics(margin, labels, row_mask), margin)

    def metric_row(self, wdelta2, margin, labels, row_mask, objv=None):
        """(the packed metric row [objv, num_ex, acc, wdelta2, pos[bins],
        neg[bins]], num_ex) of a train step."""
        objv, num_ex, acc, pos, neg = self.metrics(margin, labels,
                                                   row_mask, objv)
        return jnp.concatenate([
            jnp.stack([objv, num_ex, acc, wdelta2]), pos, neg]), num_ex

    def finish(self, new, wdelta2, margin, labels, row_mask, t, macc):
        """The train step's outputs from the new table and the margins.
        Per-step metrics ADD into the donated on-device accumulator
        ``macc``: the step returns no host-visible value at all, so the
        steady-state loop fetches ONE (4+2*bins,) buffer a display
        window. num_ex rides along as the caller's completion ticket:
        unlike t+1/macc it never re-enters the donated step chain, so
        block_until_ready on it stays legal after later steps dispatch
        (donation is real on committed multi-device layouts, not just
        TPU)."""
        packed, num_ex = self.metric_row(wdelta2, margin, labels, row_mask)
        return new, t + 1, macc + packed, num_ex


class TableCheckpoint:
    """Checkpointable {slots, t} state shared by the table-backed stores
    (rabit Serializable analogue), and the door between the table's two
    forms (learners/table.py). Stores with extra state (wide&deep's MLP)
    extend the pytree."""

    # A store whose tile steps take the table as planes sets this from
    # what it can see of itself (can_be_planar): a float32 table of whole
    # tiles, on one device, or on a mesh where its mesh tile step
    # computes on planes too. Crossings are counted in ``self.timer``,
    # which such a store makes (a learner that owns it reads it as its
    # own).
    _planar = False

    # Does this store's MESH tile step compute on the planes of its
    # shard? The linear store's does; FM's and wide&deep's slice a
    # stacked shard, so on a mesh they keep (nb, slots) and do not cross
    # every step.
    mesh_step_takes_planes = False

    # Does this store's MESH tile step take a group's overflow lists in
    # their hot form a shard (data/crec.MeshGroupFeed)? The linear
    # store's does; FM's and wide&deep's read the COO lanes.
    mesh_hot_overflow = False

    # The pairs on the overflow list of each block now on the device, by
    # the id of the list's device array (``put_block``, ``_listed_pairs``).
    # A store that counts them (FM's and wide&deep's ``_count_step``) sets
    # a dict where it builds its Timer; None: nothing is counted.
    _listed = None

    # The widest room, in whole tiles, that a COO list's distinct buckets
    # have crossed with (``put_block``, ``overflow.distinct``). A store
    # whose spill step reads a plane once a listed bucket (wide&deep's)
    # starts it at 1 where it builds its Timer; None: a COO list crosses
    # as its two arrays alone.
    _distinct_tiles = None

    @classmethod
    def can_be_planar(cls, runtime: Optional[MeshRuntime], dtype,
                      num_buckets: int) -> bool:
        if jnp.dtype(dtype) != jnp.float32:
            return False
        if _one_device(runtime):
            return num_buckets % tbl.TILE == 0
        # whole tiles a MODEL shard: what mesh_tile_geometry demands
        return (cls.mesh_step_takes_planes
                and num_buckets % (tbl.TILE * runtime.model_axis_size) == 0)

    # -- the table and its two forms (learners/table.py) --------------------

    @property
    def _on_one_device(self) -> bool:
        return _one_device(getattr(self, "rt", None))

    @property
    def slots(self):
        """The table as it stands: a ``(nb, val_len)`` array, or a
        :class:`~wormhole_tpu.learners.table.PlaneTable` that answers the
        same reads (shape, dtype, astype, indexing, ``np.asarray``,
        ``jax.block_until_ready``) from its planes. Assigning a plain
        array is always legal; the next tile step takes it across."""
        return self._table

    @slots.setter
    def slots(self, table) -> None:
        self._table = table

    def _crossed(self, planes: bool):
        """The table in its other form, planes or ``(nb, val_len)``: a
        pass over the whole table, counted (calls and seconds) under
        ``table_cross`` in the timer and, as every timer scope, in the
        trace. A run whose window shows none never rebuilt the table. On
        a mesh every chip crosses its own shard."""
        convert = tbl.crossing(planes, _table_sharding(
            self._table.shape[0], getattr(self, "rt", None), planes))
        with self.timer.scope("table_cross"):
            return jax.block_until_ready(convert(self._table))

    def _stacked(self) -> jax.Array:
        """The table as one ``(nb, val_len)`` array, for every path but
        the tile steps that take planes; it stays so until one of those
        runs."""
        if isinstance(self._table, tbl.PlaneTable):
            self._table = self._crossed(planes=False)
        return self._table

    def _planes(self):
        """The table as planes, for the tile steps that take them."""
        if not isinstance(self._table, tbl.PlaneTable):
            self._table = self._crossed(planes=True)
        return self._table

    def _tile_table(self):
        """The table as the single-device tile steps take it: planes
        where a store on one device keeps them (``_planar``)."""
        if self._planar and self._on_one_device:
            return self._planes()
        return self._table

    def put_block(self, block):
        """Ship one tile block's host arrays to the device (the feed's
        ``device_put``). Where the table is planes, an overflow list
        with no pair in it stays behind: the block then takes the tile
        step that has no spill to scatter, which for FTRL and FM is
        the in-place one; a list that comes with its hot form
        (``ovf_u``, ``ovf_pw``) crosses as that alone. (A stacked table
        keeps its one step: its no-spill
        programs slice the planes out of ``(nb, slots)`` and compile for
        six to nine minutes at 2**28, PERF.md. So does a table on a
        mesh, planes or not: the mesh step takes its list operands
        whatever they hold, and its groups come through
        ``crec.place_mesh_group``, not through here.)
        A store that keeps ``_distinct_tiles`` has a long COO list of its
        planes cross with its distinct buckets (``overflow.distinct``).
        A store that keeps ``_listed`` has the pairs on the list
        counted here: from ``ovf_b`` while the list is host memory,
        whichever form crosses, and the count kept by the id of the
        list's device array (``overflow.array``) for as long as that
        lives (a resident block is put once and stepped every pass)."""
        pairs = 0
        if isinstance(block, dict):
            if self._listed is not None and overflow.COO[0] in block:
                pairs = overflow.pairs(block[overflow.COO[0]])
            block = overflow.crossing(
                block, drop_empty=self._planar and self._on_one_device)
            block = self._with_distinct(block)
        dev = jax.device_put(block)
        lst = overflow.array(dev) if pairs else None
        if lst is not None:
            self._listed[id(lst)] = pairs
            weakref.finalize(lst, self._listed.pop, id(lst), None)
        return dev

    def _with_distinct(self, block: dict) -> dict:
        """A block about to cross, its COO list with ``ovf_d`` and
        ``ovf_k`` beside it where this store's step reads them (planes
        on one device) and the list is long enough to bring them."""
        ovf_b = block.get(overflow.COO[0])
        if (self._distinct_tiles is None or ovf_b is None
                or not (self._planar and self._on_one_device)):
            return block
        made = overflow.distinct(ovf_b, self._distinct_tiles, tbl.TILE)
        if made is None:
            return block
        self._distinct_tiles = len(made[0]) // tbl.TILE
        return dict(block, **dict(zip(overflow.DISTINCT, made)))

    def _listed_pairs(self, block: dict) -> int:
        """The pairs ``put_block`` counted on this device block's list."""
        return self._listed.get(id(overflow.array(block)), 0)

    def state_pytree(self):
        slots = self._table
        if isinstance(slots, tbl.PlaneTable):
            # the checkpoint holds (nb, val_len). One device: planes are
            # stacked on the host, where the bytes go anyway, not on the
            # device. Planes over a mesh: the global array a stacked mesh
            # store hands over, every chip stacking its own shard beside
            # its planes (counted; the store keeps its planes), so that
            # nothing comes to the host before a writer asks for it,
            # shard by shard where the table spans processes
            # (ShardCheckpointer).
            slots = (np.asarray(slots) if self._on_one_device
                     else self._crossed(planes=False))
        return {"slots": slots, "t": np.int64(self.t)}

    def restore_pytree(self, state) -> None:
        slots = state["slots"]
        if isinstance(slots, jax.Array) and not slots.is_fully_addressable:
            # already a global array (ShardCkpt), (nb, val_len) as
            # state_pytree gave it: the next mesh tile step of a store
            # that keeps planes takes it across, counted
            self.slots = slots
        else:
            self.slots = put_like(self.slots, np.asarray(slots))
        self.t = int(state["t"])
        self._t_dev = None           # re-seed the device clock
        self._macc = None            # drop pre-restore metric window

    # -- device-resident step clock -----------------------------------------
    #
    # A fresh host scalar upload per dispatched step is a host->device
    # transfer on the dispatch path of every step. The update counter
    # therefore LIVES ON DEVICE and rides the donated step chain (each
    # train step returns t+1); tau takes a handful of small values and is
    # served from a cache of device constants.

    # packed metric layout: [objv, num_ex, acc, wdelta2, pos[512], neg[512]]
    MACC_LEN = 4 + 2 * 512

    def _step_operand(self, x):
        """Place a fresh clock/tau/accumulator value where the step
        chain keeps it. A mesh step returns these replicated over the
        mesh (out_specs P()); an uncommitted host scalar in the same
        argument slot is a different input sharding, and jit compiles
        the whole step again for it — once for the fresh clock, once
        more for every fresh accumulator after a metrics fetch (at the
        criteo geometry each such compile is minutes, PERF.md)."""
        rt = getattr(self, "rt", None)
        if rt is None or rt.mesh.size == 1:
            return x
        return jax.device_put(x, rt.replicated())

    def _mesh_table(self, tile: bool = False):
        """The table as a mesh step takes it: for the ``tile`` step of a
        store that keeps planes and whose mesh step computes on them, the
        planes; for every other (the v1 dense step, FM's and
        wide&deep's), ``(nb, val_len)``. Without a model axis the
        table starts out uncommitted on the default device (the
        single-device steps want it there) while a mesh step returns it
        replicated over the mesh: the same double compile as in
        _step_operand, so commit it at its first mesh step."""
        if tile and self._planar and self.mesh_step_takes_planes:
            table = self._planes()
        else:
            table = self._stacked()
        if not isinstance(getattr(table, "sharding", None), NamedSharding):
            self.slots = jax.device_put(table, self.rt.replicated())
        return self.slots

    def _macc_buf(self):
        if getattr(self, "_macc", None) is None:
            self._macc = self._step_operand(
                jnp.zeros(self.MACC_LEN, jnp.float32))
        return self._macc

    def fetch_metrics_async(self):
        """Reset the on-device metric accumulator and start a NON-blocking
        device->host copy of its final value; ``np.asarray(ticket)``
        resolves it. The returned buffer is never donated again (the next
        step starts a fresh accumulator), so reading it later is safe —
        and the device pipeline never drains waiting on a metrics round
        trip."""
        if getattr(self, "_macc", None) is None:
            return np.zeros(self.MACC_LEN, np.float32)
        buf = self._macc
        self._macc = None
        buf.copy_to_host_async()
        return buf

    def fetch_metrics(self) -> np.ndarray:
        """Blocking fetch-and-reset of the metric accumulator."""
        return np.asarray(self.fetch_metrics_async())

    def _t_device(self):
        # int32 on device: a float32 counter freezes at 2^24 (t+1 == t)
        if getattr(self, "_t_dev", None) is None:
            self._t_dev = self._step_operand(jnp.asarray(self.t, jnp.int32))
        return self._t_dev

    def _advance_t(self, t_new) -> None:
        self._t_dev = t_new
        self.t += 1

    def _tau_const(self, tau: float):
        cache = getattr(self, "_tau_cache", None)
        if cache is None:
            cache = self._tau_cache = {}
        v = cache.get(tau)
        if v is None:
            theta = getattr(self.cfg, "lr_theta", 1.0)
            v = cache[tau] = self._step_operand(
                jnp.asarray(tau * theta, jnp.float32))
        return v

    def mesh_transport(self):
        """The shared intra-host transport leg every mesh dispatcher
        routes through (parallel/transport.MeshTransport): site/seq
        stamping, the collective:mesh span, chaos/watchdog, and
        comm/bytes_ici accounting around the compiled step."""
        tx = getattr(self, "_mesh_tx", None)
        if tx is None:
            from wormhole_tpu.parallel.transport import MeshTransport
            tx = self._mesh_tx = MeshTransport(site="mesh/step")
        return tx

    # -- the one-device tile step: the ladder every store shares -------------
    #
    # One fused program over a tile-grouped crec2 block (data/crec.py v2 +
    # ops/tilemm.py). What is the same for every model is here, once: the
    # cache of built steps, the choice of kernel, the block's decoding and
    # the metric tail (TileStep), the record of what was chosen, and the
    # dispatch. A store supplies its model: how pulls become a margin, how
    # duals become pushes, its update pass and which fused kernels it has
    # (``_tile_body``), and states the few facts below about itself.

    def _step_kernel_args(self, info, oc: int) -> dict:
        """What this store tells ``tilemm.resolve_step_kernel`` beside
        the conf's two knobs and the spec: ``ovf_cap`` and, for a
        multi-channel model, its widths."""
        return {"ovf_cap": oc}

    def _in_place_why(self) -> Optional[str]:
        """``step_kernel``'s second field where this store's fused step
        updates the table inside its kernel; None where it has no such
        kernel."""
        return None

    def _tile_body(self, ts: TileStep):
        """The store's model half: the function ``step`` that
        :meth:`_tile_step` jits, built around ``ts``."""
        raise NotImplementedError

    def _tile_extra(self, train: bool) -> tuple:
        """State the tile step takes between the table and the block
        (and, ``train``, donates and returns): wide&deep's tower."""
        return ()

    def _take_tile_extra(self, extra) -> None:
        """Keep what a train step returned for :meth:`_tile_extra`."""

    def _fused_span(self) -> str:
        """The host span a fused train step's dispatch runs under."""
        raise NotImplementedError

    def _count_step(self, block: dict, info) -> None:
        """What a store counts of a train step (counts, not seconds),
        asked once ``step_kernel`` says which variant the block takes."""

    def _tile_step(self, info, kind: str, spill: bool = True):
        """The jitted single-device tile step for a block geometry:
        ``step(table, *extra, block, t, tau, macc)`` (train) or
        ``step(table, *extra, block)`` (eval). ``spill``: the block
        brings an overflow list, as COO pairs or in its hot form (the jit
        has a program for each). Every variant computes on the float32
        (T, A_HI, B_LO) planes; a planar table IS those planes and is
        returned as such, a stacked one (bfloat16, a serving snapshot)
        is sliced into them and stacked again inside the step. Sets
        ``step_kernel`` to ``(kernel, why, one-hot cache)`` of the
        variant."""
        key = (info, kind, spill)
        steps = vars(self).setdefault("_tile_cache", {})
        records = vars(self).setdefault("_tile_kernel", {})
        if key not in steps:
            from wormhole_tpu.ops import tilemm
            train = kind == "train"
            oc = info.ovf_cap if spill else 0
            res = tilemm.resolve_step_kernel(
                self.cfg.tile_step_kernel, spec=info.spec,
                onehot_cache=self.cfg.tile_onehot_cache,
                **self._step_kernel_args(info, oc))
            # The fused one-grid step replaces the fwd/bwd pallas pair
            # when the geometry admits it; the in-place update
            # additionally needs a kernel that has it, no list (its
            # scatter needs the gradient in HBM) and a single process
            # (multihost gradients cross the wire before the update: the
            # gradient-emitting fused variant covers both).
            fused = res.kernel == "fused" and train
            why = self._in_place_why() if fused and oc == 0 else None
            in_place = why is not None and jax.process_count() == 1
            body = self._tile_body(TileStep(
                info.spec, oc, kind, fused, fused and res.cache, in_place,
                self.objv_fn))
            # table, extra state, clock and accumulator are donated where
            # the step returns them (train)
            n = len(self._tile_extra(train))
            steps[key] = jax.jit(body, donate_argnums=(
                (*range(n + 1), n + 2, n + 4) if train else ()))
            if not train:
                records[key] = ("split", "eval is forward-only",
                                "onehot_cache=off:eval is forward-only")
            else:
                # the record names the kernel the knob names, fused or
                # split; the fused step that updates the table in place
                # says so where the split step gives its reason
                records[key] = ("fused" if fused else "split",
                                why if in_place else res.why,
                                res.cache_record)
        self.step_kernel = records[key]
        return steps[key]

    def tile_train_step(self, block: dict, info, tau: float = 0.0):
        """Fused crec2-block step over a typed block dict (crec.block2_views
        shipped to device). Metrics accumulate ON DEVICE (fetch_metrics);
        the returned device scalar (this step's example count) exists
        only so callers can gate the staleness window on real completion
        — the clock itself is donated into the next step, so it is NOT
        safe to block on."""
        step = self._tile_step(info, "train", overflow.has_list(block))
        self._count_step(block, info)
        if self.step_kernel[0] == "fused":
            from wormhole_tpu.obs import trace
            span = trace.span(self._fused_span(), cat="tile")
        else:
            span = contextlib.nullcontext()
        with span:
            self.slots, *extra, t_new, self._macc, ticket = step(
                self._tile_table(), *self._tile_extra(True), block,
                self._t_device(), self._tau_const(tau), self._macc_buf())
        self._take_tile_extra(extra)
        self._advance_t(t_new)
        return ticket

    def tile_eval_step(self, block: dict, info):
        return self._tile_step(info, "eval", overflow.has_list(block))(
            self._tile_table(), *self._tile_extra(False), block)


class ShardedStore(TableCheckpoint):
    """Model state + the fused pull→forward→backward→push step."""

    # _tile_step_mesh hands plane 0 of the shard to the forward kernel
    # and pushes in one pass over the shard's planes
    mesh_step_takes_planes = True

    # this store's one-device tile steps take an overflow list in its hot
    # form too (data/crec.HotRoom), so its app has the feeds make one
    hot_overflow = True
    # ... and so does its mesh tile step, a hot form a MODEL shard
    mesh_hot_overflow = True

    def __init__(self, cfg: StoreConfig, handle: Handle,
                 runtime: Optional[MeshRuntime] = None):
        self.cfg = cfg
        self.handle = handle
        self.rt = runtime
        self.objv_fn, self.dual_fn = create_loss(cfg.loss)
        self.dtype = jnp.dtype(cfg.param_dtype)
        if self.dtype not in (jnp.float32, jnp.bfloat16):
            raise ValueError(f"param_dtype {cfg.param_dtype!r}: want "
                             "float32 or bfloat16")
        nb = cfg.num_buckets
        # crossings of the table's format (scope "table_cross"); a learner
        # that owns this store reads it as its own timer
        self.timer = Timer()
        # A float32 table of whole tiles, on one device or in whole tiles a
        # MODEL shard on a mesh: the tile steps, this store's mesh step
        # among them, can take this table, so it is built in THEIR form (one
        # plane a slot, learners/table.py; on a mesh each chip its own
        # shard of each plane) and never as (nb, val_len), which the
        # compiler lays out four wide. Every other path asks _stacked() for
        # the (nb, val_len) array and gets it, counted; a table in bfloat16
        # or without whole tiles a shard stays stacked and the tile steps
        # slice it as they go.
        self._planar = self.can_be_planar(runtime, self.dtype, nb)
        self._table = build_param_table(
            lambda: handle.init(nb).astype(self.dtype), nb, runtime,
            planes=self._planar)
        self._step = self._build_step()
        self._eval = self._build_eval()
        self.t = 1  # global update counter (SGD eta schedule)

    def with_num_buckets(self, nb: int) -> "ShardedStore":
        """A fresh store over the same config/handle/runtime at ``nb``
        buckets — the hot-tier twin constructor the bigmodel pager uses
        (bigmodel/paged.py) and the full-size oracle the paging parity
        tests compare against."""
        from dataclasses import replace
        return ShardedStore(replace(self.cfg, num_buckets=nb),
                            self.handle, self.rt)

    # -- jitted programs ----------------------------------------------------

    def _build_step(self):
        from wormhole_tpu.parallel.filters import quantize_dequantize
        handle, objv_fn, dual_fn = self.handle, self.objv_fn, self.dual_fn
        fixed_bytes = self.cfg.fixed_bytes

        @partial(jax.jit, donate_argnums=(0, 2))
        def step(slots, batch: SparseBatch, t, tau):
            # pull (gather); compute in f32 regardless of storage dtype.
            # NOTE: no indices_are_sorted/unique_indices hints here even
            # though the Localizer emits sorted-unique keys — pad_to_batch
            # pads uniq_keys with trailing zeros, so the padded vector is
            # neither sorted nor unique and the hints would be XLA UB
            # (a real bucket-0 delta could race the pad-slot zero-adds)
            rows = slots[batch.uniq_keys].astype(jnp.float32)
            w = handle.weights(rows)
            margin = spmv_times(batch.cols, batch.vals, w)
            objv = objv_fn(margin, batch.labels, batch.row_mask)
            dual = dual_fn(margin, batch.labels, batch.row_mask)
            grad = spmv_trans_times(batch.cols, batch.vals, dual,
                                    w.shape[0])
            if fixed_bytes:
                grad = quantize_dequantize(grad, 8 * fixed_bytes)
            new_rows = handle.push(rows, grad,
                                   t.astype(jnp.float32), tau)
            delta = (new_rows - rows) * batch.key_mask[:, None]
            # scatter-fallback: uniq-key push, O(uniq) rows — the sparse
            # step is the text and libsvm path's own
            slots = slots.at[batch.uniq_keys].add(
                delta.astype(slots.dtype))
            num_ex = jnp.sum(batch.row_mask)
            a = auc(batch.labels, margin, batch.row_mask)
            acc = accuracy(batch.labels, margin, batch.row_mask)
            wdelta2 = jnp.sum(delta[:, 0] * delta[:, 0])
            return slots, t + 1, (objv, num_ex, a, acc, wdelta2)

        return step

    # -- pull-only serving surface ------------------------------------------
    #
    # The inference half of the ZPush/ZPull pair (serve/): margins as a
    # pure function of caller-owned params, so a hot-swapped snapshot can
    # replace the model without touching the training store. _build_eval
    # routes through the same function — eval and serve share ONE audited
    # margin computation (the bit-equality the serve tests pin).

    def serve_params(self):
        """Live model params for the pull-only forward (serve/forward.py).
        Keys must match state_pytree's so a checkpoint restores straight
        into a serve swap."""
        return {"slots": self._stacked()}

    def build_serve_margin(self):
        """margin_fn(params, batch) -> (mb,) margins: pull (gather) +
        weights + spmv, nothing else — no push, no optimizer state, no
        metric work. Jit-compiled by the caller, once per geometry."""
        handle = self.handle

        def margin_fn(params, batch: SparseBatch):
            rows = params["slots"][batch.uniq_keys].astype(jnp.float32)
            w = handle.weights(rows)
            return spmv_times(batch.cols, batch.vals, w)

        return margin_fn

    def _build_eval(self):
        objv_fn = self.objv_fn
        margin_fn = self.build_serve_margin()

        @jax.jit
        def ev(slots, batch: SparseBatch):
            margin = margin_fn({"slots": slots}, batch)
            objv = objv_fn(margin, batch.labels, batch.row_mask)
            num_ex = jnp.sum(batch.row_mask)
            a = auc(batch.labels, margin, batch.row_mask)
            acc = accuracy(batch.labels, margin, batch.row_mask)
            return objv, num_ex, a, acc, margin

        return ev

    # -- dense-apply: the crec streaming fast path --------------------------
    #
    # One fused program over a packed crec block (data/crec.py): bitcast the
    # raw bytes to u32 keys, fold to buckets ON DEVICE (mix32 — the host
    # does zero key work), scatter-add the gradient into a table-sized
    # buffer, and apply the handle to the WHOLE table. Exact vs the sparse
    # path: handles whose zero-grad push is the identity (FTRL) sweep
    # unmasked; the rest keep old slots where grad == 0 (the touched-
    # bucket mask, see zero_grad_push_is_identity). Sentinel keys (missing
    # criteo slots) and padded tail rows are masked out of the gradient.

    def _dense_step(self, block_rows: int, nnz: int, kind: str):
        key = (block_rows, nnz, kind)
        fn = getattr(self, "_dense_cache", {}).get(key)
        if fn is not None:
            return fn
        exact_dense = zero_grad_push_is_identity(self.handle)
        handle, objv_fn, dual_fn = self.handle, self.objv_fn, self.dual_fn
        nb = self.cfg.num_buckets
        R, N = block_rows, nnz
        nk = R * N * 4

        def fold_and_forward(slots, packed):
            keys = jax.lax.bitcast_convert_type(
                packed[:nk].reshape(-1, 4), jnp.uint32)
            valid = (keys != jnp.uint32(0xFFFFFFFF))
            b = (mix32(keys) % jnp.uint32(nb)).astype(jnp.int32)
            b = jnp.where(valid, b, 0)
            lab_u8 = packed[nk:nk + R]
            row_mask = (lab_u8 != jnp.uint8(255)).astype(jnp.float32)
            labels = jnp.minimum(lab_u8, 1).astype(jnp.float32)
            w = handle.weights(slots.astype(jnp.float32))
            vf = valid.astype(jnp.float32).reshape(R, N)
            margin = jnp.sum(w[b.reshape(R, N)] * vf, axis=1)
            return b, vf, labels, row_mask, margin

        if kind == "train":
            # NOT donating `packed`: no output aliases it, so the donation
            # would be unusable (XLA warns and copies anyway)

            @partial(jax.jit, donate_argnums=(0, 2))
            def step(slots, packed, t, tau):
                b, vf, labels, row_mask, margin = fold_and_forward(slots,
                                                                  packed)
                objv = objv_fn(margin, labels, row_mask)
                dual = dual_fn(margin, labels, row_mask)
                if not exact_dense:
                    dual = _nudge_zero_dual(dual, labels, row_mask)
                contrib = (dual[:, None] * vf).reshape(-1)
                # scatter-fallback: v1 dense-apply grad build (on-device
                # fold; the tile path replaces this when admissible)
                grad = jnp.zeros((nb,), jnp.float32).at[b].add(contrib)
                s32 = slots.astype(jnp.float32)
                new = masked_push(handle, s32, grad,
                                  t.astype(jnp.float32), tau, exact_dense)
                num_ex = jnp.sum(row_mask)
                a = auc(labels, margin, row_mask)
                acc = accuracy(labels, margin, row_mask)
                d0 = new[:, 0] - s32[:, 0]
                return (new.astype(slots.dtype), t + 1,
                        (objv, num_ex, a, acc, jnp.sum(d0 * d0)))
        else:
            @jax.jit
            def step(slots, packed):
                _, _, labels, row_mask, margin = fold_and_forward(slots,
                                                                  packed)
                objv = objv_fn(margin, labels, row_mask)
                num_ex = jnp.sum(row_mask)
                a = auc(labels, margin, row_mask)
                acc = accuracy(labels, margin, row_mask)
                return objv, num_ex, a, acc, margin

        if not hasattr(self, "_dense_cache"):
            self._dense_cache = {}
        self._dense_cache[key] = step
        return step

    def dense_train_step(self, packed: jax.Array, block_rows: int,
                         nnz: int, tau: float = 0.0):
        """Fused crec-block step over the device-resident raw block
        buffer."""
        step = self._dense_step(block_rows, nnz, "train")
        self.slots, t_new, metrics = step(
            self._stacked(), packed, self._t_device(), self._tau_const(tau))
        self._advance_t(t_new)
        return metrics

    def dense_eval_step(self, packed: jax.Array, block_rows: int, nnz: int):
        return self._dense_step(block_rows, nnz, "eval")(
            self._stacked(), packed)

    # -- dense-apply over a data x model mesh -------------------------------
    #
    # The distributed form of the crec(v1) path, mirroring the crec2 mesh
    # tile step's geometry: the MODEL axis range-shards the bucket table
    # (each shard folds the block's keys and keeps only buckets in its
    # range), the DATA axis shards whole blocks. Partial margins psum over
    # model; gradients psum over data; the handle applies shard-locally.
    # Same packed-metric accumulator layout as the tile mesh step, so
    # the learner's _harvest_macc path serves both formats.

    def _dense_step_mesh(self, block_rows: int, nnz: int, kind: str):
        key = (block_rows, nnz, kind, "mesh")
        fn = getattr(self, "_dense_cache", {}).get(key)
        if fn is not None:
            return fn
        exact_dense = zero_grad_push_is_identity(self.handle)
        from wormhole_tpu.parallel.mesh import DATA_AXIS, shard_map_compat
        handle, objv_fn, dual_fn = self.handle, self.objv_fn, self.dual_fn
        mesh = self.rt.mesh
        m = self.rt.model_axis_size
        nb = self.cfg.num_buckets
        if nb % m:
            raise ValueError(f"num_buckets {nb} not shardable over "
                             f"model axis {m}")
        nb_local = nb // m
        have_model = self.rt.have_model
        R, N = block_rows, nnz
        nk = R * N * 4

        def body(slots_l, packed_l, t, tau, macc):
            packed = packed_l[0]
            keys = jax.lax.bitcast_convert_type(
                packed[:nk].reshape(-1, 4), jnp.uint32)
            valid = keys != jnp.uint32(0xFFFFFFFF)
            b = (mix32(keys) % jnp.uint32(nb)).astype(jnp.int32)
            off = (jax.lax.axis_index(MODEL_AXIS) * nb_local
                   if have_model else 0)
            inr = valid & (b >= off) & (b < off + nb_local)
            bl = jnp.where(inr, b - off, 0)
            lab_u8 = packed[nk:nk + R]
            row_mask = (lab_u8 != jnp.uint8(255)).astype(jnp.float32)
            labels = jnp.minimum(lab_u8, 1).astype(jnp.float32)
            s32 = slots_l.astype(jnp.float32)
            w = handle.weights(s32)
            vf = inr.astype(jnp.float32).reshape(R, N)
            mg = jnp.sum(w[bl.reshape(R, N)] * vf, axis=1)
            margin = (jax.lax.psum(mg, MODEL_AXIS) if have_model else mg)
            objv = objv_fn(margin, labels, row_mask)
            num_ex = jnp.sum(row_mask)
            acc = accuracy(labels, margin, row_mask)
            pos, neg = margin_hist(labels, margin, row_mask)
            objv_g, tot_ex, acc_frac, pos_g, neg_g = mesh_metric_sums(
                objv, num_ex, acc, pos, neg)
            if kind == "eval":
                return objv_g, tot_ex, acc_frac, pos_g, neg_g, margin
            dual = dual_fn(margin, labels, row_mask)
            if not exact_dense:
                dual = _nudge_zero_dual(dual, labels, row_mask)
            contrib = (dual[:, None] * vf).reshape(-1)
            # scatter-fallback: mesh v1 dense-apply grad build (shard-
            # local fold; the mesh tile path replaces this)
            grad = jnp.zeros((nb_local,), jnp.float32).at[bl].add(contrib)
            grad = jax.lax.psum(grad, DATA_AXIS)
            new = masked_push(handle, s32, grad, t.astype(jnp.float32),
                              tau, exact_dense)
            d0 = new[:, 0] - s32[:, 0]
            wdelta2 = jnp.sum(d0 * d0)
            if have_model:
                wdelta2 = jax.lax.psum(wdelta2, MODEL_AXIS)
            packed_m = mesh_macc_row(objv_g, tot_ex, acc_frac, wdelta2,
                                     pos_g, neg_g)
            return new.astype(slots_l.dtype), t + 1, macc + packed_m

        Pm, _Pblk, _ = mesh_step_specs(have_model)
        if kind == "train":
            in_specs = (Pm, P(DATA_AXIS, None), P(), P(), P())
            out_specs = (Pm, P(), P())
            fn = body
        else:
            in_specs = (Pm, P(DATA_AXIS, None))
            out_specs = (P(), P(), P(), P(), P(), P(DATA_AXIS))

            def fn(s, packed_l):
                return body(s, packed_l, jnp.float32(0), jnp.float32(0),
                            jnp.float32(0))
        step = jax.jit(
            shard_map_compat(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs),
            donate_argnums=(0, 2, 4) if kind == "train" else ())
        if not hasattr(self, "_dense_cache"):
            self._dense_cache = {}
        self._dense_cache[key] = step
        return step

    def dense_train_step_mesh(self, packed: jax.Array, block_rows: int,
                              nnz: int, tau: float = 0.0):
        """Mesh dense step over ``data_axis_size`` packed v1 blocks
        stacked on a leading axis. Metrics accumulate on device
        (fetch_metrics); returns the step-clock scalar."""
        step = self._dense_step_mesh(block_rows, nnz, "train")
        nb_local = self.cfg.num_buckets // max(self.rt.model_axis_size, 1)
        self.slots, t_new, self._macc = self.mesh_transport().dispatch(
            step, self._mesh_table(), packed, self._t_device(),
            self._tau_const(tau), self._macc_buf(),
            ici_bytes=mesh_step_ici_bytes(
                self.rt, margin_elems=block_rows, grad_elems=nb_local))
        self._advance_t(t_new)
        return t_new

    def dense_eval_step_mesh(self, packed: jax.Array, block_rows: int,
                             nnz: int):
        return self.mesh_transport().dispatch(
            self._dense_step_mesh(block_rows, nnz, "eval"),
            self._mesh_table(), packed,
            ici_bytes=mesh_step_ici_bytes(
                self.rt, margin_elems=block_rows, train=False))

    # -- tile-blocked MXU step: the crec2 streaming fast path ---------------
    #
    # One fused program over a tile-grouped crec2 block (data/crec.py v2 +
    # ops/tilemm.py): the block bytes ARE the kernel operands — digit-
    # encoded (bucket, row) pairs grouped by 16K-bucket tile, so pull and
    # push both run as dense one-hot matmuls on the MXU instead of
    # serialized gather/scatter (see tilemm module docstring). Same
    # dense-apply semantics as the v1 crec path, over every bucket, with
    # the touched-bucket mask when a zero-grad push is not the identity
    # (zero_grad_push_is_identity). Where the store keeps its table as
    # planes (_planar) they are the steps' state as they are: the in-place
    # FTRL variant touches the table only inside its kernel, the others in
    # one elementwise pass over the planes and the gradient.

    def _in_place_why(self) -> Optional[str]:
        # the in-place kernel runs FTRLHandle.update on a tile
        return IN_PLACE if isinstance(self.handle, FTRLHandle) else None

    def _fused_span(self) -> str:
        return ("tilemm:fused_cached"
                if self.step_kernel[2] == "onehot_cache=on"
                else "tilemm:fused_step")

    def _tile_body(self, ts: TileStep):
        from wormhole_tpu.ops import tilemm
        exact_dense = zero_grad_push_is_identity(self.handle)
        handle, dual_fn = self.handle, self.dual_fn
        spec, oc, fused, cache = ts.spec, ts.oc, ts.fused, ts.cache
        loss_name = self.cfg.loss

        # The phases XLA runs around the kernels are jits of their own,
        # so that the device trace's ops say which phase they belong to
        # (the profiler keeps the path of an op under a nested jit, not
        # under a bare named scope): tile_ovf_gather (the overflow
        # pairs' weights summed onto their rows), tile_ovf_scatter (the
        # pairs' duals added into the gradient), tile_table_update (the
        # one elementwise pass over the planes and the gradient). The
        # first two take the list in either form: through the hot tile
        # and the multi-channel kernel pair, or a slot a pair.
        @jax.jit
        def tile_ovf_gather(w, lst):
            helper, first, second = overflow.pick(
                lst, tilemm.spill_margin_rows, tilemm.hot_margin_rows)
            return helper(w, first, second, spec)

        @jax.jit
        def tile_ovf_scatter(grad, dual, lst):
            helper, first, second = overflow.pick(
                lst, tilemm.spill_grad_scatter, tilemm.hot_grad_scatter)
            return helper(grad, dual, first, second, spec)

        @jax.jit
        def tile_table_update(planes, grad, t, tau):
            return masked_push_planes(
                handle, planes, grad.reshape(planes[0].shape),
                t.astype(jnp.float32), tau, exact_dense)

        if ts.in_place:
            def step(table, block, t, tau, macc):
                pw, labels, row_mask, _lst = ts.decode(block)
                margin, new, wdelta2 = tilemm.fused_step_update(
                    pw, tbl.planes_of(table), labels, row_mask, spec,
                    loss_name, handle, cache=cache)
                return ts.finish(tbl.table_like(new, table), wdelta2,
                                 margin, labels, row_mask, t, macc)
        elif ts.kind == "train":
            def step(table, block, t, tau, macc):
                pw, labels, row_mask, lst = ts.decode(block)
                planes = tbl.planes_of(table)
                w = handle.weights(tbl.PlaneTable(planes))
                # the overflow pairs' margins, pre-aggregated onto
                # their rows: ONE grid add on the split path, one extra
                # operand of the fused kernel (summed into the
                # phase-boundary dual), so the two stay bitwise-equal
                sp = tile_ovf_gather(w, lst) if oc else None
                if not fused:
                    margin = tilemm.forward_margins(pw, w, spec)
                    if oc:
                        margin = margin + sp
                    dual = dual_fn(margin, labels, row_mask)
                    if not exact_dense:
                        dual = _nudge_zero_dual(dual, labels, row_mask)
                    grad = tilemm.backward_grad(pw, dual, spec)
                else:
                    margin, grad = tilemm.fused_step_grad(
                        pw, w, labels, row_mask, spec, loss_name,
                        exact_dense, cache=cache, spill_margins=sp)
                    if oc:
                        # the pairs' grad contributions scatter in XLA
                        # from the emitted margins — the dual recompute
                        # is elementwise, so the scattered duals are
                        # bitwise the kernel's own
                        dual = dual_fn(margin, labels, row_mask)
                        if not exact_dense:
                            dual = _nudge_zero_dual(dual, labels,
                                                    row_mask)
                if oc:
                    grad = tile_ovf_scatter(grad, dual, lst)
                new, wdelta2 = tile_table_update(planes, grad, t, tau)
                return ts.finish(tbl.table_like(new, table), wdelta2,
                                 margin, labels, row_mask, t, macc)
        else:
            def step(table, block):
                pw, labels, row_mask, lst = ts.decode(block)
                w = handle.weights(tbl.PlaneTable(tbl.planes_of(table)))
                margin = tilemm.forward_margins(pw, w, spec)
                if oc:
                    margin = margin + tile_ovf_gather(w, lst)
                return ts.evaluate(margin, labels, row_mask)

        return step

    # -- tile step over a data x model mesh ---------------------------------
    #
    # The distributed form of the crec2 path: the MODEL axis shards the
    # bucket tiles (each shard runs the tile kernels over its own tile
    # range — the ps-lite key-range server shard, reborn as a mesh
    # dimension), the DATA axis shards whole blocks (one per data index).
    # Partial margins psum over model; gradients psum over data; the handle
    # applies shard-locally. Inputs arrive stacked on a leading data axis.

    def _tile_step_mesh(self, info, kind: str, hot: bool = False):
        """The mesh step program. ``hot``: the group's lists crossed in
        their hot form a shard (data/crec.MeshGroupFeed chose it), so
        the two list lanes are ``ovf_u`` / ``ovf_pw`` and the two list
        phases run the hot kernel pair; a program of its own, the COO
        one is as it was."""
        key = (info, kind, "mesh") + (("hot",) if hot else ())
        fn = getattr(self, "_tile_cache", {}).get(key)
        if fn is not None:
            return fn
        exact_dense = zero_grad_push_is_identity(self.handle)
        from wormhole_tpu.ops import tilemm
        from wormhole_tpu.parallel.mesh import DATA_AXIS, shard_map_compat
        handle, objv_fn, dual_fn = self.handle, self.objv_fn, self.dual_fn
        mesh = self.rt.mesh
        spec = info.spec
        nb_local, spec_local, have_model = mesh_tile_geometry(self.rt,
                                                              spec)
        oc = info.ovf_cap
        # the list phases' static argument: a hot form reads the shard's
        # spec, the COO list the shard's key range alone
        form = {"nb_local": nb_local}
        if hot:
            form["hot"] = spec_local

        # Of the step's phases the device trace keeps the two that are
        # jits of their own, as an op's ``tf_op``: mesh_ovf_gather and
        # mesh_ovf_scatter (the listed pairs, above). The other five are
        # bare jax.named_scopes (mesh_forward, mesh_psum_margin,
        # mesh_backward, mesh_psum_grad, mesh_push), which name the HLO
        # for a reader of the dump but which the profiler loses: the
        # kernels are told by their custom call, the psums by their op
        # kind, the table's pass only by its shapes. The shard comes as
        # the store keeps it: its planes (``_planar``: plane 0 IS w, and
        # the push is one elementwise pass over the planes and the summed
        # gradient), or a stacked (nb_local, val_len) shard (bfloat16),
        # sliced into planes here and stacked again after the push.
        def mesh_step(table_l, pw_l, lab_l, ovb_l, ovr_l, t, tau, macc):
            pw1 = pw_l[0].reshape(spec_local.pairs_shape)
            lab = lab_l[0]
            row_mask = (lab != jnp.uint8(255)).astype(jnp.float32)
            labels = jnp.minimum(lab, 1).astype(jnp.float32)
            planes = tbl.planes_of(table_l)
            with jax.named_scope("mesh_forward"):
                w = handle.weights(tbl.PlaneTable(planes))
                mg = tilemm.forward_margins(pw1, w, spec_local)
                off = (jax.lax.axis_index(MODEL_AXIS) * nb_local
                       if have_model else 0)
                if oc:
                    # a COO lane is a DATA member's, a hot lane a chip's
                    ovb, ovr = ((ovb_l[0, 0], ovr_l[0, 0]) if hot
                                else (ovb_l[0], ovr_l[0]))
                    mg = mesh_ovf_gather(mg, w, ovb, ovr, off, **form)
            with jax.named_scope("mesh_psum_margin"):
                margin = (jax.lax.psum(mg, MODEL_AXIS) if have_model
                          else mg)
            objv = objv_fn(margin, labels, row_mask)
            num_ex = jnp.sum(row_mask)
            acc = accuracy(labels, margin, row_mask)
            pos, neg = margin_hist(labels, margin, row_mask)
            objv_g, tot_ex, acc_frac, pos_g, neg_g = mesh_metric_sums(
                objv, num_ex, acc, pos, neg)
            if kind == "eval":
                return objv_g, tot_ex, acc_frac, pos_g, neg_g, margin
            with jax.named_scope("mesh_backward"):
                dual = dual_fn(margin, labels, row_mask)
                if not exact_dense:
                    dual = _nudge_zero_dual(dual, labels, row_mask)
                g = tilemm.backward_grad(pw1, dual, spec_local)
                if oc:
                    g = mesh_ovf_scatter(g, dual, ovb, ovr, off, **form)
            with jax.named_scope("mesh_psum_grad"):
                g = jax.lax.psum(g, DATA_AXIS)
            with jax.named_scope("mesh_push"):
                new, wdelta2 = masked_push_planes(
                    handle, planes, g.reshape(planes[0].shape),
                    t.astype(jnp.float32), tau, exact_dense)
                if have_model:
                    wdelta2 = jax.lax.psum(wdelta2, MODEL_AXIS)
            packed = mesh_macc_row(objv_g, tot_ex, acc_frac, wdelta2,
                                   pos_g, neg_g)
            return tbl.table_like(new, table_l), t + 1, macc + packed

        Pm, _Pblk, data_specs = mesh_step_specs(have_model, self._planar,
                                                hot)
        if kind == "train":
            in_specs = data_specs + (P(), P(), P())
            out_specs = (Pm, P(), P())
            fn = mesh_step
        else:
            # eval takes no clock args (the t/tau params are train-only)
            in_specs = data_specs
            out_specs = (P(), P(), P(), P(), P(), P(DATA_AXIS))

            def mesh_eval_step(s, pw_, lab_, ovb_, ovr_):
                # the eval branch returns before touching t/tau/macc
                return mesh_step(s, pw_, lab_, ovb_, ovr_,
                                 jnp.float32(0), jnp.float32(0),
                                 jnp.float32(0))
            fn = mesh_eval_step
        step = jax.jit(
            shard_map_compat(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs),
            # donate slots/clock/accumulator only when the step returns
            # them (train); the eval step has no aliasable output, so
            # donating would leave self.slots at a donated buffer
            donate_argnums=(0, 5, 7) if kind == "train" else ())
        if not hasattr(self, "_tile_cache"):
            self._tile_cache = {}
        self._tile_cache[key] = step
        return step

    def tile_train_step_mesh(self, blocks: dict, info, tau: float = 0.0):
        """Mesh tile step over ``data_axis_size`` blocks stacked on a
        leading axis: blocks = {pw (D,T,SG,N), labels (D,R),
        ovf_b (D,O), ovf_r (D,O)}, or with the lists in their hot form
        a shard {.., ovf_u (D,M,U), ovf_pw (D,M,V,SG,N)} in the COO
        lanes' place. Metrics accumulate on device (fetch_metrics),
        cross-shard sums included; returns the step clock scalar."""
        oc = info.ovf_cap
        D = self.rt.data_axis_size
        hot = overflow.is_hot(blocks)
        step = self._tile_step_mesh(info, "train", hot)
        z = mesh_ovf_zeros(D, oc)
        nb_local = mesh_tile_geometry(self.rt, info.spec)[0]
        lists = [blocks.get(k, z) for k in overflow.names(hot)]
        self.slots, t_new, self._macc = self.mesh_transport().dispatch(
            step, self._mesh_table(tile=True), blocks["pw"],
            blocks["labels"], *lists,
            self._t_device(), self._tau_const(tau), self._macc_buf(),
            ici_bytes=mesh_step_ici_bytes(
                self.rt, margin_elems=info.block_rows,
                grad_elems=nb_local))
        self._advance_t(t_new)
        return t_new

    def tile_eval_step_mesh(self, blocks: dict, info):
        oc = info.ovf_cap
        D = self.rt.data_axis_size
        z = mesh_ovf_zeros(D, oc)
        return self.mesh_transport().dispatch(
            self._tile_step_mesh(info, "eval"),
            self._mesh_table(tile=True), blocks["pw"], blocks["labels"],
            blocks.get("ovf_b", z), blocks.get("ovf_r", z),
            ici_bytes=mesh_step_ici_bytes(
                self.rt, margin_elems=info.block_rows, train=False))

    # -- split pull/push pipeline (delay-tolerant DT2 path) -----------------
    #
    # The fused step has no pull→push gap, so the staleness DT2
    # compensates cannot arise there. This pair reintroduces the
    # reference worker's real pipeline (async_sgd.h:57-127): ``dt2_pull``
    # computes the gradient against the CURRENT weights and snapshots
    # each key's cumulative-gradient slot; other batches' pushes may land
    # before the matching ``dt2_push`` applies the update, and the handle
    # corrects for exactly that interleaved mass.

    def _build_dt2(self):
        handle, objv_fn, dual_fn = self.handle, self.objv_fn, self.dual_fn

        @jax.jit
        def pull(slots, batch: SparseBatch):
            rows = slots[batch.uniq_keys].astype(jnp.float32)
            w = handle.weights(rows)
            margin = spmv_times(batch.cols, batch.vals, w)
            objv = objv_fn(margin, batch.labels, batch.row_mask)
            dual = dual_fn(margin, batch.labels, batch.row_mask)
            grad = spmv_trans_times(batch.cols, batch.vals, dual,
                                    w.shape[0])
            snap = rows[:, 1]                      # gsum at pull time
            num_ex = jnp.sum(batch.row_mask)
            a = auc(batch.labels, margin, batch.row_mask)
            acc = accuracy(batch.labels, margin, batch.row_mask)
            return grad, snap, (objv, num_ex, a, acc)

        @partial(jax.jit, donate_argnums=(0,))
        def push(slots, uniq_keys, key_mask, grad, snap):
            rows = slots[uniq_keys].astype(jnp.float32)
            # DT2's recurrence depends on the snapshot only (the t/tau
            # schedule knobs belong to the DT-SGD variants)
            new_rows = handle.push(rows, grad, jnp.float32(0),
                                   jnp.float32(0), gsum_snap=snap)
            delta = (new_rows - rows) * key_mask[:, None]
            # scatter-fallback: dt2 uniq-key push, O(uniq) rows
            return slots.at[uniq_keys].add(delta.astype(slots.dtype))

        return pull, push

    def dt2_pull(self, batch: SparseBatch):
        """ZPull + gradient compute; returns (grad, gsum snapshot,
        metrics) for a later dt2_push of the same batch."""
        if not hasattr(self, "_dt2"):
            self._dt2 = self._build_dt2()
        return self._dt2[0](self._stacked(), batch)

    def dt2_push(self, batch: SparseBatch, grad, snap) -> None:
        """ZPush: apply the delayed gradient with its pull-time snapshot."""
        self.slots = self._dt2[1](
            self._stacked(), batch.uniq_keys, batch.key_mask, grad, snap)
        self.t += 1

    # -- dense global-delta apply (ps engine path) --------------------------
    #
    # The exchange engine ships gradient windows in dense bucket space:
    # every host scatters its per-uniq-key gradient into a num_buckets
    # vector, the engine allreduces it, and this push applies the summed
    # window to the WHOLE replicated table. Same masking contract as the
    # dense streaming steps (zero_grad_push_is_identity): exact handles
    # sweep unmasked, the rest keep old slots where the global grad is
    # exactly zero. ``tau`` is the engine-measured window delay — the DT
    # handles' staleness input, scaled by lr_theta like every other path.

    def _build_ps_push(self):
        handle = self.handle
        exact_dense = zero_grad_push_is_identity(handle)

        @partial(jax.jit, donate_argnums=(0,))
        def push(slots, grad, t, tau):
            s32 = slots.astype(jnp.float32)
            new = masked_push(handle, s32, grad, t.astype(jnp.float32),
                              tau, exact_dense)
            return new.astype(slots.dtype), t + 1

        return push

    def ps_push(self, grad, tau: float = 0.0) -> None:
        """Apply one globally-reduced dense delta window (ps/ engine)."""
        if not hasattr(self, "_ps_push_fn"):
            self._ps_push_fn = self._build_ps_push()
        self.slots, t_new = self._ps_push_fn(
            self._stacked(), jnp.asarray(grad, jnp.float32),
            self._t_device(), self._tau_const(tau))
        self._advance_t(t_new)

    # -- the ZPush/ZPull surface --------------------------------------------

    def train_step(self, batch: SparseBatch, tau: float = 0.0):
        """Dispatch one fused step; returns the (async) metrics tuple."""
        self.slots, t_new, metrics = self._step(
            self._stacked(), batch, self._t_device(), self._tau_const(tau))
        self._advance_t(t_new)
        return metrics

    def eval_step(self, batch: SparseBatch):
        return self._eval(self._stacked(), batch)

    def pull(self, keys: np.ndarray) -> np.ndarray:
        """Debug/oracle surface: weights for explicit bucket ids."""
        return np.asarray(self.handle.weights(
            self.slots[jnp.asarray(keys)].astype(jnp.float32)))

    def nnz_weight(self) -> int:
        return int(jnp.sum(self.handle.weights(
            self.slots.astype(jnp.float32)) != 0))

    # -- model IO (per-shard text dump, guide/conf.md:25-27) ----------------

    def save_model(self, path: str, rank: Optional[int] = None,
                   key_fold: str = "") -> None:
        """Write nonzero (bucket, weight) pairs as text — the reference's
        per-server ``${model_out}_${server_id}`` shards; here one file per
        host (process). With the table sharded ACROSS processes, each host
        writes exactly its addressable bucket rows (global ids).

        ``key_fold`` names the key→bucket scheme the model was trained
        under ("splitmix64" for the text/sparse formats, "mix32" for
        crec/crec2) — recorded as a header comment so a cross-format
        warm start fails loudly instead of silently remapping every
        feature (the two folds bucket the same key differently)."""
        from wormhole_tpu.data.stream import open_stream
        if rank is None:
            rank = jax.process_index()
        if getattr(self.slots, "is_fully_addressable", True):
            shards = [(0, self.slots)]
        else:
            parts = {}
            for s in self.slots.addressable_shards:
                start = s.index[0].start or 0
                parts[start] = s.data
            shards = sorted(parts.items())
        with open_stream(f"{path}_{rank}", "w") as f:
            if key_fold:
                f.write(f"# key_fold={key_fold}\n")
            for start, block in shards:
                w = np.asarray(self.handle.weights(
                    block.astype(jnp.float32)))
                for i in np.nonzero(w)[0]:
                    f.write(f"{start + i}\t{w[i]:.6g}\n")

    def load_model(self, path: str, expect_key_fold: str = "") -> None:
        """Read back a save_model dump. ``path`` may be the bare
        ``model_out`` prefix: all ``{path}_{rank}`` shard files are merged
        (save_model writes per-host shards, so a bare model_out -> model_in
        round trip works without manually appending "_0").

        ``expect_key_fold`` (when both sides name a scheme) must match the
        recorded ``# key_fold=`` header: a model trained under one
        data_format family silently maps every feature to different
        buckets under the other."""
        import glob as _glob
        from wormhole_tpu.data.stream import open_stream
        paths = [path]
        if not os.path.exists(path):
            shard_paths = sorted(_glob.glob(path + "_*"))
            if not shard_paths:
                raise FileNotFoundError(path)
            paths = shard_paths
        text = ""
        for pth in paths:
            with open_stream(pth, "r") as f:
                t = f.read()
            text += t.decode() if isinstance(t, bytes) else t
            text += "\n"
        w = np.zeros(self.cfg.num_buckets, np.float32)
        for ln in text.splitlines():
            ln = ln.strip()
            if not ln:
                continue
            if ln.startswith("#"):
                if "key_fold=" in ln and expect_key_fold:
                    saved = ln.split("key_fold=")[1].split()[0]
                    if saved != expect_key_fold:
                        raise ValueError(
                            f"model {path} was trained with "
                            f"key_fold={saved} but this run folds keys "
                            f"with {expect_key_fold} (crec formats hash "
                            "differently from the text formats, and "
                            "text data itself folds mix32 on the "
                            "single-process text_dense fast path but "
                            "splitmix64 under run_multihost — set "
                            "text_dense=false to continue a multi-"
                            "process model single-process); retrain or "
                            "convert the data, a warm start would "
                            "remap every feature")
                continue
            k, v = ln.split()
            w[int(k)] = float(v)
        # handle-aware warm start: slots such that w is a fixed point of a
        # zero-gradient push (FTRL must seed z, not just slot 0)
        self.slots = put_like(self.slots,
                              np.asarray(self.handle.warm_start(
                                  jnp.asarray(w)).astype(self.dtype)))
