"""Tile-blocked MXU gather/scatter — the TPU-native sparse hot path.

The reference's server hot loop applies per-key updates with random access
into the model (sgd_server_handle.h:121-140 via ps-lite's key->offset map);
its worker computes margins with an OpenMP SpMV (spmv.h:72-119). Random
per-element access is exactly what a TPU TensorCore cannot do (no
SparseCore on v5e; XLA lowers 4M-index gather/scatter to a serialized
per-element loop measured at ~13-25ns/elem). This module restructures the
sparse compute so BOTH directions run on the MXU as dense one-hot matmuls:

  * The hashed bucket space [0, nb) is factored into tiles of 16384 =
    (hi 128) x (lo 128). Offline (the crec2 writer, data/crec.py), each
    block's (bucket, row) pairs are grouped by tile and digit-encoded.
  * Pull (w per pair):   m = OH(hi) @ W_tile;  w_p = m[p, lo_p] via a
    one-hot lane pick. A gather became a (C,128)@(128,128) matmul.
  * Row reduce (margin): rows factor as (rhi 64) x (rlo 128); the margin
    grid is the joint histogram  OH(rhi)^T @ (w_p * OH(rlo))  — a matmul
    whose (64,128) output IS the per-row margins, reshaped.
  * Push (grad histogram): G_tile = OH(hi)^T @ (dual_p * OH(lo)) — the
    4M-bin scatter-add became a (128,C)@(C,128) matmul per tile.

Cost is pairs x tile_size x 2 flops — independent of nb — ~600 GFLOP per
100K-row criteo block of MXU instead of ~77ms of serialized scatter
(round-2 BENCH). The kernels are VPU/relayout-sensitive, not just
MXU-bound; two layout rules brought them from 21% to >50% of the
MXU-pass floor (measured on the chip, round 3):

  1. every dot is a plain A@B (contract lanes of lhs with sublanes of
     rhs) — the "transposed" one-hots (rhiT, ohhiT) are BUILT in that
     orientation (digit on sublanes, pair index on lanes), so Mosaic
     inserts no transposes and the digit vector needs no relayout there;
  2. all four digits of a pair are packed into ONE u32 word, so the
     value-chain one-hots (pair index on sublanes) need a single
     lanes->sublanes relayout of the packed word — per (group, tile) in
     the fwd kernel (the value chain runs group-wide), per subblock in
     the bwd kernel — instead of one per one-hot.

Pair word fields: lo = bits 0..6, hi = bits 7..15 (9 bits so the pad
value 511 is representable), rlo = bits 16..22, rhi = bits 23..28.
Pad word = 511 << 7: its hi digit matches no iota in [0,128), so the
pad row/column of every hi one-hot is all-zero — and the hi one-hot
guards both directions (fwd: m row = 0 kills the value chain; bwd: the
ohhiT column = 0 kills the contribution). No masks needed.

Skewed data (a bucket hit by more than `cap` pairs of one subblock, e.g.
a criteo missing-value token) overflows to a (bucket, row) COO list —
exact in float32, and empty for hashed uniform-ish data. A SHORT list,
or one whose buckets are mostly distinct, is handled by the classic
gather/scatter path (the COO spill helpers below). A long list of a
skewed block names few buckets many times (1.3M pairs, 5,600 buckets at
the criteo geometry), so the feed also ships it in a second form
(``encode_hot``): the distinct buckets, and the pairs as packed words
over their RANK in that list. The step then gathers the few thousand
weights once into a hot tile and runs the pairs through the
multi-channel kernels below over three bfloat16 channels whose sum is
the float32 value (``split3``): the same one-hot matmuls, exact, with a
16K-slot gather and scatter where the COO path has one a pair (the hot
tile helpers). FMStore's spill step takes the same form at k + 2 float32
channels, 3(k + 2) parts through one call of each kernel (``_hot_pull``
and ``_hot_push`` are parameterised by the channels; FTRL's helpers are
their one-channel case). Which form a block takes is data/crec.HotRoom's
rule.

Off the TPU backend the kernels run in Pallas interpret mode, which is
how the CPU tests drive them; the first such build says so on the log.
Interpret mode is a correctness tool only — a run that asks for the chip
calls ``parallel.mesh.require_tpu`` first and fails without one.
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import lru_cache, partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from wormhole_tpu.ops.overflow import UNUSED

A_HI = 128          # bucket hi digit (one-hot width, MXU-native)
B_LO = 128          # bucket lo digit
TILE = A_HI * B_LO  # buckets per tile
RH = 64             # row hi digit
RL = 128            # row lo digit
RSUB = RH * RL      # rows per subblock (8192)

# packed pair word (u32): lo | hi<<7 | rlo<<16 | rhi<<23
#
# RH=64/RL=128 (not 128/64): the row-hi digit is the STREAMING dim (lhs
# rows) of the fwd histogram matmul rhiT @ rhs — RH=64 halves its MXU
# time — and with RL=128 every matmul in both kernels is 128 lanes wide
# (the old RL=64 pick/hist ran half-lane). Measured round 4: fwd -17%.
LO_SH, HI_SH, RLO_SH, RHI_SH = 0, 7, 16, 23
LO_M, HI_M, RLO_M, RHI_M = 127, 511, 127, 63
PADWORD = np.uint32(511 << HI_SH)


@lru_cache(maxsize=None)
def _log_interpret(backend: str) -> None:
    """Once per backend: say that the kernels are being interpreted."""
    from wormhole_tpu.utils.logging import get_logger
    get_logger("tilemm").warning(
        "backend is %r, not tpu: tile kernels build in Pallas INTERPRET "
        "mode (correctness only; no time or rate from this process is a "
        "device number)", backend)


def _interpret() -> bool:
    """True off the TPU backend: the kernels then build in Pallas
    interpret mode (the CPU tests' path). Never silent — the first
    build that takes it logs the backend, so no output can be read as
    a device run by mistake."""
    backend = jax.default_backend()
    if backend == "tpu":
        return False
    _log_interpret(backend)
    return True


@dataclass(frozen=True)
class TileSpec:
    """Static layout of one encoded block (stored in the crec2 header)."""

    nb: int              # model buckets; multiple of TILE
    subblocks: int       # S: rows per block = S * 8192
    cap: int             # C: max pairs per (subblock, tile); mult of 128
    group: int = 4       # GS: subblocks batched per pairs-array slice
    tiles_step: int = 4  # TB: tiles per pallas grid step
    fuse: int = 1        # K: adjacent tiles fused per BWD value chain
                         # (high-nb regime: chains stay ~4-6K pairs
                         # long when cap floors at 128; a pure kernel
                         # view — the pairs bytes are unchanged; fwd
                         # measured faster per-tile, see the fused
                         # section comment)

    def __post_init__(self):
        if self.nb % TILE:
            raise ValueError(f"nb {self.nb} not a multiple of {TILE}")
        if self.subblocks % self.group:
            raise ValueError("subblocks must be a multiple of group")
        if self.cap % 128:
            raise ValueError("cap must be a multiple of 128")
        if self.tiles % self.tiles_step:
            raise ValueError(f"tiles {self.tiles} not a multiple of "
                             f"tiles_step {self.tiles_step}")
        if self.fuse > 1 and self.tiles_step % self.fuse:
            raise ValueError(f"tiles_step {self.tiles_step} not a "
                             f"multiple of fuse {self.fuse}")

    @property
    def tiles(self) -> int:
        return self.nb // TILE

    @property
    def block_rows(self) -> int:
        return self.subblocks * RSUB

    @property
    def n(self) -> int:  # pairs per grouped slice
        return self.group * self.cap

    @property
    def pairs_shape(self) -> Tuple[int, int, int]:
        return (self.tiles, self.subblocks // self.group, self.n)


def make_spec(nb: int, subblocks: int, cap: int) -> TileSpec:
    """TileSpec with the largest group (<=4) and tiles_step (<=16, the
    measured sweet spot: amortizes grid overhead, still compiles fast)
    that divide the given shape — small files get degenerate but valid
    batching. When cap floors leave value chains short (high-nb regime,
    docs/perf.md "Model-size scaling"), adjacent tiles FUSE in the bwd
    kernel so its chains stay ~4-6K pairs long."""
    group = max(g for g in (4, 2, 1) if subblocks % g == 0)
    tiles = nb // TILE
    tb = max(t for t in (16, 8, 4, 2, 1) if tiles % t == 0)
    # fuse only in the deep cap-floor regime (cap <= 256): at cap=384
    # (nb=2^24 criteo) the unfused kernels measured ~5% faster — the
    # K-wide fwd one-hot build costs more than the chain savings until
    # chains are truly short. fuse <= 8: the bwd joint-digit compare
    # constant is (K*N, GS*RH) i32 (~4 MB at K=8, cap=128) and the
    # chain intermediates scale with K*N — both must stay VMEM-friendly.
    fuse = 1
    if cap <= 256:
        while (group * cap * fuse * 2 <= 8192 and fuse * 2 <= min(tb, 8)):
            fuse *= 2
    return TileSpec(nb=nb, subblocks=subblocks, cap=cap, group=group,
                    tiles_step=tb, fuse=fuse)


# ---------------------------------------------------------------------------
# offline encoder (host, numpy) — used by the crec2 writer and tests
# ---------------------------------------------------------------------------

def pack_fields(bucket_in_tile: np.ndarray, row_in_sub: np.ndarray
                ) -> np.ndarray:
    """Digit-encode (bucket % TILE, row % RSUB) into packed u32 words."""
    b = bucket_in_tile.astype(np.uint32)
    r = row_in_sub.astype(np.uint32)
    return ((b & 127) | ((b >> 7) << HI_SH)
            | ((r & np.uint32(RL - 1)) << RLO_SH) | ((r >> 7) << RHI_SH))


def unpack_fields(pw: np.ndarray) -> Tuple[np.ndarray, np.ndarray,
                                           np.ndarray]:
    """(bucket_in_tile, row_in_sub, is_pad) from packed words."""
    pw = pw.astype(np.uint32)
    hi = (pw >> HI_SH) & HI_M
    b = (hi << 7) | (pw & LO_M)
    r = (((pw >> RHI_SH) & RHI_M) << 7) | ((pw >> RLO_SH) & RLO_M)
    return b, r, hi >= 128


def encode_subblock(buckets: np.ndarray, rows: np.ndarray,
                    spec: TileSpec) -> Tuple[np.ndarray, np.ndarray,
                                             np.ndarray]:
    """Group one subblock's pairs by tile.

    buckets int64 (P,) in [0, nb); rows (P,) in [0, 8192).
    Returns (pw u32 (T, cap), ovf_buckets, ovf_rows);
    overflow = pairs beyond `cap` in their tile (exact COO spill).
    """
    T, C = spec.tiles, spec.cap
    tile = buckets >> 14
    order = np.argsort(tile, kind="stable")
    tile_s = tile[order]
    counts = np.bincount(tile_s, minlength=T)
    starts = np.zeros(T + 1, np.int64)
    np.cumsum(counts, out=starts[1:])
    out = np.full((T, C), PADWORD, np.uint32)
    pw_s = pack_fields(buckets & 16383, rows)[order]
    # vectorized ragged copy: positions of kept pairs in the sorted stream
    idx = np.arange(len(tile_s)) - starts[tile_s]
    keep = idx < C
    out[tile_s[keep], idx[keep]] = pw_s[keep]
    spill = ~keep
    return (out,
            buckets[order][spill].astype(np.uint32),
            rows[order][spill].astype(np.uint32))


def encode_block(buckets: np.ndarray, rows: np.ndarray,
                 spec: TileSpec) -> Tuple[np.ndarray, np.ndarray,
                                          np.ndarray]:
    """Encode a whole block of valid (bucket, global-row) pairs.

    rows in [0, block_rows). Returns (pw (T, S//GS, N) u32,
    ovf_buckets u32, ovf_rows u32 (block-global rows))."""
    S, T, C = spec.subblocks, spec.tiles, spec.cap
    pw = np.empty((S, T, C), np.uint32)
    ovb: List[np.ndarray] = []
    ovr: List[np.ndarray] = []
    sub = rows // RSUB
    for s in range(S):
        m = sub == s
        p, ob, orow = encode_subblock(buckets[m], rows[m] % RSUB, spec)
        pw[s] = p
        if len(ob):
            ovb.append(ob)
            ovr.append(orow + s * RSUB)
    # (S,T,C) -> (T,S,C) -> group-flattened kernel layout
    pw = np.swapaxes(pw, 0, 1).reshape(spec.pairs_shape)
    return (pw,
            np.concatenate(ovb) if ovb else np.zeros(0, np.uint32),
            np.concatenate(ovr) if ovr else np.zeros(0, np.uint32))


def cap_overflow(ovb: np.ndarray, ovr: np.ndarray,
                 ovf_cap: int) -> Tuple[np.ndarray, np.ndarray]:
    """An overflow list at the fixed width every consumer wants: exactly
    ``ovf_cap`` long, unused slots carrying ``UNUSED`` buckets (the
    kernels' no-op sentinel) and row 0. A list longer than ``ovf_cap``
    is cut to its first ``ovf_cap`` entries: the caller compares the
    true count first."""
    ob = np.full(max(ovf_cap, 0), UNUSED, np.uint32)
    orow = np.zeros(max(ovf_cap, 0), np.uint32)
    keep = min(len(ovb), ovf_cap)
    ob[:keep] = ovb[:keep]
    orow[:keep] = ovr[:keep]
    return ob, orow


# -- the overflow list's hot form (host, numpy: the written specification) ---

HOT_CAP = 512       # C': slots of a (subblock, virtual tile) cell
HOT_CH = 3          # bfloat16 channels a float32 value splits into


def hot_spec(tiles: int, subblocks: int) -> TileSpec:
    """The TileSpec of a hot form of ``tiles`` virtual tiles: the block's
    own subblocks and group, ``HOT_CAP`` slots a cell, two tiles a grid
    step (some 12K slots of work a step at twelve subblocks, and a
    quarter of the unrolled body that eight would lower and compile on
    every start). FM's thirty parts keep the two: one tile a step would
    compile the pair cold in 20 + 17 s where two take 46 + 51, beside the
    step kernel's own 37 and not after it, and cost 1.5 ms a call, 3 ms
    of every step (PERF.md section 6, PR 48)."""
    group = max(g for g in (4, 2, 1) if subblocks % g == 0)
    return TileSpec(nb=tiles * TILE, subblocks=subblocks, cap=HOT_CAP,
                    group=group, tiles_step=2 if tiles % 2 == 0 else 1)


def hot_ranks(ovf_b: np.ndarray, ovf_r: np.ndarray, subblocks: int
              ) -> Tuple[np.ndarray, np.ndarray, int]:
    """``(uniq, rank, cell_max)`` of a list of valid pairs: its distinct
    buckets in ascending order, each pair's index in them, and the most
    pairs a (subblock, hot tile) cell holds, a hot tile being ``TILE``
    consecutive ranks."""
    uniq, rank = np.unique(ovf_b, return_inverse=True)
    rank = rank.reshape(-1).astype(np.uint32)
    tiles = max(-(-len(uniq) // TILE), 1)
    cell = (ovf_r // RSUB).astype(np.int64) * tiles + (rank >> 14)
    cells = np.bincount(cell, minlength=subblocks * tiles)
    return uniq.astype(np.uint32), rank, int(cells.max(initial=0))


def hot_buckets(uniq: np.ndarray, tiles: int) -> np.ndarray:
    """``ovf_u``: the distinct buckets padded with ``UNUSED`` to ``tiles``
    whole hot tiles."""
    ovf_u = np.full(tiles * TILE, UNUSED, np.uint32)
    ovf_u[:len(uniq)] = uniq
    return ovf_u


def encode_hot(uniq: np.ndarray, rank: np.ndarray, ovf_r: np.ndarray,
               subblocks: int, tiles: int, vtiles: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """The hot form ``(ovf_u, ovf_pw)`` of an overflow list whose pairs
    are ``(uniq[rank[i]], ovf_r[i])``, at a room of ``tiles`` hot tiles
    and ``vtiles`` virtual tiles each (data/crec.HotRoom sizes both).

    ``ovf_u`` is ``hot_buckets(uniq, tiles)``. ``ovf_pw`` is the kernels' pair-word array ``(tiles * vtiles, S//GS,
    GS * HOT_CAP)`` over ``hot_spec``: a pair's bucket digits are its
    rank in its hot tile (``rank % TILE``), its row digits the row's in
    its subblock, and its place is dealt in list order: pair ``k`` of
    the (subblock ``s``, hot tile ``h``) cell goes to virtual tile
    ``h * vtiles + k // HOT_CAP``, slot ``k % HOT_CAP`` of subblock
    ``s``. Virtual tiles ``h * vtiles ..`` all alias hot tile ``h``, so
    the form is packed to the room, not to the table's tiles."""
    spec = hot_spec(tiles * vtiles, subblocks)
    pw = np.full((tiles * vtiles, subblocks, HOT_CAP), PADWORD, np.uint32)
    if len(rank):
        sub = (ovf_r // RSUB).astype(np.int64)
        cell = sub * tiles + (rank >> 14)
        order = np.argsort(cell, kind="stable")
        starts = np.zeros(subblocks * tiles + 1, np.int64)
        np.cumsum(np.bincount(cell, minlength=subblocks * tiles),
                  out=starts[1:])
        k = np.empty(len(rank), np.int64)
        k[order] = np.arange(len(rank)) - starts[cell[order]]
        if k.max() >= vtiles * HOT_CAP:
            raise ValueError(f"a cell holds {k.max() + 1} pairs, the "
                             f"room {vtiles} x {HOT_CAP}")
        vt = (rank >> 14).astype(np.int64) * vtiles + k // HOT_CAP
        pw[vt, sub, k % HOT_CAP] = pack_fields(rank & 16383, ovf_r % RSUB)
    return hot_buckets(uniq, tiles), pw.reshape(spec.pairs_shape)


def decode_hot(ovf_u: np.ndarray, ovf_pw: np.ndarray, subblocks: int
               ) -> Tuple[np.ndarray, np.ndarray]:
    """``(ovf_b, ovf_r)`` of a hot form's pairs, in the form's order."""
    tiles = len(ovf_u) // TILE
    vtiles = ovf_pw.shape[0] // tiles
    pw = ovf_pw.reshape(tiles * vtiles, subblocks, -1)
    b, r, pad = unpack_fields(pw)
    vt, sub, _slot = np.nonzero(~pad)
    rank = (vt // vtiles) * TILE + b[~pad]
    return ovf_u[rank], (sub * RSUB + r[~pad]).astype(np.uint32)


# ---------------------------------------------------------------------------
# pallas kernels
# ---------------------------------------------------------------------------

def _oh_rep(rep: jax.Array, shift: int, mask: int, n: int,
            width: int) -> jax.Array:
    """(n, width) bf16 one-hot of a digit of the sublane-replicated packed
    word. The field is compared IN PLACE — ``rep & (mask<<shift)`` against
    a pre-shifted iota constant — which drops the per-site shift pass the
    old ``(rep>>shift)&mask`` form paid on the (n,1) word column (the
    round-5 floor model: the kernels are bound by exactly these
    vreg-level VPU passes, docs/perf.md)."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, width), 1) << shift
    return ((rep & (mask << shift)) == iota).astype(jnp.bfloat16)


def _digit_cond(rep: jax.Array, shift: int, mask: int, n: int,
                width: int) -> jax.Array:
    """(n, width) bool digit compare of the sublane-replicated packed
    word against a pre-shifted iota — the compare half of _mask_sel,
    split out so the fused grid's one-hot cache can stage the plane in
    phase 1 and replay it in phase 2 instead of rebuilding it."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, width), 1) << shift
    return (rep & (mask << shift)) == iota


def _sel_cond(cond: jax.Array, x: jax.Array) -> jax.Array:
    """The select half of _mask_sel: the f32->bf16 convert runs BEFORE
    the select so the select touches half the vregs."""
    return jnp.where(cond, x.astype(jnp.bfloat16), jnp.bfloat16(0))


def _mask_sel(rep: jax.Array, shift: int, mask: int,
              x: jax.Array) -> jax.Array:
    """x masked by a digit one-hot, as one in-place compare + a bf16
    select: the f32->bf16 convert runs BEFORE the select so the select
    touches half the vregs, and the field compares in place (no shift
    pass) — two fewer VPU passes per site than cmp/sel-f32/convert."""
    n, width = x.shape
    return _sel_cond(_digit_cond(rep, shift, mask, n, width), x)


def _ohT_vec(vec: jax.Array, shift: int, mask: int, width: int,
             n: int) -> jax.Array:
    """(width, n) bf16 one-hot of a digit; the word vector stays on lanes
    (no relayout) — the orientation the histogram lhs consumes."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (width, n), 0)
    return ((((vec >> shift) & mask)[None, :]) == iota).astype(jnp.bfloat16)


def _fwd_kernel(spec: TileSpec, pw_ref, w_ref, mg_ref, t=None):
    # The fused step kernel invokes this body inside a @pl.when phase
    # branch, where pl.program_id cannot be read (interpret mode leaves
    # the primitive unlowered inside cond) — it passes the grid index it
    # already read at its own top level.
    t = pl.program_id(0) if t is None else t

    @pl.when(t == 0)
    def _():
        mg_ref[:] = jnp.zeros_like(mg_ref)

    S, GS, C, N = spec.subblocks, spec.group, spec.cap, spec.n
    ones_pick = jnp.ones((B_LO, RL), jnp.bfloat16)
    # the value chain (gather -> pick -> row-lo spread) runs GROUP-wide:
    # one lanes->sublanes relayout and one long (N,128) matmul pair per
    # (group, tile) instead of GS short ones — measured 15% faster than
    # the per-subblock chain; only the histogram lhs (lanes-native, no
    # relayout) stays per-subblock, since each subblock owns its margin
    # grid. The bwd kernel keeps per-subblock md (each needs its own
    # dual grid; a group-wide chain there needs a concat that eats the
    # saving — measured neutral).
    for g in range(S // GS):
        mgs = [mg_ref[g * GS + j] for j in range(GS)]
        for tb in range(spec.tiles_step):
            # the float32 table tile, rounded to the kernel operand
            # here (round-to-nearest-even, as astype in XLA): no
            # bfloat16 copy of the table exists in HBM
            wt = w_ref[tb].astype(jnp.bfloat16)            # (128,128)
            pc = pw_ref[tb, g].astype(jnp.int32)           # (N,)
            rep = pc[:, None]                              # ONE relayout
            ohhi = _oh_rep(rep, HI_SH, HI_M, N, 128)       # pad -> 0 row
            # (bf16 matmul accumulators would skip the astype passes and
            # are exact for one-hot contractions, but Mosaic requires a
            # 32-bit acc — measured round 4, not supported on this MXU)
            m = jnp.dot(ohhi, wt, preferred_element_type=jnp.float32)
            # lane pick + broadcast via ones-matmul: (m masked to lane
            # lo_p) @ 1s == w_p replicated across RL lanes — the MXU does
            # the cross-lane reduction (VPU cross-lane sums relayout)
            wp = jnp.dot(_mask_sel(rep, LO_SH, LO_M, m), ones_pick,
                         preferred_element_type=jnp.float32)
            rhs = _mask_sel(rep, RLO_SH, RLO_M, wp)        # (N, RL)
            for j in range(GS):
                rhiT = _ohT_vec(pc[j * C:(j + 1) * C],
                                RHI_SH, RHI_M, RH, C)
                mgs[j] += jnp.dot(rhiT, rhs[j * C:(j + 1) * C],
                                  preferred_element_type=jnp.float32)
        for j in range(GS):
            mg_ref[g * GS + j] = mgs[j]


def _fwd_kernel_cached(spec: TileSpec, pw_ref, w_ref, mg_ref,
                       lo_c, rlo_c, t):
    """_fwd_kernel staging the one-hot cache as it computes: the lo/rlo
    digit compare planes it already builds per (group, tile) are
    written to full-tile-set VMEM scratch so phase 2 replays them
    instead of rebuilding (the round-5 floor model charges the residual
    VPU time to exactly these rebuilds, docs/perf.md round 8). The
    packed-word relayout is NOT staged: an (N, 1) i32 column occupies a
    full 128-lane vreg row in VMEM, 512 B a slot — as much as both
    planes together — and the chip's compiler refused the scratch for
    it (PERF.md, PR 23); phase 2 redoes that one relayout per chain.
    The compute is bitwise IDENTICAL to _fwd_kernel — the staged planes
    are the same booleans the uncached body folds into its selects.
    Only used from the fused step grid, which passes its own grid index
    ``t``."""
    @pl.when(t == 0)
    def _():
        mg_ref[:] = jnp.zeros_like(mg_ref)

    S, GS, C, N = spec.subblocks, spec.group, spec.cap, spec.n
    TB = spec.tiles_step
    ones_pick = jnp.ones((B_LO, RL), jnp.bfloat16)
    for g in range(S // GS):
        mgs = [mg_ref[g * GS + j] for j in range(GS)]
        for tb in range(TB):
            wt = w_ref[tb].astype(jnp.bfloat16)   # as in _fwd_kernel
            pc = pw_ref[tb, g].astype(jnp.int32)           # (N,)
            rep = pc[:, None]                              # ONE relayout
            cond_lo = _digit_cond(rep, LO_SH, LO_M, N, B_LO)
            cond_rlo = _digit_cond(rep, RLO_SH, RLO_M, N, RL)
            # stage at the GLOBAL tile index: phase 2's grid step nt+j
            # re-visits pairs block j, so nothing is evictable at the
            # phase boundary and the cache spans all T tiles (this is
            # what onehot_cache_bytes budgets against VMEM)
            lo_c[t * TB + tb, g] = cond_lo.astype(jnp.bfloat16)
            rlo_c[t * TB + tb, g] = cond_rlo.astype(jnp.bfloat16)
            ohhi = _oh_rep(rep, HI_SH, HI_M, N, 128)       # pad -> 0 row
            m = jnp.dot(ohhi, wt, preferred_element_type=jnp.float32)
            wp = jnp.dot(_sel_cond(cond_lo, m), ones_pick,
                         preferred_element_type=jnp.float32)
            rhs = _sel_cond(cond_rlo, wp)                  # (N, RL)
            for j in range(GS):
                rhiT = _ohT_vec(pc[j * C:(j + 1) * C],
                                RHI_SH, RHI_M, RH, C)
                mgs[j] += jnp.dot(rhiT, rhs[j * C:(j + 1) * C],
                                  preferred_element_type=jnp.float32)
        for j in range(GS):
            mg_ref[g * GS + j] = mgs[j]


def _bwd_kernel_cached(spec: TileSpec, pw_ref, dual_ref, g_ref,
                       lo_c, rlo_c, tj):
    """_bwd_kernel replaying the phase-1 one-hot cache: the lo/rlo
    compare planes load from VMEM instead of being rebuilt — the
    packed-word relayout, the joint subblock-parity digit (ohghi, a
    bwd-only layout) and the lanes-native histogram lhs (ohhiT, no
    relayout to save) are still built here. The staged bf16 0/1 planes
    recover the original booleans exactly (``!= 0``), so the selects —
    and therefore the emitted grads — stay bitwise-identical to the
    uncached body. ``tj`` is the phase-2 step index (t - nt)."""
    S, GS, C = spec.subblocks, spec.group, spec.cap
    TB = spec.tiles_step
    bp = _bp(spec)
    NC = bp * C
    ones_bcast = jnp.ones((RL, B_LO), jnp.bfloat16)
    offs = (jax.lax.broadcasted_iota(jnp.int32, (NC, 1), 0) // C) * RH
    iota_ghi_sh = ((jax.lax.broadcasted_iota(jnp.int32, (NC, bp * RH), 1)
                    - offs) << RHI_SH)
    for tb in range(TB):
        acc = jnp.zeros((A_HI, B_LO), jnp.float32)
        for g in range(S // GS):
            lo_g = lo_c[tj * TB + tb, g]                   # (N, 128) 0/1
            rlo_g = rlo_c[tj * TB + tb, g]                 # (N, 128) 0/1
            for h in range(GS // bp):
                sp = (g * GS) // bp + h
                sl = slice(h * NC, (h + 1) * NC)
                pc = pw_ref[tb, g, sl].astype(jnp.int32)
                rep = pc[:, None]                          # one relayout
                ohghi = ((rep & (RHI_M << RHI_SH))
                         == iota_ghi_sh).astype(jnp.bfloat16)
                md = jnp.dot(ohghi, dual_ref[sp],
                             preferred_element_type=jnp.float32)
                dp = jnp.dot(_sel_cond(rlo_g[sl] != 0, md), ones_bcast,
                             preferred_element_type=jnp.float32)
                rhs = _sel_cond(lo_g[sl] != 0, dp)         # (NC, 128)
                ohhiT = _ohT_vec(pc, HI_SH, HI_M, A_HI, NC)
                acc += jnp.dot(ohhiT, rhs,
                               preferred_element_type=jnp.float32)
        g_ref[tb] = acc


# ---------------------------------------------------------------------------
# fused-tile BWD kernel (high-nb regime: K adjacent tiles per chain)
# ---------------------------------------------------------------------------
#
# When cap floors at 128 (nb >= ~2^25 for criteo-shaped data), per-tile
# chains are only group*cap = 512 pairs long and per-chain fixed costs
# multiply into 3*tiles units. Fusing K adjacent tiles into ONE bwd
# chain (same pairs bytes, re-viewed (T/K, SG, K*N) by an XLA
# transpose) measured 13-20% faster at nb=2^26: the dual gather runs
# once per chain against the group's FULL dual grid (GS*RH deep, the
# joint digit from the in-place compare constant below) and the grad
# histogram runs once per tile. The same trick on FWD measured 5-18%
# SLOWER at both 2^24 and 2^26 (the K*128-wide block-diagonal one-hot
# build outweighs the chain savings; a joint-digit single-matmul
# histogram did not close the gap) — so fwd always runs the per-tile
# kernel and `fuse` only gates the bwd view.


@lru_cache(maxsize=None)
def _fused_ghi_const(K: int, N: int, C: int, GS: int) -> np.ndarray:
    """(K*N, GS*RH) i32: the bwd joint digit (rhi + RH*subblock-in-
    group, from the chain position's static (p %% N) // C), pre-shifted
    for the in-place field compare."""
    p = np.arange(K * N)[:, None]
    sb = (p % N) // C
    l = np.arange(GS * RH)[None, :]
    return ((l - RH * sb) << RHI_SH).astype(np.int32)


def _bwd_kernel_fused(spec: TileSpec, pw_ref, dual_ref, ghic_ref,
                      g_ref):
    """Fused bwd: the whole (group, K tiles) chain gathers duals in ONE
    matmul against the group's full dual grid (GS*RH = 256 deep; the
    joint digit is rhi + RH*subblock-in-group, from the chain position's
    static (p % N) // C), then the grad histogram splits back per
    (tile, subblock)."""
    S, GS, C, K = spec.subblocks, spec.group, spec.cap, spec.fuse
    N = spec.n
    KN = K * N
    ones_bcast = jnp.ones((RL, B_LO), jnp.bfloat16)
    ghi_const = ghic_ref[...]
    for ts in range(spec.tiles_step // K):
        accs = [jnp.zeros((A_HI, B_LO), jnp.float32) for _ in range(K)]
        for g in range(S // GS):
            pc = pw_ref[ts, g].astype(jnp.int32)           # (KN,)
            rep = pc[:, None]                              # one relayout
            ohghi = ((rep & (RHI_M << RHI_SH))
                     == ghi_const).astype(jnp.bfloat16)    # (KN, GS*RH)
            md = jnp.dot(ohghi, dual_ref[g],
                         preferred_element_type=jnp.float32)
            dp = jnp.dot(_mask_sel(rep, RLO_SH, RLO_M, md), ones_bcast,
                         preferred_element_type=jnp.float32)
            rhs = _mask_sel(rep, LO_SH, LO_M, dp)          # (KN, 128)
            for f in range(K):
                # whole-tile grad histogram: one matmul per tile (the
                # subblock split was pure matmul count)
                sl = slice(f * N, (f + 1) * N)
                ohhiT = _ohT_vec(pc[sl], HI_SH, HI_M, A_HI, N)
                accs[f] += jnp.dot(ohhiT, rhs[sl],
                                   preferred_element_type=jnp.float32)
        for f in range(K):
            g_ref[ts * K + f] = accs[f]


def _fused_pairs_view(pw, spec: TileSpec):
    """(T, SG, N) pairs -> (T/K, SG, K*N): K adjacent tiles' slices
    side by side in one chain (f-major). An XLA transpose; the crec2
    bytes are untouched."""
    T, K = spec.tiles, spec.fuse
    SG, N = spec.subblocks // spec.group, spec.n
    return (pw.reshape(T // K, K, SG, N).transpose(0, 2, 1, 3)
            .reshape(T // K, SG, K * N))


BP = 2  # subblocks per bwd value chain: BP * RH = 128, one full-K pass


def _bp(spec: TileSpec) -> int:
    """Subblocks fused per bwd value chain (BP when the group allows)."""
    return BP if spec.group % BP == 0 else 1


def _bwd_kernel(spec: TileSpec, pw_ref, dual_ref, g_ref):
    """dual_ref arrives pre-reshaped (S//bp, bp*RH, RL): the value chain
    runs over bp=2 subblocks at once — the dual-grid pick contracts a
    128-deep joint digit ghi = rhi + RH*(subblock parity), so every
    matmul is full-K, 128 lanes, and 2C rows long (the same long-chain
    layout that made fwd fast; per-subblock chains measured slower,
    round 4). Only the grad histogram splits back per subblock (each
    needs its own ohhiT lhs)."""
    S, GS, C = spec.subblocks, spec.group, spec.cap
    bp = _bp(spec)
    NC = bp * C
    ones_bcast = jnp.ones((RL, B_LO), jnp.bfloat16)
    # chain-local subblock offset of each pair (static)
    # joint subblock-parity digit compared IN PLACE: the chain-local
    # offset folds into the shifted iota constant (rows where
    # iota - offs < 0 go negative and match no masked field)
    offs = (jax.lax.broadcasted_iota(jnp.int32, (NC, 1), 0) // C) * RH
    iota_ghi_sh = ((jax.lax.broadcasted_iota(jnp.int32, (NC, bp * RH), 1)
                    - offs) << RHI_SH)
    for tb in range(spec.tiles_step):
        acc = jnp.zeros((A_HI, B_LO), jnp.float32)
        for g in range(S // GS):
            for h in range(GS // bp):
                sp = (g * GS) // bp + h
                pc = pw_ref[tb, g, h * NC:(h + 1) * NC].astype(jnp.int32)
                rep = pc[:, None]                          # one relayout
                ohghi = ((rep & (RHI_M << RHI_SH))
                         == iota_ghi_sh).astype(jnp.bfloat16)
                md = jnp.dot(ohghi, dual_ref[sp],
                             preferred_element_type=jnp.float32)
                dp = jnp.dot(_mask_sel(rep, RLO_SH, RLO_M, md), ones_bcast,
                             preferred_element_type=jnp.float32)
                rhs = _mask_sel(rep, LO_SH, LO_M, dp)      # (NC, 128)
                # grad histogram over the WHOLE chain in one matmul:
                # the per-tile sum doesn't care which subblock a pair
                # came from, so the per-subblock split was pure matmul
                # count (same flops, same one-hot elems, bp x fewer
                # issues — round-5: tiny-matmul issue count is what
                # dominates at high tile counts)
                ohhiT = _ohT_vec(pc, HI_SH, HI_M, A_HI, NC)
                acc += jnp.dot(ohhiT, rhs,
                               preferred_element_type=jnp.float32)
        g_ref[tb] = acc


@lru_cache(maxsize=None)
def _build_fwd(spec: TileSpec):
    # fwd ignores spec.fuse: per-tile chains measured faster in every
    # fused-fwd A/B (see the fused section comment)
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks

    @jax.jit
    def fwd(pw, w):
        wt = w.reshape(T, A_HI, B_LO)      # rounded tile by tile in-kernel
        mg = pl.pallas_call(
            partial(_fwd_kernel, spec),
            grid=(T // TB,),
            in_specs=[
                pl.BlockSpec((TB, SG, N), lambda t: (t, 0, 0)),
                pl.BlockSpec((TB, A_HI, B_LO), lambda t: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((S, RH, RL), jnp.float32),
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(pw, wt)
        return mg.reshape(spec.block_rows)

    return fwd


@lru_cache(maxsize=None)
def _build_bwd(spec: TileSpec):
    T, TB, K = spec.tiles, spec.tiles_step, spec.fuse
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks
    GS = spec.group

    if K > 1:
        @jax.jit
        def bwd(pw, dual_rows):
            dg = (dual_rows.reshape(S // GS, GS * RH, RL)
                  .astype(jnp.bfloat16))
            pw_k = _fused_pairs_view(pw, spec)
            ghic = jnp.asarray(_fused_ghi_const(K, N, spec.cap, GS))
            g = pl.pallas_call(
                partial(_bwd_kernel_fused, spec),
                grid=(T // TB,),
                in_specs=[
                    pl.BlockSpec((TB // K, SG, K * N),
                                 lambda t: (t, 0, 0)),
                    pl.BlockSpec((S // GS, GS * RH, RL),
                                 lambda t: (0, 0, 0)),
                    pl.BlockSpec((K * N, GS * RH),
                                 lambda t: (0, 0)),
                ],
                out_specs=pl.BlockSpec((TB, A_HI, B_LO),
                                       lambda t: (t, 0, 0)),
                out_shape=jax.ShapeDtypeStruct((T, A_HI, B_LO),
                                               jnp.float32),
                compiler_params=None if _interpret()
                else pltpu.CompilerParams(
                    vmem_limit_bytes=100 * 1024 * 1024),
                interpret=_interpret(),
            )(pw_k, dg, ghic)
            return g.reshape(spec.nb)

        return bwd

    bp = _bp(spec)

    @jax.jit
    def bwd(pw, dual_rows):
        dg = dual_rows.reshape(S // bp, bp * RH, RL).astype(jnp.bfloat16)
        g = pl.pallas_call(
            partial(_bwd_kernel, spec),
            grid=(T // TB,),
            in_specs=[
                pl.BlockSpec((TB, SG, N), lambda t: (t, 0, 0)),
                pl.BlockSpec((S // bp, bp * RH, RL), lambda t: (0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((TB, A_HI, B_LO), lambda t: (t, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((T, A_HI, B_LO), jnp.float32),
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(pw, dg)
        return g.reshape(spec.nb)

    return bwd


# ---------------------------------------------------------------------------
# multi-channel kernels (FM / wide&deep embedding pulls and pushes)
# ---------------------------------------------------------------------------
#
# The embedding-table generalization of the scalar kernels: CH per-bucket
# values instead of one. Forward returns per-row SUMS over the row's pairs
# for every channel (the pooled embedding Σ_p v[b_p, :] — FM's interaction
# state and wide&deep's MLP input come from exactly this); backward
# scatters per-(row,channel) values into per-(bucket,channel) sums.
#
# Channels ride contiguous 128-lane slices (channel-major: lane block j
# holds channel j), and everything that CAN contract all channels at once
# does (round-5 batching; round 4 ran a full per-channel chain and
# measured ch x the scalar step):
#
#   * gather:   ONE (N,128) @ (128, ch*128) matmul — the one-hot lhs is
#     shared, so ch gathers are one long-lane matmul (same flops, one
#     issue);
#   * histogram: the transposed one-hot lhs is channel-independent, so
#     each subblock's ch histograms are ONE (RH, C) @ (C, ch*RL) matmul;
#   * masks: applied once across all ch*128 lanes (iota % 128 compare) —
#     same element count, ch x fewer VPU issues.
#
# Only the lane pick (the cross-lane reduce) is irreducibly per-channel:
# a single matmul over all channels would need a block-diagonal rhs and
# ch x the flops. Per-channel cost is therefore ONE (N,128)@(128,RL)
# matmul plus 1/ch of every shared op.


def _wide_cond(rep: jax.Array, shift: int, mask: int, n: int,
               lanes: int, width: int) -> jax.Array:
    """(n, lanes) digit compare replicated across lane blocks of
    ``width`` (iota % width) — one compare covering every channel."""
    iota = jax.lax.broadcasted_iota(jnp.int32, (n, lanes), 1)
    return (rep & (mask << shift)) == ((iota % width) << shift)


def _mask_where(cond: jax.Array, x: jax.Array) -> jax.Array:
    """where(cond, x, 0) in bf16 — the digit compare is hoisted and
    shared across channels (cond built once per (group, tile))."""
    return jnp.where(cond, x, jnp.float32(0)).astype(jnp.bfloat16)


def _fwd_multi_kernel(spec: TileSpec, ch: int, pw_ref, w_ref, mg_ref,
                      t=None):
    t = pl.program_id(0) if t is None else t

    @pl.when(t == 0)
    def _():
        mg_ref[:] = jnp.zeros_like(mg_ref)

    S, GS, C, N = spec.subblocks, spec.group, spec.cap, spec.n
    ones_pick = jnp.ones((B_LO, RL), jnp.bfloat16)
    for g in range(S // GS):
        mgs = [mg_ref[g * GS + j] for j in range(GS)]      # (RH, ch*RL)
        for tb in range(spec.tiles_step):
            pc = pw_ref[tb, g].astype(jnp.int32)           # (N,)
            rep = pc[:, None]                              # ONE relayout
            ohhi = _oh_rep(rep, HI_SH, HI_M, N, 128)       # pad -> 0 row
            cond_lo = _wide_cond(rep, LO_SH, LO_M, N, ch * 128, 128)
            cond_rlo = _wide_cond(rep, RLO_SH, RLO_M, N, ch * RL, RL)
            rhiTs = [_ohT_vec(pc[j * C:(j + 1) * C], RHI_SH, RHI_M,
                              RH, C) for j in range(GS)]
            # batched gather: every channel in one long-lane matmul
            m_all = jnp.dot(ohhi, w_ref[tb],
                            preferred_element_type=jnp.float32)
            masked = _mask_where(cond_lo, m_all)           # (N, ch*128)
            # lane pick per channel (the irreducible part), re-joined on
            # lanes so the spread mask and histogram run channel-wide
            wp_all = jnp.concatenate(
                [jnp.dot(masked[:, jc * 128:(jc + 1) * 128], ones_pick,
                         preferred_element_type=jnp.float32)
                 for jc in range(ch)], axis=1)             # (N, ch*RL)
            rhs = _mask_where(cond_rlo, wp_all)
            for j in range(GS):
                mgs[j] += jnp.dot(rhiTs[j], rhs[j * C:(j + 1) * C],
                                  preferred_element_type=jnp.float32)
        for j in range(GS):
            mg_ref[g * GS + j] = mgs[j]


def _bwd_multi_kernel(spec: TileSpec, ch: int, pw_ref, dual_ref, g_ref):
    """dual_ref (S//bp, bp*RH, ch*RL): per-channel row grids on
    contiguous lane blocks; same paired-subblock value chain as the
    scalar bwd kernel, digit work hoisted out of the channel loop and
    the dual gather + grad histogram contracted channel-wide."""
    S, GS, C = spec.subblocks, spec.group, spec.cap
    bp = _bp(spec)
    NC = bp * C
    ones_bcast = jnp.ones((RL, B_LO), jnp.bfloat16)
    # joint subblock-parity digit compared IN PLACE: the chain-local
    # offset folds into the shifted iota constant (rows where
    # iota - offs < 0 go negative and match no masked field)
    offs = (jax.lax.broadcasted_iota(jnp.int32, (NC, 1), 0) // C) * RH
    iota_ghi_sh = ((jax.lax.broadcasted_iota(jnp.int32, (NC, bp * RH), 1)
                    - offs) << RHI_SH)
    for tb in range(spec.tiles_step):
        acc = jnp.zeros((A_HI, ch * B_LO), jnp.float32)
        for g in range(S // GS):
            for h in range(GS // bp):
                sp = (g * GS) // bp + h
                pc = pw_ref[tb, g, h * NC:(h + 1) * NC].astype(jnp.int32)
                rep = pc[:, None]                          # one relayout
                ohghi = ((rep & (RHI_M << RHI_SH))
                         == iota_ghi_sh).astype(jnp.bfloat16)
                cond_rlo = _wide_cond(rep, RLO_SH, RLO_M, NC,
                                      ch * RL, RL)
                cond_lo = _wide_cond(rep, LO_SH, LO_M, NC, ch * 128, 128)
                # batched dual gather: all channels in one matmul
                md_all = jnp.dot(ohghi, dual_ref[sp],
                                 preferred_element_type=jnp.float32)
                masked = _mask_where(cond_rlo, md_all)     # (NC, ch*RL)
                dp_all = jnp.concatenate(
                    [jnp.dot(masked[:, jc * RL:(jc + 1) * RL], ones_bcast,
                             preferred_element_type=jnp.float32)
                     for jc in range(ch)], axis=1)         # (NC, ch*128)
                rhs = _mask_where(cond_lo, dp_all)
                # whole-chain grad histogram (subblock split was pure
                # matmul count; see the scalar bwd kernel)
                ohhiT = _ohT_vec(pc, HI_SH, HI_M, A_HI, NC)
                acc += jnp.dot(ohhiT, rhs,
                               preferred_element_type=jnp.float32)
        g_ref[tb] = acc


MULTI_BUDGET = 128  # tiles_step * (ch + 6) of one multi-channel call


def _multi_spec(spec: TileSpec, ch: int) -> TileSpec:
    """Shrink tiles_step so the unrolled kernel body stays near the ch=1
    compile budget. The round-5 batched kernels carry ~(2 + GS + ch)
    matmuls per (group, tile) vs the old ~(2 + GS) * ch, so the budget is
    on tiles_step * (ch + 6) rather than tiles_step * ch * 6 — tb=8 at
    ch=10 compiles in the tb=16 scalar envelope (measured round 5);
    tiles_step=16 at ch=10 with the OLD kernels measured >10 min."""
    import dataclasses
    tb = max((t for t in (16, 8, 4, 2)
              if spec.tiles % t == 0 and t * (ch + 6) <= MULTI_BUDGET),
             default=1)
    # a spec made for fewer tiles a step keeps them (make_spec never
    # is: its own tiles_step is the largest divisor, so this changes
    # none of its callers): the hot tile's pair, whose lowering time is
    # set-up on every start, asks for two
    tb = min(tb, spec.tiles_step)
    # fuse=1: the multi-channel kernels keep per-tile chains (their
    # channel batching already amortizes the per-chain fixed cost)
    return dataclasses.replace(spec, tiles_step=tb, fuse=1)


@lru_cache(maxsize=None)
def _build_fwd_multi(spec: TileSpec, ch: int, tiled: bool = False):
    """``tiled``: ``w`` is the kernel's own operand already, the
    bfloat16 (T, A_HI, ch*B_LO) tiles (a table kept as channel planes,
    learners/table.py, forms it without a transpose)."""
    spec = _multi_spec(spec, ch)
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks

    @jax.jit
    def fwd(pw, w):
        # (nb, ch) -> (T, A_HI, ch*B_LO): channel-major contiguous lanes
        wt = w if tiled else (
            w.reshape(T, A_HI, B_LO, ch).transpose(0, 1, 3, 2)
            .reshape(T, A_HI, ch * B_LO).astype(jnp.bfloat16))
        mg = pl.pallas_call(
            partial(_fwd_multi_kernel, spec, ch),
            grid=(T // TB,),
            in_specs=[
                pl.BlockSpec((TB, SG, N), lambda t: (t, 0, 0)),
                pl.BlockSpec((TB, A_HI, ch * B_LO), lambda t: (t, 0, 0)),
            ],
            out_specs=pl.BlockSpec((S, RH, ch * RL), lambda t: (0, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((S, RH, ch * RL), jnp.float32),
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(pw, wt)
        # (S, RH, ch*RL) channel-major lanes -> (rows, ch)
        return (mg.reshape(S, RH, ch, RL).transpose(0, 1, 3, 2)
                .reshape(spec.block_rows, ch))

    return fwd


@lru_cache(maxsize=None)
def _build_bwd_multi(spec: TileSpec, ch: int, tiled: bool = False):
    """``tiled``: the pushes stay as the kernel wrote them, float32
    (T, A_HI, ch*B_LO) tiles (a channel is a lane slice)."""
    spec = _multi_spec(spec, ch)
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks
    bp = _bp(spec)

    @jax.jit
    def bwd(pw, dual_rows):
        # (rows, ch) -> (S//bp, bp*RH, ch*RL): channel-major lane blocks
        dg = (dual_rows.reshape(S // bp, bp * RH, RL, ch)
              .transpose(0, 1, 3, 2).reshape(S // bp, bp * RH, ch * RL)
              .astype(jnp.bfloat16))
        g = pl.pallas_call(
            partial(_bwd_multi_kernel, spec, ch),
            grid=(T // TB,),
            in_specs=[
                pl.BlockSpec((TB, SG, N), lambda t: (t, 0, 0)),
                pl.BlockSpec((S // bp, bp * RH, ch * RL),
                             lambda t: (0, 0, 0)),
            ],
            out_specs=pl.BlockSpec((TB, A_HI, ch * B_LO),
                                   lambda t: (t, 0, 0)),
            out_shape=jax.ShapeDtypeStruct((T, A_HI, ch * B_LO),
                                           jnp.float32),
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(pw, dg)
        if tiled:
            return g
        # (T, A_HI, ch*B_LO) channel-major lanes -> (nb, ch)
        return (g.reshape(T, A_HI, ch, B_LO).transpose(0, 1, 3, 2)
                .reshape(spec.nb, ch))

    return bwd


# -- COO spill helpers -------------------------------------------------------
#
# The list as (bucket, row) pairs, a gather and a scatter slot a pair:
# what a short list takes, and one whose buckets are mostly distinct
# (data/crec.HotRoom's rule), every list in eval, on a mesh and over a
# stacked multi-channel table. A long list of few buckets comes through
# the hot tile helpers further down (FTRL's one channel), through
# fm_hot_pull_rows / hot_push_scatter_planes (FM's k + 2) and through
# plane_hot_pull_rows / hot_push_scatter_lanes (wide&deep's 1 + k and
# k + 2).
#
# One shared aggregation for both step formulations: the spill pairs are
# pre-aggregated into a zero row grid, and the kernel margins/pulls get
# ONE elementwise add of that grid — in XLA on the split path, at the
# phase boundary (as an operand) on the fused path. Pre-aggregating is
# what makes the fused path possible at all (the boundary phase cannot
# run a scatter), and doing it on BOTH paths keeps them bitwise-equal
# even when several spills share a row. The grad-side scatters need the
# grad/push in HBM, so they stay in XLA on every path — the fused
# callers recompute the dual from the emitted margins (elementwise,
# bitwise-equal) and land in the same shared helper.

def spill_margin_rows(w: jax.Array, ovf_b: jax.Array, ovf_r: jax.Array,
                      spec: TileSpec) -> jax.Array:
    """(block_rows,) f32 pre-aggregated spill margins: each valid COO
    pair's w lands on its row (``UNUSED`` slots add 0)."""
    valid = ovf_b != UNUSED
    wv = jnp.where(valid, w[jnp.where(valid, ovf_b, 0).astype(jnp.int32)],
                   0.0)
    return jnp.zeros(spec.block_rows, w.dtype).at[
        ovf_r.astype(jnp.int32) % spec.block_rows].add(wv)


def spill_pull_rows(w: jax.Array, ovf_b: jax.Array, ovf_r: jax.Array,
                    spec: TileSpec) -> jax.Array:
    """(block_rows, ch) multi-channel variant of spill_margin_rows."""
    valid = ovf_b != UNUSED
    idx = jnp.where(valid, ovf_b, 0).astype(jnp.int32)
    wv = jnp.where(valid[:, None], w[idx], 0.0)
    return jnp.zeros((spec.block_rows, w.shape[1]), w.dtype).at[
        ovf_r.astype(jnp.int32) % spec.block_rows].add(wv)


def spill_grad_scatter(g: jax.Array, dual_rows: jax.Array,
                       ovf_b: jax.Array, ovf_r: jax.Array,
                       spec: TileSpec) -> jax.Array:
    """Scatter each spill pair's dual into the (nb,) gradient — the
    grad-side COO tail shared by backward_grad and the fused spill
    branch."""
    valid = ovf_b != UNUSED
    d = jnp.where(valid,
                  dual_rows[ovf_r.astype(jnp.int32) % spec.block_rows],
                  0.0)
    return g.at[jnp.where(valid, ovf_b, 0).astype(jnp.int32)].add(d)


def spill_push_scatter(g: jax.Array, dual_rows: jax.Array,
                       ovf_b: jax.Array, ovf_r: jax.Array,
                       spec: TileSpec) -> jax.Array:
    """(nb, ch) variant of spill_grad_scatter (backward_pushes' tail;
    the FM steps on planes use spill_push_scatter_planes)."""
    valid = ovf_b != UNUSED
    d = jnp.where(valid[:, None],
                  dual_rows[ovf_r.astype(jnp.int32) % spec.block_rows],
                  0.0)
    return g.at[jnp.where(valid, ovf_b, 0).astype(jnp.int32)].add(d)


# -- the hot tile helpers ----------------------------------------------------
#
# The same two sums as spill_margin_rows / spill_grad_scatter, from the
# list's hot form (encode_hot): the distinct buckets' values are gathered
# ONCE (16K slots a hot tile, where the COO helpers gather and scatter a
# slot a pair), and the pairs run through the multi-channel kernel pair
# above: _hot_pull (float32 hot tiles in, row sums out) and _hot_push
# (float32 dual rows in, hot tiles out), for any number of channels. The
# stated precision holds by construction: a float32 value is
# split into three float32 parts that are each a bfloat16 value (split3),
# so every cast to bfloat16 inside those kernels is the identity, every
# one-hot matmul picks exactly one value, and the only sums are the row
# histogram's and the bucket histogram's, in the MXU's float32
# accumulator. Where a row (a bucket) has one listed pair the result is
# the COO helpers' to the bit; where it has many, to the order of the
# float32 additions.

def split3(x: jax.Array) -> Tuple[jax.Array, jax.Array, jax.Array]:
    """``(hi, mid, lo)`` float32 with ``(hi + mid) + lo == x`` to the bit
    and each part a bfloat16 value: 8 + 8 + 8 of a float32's 24
    significant bits, cut by masking (a cast that rounds would take the
    largest normals to infinity, and XLA may drop a cast pair). Exact for
    every finite ``x`` whose last bit is a normal float32 (``|x| >=
    2**-103``, and 0, ``-0.0`` coming back as ``+0.0``); below that the
    chip flushes the subnormal parts to zero and the sum is off by less
    than ``2**-126``."""
    top = jnp.uint32(0xFFFF0000)
    u32, f32 = jnp.uint32, jnp.float32
    bits = jax.lax.bitcast_convert_type
    hi = bits(bits(x, u32) & top, f32)
    r = x - hi
    mid = bits(bits(r, u32) & top, f32)
    return hi, mid, r - mid


def _hot_dims(ovf_u: jax.Array, ovf_pw: jax.Array, spec: TileSpec):
    """``(tiles, vtiles, hot spec, valid, index)`` of a hot form."""
    tiles = ovf_u.shape[0] // TILE
    valid = ovf_u != UNUSED
    return (tiles, ovf_pw.shape[0] // tiles,
            hot_spec(ovf_pw.shape[0], spec.subblocks), valid,
            jnp.where(valid, ovf_u, 0).astype(jnp.int32))


def _hot_calls(hs: TileSpec, c: int) -> int:
    """The calls of a hot kernel that ``c`` float32 channels go through:
    ONE with all ``3c`` parts where the multi-channel pair admits that
    many at the hot spec's tiles a step (``_multi_spec``'s budget: FTRL's
    3 parts, FM's 30), else one a part, ``c`` channels each (wide&deep's
    99 and 102: three calls at 33 and 34 channels, what the store's main
    pair runs; one call's resident row grid alone would be 39 MB)."""
    one = hs.tiles_step * (HOT_CH * c + 6) <= MULTI_BUDGET
    return 1 if one else HOT_CH


def _hot_pull(values, ovf_pw: jax.Array, vtiles: int,
              hs: TileSpec) -> jax.Array:
    """``(block_rows, c)`` row sums of the hot form's pairs over ``c``
    float32 channels, each a ``(tiles, A_HI, B_LO)`` hot tile: the
    ``3c`` parts ride part-major on the lanes (every channel's hi, then
    every mid, then every lo), every virtual tile a copy of its hot
    tile, and a channel's three sums add as ``(hi + mid) + lo``. Where
    one call does not admit ``3c`` parts (``_hot_calls``) the cut falls
    between the parts: a call a part, three calls in the program (they
    compile side by side; as one traced body, a ``lax.map``, the trace's
    op line would hold the ``while`` beside the calls inside it, and
    every reader that sums a step's ops would count them twice)."""
    c = len(values)
    parts = zip(*(split3(v) for v in values))
    if _hot_calls(hs, c) == 1:
        wt = jnp.repeat(jax.lax.concatenate(
            [x.astype(jnp.bfloat16) for part in parts for x in part], 2),
            vtiles, axis=0)
        p = _build_fwd_multi(hs, HOT_CH * c, True)(ovf_pw, wt)
        p = p.reshape(-1, HOT_CH, c)
        return (p[:, 0] + p[:, 1]) + p[:, 2]
    fwd = _build_fwd_multi(hs, c, True)
    hi, mid, lo = (fwd(ovf_pw, jnp.repeat(jax.lax.concatenate(
        [x.astype(jnp.bfloat16) for x in part], 2), vtiles, axis=0))
        for part in parts)
    return (hi + mid) + lo


def _hot_push(dual_rows: jax.Array, ovf_pw: jax.Array, tiles: int,
              vtiles: int, hs: TileSpec) -> jax.Array:
    """``(tiles, A_HI, c, B_LO)`` hot tiles of the pairs' ``(block_rows,
    c)`` float32 duals: the push kernel sums each of the ``3c`` parts a
    virtual tile, a channel's three add as ``(hi + mid) + lo``, and a
    hot tile's virtual tiles are summed (a call a part where one does not
    admit ``3c``, ``_hot_calls``: a part's virtual tiles are summed
    first there, and the three sums added)."""
    c = dual_rows.shape[1]
    if _hot_calls(hs, c) == 1:
        push = _build_bwd_multi(hs, HOT_CH * c, True)(
            ovf_pw, jnp.concatenate(split3(dual_rows), axis=1))
        p = push.reshape(tiles, vtiles, A_HI, HOT_CH, c, B_LO)
        return ((p[:, :, :, 0] + p[:, :, :, 1]) + p[:, :, :, 2]).sum(axis=1)
    bwd = _build_bwd_multi(hs, c, True)
    hi, mid, lo = (bwd(ovf_pw, part).reshape(
        tiles, vtiles, A_HI, c, B_LO).sum(axis=1)
        for part in split3(dual_rows))
    return (hi + mid) + lo


def hot_margin_rows(w: jax.Array, ovf_u: jax.Array, ovf_pw: jax.Array,
                    spec: TileSpec) -> jax.Array:
    """spill_margin_rows from the list's hot form: ``w[ovf_u]`` is the
    hot tile, its three parts the kernel's operand."""
    tiles, vtiles, hs, valid, idx = _hot_dims(ovf_u, ovf_pw, spec)
    wu = jnp.where(valid, w[idx], 0.0).reshape(tiles, A_HI, B_LO)
    return _hot_pull([wu], ovf_pw, vtiles, hs)[:, 0]


def hot_grad_scatter(g: jax.Array, dual_rows: jax.Array, ovf_u: jax.Array,
                     ovf_pw: jax.Array, spec: TileSpec) -> jax.Array:
    """spill_grad_scatter from the list's hot form: the pairs' duals
    summed to the hot tile, and the hot tile added at its buckets."""
    tiles, vtiles, hs, valid, idx = _hot_dims(ovf_u, ovf_pw, spec)
    gu = _hot_push(dual_rows[:, None], ovf_pw, tiles, vtiles, hs)
    # scatter-fallback: ONE scatter of tiles * TILE slots (16,384 a hot
    # tile) where the COO helper scatters a slot a pair; unused slots
    # add 0 at bucket 0
    return g.at[idx].add(jnp.where(valid, gu.reshape(-1), 0.0))


def forward_pulls(pw: jax.Array, w: jax.Array, spec: TileSpec,
                  ovf_b: Optional[jax.Array] = None,
                  ovf_r: Optional[jax.Array] = None) -> jax.Array:
    """(block_rows, ch) per-row sums of w[bucket, :] over each row's
    pairs — the pooled-embedding pull. w is (nb, ch) f32 (values round
    through bf16 inside the kernel, like the scalar path)."""
    ch = w.shape[1]
    pulls = _build_fwd_multi(spec, ch)(pw, w)
    if ovf_b is not None and ovf_b.shape[0]:
        pulls = pulls + spill_pull_rows(w, ovf_b, ovf_r, spec)
    return pulls


def backward_pushes(pw: jax.Array, dual_rows: jax.Array, spec: TileSpec,
                    ovf_b: Optional[jax.Array] = None,
                    ovf_r: Optional[jax.Array] = None) -> jax.Array:
    """(nb, ch) per-bucket sums of dual_rows[row, :] over the bucket's
    pairs — the embedding-gradient push."""
    ch = dual_rows.shape[1]
    g = _build_bwd_multi(spec, ch)(pw, dual_rows)
    if ovf_b is not None and ovf_b.shape[0]:
        g = spill_push_scatter(g, dual_rows, ovf_b, ovf_r, spec)
    return g


# -- the multi-channel path over a table kept as channel planes --------------
#
# A store that keeps one float32 (T, A_HI, B_LO) plane a channel
# (learners/table.py) never forms the (nb, ch) array: the kernels' operand
# is the planes' tiles side by side on the lanes, rounded (fm_operand, in
# VMEM inside the fused step, one XLA op before the split pair), and a push
# channel is a plane as the kernel wrote it.

def fm_pull_channels(w, vs, one):
    """FM's pull channels ``[w, v_1..v_k, Σ_j v_j²]`` of float32 ``w``
    and ``vs`` of one shape (tiles in a kernel, planes or gathered
    values in XLA). The sum runs over the unrounded factors in float32,
    in order, every product ``*one``-guarded (loss.opaque_one) so that
    it has the same bits in every context."""
    q = (vs[0] * vs[0]) * one
    for v in vs[1:]:
        q = q + (v * v) * one
    return [w, *vs, q]


def fm_operand(w, vs, one) -> jax.Array:
    """The FM kernels' bfloat16 operand of (..., A_HI, B_LO) float32
    tiles: the pull channels rounded, channel-major on the lanes."""
    return jnp.concatenate(
        [c.astype(jnp.bfloat16) for c in fm_pull_channels(w, vs, one)],
        axis=-1)


def plane_pulls(pw: jax.Array, wt: jax.Array, spec: TileSpec) -> jax.Array:
    """forward_pulls from the operand as the kernel takes it, bfloat16
    (T, A_HI, ch*B_LO)."""
    return _build_fwd_multi(spec, wt.shape[-1] // B_LO, True)(pw, wt)


def plane_pushes(pw: jax.Array, dual_rows: jax.Array,
                 spec: TileSpec) -> tuple:
    """backward_pushes as one (T, A_HI, B_LO) plane a channel."""
    ch = dual_rows.shape[1]
    g = _build_bwd_multi(spec, ch, True)(pw, dual_rows)
    return tuple(g[..., c * B_LO:(c + 1) * B_LO] for c in range(ch))


def fm_spill_pull_rows(planes, ovf_b: jax.Array, ovf_r: jax.Array,
                       spec: TileSpec, one) -> jax.Array:
    """spill_pull_rows from the w and v planes: the listed buckets'
    values are gathered plane by plane and their pull channels formed
    from those (float32, unrounded, as the stacked path pulls them)."""
    valid = ovf_b != UNUSED
    idx = jnp.where(valid, ovf_b, 0).astype(jnp.int32)
    got = [p.reshape(-1)[idx] for p in planes]
    wv = jnp.where(valid[:, None],
                   jnp.stack(fm_pull_channels(got[0], got[1:], one), axis=1),
                   0.0)
    return jnp.zeros((spec.block_rows, wv.shape[1]), jnp.float32).at[
        ovf_r.astype(jnp.int32) % spec.block_rows].add(wv)


def spill_push_scatter_planes(push, dual_rows: jax.Array, ovf_b: jax.Array,
                              ovf_r: jax.Array, spec: TileSpec) -> tuple:
    """spill_push_scatter into push planes, a channel at a time (a
    slot's row is its row of ``dual_rows``)."""
    valid = ovf_b != UNUSED
    idx = jnp.where(valid, ovf_b, 0).astype(jnp.int32)
    d = jnp.where(valid[:, None],
                  dual_rows[ovf_r.astype(jnp.int32) % dual_rows.shape[0]],
                  0.0)
    return tuple(p.reshape(-1).at[idx].add(d[:, c]).reshape(p.shape)
                 for c, p in enumerate(push))


def _hot_values(planes, ovf_u: jax.Array, ovf_pw: jax.Array,
                spec: TileSpec):
    """``(values, vtiles, hot spec)``: every plane read once a distinct
    bucket of the hot form (``tiles * TILE`` slots, the unused 0.0), as
    ``(tiles, A_HI, B_LO)`` hot tiles."""
    tiles, vtiles, hs, valid, idx = _hot_dims(ovf_u, ovf_pw, spec)
    got = [jnp.where(valid, p.reshape(-1)[idx], 0.0)
           .reshape(tiles, A_HI, B_LO) for p in planes]
    return got, vtiles, hs


def fm_hot_pull_rows(planes, ovf_u: jax.Array, ovf_pw: jax.Array,
                     spec: TileSpec, one) -> jax.Array:
    """fm_spill_pull_rows from the list's hot form: the w and v planes
    are read once a distinct bucket (``tiles * TILE`` slots a plane),
    the pull channels formed from those in float32 (Σv² from the
    unrounded factors, as the COO helper forms it a pair), and every
    channel runs through the hot tile as three parts: the float32
    values to the bit, a row's pairs summed in the MXU's accumulator."""
    got, vtiles, hs = _hot_values(planes, ovf_u, ovf_pw, spec)
    return _hot_pull(fm_pull_channels(got[0], got[1:], one), ovf_pw,
                     vtiles, hs)


def hot_push_scatter_planes(push, dual_rows: jax.Array, ovf_u: jax.Array,
                            ovf_pw: jax.Array, spec: TileSpec) -> tuple:
    """spill_push_scatter_planes from the list's hot form: a bucket's
    duals are summed a channel in the hot tile, and each channel's hot
    tile is added at its buckets into its push plane."""
    tiles, vtiles, hs, valid, idx = _hot_dims(ovf_u, ovf_pw, spec)
    gu = _hot_push(dual_rows, ovf_pw, tiles, vtiles, hs)
    # scatter-fallback: a scatter of tiles * TILE slots a channel where
    # the COO helper scatters a slot a pair; unused slots add 0 at
    # bucket 0
    return tuple(p.reshape(-1).at[idx].add(
        jnp.where(valid, gu[:, :, c].reshape(-1), 0.0)).reshape(p.shape)
        for c, p in enumerate(push))


def plane_operand(planes) -> jax.Array:
    """The multi-channel kernels' bfloat16 operand of float32
    (T, A_HI, B_LO) planes that are the pull channels as they stand (a
    table whose every pulled value is a stored one: wide&deep's w and
    v): each rounded once, channel-major on the lanes. One concatenate
    (jnp.concatenate nests them sixteen at a time)."""
    return jax.lax.concatenate([p.astype(jnp.bfloat16) for p in planes],
                               planes[0].ndim - 1)


def plane_spill_pull_rows(planes, ovf_b: jax.Array, ovf_r: jax.Array,
                          spec: TileSpec, distinct=None) -> jax.Array:
    """spill_pull_rows from channel planes: the listed buckets' values
    are gathered plane by plane (float32, unrounded, as the stacked
    path pulls them) and summed onto their rows. ``distinct``:
    ``(ovf_d, ovf_k)``, the list's distinct buckets and each slot's index
    in them (ops/overflow.distinct). A plane is then read once a listed
    bucket and the slots read those few tiles of values: the same values,
    so the same bits, and a gather whose time no longer follows which
    addresses the list names."""
    valid = ovf_b != UNUSED
    if distinct is None:
        idx = jnp.where(valid, ovf_b, 0).astype(jnp.int32)

        def take(flat):
            return flat[idx]
    else:
        ovf_d, ovf_k = distinct
        at = jnp.where(ovf_d != UNUSED, ovf_d, 0).astype(jnp.int32)
        idx = ovf_k.astype(jnp.int32)

        def take(flat):
            return flat[at][idx]
    wv = jnp.where(valid[:, None],
                   jnp.stack([take(p.reshape(-1)) for p in planes], axis=1),
                   0.0)
    return jnp.zeros((spec.block_rows, wv.shape[1]), jnp.float32).at[
        ovf_r.astype(jnp.int32) % spec.block_rows].add(wv)


def plane_hot_pull_rows(planes, ovf_u: jax.Array, ovf_pw: jax.Array,
                        spec: TileSpec) -> jax.Array:
    """plane_spill_pull_rows from the list's hot form: every plane is
    read once a distinct bucket and its values run through the hot tile
    as they stand, three parts each (fm_hot_pull_rows without FM's
    formed channel): the float32 values to the bit, a row's pairs summed
    in the MXU's accumulator."""
    got, vtiles, hs = _hot_values(planes, ovf_u, ovf_pw, spec)
    return _hot_pull(got, ovf_pw, vtiles, hs)


def tiled_pushes(pw: jax.Array, dual_rows: jax.Array,
                 spec: TileSpec) -> jax.Array:
    """backward_pushes as the kernel writes them: float32
    (T, A_HI, ch*B_LO), channel ``c`` the lane block
    ``[c*B_LO, (c+1)*B_LO)`` (push_planes)."""
    return _build_bwd_multi(spec, dual_rows.shape[1], True)(pw, dual_rows)


def push_planes(g: jax.Array) -> tuple:
    """The (T, A_HI, B_LO) plane a channel of tiled pushes: lane slices,
    which fuse into whatever reads them."""
    return tuple(g[..., c * B_LO:(c + 1) * B_LO]
                 for c in range(g.shape[-1] // B_LO))


def spill_push_scatter_lanes(g: jax.Array, dual_rows: jax.Array,
                             ovf_b: jax.Array, ovf_r: jax.Array,
                             spec: TileSpec) -> tuple:
    """spill_push_scatter into tiled pushes (tiled_pushes) -> their
    planes. Bucket ``b``'s channels lie on ONE lane row of ``g``,
    ``(b // TILE, (b % TILE) // B_LO)``, at lanes ``c*B_LO + b % B_LO``:
    a list short beside the table is ONE scatter-add of such rows (a
    pair's values spread over a row of zeros), in place on the kernel's
    own output. (The v5e compiler flattens a scatter of single values
    into the (8, 128)-tiled array, copying it out and back, 2.3 GB each
    way at 2**24 x 34; a scatter a plane copies every plane out first.)
    A long list, whose spread rows would outgrow the pushes themselves,
    goes a plane at a time (spill_push_scatter_planes)."""
    if 8 * ovf_b.shape[0] > g.shape[0] * g.shape[1]:
        return spill_push_scatter_planes(push_planes(g), dual_rows, ovf_b,
                                         ovf_r, spec)
    valid = ovf_b != UNUSED
    idx = jnp.where(valid, ovf_b, 0).astype(jnp.int32)
    d = jnp.where(valid[:, None],
                  dual_rows[ovf_r.astype(jnp.int32) % dual_rows.shape[0]],
                  0.0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (d.shape[0], g.shape[-1]), 1)
    rows = jnp.where(lane % B_LO == (idx % B_LO)[:, None],
                     jnp.repeat(d, B_LO, axis=1), 0.0)
    return push_planes(g.at[idx // TILE, (idx % TILE) // B_LO].add(rows))


def hot_push_scatter_lanes(g: jax.Array, dual_rows: jax.Array,
                           ovf_u: jax.Array, ovf_pw: jax.Array,
                           spec: TileSpec) -> tuple:
    """spill_push_scatter_lanes from the list's hot form: a bucket's
    duals are summed a channel in the hot tiles, and those sums are a
    COO list of their own, ``tiles * TILE`` slots of distinct buckets:
    slot ``r`` names bucket ``ovf_u[r]`` and row ``r`` of the sums (an
    unused slot adds 0.0 at bucket 0). That list is added into the
    tiled pushes as any COO list is."""
    tiles, vtiles, hs, _valid, _idx = _hot_dims(ovf_u, ovf_pw, spec)
    gu = _hot_push(dual_rows, ovf_pw, tiles, vtiles, hs)
    sums = gu.transpose(0, 1, 3, 2).reshape(tiles * TILE, -1)
    return spill_push_scatter_lanes(
        g, sums, ovf_u, jnp.arange(tiles * TILE, dtype=jnp.uint32), spec)


# ---------------------------------------------------------------------------
# fused train-step kernels (tile_step_kernel=fused)
# ---------------------------------------------------------------------------
#
# The split formulation runs forward_margins and backward_grad as two
# pallas_calls with the loss dual (and the FTRL update) in XLA between
# them, so the (S,RH,RL) margin grid and the (nb,) gradient round-trip
# HBM every step and the bwd call re-streams the pairs the fwd call just
# had resident. The fused step is ONE two-phase grid of 2*(T/TB) steps:
#
#   phase 1 (t < NT):   the unmodified _fwd_kernel body accumulates the
#                       margin grid in its (VMEM-resident, constant-
#                       index) output block;
#   boundary (t == NT): the loss dual is computed elementwise from the
#                       margin grid and the labels/row-mask grids passed
#                       as operands, then written — pre-reshaped and
#                       cast exactly as the split bwd wrapper does — to
#                       a VMEM scratch the dual grid never leaves;
#   phase 2 (t >= NT):  the unmodified _bwd_kernel body (or the K-tile
#                       _bwd_kernel_fused when spec.fuse > 1) consumes
#                       the scratch. For the single-process FTRL path
#                       the per-tile grad never reaches HBM either: a
#                       _GradSink captures each tile's accumulator and
#                       the elementwise FTRL update writes the w/z/cg
#                       slot planes in place via input_output_aliases.
#
# The table's weights enter every scalar kernel as float32 tiles of the
# (T, A_HI, B_LO) weight plane (learners/table.py) and are rounded to
# the bfloat16 operand tile by tile inside phase 1; the in-place variant
# reads that one aliased plane in both phases (index map t % nt) and
# sums (w_new - w_old)^2, the step's progress number, where both are in
# registers. So no XLA op around the call touches the table. The FM step
# over channel planes has the same two forms (_build_fm_step_update, all
# 2(1+k) planes aliased, the model's update run on each tile;
# _build_fm_step_fused, push planes out for blocks with a spill): its
# margins are the split pair's to the bit and so is its in-kernel update
# (models/fm.FMAdaGrad guards its products as FTRLHandle.update does).
#
# Reusing the split kernel BODIES (not re-deriving them) is what makes
# the split path a bit-parity oracle: both paths run the same bf16
# one-hot matmuls over the same blocks in the same order, and the dual/
# update math is elementwise — tests assert margins, grads, and post-
# update slots bitwise-equal in interpret mode. COO spill blocks fuse
# too: the spill margins are pre-aggregated to a row grid in XLA
# (spill_margin_rows) and enter the grid as one extra operand the
# boundary phase adds before the dual — the same elementwise add the
# split forward_margins runs, so parity survives (only the grad-side
# scatter stays in XLA, where the dual recomputed from the emitted
# margins is bitwise-equal). Wide&deep fuses by running the MLP
# forward and backward at the boundary (a dense third phase between
# the embedding pulls and pushes, in grid-layout chunks; equal to the
# split path to rounding, not bitwise), budgeted against VMEM below. Only the
# mesh path stays structurally split: psums over MODEL (margins) and
# DATA (grads) sit at exactly the two seams the fusion removes.
#
# On top of the fusion, the ONE-HOT CACHE (tile_onehot_cache) removes
# the last duplicated work: phase 2 used to rebuild the packed-word
# relayout and the lo/rlo digit compare planes phase 1 built moments
# earlier for the same tiles. The cached kernel variants stage them in
# VMEM scratch (phase 1) and replay them (phase 2) — admitted by an
# explicit budget model, since the planes must persist for ALL tiles
# across the phase boundary.

STEP_KERNELS = ("auto", "fused", "split")
ONEHOT_CACHES = ("auto", "on", "off")

# VMEM budget model for the fused-step extras. The kernels request
# vmem_limit_bytes=100MB; the round-5 floor model puts the fused scalar
# step's resident working set at ~704 vregs (pairs + weight tile +
# margin grid + dual scratch + the value-chain intermediates), and
# anything added on top — the one-hot cache planes, the wide&deep MLP
# phase activations — must fit in the remainder.
VMEM_LIMIT_BYTES = 100 * 1024 * 1024
WORKING_SET_VREGS = 704
_VREG_BYTES = 8 * 128 * 4
VMEM_EXTRA_BUDGET = VMEM_LIMIT_BYTES - WORKING_SET_VREGS * _VREG_BYTES


def onehot_cache_bytes(spec: TileSpec) -> int:
    """Bytes of the phase-shared one-hot cache: per (tile, group) the
    staged planes are two (N, 128) bf16 digit compare planes, held for
    the FULL tile set (phase 2's grid step nt+j revisits pairs block j,
    so nothing is evictable at the phase boundary). Both planes are
    128 lanes wide, so this is also what they occupy in VMEM."""
    SG = spec.subblocks // spec.group
    return spec.tiles * SG * spec.n * (2 * B_LO + 2 * RL)


MLP_ROWS = 8   # grid sublanes (row-hi digits) per in-kernel MLP chunk


def mlp_phase_bytes(spec: TileSpec, dim: int, hidden: Tuple[int, ...]
                    ) -> int:
    """VMEM bytes the wide&deep boundary phase holds live: the pulls
    (f32) and dual (bf16) channel grids, plus the MLP weights in the
    row-blocked form the phase multiplies by (_wd_blocked_params:
    MLP_ROWS**2 times the raw matrix), three times over — the matrix,
    its transpose for the backward, and its gradient. The activations
    are one (width * MLP_ROWS, 128) chunk at a time and do not
    count."""
    rows = spec.block_rows
    ch_in, ch_out = 1 + dim, dim + 2
    grids = rows * (ch_in * 4 + ch_out * 2)
    sizes = [dim, *hidden, 1]
    weights = sum(a * b for a, b in zip(sizes, sizes[1:]))
    return grids + 3 * MLP_ROWS * MLP_ROWS * weights * 4


@dataclass(frozen=True)
class StepResolution:
    """Structured result of resolve_step_kernel: the resolved kernel,
    the split reason (empty when fused), and the one-hot cache decision
    with its off-reason (empty when on). ``cache_record`` is the string
    store.step_kernel records alongside the split reason."""
    kernel: str
    why: str = ""
    cache: bool = False
    cache_why: str = ""

    @property
    def cache_record(self) -> str:
        return ("onehot_cache=on" if self.cache
                else f"onehot_cache=off:{self.cache_why}")


def _onehot_cache_decision(resolved: str, knob: str,
                           spec: Optional[TileSpec], channels: int,
                           deep: bool) -> Tuple[bool, str]:
    """The cache half of resolve_step_kernel. Structural exclusions
    (split resolution, multi-channel, K>1 chains) hold even under a
    forced ``on``; the VMEM budget model only gates ``auto`` — ``on``
    overrides it so a measurement can go past the model."""
    if knob == "off":
        return False, "forced off"
    if resolved != "fused":
        return False, "split path shares no phases"
    if channels > 1 or deep:
        return False, ("multi-channel kernels hoist one wide compare "
                       "across channels; no per-phase rebuild to stage")
    if spec is None:
        return False, "no tile spec at resolve time"
    if spec.fuse > 1:
        return False, ("fuse>1 re-views pairs into K-tile chains; the "
                       "staged planes do not align with the bwd view")
    if knob == "on":
        return True, ""
    need = onehot_cache_bytes(spec)
    if need > VMEM_EXTRA_BUDGET:
        return False, (f"cache planes need ~{need // 2**20} MB, over "
                       f"the {VMEM_EXTRA_BUDGET // 2**20} MB left "
                       f"beside the {WORKING_SET_VREGS}-vreg working "
                       f"set")
    return True, ""


def resolve_step_kernel(kernel: str, *, ovf_cap: int = 0,
                        mesh: bool = False, deep: bool = False,
                        spec: Optional[TileSpec] = None,
                        onehot_cache: str = "auto", dim: int = 0,
                        hidden: Tuple[int, ...] = (),
                        channels: int = 1) -> StepResolution:
    """Resolve the ``tile_step_kernel`` + ``tile_onehot_cache`` knobs
    to a :class:`StepResolution` — ``why`` names the reason whenever
    the resolution is split, ``cache_why`` whenever the one-hot cache
    is off. Structural inadmissibility (mesh, an over-VMEM-budget MLP
    phase, wide&deep spill) wins over a forced ``fused``: unlike
    ``tile_online=on`` this never raises, because ovf_cap and the
    model geometry are properties of the dataset, not misconfiguration.
    ``auto`` resolves to fused only on the TPU backend (mirroring
    ``gbdt_hist_kernel``); a forced ``fused`` runs anywhere —
    interpret mode included, which is how the CPU parity tests drive
    it. Callers pass ``spec`` (for the VMEM budget models), ``dim`` /
    ``hidden`` on the wide&deep path, and ``channels`` (pull/push
    channel count) on any multi-channel path."""
    if kernel not in STEP_KERNELS:
        raise ValueError(f"tile_step_kernel must be one of "
                         f"{STEP_KERNELS}, got {kernel!r}")
    if onehot_cache not in ONEHOT_CACHES:
        raise ValueError(f"tile_onehot_cache must be one of "
                         f"{ONEHOT_CACHES}, got {onehot_cache!r}")

    def res(k: str, why: str = "") -> StepResolution:
        cache, cwhy = _onehot_cache_decision(k, onehot_cache, spec,
                                             channels, deep)
        return StepResolution(k, why, cache, cwhy)

    if mesh:
        return res("split", ("mesh psums (margins over model, grads "
                             "over data) sit between the phases the "
                             "fusion joins"))
    if deep:
        if ovf_cap > 0:
            return res("split", ("wide&deep spill needs the pull "
                                 "channels in HBM for the COO scatter "
                                 "between the phases"))
        if spec is None:
            return res("split", ("no tile spec at resolve time to "
                                 "budget the in-kernel MLP phase "
                                 "against VMEM"))
        need = mlp_phase_bytes(spec, dim, tuple(hidden))
        if need > VMEM_EXTRA_BUDGET:
            return res("split", (f"wide&deep MLP phase needs ~"
                                 f"{need // 2**20} MB of VMEM for the "
                                 f"row-blocked weights, over the "
                                 f"{VMEM_EXTRA_BUDGET // 2**20} MB "
                                 f"left beside the working set"))
    if kernel == "split":
        return res("split", "forced")
    if kernel == "fused":
        return res("fused")
    if jax.default_backend() == "tpu":
        return res("fused")
    return res("split", f"auto on {jax.default_backend()} backend")


class _GradSink:
    """Stands in for ``g_ref`` when the bwd kernel bodies run inside the
    fused-update phase: they only ever assign whole tiles
    (``g_ref[tb] = acc``), so capturing the assignments keeps each
    tile's f32 gradient in registers for the in-place FTRL update
    instead of routing it through an HBM output."""

    def __init__(self):
        self.tiles = {}

    def __setitem__(self, tb, acc):
        self.tiles[tb] = acc


def _make_step_kernel(spec: TileSpec, loss: str, exact_dense: bool,
                      handle, nt: int, cache: bool = False,
                      spill: bool = False):
    """Two-phase scalar kernel body; see the section comment.
    ``handle`` is None for the grad-emitting variant or an FTRLHandle
    for the in-place slot update — the kernel calls the handle's own
    ``update`` on the tile planes, so the in-kernel math can never
    drift from the split path's push(). ``cache`` swaps in the one-hot
    cache kernel bodies (stage in phase 1, replay in phase 2; K == 1
    only — the resolver enforces the structural exclusions); ``spill``
    adds a pre-aggregated COO spill-margin grid operand the boundary
    phase sums in before the dual (grad-emitting variant only: the
    spill grad scatter needs the grad in HBM, so the in-place update
    variant never sees spill)."""
    from .loss import create_loss, opaque_one
    _, dual_fn = create_loss(loss)
    K = spec.fuse
    assert not (cache and K > 1), "one-hot cache excludes K>1 chains"
    assert not (spill and handle is not None), \
        "spill blocks use the grad-emitting variant"

    def kernel(*refs):
        if K > 1:
            pw_ref, w_ref, lab_ref, msk_ref, pwk_ref, ghic_ref = refs[:6]
            rest = refs[6:]
        else:
            pw_ref, w_ref, lab_ref, msk_ref = refs[:4]
            rest = refs[4:]
        if spill:
            sp_ref, rest = rest[0], rest[1:]
        if handle is not None:
            # w_ref walks the tiles in BOTH phases (it is the one plane
            # aliased onto wo_ref): rounded in phase 1, updated in phase 2
            (zp_ref, np_ref, mg_ref, wo_ref, zo_ref, no_ref, wd_ref,
             *scr) = rest
        else:
            mg_ref, g_ref, *scr = rest
        if cache:
            dual_s, lo_c, rlo_c = scr
        else:
            (dual_s,) = scr
        t = pl.program_id(0)

        @pl.when(t < nt)
        def _fwd():
            if cache:
                _fwd_kernel_cached(spec, pw_ref, w_ref, mg_ref,
                                   lo_c, rlo_c, t)
            else:
                _fwd_kernel(spec, pw_ref, w_ref, mg_ref, t)

        @pl.when(t == nt)
        def _dual():
            lab = lab_ref[...]
            msk = msk_ref[...]
            mg = mg_ref[...]
            if spill:
                # the pre-aggregated spill grid lands on the margins
                # BEFORE the dual — the same elementwise add the split
                # path's forward_margins runs in XLA, so the emitted
                # margins (and the dual) stay bitwise-identical
                mg = mg + sp_ref[...]
                mg_ref[...] = mg
            dual = dual_fn(mg, lab, msk)
            if not exact_dense:
                # _nudge_zero_dual (learners/store.py), elementwise —
                # same bits as the split path's XLA nudge
                eps = jnp.where(lab > 0.5, jnp.float32(-1e-30),
                                jnp.float32(1e-30))
                dual = jnp.where((dual == 0.0) & (msk > 0), eps, dual)
            dual_s[...] = dual.reshape(dual_s.shape).astype(jnp.bfloat16)
            if handle is not None:
                wd_ref[...] = jnp.zeros_like(wd_ref)

        @pl.when(t >= nt)
        def _bwd():
            if handle is None:
                if cache:
                    _bwd_kernel_cached(spec, pw_ref, dual_s, g_ref,
                                       lo_c, rlo_c, t - nt)
                elif K > 1:
                    _bwd_kernel_fused(spec, pwk_ref, dual_s, ghic_ref,
                                      g_ref)
                else:
                    _bwd_kernel(spec, pw_ref, dual_s, g_ref)
                return
            sink = _GradSink()
            if cache:
                _bwd_kernel_cached(spec, pw_ref, dual_s, sink,
                                   lo_c, rlo_c, t - nt)
            elif K > 1:
                _bwd_kernel_fused(spec, pwk_ref, dual_s, ghic_ref, sink)
            else:
                _bwd_kernel(spec, pw_ref, dual_s, sink)
            one = opaque_one(msk_ref[0, 0, 0])
            wd = wd_ref[...]
            for tb in range(spec.tiles_step):
                w_old = w_ref[tb]
                w_new, z_new, cg_new = handle.update(
                    w_old, zp_ref[tb], np_ref[tb], sink.tiles[tb], one)
                wo_ref[tb] = w_new
                zo_ref[tb] = z_new
                no_ref[tb] = cg_new
                # the progress number's partial sums, a lane apiece
                # (summed by the wrapper): old and new w are in registers
                d = w_new - w_old
                wd = wd + d * d
            wd_ref[...] = wd

    return kernel


def _step_grid_specs(spec: TileSpec, spill: bool = False,
                     w_both_phases: bool = False):
    """(grid, in_specs, nt) shared by both fused scalar variants: pairs
    + float32 weight-plane tiles stream through phase 1 (and, at K == 1,
    phase 2 re-streams the pairs exactly as the split bwd call would),
    the label/mask grids sit at a constant index, and the K > 1 variant
    adds the re-viewed pairs + the joint-digit compare constant for
    _bwd_kernel_fused. ``spill`` appends the constant-index
    pre-aggregated spill-margin grid the boundary phase consumes.
    ``w_both_phases`` (the in-place variant) walks the weight tiles
    again in phase 2, where that variant updates them."""
    T, TB, K = spec.tiles, spec.tiles_step, spec.fuse
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks
    GS = spec.group
    nt = T // TB
    pw_map = ((lambda t: (jnp.minimum(t, nt - 1), 0, 0)) if K > 1
              else (lambda t: (t % nt, 0, 0)))
    in_specs = [
        pl.BlockSpec((TB, SG, N), pw_map),
        pl.BlockSpec((TB, A_HI, B_LO),
                     (lambda t: (t % nt, 0, 0)) if w_both_phases
                     else (lambda t: (jnp.minimum(t, nt - 1), 0, 0))),
        pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0)),
        pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0)),
    ]
    if K > 1:
        in_specs += [
            pl.BlockSpec((TB // K, SG, K * N),
                         lambda t: (jnp.maximum(t - nt, 0), 0, 0)),
            pl.BlockSpec((K * N, GS * RH), lambda t: (0, 0)),
        ]
    if spill:
        in_specs += [pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0))]
    return (2 * nt,), in_specs, nt


def _cache_scratch(spec: TileSpec):
    """The one-hot cache's VMEM scratch: the two digit compare planes
    for every (tile, group) — the shapes onehot_cache_bytes budgets."""
    T = spec.tiles
    SG, N = spec.subblocks // spec.group, spec.n
    return [pltpu.VMEM((T, SG, N, B_LO), jnp.bfloat16),
            pltpu.VMEM((T, SG, N, RL), jnp.bfloat16)]


def _step_dual_scratch(spec: TileSpec):
    """The VMEM dual-grid scratch, shaped exactly as the split bwd
    wrapper's XLA reshape of the flat dual — (S//bp, bp*RH, RL) for the
    paired-subblock kernel, (S//GS, GS*RH, RL) for the K-tile one."""
    S, GS = spec.subblocks, spec.group
    if spec.fuse > 1:
        return pltpu.VMEM((S // GS, GS * RH, RL), jnp.bfloat16)
    bp = _bp(spec)
    return pltpu.VMEM((S // bp, bp * RH, RL), jnp.bfloat16)


def _step_extra_args(pw, spec: TileSpec):
    """The K > 1 variant's extra operands (re-viewed pairs + compare
    constant) — identical to what the split _build_bwd K > 1 wrapper
    feeds _bwd_kernel_fused."""
    if spec.fuse <= 1:
        return []
    return [_fused_pairs_view(pw, spec),
            jnp.asarray(_fused_ghi_const(spec.fuse, spec.n, spec.cap,
                                         spec.group))]


@lru_cache(maxsize=None)
def _build_step_grad(spec: TileSpec, loss: str, exact_dense: bool,
                     cache: bool = False, spill: bool = False):
    """Fused step, grad-emitting variant: (margins, grad) with the dual
    grid never materialized in HBM. The handle update stays in XLA —
    the multihost path (gradients cross the wire before the update) and
    every non-FTRL handle. ``spill`` takes the pre-aggregated spill-
    margin grid as a trailing operand (the grad-side scatter stays with
    the caller, where the grad lives in HBM anyway)."""
    T, TB = spec.tiles, spec.tiles_step
    S = spec.subblocks
    grid, in_specs, nt = _step_grid_specs(spec, spill=spill)
    kernel = _make_step_kernel(spec, loss, exact_dense, None, nt,
                               cache=cache, spill=spill)

    @jax.jit
    def step(pw, w, labels, mask, *spill_rows):
        wt = w.reshape(T, A_HI, B_LO)      # rounded tile by tile in-kernel
        args = ([pw, wt, labels.reshape(S, RH, RL),
                 mask.reshape(S, RH, RL)] + _step_extra_args(pw, spec)
                + [s.reshape(S, RH, RL) for s in spill_rows])
        mg, g = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs,
            out_specs=[
                pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0)),
                pl.BlockSpec((TB, A_HI, B_LO),
                             lambda t: (jnp.maximum(t - nt, 0), 0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((S, RH, RL), jnp.float32),
                jax.ShapeDtypeStruct((T, A_HI, B_LO), jnp.float32),
            ],
            scratch_shapes=([_step_dual_scratch(spec)]
                            + (_cache_scratch(spec) if cache else [])),
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(*args)
        return mg.reshape(spec.block_rows), g.reshape(spec.nb)

    return step


@lru_cache(maxsize=None)
def _build_step_update(spec: TileSpec, loss: str, handle,
                       cache: bool = False):
    """Fused step, in-place FTRL variant: (margins, (w, z, cg) planes,
    Σ(Δw)²). The three (T, A_HI, B_LO) planes of the table
    (learners/table.py) go into the call as they are, aliased onto its
    outputs, and come back as the next step's state; the weight plane is
    the forward phase's operand too (rounded in-kernel). The (nb,)
    gradient never exists in HBM — each tile's grad goes straight from
    the bwd accumulator into the elementwise slot update — and no XLA
    op on either side of the call reads or writes a table-sized array.
    FTRL is exact-dense (zero_grad_push_is_identity), so there is no
    nudge and no touched mask to apply. ``handle`` is the (frozen,
    hashable) FTRLHandle — the kernel runs its update() verbatim."""
    S = spec.subblocks
    grid, in_specs, nt = _step_grid_specs(spec, w_both_phases=True)
    kernel = _make_step_kernel(spec, loss, True, handle, nt, cache=cache)
    n_in = len(in_specs)
    plane = pl.BlockSpec((spec.tiles_step, A_HI, B_LO),
                         lambda t: (jnp.maximum(t - nt, 0), 0, 0))
    plane_shape = jax.ShapeDtypeStruct((spec.tiles, A_HI, B_LO),
                                       jnp.float32)

    @jax.jit
    def step(pw, planes, labels, mask):
        w, z, cg = planes
        args = ([pw, w, labels.reshape(S, RH, RL),
                 mask.reshape(S, RH, RL)] + _step_extra_args(pw, spec)
                + [z, cg])
        mg, wn, zn, nn, wd = pl.pallas_call(
            kernel,
            grid=grid,
            in_specs=in_specs + [plane, plane],
            out_specs=[
                pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0)),
                plane, plane, plane,
                pl.BlockSpec((A_HI, B_LO), lambda t: (0, 0)),
            ],
            out_shape=[
                jax.ShapeDtypeStruct((S, RH, RL), jnp.float32),
                plane_shape, plane_shape, plane_shape,
                jax.ShapeDtypeStruct((A_HI, B_LO), jnp.float32),
            ],
            input_output_aliases={1: 1, n_in: 2, n_in + 1: 3},
            scratch_shapes=([_step_dual_scratch(spec)]
                            + (_cache_scratch(spec) if cache else [])),
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(*args)
        return mg.reshape(spec.block_rows), (wn, zn, nn), jnp.sum(wd)

    return step


def fm_margin_math(lin, s_parts, q, one):
    """FM margin lin + ½(Σ s_j² − q), the sum accumulated in fixed
    sequential order with every product ``*one``-guarded (``one`` =
    opaque_one(...)) — the fused kernel's boundary phase and the split
    XLA forward (models/fm.py) both call this, so the margin bits match
    across contexts regardless of FMA contraction."""
    ss = (s_parts[0] * s_parts[0]) * one
    for sj in s_parts[1:]:
        ss = ss + (sj * sj) * one
    return lin + (jnp.float32(0.5) * (ss - q)) * one


def _lane_channels(acc):
    """The channels of an (A_HI, ch*B_LO) tile: lane slices."""
    return [acc[:, c * B_LO:(c + 1) * B_LO]
            for c in range(acc.shape[1] // B_LO)]


class _PlaneSink:
    """Stands in for ``g_ref`` when the multi-channel bwd body writes a
    tile's (A_HI, ch*B_LO) accumulator: channel c goes to push plane c's
    block."""

    def __init__(self, refs):
        self.refs = refs

    def __setitem__(self, tb, acc):
        for ref, x in zip(self.refs, _lane_channels(acc)):
            ref[tb] = x


class _UpdateSink:
    """Stands in for ``g_ref`` in the in-place FM step: a tile's pushes
    go from the accumulator straight into ``update`` with the tile's
    state, and the new state into the aliased output blocks — the push
    never reaches HBM. ``wd`` sums (w_new − w_old)² over the tiles."""

    def __init__(self, update, one, theta, cg, theta_out, cg_out):
        self.update, self.one = update, one
        self.theta, self.cg = theta, cg
        self.out = tuple(theta_out) + tuple(cg_out)
        self.wd = 0.0

    def __setitem__(self, tb, acc):
        theta = [r[tb] for r in self.theta]
        theta_new, cg_new = self.update(theta, [r[tb] for r in self.cg],
                                        _lane_channels(acc), self.one)
        for ref, x in zip(self.out, theta_new + cg_new):
            ref[tb] = x
        d = theta_new[0] - theta[0]
        self.wd = self.wd + d * d


def _make_fm_step_kernel(spec: TileSpec, ch: int, k: int, loss: str,
                         nt: int, spill: bool = False, update=None):
    """Two-phase multi-channel kernel body for the FM step over a table
    kept as channel planes. Phase 1 puts the bfloat16 operand tiles of
    this grid step together in VMEM from the float32 w and v plane
    blocks (fm_operand) and runs the unmodified _fwd_multi_kernel over
    them, accumulating the (S, RH, ch*RL) pulls grid in VMEM scratch (it
    never reaches HBM at all); the boundary computes the FM margin
    (lin + 0.5*(Σ s_j² − q), summed sequentially — the split path
    mirrors the same order), the dual, and the [dual, dual*s_j..., mask]
    push channels; phase 2 is the unmodified _bwd_multi_kernel, its
    tiles landing a channel a push plane (_PlaneSink). ``spill`` adds
    (a) a pre-aggregated COO spill-pulls grid operand summed into the
    pulls before the margin (the same elementwise add the split path
    runs) and (b) an extra f32 output carrying the dual-channel grid, so
    the caller can run the spill push scatter in XLA — in-kernel it is
    bitwise what the split path's XLA dvals would be. ``update`` (the
    in-place variant, no spill: the COO scatter needs the pushes in HBM)
    is the model's elementwise update of a tile's channels: the w and v
    planes then walk the tiles in BOTH phases, the accumulator planes in
    phase 2, all aliased onto the outputs, and phase 2 updates each
    tile from its accumulator (_UpdateSink) and sums (w_new − w_old)²."""
    from .loss import create_loss, opaque_one
    _, dual_fn = create_loss(loss)
    n_theta = 1 + k
    assert not (spill and update is not None), \
        "spill blocks use the push-emitting variant"

    def kernel(*refs):
        pw_ref, theta, (lab_ref, msk_ref) = (
            refs[0], refs[1:1 + n_theta], refs[1 + n_theta:3 + n_theta])
        rest = refs[3 + n_theta:]
        if spill:
            sp_ref, rest = rest[0], rest[1:]
        if update is not None:
            cg, rest = rest[:n_theta], rest[n_theta:]
            mg_ref, theta_out, cg_out, wd_ref, rest = (
                rest[0], rest[1:1 + n_theta],
                rest[1 + n_theta:1 + 2 * n_theta], rest[1 + 2 * n_theta],
                rest[2 + 2 * n_theta:])
        else:
            mg_ref, push, rest = rest[0], rest[1:1 + ch], rest[1 + ch:]
        if spill:
            dv_ref, rest = rest[0], rest[1:]
        wt_s, pulls_s, dual_s = rest
        t = pl.program_id(0)

        @pl.when(t < nt)
        def _fwd():
            one = opaque_one(msk_ref[0, 0, 0])
            for tb in range(spec.tiles_step):
                wt_s[tb] = fm_operand(theta[0][tb],
                                      [v[tb] for v in theta[1:]], one)
            _fwd_multi_kernel(spec, ch, pw_ref, wt_s, pulls_s, t)

        @pl.when(t == nt)
        def _dual():
            pulls = pulls_s[...]                   # (S, RH, ch*RL)
            if spill:
                pulls = pulls + sp_ref[...]
            msk = msk_ref[...]
            one = opaque_one(msk[0, 0, 0])
            s_parts = [pulls[..., (1 + j) * RL:(2 + j) * RL]
                       for j in range(k)]
            margin = fm_margin_math(
                pulls[..., 0:RL], s_parts,
                pulls[..., (1 + k) * RL:(2 + k) * RL], one)
            mg_ref[...] = margin
            dual = dual_fn(margin, lab_ref[...], msk)
            parts = [dual]
            for j in range(k):
                parts.append(dual * pulls[..., (1 + j) * RL:
                                          (2 + j) * RL])
            parts.append(msk)                      # touched-count channel
            dv = jnp.concatenate(parts, axis=-1)   # (S, RH, ch*RL)
            if spill:
                dv_ref[...] = dv
            dual_s[...] = dv.reshape(dual_s.shape).astype(jnp.bfloat16)
            if update is not None:
                wd_ref[...] = jnp.zeros_like(wd_ref)

        @pl.when(t >= nt)
        def _bwd():
            if update is None:
                _bwd_multi_kernel(spec, ch, pw_ref, dual_s, _PlaneSink(push))
                return
            sink = _UpdateSink(update, opaque_one(msk_ref[0, 0, 0]), theta,
                               cg, theta_out, cg_out)
            _bwd_multi_kernel(spec, ch, pw_ref, dual_s, sink)
            # the progress number's partial sums, a lane apiece (summed
            # by the wrapper)
            wd_ref[...] += sink.wd

    return kernel


def _fm_step_scratch(spec: TileSpec, ch: int):
    """VMEM scratch of the fused FM steps: a grid step's bfloat16
    operand tiles, the pulls grid, and the dual channels shaped as the
    split bwd wrapper reshapes them."""
    S, bp = spec.subblocks, _bp(spec)
    return [pltpu.VMEM((spec.tiles_step, A_HI, ch * B_LO), jnp.bfloat16),
            pltpu.VMEM((S, RH, ch * RL), jnp.float32),
            pltpu.VMEM((S // bp, bp * RH, ch * RL), jnp.bfloat16)]


@lru_cache(maxsize=None)
def _build_fm_step_fused(spec: TileSpec, k: int, loss: str,
                         spill: bool = False):
    """Fused FM step over channel planes: ``step(pw, theta, labels, mask
    [, spill_pulls])`` with ``theta`` the float32 (T, A_HI, B_LO) planes
    ``(w, v_1..v_k)`` as the table holds them -> (margins, the ch push
    planes[, the (rows, ch) dual channels]). No XLA op on either side of
    the call forms an operand or re-forms the pushes: nothing table-sized
    but the call's own reads and writes."""
    ch = k + 2
    spec = _multi_spec(spec, ch)       # same compile-budget rule as split
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks
    nt = T // TB
    kernel = _make_fm_step_kernel(spec, ch, k, loss, nt, spill=spill)
    const_grid = pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0))
    const_wide = pl.BlockSpec((S, RH, ch * RL), lambda t: (0, 0, 0))
    plane_shape = jax.ShapeDtypeStruct((T, A_HI, B_LO), jnp.float32)

    @jax.jit
    def step(pw, theta, labels, mask, *spill_pulls):
        args = [pw, *theta, labels.reshape(S, RH, RL),
                mask.reshape(S, RH, RL)]
        in_specs = (
            [pl.BlockSpec((TB, SG, N), lambda t: (t % nt, 0, 0))]
            + [pl.BlockSpec((TB, A_HI, B_LO),
                            lambda t: (jnp.minimum(t, nt - 1), 0, 0))
               ] * (1 + k)
            + [const_grid, const_grid])
        out_specs = [const_grid] + [
            pl.BlockSpec((TB, A_HI, B_LO),
                         lambda t: (jnp.maximum(t - nt, 0), 0, 0))] * ch
        out_shape = ([jax.ShapeDtypeStruct((S, RH, RL), jnp.float32)]
                     + [plane_shape] * ch)
        if spill:
            # (rows, ch) pre-aggregated spill pulls -> the channel-major
            # grid layout the pulls scratch carries
            sp = (spill_pulls[0].reshape(S, RH, RL, ch)
                  .transpose(0, 1, 3, 2).reshape(S, RH, ch * RL))
            args.append(sp)
            in_specs.append(const_wide)
            out_specs.append(const_wide)
            out_shape.append(
                jax.ShapeDtypeStruct((S, RH, ch * RL), jnp.float32))
        outs = pl.pallas_call(
            kernel,
            grid=(2 * nt,),
            in_specs=in_specs,
            out_specs=out_specs,
            out_shape=out_shape,
            scratch_shapes=_fm_step_scratch(spec, ch),
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(*args)
        mg, push = outs[0].reshape(spec.block_rows), tuple(outs[1:1 + ch])
        if spill:
            # dual-channel grid -> (rows, ch), for the caller's XLA
            # spill push scatter — the inverse of the pulls transpose
            dv_rows = (outs[1 + ch].reshape(S, RH, ch, RL)
                       .transpose(0, 1, 3, 2).reshape(spec.block_rows, ch))
            return mg, push, dv_rows
        return mg, push

    return step


@lru_cache(maxsize=None)
def _build_fm_step_update(spec: TileSpec, k: int, loss: str, update):
    """Fused FM step, in-place variant: ``step(pw, planes, labels, mask)``
    with all 2(1+k) planes of the table -> (margins, the new planes,
    Σ(Δw)²). The planes go into the call as they are, aliased onto its
    outputs; the pushes never exist in HBM and no XLA op on either side
    reads or writes a table-sized array (FTRL's _build_step_update, for
    ten channels). ``update`` is the model's hashable elementwise update
    (models/fm.FMAdaGrad), run verbatim on tiles."""
    ch = k + 2
    spec = _multi_spec(spec, ch)
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks
    nt = T // TB
    kernel = _make_fm_step_kernel(spec, ch, k, loss, nt, update=update)
    const_grid = pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0))
    both = pl.BlockSpec((TB, A_HI, B_LO), lambda t: (t % nt, 0, 0))
    second = pl.BlockSpec((TB, A_HI, B_LO),
                          lambda t: (jnp.maximum(t - nt, 0), 0, 0))
    plane_shape = jax.ShapeDtypeStruct((T, A_HI, B_LO), jnp.float32)
    n = 1 + k

    @jax.jit
    def step(pw, planes, labels, mask):
        outs = pl.pallas_call(
            kernel,
            grid=(2 * nt,),
            in_specs=([pl.BlockSpec((TB, SG, N), lambda t: (t % nt, 0, 0))]
                      + [both] * n + [const_grid, const_grid]
                      + [second] * n),
            out_specs=([const_grid] + [second] * (2 * n)
                       + [pl.BlockSpec((A_HI, B_LO), lambda t: (0, 0))]),
            out_shape=([jax.ShapeDtypeStruct((S, RH, RL), jnp.float32)]
                       + [plane_shape] * (2 * n)
                       + [jax.ShapeDtypeStruct((A_HI, B_LO), jnp.float32)]),
            input_output_aliases={
                **{1 + i: 1 + i for i in range(n)},
                **{3 + n + i: 1 + n + i for i in range(n)}},
            scratch_shapes=_fm_step_scratch(spec, ch),
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(pw, *planes[:n], labels.reshape(S, RH, RL),
          mask.reshape(S, RH, RL), *planes[n:])
        return (outs[0].reshape(spec.block_rows), tuple(outs[1:1 + 2 * n]),
                jnp.sum(outs[1 + 2 * n]))

    return step


# The deep tower's precision, stated and not the backend's default (one
# bfloat16 pass on the TPU, float32 on the CPU): every matmul of the tower,
# forward and backward, in XLA (tower_dot) and in the fused kernel's dense
# phase (_tower_mm there too), rounds BOTH operands to TOWER_OPERANDS, once,
# and accumulates the products in float32. Biases, ReLU, the dual and the
# AdaGrad update stay float32.
TOWER_OPERANDS = jnp.bfloat16


def _tower_mm(a: jax.Array, b: jax.Array, contract,
              held: bool = False) -> jax.Array:
    """``a`` and ``b`` contracted over ``contract`` = (dims of a, dims
    of b): operands rounded to TOWER_OPERANDS, float32 accumulation.

    ``held``: for a product that is no matmul to XLA. A one-column
    layer (the tower's last) is a multiply to the TPU compiler, its
    operands' ``convert`` to bfloat16 and back is then a pair that it
    drops (excess precision is allowed), and the layer runs in float32:
    the first chip runs read the tower's gradient norms 0.15% off the
    reference for it. There the rounding is a ``reduce_precision``,
    which it may not drop, and the rounded values stay float32 (their
    products are exact in it)."""
    if held:
        info = jnp.finfo(TOWER_OPERANDS)
        a, b = (jax.lax.reduce_precision(x, info.nexp, info.nmant)
                for x in (a, b))
    else:
        a, b = a.astype(TOWER_OPERANDS), b.astype(TOWER_OPERANDS)
    return jax.lax.dot_general(a, b, (contract, ((), ())),
                               preferred_element_type=jnp.float32)


@jax.custom_vjp
def tower_dot(h: jax.Array, w: jax.Array) -> jax.Array:
    """``h @ w`` of the tower, (rows, a) @ (a, b), at the stated
    precision. The backward's two matmuls (``g @ w.T``, ``h.T @ g``) are
    written out so that they round their operands the same way, and not
    however autodiff would transpose a mixed-precision product."""
    return _tower_mm(h, w, ((1,), (0,)), held=w.shape[1] == 1)


def _tower_dot_fwd(h, w):
    return tower_dot(h, w), (h, w)


def _tower_dot_bwd(res, g):
    h, w = res
    held = w.shape[1] == 1
    return (_tower_mm(g, w, ((1,), (1,)), held),
            _tower_mm(h, g, ((0,), (0,)), held))


tower_dot.defvjp(_tower_dot_fwd, _tower_dot_bwd)


def tower_flops(rows: int, dim: int, hidden: Tuple[int, ...]) -> int:
    """FLOPs of the tower for ``rows`` rows, forward and backward: three
    matmuls a layer (``h @ W``, ``g @ W.T``, ``h.T @ g``; the first
    layer's input gradient is needed, the embeddings train)."""
    sizes = [dim, *hidden, 1]
    return 6 * rows * sum(a * b for a, b in zip(sizes, sizes[1:]))


def mlp_forward(params: dict, x: jax.Array, n_layers: int) -> jax.Array:
    """Dense MLP forward on the pooled embeddings (wide&deep's deep
    tower; models/wide_deep.py re-exports this): the split step's, the
    eval step's and the serving path's. The fused wd step runs the
    same tower over grid-layout chunks (_make_wd_step_kernel), and is
    held to this function at float tolerance."""
    h = x
    for i in range(n_layers):
        h = tower_dot(h, params[f"W{i}"]) + params[f"b{i}"]
        if i + 1 < n_layers:
            h = jax.nn.relu(h)
    return h[:, 0]


def _wd_blocked_params(mlp: dict, n_layers: int):
    """The deep tower's parameters in the form the in-kernel MLP phase
    multiplies by. The pulls grid keeps a row's channels on LANE blocks
    and the rows themselves on (sublane, lane), so a plain ``x @ W``
    over (rows, channels) needs a relayout Mosaic has no lowering for
    (and would pad every activation to 128 lanes: 50 MB apiece at
    98,304 rows). Instead one chunk of MLP_ROWS grid sublanes is
    stacked channel-major on sublanes — index ``c * MLP_ROWS + r`` —
    and each layer is ``kron(W.T, I) @ chunk``: the identity block
    keeps the MLP_ROWS rows apart, so row r of channel j comes out at
    ``j * MLP_ROWS + r``, already in grid layout. Returns per layer
    (Wk, Wk.T, bk): (b*R, a*R), (a*R, b*R), (b*R, 1)."""
    eye = jnp.eye(MLP_ROWS, dtype=jnp.float32)
    out = []
    for i in range(n_layers):
        wk = jnp.kron(mlp[f"W{i}"].T, eye)
        out += [wk, wk.T, jnp.repeat(mlp[f"b{i}"], MLP_ROWS)[:, None]]
    return out


def _wd_unblock_grads(g_blocked, sizes):
    """Blocked parameter gradients -> the MLP's own shapes: the tied
    entries of kron(W.T, I) sum back over the identity's diagonal."""
    R = MLP_ROWS
    g_mlp = {}
    for i, (a, b) in enumerate(zip(sizes, sizes[1:])):
        gwk, gbk = g_blocked[2 * i], g_blocked[2 * i + 1]
        g_mlp[f"W{i}"] = jnp.einsum("jrcr->cj", gwk.reshape(b, R, a, R))
        g_mlp[f"b{i}"] = gbk.reshape(b, R).sum(axis=1)
    return g_mlp


def _make_wd_step_kernel(spec: TileSpec, ch_in: int, ch_out: int,
                         k: int, n_layers: int, loss: str, nt: int):
    """Three-phase wide&deep kernel body: phase 1 is the unmodified
    _fwd_multi_kernel accumulating the (S, RH, ch_in*RL) pulls grid in
    VMEM scratch; the boundary is the DENSE phase — the MLP forward and
    backward over the pooled embeddings, the dual, and the
    [dual, g_pooled_j..., mask] push channels written to the
    channel-major dual grid; phase 2 is the unmodified
    _bwd_multi_kernel over ch_out push channels. The per-parameter MLP
    grads leave (row-blocked, see _wd_blocked_params) through
    constant-index outputs accumulated at the boundary. No nudge: the
    split wd path applies none (AdaGrad + explicit touched mask).

    The dense phase walks the grid in chunks of MLP_ROWS sublanes x RL
    lanes (1024 rows), every value in grid layout throughout, so it
    needs no relayout. It is the same MLP as ``mlp_forward`` (the split
    path's, and the reference the tests compare against at float
    tolerance) summed in another order, so the two paths agree to
    rounding, not bitwise."""
    from .loss import create_loss
    _, dual_fn = create_loss(loss)
    S = spec.subblocks
    bp = _bp(spec)
    R = MLP_ROWS
    NI = RH // R                      # chunks per subblock
    UN = 2                            # chunks per loop step: the bf16
    #                                   dual grid stores 16 sublanes

    def kernel(*refs):
        pw_ref, wt_ref, lab_ref, msk_ref = refs[:4]
        p_refs = refs[4:4 + 3 * n_layers]
        mg_ref, push_ref = refs[4 + 3 * n_layers:6 + 3 * n_layers]
        g_refs = refs[6 + 3 * n_layers:6 + 5 * n_layers]
        pulls_s, dual_s = refs[6 + 5 * n_layers:]
        t = pl.program_id(0)

        @pl.when(t < nt)
        def _fwd():
            _fwd_multi_kernel(spec, ch_in, pw_ref, wt_ref, pulls_s, t)

        @pl.when(t == nt)
        def _mlp():
            for gr in g_refs:
                gr[...] = jnp.zeros_like(gr)
            # rounded once, not a chunk at a time (_tower_mm's cast of
            # a TOWER_OPERANDS value is no cast)
            wk = [p_refs[3 * i][...].astype(TOWER_OPERANDS)
                  for i in range(n_layers)]
            wkt = [p_refs[3 * i + 1][...].astype(TOWER_OPERANDS)
                   for i in range(n_layers)]
            bk = [p_refs[3 * i + 2][...] for i in range(n_layers)]

            def chunk(s, r0):
                g = pulls_s[s, pl.ds(r0, R), :]        # (R, ch_in*RL)
                lab = lab_ref[s, pl.ds(r0, R), :]
                msk = msk_ref[s, pl.ds(r0, R), :]
                # pooled channels stacked channel-major on sublanes
                acts = [jnp.concatenate(
                    [g[:, (1 + j) * RL:(2 + j) * RL] for j in range(k)],
                    axis=0)]                           # (k*R, RL)
                pre = []
                for i in range(n_layers):
                    z = _tower_mm(wk[i], acts[-1], ((1,), (0,))) + bk[i]
                    pre.append(z)
                    if i + 1 < n_layers:
                        acts.append(jnp.maximum(z, 0.0))
                margin = g[:, 0:RL] + pre[-1]          # wide + deep
                mg_ref[s, pl.ds(r0, R), :] = margin
                dual = dual_fn(margin, lab, msk)
                d = dual
                for i in reversed(range(n_layers)):
                    g_refs[2 * i][...] += _tower_mm(d, acts[i],
                                                    ((1,), (1,)))
                    g_refs[2 * i + 1][...] += jnp.sum(d, axis=1,
                                                      keepdims=True)
                    d = _tower_mm(wkt[i], d, ((1,), (0,)))
                    if i:
                        d = jnp.where(pre[i - 1] > 0.0, d, 0.0)
                # [dual, g_pooled..., mask], channel-major on lanes
                return jnp.concatenate(
                    [dual] + [d[j * R:(j + 1) * R] for j in range(k)]
                    + [msk], axis=1)                   # (R, ch_out*RL)

            def step(b, carry):
                s = b // (NI // UN)
                r0 = pl.multiple_of((b % (NI // UN)) * (UN * R), UN * R)
                dv = jnp.concatenate(
                    [chunk(s, r0 + u * R) for u in range(UN)], axis=0)
                off = pl.multiple_of((s % bp) * RH + r0, UN * R)
                dual_s[s // bp, pl.ds(off, UN * R), :] = \
                    dv.astype(jnp.bfloat16)
                return carry

            jax.lax.fori_loop(0, S * NI // UN, step, 0)

        @pl.when(t >= nt)
        def _bwd():
            _bwd_multi_kernel(spec, ch_out, pw_ref, dual_s, push_ref)

    return kernel


@lru_cache(maxsize=None)
def _build_wd_step_fused(spec: TileSpec, k: int,
                         hidden: Tuple[int, ...], loss: str):
    """Fused wide&deep step: (margins (rows,), pushes (nb, k+2), g_mlp
    tree). Both embedding phases run under ONE grid spec sized by the
    wider channel count (ch_out = k+2) — margins and pushes are
    tile-sequential accumulations, so they are bitwise-independent of
    the tiles_step split and match the split wrappers' (differently
    blocked) results exactly."""
    ch_in, ch_out = 1 + k, k + 2
    spec = _multi_spec(spec, ch_out)
    T, TB = spec.tiles, spec.tiles_step
    SG, N, S = spec.subblocks // spec.group, spec.n, spec.subblocks
    bp = _bp(spec)
    nt = T // TB
    sizes = [k] + list(hidden) + [1]
    n_layers = len(sizes) - 1
    R = MLP_ROWS
    kernel = _make_wd_step_kernel(spec, ch_in, ch_out, k, n_layers,
                                  loss, nt)
    const_grid = pl.BlockSpec((S, RH, RL), lambda t: (0, 0, 0))

    def whole(shape):
        return pl.BlockSpec(shape, lambda t: (0, 0))

    @jax.jit
    def step(pw, wpull, labels, mask, mlp):
        # (nb, ch_in) -> (T, A_HI, ch_in*B_LO): channel-major lanes
        wt = (wpull.reshape(T, A_HI, B_LO, ch_in).transpose(0, 1, 3, 2)
              .reshape(T, A_HI, ch_in * B_LO).astype(jnp.bfloat16))
        args = [pw, wt, labels.reshape(S, RH, RL),
                mask.reshape(S, RH, RL)] + _wd_blocked_params(mlp,
                                                              n_layers)
        in_specs = [
            pl.BlockSpec((TB, SG, N), lambda t: (t % nt, 0, 0)),
            pl.BlockSpec((TB, A_HI, ch_in * B_LO),
                         lambda t: (jnp.minimum(t, nt - 1), 0, 0)),
            const_grid, const_grid,
        ]
        g_specs, g_shapes = [], []
        for a, b in zip(sizes, sizes[1:]):
            in_specs += [whole((b * R, a * R)), whole((a * R, b * R)),
                         whole((b * R, 1))]
            g_specs += [whole((b * R, a * R)), whole((b * R, 1))]
            g_shapes += [jax.ShapeDtypeStruct((b * R, a * R), jnp.float32),
                         jax.ShapeDtypeStruct((b * R, 1), jnp.float32)]
        outs = pl.pallas_call(
            kernel,
            grid=(2 * nt,),
            in_specs=in_specs,
            out_specs=[
                const_grid,
                pl.BlockSpec((TB, A_HI, ch_out * B_LO),
                             lambda t: (jnp.maximum(t - nt, 0), 0, 0)),
            ] + g_specs,
            out_shape=[
                jax.ShapeDtypeStruct((S, RH, RL), jnp.float32),
                jax.ShapeDtypeStruct((T, A_HI, ch_out * B_LO),
                                     jnp.float32),
            ] + g_shapes,
            scratch_shapes=[
                pltpu.VMEM((S, RH, ch_in * RL), jnp.float32),
                pltpu.VMEM((S // bp, bp * RH, ch_out * RL),
                           jnp.bfloat16),
            ],
            compiler_params=None if _interpret() else pltpu.CompilerParams(
                vmem_limit_bytes=100 * 1024 * 1024),
            interpret=_interpret(),
        )(*args)
        mg, push = outs[0], outs[1]
        pushes = (push.reshape(T, A_HI, ch_out, B_LO)
                  .transpose(0, 1, 3, 2).reshape(spec.nb, ch_out))
        return (mg.reshape(spec.block_rows), pushes,
                _wd_unblock_grads(outs[2:], sizes))

    return step


# -- fused-step public surface (call inside a jitted step) ------------------

def fused_step_grad(pw: jax.Array, w: jax.Array, labels: jax.Array,
                    mask: jax.Array, spec: TileSpec, loss: str,
                    exact_dense: bool, cache: bool = False,
                    spill_margins: Optional[jax.Array] = None
                    ) -> Tuple[jax.Array, jax.Array]:
    """One-grid margins + dual + grad: (margins (block_rows,),
    grad (nb,)), bitwise-identical to forward_margins -> dual_fn
    [-> nudge] -> backward_grad. ``cache`` stages/replays the one-hot
    planes across the phases (resolve_step_kernel decides; parity is
    unchanged). ``spill_margins`` is the pre-aggregated spill grid
    (spill_margin_rows) summed in before the dual — the caller runs
    spill_grad_scatter on the returned grad with the dual it recomputes
    from the returned margins (elementwise, so bitwise-equal to the
    in-kernel dual). Callers must have resolved the geometry admissible
    (resolve_step_kernel)."""
    if spill_margins is None:
        return _build_step_grad(spec, loss, exact_dense, cache)(
            pw, w, labels, mask)
    return _build_step_grad(spec, loss, exact_dense, cache, True)(
        pw, w, labels, mask, spill_margins)


def fused_step_update(pw: jax.Array, planes, labels: jax.Array,
                      mask: jax.Array, spec: TileSpec, loss: str,
                      handle, cache: bool = False):
    """One-grid margins + dual + grad + in-place FTRL over the table's
    (w, z, cg) planes (learners/table.py): (margins, new planes,
    Σ(w_new − w_old)²). ``handle`` is the FTRLHandle whose update()
    runs in-kernel. The gradient never exists in HBM — single-process,
    spill-free blocks only (multihost gradients must cross the wire
    first and spill scatters need the grad in HBM; use
    fused_step_grad)."""
    return _build_step_update(spec, loss, handle, cache)(
        pw, tuple(planes), labels, mask)


def fused_fm_step(pw: jax.Array, theta, labels: jax.Array,
                  mask: jax.Array, spec: TileSpec, k: int, loss: str,
                  spill_pulls: Optional[jax.Array] = None):
    """One-grid FM step: (margins (block_rows,), the k+2 push planes)
    from ``theta``, the table's float32 (T, A_HI, B_LO) planes
    ``(w, v_1..v_k)``; the operand [w, v_j..., Σv²] is formed in VMEM.
    Neither the pulls nor the dual-channel grid touches HBM; the AdaGrad
    update stays in XLA (it is elementwise over buckets either way).
    With ``spill_pulls`` (the pre-aggregated (rows, k+2) grid from
    fm_spill_pull_rows) the boundary sums it into the pulls and a third
    result — the (rows, k+2) dual-channel values — comes back for the
    caller's XLA spill_push_scatter_planes."""
    theta = tuple(theta)
    if spill_pulls is None:
        return _build_fm_step_fused(spec, k, loss)(
            pw, theta, labels, mask)
    return _build_fm_step_fused(spec, k, loss, True)(
        pw, theta, labels, mask, spill_pulls)


def fused_fm_step_update(pw: jax.Array, planes, labels: jax.Array,
                         mask: jax.Array, spec: TileSpec, k: int,
                         loss: str, update):
    """One-grid FM step with the update inside: (margins, the 2(1+k)
    updated planes, Σ(Δw)²) from the table's planes, which go back as
    the next step's state."""
    return _build_fm_step_update(spec, k, loss, update)(
        pw, tuple(planes), labels, mask)


def fused_wd_step(pw: jax.Array, wpull: jax.Array, labels: jax.Array,
                  mask: jax.Array, mlp: dict, spec: TileSpec, k: int,
                  hidden: Tuple[int, ...], loss: str):
    """One-grid wide&deep step: (margins (rows,), pushes (nb, k+2),
    g_mlp param-grad tree) — the embedding pulls, the in-kernel MLP
    forward/backward, the dual, and the pushes in one dispatch. Spill-free
    blocks only (resolve_step_kernel sends wd spill to split); the
    sparse/dense updates stay in XLA, identical to the split tail."""
    return _build_wd_step_fused(spec, k, tuple(hidden), loss)(
        pw, wpull, labels, mask, mlp)


# -- public jit-safe surface (call inside a jitted step) --------------------

def forward_margins(pw: jax.Array, w: jax.Array,
                    spec: TileSpec,
                    ovf_b: Optional[jax.Array] = None,
                    ovf_r: Optional[jax.Array] = None) -> jax.Array:
    """margins (block_rows,) = sum of w[bucket] over each row's pairs.
    The spill margins come in as ONE pre-aggregated grid add
    (spill_margin_rows) — the same add the fused boundary phase runs,
    so the two paths stay bitwise-identical."""
    margins = _build_fwd(spec)(pw, w)
    if ovf_b is not None and ovf_b.shape[0]:
        margins = margins + spill_margin_rows(w, ovf_b, ovf_r, spec)
    return margins


def backward_grad(pw: jax.Array, dual_rows: jax.Array,
                  spec: TileSpec,
                  ovf_b: Optional[jax.Array] = None,
                  ovf_r: Optional[jax.Array] = None) -> jax.Array:
    """G (nb,) = per-bucket sum of dual over the bucket's pairs."""
    g = _build_bwd(spec)(pw, dual_rows)
    if ovf_b is not None and ovf_b.shape[0]:
        g = spill_grad_scatter(g, dual_rows, ovf_b, ovf_r, spec)
    return g


# -- slow exact reference (tests / differential checking) -------------------

def forward_margins_ref(buckets: np.ndarray, rows: np.ndarray,
                        w: np.ndarray, block_rows: int) -> np.ndarray:
    out = np.zeros(block_rows, np.float64)
    np.add.at(out, rows, np.asarray(w, np.float64)[buckets])
    return out.astype(np.float32)


def backward_grad_ref(buckets: np.ndarray, rows: np.ndarray,
                      dual_rows: np.ndarray, nb: int) -> np.ndarray:
    out = np.zeros(nb, np.float64)
    np.add.at(out, buckets, np.asarray(dual_rows, np.float64)[rows])
    return out.astype(np.float32)
