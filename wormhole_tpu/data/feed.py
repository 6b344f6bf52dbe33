"""Device feed: pad ragged CSR minibatches into fixed-shape dense arrays.

This is the TPU-specific piece with no direct reference analogue (SURVEY.md
§7 stage 1): XLA compiles per shape, so sparse minibatches are padded/bucketed
into a small set of static shapes — ``(mb, max_nnz)`` index/value arrays plus
masks — and the per-batch unique-key vector (from the Localizer) is padded to
a bucketed length. Padding entries point at local id 0 with value 0, so every
op (gather, segment-sum scatter) treats them as no-ops; padded keys carry a
zero mask so their parameter updates vanish.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import numpy as np

from wormhole_tpu.data.localizer import Localized
from wormhole_tpu.data.rowblock import RowBlock


@jax.tree_util.register_dataclass
@dataclass
class SparseBatch:
    """Fixed-shape padded sparse minibatch (a pytree of arrays).

    cols[i, j] is the *local* feature id of the j-th entry of row i (0 when
    padded — harmless because vals is 0 there); uniq_keys maps local ids back
    to global bucket ids for parameter pull/push.
    """

    cols: jax.Array       # int32 (mb, max_nnz)
    vals: jax.Array       # f32   (mb, max_nnz); 0 on padding
    labels: jax.Array     # f32   (mb,)
    row_mask: jax.Array   # f32   (mb,); 1 real row, 0 padded row
    uniq_keys: jax.Array  # int64/int32 (kpad,); global bucket id per local id
    key_mask: jax.Array   # f32   (kpad,); 1 real key, 0 padding

    @property
    def batch_size(self) -> int:
        return self.cols.shape[0]

    @property
    def num_local_keys(self) -> int:
        return self.uniq_keys.shape[0]

    def num_examples(self) -> int:
        return int(np.asarray(self.row_mask).sum())


def _scatter_padded(blk: RowBlock, mb: int, max_nnz: int):
    """Shared CSR→padded-dense scatter: (cols, vals, labels, row_mask).

    Rows with more than ``max_nnz`` entries are truncated positionally (the
    first ``max_nnz`` entries in storage order are kept)."""
    n = blk.size
    assert n <= mb, (n, mb)
    cols = np.zeros((mb, max_nnz), np.int32)
    vals = np.zeros((mb, max_nnz), np.float32)
    if blk.nnz:
        per_row = np.diff(blk.offset).astype(np.int64)
        row_ids = np.repeat(np.arange(n, dtype=np.int64), per_row)
        pos = np.arange(blk.nnz, dtype=np.int64) - np.repeat(
            blk.offset[:-1].astype(np.int64), per_row)
        keep = pos < max_nnz
        cols[row_ids[keep], pos[keep]] = blk.index[keep].astype(np.int64)
        vals[row_ids[keep], pos[keep]] = blk.values_or_ones()[keep]
    labels = np.zeros(mb, np.float32)
    labels[:n] = blk.label
    row_mask = np.zeros(mb, np.float32)
    row_mask[:n] = 1.0
    if blk.weight is not None:
        row_mask[:n] = blk.weight
    return cols, vals, labels, row_mask


def next_bucket(n: int, minimum: int = 256) -> int:
    """Round up to a power of two (shape-bucketing to bound recompiles)."""
    b = minimum
    while b < n:
        b <<= 1
    return b


def pad_to_batch(loc: Localized, minibatch_size: int,
                 max_nnz: int, key_pad: Optional[int] = None,
                 key_dtype=np.int32) -> SparseBatch:
    """Pad a localized RowBlock into a SparseBatch.

    Rows with more than ``max_nnz`` entries are truncated positionally (the
    first ``max_nnz`` entries in storage order are kept).

    ``uniq_keys`` must fit ``key_dtype``: use Localizer bucket folding (or an
    explicitly 64-bit dtype) for raw 64-bit id spaces — a silent wraparound
    would corrupt parameter pull/push, so it raises instead."""
    blk = loc.block
    mb = minibatch_size
    cols, vals, labels, row_mask = _scatter_padded(blk, mb, max_nnz)

    k = len(loc.uniq_keys)
    kpad = key_pad or next_bucket(k)
    if k > kpad:
        raise ValueError(
            f"batch has {k} unique keys but key_pad={kpad}: raise "
            "key_pad (it must cover minibatch x max row nnz worth of "
            "distinct hashed keys) or lower minibatch")
    if k and int(loc.uniq_keys.max()) > np.iinfo(key_dtype).max:
        raise OverflowError(
            f"uniq key {int(loc.uniq_keys.max())} exceeds {np.dtype(key_dtype)}; "
            "fold the key space with Localizer(num_buckets=...) or pass "
            "key_dtype=np.int64")
    uniq = np.zeros(kpad, key_dtype)
    uniq[:k] = loc.uniq_keys.astype(key_dtype)
    key_mask = np.zeros(kpad, np.float32)
    key_mask[:k] = 1.0

    out = SparseBatch(cols=cols, vals=vals, labels=labels, row_mask=row_mask,
                      uniq_keys=uniq, key_mask=key_mask)
    # plain attribute (not a pytree leaf, dropped by device_put): lets eval
    # consumers distinguish padded rows from real rows whose example weight
    # is 0 — row_mask alone can't
    out.num_real = blk.size
    return out


def nnz_bucket(densest: int, cap: int = 4096) -> int:
    """The per-row padded-nnz bucketing policy: power-of-two, min 8,
    capped (denser rows are positionally truncated)."""
    return min(next_bucket(max(densest, 1), 8), cap)


def batch_max_nnz(blk: RowBlock, cap: int = 4096) -> int:
    return nnz_bucket(blk.max_row_nnz(), cap)


@jax.tree_util.register_dataclass
@dataclass
class DenseBatch:
    """Fixed-shape padded batch in *global* feature space (no localization).

    Used by the BSP apps (k-means, L-BFGS linear) whose model lives as a
    full dense array over all ``num_features`` columns — the reference's
    ``RowBlockIter`` path (kmeans.cc:155-160, lbfgs-linear/linear.cc:229-234)
    where feature ids index the model directly.
    """

    cols: jax.Array      # int32 (mb, max_nnz) global feature id; 0 on padding
    vals: jax.Array      # f32   (mb, max_nnz); 0 on padding
    labels: jax.Array    # f32   (mb,)
    row_mask: jax.Array  # f32   (mb,)

    @property
    def batch_size(self) -> int:
        return self.cols.shape[0]


def pad_block_global(blk: RowBlock, minibatch_size: int,
                     max_nnz: int) -> DenseBatch:
    """Pad a RowBlock (global uint64 ids) into a DenseBatch.

    Feature ids must fit int32 (use Localizer bucket folding upstream for
    hashed 64-bit spaces). Rows with more than ``max_nnz`` entries are
    truncated positionally."""
    if blk.nnz and blk.max_index() > np.iinfo(np.int32).max:
        raise OverflowError(
            f"feature id {blk.max_index()} exceeds int32; fold the key space")
    cols, vals, labels, row_mask = _scatter_padded(
        blk, minibatch_size, max_nnz)
    return DenseBatch(cols=cols, vals=vals, labels=labels, row_mask=row_mask)
