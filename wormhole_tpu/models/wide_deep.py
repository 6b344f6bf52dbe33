"""Wide & Deep on the sharded embedding table.

Second BASELINE.json stretch model: the wide part is the linear term over
hashed sparse features (the existing learner), the deep part an MLP over
the value-weighted sum-pooled k-dim embeddings of the row's features
(Cheng et al. 2016's dense path, field-agnostic pooled variant — our rows
are generic hashed bags, not fixed field slots).

margin(row) = Σᵢ wᵢxᵢ  +  MLP( Σᵢ xᵢ·vᵢ )

Parameters:
- sparse: one sharded ``(num_buckets, 1 + k + 1 + k)`` table
  ``[w, v, cg_w, cg_v]`` over the ``model`` mesh axis (same layout idea as
  the FM store; on one device, where the tile kernels step it, kept as one
  plane a channel in their layout: learners/table.py);
- dense: MLP weights, replicated, updated with AdaGrad as well.

Both parts train jointly in one jitted step via ``jax.grad`` through the
whole forward; sparse grads delta-scatter into the table, dense grads
update in place. Pluggable into the AsyncSGD driver (store surface).
"""

from __future__ import annotations

from dataclasses import dataclass, field
from functools import partial
from typing import List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from wormhole_tpu.data.feed import SparseBatch
from wormhole_tpu.learners import table as tbl
from wormhole_tpu.learners.store import (TableCheckpoint,
                                          TileStep,
                                          factor_table,
                                          mesh_ovf_zeros,
                                          mesh_step_ici_bytes,
                                          mesh_tile_geometry)
from wormhole_tpu.ops import overflow
from wormhole_tpu.ops.loss import create_loss
from wormhole_tpu.ops.metrics import accuracy, auc
from wormhole_tpu.ops.spmv import spmv_times
from wormhole_tpu.parallel.mesh import MeshRuntime
from wormhole_tpu.utils.timer import Timer


@dataclass
class WideDeepConfig:
    num_buckets: int = 1 << 20
    dim: int = 16                      # embedding size k
    hidden: Tuple[int, ...] = (64, 32)
    loss: str = "logit"
    lr_alpha: float = 0.05             # AdaGrad, sparse table
    lr_alpha_dense: float = 0.01       # AdaGrad, MLP
    lr_beta: float = 1.0
    l2_v: float = 1e-5
    init_scale: float = 0.01
    seed: int = 0
    tile_step_kernel: str = "auto"  # auto|fused|split: the MLP runs
                                    # in-kernel at the fused phase
                                    # boundary when its row-blocked
                                    # weights fit the VMEM budget
                                    # (ops/tilemm.resolve_step_kernel)
    tile_onehot_cache: str = "auto"  # auto|on|off — accepted for config
                                     # parity; the multi-channel wd
                                     # kernel always resolves off


def init_mlp(sizes: List[int], rng: np.random.Generator):
    """He-init MLP params as a flat dict pytree (+ AdaGrad accumulators)."""
    params, accum = {}, {}
    for i, (a, b) in enumerate(zip(sizes[:-1], sizes[1:])):
        params[f"W{i}"] = (rng.standard_normal((a, b))
                           * np.sqrt(2.0 / a)).astype(np.float32)
        params[f"b{i}"] = np.zeros(b, np.float32)
    for k, v in params.items():
        accum[k] = np.zeros_like(v)
    return (jax.tree.map(jnp.asarray, params),
            jax.tree.map(jnp.asarray, accum))


# The deep-tower forward lives in ops/tilemm.py beside the fused wd step,
# whose boundary phase runs the same tower over grid-layout chunks —
# re-exported here for the split path and external users.
from wormhole_tpu.ops.tilemm import mlp_forward, tower_flops  # noqa: E402,F401


class WideDeepStore(TableCheckpoint):
    """Sharded embedding table + replicated MLP, fused joint train step."""

    # the one-device spill TRAIN step takes its list in the hot form too
    # (data/crec.HotRoom): AsyncSGD hands the feeds a room
    hot_overflow = True

    def __init__(self, cfg: WideDeepConfig,
                 runtime: Optional[MeshRuntime] = None):
        self.cfg = cfg
        self.rt = runtime
        self.objv_fn, _ = create_loss(cfg.loss)
        # a learner that owns this store reads this timer as its own
        # (AsyncSGD): the tower's work a tile step (tower_flops,
        # dense_param_bytes: counts, not seconds) and, as every
        # TableCheckpoint, any crossing of the table's form (table_cross)
        self.timer = Timer()
        # _count_step adds the pairs on a spill block's list, which
        # TableCheckpoint.put_block counts for a store that keeps this
        self._listed = {}
        # the spill step reads a plane once a listed bucket where
        # put_block sent a long list's distinct buckets beside it
        self._distinct_tiles = 1
        k, nb = cfg.dim, cfg.num_buckets
        rng = np.random.default_rng(cfg.seed)
        # v must break symmetry; w and accumulators start at 0
        v0 = (cfg.init_scale * rng.standard_normal((nb, k))).astype(
            np.float32)
        # One device and whole tiles: the tile steps take this table as
        # one float32 (T, A_HI, B_LO) plane a channel (w, v_1..v_k, cg_w,
        # cg_v_1..k; learners/table.py), the multi-channel kernels' own
        # layout, so it is built so, plane by plane, and the step never
        # re-forms it. Every other path asks _stacked() for (nb, 2(1+k))
        # and gets it, counted.
        self._planar = self.can_be_planar(runtime, np.float32, nb)
        self.slots = factor_table(v0, runtime, self._planar)
        del v0
        sizes = [k] + list(cfg.hidden) + [1]
        self.mlp, self.mlp_accum = init_mlp(sizes, rng)
        self.n_layers = len(sizes) - 1
        # float32 parameters and accumulators, each read and written once
        # by the dense update
        self._dense_bytes = 2 * 2 * 4 * sum(
            a * b + b for a, b in zip(sizes, sizes[1:]))
        self._step = self._build_step()
        self._eval = self._build_eval()
        self.t = 1

    def with_num_buckets(self, nb: int) -> "WideDeepStore":
        """Same config/runtime at ``nb`` buckets (bigmodel hot-tier
        twin / full-size parity oracle). The fresh MLP is discarded by
        paged use — only the embedding table pages; callers wanting the
        trained MLP copy ``mlp``/``mlp_accum`` across."""
        from dataclasses import replace
        return WideDeepStore(replace(self.cfg, num_buckets=nb), self.rt)

    def _forward(self, theta, mlp, batch: SparseBatch):
        w = theta[:, 0]
        v = theta[:, 1:]
        wide = spmv_times(batch.cols, batch.vals, w)
        pooled = jnp.einsum("bnk,bn->bk", v[batch.cols], batch.vals)
        deep = mlp_forward(mlp, pooled, self.n_layers)
        return wide + deep

    def _build_step(self):
        cfg = self.cfg
        k = cfg.dim
        objv_fn = self.objv_fn
        forward = self._forward

        @partial(jax.jit, donate_argnums=(0, 1, 2, 4))
        def step(slots, mlp, accum, batch: SparseBatch, t, tau):
            rows = slots[batch.uniq_keys]
            theta, cg = rows[:, :1 + k], rows[:, 1 + k:]

            def loss_fn(th, m):
                margin = forward(th, m, batch)
                objv = objv_fn(margin, batch.labels, batch.row_mask)
                reg = 0.5 * cfg.l2_v * jnp.sum(
                    (th[:, 1:] * batch.key_mask[:, None]) ** 2)
                return objv + reg, (margin, objv)

            (g_theta, g_mlp), (margin, objv) = jax.grad(
                loss_fn, argnums=(0, 1), has_aux=True)(theta, mlp)

            # sparse AdaGrad
            cg_new = jnp.sqrt(cg * cg + g_theta * g_theta)
            eta = cfg.lr_alpha / (cfg.lr_beta + cg_new)
            theta_new = theta - eta * g_theta
            new_rows = jnp.concatenate([theta_new, cg_new], axis=1)
            delta = (new_rows - rows) * batch.key_mask[:, None]
            # scatter-fallback: uniq-key push, O(uniq) rows — the sparse
            # step is the text and libsvm path's own
            slots = slots.at[batch.uniq_keys].add(delta)

            # dense AdaGrad
            accum = jax.tree.map(lambda a, g: jnp.sqrt(a * a + g * g),
                                 accum, g_mlp)
            mlp = jax.tree.map(
                lambda p, g, a: p - cfg.lr_alpha_dense
                / (cfg.lr_beta + a) * g, mlp, g_mlp, accum)

            num_ex = jnp.sum(batch.row_mask)
            a_ = auc(batch.labels, margin, batch.row_mask)
            acc = accuracy(batch.labels, margin, batch.row_mask)
            # w column only — comparable with the linear store's metric
            wdelta2 = jnp.sum(delta[:, 0] * delta[:, 0])
            return slots, mlp, accum, t + 1, (objv, num_ex, a_, acc, wdelta2)

        return step

    # -- pull-only serving surface (serve/forward.py; see ShardedStore) -----

    def serve_params(self):
        return {"slots": self._stacked(), "mlp": self.mlp}

    def build_serve_margin(self):
        k = self.cfg.dim
        forward = self._forward

        def margin_fn(params, batch: SparseBatch):
            theta = params["slots"][batch.uniq_keys][:, :1 + k]
            return forward(theta, params["mlp"], batch)

        return margin_fn

    def _build_eval(self):
        objv_fn = self.objv_fn
        margin_fn = self.build_serve_margin()

        @jax.jit
        def ev(slots, mlp, batch: SparseBatch):
            margin = margin_fn({"slots": slots, "mlp": mlp}, batch)
            objv = objv_fn(margin, batch.labels, batch.row_mask)
            num_ex = jnp.sum(batch.row_mask)
            a = auc(batch.labels, margin, batch.row_mask)
            acc = accuracy(batch.labels, margin, batch.row_mask)
            return objv, num_ex, a, acc, margin

        return ev

    # -- crec2 tile fast path ------------------------------------------------
    #
    # Binary features make the wide&deep forward a function of pooled
    # per-row sums only: wide = Σ w[b], pooled_j = Σ v_j[b] — the same
    # multi-channel tile pull as the FM path (1+k channels, one one-hot
    # build shared). Backward: dual backprops through the MLP via vjp to
    # d pooled (R, k); the embedding grads are plain channel pushes
    # [dual, dpooled_1..k] plus a row-mask count channel for the exact
    # touched-bucket set.
    #
    # On one device the table is 2(1+k) float32 channel planes in the
    # kernels' (T, A_HI, B_LO) layout (learners/table.py) and the split
    # step never forms (nb, 1+k), (nb, 2+k) or (nb, 2(1+k)): the pull
    # kernel's bfloat16 operand is ONE op over the w and v planes
    # (tilemm.plane_operand), the push kernel's output stays as it wrote
    # it (a channel is a lane block, the overflow pairs scattered into
    # it in place, a lane row a pair), and ONE elementwise pass updates
    # the donated planes.
    # A list with no pair in it stays on the host (put_block), and its
    # block takes the program without the gather and the scatter.
    # A stacked (nb, 2(1+k)) array assigned to ``slots`` (a seeding hook,
    # a restored checkpoint, what the sparse step leaves) is taken by the
    # next tile step as it is, through the (nb, ch) helpers at the
    # kernels' edges, and comes back from that step as planes: the change
    # of form rides in the step's own update pass and is no pass of its
    # own (so no ``table_cross``), at the price of one more program.

    def _step_kernel_args(self, info, oc: int) -> dict:
        # the MLP runs in-kernel at the fused phase boundary when its
        # row-blocked weights fit the VMEM budget; a file whose blocks
        # can spill (with a list or, this once, without) and oversized
        # hidden widths fall back split with a recorded reason
        k = self.cfg.dim
        return {"ovf_cap": info.ovf_cap, "deep": True, "dim": k,
                "hidden": tuple(self.cfg.hidden), "channels": k + 2}

    def _fused_span(self) -> str:
        return "tilemm:mlp_phase"

    def _tile_table(self):
        # as it stands: a stacked array is taken by the step itself
        return self._table

    def _tile_extra(self, train: bool) -> tuple:
        return (self.mlp, self.mlp_accum) if train else (self.mlp,)

    def _take_tile_extra(self, extra) -> None:
        self.mlp, self.mlp_accum = extra

    def _count_step(self, block: dict, info) -> None:
        # the tower's FLOPs, forward and backward, and the dense update's
        # bytes
        self.timer.add("tower_flops", tower_flops(
            info.spec.block_rows, self.cfg.dim, tuple(self.cfg.hidden)))
        self.timer.add("dense_param_bytes", self._dense_bytes)
        # whether the train block brought a list (the spill step's two
        # list phases run) and the pairs on it, into the timer and the
        # registry: a resident click-log shard whose listless count moves
        # is stepping blocks that lost their lists
        from wormhole_tpu.obs import metrics
        spill_c, pairs_c, listless_c = metrics.wd_step_metrics()
        if not overflow.has_list(block):
            self.timer.add("wd_listless_blocks", 1)
            listless_c.inc()
            return
        pairs = self._listed_pairs(block)
        self.timer.add("wd_spill_blocks", 1)
        self.timer.add("wd_listed_pairs", pairs)
        spill_c.inc()
        pairs_c.inc(pairs)

    def _tile_body(self, ts: TileStep):
        """``step(table, mlp, accum, block, t, tau, macc)`` (train) or
        ``step(table, mlp, block)`` (eval). Every variant updates the
        float32 (T, A_HI, B_LO) channel planes; a planar table IS those
        planes, a stacked one is sliced into them inside the step. A
        store that keeps planes gets planes back either way; one that
        does not, the stacked array."""
        from wormhole_tpu.ops import tilemm
        cfg = self.cfg
        k = cfg.dim
        n_layers = self.n_layers
        _, dual_fn = create_loss(cfg.loss)
        spec, oc = ts.spec, ts.oc
        keeps_planes = self._planar

        def coo(lst):
            # a COO list's two arrays for the (nb, ch) helpers' own tails
            return ((None, None) if lst is None
                    else tuple(lst[name] for name in overflow.COO))

        # The phases are jits of their own inside the step, named for
        # what they do, so that the device trace's ops say which phase
        # they belong to: wd_pull, wd_tower (forward under the scope
        # wd_tower_forward, its vjp under wd_tower_backward), wd_push,
        # wd_table_update, wd_dense_update; and inside wd_pull and
        # wd_push the list's halves, wd_ovf_pull (the listed buckets' 1+k
        # values read plane by plane and summed onto their rows) and
        # wd_ovf_scatter (the pairs' k+2 dual channels added into the
        # kernel's pushes). Both take the list in the form it crossed in
        # (ops/overflow.py): hot, a train block's long list of few
        # buckets, a plane read and the pushes added to once a distinct
        # bucket and the pairs through the multi-channel kernel pair
        # over the hot tiles, every float32 value as three bfloat16
        # parts, a call a part; or COO, a slot a pair (a plane read once
        # a listed bucket where the list brings its distinct buckets):
        # a short list, one of mostly distinct buckets, every eval
        # block's. A nested jit and not a bare jax.named_scope (as the
        # mesh step has, learners/store.py): the profiler's op metadata
        # keeps the path of an op inside a nested jit and drops a bare
        # scope's (jit(fwd) in the kept traces). XLA inlines them: the
        # step is one program as before.
        # At the kernels' edges a planar table takes the helpers over
        # planes; a stacked one (``stacked``, static) the (nb, ch) helpers
        # it had, which transpose the operand and the pushes
        @jax.jit
        def wd_ovf_pull(theta, lst):
            helper, first, second = overflow.pick(
                lst, tilemm.plane_spill_pull_rows,
                tilemm.plane_hot_pull_rows)
            # a long COO list brings its distinct buckets beside it
            distinct = overflow.distinct_of(lst)
            return helper(theta, first, second, spec,
                          *(() if distinct is None else (distinct,)))

        @jax.jit
        def wd_ovf_scatter(push, dvals, lst):
            helper, first, second = overflow.pick(
                lst, tilemm.spill_push_scatter_lanes,
                tilemm.hot_push_scatter_lanes)
            return helper(push, dvals, first, second, spec)

        @partial(jax.jit, static_argnums=(0,))
        def wd_pull(stacked, theta, pw, lst):
            if stacked:
                return tilemm.forward_pulls(pw, tbl.join(theta), spec,
                                            *coo(lst))
            # the operand rounded once from the w and v planes; the
            # overflow pairs' values gathered from the planes, unrounded
            pulls = tilemm.plane_pulls(pw, tilemm.plane_operand(theta),
                                       spec)
            if oc:
                pulls = pulls + wd_ovf_pull(theta, lst)
            return pulls

        @jax.jit
        def wd_tower(m, x):
            return mlp_forward(m, x, n_layers)

        @partial(jax.jit, static_argnums=(0,))
        def wd_push(stacked, pw, dual, g_pooled, row_mask, lst):
            dvals = jnp.concatenate(
                [dual[:, None], g_pooled, row_mask[:, None]], axis=1)
            if stacked:
                return tbl.split(tilemm.backward_pushes(
                    pw, dvals, spec, *coo(lst)))
            # the pushes stay as the kernel writes them,
            # (T, A_HI, (k+2)*B_LO): a channel's plane is a lane slice
            push = tilemm.tiled_pushes(pw, dvals, spec)
            if oc:
                return wd_ovf_scatter(push, dvals, lst)
            return tilemm.push_planes(push)

        @jax.jit
        def wd_table_update(planes, push):
            # ONE elementwise pass, k+2 push and 2(1+k) state planes in,
            # 2(1+k) out onto the donated state: AdaGrad (with weight
            # decay on v) on the buckets the block touched (the count
            # channel) and the progress number from the w plane
            theta, cg = planes[:1 + k], planes[1 + k:]
            touched = push[1 + k] > 0
            grads = (push[0],) + tuple(
                p + cfg.l2_v * v * touched
                for p, v in zip(push[1:1 + k], theta[1:]))
            cg_new = tuple(
                jnp.where(touched, jnp.sqrt(a * a + g * g), a)
                for a, g in zip(cg, grads))
            theta_new = tuple(
                jnp.where(touched,
                          th - cfg.lr_alpha / (cfg.lr_beta + a) * g, th)
                for th, a, g in zip(theta, cg_new, grads))
            d0 = theta_new[0] - theta[0]
            return theta_new + cg_new, jnp.sum(d0 * d0)

        @jax.jit
        def wd_dense_update(mlp, accum, g_mlp):
            accum = jax.tree.map(
                lambda a, g: jnp.sqrt(a * a + g * g), accum, g_mlp)
            return jax.tree.map(
                lambda p, g, a: p - cfg.lr_alpha_dense
                / (cfg.lr_beta + a) * g, mlp, g_mlp, accum), accum

        def is_stacked(table, lst) -> bool:
            # the hot helpers know planes alone: a stacked table that
            # meets a hot list (no feed of a cell hands it one) takes the
            # planes' edges over planes_of's slices
            return not (isinstance(table, tbl.PlaneTable)
                        or (oc and overflow.is_hot(lst)))

        def forward(table, mlp, block):
            pw, labels, row_mask, lst = ts.decode(block)
            pulls = wd_pull(is_stacked(table, lst),
                            tbl.planes_of(table)[:1 + k], pw, lst)
            pooled = pulls[:, 1:]
            with jax.named_scope("wd_tower_forward"):
                deep, vjp = jax.vjp(wd_tower, mlp, pooled)
            margin = pulls[:, 0] + deep
            return pw, labels, row_mask, lst, pooled, vjp, margin

        def finish(table, planes, mlp, accum, push, g_mlp, margin, labels,
                   row_mask, t, macc):
            # shared update/metric tail downstream of the push planes
            # and MLP grads — structurally identical XLA in the fused
            # and split programs, so the update bits agree between them.
            # TileStep.finish with this store's own state among the
            # outputs: the loss ahead of the two updates, the metric row
            # after them
            objv = ts.objv_fn(margin, labels, row_mask)
            new, d0_sq = wd_table_update(planes, push)
            mlp_new, accum = wd_dense_update(mlp, accum, g_mlp)
            packed, num_ex = ts.metric_row(d0_sq, margin, labels, row_mask,
                                           objv)
            new = (tbl.PlaneTable(new) if keeps_planes
                   else tbl.table_like(new, table))
            return new, mlp_new, accum, t + 1, macc + packed, num_ex

        if ts.fused:
            # one grid: embedding pulls, in-kernel MLP forward/backward at
            # the phase boundary, dual, channel pushes and MLP param
            # grads in a single dispatch (resolve_step_kernel admits
            # this only for spill-free blocks within the VMEM budget).
            # Its kernel takes (nb, 1+k) and gives (nb, k+2): formed from
            # the planes and sliced back into them here
            def step(table, mlp, accum, block, t, tau, macc):
                planes = tbl.planes_of(table)
                pw, labels, row_mask, _lst = ts.decode(block)
                # pull, tower and push are one kernel here
                margin, push, g_mlp = tilemm.fused_wd_step(
                    pw, tbl.join(planes[:1 + k]), labels, row_mask, mlp,
                    spec, k, tuple(cfg.hidden), cfg.loss)
                return finish(table, planes, mlp, accum, tbl.split(push),
                              g_mlp, margin, labels, row_mask, t, macc)
        elif ts.kind == "train":
            def step(table, mlp, accum, block, t, tau, macc):
                (pw, labels, row_mask, lst, pooled, vjp,
                 margin) = forward(table, mlp, block)
                dual = dual_fn(margin, labels, row_mask)
                with jax.named_scope("wd_tower_backward"):
                    g_mlp, g_pooled = vjp(dual)
                push = wd_push(is_stacked(table, lst), pw, dual, g_pooled,
                               row_mask, lst)
                return finish(table, tbl.planes_of(table), mlp, accum,
                              push, g_mlp, margin, labels, row_mask, t,
                              macc)
        else:
            def step(table, mlp, block):
                (_, labels, row_mask, _, _, _,
                 margin) = forward(table, mlp, block)
                return ts.evaluate(margin, labels, row_mask)

        return step

    def _tile_step_mesh(self, info, kind: str):
        """Distributed wide&deep tile step (same mesh geometry as the FM
        and linear stores): the MODEL axis shards the embedding-table
        tiles, the DATA axis shards blocks; pooled pulls psum over
        model, channel pushes and MLP gradients psum over data, the MLP
        parameters stay replicated."""
        key = (info, kind, "mesh")
        fn = getattr(self, "_tile_cache", {}).get(key)
        if fn is not None:
            return fn
        from wormhole_tpu.ops import tilemm
        from wormhole_tpu.ops.metrics import margin_hist
        from wormhole_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                                shard_map_compat)
        cfg = self.cfg
        k = cfg.dim
        n_layers = self.n_layers
        objv_fn = self.objv_fn
        _, dual_fn = create_loss(cfg.loss)
        from wormhole_tpu.learners.store import (mesh_macc_row,
                                                 mesh_metric_sums,
                                                 mesh_tile_geometry,
                                                 shard_range_mask)
        mesh = self.rt.mesh
        spec = info.spec
        nb_local, spec_local, have_model = mesh_tile_geometry(self.rt,
                                                              spec)
        oc, R = info.ovf_cap, info.block_rows

        def body(slots_l, mlp, accum, pw_l, lab_l, ovb_l, ovr_l, t, tau,
                 macc):
            pw1 = pw_l[0].reshape(spec_local.pairs_shape)
            lab = lab_l[0]
            row_mask = (lab != jnp.uint8(255)).astype(jnp.float32)
            labels = jnp.minimum(lab, 1).astype(jnp.float32)
            s32 = slots_l.astype(jnp.float32)
            theta, cg = s32[:, :1 + k], s32[:, 1 + k:]
            v = theta[:, 1:]
            wpull = jnp.concatenate([theta[:, :1], v], axis=1)
            pulls = tilemm.forward_pulls(pw1, wpull, spec_local)
            off = (jax.lax.axis_index(MODEL_AXIS) * nb_local
                   if have_model else 0)
            if oc:
                ovb, ovr = ovb_l[0], ovr_l[0]
                valid, idx = shard_range_mask(ovb, off, nb_local)
                wv = jnp.where(valid[:, None], wpull[idx], 0.0)
                # scatter-fallback: COO overflow spill, O(ovf_cap)
                pulls = pulls.at[ovr.astype(jnp.int32) % R].add(wv)
            pulls = (jax.lax.psum(pulls, MODEL_AXIS) if have_model
                     else pulls)
            pooled = pulls[:, 1:]
            deep_fn = lambda mm, x: mlp_forward(mm, x, n_layers)  # noqa
            deep, vjp = jax.vjp(deep_fn, mlp, pooled)
            margin = pulls[:, 0] + deep
            objv = objv_fn(margin, labels, row_mask)
            num_ex = jnp.sum(row_mask)
            acc = accuracy(labels, margin, row_mask)
            pos, neg = margin_hist(labels, margin, row_mask)
            objv_g, tot_ex, acc_frac, pos_g, neg_g = mesh_metric_sums(
                objv, num_ex, acc, pos, neg)
            if kind == "eval":
                return objv_g, tot_ex, acc_frac, pos_g, neg_g, margin
            dual = dual_fn(margin, labels, row_mask)
            g_mlp, g_pooled = vjp(dual)
            # MLP params are replicated; their per-shard gradients cover
            # only the shard's rows — sum over the data axis
            g_mlp = jax.tree.map(lambda g: jax.lax.psum(g, DATA_AXIS),
                                 g_mlp)
            dvals = jnp.concatenate(
                [dual[:, None], g_pooled, row_mask[:, None]], axis=1)
            push = tilemm.backward_pushes(pw1, dvals, spec_local)
            if oc:
                dv = jnp.where(valid[:, None],
                               dvals[ovr.astype(jnp.int32) % R], 0.0)
                # scatter-fallback: COO overflow spill, O(ovf_cap)
                push = push.at[idx].add(dv)
            push = jax.lax.psum(push, DATA_AXIS)
            touched = push[:, 1 + k] > 0
            g_v = push[:, 1:1 + k] + cfg.l2_v * v * touched[:, None]
            grads = jnp.concatenate([push[:, :1], g_v], axis=1)
            cg_new = jnp.where(touched[:, None],
                               jnp.sqrt(cg * cg + grads * grads), cg)
            eta = cfg.lr_alpha / (cfg.lr_beta + cg_new)
            theta_new = jnp.where(touched[:, None], theta - eta * grads,
                                  theta)
            new = jnp.concatenate([theta_new, cg_new], axis=1)
            accum = jax.tree.map(
                lambda a, g: jnp.sqrt(a * a + g * g), accum, g_mlp)
            mlp_new = jax.tree.map(
                lambda p, g, a: p - cfg.lr_alpha_dense
                / (cfg.lr_beta + a) * g, mlp, g_mlp, accum)
            d0 = theta_new[:, 0] - theta[:, 0]
            wdelta2 = jnp.sum(d0 * d0)
            if have_model:
                wdelta2 = jax.lax.psum(wdelta2, MODEL_AXIS)
            packed = mesh_macc_row(objv_g, tot_ex, acc_frac, wdelta2,
                                   pos_g, neg_g)
            return (new.astype(slots_l.dtype), mlp_new, accum, t + 1,
                    macc + packed)

        from jax.sharding import PartitionSpec as P
        from wormhole_tpu.learners.store import mesh_step_specs
        Pm, Pblk, _ = mesh_step_specs(have_model)
        Pmlp = jax.tree.map(lambda _: P(), self.mlp)
        data_specs = (Pm, Pmlp, Pmlp, Pblk, P(DATA_AXIS, None),
                      P(DATA_AXIS, None), P(DATA_AXIS, None))
        if kind == "train":
            in_specs = data_specs + (P(), P(), P())
            out_specs = (Pm, Pmlp, Pmlp, P(), P())
            fn = body
        else:
            in_specs = data_specs

            def fn(s, mm, aa, pw_, lab_, ovb_, ovr_):
                return body(s, mm, aa, pw_, lab_, ovb_, ovr_,
                            jnp.float32(0), jnp.float32(0),
                            jnp.float32(0))
            out_specs = (P(), P(), P(), P(), P(), P(DATA_AXIS))
        step = jax.jit(
            shard_map_compat(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs),
            donate_argnums=(0, 1, 2, 7, 9) if kind == "train" else ())
        if not hasattr(self, "_tile_cache"):
            self._tile_cache = {}
        self._tile_cache[key] = step
        return step

    def tile_train_step_mesh(self, blocks: dict, info, tau: float = 0.0):
        """Mesh wide&deep tile step over ``data_axis_size`` blocks
        stacked on a leading axis (ShardedStore calling convention)."""
        oc = info.ovf_cap
        D = self.rt.data_axis_size
        step = self._tile_step_mesh(info, "train")
        z = mesh_ovf_zeros(D, oc)
        # pull channels: w + pooled[dim]; push adds the row-mask ticket;
        # replicated MLP grads psum over data as an extra payload
        ch = self.cfg.dim + 1
        nb_local = mesh_tile_geometry(self.rt, info.spec)[0]
        mlp_elems = sum(int(np.asarray(p).size)
                        for p in jax.tree.leaves(self.mlp))
        (self.slots, self.mlp, self.mlp_accum, t_new,
         self._macc) = self.mesh_transport().dispatch(
            step, self._mesh_table(), self.mlp, self.mlp_accum,
            blocks["pw"], blocks["labels"],
            blocks.get("ovf_b", z), blocks.get("ovf_r", z),
            self._t_device(), self._tau_const(tau), self._macc_buf(),
            ici_bytes=mesh_step_ici_bytes(
                self.rt, margin_elems=info.block_rows * ch,
                grad_elems=nb_local * (ch + 1),
                extra_data_elems=mlp_elems))
        self._advance_t(t_new)
        return t_new

    def tile_eval_step_mesh(self, blocks: dict, info):
        oc = info.ovf_cap
        D = self.rt.data_axis_size
        z = mesh_ovf_zeros(D, oc)
        ch = self.cfg.dim + 1
        return self.mesh_transport().dispatch(
            self._tile_step_mesh(info, "eval"),
            self._mesh_table(), self.mlp, self.mlp_accum, blocks["pw"],
            blocks["labels"], blocks.get("ovf_b", z),
            blocks.get("ovf_r", z),
            ici_bytes=mesh_step_ici_bytes(
                self.rt, margin_elems=info.block_rows * ch,
                train=False))

    # -- ShardedStore surface ------------------------------------------------

    def train_step(self, batch: SparseBatch, tau: float = 0.0):
        self.slots, self.mlp, self.mlp_accum, t_new, metrics = self._step(
            self._stacked(), self.mlp, self.mlp_accum, batch,
            self._t_device(), self._tau_const(tau))
        self._advance_t(t_new)
        return metrics

    def eval_step(self, batch: SparseBatch):
        return self._eval(self._stacked(), self.mlp, batch)

    def nnz_weight(self) -> int:
        return int(jnp.sum(self.slots[:, 0] != 0))

    def state_pytree(self):
        base = super().state_pytree()
        base.update(mlp=self.mlp, accum=self.mlp_accum)
        return base

    def restore_pytree(self, state) -> None:
        super().restore_pytree(state)
        self.mlp = jax.tree.map(jnp.asarray, state["mlp"])
        self.mlp_accum = jax.tree.map(jnp.asarray, state["accum"])

    def save_model(self, path: str, rank: Optional[int] = None,
                   key_fold: str = "") -> None:
        if rank is None:
            rank = jax.process_index()
        k = self.cfg.dim
        arr = np.asarray(self._stacked()[:, :1 + k])
        dense = {f"mlp_{k2}": np.asarray(v) for k2, v in self.mlp.items()}
        np.savez_compressed(f"{path}_{rank}.npz", w=arr[:, 0],
                            v=arr[:, 1:], **dense)

    def load_model(self, path: str, expect_key_fold: str = "") -> None:
        data = np.load(path)
        like = self._stacked()
        slots = np.array(like)
        slots[:, 0] = data["w"]
        slots[:, 1:1 + self.cfg.dim] = data["v"]
        self.slots = jax.device_put(jnp.asarray(slots), like.sharding)
        self.mlp = {k.replace("mlp_", ""): jnp.asarray(v)
                    for k, v in data.items() if k.startswith("mlp_")}


def build_app(argv):
    """The app of ``python -m wormhole_tpu.models.wide_deep [conf]
    train_data=<uri> dim=32 hidden=1024,512,256 [key=val ...]``: the
    AsyncSGD driver with a WideDeepStore plugged in (what ``main`` runs,
    and what anything that wants the same app builds).

    ``key=val`` routing mirrors the FM CLI: WideDeepConfig fields go to
    the model, the rest to the driver Config, with ``num_buckets`` /
    ``loss`` / ``seed`` mirrored from the driver."""
    import dataclasses as _dc

    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import apply_kvs, load_config

    args = list(argv)
    conf = args.pop(0) if args and "=" not in args[0] else None
    shared = {"num_buckets", "loss", "seed", "tile_step_kernel",
              "tile_onehot_cache"}
    model_keys = {f.name for f in _dc.fields(WideDeepConfig)} - shared
    model_kvs = [a for a in args
                 if a.partition("=")[0].strip() in model_keys]
    cfg = load_config(conf, [a for a in args if a not in model_kvs])
    mcfg = WideDeepConfig(num_buckets=cfg.num_buckets,
                          loss=cfg.loss.value, seed=cfg.seed,
                          tile_step_kernel=cfg.tile_step_kernel,
                          tile_onehot_cache=cfg.tile_onehot_cache)
    apply_kvs(mcfg, model_kvs)
    rt = MeshRuntime.create(cfg.mesh_shape)
    return AsyncSGD(cfg, rt, store=WideDeepStore(mcfg, rt))


def main(argv=None) -> int:
    """CLI: ``python -m wormhole_tpu.models.wide_deep [conf]
    train_data=<uri> hidden=64,32 [key=val ...]`` (build_app); ingest
    flows through the shared DeviceFeed pipeline."""
    import sys
    build_app(sys.argv[1:] if argv is None else argv).run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
