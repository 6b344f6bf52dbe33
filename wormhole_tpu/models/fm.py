"""Factorization machine on the sharded parameter store.

The BASELINE.json stretch config ("factorization-machine / wide-deep on
Criteo — stretch param-server to TPU embedding tables"): second-order FM
over the same hashed-bucket key space as the linear learner. Each bucket
row holds ``[w, v_1..v_k, cg_w, cg_v1..cg_vk]`` — a weight, a k-dim latent
factor, and their AdaGrad accumulators — so the "parameter server" is now a
genuine sharded embedding table over the ``model`` mesh axis (on one device,
where the tile kernels step it, kept as one plane a channel in their layout:
learners/table.py).

Forward (Rendle 2010):  margin = Σ wᵢxᵢ + ½ Σ_f [(Σᵢ v_{if}xᵢ)² − Σᵢ v²_{if}x²ᵢ]

TPU mapping: pull = one gather of the batch's unique rows; the interaction
term is two einsums over the padded (mb, nnz, k) gathered factors (MXU
work); the backward is ``jax.grad`` through the same expression (no
hand-derived gradients to get wrong); push = AdaGrad + L1L2-prox on w,
AdaGrad + weight decay on v, applied to the gathered rows and delta-
scattered back. Same bounded-staleness driver as the linear learner
(AsyncSGD with store=FMStore).
"""

from __future__ import annotations

from dataclasses import dataclass
from functools import partial
from typing import Optional

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
from wormhole_tpu.ops.penalty import L1L2
from wormhole_tpu.ops.spmv import spmv_times
from wormhole_tpu.parallel.mesh import MeshRuntime
from wormhole_tpu.utils.timer import Timer


@dataclass
class FMConfig:
    num_buckets: int = 1 << 20
    dim: int = 8                  # latent factor size k
    loss: str = "logit"
    lr_alpha: float = 0.05
    lr_beta: float = 1.0
    l1: float = 0.0               # L1 on w (prox)
    l2: float = 0.0               # L2 on w (prox)
    l2_v: float = 1e-4            # weight decay on v (in-loss)
    init_scale: float = 0.01      # v init stddev
    seed: int = 0
    tile_step_kernel: str = "auto"  # auto|fused|split: one-grid fused
                                    # tile train step vs the two-call
                                    # split oracle (ops/tilemm.py)
    tile_onehot_cache: str = "auto"  # auto|on|off — accepted for config
                                     # parity; the multi-channel FM
                                     # kernel shares one one-hot build
                                     # already, so this always resolves
                                     # off (tilemm.resolve_step_kernel)


@dataclass(frozen=True)
class FMAdaGrad:
    """The tile path's update of a bucket's channels, elementwise over
    arrays of one shape — whole (T, A_HI, B_LO) planes in XLA, or a tile
    of each inside the kernel (hashable: the kernel builder caches on
    it). AdaGrad + L1L2 prox on w (the rule of AdaGradHandle), AdaGrad
    with weight decay on v, on the buckets the block touched (the count
    channel) and no others."""
    lr_alpha: float
    lr_beta: float
    l2_v: float
    penalty: L1L2

    def __call__(self, theta, cg, push, one):
        """(w, v_1..v_k), their accumulators and the k+2 pushes
        [Σdual, Σdual·s_j.., count] -> (theta', cg'). ``one`` is
        ``opaque_one(...)`` of a value the compiler cannot see through
        (a float it loads, not a compare it made): every product that
        feeds an add is ``*one``-guarded, so the kernel and XLA round it
        alike (FTRLHandle.update's contract; ops/loss.opaque_one)."""
        (w, *vs), (cg_w, *cg_vs) = theta, cg
        g_w, touched = push[0], push[-1] > 0

        def adagrad(acc, g):
            acc = jnp.where(
                touched, jnp.sqrt((acc * acc) * one + (g * g) * one), acc)
            return acc, self.lr_alpha / (self.lr_beta + acc)

        cg_w, eta = adagrad(cg_w, g_w)
        theta_new = [jnp.where(
            touched,
            self.penalty.solve((w / eta) * one - g_w, 1.0 / eta), w)]
        cg_new = [cg_w]
        for v, acc, p in zip(vs, cg_vs, push[1:-1]):
            # p - v*g_w + l2_v*v as ONE guarded product: XLA folds the
            # guard of a constant's product into the constant
            # ((l2_v*v)*one -> v*(l2_v*one)), which leaves it bare
            g_v = p - (v * (g_w - self.l2_v)) * one
            acc, eta = adagrad(acc, g_v)
            theta_new.append(jnp.where(touched, v - (eta * g_v) * one, v))
            cg_new.append(acc)
        return tuple(theta_new), tuple(cg_new)


# step_kernel's second field when the fused tile step is the in-place one
IN_PLACE = "in place: the AdaGrad update runs inside the kernel"

def fm_margin(theta: jax.Array, batch: SparseBatch) -> jax.Array:
    """theta (kpad, 1+k): col 0 = w, cols 1: = v. Returns (mb,) margins."""
    w = theta[:, 0]
    v = theta[:, 1:]
    lin = spmv_times(batch.cols, batch.vals, w)
    vx = v[batch.cols] * batch.vals[..., None]        # (mb, nnz, k)
    s = jnp.sum(vx, axis=1)                           # (mb, k)
    s2 = jnp.sum(vx * vx, axis=1)                     # (mb, k)
    inter = 0.5 * jnp.sum(s * s - s2, axis=-1)
    return lin + inter


class FMStore(TableCheckpoint):
    """Sharded FM parameters + fused train/eval steps (ShardedStore
    surface, pluggable into the AsyncSGD driver)."""

    # the one-device spill train step takes an overflow list in its hot
    # form too (data/crec.HotRoom), so the app has the feeds make one
    hot_overflow = True

    def __init__(self, cfg: FMConfig, runtime: Optional[MeshRuntime] = None):
        self.cfg = cfg
        self.rt = runtime
        self.objv_fn, self.dual_fn = create_loss(cfg.loss)
        k, nb = cfg.dim, cfg.num_buckets
        rng = np.random.default_rng(cfg.seed)
        # v must break symmetry; w and accumulators start at 0
        v0 = (cfg.init_scale * rng.standard_normal((nb, k))).astype(
            np.float32)
        # crossings of the table's format (scope "table_cross"), as
        # ShardedStore counts them
        self.timer = Timer()
        # _count_step adds the pairs on a spill block's list, which
        # TableCheckpoint.put_block counts for a store that keeps this
        self._listed = {}
        # One device and whole tiles: the tile steps take this table as
        # one float32 (T, A_HI, B_LO) plane a channel (w, v_1..v_k, cg_w,
        # cg_v_1..k; learners/table.py), the multi-channel kernel's own
        # layout, so it is built so and the step never re-forms it. Every
        # other path asks _stacked() for (nb, 2(1+k)) and gets it, counted.
        self._planar = self.can_be_planar(runtime, np.float32, nb)
        self.slots = factor_table(v0, runtime, self._planar)
        self._step = self._build_step()
        self._eval = self._build_eval()
        self.t = 1

    def with_num_buckets(self, nb: int) -> "FMStore":
        """Same config/runtime at ``nb`` buckets (bigmodel hot-tier twin
        / full-size parity oracle). The v init re-draws from cfg.seed
        over the new bucket count — paged runs overwrite hot rows on
        first touch, so only the COLD table's init matters for parity."""
        from dataclasses import replace
        return FMStore(replace(self.cfg, num_buckets=nb), self.rt)

    def _build_step(self):
        cfg = self.cfg
        k = cfg.dim
        objv_fn = self.objv_fn
        penalty = L1L2(cfg.l1, cfg.l2)

        @partial(jax.jit, donate_argnums=(0, 2))
        def step(slots, batch: SparseBatch, t, tau):
            rows = slots[batch.uniq_keys]              # (kpad, 2(1+k))
            theta, cg = rows[:, :1 + k], rows[:, 1 + k:]

            def loss_fn(th):
                margin = fm_margin(th, batch)
                objv = objv_fn(margin, batch.labels, batch.row_mask)
                reg = 0.5 * cfg.l2_v * jnp.sum(
                    (th[:, 1:] * batch.key_mask[:, None]) ** 2)
                return objv + reg, (margin, objv)

            grads, (margin, objv) = jax.grad(loss_fn, has_aux=True)(theta)
            cg_new = jnp.sqrt(cg * cg + grads * grads)
            eta = cfg.lr_alpha / (cfg.lr_beta + cg_new)
            # w: AdaGrad + L1L2 prox (same rule as AdaGradHandle);
            # v: plain AdaGrad (decay was in the loss)
            w_new = penalty.solve(theta[:, 0] / eta[:, 0] - grads[:, 0],
                                  1.0 / eta[:, 0])
            v_new = theta[:, 1:] - eta[:, 1:] * grads[:, 1:]
            new_rows = jnp.concatenate(
                [w_new[:, None], v_new, cg_new], axis=1)
            delta = (new_rows - rows) * batch.key_mask[:, None]
            # scatter-fallback: uniq-key push, O(uniq) rows — the sparse
            # step is the text and libsvm path's own
            slots = slots.at[batch.uniq_keys].add(delta)
            num_ex = jnp.sum(batch.row_mask)
            a = auc(batch.labels, margin, batch.row_mask)
            acc = accuracy(batch.labels, margin, batch.row_mask)
            # w column only — comparable with the linear store's metric
            wdelta2 = jnp.sum(delta[:, 0] * delta[:, 0])
            return slots, t + 1, (objv, num_ex, a, acc, wdelta2)

        return step

    # -- pull-only serving surface (serve/forward.py; see ShardedStore) -----

    def serve_params(self):
        return {"slots": self._stacked()}

    def build_serve_margin(self):
        k = self.cfg.dim

        def margin_fn(params, batch: SparseBatch):
            theta = params["slots"][batch.uniq_keys][:, :1 + k]
            return fm_margin(theta, batch)

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

    # -- crec2 tile fast path ------------------------------------------------
    #
    # The FM margin needs only three per-row POOLED sums over the row's
    # hashed features (binary x): lin = Σ w[b], s_j = Σ v_j[b], and
    # q = Σ (Σ_j v_j²)[b] — all instances of the multi-channel tile pull
    # (ops/tilemm.forward_pulls, k+2 channels, one one-hot build shared).
    # The backward splits per-pair dv_j = dual·(s_j − v_j[b]) into a
    # row-side push channel (dual·s_j) and a bucket-side correction
    # (v_j ⊙ push(dual)) computed OUTSIDE the kernel; a row-mask "count"
    # channel gives the exact touched-bucket set, so update masking
    # matches the sparse path's update-only-batch-keys semantics.
    # (crec2 used to reject FM; the reference served every model from
    # one data path, async_sgd.h:84-117.)
    #
    # On one device the table is 2(1+k) float32 channel planes in the
    # kernels' (T, A_HI, B_LO) layout (learners/table.py) and the step
    # never re-forms it: the fused kernel reads the w and v planes and
    # rounds the operand [w, v, Σv²] tile by tile in VMEM (Σv² summed in
    # float32 from the unrounded v, then rounded), and applies AdaGrad
    # (FMAdaGrad) to the touched buckets of each tile from the tile's
    # push accumulator, all planes aliased onto its outputs. A block
    # with an overflow list needs the pushes in HBM for the list's own
    # pushes: its kernel writes a push plane a channel, and ONE
    # elementwise pass over planes updates the donated state. The list
    # comes in one of two forms (a pytree structure each, so a program
    # each of the one jit): hot, where data/crec.HotRoom takes a train
    # block's list (a long list of few buckets: its distinct buckets'
    # values gathered once, the pairs through the multi-channel kernel
    # pair over a hot tile, every float32 channel as three bfloat16
    # parts), or COO, a gather and a scatter-add a slot (a short list,
    # one of mostly distinct buckets, every eval block's). A list with
    # no pair in it stays on the host (put_block), so its block takes
    # the first step.

    def _step_kernel_args(self, info, oc: int) -> dict:
        return {"ovf_cap": oc, "channels": self.cfg.dim + 2}

    def _in_place_why(self) -> str:
        return IN_PLACE

    def _fused_span(self) -> str:
        return "tilemm:fused_multi"

    def _tile_body(self, ts: TileStep):
        from wormhole_tpu.ops import tilemm
        from wormhole_tpu.ops.loss import opaque_one
        cfg = self.cfg
        k = cfg.dim
        dual_fn = self.dual_fn
        adagrad = FMAdaGrad(cfg.lr_alpha, cfg.lr_beta, cfg.l2_v,
                            L1L2(cfg.l1, cfg.l2))
        spec, oc = ts.spec, ts.oc

        def forward(planes, block):
            # the split kernel pair's forward half: the operand is ONE
            # XLA op over the w and v planes (fm_operand, which the fused
            # step runs tile by tile in VMEM)
            pw, labels, row_mask, lst = ts.decode(block)
            one = opaque_one(row_mask)
            theta = planes[:1 + k]
            pulls = tilemm.plane_pulls(
                pw, tilemm.fm_operand(theta[0], theta[1:], one), spec)
            if oc:
                pulls = pulls + fm_ovf_pull(theta, lst, one)
            s = pulls[:, 1:1 + k]
            # same guarded channel-by-channel sum the fused kernel runs
            # at its phase boundary — keeps split/fused margins bitwise
            margin = tilemm.fm_margin_math(
                pulls[:, 0], [s[:, j] for j in range(k)], pulls[:, 1 + k],
                one)
            return pw, labels, row_mask, lst, s, margin

        # The phases XLA runs around the kernel are jits of their own, so
        # that the device trace's ops say which phase they belong to (the
        # profiler keeps the path of an op under a nested jit, not under
        # a bare named scope; ShardedStore._tile_body does the same):
        # fm_ovf_pull (the listed buckets' w and v gathered plane by plane,
        # the pairs' pull channels formed unrounded and summed onto their
        # rows), fm_ovf_scatter (the pairs' dual channels added into the
        # push planes), fm_table_update (the one elementwise pass). The
        # first two take the list in either form: through the hot tile
        # and the multi-channel kernel pair at 3(k + 2) parts (the planes
        # read and the push planes added to once a distinct bucket), or
        # a slot a pair.
        @jax.jit
        def fm_ovf_pull(theta, lst, one):
            helper, first, second = overflow.pick(
                lst, tilemm.fm_spill_pull_rows, tilemm.fm_hot_pull_rows)
            return helper(theta, first, second, spec, one)

        @jax.jit
        def fm_ovf_scatter(push, dvals, lst):
            helper, first, second = overflow.pick(
                lst, tilemm.spill_push_scatter_planes,
                tilemm.hot_push_scatter_planes)
            return helper(push, dvals, first, second, spec)

        @jax.jit
        def fm_table_update(planes, push):
            # everything downstream of the push planes: ONE elementwise
            # pass, 10 push and 18 state planes in, 18 out onto the
            # donated state — AdaGrad on the touched buckets (the count
            # channel) and the progress number. The same guarded
            # FMAdaGrad the in-place kernel runs on tiles, so the table's
            # bits agree between every variant. Its ``one`` comes from a
            # push plane: one made of row_mask is a compare in this same
            # program, which the CPU compiler folds to 1.0, guards and all
            theta_new, cg_new = adagrad(planes[:1 + k], planes[1 + k:],
                                        push, opaque_one(push[-1]))
            d0 = theta_new[0] - planes[0]
            return theta_new + cg_new, jnp.sum(d0 * d0)

        def update(table, planes, push, margin, labels, row_mask, t, macc):
            new, wdelta2 = fm_table_update(tuple(planes), tuple(push))
            return ts.finish(tbl.table_like(new, table), wdelta2, margin,
                             labels, row_mask, t, macc)

        if ts.in_place:
            # all 2(1+k) planes go into the kernel as they are, aliased
            # onto its outputs: phase 1 rounds the operand from the w and
            # v tiles, phase 2 updates each tile from its accumulator.
            # Neither the pushes nor anything else table-sized exists
            # outside the call (chip: 74.7 ms a step against 81.1 with
            # the update as an XLA pass, PERF.md section 6, PR 33)
            def step(table, block, t, tau, macc):
                pw, labels, row_mask, _lst = ts.decode(block)
                margin, new, wdelta2 = tilemm.fused_fm_step_update(
                    pw, tbl.planes_of(table), labels, row_mask, spec, k,
                    cfg.loss, adagrad)
                return ts.finish(tbl.table_like(new, table), wdelta2,
                                 margin, labels, row_mask, t, macc)
        elif ts.fused:
            # the kernel reads the w and v planes and writes a push plane
            # a channel, for the one update pass in XLA. With an overflow
            # list the pre-aggregated spill pulls ride in as an extra grid
            # operand (summed into the boundary pulls) and the kernel
            # emits the (rows, ch) dual channels, so the listed pairs'
            # pushes are added in XLA first
            def step(table, block, t, tau, macc):
                planes = tbl.planes_of(table)
                pw, labels, row_mask, lst = ts.decode(block)
                theta = planes[:1 + k]
                if oc:
                    sp = fm_ovf_pull(theta, lst, opaque_one(row_mask))
                    margin, push, dv = tilemm.fused_fm_step(
                        pw, theta, labels, row_mask, spec, k, cfg.loss,
                        spill_pulls=sp)
                    push = fm_ovf_scatter(push, dv, lst)
                else:
                    margin, push = tilemm.fused_fm_step(
                        pw, theta, labels, row_mask, spec, k, cfg.loss)
                return update(table, planes, push, margin, labels,
                              row_mask, t, macc)
        elif ts.kind == "train":
            def step(table, block, t, tau, macc):
                planes = tbl.planes_of(table)
                pw, labels, row_mask, lst, s, margin = forward(planes,
                                                               block)
                dual = dual_fn(margin, labels, row_mask)
                dvals = jnp.concatenate(
                    [dual[:, None], dual[:, None] * s,
                     row_mask[:, None]], axis=1)
                push = tilemm.plane_pushes(pw, dvals, spec)
                if oc:
                    push = fm_ovf_scatter(push, dvals, lst)
                return update(table, planes, push, margin, labels,
                              row_mask, t, macc)
        else:
            def step(table, block):
                (_, labels, row_mask, _, _,
                 margin) = forward(tbl.planes_of(table), block)
                return ts.evaluate(margin, labels, row_mask)

        return step

    def _tile_step_mesh(self, info, kind: str):
        """The distributed form of the FM tile path, with the same mesh
        geometry as ShardedStore's: the MODEL axis shards the bucket
        tiles (each shard pulls/pushes its own tile range with a local
        TileSpec), the DATA axis shards whole blocks; pooled pulls psum
        over model, channel pushes psum over data, the AdaGrad update
        applies shard-locally."""
        key = (info, kind, "mesh")
        fn = getattr(self, "_tile_cache", {}).get(key)
        if fn is not None:
            return fn
        from wormhole_tpu.ops import tilemm
        from wormhole_tpu.ops.metrics import accuracy, margin_hist
        from wormhole_tpu.parallel.mesh import (DATA_AXIS, MODEL_AXIS,
                                                shard_map_compat)
        cfg = self.cfg
        k = cfg.dim
        objv_fn, dual_fn = self.objv_fn, self.dual_fn
        penalty = L1L2(cfg.l1, cfg.l2)
        from wormhole_tpu.learners.store import (mesh_macc_row,
                                                 mesh_metric_sums,
                                                 mesh_step_specs,
                                                 mesh_tile_geometry,
                                                 shard_range_mask)
        mesh = self.rt.mesh
        spec = info.spec
        nb_local, spec_local, have_model = mesh_tile_geometry(self.rt,
                                                              spec)
        oc, R = info.ovf_cap, info.block_rows

        def body(slots_l, pw_l, lab_l, ovb_l, ovr_l, t, tau, macc):
            pw1 = pw_l[0].reshape(spec_local.pairs_shape)
            lab = lab_l[0]
            row_mask = (lab != jnp.uint8(255)).astype(jnp.float32)
            labels = jnp.minimum(lab, 1).astype(jnp.float32)
            s32 = slots_l.astype(jnp.float32)
            theta, cg = s32[:, :1 + k], s32[:, 1 + k:]
            w, v = theta[:, 0], theta[:, 1:]
            wpull = jnp.concatenate(
                [w[:, None], v, jnp.sum(v * v, 1, keepdims=True)], axis=1)
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
            s = pulls[:, 1:1 + k]
            margin = (pulls[:, 0]
                      + 0.5 * (jnp.sum(s * s, axis=1) - pulls[:, 1 + k]))
            objv = objv_fn(margin, labels, row_mask)
            num_ex = jnp.sum(row_mask)
            acc = accuracy(labels, margin, row_mask)
            pos, neg = margin_hist(labels, margin, row_mask)
            objv_g, tot_ex, acc_frac, pos_g, neg_g = mesh_metric_sums(
                objv, num_ex, acc, pos, neg)
            if kind == "eval":
                return objv_g, tot_ex, acc_frac, pos_g, neg_g, margin
            dual = dual_fn(margin, labels, row_mask)
            dvals = jnp.concatenate(
                [dual[:, None], dual[:, None] * s, row_mask[:, None]],
                axis=1)
            push = tilemm.backward_pushes(pw1, dvals, spec_local)
            if oc:
                dv = jnp.where(valid[:, None],
                               dvals[ovr.astype(jnp.int32) % R], 0.0)
                # scatter-fallback: COO overflow spill, O(ovf_cap)
                push = push.at[idx].add(dv)
            push = jax.lax.psum(push, DATA_AXIS)
            g_w = push[:, 0]
            touched = push[:, 1 + k] > 0
            g_v = push[:, 1:1 + k] - v * g_w[:, None] \
                + cfg.l2_v * v * touched[:, None]
            grads = jnp.concatenate([g_w[:, None], g_v], axis=1)
            cg_new = jnp.where(touched[:, None],
                               jnp.sqrt(cg * cg + grads * grads), cg)
            eta = cfg.lr_alpha / (cfg.lr_beta + cg_new)
            w_new = penalty.solve(w / eta[:, 0] - g_w, 1.0 / eta[:, 0])
            v_new = v - eta[:, 1:] * g_v
            theta_new = jnp.where(
                touched[:, None],
                jnp.concatenate([w_new[:, None], v_new], axis=1), theta)
            new = jnp.concatenate([theta_new, cg_new], axis=1)
            d0 = theta_new[:, 0] - w
            wdelta2 = jnp.sum(d0 * d0)
            if have_model:
                wdelta2 = jax.lax.psum(wdelta2, MODEL_AXIS)
            packed = mesh_macc_row(objv_g, tot_ex, acc_frac, wdelta2,
                                   pos_g, neg_g)
            return new.astype(slots_l.dtype), t + 1, macc + packed

        from jax.sharding import PartitionSpec as P
        Pm, _Pblk, data_specs = mesh_step_specs(have_model)
        if kind == "train":
            in_specs = data_specs + (P(), P(), P())
            out_specs = (Pm, P(), P())
            fn = body
        else:
            in_specs = data_specs
            out_specs = (P(), P(), P(), P(), P(), P(DATA_AXIS))

            def fn(s, pw_, lab_, ovb_, ovr_):
                return body(s, pw_, lab_, ovb_, ovr_, jnp.float32(0),
                            jnp.float32(0), jnp.float32(0))
        step = jax.jit(
            shard_map_compat(fn, mesh=mesh, in_specs=in_specs,
                             out_specs=out_specs),
            donate_argnums=(0, 5, 7) if kind == "train" else ())
        if not hasattr(self, "_tile_cache"):
            self._tile_cache = {}
        self._tile_cache[key] = step
        return step

    def tile_train_step_mesh(self, blocks: dict, info, tau: float = 0.0):
        """Mesh FM tile step over ``data_axis_size`` blocks stacked on a
        leading axis (same calling convention as ShardedStore's)."""
        oc = info.ovf_cap
        D = self.rt.data_axis_size
        step = self._tile_step_mesh(info, "train")
        z = mesh_ovf_zeros(D, oc)
        # pull/push channels: w, v[dim], sum(v*v) / dual row-mask ticket
        ch = self.cfg.dim + 2
        nb_local = mesh_tile_geometry(self.rt, info.spec)[0]
        self.slots, t_new, self._macc = self.mesh_transport().dispatch(
            step, self._mesh_table(), blocks["pw"], blocks["labels"],
            blocks.get("ovf_b", z), blocks.get("ovf_r", z),
            self._t_device(), self._tau_const(tau), self._macc_buf(),
            ici_bytes=mesh_step_ici_bytes(
                self.rt, margin_elems=info.block_rows * ch,
                grad_elems=nb_local * ch))
        self._advance_t(t_new)
        return t_new

    def tile_eval_step_mesh(self, blocks: dict, info):
        oc = info.ovf_cap
        D = self.rt.data_axis_size
        z = mesh_ovf_zeros(D, oc)
        ch = self.cfg.dim + 2
        return self.mesh_transport().dispatch(
            self._tile_step_mesh(info, "eval"),
            self._mesh_table(), blocks["pw"], blocks["labels"],
            blocks.get("ovf_b", z), blocks.get("ovf_r", z),
            ici_bytes=mesh_step_ici_bytes(
                self.rt, margin_elems=info.block_rows * ch,
                train=False))

    def _count_step(self, block: dict, info) -> None:
        """Which variant a train block took and the pairs its list holds,
        into the timer and the registry: counts, not seconds."""
        from wormhole_tpu.obs import metrics
        spill_c, in_place_c, pairs_c = metrics.fm_step_metrics()
        lst = overflow.array(block)
        if lst is None:
            if self.step_kernel[1] == IN_PLACE:
                self.timer.add("fm_in_place_blocks", 1)
                in_place_c.inc()
            return
        pairs = self._listed_pairs(block)
        self.timer.add("fm_spill_blocks", 1)
        self.timer.add("fm_listed_pairs", pairs)
        spill_c.inc()
        pairs_c.inc(pairs)

    # -- ShardedStore surface ------------------------------------------------

    def train_step(self, batch: SparseBatch, tau: float = 0.0):
        self.slots, t_new, metrics = self._step(
            self._stacked(), batch, self._t_device(), self._tau_const(tau))
        self._advance_t(t_new)
        return metrics

    def eval_step(self, batch: SparseBatch):
        return self._eval(self._stacked(), batch)

    def nnz_weight(self) -> int:
        return int(jnp.sum(self.slots[:, 0] != 0))

    def save_model(self, path: str, rank: Optional[int] = None,
                   key_fold: str = "") -> None:
        """npz of (w, v) — the embedding-table export. ``key_fold`` is
        accepted for ShardedStore surface parity; npz carries it as an
        attribute-free no-op (the FM table is format-agnostic here)."""
        if rank is None:
            rank = jax.process_index()
        k = self.cfg.dim
        arr = np.asarray(self._stacked()[:, :1 + k])
        np.savez_compressed(f"{path}_{rank}.npz", w=arr[:, 0],
                            v=arr[:, 1:])

    def load_model(self, path: str, expect_key_fold: str = "") -> None:
        data = np.load(path)
        like = self._stacked()
        slots = np.array(like)
        slots[:, 0] = data["w"]
        slots[:, 1:1 + self.cfg.dim] = data["v"]
        self.slots = jax.device_put(jnp.asarray(slots), like.sharding)


def main(argv=None) -> int:
    """CLI: ``python -m wormhole_tpu.models.fm [conf] train_data=<uri>
    dim=8 [key=val ...]`` — the AsyncSGD driver with an FMStore plugged
    in, so FM training streams through the same DeviceFeed ingest
    pipeline as the linear learner.

    ``key=val`` tokens are routed by field name: FMConfig fields go to
    the model, everything else to the driver Config. ``num_buckets``,
    ``loss`` and ``seed`` live on the driver and are mirrored into the
    model config (AsyncSGD rejects a store whose bucket count disagrees
    with the driver's)."""
    import dataclasses as _dc
    import sys

    from wormhole_tpu.learners.async_sgd import AsyncSGD
    from wormhole_tpu.utils.config import apply_kvs, load_config

    args = list(sys.argv[1:] if argv is None else argv)
    conf = args.pop(0) if args and "=" not in args[0] else None
    shared = {"num_buckets", "loss", "seed", "tile_step_kernel",
              "tile_onehot_cache"}
    model_keys = {f.name for f in _dc.fields(FMConfig)} - shared
    model_kvs = [a for a in args
                 if a.partition("=")[0].strip() in model_keys]
    cfg = load_config(conf, [a for a in args if a not in model_kvs])
    mcfg = FMConfig(num_buckets=cfg.num_buckets, loss=cfg.loss.value,
                    seed=cfg.seed,
                    tile_step_kernel=cfg.tile_step_kernel,
                    tile_onehot_cache=cfg.tile_onehot_cache)
    apply_kvs(mcfg, model_kvs)
    rt = MeshRuntime.create(cfg.mesh_shape)
    AsyncSGD(cfg, rt, store=FMStore(mcfg, rt)).run()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
