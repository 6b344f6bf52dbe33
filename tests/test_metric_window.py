"""The crec passes' metric window (learners/window.py).

  * ``fold_row`` knows the stores' two metric-row layouts by name and
    ``pool_margins`` that label 255 is a PAD row;
  * the accumulator's async tickets: a non-final drain leaves the
    newest in flight, a final one resolves all; the mesh rule bounds a
    part that gates on no step;
  * the app's pass-level AUC histograms are the live accumulator's;
  * the step table dispatches the store call its row names;
  * a one-device pass and a mesh pass over one file read the Progress
    the parent of the PR that made the window read (pinned).
"""

import types

import numpy as np
import pytest

from test_mesh_feed import BR, make_app, make_rows, write_file
from wormhole_tpu.learners.window import (MetricAccumulator, MetricWindow,
                                          fold_row, pool_margins)
from wormhole_tpu.sched.workload_pool import TRAIN, VAL
from wormhole_tpu.utils.progress import Progress
from wormhole_tpu.utils.timer import Timer

MARGINS = np.array([0.5, -1.0, 2.0], np.float32)
# every positive above every negative: an AUC of exactly 1
POS, NEG = np.array([0.0, 0.0, 2.0]), np.array([3.0, 1.0, 0.0])
ROWS = {
    "sparse": lambda tail: [np.float32(2.5), np.float32(7), np.float32(0.75),
                            np.float32(0.5), tail],
    "tile": lambda tail: [np.float32(2.5), np.float32(7), np.float32(0.5),
                          POS, NEG, tail],
}


@pytest.mark.parametrize("kind", [TRAIN, VAL])
@pytest.mark.parametrize("layout", ["tile", "sparse"])
def test_fold_row_layouts(layout, kind):
    local = Progress()
    tail = np.float32(0.125) if kind == TRAIN else MARGINS
    margin = fold_row(local, ROWS[layout](tail), layout, kind)
    assert (local.objv, local.num_ex, local.count) == (2.5, 7, 1)
    assert local.acc == 0.5
    assert local.auc == (1.0 if layout == "tile" else 0.75)
    if kind == TRAIN:
        assert margin is None and local.wdelta2 == 0.125
    else:
        assert margin is MARGINS and local.wdelta2 == 0.0
    # a row that ends before the last slot (the multihost eval fold
    # hands the margins over apart) folds the same and returns nothing
    short = Progress()
    assert fold_row(short, ROWS[layout](tail)[:-1], layout, kind) is None
    assert (short.objv, short.acc, short.auc, short.wdelta2) == \
        (2.5, 0.5, local.auc, 0.0)


def test_pool_margins_drops_pad_rows_and_clips_labels():
    pooled = []
    labels = np.array([0, 255, 1, 7, 255], np.uint8)
    pool_margins(pooled, np.arange(5, dtype=np.float32), labels)
    (m, y, w), = pooled
    assert m.tolist() == [0.0, 2.0, 3.0]
    assert y.tolist() == [0.0, 1.0, 1.0] and y.dtype == np.float32
    assert w.tolist() == [1.0, 1.0, 1.0] and w.dtype == np.float32


class FakeApp:
    """What a window asks of the app: a store whose accumulator reads
    are host rows, a timer, a reporter, ``_display``."""
    CREC_DRAIN_CHUNK = 3

    def __init__(self):
        self.fetched = 0
        self.shown = 0
        self.due = False
        self.timer = Timer()
        self.reporter = types.SimpleNamespace(due=lambda: self.due)
        self.store = types.SimpleNamespace(
            fetch_metrics_async=self._fetch)

    def _fetch(self):
        self.fetched += 1
        # [objv, num_ex, acc, wdelta2, pos x2, neg x2]
        return np.array([1.0, 10, 0.5, 0.25, 0, 2, 3, 0], np.float64)

    def _display(self, local):
        self.shown += 1


@pytest.mark.parametrize("final", [False, True])
def test_drain_resolves_all_but_the_newest_ticket(final):
    app, local, acc = FakeApp(), Progress(), MetricAccumulator()
    acc.hist = [np.zeros(2), np.zeros(2)]
    win = MetricWindow(app, local, TRAIN, None, acc=acc)
    for steps in (2, 3):                # two windows, two tickets
        for _ in range(steps):
            win.count_step()
        win.drain(final=False)
    assert app.fetched == 2 and acc.count == 0
    assert len(acc.tickets) == 1 and local.count == 2
    win.count_step()
    win.drain(final=final)              # a third ticket
    assert app.fetched == 3
    assert len(acc.tickets) == (0 if final else 1)
    assert local.count == (6 if final else 5)
    assert local.num_ex == 10 * (3 if final else 2)
    # the display AUC is the running histograms', stored as auc*count
    assert local.auc == 1.0 * local.count
    assert app.shown == 2               # one row a drain that resolved
    win.drain(final=True)               # nothing new: no fetch
    assert app.fetched == 3 and not acc.tickets and local.count == 6


def test_mesh_rule_bounds_a_part_that_gates_on_no_step():
    app, local = FakeApp(), Progress()
    win = MetricWindow(app, local, TRAIN, None, acc=MetricAccumulator(),
                       bounded=True)
    win.acc.hist = [np.zeros(2), np.zeros(2)]
    win.count_step()
    win.count_step()
    assert app.fetched == 0             # under the chunk, nothing due
    win.count_step()                    # CREC_DRAIN_CHUNK steps are out
    assert app.fetched == 1 and len(win.acc.tickets) == 1
    assert local.count == 0             # non-final: the ticket flies
    app.due = True
    win.count_step()                    # a display is due
    assert app.fetched == 2 and local.count == 3
    assert app.timer.totals["wait"] >= 0.0
    # an eval part's vectors fold when the list holds a chunk
    ev = MetricWindow(app, Progress(), VAL, [], bounded=True)
    lab = np.array([1, 255, 0], np.uint8)
    for k in range(3):
        assert len(ev.steps) == k
        ev.add_step(ROWS["tile"](MARGINS), lab, "tile")
    assert not ev.steps and ev.local.count == 3 and len(ev.pooled) == 3
    assert "eval_wait" in app.timer.totals
    unbounded = MetricWindow(app, Progress(), VAL, None)
    for _ in range(4):
        unbounded.add_step(ROWS["sparse"](MARGINS), lab, "sparse")
    assert len(unbounded.steps) == 4 and unbounded.local.count == 0
    unbounded.drain()
    assert not unbounded.steps and unbounded.local.count == 4


class RecordingStore:
    def __getattr__(self, name):
        return lambda *a, **kw: (name, a, kw)


STEP_TABLE = {
    # form: (train call, eval call, layout) as (method, takes tau)
    "tile": ("tile_train_step", "tile_eval_step", "tile", True),
    "dense": ("dense_train_step", "dense_eval_step", "sparse", True),
    "tile_mesh": ("tile_train_step_mesh", "tile_eval_step_mesh", "tile",
                  False),
    "dense_mesh": ("dense_train_step_mesh", "dense_eval_step_mesh", "tile",
                   False),
}


@pytest.mark.parametrize("kind", [TRAIN, VAL])
@pytest.mark.parametrize("form", sorted(STEP_TABLE))
def test_crec_step_table(form, kind):
    from wormhole_tpu.learners.async_sgd import AsyncSGD
    app = types.SimpleNamespace(store=RecordingStore())
    info = types.SimpleNamespace(block_rows=64, nnz=8)
    step, layout = AsyncSGD._crec_step(app, kind, form, info)
    train_name, eval_name, want_layout, takes_tau = STEP_TABLE[form]
    name, args, kw = step("operand", 2.0)
    assert layout == want_layout
    assert name == (train_name if kind == TRAIN else eval_name)
    geo = (info,) if form.startswith("tile") else (64, 8)
    assert args == ("operand",) + geo
    assert kw == ({"tau": 2.0} if kind == TRAIN and takes_tau else {})


@pytest.fixture(scope="module")
def crec2_file(tmp_path_factory):
    """Three blocks (a data:2 tail group pads), planted labels."""
    n = 2 * BR + 1000
    keys, labels = make_rows(np.random.default_rng(0), n)
    path = tmp_path_factory.mktemp("window") / "w.crec2"
    write_file(path, keys, labels)
    return path, n


def test_crec_hist_assignment_resets_the_live_window(crec2_file):
    """``app._crec_hist = [zeros, zeros]`` is how a caller that ends a
    pass itself (benchmark/system.py, chip_smoke.py) resets the
    pass-level AUC: it must reach the accumulator the next pass uses."""
    path, n = crec2_file
    app = make_app(path, "data:1", cache_device=True)
    app.process(str(path), 0, 1)
    prog = app.process(str(path), 0, 1)     # replay: left deferred
    prog.merge(app.flush_metrics())
    assert app._crec_hist is app._crec_acc.hist
    assert app._crec_hist[0].sum() + app._crec_hist[1].sum() == 2 * n
    fresh = [np.zeros(512), np.zeros(512)]
    app._crec_hist = fresh
    assert app._crec_acc.hist is fresh
    tail = app.process(str(path), 0, 1)
    tail.merge(app.flush_metrics())
    assert fresh[0].sum() + fresh[1].sum() == n
    assert not app._crec_acc.tickets and app._crec_acc.count == 0


# Progress of one TRAIN part from zero weights, then one VAL part over
# the trained table, read at the parent of the PR that made the window
# (96b8650; CPU devices): (objv, num_ex, count, acc, auc, wdelta2)
PARENT = {
    ("data:1", TRAIN): (10133.73046875, 17384, 3, 2.312582015991211,
                        2.4722203209955644, 1463.177978515625),
    ("data:2", TRAIN): (11864.296875, 17384, 2, 1.3260610103607178,
                        1.076035558084426, 1374.1517333984375),
    ("data:1", VAL): (3787.83984375, 17384, 3, 2.998779296875,
                      2.9999972878417625, 0.0),
    ("data:2", VAL): (4961.671203613281, 17384, 2, 1.9879902601242065,
                      1.9994650271272911, 0.0),
}


@pytest.mark.parametrize("kind", [TRAIN, VAL])
@pytest.mark.parametrize("mesh_spec", ["data:1", "data:2"])
def test_pass_progress_is_the_parents(crec2_file, mesh_spec, kind):
    path, n = crec2_file
    app = make_app(path, mesh_spec)
    prog = app.process(str(path), 0, 1)
    prog.merge(app.flush_metrics())
    if kind == VAL:
        pooled = []
        prog = app.process(str(path), 0, 1, kind=VAL, pooled=pooled)
        assert sum(len(p[0]) for p in pooled) == n
    got = (prog.objv, prog.num_ex, prog.count, prog.acc, prog.auc,
           prog.wdelta2)
    want = PARENT[mesh_spec, kind]
    assert got[1:3] == want[1:3]
    assert np.allclose(got, want, rtol=2e-5, atol=0), got


# the multihost crec pass in one process (world 1: both data indices
# are this host's), read at the same parent: TRAIN from zero weights,
# then VAL with pooling: (objv, num_ex, count, acc, auc, wdelta2), and
# the pooled rows' (sum of |margin|, sum of labels)
PARENT_MULTIHOST = {
    "crec2": ((11864.296875, 17384, 2, 1.3260610103607178,
               1.076035558084426, 1374.1517333984375),
              (4961.671203613281, 17384, 2, 1.9879902601242065,
               1.9994650271272911, 0.0),
              (20829.46875, 8707.0)),
    "crec": ((11864.275390625, 17384, 2, 1.3230609893798828,
              1.0760394627502754, 1374.1787109375),
             (4964.032165527344, 17384, 2, 1.9879902601242065,
              1.9994670462346629, 0.0),
             (20816.447265625, 8707.0)),
}


@pytest.mark.parametrize("fmt", ["crec2", "crec"])
def test_multihost_pass_progress_is_the_parents(tmp_path, fmt):
    """A padded tail group through ``stack_mesh_group`` and the pass's
    eval fold (``fold_row`` on the replicated sums, the margins through
    ``_my_shard_rows``): PAD lanes pool nothing, on either format."""
    from test_mesh_feed import NNZ
    from wormhole_tpu.data.crec import CRecWriter
    n = 2 * BR + 1000
    keys, labels = make_rows(np.random.default_rng(0), n)
    path = tmp_path / f"w.{fmt}"
    if fmt == "crec2":
        write_file(path, keys, labels)
    else:
        with CRecWriter(str(path), nnz=NNZ, block_rows=BR) as w:
            w.append(keys, labels)
    app = make_app(path, "data:2", fmt=fmt)
    train = app._multihost_pass_crec(str(path), TRAIN)
    pooled = []
    val = app._multihost_pass_crec(str(path), VAL, pooled)
    want_train, want_val, (msum, ysum) = PARENT_MULTIHOST[fmt]
    for prog, want in ((train, want_train), (val, want_val)):
        got = (prog.objv, prog.num_ex, prog.count, prog.acc, prog.auc,
               prog.wdelta2)
        assert got[1:3] == want[1:3]
        assert np.allclose(got, want, rtol=2e-5, atol=0), got
    m = np.concatenate([p[0] for p in pooled])
    y = np.concatenate([p[1] for p in pooled])
    assert len(m) == n and float(y.sum()) == ysum
    assert np.isclose(float(np.abs(m).sum()), msum, rtol=1e-4)
    if fmt == "crec2":      # one process: the mesh pass's own numbers
        assert want_train == PARENT["data:2", TRAIN]
