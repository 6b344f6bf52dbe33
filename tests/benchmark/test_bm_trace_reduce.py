"""The trace reducer on a recorded trace and on hand-made intervals.

``data/ftrl_replay.xplane.pb`` was recorded on a TPU v5 lite in PR 25: 48
steps (6 passes of 8 blocks) of ``criteo_ftrl.replay_uniform``, a 4.7 s
window. The numbers below are what the chip run's own reduction printed."""

import os

import pytest

import bm_helpers  # noqa: F401  (puts the repo on sys.path)
from benchmark import trace_reduce as tr

XPLANE = os.path.join(bm_helpers.DATA, "ftrl_replay.xplane.pb")


@pytest.fixture(scope="module")
def reduced():
    return tr.reduce_trace(tr.load(XPLANE))


def test_union_of_overlapping_intervals():
    ns = [(0, 10), (5, 20), (30, 40), (35, 36)]
    assert tr.union_seconds(ns) == pytest.approx(30e-9)
    assert tr.union_seconds([]) == 0.0


def test_gaps_inside_a_window():
    assert tr.gaps([(10, 20), (15, 30), (50, 60)], 0, 100) == [
        (0, 10), (30, 50), (60, 100)]
    assert tr.gaps([(0, 100)], 10, 90) == []
    assert tr.gaps([], 10, 90) == [(10, 90)]


def test_only_mosaic_custom_calls_are_kernels():
    mosaic = ('%step.1 = (f32[12,64,128]{2,1,0}) custom-call(u32[8] %a), '
              'custom_call_target="tpu_custom_call"')
    other = '%custom-call.4 = s32[4096,256]{1,0} custom-call(), ' \
            'custom_call_target="SomethingElse"'
    assert tr.is_kernel(mosaic)
    assert not tr.is_kernel(other)
    assert not tr.is_kernel("%fusion.3 = f32[8]{0} fusion(f32[8] %x)")


def test_short_name_keeps_op_kind_and_shape():
    name = ('%fusion.34 = (f32[268435456,1]{0,1:T(1,128)}, f32[268435456,1]'
            '{0,1:T(1,128)}) fusion(f32[268435456,3]{0,1:T(4,128)} %slots.1)'
            ', kind=kLoop, calls=%fused_computation.59')
    short = tr.short_name(name)
    assert short.startswith("%fusion.34 fusion (f32[268435456,1]")
    assert len(short) <= 96
    assert tr.short_name("no hlo here") == "no hlo here"


def test_recorded_trace_busy_union_and_window(reduced):
    assert reduced["window_s"] == pytest.approx(4.699165548, rel=1e-9)
    assert reduced["busy_s"] == pytest.approx(4.678903015, rel=1e-9)
    idle = 100.0 * (1.0 - reduced["busy_s"] / reduced["window_s"])
    assert idle == pytest.approx(0.4311942789, rel=1e-6)


def test_recorded_trace_steps_and_custom_call_time(reduced):
    assert reduced["steps"] == 48
    assert reduced["kernel_s"] == pytest.approx(2.127534359, rel=1e-9)
    # one program does all the work: every op lies inside a step
    assert reduced["step_s"] == pytest.approx(reduced["busy_s"], rel=1e-5)
    assert 1e3 * reduced["kernel_s"] / 48 == pytest.approx(44.3236, rel=1e-4)


def test_recorded_trace_top_ops(reduced):
    ops = reduced["device_ops"]
    assert len(ops) == 10
    assert ops[0][0].startswith("%step.1 tpu_custom_call")
    assert [s for _n, s in ops] == sorted((s for _n, s in ops), reverse=True)
    assert sum(s for _n, s in ops) <= reduced["busy_s"] * (1 + 1e-9)


def test_recorded_trace_gap_attribution(reduced):
    gaps = dict(reduced["idle_gaps"])
    assert set(gaps) == {"inside_a_pass", "between_passes"}
    # six bench_pass annotations back to back: all idle time is inside one
    assert gaps["between_passes"] == pytest.approx(0.0, abs=1e-3)
    assert gaps["inside_a_pass"] + gaps["between_passes"] == pytest.approx(
        reduced["window_s"] - reduced["busy_s"], rel=1e-6)


def test_pass_annotations_are_found():
    spans = tr.pass_spans(tr.load(XPLANE))
    assert len(spans) == 6
    assert all(e > s for s, e in spans)


def test_a_trace_without_device_planes_reduces_to_nothing():
    class Plane:
        name, lines = "/host:CPU", []

    class Profile:
        planes = [Plane()]

    assert tr.reduce_trace(Profile()) == {}
