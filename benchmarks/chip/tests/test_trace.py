"""The trace reduction: busy union, per-kind time, idle-gap attribution."""

import os

import pytest

from benchlib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")


def test_reduce_events_by_hand():
    # window [0, 100) ns; ops overlap in [10, 30) and [20, 40)
    dev = {0: [(10, 30, "fusion.1", None), (20, 40, "custom-call.2",
                                             "managed_read"),
               (60, 70, "custom-call.3", "managed_read"),
               (95, 120, "fusion.4", None)]}
    host = [(0, 50, "decode"), (50, 100, "admit"), (0, 100, "window")]
    red = T.reduce_events(dev, host, (0, 100))
    # busy: [10, 40) + [60, 70) + [95, 100) = 45 ns
    assert red["busy_s"] == pytest.approx(45e-9)
    assert red["window_s"] == pytest.approx(100e-9)
    assert red["kind_s"] == {"managed_read": pytest.approx(30e-9)}
    assert red["kind_launches"] == {"managed_read": 2}
    # gaps [0,10) and [40,60) go to decode (a gap goes whole to the span
    # that overlaps it most; [40,60) overlaps both by 10, the first wins),
    # [70,95) to admit
    gaps = dict(red["idle_gaps"])
    assert gaps["decode"] == pytest.approx(30e-9)
    assert gaps["admit"] == pytest.approx(25e-9)
    assert red["top_ops"][0][0] == "managed_read"


def test_nested_ops_count_self_time():
    # a while op [0, 100) holding two body ops: the top ops list self time
    dev = {0: [(0, 100, "%while.1 = (s32[]) while(...)", None),
               (10, 40, "%fusion.2 = f32[8] fusion(...)", None),
               (50, 60, "%custom-call.3 = ...", "bwd_update_conv")]}
    red = T.reduce_events(dev, [], (0, 100))
    top = dict(red["top_ops"])
    assert top["%while.1"] == pytest.approx(60e-9)
    assert top["%fusion.2"] == pytest.approx(30e-9)
    assert top["bwd_update_conv"] == pytest.approx(10e-9)
    assert red["busy_s"] == pytest.approx(100e-9)


def test_union_merges_touching_intervals():
    assert T.union([(5, 7), (0, 2), (2, 4), (6, 9)]) == [(0, 4), (5, 9)]


def test_kind_from_op_name():
    class Ev:
        name = "%noisy_read.79 = (f32[512,512]) custom-call(...)"
        stats = []
    assert T.kind_of(Ev()) == "noisy_read"
    Ev.name = "%fusion.2 = f32[8] fusion(...)"
    assert T.kind_of(Ev()) is None


def test_kind_from_op_metadata():
    class Ev:
        name = "custom-call.7"
        stats = [("tf_op", "jit(step)/while/body/bwd_update_conv/pallas_call")]
    assert T.kind_of(Ev()) == "bwd_update_conv"
    Ev.stats = [("tf_op", "jit(f)/managed_read__K2/pallas_call")]
    assert T.kind_of(Ev()) == "managed_read"


def test_recorded_chip_trace():
    """A trace recorded on a TPU v5 lite: one 64-step epoch call of the
    recipe cell.  The window is the host's ``bench:window`` span on the
    device's clock, both kernel kinds are found (four ``pulse_counts`` per
    step, at least one ``noisy_read`` per tile read), the busy time lies
    inside the window and the idle gaps fall under ``epoch_call``."""
    red = T.reduce_file(os.path.join(DATA, "lenet_recipe.xplane.pb.gz"), 1)
    assert 0 < red["busy_s"] < red["window_s"]
    n = red["kind_launches"]
    assert set(n) == {"noisy_read", "pulse_counts"}
    assert n["pulse_counts"] == 4 * 64
    assert n["noisy_read"] >= 7 * 64
    assert dict(red["idle_gaps"]).get("epoch_call", 0) > 0
