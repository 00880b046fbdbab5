"""Device time by program scope (``benchlib.scopes``): scope paths from
``tf_op`` strings, the self-time reduction, and two traces recorded on a
TPU v5 lite: one from before the program named its scopes, one after."""

import os

import pytest

from benchlib import scopes as S
from benchlib import trace as T

DATA = os.path.join(os.path.dirname(os.path.abspath(__file__)), "data")
LAYERS = ("K1", "K2", "W3", "W4")
UNSCOPED_TRACE = os.path.join(DATA, "lenet_recipe.xplane.pb.gz")
SCOPED_TRACE = os.path.join(DATA, "lenet_recipe_scoped.xplane.pb.gz")
STEPS_PER_CALL = 64


@pytest.mark.parametrize("tf_op,path", [
    # nested transform wrappers, while bodies and calls between scopes
    ("jit(run_epoch)/while/body/closed_call/transpose(jvp(K2))/backward/"
     "while/body/closed_call/col2im/iota:", "K2/backward/col2im"),
    ("jit(run_epoch)/while/body/closed_call/jvp(K1)/forward/while/body/"
     "closed_call/im2col/gather:", "K1/forward/im2col"),
    ("jit(f)/vmap(transpose(jvp(W3)))/update/jit(_threefry_split)/slice:",
     "W3/update"),
    ("jit(f)/transpose(jvp(W4))/backward_update/noisy_read/pallas_call:",
     "W4/backward_update"),
    # a layer with no cycle (the key split before the cycles)
    ("jit(run_epoch)/while/body/closed_call/jvp(K2)/jit(_threefry_split)/"
     "slice:", "K2"),
    # ops that XLA merged keep the first name
    ("jit(f)/jvp(W3)/forward/div;jvp(W4)/update/mul:", "W3/forward"),
    # no known component
    ("jit(run_epoch)/while:", S.UNSCOPED),
    ("jit(run_epoch)/jit(_shuffle)/make_cnn_epoch_fn.<locals>.run_epoch/"
     "add:", S.UNSCOPED),
    ("xs:", S.UNSCOPED),
    ("", S.UNSCOPED),
    # a name that only contains a known word is not a scope
    ("jit(forward_fn)/jit(update_rule)/K1x/add:", S.UNSCOPED),
])
def test_scope_of(tf_op, path):
    assert S.scope_of(tf_op, LAYERS) == path


def test_reduce_scopes_by_hand():
    # window [0, 100) ns; a while op [0, 60) holds two body ops; the last
    # op runs past the window
    dev = {0: [(0, 60, "jit(f)/while:", None),
               (10, 30, "jit(f)/while/body/jvp(K1)/forward/im2col/gather:",
                None),
               (30, 50, "jit(f)/while/body/jvp(K1)/forward/noisy_read/"
                "pallas_call:", "noisy_read"),
               (70, 80, "jit(f)/transpose(jvp(K1))/update/pulse_counts/"
                "pallas_call:", "pulse_counts"),
               (90, 130, "jit(f)/add:", None)]}
    red = S.reduce_scopes(dev, (0, 100), LAYERS)
    s = red["scope_s"]
    assert s["unscoped"] == pytest.approx(30e-9)     # 20 of while + 10
    assert s["K1/forward/im2col"] == pytest.approx(20e-9)
    assert s["K1/forward"] == pytest.approx(20e-9)
    assert s["K1/update"] == pytest.approx(10e-9)
    assert red["scope_launches"] == {"noisy_read": {"K1/forward": 1},
                                     "pulse_counts": {"K1/update": 1}}
    assert S.cycle_s(s, "forward") == pytest.approx(40e-9)
    assert S.cycle_s(s, "backward") is None
    assert S.stage_s(s) == pytest.approx(20e-9)
    busy = T.reduce_events({0: [(a, b, n, k) for a, b, n, k in dev[0]]},
                           [], (0, 100))["busy_s"]
    assert sum(s.values()) == pytest.approx(busy)


def test_unscoped_trace_reduction_unchanged():
    """The trace recorded before the scopes: the harness's reduction reads
    as it did, and every op is unscoped."""
    red = T.reduce_file(UNSCOPED_TRACE, 1)
    assert sorted(red) == ["busy_s", "idle_gaps", "kind_launches", "kind_s",
                           "top_ops", "window_s"]
    assert red["busy_s"] == pytest.approx(0.162982007, rel=1e-9)
    assert red["window_s"] == pytest.approx(0.165727547, rel=1e-9)
    assert red["kind_launches"] == {"noisy_read": 448, "pulse_counts": 256}
    assert red["kind_s"] == pytest.approx(
        {"noisy_read": 0.006040097, "pulse_counts": 0.002307918}, rel=1e-9)
    assert red["top_ops"][0] == ["%fusion.632", pytest.approx(0.073440367)]
    assert red["idle_gaps"] == [["epoch_call",
                                 pytest.approx(0.00274554)]]
    sc = S.reduce_file(UNSCOPED_TRACE, LAYERS)
    assert list(sc["scope_s"]) == [S.UNSCOPED]
    assert sc["scope_s"][S.UNSCOPED] == pytest.approx(red["busy_s"],
                                                      rel=1e-3)
    assert sc["scope_launches"] == {"noisy_read": {S.UNSCOPED: 448},
                                    "pulse_counts": {S.UNSCOPED: 256}}


@pytest.fixture(scope="module")
def scoped():
    return S.reduce_file(SCOPED_TRACE, LAYERS), T.reduce_file(SCOPED_TRACE, 1)


def test_scoped_trace_adds_up_to_busy(scoped):
    """One 64-step call recorded after the program named its scopes: the
    scopes and ``unscoped`` together are the busy time within 1%, and most
    of it is scoped."""
    sc, red = scoped
    total = sum(sc["scope_s"].values())
    assert total == pytest.approx(red["busy_s"], rel=0.01)
    assert sc["scope_s"].get(S.UNSCOPED, 0.0) < 0.5 * total


def test_scoped_trace_layers_and_cycles(scoped):
    """Every layer reads forward and update; K2, W3, W4 read backward (K1's
    backward read is dead: nothing reads the image gradient)."""
    s = scoped[0]["scope_s"]
    for layer in LAYERS:
        assert s.get(f"{layer}/forward", 0) > 0, layer
        assert s.get(f"{layer}/update", 0) > 0, layer
    for layer in ("K2", "W3", "W4"):
        assert s.get(f"{layer}/backward", 0) > 0, layer
    assert s.get("K2/backward/col2im", 0) > 0
    assert any(k.startswith("K1/") and "im2col" in k for k in s)
    assert S.stage_s(s) > 0


def test_scoped_trace_launches(scoped):
    """The kernel launches fall under their cycles: one ``pulse_counts`` per
    layer and step under ``update``; every ``noisy_read`` under ``forward``
    or ``backward``, at least one per read."""
    sc, red = scoped
    n = sc["scope_launches"]
    assert sum(n["pulse_counts"].values()) == \
        red["kind_launches"]["pulse_counts"]
    for layer in LAYERS:
        assert n["pulse_counts"].get(f"{layer}/update") == STEPS_PER_CALL
        assert n["noisy_read"].get(f"{layer}/forward", 0) >= STEPS_PER_CALL
    for layer in ("K2", "W3", "W4"):
        assert n["noisy_read"].get(f"{layer}/backward", 0) >= STEPS_PER_CALL
    assert set(n["noisy_read"]) <= {f"{layer}/{cycle}" for layer in LAYERS
                                    for cycle in ("forward", "backward")}
