"""The static-analysis audit gate end to end.

In-process: the lenet target's acceptance pins (exactly ONE managed-read
launch per analog layer; full donation), the budget projection/diff
machinery, and the PR-5 donation-hazard detector against the real
``AsyncCheckpointer`` host-snapshot (pre-fix device tree flagged, post-fix
host tree clean).

Subprocess (pattern of tests/test_tile_grid.py — the main pytest process
keeps its single CPU device): ``scripts/audit.py`` against the sharded
tile-grid target under 8 forced host devices, green against the checked-in
budgets, and the mutation gate — a deliberately broken budget (extra
managed-read launch, extra psum round) must exit 1 with a BUDGET VIOLATION.
"""

import json
import os
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.analysis import budgets, jaxpr_audit

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
AUDIT = os.path.join(REPO, "scripts", "audit.py")


def _run_audit(args, timeout=900):
    # the child is CPU-only: a parent holding a TPU would starve it
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)        # the CLI forces its own device count
    return subprocess.run([sys.executable, AUDIT, *args],
                          capture_output=True, text=True, timeout=timeout,
                          env=env, cwd=REPO)


# ---------------------------------------------------------------------------
# In-process: lenet target pins
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def lenet_out():
    from repro.analysis.targets import lenet_target
    return lenet_target()


def test_one_managed_read_launch_per_analog_layer(lenet_out):
    """PR 2's contract, the headline acceptance pin: each analog LeNet
    layer's forward read is exactly ONE fused managed-read launch."""
    from repro.models import lenet
    for layer in lenet.LAYERS:
        rep = lenet_out[f"read__{layer}"]
        per_layer = {k: v for k, v in rep["launches"].items()
                     if jaxpr_audit.split_launch_name(k)[1] == layer}
        assert sum(per_layer.values()) == 1, (layer, rep["launches"])
        (kind,) = {jaxpr_audit.split_launch_name(k)[0] for k in per_layer}
        assert kind in ("managed_read", "managed_read_conv")


def test_full_step_donation_fully_honored(lenet_out):
    don = lenet_out["donation__step"]
    assert don["ok"] and don["declined"] == []
    assert don["honored"] == don["requested"] > 0


def test_lenet_budget_green_in_process(lenet_out):
    budget = budgets.load_budget("lenet")
    assert budget is not None
    assert budgets.diff(budget, budgets.project(lenet_out)) == []


def test_lenet_budget_mutation_detected(lenet_out):
    """Tampering the managed-read pin must produce a diff (the CLI turns
    any diff into exit 1 — exercised end to end in the subprocess test)."""
    budget = budgets.load_budget("lenet")
    prog = budget["read__K1"]
    (name,) = [k for k in prog["launches"]
               if jaxpr_audit.split_launch_name(k)[1] == "K1"]
    prog["launches"][name] += 1        # "two launches per layer is fine"
    diffs = budgets.diff(budget, budgets.project(lenet_out))
    assert any(name in d for d in diffs), diffs


def test_projection_drops_unstable_keys(lenet_out):
    proj = budgets.project(lenet_out)
    for prog, rep in proj.items():
        assert "key_reuse" not in rep         # messages carry trace-local ids
        if not prog.startswith("donation"):
            assert "key_reuse_count" in rep   # ...but the count is pinned


# ---------------------------------------------------------------------------
# In-process: the PR-5 donation/snapshot hazard class
# ---------------------------------------------------------------------------

def test_snapshot_hazards_flags_device_tree_and_passes_host_snapshot():
    """The exact PR-5 crash shape: a checkpoint tree captured for the
    background writer while the training carry is donated.  Pre-fix the
    tree still held ``jax.Array`` leaves (the next step's donation deletes
    them under the writer); post-fix ``AsyncCheckpointer`` snapshots to
    host first (``_to_numpy_host``, typed keys via ``_HostKeyData``)."""
    from repro.checkpoint.store import _HostKeyData, _to_numpy_host

    device_tree = {"params": {"w": jnp.zeros((2, 2)),
                              "seed": jax.random.key(3)},
                   "step": 7}
    bad = jaxpr_audit.snapshot_hazards(device_tree)
    assert sorted(bad) == ["params/seed", "params/w"]

    host_tree = jax.tree_util.tree_map(_to_numpy_host, device_tree)
    assert jaxpr_audit.snapshot_hazards(host_tree) == []
    assert isinstance(host_tree["params"]["w"], np.ndarray)
    assert isinstance(host_tree["params"]["seed"], _HostKeyData)


# ---------------------------------------------------------------------------
# Subprocess: the CLI gate on the sharded tile grid (8 forced devices)
# ---------------------------------------------------------------------------

def test_audit_cli_tile_grid_green_and_pins(tmp_path):
    report = tmp_path / "report.json"
    res = _run_audit(["lenet_tile_grid", "--report", str(report)])
    assert res.returncode == 0, res.stdout + res.stderr
    out = json.loads(report.read_text())["lenet_tile_grid"]["reports"]

    # one raw sharded read: 2 psum eqns (y-reduce + saturation OR), 1 round
    grid = out["grid_read"]
    assert grid["collectives"] == {"psum": 2}
    assert grid["max_collective_rounds_per_loop_iter"] == 0  # no loop

    # the acceptance pin: exactly one psum ROUND per streamed chunk round
    stream = out["streamed_read"]
    chunk_loops = [lp for lp in stream["loops"]
                   if lp["collectives_per_iter"]]
    assert chunk_loops, stream["loops"]
    assert all(lp["collective_rounds_per_iter"] == 1 for lp in chunk_loops)

    # streamed grid update: chunk loops are collective-silent
    assert out["streamed_update"]["collective_total"] == 0


def test_audit_cli_fails_on_broken_budgets(tmp_path):
    """Deliberately break BOTH acceptance budgets and require exit 1."""
    bdir = tmp_path / "budgets"
    shutil.copytree(os.path.join(REPO, "analysis", "budgets"), bdir)

    tg = json.loads((bdir / "lenet_tile_grid.json").read_text())
    for lp in tg["streamed_read"]["loops"]:
        if lp["collectives_per_iter"]:
            lp["collective_rounds_per_iter"] += 1   # "two rounds is fine"
    (bdir / "lenet_tile_grid.json").write_text(json.dumps(tg))

    ln = json.loads((bdir / "lenet.json").read_text())
    for k in ln["read__K1"]["launches"]:
        ln["read__K1"]["launches"][k] += 1          # extra launch per layer
    (bdir / "lenet.json").write_text(json.dumps(ln))

    res = _run_audit(["lenet", "lenet_tile_grid", "--budget-dir", str(bdir)])
    assert res.returncode == 1, res.stdout + res.stderr
    assert res.stdout.count("BUDGET VIOLATION") == 2
    assert "collective_rounds_per_iter" in res.stdout
    assert "launches" in res.stdout


def test_audit_cli_unknown_target_exits_2():
    res = _run_audit(["no_such_target"], timeout=300)
    assert res.returncode == 2
    assert "unknown target" in res.stderr
