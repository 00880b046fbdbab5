"""Kill-and-resume parity: a SIGKILLed training run, resumed from its
latest complete checkpoint, must reproduce the uninterrupted run BIT-EXACT.

This is the survivability headline of the fault-tolerance stack
(docs/scaling.md): every random draw in both drivers is indexed absolutely
(epoch shuffles ``fold_in(k_data, epoch)``, step keys
``fold_in(k_train, epoch*spe + s)`` / ``fold_in(key_base, step)``), the
checkpoint store writes atomically (tmp + rename) and ``latest_step`` only
ever resumes from a *complete* snapshot — so kill/resume == uninterrupted
is an equality of bytes, not a tolerance.

Each scenario runs the real drivers in subprocesses (SIGKILL cannot be
caught, so an in-process simulation would prove nothing):

* CNN driver (``train.cnn``): digital and policy-converted analog models,
  both engines (scan / python oracle), killed at an epoch boundary;
* LM driver (``launch.train``): killed at a non-checkpoint step boundary,
  and killed *mid-async-checkpoint-write* (``REPRO_CKPT_WRITE_DELAY`` holds
  the background serialisation open) — resume falls back to the previous
  complete step;
* ``AsyncCheckpointer`` hard-kill atomicity in isolation;
* the tile-grid elastic shrink: a forced-8-device run with a sharded
  ``2x4`` crossbar grid is killed, resumed on 4 devices (grid falls back to
  its serial oracle) and pinned against a 1-device uninterrupted oracle —
  PR 3's sharded == serial bit-exactness is what makes elastic resharding
  trajectory-preserving;
* an in-process simulated *device loss* (``fault.run_with_restarts`` +
  ``elastic.mark_lost``): the restart rebuilds the step functions, the
  grid re-resolves on the 4 survivors, and the finished run still matches
  the oracle bit-exact.

Bit-exactness is asserted on the checkpoint store's own per-leaf crc32
index (bf16 is stored as a uint16 byte view, typed PRNG keys as key data —
every leaf comparison is byte-level).

The whole module is ``slow``: tier-1 deselects it (pyproject addopts); the
forced-8-device CI ``distributed`` job runs it with ``-m 'slow or not
slow'``.
"""

import json
import os
import signal
import subprocess
import sys
import textwrap

import pytest

from repro.checkpoint import store

pytestmark = pytest.mark.slow

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run(body: str, *, env=None, devices=None, expect_sigkill=False,
         timeout=900):
    code = textwrap.dedent(body)
    # the child is CPU-only: a parent holding a TPU would starve it
    e = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
             JAX_PLATFORMS="cpu")
    # never inherit fault-injection config from an outer harness
    for k in ("REPRO_FAULT_MODE", "REPRO_FAULT_STEP", "REPRO_FAULT_DROP",
              "REPRO_CKPT_WRITE_DELAY"):
        e.pop(k, None)
    if devices:
        e["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    if env:
        e.update({k: str(v) for k, v in env.items()})
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=timeout, env=e)
    if expect_sigkill:
        assert res.returncode == -signal.SIGKILL, (
            res.returncode, res.stdout[-2000:], res.stderr[-2000:])
    else:
        assert res.returncode == 0, (res.stdout[-2000:], res.stderr[-4000:])
    return res


def _fingerprint(ckpt_dir: str, step: int):
    """Byte-level identity of one checkpoint: per-leaf (path, shape, dtype,
    crc32) from the store's own index, plus the saved metadata."""
    with open(os.path.join(ckpt_dir, f"step_{step:010d}",
                           "index.json")) as f:
        idx = json.load(f)
    leaves = [(e["key"], tuple(e["shape"]), e["dtype"], e["crc32"])
              for e in idx["leaves"]]
    return leaves, idx["meta"]


# ---------------------------------------------------------------------------
# CNN driver: digital + policy-converted analog, both engines
# ---------------------------------------------------------------------------

_CNN_BODY = """
    from repro.models import lenet
    from repro.analog import presets
    from repro.train import cnn

    if {analog!r}:
        cfg = lenet.LeNetConfig.from_policy(
            presets.parse_policy("K2=rpu_baseline,*=managed"))
    else:
        cfg = lenet.LeNetConfig(mode="digital")
    cnn.train(cfg, epochs=3, batch=8, n_train={n_train}, n_test=32,
              seed=0, verbose=True, engine={engine!r},
              ckpt_dir={ckpt_dir!r})
    print("RUN_DONE")
"""


def _cnn_body(analog, engine, ckpt_dir):
    n_train = 64 if analog else 96
    return _CNN_BODY.format(analog=analog, engine=engine,
                            ckpt_dir=str(ckpt_dir), n_train=n_train)


@pytest.mark.parametrize("analog", [False, True],
                         ids=["digital", "analog_policy"])
@pytest.mark.parametrize("engine", ["scan", "python"])
def test_cnn_kill_resume_bitexact(tmp_path, analog, engine):
    oracle, faulted = tmp_path / "oracle", tmp_path / "faulted"
    _run(_cnn_body(analog, engine, oracle))

    # kill at the epoch-2 boundary (uncatchable SIGKILL, async checkpoint
    # thread dies mid-whatever-it-was-doing)
    _run(_cnn_body(analog, engine, faulted),
         env={"REPRO_FAULT_MODE": "sigkill", "REPRO_FAULT_STEP": 2},
         expect_sigkill=True)
    latest = store.latest_step(str(faulted))
    assert latest is not None and latest < 3, latest

    res = _run(_cnn_body(analog, engine, faulted))
    assert "resumed after epoch" in res.stdout

    leaves_o, meta_o = _fingerprint(str(oracle), 3)
    leaves_f, meta_f = _fingerprint(str(faulted), 3)
    assert leaves_f == leaves_o          # params+opt_state, byte-exact
    assert meta_f["history"] == meta_o["history"]


# ---------------------------------------------------------------------------
# LM driver (launch.train)
# ---------------------------------------------------------------------------

_LM_BODY = """
    from repro.launch.train import train
    train("stablelm_3b", steps=8, batch=2, seq=32, smoke=True,
          ckpt_dir={ckpt_dir!r}, ckpt_every=3, log_every=100,
          engine="scan", max_restarts={max_restarts})
    print("RUN_DONE")
"""


def _lm_body(ckpt_dir, max_restarts=0):
    return _LM_BODY.format(ckpt_dir=str(ckpt_dir), max_restarts=max_restarts)


def test_lm_kill_at_nonboundary_step_resumes_bitexact(tmp_path):
    oracle, faulted = tmp_path / "oracle", tmp_path / "faulted"
    _run(_lm_body(oracle))

    # step 7 is not a checkpoint boundary (saves land at 3, 6, 8); the
    # injector clips the scan chunk so the kill fires exactly there
    _run(_lm_body(faulted),
         env={"REPRO_FAULT_MODE": "sigkill", "REPRO_FAULT_STEP": 7},
         expect_sigkill=True)
    latest = store.latest_step(str(faulted))
    assert latest in (3, 6), latest      # 6 if its async write finished

    _run(_lm_body(faulted))
    leaves_o, _ = _fingerprint(str(oracle), 8)
    leaves_f, _ = _fingerprint(str(faulted), 8)
    assert leaves_f == leaves_o


def test_lm_kill_mid_async_save_falls_back_and_resumes(tmp_path):
    oracle, faulted = tmp_path / "oracle", tmp_path / "faulted"
    _run(_lm_body(oracle))

    # sigkill_mid_save only fires right after a save is initiated; the
    # write delay holds the background serialisation open so the kill
    # provably lands mid-write of step 6
    _run(_lm_body(faulted),
         env={"REPRO_FAULT_MODE": "sigkill_mid_save",
              "REPRO_FAULT_STEP": 6, "REPRO_CKPT_WRITE_DELAY": 0.2},
         expect_sigkill=True)
    assert store.latest_step(str(faulted)) == 3   # 6 was torn mid-write

    _run(_lm_body(faulted))
    leaves_o, _ = _fingerprint(str(oracle), 8)
    leaves_f, _ = _fingerprint(str(faulted), 8)
    assert leaves_f == leaves_o
    # the torn step_6 partial was garbage-collected by the resumed run
    assert not any(n.endswith(".tmp") for n in os.listdir(faulted))


# ---------------------------------------------------------------------------
# AsyncCheckpointer hard-kill atomicity, in isolation
# ---------------------------------------------------------------------------

def test_async_checkpointer_hard_kill_atomicity(tmp_path):
    """SIGKILL the process while the background writer is mid-serialisation:
    latest_step must fall back to the previous complete step and restore
    cleanly (crc-verified)."""
    _run(f"""
        import os, signal, time
        import jax, jax.numpy as jnp
        from repro.checkpoint import store

        t = {{"w": jnp.arange(64, dtype=jnp.float32),
              "k": jax.random.key(1)}}
        ck = store.AsyncCheckpointer({str(tmp_path)!r})
        ck.save(1, t)
        ck.wait()
        ck.save(2, t)          # held open by REPRO_CKPT_WRITE_DELAY
        time.sleep(0.1)        # kill lands inside the leaf-write loop
        os.kill(os.getpid(), signal.SIGKILL)
    """, env={"REPRO_CKPT_WRITE_DELAY": 0.3}, expect_sigkill=True)

    assert store.latest_step(str(tmp_path)) == 1
    _run(f"""
        import jax, jax.numpy as jnp
        import numpy as np
        from repro.checkpoint import store
        like = {{"w": jnp.zeros(64), "k": jax.random.key(0)}}
        restored, _ = store.restore({str(tmp_path)!r}, 1, like)
        np.testing.assert_array_equal(np.asarray(restored["w"]),
                                      np.arange(64, dtype=np.float32))
    """)


# ---------------------------------------------------------------------------
# Tile-grid elastic shrink 8 -> 4
# ---------------------------------------------------------------------------

_GRID_BODY = """
    from repro.core import device as dev
    from repro.models import lenet
    from repro.train import cnn

    cfg = lenet.LeNetConfig.uniform(
        dev.rpu_nm_bm_um_bl1().with_tile_grid(2, 4))
    cnn.train(cfg, epochs=3, batch=8, n_train=32, n_test=16, seed=0,
              verbose=True, engine="scan", ckpt_dir={ckpt_dir!r})
    print("RUN_DONE")
"""


def test_tile_grid_elastic_shrink_8_to_4_bitexact(tmp_path):
    """Kill a run whose 2x4 crossbar grid is sharded over 8 forced devices;
    resume it on 4 devices (grid -> serial oracle).  The decomposition and
    per-block key schedule never change, so the finished trajectory is
    byte-identical to a 1-device uninterrupted oracle."""
    oracle, faulted = tmp_path / "oracle", tmp_path / "faulted"
    _run(_GRID_BODY.format(ckpt_dir=str(oracle)), devices=1)

    # kill at the epoch-2 boundary: the epoch-1 snapshot had a whole epoch
    # to land; the epoch-2 one races the SIGKILL (either resume point is
    # bit-exact — atomicity guarantees a complete snapshot either way)
    _run(_GRID_BODY.format(ckpt_dir=str(faulted)), devices=8,
         env={"REPRO_FAULT_MODE": "sigkill", "REPRO_FAULT_STEP": 2},
         expect_sigkill=True)
    latest = store.latest_step(str(faulted))
    assert latest in (1, 2), latest

    res = _run(_GRID_BODY.format(ckpt_dir=str(faulted)), devices=4)
    assert "resumed after epoch" in res.stdout

    leaves_o, meta_o = _fingerprint(str(oracle), 3)
    leaves_f, meta_f = _fingerprint(str(faulted), 3)
    assert leaves_f == leaves_o
    assert meta_f["history"] == meta_o["history"]


# ---------------------------------------------------------------------------
# In-process device loss: run_with_restarts + elastic re-shard
# ---------------------------------------------------------------------------

def test_device_loss_elastic_restart_matches_oracle(tmp_path):
    """The full elastic loop in ONE process: the injector raises
    DeviceLossError at the epoch-1 boundary, run_with_restarts marks 4 of
    the 8 devices lost, rebuilds the epoch program (fresh trace: the 2x4
    grid re-resolves to its serial oracle on the 4 survivors) and resumes
    from the epoch-1 snapshot — finishing byte-identical to the 1-device
    uninterrupted oracle."""
    oracle, faulted = tmp_path / "oracle", tmp_path / "faulted"
    _run(_GRID_BODY.format(ckpt_dir=str(oracle)), devices=1)

    res = _run(f"""
        from repro.core import device as dev
        from repro.models import lenet
        from repro.train import cnn
        from repro.distributed import elastic, fault

        cfg = lenet.LeNetConfig.uniform(
            dev.rpu_nm_bm_um_bl1().with_tile_grid(2, 4))
        assert elastic.n_healthy() == 8

        def make_state():
            return {{}}

        def run(state):
            cnn.train(cfg, epochs=3, batch=8, n_train=32, n_test=16,
                      seed=0, verbose=True, engine="scan",
                      ckpt_dir={str(tmp_path / 'faulted')!r})

        def on_restart(attempt, exc):
            assert isinstance(exc, fault.DeviceLossError), exc
            n = elastic.mark_lost(exc.n_lost)
            gp = elastic.grid_plan(n, (2, 4))
            print(f"RESTART healthy={{n}} sharded={{gp.sharded}}")

        attempts = fault.run_with_restarts(make_state, run, max_restarts=1,
                                           on_restart=on_restart)
        assert attempts == 1
    """, devices=8,
        env={"REPRO_FAULT_MODE": "device_loss", "REPRO_FAULT_STEP": 1,
             "REPRO_FAULT_DROP": 4})
    assert "RESTART healthy=4 sharded=False" in res.stdout
    assert "resumed after epoch 1" in res.stdout

    leaves_o, meta_o = _fingerprint(str(oracle), 3)
    leaves_f, meta_f = _fingerprint(str(faulted), 3)
    assert leaves_f == leaves_o
    assert meta_f["history"] == meta_o["history"]


def test_lm_device_loss_restart_matches_oracle(tmp_path):
    """launch.train's own restart driver: a simulated device loss at step 7
    triggers an in-process elastic restart (mark_lost + rebuilt step
    functions + restore from step 6); the finished run matches the
    uninterrupted oracle byte-exact."""
    oracle, faulted = tmp_path / "oracle", tmp_path / "faulted"
    _run(_lm_body(oracle), devices=8)

    res = _run(_lm_body(faulted, max_restarts=1), devices=8,
               env={"REPRO_FAULT_MODE": "device_loss",
                    "REPRO_FAULT_STEP": 7, "REPRO_FAULT_DROP": 4})
    assert "lost 4 device(s), 4 healthy" in res.stdout
    assert "restored step 6" in res.stdout

    leaves_o, _ = _fingerprint(str(oracle), 8)
    leaves_f, _ = _fingerprint(str(faulted), 8)
    assert leaves_f == leaves_o
