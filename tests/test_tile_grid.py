"""Sharded crossbar tile grids: serial-oracle semantics in-process, and
sharded == serial bit-parity in a forced 8-device subprocess.

The in-process tests pin the *serial grid oracle* against the existing
single-tile split semantics (same clip-before-digital-sum physics).  The
subprocess tests (pattern of tests/test_distributed.py: the main pytest
process keeps its single real CPU device) force
``--xla_force_host_platform_device_count=8`` and pin the shard_map paths
numerically identical to the serial oracle — the acceptance contract of the
grid subsystem, including the jit regression for the jax 0.4.37
concat-into-shard_map miscompilation that ``tile_grid._replicated`` guards.
"""

import dataclasses
import os
import subprocess
import sys
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tile as tl
from repro.core import tile_grid as tg
from repro.core.device import RPUConfig, sample_device_maps

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _run_sub(body: str, devices: int = 8) -> str:
    code = textwrap.dedent(f"""
        import os
        os.environ["XLA_FLAGS"] = (
            "--xla_force_host_platform_device_count={devices}")
        import dataclasses
        import jax, jax.numpy as jnp, numpy as np
        from repro.core import tile as tl, tile_grid as tg
        from repro.core.device import RPUConfig, sample_device_maps
        {textwrap.indent(textwrap.dedent(body), '        ').strip()}
        print("SUBPROCESS_OK")
    """)
    # the child is CPU-only: a parent holding a TPU would starve it
    env = dict(os.environ, PYTHONPATH=os.path.join(REPO, "src"),
               JAX_PLATFORMS="cpu")
    res = subprocess.run([sys.executable, "-c", code], capture_output=True,
                         text=True, timeout=900, env=env)
    assert res.returncode == 0, res.stderr[-4000:]
    assert "SUBPROCESS_OK" in res.stdout
    return res.stdout


# ---------------------------------------------------------------------------
# In-process: serial grid oracle semantics (single device)
# ---------------------------------------------------------------------------

def test_grid_geometry_and_validation():
    cfg = RPUConfig(tile_grid=(2, 3))
    g = tg.TileGrid.for_tile((10, 20), cfg)
    assert (g.block_rows, g.block_cols) == (5, 7)
    assert (g.rows_pad, g.cols_pad) == (10, 21)
    assert not g.sharded() or jax.device_count() >= 6
    with pytest.raises(ValueError):
        tg.TileGrid.for_tile((1, 20), cfg)      # more row blocks than rows
    with pytest.raises(ValueError):
        RPUConfig().with_tile_grid(0, 2)


def test_trivial_grid_bit_matches_plain_read():
    """(1, 1) grid == the plain single-tile read, bit for bit (same key:
    ``_block_key`` is the identity for one block)."""
    cfg = RPUConfig(tile_grid=(1, 1))
    w = jax.random.normal(jax.random.key(0), (8, 30)) * 0.3
    x = jax.random.normal(jax.random.key(1), (5, 30))
    for transpose, xin in ((False, x), (True, x[:, :8])):
        y0, s0 = tl.analog_mvm_reference(w, xin, jax.random.key(2), cfg,
                                         transpose=transpose)
        y1, s1 = tg.grid_analog_mvm_reference(w, xin, jax.random.key(2), cfg,
                                              transpose=transpose)
        np.testing.assert_array_equal(np.asarray(y0), np.asarray(y1))
        np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))


def test_grid_matches_split_semantics_noise_free():
    """A (1, C) grid reproduces the legacy contraction-split physics
    (partials clipped before the digital sum) up to einsum association."""
    w = jnp.array([[10.0, 10.0, -5.0, -5.0]])
    x = jnp.ones((1, 4))
    cfg_split = RPUConfig(read_noise=0.0, out_bound=1.0, max_array_cols=2)
    cfg_grid = RPUConfig(read_noise=0.0, out_bound=1.0, tile_grid=(1, 2))
    y0, s0 = tl.analog_mvm_reference(w, x, jax.random.key(0), cfg_split)
    y1, s1 = tg.grid_analog_mvm_reference(w, x, jax.random.key(0), cfg_grid)
    # clip(+20)=1, clip(-10)=-1 -> 0; the unsplit read would give +1
    assert float(y1[0, 0]) == 0.0
    np.testing.assert_allclose(np.asarray(y0), np.asarray(y1),
                               rtol=1e-6, atol=1e-7)
    np.testing.assert_array_equal(np.asarray(s0), np.asarray(s1))

    # dense case incl. padding (cols 17 -> blocks of 9)
    w2 = jax.random.normal(jax.random.key(3), (6, 17)) * 0.3
    x2 = jax.random.normal(jax.random.key(4), (4, 17))
    cfg0 = RPUConfig(read_noise=0.0, out_bound=float("inf"))
    cfg2 = dataclasses.replace(cfg0, tile_grid=(3, 2))
    y2, _ = tg.grid_analog_mvm_reference(w2, x2, jax.random.key(5), cfg2)
    np.testing.assert_allclose(np.asarray(y2), np.asarray(x2 @ w2.T),
                               rtol=1e-5, atol=1e-6)


def test_grid_forward_backward_replica_semantics():
    """#_d replica averaging / replica divide survive the grid routing."""
    cfg = dataclasses.replace(
        RPUConfig(read_noise=0.0, out_bound=float("inf")),
        devices_per_weight=3, tile_grid=(2, 2))
    state = tl.init_tile(jax.random.key(0), 4, 8, cfg)
    w = state.w.at[0].add(0.3).at[4].add(-0.3)
    state = tl.TileState(w=w, maps=state.maps, seed=state.seed)
    x = jax.random.normal(jax.random.key(1), (5, 8)) * 0.2
    y = tl.tile_forward(state, x, jax.random.key(2), cfg)
    want = x @ tl.effective_weights(state, cfg).T
    np.testing.assert_allclose(np.asarray(y), np.asarray(want), rtol=1e-4,
                               atol=1e-5)
    d = jax.random.normal(jax.random.key(3), (5, 4)) * 0.2
    z = tl.tile_backward(state, d, jax.random.key(4), cfg)
    want_z = d @ tl.effective_weights(state, cfg)
    np.testing.assert_allclose(np.asarray(z), np.asarray(want_z), rtol=1e-4,
                               atol=1e-5)


def test_grid_update_matches_plain_update_without_ctoc():
    """With ctoc=0 (the only per-block noise draw) and divisible shapes the
    serial grid update is bit-identical to the plain pulse update: the
    coincidence contraction is slice-exact and the streams share one
    sampling layout."""
    from repro.core import update as update_lib
    cfg_plain = RPUConfig(dw_min_ctoc=0.0)
    cfg_grid = dataclasses.replace(cfg_plain, tile_grid=(2, 4))
    w = jax.random.normal(jax.random.key(0), (8, 16)) * 0.1
    maps = sample_device_maps(jax.random.key(1), 8, 16, cfg_plain)
    x = jax.random.normal(jax.random.key(2), (5, 16))
    delta = jax.random.normal(jax.random.key(3), (5, 8)) * 0.5
    w_plain = update_lib.pulse_update(w, maps, x, delta, jax.random.key(4),
                                     cfg_plain, 0.01)
    w_grid = update_lib.pulse_update(w, maps, x, delta, jax.random.key(4),
                                    cfg_grid, 0.01)
    np.testing.assert_array_equal(np.asarray(w_plain), np.asarray(w_grid))


def test_replicate_delta_single_layout_source():
    d = jnp.ones((3, 4))
    out = tl.replicate_delta(d, 3, rows_phys=12)
    assert out.shape == (3, 12)
    np.testing.assert_array_equal(np.asarray(out[:, :4]), np.asarray(d))
    with pytest.raises(AssertionError):
        tl.replicate_delta(d, 2, rows_phys=12)


def test_grid_is_sharded_and_engine_guard_on_single_device():
    cfg = RPUConfig(tile_grid=(2, 2))
    if jax.device_count() == 1:
        assert not tg.grid_is_sharded(cfg)   # falls back to serial oracle
    assert not tg.grid_is_sharded(RPUConfig())
    assert not tg.grid_is_sharded(RPUConfig(tile_grid=(1, 1)))


# ---------------------------------------------------------------------------
# Subprocess: sharded == serial oracle on a forced 8-device host
# ---------------------------------------------------------------------------

def test_sharded_read_parity_with_serial_oracle():
    """Managed reads (forward + transpose) bit-identical between the
    shard_map path and the serial single-device grid oracle across NM
    on/off x BM off/two-phase/iterative x #_d x grid shapes."""
    _run_sub("""
        cases = [
            # (grid, nm, bm_mode_or_None, devices_per_weight, use_pallas)
            ((2, 2), True, "two_phase", 2, False),
            ((1, 4), False, None, 1, False),
            ((4, 2), True, "iterative", 1, False),
            ((2, 3), True, None, 2, False),
            ((2, 2), True, "two_phase", 1, True),   # noisy_mvm kernel/shard
        ]
        for grid, nm, bm, dpw, pallas in cases:
            cfg = RPUConfig(tile_grid=grid, devices_per_weight=dpw,
                            noise_management=nm, nm_forward=nm,
                            bound_management=bm is not None,
                            bm_mode=bm or "iterative", out_bound=2.0,
                            use_pallas=pallas)
            w = jax.random.normal(jax.random.key(0), (12, 21)) * 0.8
            x = jax.random.normal(jax.random.key(1), (5, 21)) * 3.0
            dlt = jax.random.normal(jax.random.key(2), (5, 12)) * 3.0
            key = jax.random.key(3)
            for transpose, xin in ((False, x), (True, dlt)):
                ref = tg.grid_managed_mvm(w, xin, key, cfg,
                                          transpose=transpose,
                                          backward=transpose,
                                          force_reference=True)
                got = tg.grid_managed_mvm(w, xin, key, cfg,
                                          transpose=transpose,
                                          backward=transpose)
                for a, b in zip(ref, got):
                    np.testing.assert_array_equal(np.asarray(a),
                                                  np.asarray(b))
    """)


def test_sharded_update_parity_with_serial_oracle():
    """Communication-free sharded pulse update == serial oracle, with UM,
    ctoc noise, #_d replication and non-divisible padding."""
    _run_sub("""
        cfg = RPUConfig(tile_grid=(2, 3), update_management=True,
                        devices_per_weight=2)
        w = jax.random.normal(jax.random.key(0), (10, 21)) * 0.1
        maps = sample_device_maps(jax.random.key(4), 10, 21, cfg)
        x = jax.random.normal(jax.random.key(5), (5, 21))
        dlt = jax.random.normal(jax.random.key(6), (5, 10)) * 0.5
        wr = tg.grid_pulse_update(w, maps, x, dlt, jax.random.key(7), cfg,
                                  0.01, force_reference=True)
        ws = tg.grid_pulse_update(w, maps, x, dlt, jax.random.key(7), cfg,
                                  0.01)
        np.testing.assert_array_equal(np.asarray(wr), np.asarray(ws))
        assert np.any(np.asarray(wr) != np.asarray(w))
    """)


def test_sharded_jit_concat_producer_regression():
    """jit parity when the shard_map operand is produced by concatenate
    (the analog bias column): regression for the jax 0.4.37 GSPMD
    miscompilation guarded by ``tile_grid._replicated`` — without the
    replicated constraint the read returns clean+read instead of read."""
    _run_sub("""
        from repro.core import analog_linear as al
        rpu = RPUConfig(tile_grid=(2, 2), noise_management=True,
                        bound_management=True)
        lin = al.init(jax.random.key(6), 17, 6, rpu)
        x = jax.random.normal(jax.random.key(1), (4, 17)) * 2.0
        key = jax.random.key(7)
        y_eager = al.apply(lin, x, key, rpu, jnp.asarray(0.01))
        y_jit = jax.jit(lambda st, xx, k: al.apply(
            st, xx, k, rpu, jnp.asarray(0.01)))(lin, x, key)
        # tight tolerance, not bit-equality: jit fuses the digital scale
        # muls in a different order (ulp-level); the miscompilation this
        # guards against returned clean+read — an O(1) difference
        np.testing.assert_allclose(np.asarray(y_eager), np.asarray(y_jit),
                                   rtol=2e-6, atol=2e-6)

        # full custom_vjp train-grad parity, sharded vs forced-serial
        def loss(st, xx, k):
            y = al.apply(st, xx, k, rpu, jnp.asarray(0.01))
            return jnp.sum(y ** 2)
        gfn = jax.jit(lambda st, xx, k: jax.grad(
            loss, allow_int=True)(st, xx, k).w)
        g_sharded = np.asarray(gfn(lin, x, key))
        orig = tg.TileGrid.sharded
        tg.TileGrid.sharded = lambda self: False
        jax.clear_caches()
        g_serial = np.asarray(jax.jit(lambda st, xx, k: jax.grad(
            loss, allow_int=True)(st, xx, k).w)(lin, x, key))
        tg.TileGrid.sharded = orig
        np.testing.assert_array_equal(g_sharded, g_serial)
    """)


def test_sharded_chained_conv_regression():
    """Chained conv reads (im2col slice-concats over a previous read's
    mesh-sharded output) were the second trigger of the jax 0.4.37
    miscompilation — only pinning shard_map *outputs* to a replicated
    layout as well keeps the whole chain bit-equal to the serial oracle
    under one jit."""
    _run_sub("""
        from repro.core import conv_mapping
        rpu = RPUConfig(tile_grid=(2, 2), noise_management=True,
                        nm_forward=True)
        k1 = conv_mapping.init(jax.random.key(0), 4, 8, 3, rpu)
        k2 = conv_mapping.init(jax.random.key(1), 8, 6, 3, rpu)
        imgs = jax.random.normal(jax.random.key(2), (2, 10, 10, 4))
        key = jax.random.key(3)

        def chain(a, b, xx, k):
            ka, kb = jax.random.split(k)
            h = jnp.tanh(conv_mapping.apply(a, xx, ka, rpu,
                                            jnp.asarray(0.01), kernel=3))
            return conv_mapping.apply(b, h, kb, rpu, jnp.asarray(0.01),
                                      kernel=3)

        y_sh = np.asarray(jax.jit(chain)(k1, k2, imgs, key))
        orig = tg.TileGrid.sharded
        tg.TileGrid.sharded = lambda self: False
        jax.clear_caches()
        y_se = np.asarray(jax.jit(chain)(k1, k2, imgs, key))
        tg.TileGrid.sharded = orig
        np.testing.assert_array_equal(y_sh, y_se)
    """)


def test_sharded_training_parity_scan_engine():
    """End-to-end acceptance: one epoch of grid-sharded LeNet training
    through the scan-fused engine produces bit-identical parameters to the
    same training with the grid forced onto the serial oracle."""
    _run_sub("""
        from repro.core import device as dev
        from repro.models.lenet import LeNetConfig
        from repro.train import cnn
        rpu = dev.rpu_nm_bm().with_tile_grid(2, 2)
        cfg = LeNetConfig.uniform(rpu, mode="analog")
        kw = dict(epochs=1, batch=8, n_train=32, n_test=32, verbose=False,
                  return_params=True, engine="scan")
        res_sharded = cnn.train(cfg, **kw)
        orig = tg.TileGrid.sharded
        tg.TileGrid.sharded = lambda self: False
        jax.clear_caches()
        res_serial = cnn.train(cfg, **kw)
        tg.TileGrid.sharded = orig
        assert res_sharded["test_error"] == res_serial["test_error"]
        for name in ("K1", "K2", "W3", "W4"):
            np.testing.assert_array_equal(
                np.asarray(res_sharded["params"][name].w),
                np.asarray(res_serial["params"][name].w))
    """)


def test_engine_rejects_crossbar_data_parallel_conflict():
    """The scan engine refuses to nest a sharded tile grid inside its
    data-parallel mesh (same devices, conflicting placements)."""
    _run_sub("""
        from repro.core import device as dev
        from repro.models.lenet import LeNetConfig
        from repro.optim import analog_sgd
        from repro.train import engine as eng
        rpu = dev.rpu_nm_bm().with_tile_grid(2, 2)
        cfg = LeNetConfig.uniform(rpu, mode="analog")
        try:
            eng.make_cnn_epoch_fn(cfg, analog_sgd(), batch=8,
                                  data_parallel=True)
        except ValueError as e:
            assert "crossbar" in str(e) or "tile grid" in str(e), e
        else:
            raise AssertionError("expected the mesh-conflict ValueError")
        # without data parallelism the same config builds fine
        eng.make_cnn_epoch_fn(cfg, analog_sgd(), batch=8)
    """)
