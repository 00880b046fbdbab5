"""Parity: fused managed-read Pallas kernel (interpret mode) vs the reworked
pure-jnp reference pipeline.

The fused kernel (`kernels/managed_mvm.py`) draws bit-identical counter-hash
noise to `core.tile.managed_mvm_reference` with the same key discipline, so
tolerances are matmul-reassociation-level only (the kernel applies the
digital scale after the MXU product, the reference before).  Sweeps forward
and transpose reads over NM on/off × BM {off, two_phase} × #_d × contraction
splits; the iterative BM mode is exercised end-to-end through the tile API
(one `noisy_mvm` launch per retry inside the while_loop).
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import tile as tl
from repro.core.device import RPUConfig
from repro.kernels import ops as kops

TOL = dict(rtol=2e-5, atol=2e-5)

CASES = [
    # (rows, cols, batch, n_seg, transpose, #_d)
    (16, 26, 8, 1, False, 1),       # the paper's K1 tile
    (32, 401, 16, 1, False, 1),     # K2
    (39, 20, 8, 1, False, 3),       # multi-device replica average
    (130, 48, 24, 1, False, 13),    # paper's 13-device mapping, odd dims
    (30, 200, 8, 2, False, 1),      # contraction split x2
    (24, 16, 8, 1, True, 1),        # transpose (backward) read
    (300, 20, 10, 3, True, 1),      # transpose + contraction split x3
]

MODES = [
    (False, False),
    (True, False),
    (False, True),
    (True, True),
]


def _cfg(r, c, n_seg, tr, d, *, nm, bm, alpha=4.0, sigma=0.06):
    return RPUConfig(
        read_noise=sigma, out_bound=alpha,
        noise_management=nm, nm_forward=True,
        bound_management=bm, bm_mode="two_phase",
        devices_per_weight=d,
        max_array_cols=10 ** 9 if tr else -(-c // n_seg),
        max_array_rows=-(-r // n_seg) if tr else 10 ** 9)


def _data(r, c, b, tr, scale=1.5):
    w = jax.random.normal(jax.random.key(1), (r, c)) * 0.3
    k_in = r if tr else c
    x = jax.random.normal(jax.random.key(2), (b, k_in)) * scale
    return w, x


@pytest.mark.parametrize("nm,bm", MODES)
@pytest.mark.parametrize("r,c,b,n_seg,tr,d", CASES)
def test_fused_managed_read_matches_reference(r, c, b, n_seg, tr, d, nm, bm):
    if tr and d > 1:
        pytest.skip("replica average is a forward-read operation")
    cfg = _cfg(r, c, n_seg, tr, d, nm=nm, bm=bm)
    w, x = _data(r, c, b, tr)
    key = jax.random.key(hash((r, c, b, n_seg, tr, d, nm, bm)) % (2 ** 31))

    y_ref, sat_ref = tl.managed_mvm_reference(w, x, key, cfg, transpose=tr,
                                              backward=tr)
    if not tr and d > 1:
        y_ref = tl._replica_mean(y_ref, d)
    y_k, sat_k = tl.managed_mvm(w, x, key, cfg, transpose=tr, backward=tr)

    assert y_k.shape == y_ref.shape
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k), **TOL)
    np.testing.assert_array_equal(np.asarray(sat_ref), np.asarray(sat_k))


@pytest.mark.parametrize("nm,bm", MODES)
def test_tile_forward_pallas_matches_reference(nm, bm):
    """Full tile-level routing parity (fused launch vs jnp pipeline),
    including the replica average baked into the kernel."""
    cfg = dataclasses.replace(
        _cfg(39, 20, 1, False, 3, nm=nm, bm=bm), use_pallas=False)
    w, x = _data(39, 20, 12, False)
    state = tl.TileState(w=w, maps=None, seed=jax.random.key(0))
    key = jax.random.key(11)
    y_ref, sat_ref = tl.tile_forward(state, x, key, cfg, return_sat=True)
    cfg_k = dataclasses.replace(cfg, use_pallas=True)
    y_k, sat_k = tl.tile_forward(state, x, key, cfg_k, return_sat=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k), **TOL)
    np.testing.assert_array_equal(np.asarray(sat_ref), np.asarray(sat_k))


@pytest.mark.parametrize("nm,bm", MODES)
def test_tile_backward_pallas_matches_reference(nm, bm):
    """Transpose-read routing parity with #_d input-side replication."""
    cfg = dataclasses.replace(
        _cfg(39, 20, 1, True, 1, nm=nm, bm=bm), devices_per_weight=3,
        use_pallas=False)
    w = jax.random.normal(jax.random.key(1), (39, 20)) * 0.3
    delta = jax.random.normal(jax.random.key(2), (6, 13)) * 1.5
    state = tl.TileState(w=w, maps=None, seed=jax.random.key(0))
    key = jax.random.key(12)
    z_ref, s_ref = tl.tile_backward(state, delta, key, cfg, return_sat=True)
    cfg_k = dataclasses.replace(cfg, use_pallas=True)
    z_k, s_k = tl.tile_backward(state, delta, key, cfg_k, return_sat=True)
    np.testing.assert_allclose(np.asarray(z_ref), np.asarray(z_k), **TOL)
    np.testing.assert_array_equal(np.asarray(s_ref), np.asarray(s_k))


def test_tile_iterative_bm_pallas_matches_reference():
    """Iterative BM is NOT fusable — it must route through one noisy_mvm
    launch per retry and still match the jnp while_loop bit-compatibly."""
    cfg = RPUConfig(read_noise=0.06, out_bound=4.0, noise_management=True,
                    nm_forward=True, bound_management=True,
                    bm_mode="iterative", bm_max_iters=8)
    w, x = _data(16, 26, 8, False, scale=2.0)
    state = tl.TileState(w=w, maps=None, seed=jax.random.key(0))
    key = jax.random.key(13)
    y_ref, sat_ref = tl.tile_forward(state, x, key, cfg, return_sat=True)
    cfg_k = dataclasses.replace(cfg, use_pallas=True)
    y_k, sat_k = tl.tile_forward(state, x, key, cfg_k, return_sat=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k), **TOL)
    np.testing.assert_array_equal(np.asarray(sat_ref), np.asarray(sat_k))


def test_fused_residual_saturation_semantics():
    """Two-phase residual flag from the kernel: True only where the 1/16
    read also clipped."""
    cfg = RPUConfig(read_noise=0.0, out_bound=12.0, bound_management=True,
                    bm_mode="two_phase")
    w = jnp.eye(4)
    x = jnp.stack([jnp.full((4,), 100.0), jnp.full((4,), 1000.0),
                   jnp.full((4,), 1.0)])
    y, sat = tl.managed_mvm(w, x, jax.random.key(3), cfg)
    np.testing.assert_array_equal(np.asarray(sat), [False, True, False])
    np.testing.assert_allclose(np.asarray(y[0]), 100.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y[1]), 16.0 * 12.0, rtol=1e-5)
    np.testing.assert_allclose(np.asarray(y[2]), 1.0, rtol=1e-5)


def test_fused_managed_read_batch_shapes():
    cfg = RPUConfig(noise_management=True, nm_forward=True,
                    bound_management=True, bm_mode="two_phase")
    w = jax.random.normal(jax.random.key(1), (40, 30)) * 0.2
    x = jax.random.normal(jax.random.key(2), (4, 7, 30))
    y, sat = tl.managed_mvm(w, x, jax.random.key(5), cfg)
    assert y.shape == (4, 7, 40)
    assert sat.shape == (4, 7)


def test_interpret_default_tracks_backend(monkeypatch):
    """Regression: `_interpret_default` must follow the ACTIVE backend, not
    an lru_cache'd snapshot from the first kernel call — a platform change
    after import silently ran the wrong mode."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert kops._interpret_default() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert kops._interpret_default() is True
