"""Implicit-im2col conv kernel (``kernels/conv_mvm.py``) parity.

The kernel assembles patch tiles in VMEM and reuses the managed-read body
shared with ``kernels/managed_mvm.py``, so against the pure-jnp reference it
may differ only by matmul reassociation (allclose) while the saturation
flags and — via the shared epilogue — the select/average structure match
exactly.  Runs in interpret mode on CPU (the CI kernel job forces the
platform); TPU is the performance target.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv_mapping as cm
from repro.core import management
from repro.core import tile as tl
from repro.core.device import RPUConfig
from repro.kernels import conv_mvm
from repro.kernels import ops as kops

TOL = dict(rtol=2e-5, atol=2e-5)


def _setup(nm=True, bm=True, dpw=1, bias=True, cin=3, cout=5, k=3,
           hw=(10, 9), bsz=2):
    cfg = RPUConfig(noise_management=nm, nm_forward=nm, bound_management=bm,
                    bm_mode="two_phase", devices_per_weight=dpw,
                    use_pallas=True)
    x = jax.random.normal(jax.random.key(0), (bsz, *hw, cin))
    st = cm.init(jax.random.key(5), cin, cout, k, cfg, bias=bias)
    geom = cm.conv_geometry(x.shape, k, bias=bias)
    return cfg, st, x, geom


def _reference_read(cfg, st, x, geom, key):
    """Materialized oracle: gather all columns, managed reference read."""
    xpad = cm._pad_volume(x, geom)
    cols = cm.gather_columns(xpad, geom, 0, geom.positions)
    cfg_ref = dataclasses.replace(cfg, use_pallas=False)
    y, sat = tl.tile_forward(
        tl.TileState(w=st.w, maps=None, seed=key), cols, key, cfg_ref,
        return_sat=True)
    return y, sat


@pytest.mark.parametrize("nm,bm,dpw,bias", [
    (False, False, 1, True),
    (True, False, 1, False),
    (True, True, 1, True),
    (True, True, 3, True),
])
def test_conv_kernel_matches_reference(nm, bm, dpw, bias):
    cfg, st, x, geom = _setup(nm=nm, bm=bm, dpw=dpw, bias=bias)
    assert conv_mvm.conv_kernel_eligible(cfg, geom, st.w.shape)
    key = jax.random.key(7)
    xpad = cm._pad_volume(x, geom)
    use_nm = nm  # forward NM needs nm_forward
    nm_s = (cm._conv_nm_scale(xpad, geom) if use_nm
            else jnp.ones((geom.positions, 1), x.dtype))
    args = management.launch_args(cfg, key, backward=False, nm_s=nm_s)
    y_k, sat_k = kops.conv_managed_mvm(st.w, xpad, geom, args, cfg)
    y_ref, sat_ref = _reference_read(cfg, st, x, geom, key)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k), **TOL)
    np.testing.assert_array_equal(np.asarray(sat_ref), np.asarray(sat_k))


def test_conv_kernel_stride_dilation():
    cfg = RPUConfig(use_pallas=True)
    x = jax.random.normal(jax.random.key(0), (2, 11, 10, 2))
    st = cm.init(jax.random.key(5), 2, 4, 3, cfg, bias=True)
    geom = cm.conv_geometry(x.shape, 3, stride=(2, 1), dilation=(1, 2),
                            bias=True)
    assert conv_mvm.conv_kernel_eligible(cfg, geom, st.w.shape)
    key = jax.random.key(7)
    xpad = cm._pad_volume(x, geom)
    nm_s = jnp.ones((geom.positions, 1), x.dtype)
    args = management.launch_args(cfg, key, backward=False, nm_s=nm_s)
    y_k, _ = kops.conv_managed_mvm(st.w, xpad, geom, args, cfg)
    y_ref, _ = _reference_read(cfg, st, x, geom, key)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k), **TOL)


def test_tap_major_weights_layout():
    """w_tm[t*C + c, m] == K[m, c*kh*kw + t]; bias lands as the last row."""
    geom = cm.conv_geometry((1, 6, 6, 2), 3, bias=True)
    m = 4
    w = jax.random.normal(jax.random.key(1), (m, geom.cols))
    w_tm = conv_mvm.tap_major_weights(w, geom, d_avg=1, out_f_p=128)
    kk = geom.kh * geom.kw
    for t in range(kk):
        for c in range(geom.c):
            np.testing.assert_array_equal(
                np.asarray(w_tm[t * geom.c + c, :m]),
                np.asarray(w[:, c * kk + t]))
    np.testing.assert_array_equal(np.asarray(w_tm[kk * geom.c, :m]),
                                  np.asarray(w[:, -1]))


def test_eligibility_gates():
    cfg = RPUConfig(use_pallas=True)
    geom = cm.conv_geometry((1, 8, 8, 2), 3)
    assert conv_mvm.conv_kernel_eligible(cfg, geom, (4, geom.cols))
    assert not conv_mvm.conv_kernel_eligible(
        dataclasses.replace(cfg, use_pallas=False), geom, (4, geom.cols))
    assert not conv_mvm.conv_kernel_eligible(
        dataclasses.replace(cfg, tile_grid=(2, 2)), geom, (4, geom.cols))
    assert not conv_mvm.conv_kernel_eligible(
        dataclasses.replace(cfg, bound_management=True), geom,
        (4, geom.cols))  # iterative BM default
    assert not conv_mvm.conv_kernel_eligible(
        dataclasses.replace(cfg, max_array_cols=4), geom, (4, geom.cols))
    # VMEM budget: a giant image falls back to the gather path
    giant = cm.conv_geometry((1, 2048, 2048, 8), 5)
    assert not conv_mvm.conv_kernel_eligible(cfg, giant, (64, giant.cols))
