"""Streaming conv pipeline: chunked-vs-materialized bit-parity.

The streaming driver (``core/conv_mapping.py``) must be *bit-identical* to
the materialized path (``conv_stream_chunk=None`` — one chunk) in all three
analog cycles, for every routing: reference / Pallas, plain tile /
sub-tile grid, NM x BM x #_d x UM.  These tests pin that contract with
``assert_array_equal`` (not allclose): the update counts are integer sums,
the read noise uses counter-offset draws, and col2im accumulates in a
chunk-invariant order, so nothing may drift even one ulp.

Tier-1 runs a representative sample; the full cross-product carries the
``slow`` marker (deselected by default via pyproject addopts) and runs in
the CI kernel/distributed jobs.  Sharded-grid cases skip below 8 devices
and are exercised by the forced-8-device distributed CI job.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import conv_mapping as cm
from repro.core import tile_grid as tg
from repro.core import update as up
from repro.core.device import RPUConfig, sample_device_maps
from repro.core.tile import TileState


def _state(cfg, cin=3, cout=5, k=3, seed=5, bias=True):
    return cm.init(jax.random.key(seed), cin, cout, k, cfg, bias=bias)


def _x(shape=(2, 10, 10, 3), seed=0):
    return jax.random.normal(jax.random.key(seed), shape)


def _grads(st, x, cfg, **conv_kw):
    """Full three-cycle pull: (w_bar, x_bar) through the analog conv."""
    def f(w, xx):
        s = TileState(w=w, maps=st.maps, seed=st.seed)
        y = cm.apply(s, xx, jax.random.key(11), cfg, 0.01, **conv_kw)
        return jnp.sum(y ** 2)

    return jax.grad(f, argnums=(0, 1))(st.w, x)


def _assert_cycles_match(cfg_mat, cfg_chunk, conv_kw=None, x=None,
                         state_kw=None):
    conv_kw = dict(kernel=3, **(conv_kw or {}))
    x = _x() if x is None else x
    st = _state(cfg_mat, **(state_kw or {}))
    y_mat = cm.apply(st, x, jax.random.key(11), cfg_mat, 0.01, **conv_kw)
    y_ch = cm.apply(st, x, jax.random.key(11), cfg_chunk, 0.01, **conv_kw)
    np.testing.assert_array_equal(np.asarray(y_mat), np.asarray(y_ch))
    gw_mat, gx_mat = _grads(st, x, cfg_mat, **conv_kw)
    gw_ch, gx_ch = _grads(st, x, cfg_chunk, **conv_kw)
    np.testing.assert_array_equal(np.asarray(gw_mat), np.asarray(gw_ch))
    np.testing.assert_array_equal(np.asarray(gx_mat), np.asarray(gx_ch))


def _chunked(cfg, chunk):
    return dataclasses.replace(cfg, conv_stream_chunk=chunk,
                               update_chunk=chunk)


# ---------------------------------------------------------------------------
# Reference-path parity (tier-1)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("chunk", [7, 64])
def test_chunked_cycles_bit_match_materialized(chunk):
    cfg = RPUConfig(noise_management=True, nm_forward=True,
                    bound_management=True, bm_mode="two_phase")
    _assert_cycles_match(cfg, _chunked(cfg, chunk))


def test_chunked_with_um_and_multi_device():
    cfg = RPUConfig(noise_management=True, bound_management=True,
                    bm_mode="two_phase", update_management=True,
                    devices_per_weight=3)
    _assert_cycles_match(cfg, _chunked(cfg, 13))


def test_chunked_iterative_bm_noise_free():
    # Iterative BM's retry loop is chunk-local; with read noise the extra
    # re-reads draw fresh (distribution-identical) noise, so exact parity
    # is pinned in the deterministic noise-free setting.
    cfg = RPUConfig(noise_management=True, bound_management=True,
                    bm_mode="iterative", read_noise=0.0, out_bound=4.0)
    _assert_cycles_match(cfg, _chunked(cfg, 9))


def test_chunked_stride_dilation_explicit_padding():
    cfg = RPUConfig(noise_management=True, bound_management=True,
                    bm_mode="two_phase")
    _assert_cycles_match(
        cfg, _chunked(cfg, 5),
        conv_kw=dict(stride=(2, 1), dilation=(1, 2),
                     padding=((2, 1), (0, 3))),
        x=_x((2, 11, 9, 3), seed=3))


def test_with_streaming_preserves_unspecified_fields():
    cfg = RPUConfig().with_streaming(conv_stream_chunk=64)
    cfg = cfg.with_streaming(update_chunk=128)
    assert cfg.conv_stream_chunk == 64          # not reset by second call
    assert cfg.update_chunk == 128
    with pytest.raises(ValueError):
        RPUConfig().with_streaming(update_chunk=0)
    with pytest.raises(ValueError):
        dataclasses.replace(RPUConfig(), fast_rng=False).with_streaming(
            update_chunk=8)


def test_update_chunk_linear_layer_bit_match():
    """cfg.update_chunk streams ANY tile's update cycle (linear included)."""
    cfg = RPUConfig(update_management=True)
    maps = sample_device_maps(jax.random.key(3), 16, 26, cfg)
    w = jax.random.uniform(jax.random.key(4), (16, 26), minval=-.3, maxval=.3)
    x = jax.random.normal(jax.random.key(1), (7, 9, 26)) * 0.5
    d = jax.random.normal(jax.random.key(2), (7, 9, 16)) * 0.2
    w_mat = up.pulse_update(w, maps, x, d, jax.random.key(0), cfg, 0.01)
    for chunk in (1, 5, 64, 200):
        c = dataclasses.replace(cfg, update_chunk=chunk)
        w_ch = up.pulse_update(w, maps, x, d, jax.random.key(0), c, 0.01)
        np.testing.assert_array_equal(np.asarray(w_mat), np.asarray(w_ch))


def test_materialized_stream_path_matches_legacy_dense_layer():
    """chunk=None through the streaming vjp == the historical im2col +
    analog_linear path for the forward read (same key discipline, same
    managed read over the same column matrix).  Both sides are jitted:
    the streaming driver's chunk loop is compiled by construction, and XLA
    fuses (e.g. FMAs) identically only when the dense oracle compiles too
    — eager-vs-compiled differs by ulps, jit-vs-jit is exact.
    """
    from repro.core import analog_linear
    cfg = RPUConfig(noise_management=True, nm_forward=True,
                    bound_management=True, bm_mode="two_phase")
    st = _state(cfg)
    x = _x()
    key = jax.random.key(11)
    y_stream = jax.jit(
        lambda xx: cm.apply(st, xx, key, cfg, 0.01, kernel=3))(x)
    y_dense = jax.jit(
        lambda xx: analog_linear.apply(st, cm.im2col(xx, 3), key, cfg,
                                       jnp.asarray(0.01)))(x)
    np.testing.assert_array_equal(np.asarray(y_stream), np.asarray(y_dense))


def test_gather_columns_match_im2col_rows():
    """The streamed gather is the same column matrix im2col materializes."""
    x = _x((2, 9, 8, 3), seed=7)
    for stride, pad, dil in [(1, "VALID", 1), ((2, 1), "SAME", 1),
                             (1, ((1, 2), (2, 0)), (2, 1))]:
        geom = cm.conv_geometry(x.shape, (3, 2), stride, pad, dil, bias=True)
        patches = cm.im2col(x, (3, 2), stride, pad, dil)
        cols_ref = patches.reshape(-1, geom.features)
        xpad = cm._pad_volume(x, geom)
        got = cm.gather_columns(xpad, geom, 0, geom.positions)
        np.testing.assert_array_equal(np.asarray(got[:, :-1]),
                                      np.asarray(cols_ref))
        np.testing.assert_array_equal(np.asarray(got[:, -1]),
                                      np.ones(geom.positions, np.float32))
        # chunked gather slices the same rows (incl. zero tail padding)
        part = cm.gather_columns(xpad, geom, 5, 7)
        np.testing.assert_array_equal(np.asarray(part),
                                      np.asarray(got[5:12]))


def test_explicit_padding_matches_conv_oracle():
    """apply() explicit per-dim padding pairs drive lax-conv semantics."""
    cfg = RPUConfig(read_noise=0.0, out_bound=float("inf"))
    x = _x((2, 8, 9, 2), seed=9)
    kernels = jax.random.normal(jax.random.key(1), (3, 3, 2, 4)) * 0.3
    kmat = cm.kernel_matrix_from_conv(kernels)
    st = cm.init(jax.random.key(2), 2, 4, 3, cfg, bias=False)
    st = TileState(w=kmat.astype(jnp.float32), maps=st.maps, seed=st.seed)
    pads = ((2, 0), (1, 3))
    got = cm.apply(st, x, jax.random.key(3), cfg, 0.01, kernel=3,
                   padding=pads, bias=False)
    want = jax.lax.conv_general_dilated(
        x, kernels, (1, 1), list(pads),
        dimension_numbers=("NHWC", "HWIO", "NHWC"))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Pallas-path parity (tier-1 sample; CI kernel job runs this file too)
# ---------------------------------------------------------------------------

def test_chunked_pallas_cycles_bit_match_materialized():
    cfg = RPUConfig(noise_management=True, nm_forward=True,
                    bound_management=True, bm_mode="two_phase",
                    use_pallas=True, devices_per_weight=2)
    _assert_cycles_match(cfg, _chunked(cfg, 7))


def test_pallas_update_bit_matches_reference():
    """The pallas update now routes counts -> shared finalize: bit-equal to
    the reference across chunked AND unchunked (integer counts + one shared
    finalize), not merely allclose."""
    cfg = RPUConfig()
    cfgp = dataclasses.replace(cfg, use_pallas=True)
    maps = sample_device_maps(jax.random.key(3), 16, 26, cfg)
    w = jax.random.uniform(jax.random.key(4), (16, 26), minval=-.3, maxval=.3)
    x = jax.random.normal(jax.random.key(1), (5, 26)) * 0.5
    d = jax.random.normal(jax.random.key(2), (5, 16)) * 0.2
    w_ref = up.pulse_update(w, maps, x, d, jax.random.key(0), cfg, 0.01)
    w_pal = up.pulse_update(w, maps, x, d, jax.random.key(0), cfgp, 0.01)
    np.testing.assert_array_equal(np.asarray(w_ref), np.asarray(w_pal))


# ---------------------------------------------------------------------------
# Grid composition (serial oracle in tier-1; sharded in the 8-device job)
# ---------------------------------------------------------------------------

def test_chunked_grid_serial_cycles_bit_match():
    cfg = RPUConfig(noise_management=True, bound_management=True,
                    bm_mode="two_phase", tile_grid=(2, 2))
    _assert_cycles_match(cfg, _chunked(cfg, 9), state_kw=dict(cout=4))


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (forced-host CI job)")
def test_chunked_grid_sharded_cycles_bit_match():
    cfg = RPUConfig(noise_management=True, bound_management=True,
                    bm_mode="two_phase", tile_grid=(2, 4))
    assert tg.grid_is_sharded(cfg)
    _assert_cycles_match(cfg, _chunked(cfg, 9), state_kw=dict(cout=6))


@pytest.mark.skipif(jax.device_count() < 8,
                    reason="needs 8 devices (forced-host CI job)")
def test_chunked_grid_sharded_update_matches_serial():
    cfg = RPUConfig(update_management=True, tile_grid=(2, 4),
                    update_chunk=5)
    maps = sample_device_maps(jax.random.key(3), 16, 26, cfg)
    w = jax.random.uniform(jax.random.key(4), (16, 26), minval=-.3, maxval=.3)
    x = jax.random.normal(jax.random.key(1), (13, 26)) * 0.5
    d = jax.random.normal(jax.random.key(2), (13, 16)) * 0.2
    w_sh = up.pulse_update(w, maps, x, d, jax.random.key(0), cfg, 0.01)
    w_se = tg.grid_pulse_update(w, maps, x, d, jax.random.key(0), cfg, 0.01,
                                force_reference=True)
    np.testing.assert_array_equal(np.asarray(w_sh), np.asarray(w_se))


# ---------------------------------------------------------------------------
# Full cross-product (slow — CI kernel/distributed jobs)
# ---------------------------------------------------------------------------

@pytest.mark.slow
@pytest.mark.parametrize("nm", [False, True])
@pytest.mark.parametrize("bm", [False, True])
@pytest.mark.parametrize("dpw", [1, 2])
@pytest.mark.parametrize("grid", [None, (2, 2)])
@pytest.mark.parametrize("pallas", [False, True])
def test_chunked_cycles_cross_product(nm, bm, dpw, grid, pallas):
    cfg = RPUConfig(noise_management=nm, nm_forward=nm,
                    bound_management=bm, bm_mode="two_phase",
                    devices_per_weight=dpw, tile_grid=grid,
                    use_pallas=pallas)
    _assert_cycles_match(cfg, _chunked(cfg, 11), state_kw=dict(cout=4),
                         x=_x((2, 8, 8, 3), seed=2))


# ---------------------------------------------------------------------------
# One-chunk conv mapping: tap slices and pad-and-add, bit-equal to the
# index gather and scatter-add that smaller chunks use
# ---------------------------------------------------------------------------

_GEOMS = {
    "lenet_k1": ((8, 28, 28, 1), 5, 1, "VALID", 1, True),
    "lenet_k2": ((8, 12, 12, 16), 5, 1, "VALID", 1, True),
    "stride_dilation_explicit_pad": ((2, 9, 8, 3), 3, (2, 1),
                                     ((2, 1), (0, 3)), (1, 2), True),
    "same": ((2, 9, 8, 3), (3, 2), 1, "SAME", 1, True),
    "no_bias": ((2, 10, 10, 3), 3, 1, "VALID", 1, False),
}


def _geom_and_volume(name):
    shape, kernel, stride, pad, dil, bias = _GEOMS[name]
    geom = cm.conv_geometry(shape, kernel, stride, pad, dil, bias=bias)
    xpad = cm._pad_volume(_x(shape, seed=3), geom)
    return geom, xpad


def _chunked_col2im(z, geom, chunk):
    """The chunk loop ``_stream_backward`` runs, over index scatter-adds."""
    nchunks = -(-geom.positions // chunk)
    zp = jnp.pad(z, ((0, nchunks * chunk - geom.positions), (0, 0)))

    def body(ci, xbar):
        start = ci * chunk
        zc = jax.lax.dynamic_slice_in_dim(zp, start, chunk)
        return cm.col2im_add(zc, geom, start, chunk, xbar)

    xbar = jnp.zeros((geom.b, geom.h, geom.w, geom.c), z.dtype)
    return jax.lax.fori_loop(0, nchunks, body, xbar)


@pytest.mark.parametrize("name", list(_GEOMS))
def test_one_chunk_columns_bit_match_gathered_chunks(name):
    geom, xpad = _geom_and_volume(name)
    whole = cm.gather_columns(xpad, geom, 0, geom.positions)
    parts = [cm.gather_columns(xpad, geom, s, 7)
             for s in range(0, geom.positions, 7)]
    gathered = jnp.concatenate(parts)[:geom.positions]
    assert whole.shape == (geom.positions, geom.cols)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(gathered))


@pytest.mark.parametrize("name", list(_GEOMS))
def test_one_chunk_col2im_bit_matches_chunked_scatter(name):
    """``z`` is made by a multiply inside the compiled program, as the
    transpose read's scaling makes it, so a multiply-add contraction across
    the col2im adds would show."""
    geom, _ = _geom_and_volume(name)
    a = jax.random.normal(jax.random.key(4), (geom.positions, geom.features))
    s = jax.random.uniform(jax.random.key(5), (geom.positions, 1),
                           minval=0.5, maxval=2.0)
    zero = jnp.zeros((geom.b, geom.h, geom.w, geom.c), a.dtype)
    whole = jax.jit(lambda aa, ss: cm.col2im_add(aa * ss, geom, 0,
                                                 geom.positions, zero))(a, s)
    chunked = jax.jit(lambda aa, ss: _chunked_col2im(aa * ss, geom, 7))(a, s)
    np.testing.assert_array_equal(np.asarray(whole), np.asarray(chunked))


def _primitives(jaxpr):
    """Every primitive name in a jaxpr, sub-jaxprs included."""
    names = set()
    for eqn in jaxpr.eqns:
        names.add(eqn.primitive.name)
        for sub in jax.core.jaxprs_in_params(eqn.params):
            names |= _primitives(sub)
    return names


@pytest.mark.parametrize("name", ["lenet_k1", "lenet_k2"])
def test_one_chunk_conv_mapping_has_no_gather_or_scatter(name):
    """The materialized path moves data by static slices and pads; only a
    chunk smaller than the positions gathers and scatter-adds by index."""
    geom, xpad = _geom_and_volume(name)
    xbar = jnp.zeros_like(xpad)
    for chunk, indexed in [(geom.positions, False), (7, True)]:
        z = jnp.zeros((chunk, geom.features))
        cols = _primitives(jax.make_jaxpr(
            lambda x: cm.gather_columns(x, geom, 0, chunk))(xpad).jaxpr)
        back = _primitives(jax.make_jaxpr(
            lambda zz, xb: cm.col2im_add(zz, geom, 0, chunk, xb))(
                z, xbar).jaxpr)
        assert ("gather" in cols) is indexed, (chunk, sorted(cols))
        assert ("scatter-add" in back) is indexed, (chunk, sorted(back))
