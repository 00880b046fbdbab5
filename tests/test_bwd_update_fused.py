"""Fused backward+update megakernel: bit-parity vs the separate-launch oracle.

``cfg.fuse_bwd_update`` routes each analog layer's backward transpose read
AND its stochastic-pulse update through ONE Pallas launch
(``kernels/bwd_update_mvm.py``).  The fusion must be *bit-identical* to the
separate cycles (``tile_backward`` + ``pulse_update`` — the oracle kept for
ineligible shapes): the transpose read reuses the managed-read body at the
reference counter layout, the pulse streams are re-drawn in VMEM at the
reference counter offsets, and the coincidence counts are integer sums, so
nothing may drift one ulp under any accumulation blocking.  These tests pin
that contract with ``assert_array_equal`` across NM x BM x #_d x
update-chunk, eager and jitted, dense and conv — plus the LeNet headline:
a full train step fused vs separate lands bit-identical parameters.

Tier-1 runs a representative sample; the full cross-product carries the
``slow`` marker (deselected by default via pyproject addopts) and runs in
the CI kernel job under forced-CPU interpret mode.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import analog_linear as al
from repro.core import conv_mapping as cm
from repro.core import tile as tl
from repro.core.device import RPUConfig
from repro.core.tile import TileState
from repro.kernels import ops as kops

BASE = RPUConfig(use_pallas=True, fast_rng=True)


def _fused(cfg):
    return dataclasses.replace(cfg, fuse_bwd_update=True)


def _dense_grads(cfg, jit=False, rows=7, cols=12, batch=4):
    st = al.init(jax.random.key(5), cols, rows, cfg)
    x = jax.random.normal(jax.random.key(0), (batch, cols))

    def f(w, xx):
        s = TileState(w=w, maps=st.maps, seed=st.seed)
        y = al.apply(s, xx, jax.random.key(11), cfg, 0.01)
        return jnp.sum(y ** 2)

    g = jax.grad(f, argnums=(0, 1))
    return (jax.jit(g) if jit else g)(st.w, x)


def _conv_grads(cfg, jit=False, **conv_kw):
    conv_kw = dict(kernel=3, **conv_kw)
    st = cm.init(jax.random.key(5), 3, 5, 3, cfg)
    x = jax.random.normal(jax.random.key(0), (2, 10, 10, 3))

    def f(w, xx):
        s = TileState(w=w, maps=st.maps, seed=st.seed)
        y = cm.apply(s, xx, jax.random.key(11), cfg, 0.01, **conv_kw)
        return jnp.sum(y ** 2)

    g = jax.grad(f, argnums=(0, 1))
    return (jax.jit(g) if jit else g)(st.w, x)


def _assert_same(a, b):
    (gw_a, gx_a), (gw_b, gx_b) = a, b
    np.testing.assert_array_equal(np.asarray(gw_a), np.asarray(gw_b))
    np.testing.assert_array_equal(np.asarray(gx_a), np.asarray(gx_b))


def _cfg(nm=False, bm=False, um=False, d=1, chunk=None):
    c = dataclasses.replace(
        BASE, noise_management=nm, nm_forward=nm, bound_management=bm,
        bm_mode="two_phase" if bm else "iterative", update_management=um,
        devices_per_weight=d)
    if chunk:
        c = dataclasses.replace(c, update_chunk=chunk,
                                conv_stream_chunk=chunk)
    return c


# ---------------------------------------------------------------------------
# Representative sample (tier-1)
# ---------------------------------------------------------------------------

SAMPLE = {
    "plain": _cfg(),
    "nm_bm2p": _cfg(nm=True, bm=True),
    "nm_bm2p_um_d3": _cfg(nm=True, bm=True, um=True, d=3),
    "nm_bm2p_chunk3": _cfg(nm=True, bm=True, chunk=3),
}


@pytest.mark.parametrize("name", sorted(SAMPLE))
@pytest.mark.parametrize("jit", [False, True], ids=["eager", "jit"])
def test_dense_fused_bit_matches_separate(name, jit):
    cfg = SAMPLE[name]
    _assert_same(_dense_grads(cfg, jit=jit),
                 _dense_grads(_fused(cfg), jit=jit))


@pytest.mark.parametrize("name", sorted(SAMPLE))
def test_conv_fused_bit_matches_separate(name):
    cfg = SAMPLE[name]
    _assert_same(_conv_grads(cfg), _conv_grads(_fused(cfg)))


def test_conv_fused_stride2_same_padding():
    cfg = _cfg(nm=True, bm=True)
    kw = dict(stride=2, padding="SAME")
    _assert_same(_conv_grads(cfg, **kw), _conv_grads(_fused(cfg), **kw))


# ---------------------------------------------------------------------------
# Full cross-product (slow — CI kernel job)
# ---------------------------------------------------------------------------

GRID = [(nm, bm, d, chunk)
        for nm in (False, True) for bm in (False, True)
        for d in (1, 3) for chunk in (None, 3)]
_IDS = [f"nm{int(n)}-bm{int(b)}-d{d}-ch{c or 0}" for n, b, d, c in GRID]


@pytest.mark.slow
@pytest.mark.parametrize("nm,bm,d,chunk", GRID, ids=_IDS)
def test_dense_fused_cross_product(nm, bm, d, chunk):
    cfg = _cfg(nm=nm, bm=bm, d=d, chunk=chunk)
    _assert_same(_dense_grads(cfg), _dense_grads(_fused(cfg)))


@pytest.mark.slow
@pytest.mark.parametrize("nm,bm,d,chunk", GRID, ids=_IDS)
def test_conv_fused_cross_product(nm, bm, d, chunk):
    cfg = _cfg(nm=nm, bm=bm, d=d, chunk=chunk)
    _assert_same(_conv_grads(cfg), _conv_grads(_fused(cfg)))


# ---------------------------------------------------------------------------
# LeNet headline: one fused train step lands bit-identical parameters
# ---------------------------------------------------------------------------

def _lenet_step_params(policy):
    from repro.analog.presets import parse_policy
    from repro.models import lenet
    from repro.train import cnn

    cfg = lenet.LeNetConfig.from_policy(parse_policy(policy))
    params = lenet.init(jax.random.key(3), cfg)
    step, opt = cnn.make_train_step(cfg)
    opt_state = opt.init(params)
    x = jax.random.normal(jax.random.key(1), (4, 28, 28, 1))
    y = jnp.arange(4) % 10
    params, _ = step(params, opt_state, x, y, jax.random.key(2))
    return params


def test_lenet_train_step_fused_bit_identical():
    base = "managed:use_pallas=true:bm_mode=two_phase"
    p_sep = _lenet_step_params(base)
    p_fus = _lenet_step_params(base + ":fuse_bwd_update=true")

    def _raw(v):
        if jnp.issubdtype(getattr(v, "dtype", None), jax.dtypes.prng_key):
            return np.asarray(jax.random.key_data(v))
        return np.asarray(v)

    flat_s = jax.tree.leaves(p_sep)
    flat_f = jax.tree.leaves(p_fus)
    assert len(flat_s) == len(flat_f) and flat_s
    for a, b in zip(flat_s, flat_f):
        np.testing.assert_array_equal(_raw(a), _raw(b))


# ---------------------------------------------------------------------------
# Routing: iterative BM cannot fuse
# ---------------------------------------------------------------------------

def test_iterative_bm_falls_back_to_separate_launches():
    """``fuse_bwd_update=True`` with the multi-launch iterative BM mode is
    simply ineligible: the layer routes through the separate-launch cycles
    and matches the unfused config bitwise."""
    cfg = dataclasses.replace(BASE, noise_management=True,
                              bound_management=True, bm_mode="iterative")
    from repro.kernels.bwd_update_mvm import bwd_update_eligible
    assert not bwd_update_eligible(_fused(cfg), (7, 12))
    _assert_same(_dense_grads(cfg), _dense_grads(_fused(cfg)))


def test_fused_wrapper_rejects_iterative_bm():
    cfg = _fused(dataclasses.replace(BASE, bound_management=True,
                                     bm_mode="iterative"))
    w = jnp.zeros((8, 12))
    with pytest.raises(ValueError, match="iterative"):
        tl.backward_update_counts(w, jnp.zeros((4, 12)), jnp.zeros((4, 8)),
                                  jax.random.key(0), jax.random.key(1),
                                  jax.random.key(2), cfg, 0.01)


# ---------------------------------------------------------------------------
# Launch accounting + label hygiene
# ---------------------------------------------------------------------------

def test_fused_backward_is_one_launch():
    """The whole vjp of an eligible layer traces to exactly ONE
    ``bwd_update`` launch (plus the forward managed read) — no separate
    transpose read, no pulse-counts launch."""
    from repro.analysis import jaxpr_audit

    cfg = _fused(_cfg(nm=True, bm=True))
    st = al.init(jax.random.key(5), 12, 7, cfg)
    x = jax.random.normal(jax.random.key(0), (4, 12))

    def f(w, xx):
        s = TileState(w=w, maps=st.maps, seed=st.seed)
        return jnp.sum(al.apply(s, xx, jax.random.key(11), cfg, 0.01) ** 2)

    with kops.launch_label("L"):
        rep = jaxpr_audit.audit_fn(jax.grad(f, argnums=(0, 1)), st.w, x)
    launches = rep.to_json()["launches"]
    kinds = {}
    for name, n in launches.items():
        kind, _ = jaxpr_audit.split_launch_name(name)
        kinds[kind] = kinds.get(kind, 0) + n
    assert kinds == {"managed_read": 1, "bwd_update": 1}, launches


def test_launch_label_restored_after_trace_error():
    """Regression: ``launch_label`` resets its contextvar even when the
    traced body raises (try/finally) — a crashed audit must not leak its
    layer label into subsequent launches."""
    with pytest.raises(RuntimeError, match="boom"):
        with kops.launch_label("leaky"):
            raise RuntimeError("boom")
    assert kops.launch_name("managed_read") == "managed_read"
    with kops.launch_label("ok"):
        assert kops.launch_name("managed_read") == "managed_read__ok"
    assert kops.launch_name("managed_read") == "managed_read"
