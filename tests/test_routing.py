"""Routing table of the three RPU cycles, and the core/kernels seam.

Each row is one configuration: the management state its derived rules
report (``RPUConfig.bm_active``, ``bm_iterative``, ``grid_routed``,
``nm_active``) and the launch kinds the vjp of a dense layer and of a LeNet
conv layer (K1: 5x5, 1 -> 16 channels) traces, counted by kernel name as
``analysis/jaxpr_audit.py`` counts them.  The table covers every value of
``use_pallas``, BM (off / two-phase / iterative), NM (off / on /
``nm_forward``), ``tile_grid`` (None / (1, 1) / (2, 1)),
``fuse_bwd_update`` and ``fast_rng``.  Nothing runs: each case is traced.

A conv entry that is a string names the ``ValueError`` the conv layer
raises: its streamed update needs counter-offset streams (``fast_rng``).
"""

import ast
import pathlib

import jax
import jax.numpy as jnp
import pytest

from repro.analysis import jaxpr_audit
from repro.core import analog_linear as al
from repro.core import conv_mapping as cm
from repro.core.device import RPUConfig
from repro.core.tile import TileState

PALLAS = dict(use_pallas=True)
NM = dict(noise_management=True)
BM2 = dict(bound_management=True, bm_mode="two_phase")
BMI = dict(bound_management=True, bm_mode="iterative")
FUSE = dict(use_pallas=True, fuse_bwd_update=True)

READS = {"managed_read": 2, "pulse_counts": 1}
CONV_READS = {"managed_read_conv": 1, "managed_read": 1, "pulse_counts": 1}
RAW = {"noisy_read": 4, "pulse_counts": 1}
FUSED = {"managed_read": 1, "bwd_update": 1}
CONV_FUSED = {"managed_read_conv": 1, "bwd_update_conv": 1}
GRID = {"noisy_read": 8}
NONE = {}

# name, overrides, BM state, NM state, grid-routed, dense launches,
# conv launches
ROUTES = [
    ("jnp", {}, "off", "off", False, NONE, NONE),
    ("jnp_nm_bm_iter", {**NM, **BMI}, "iterative", "backward", False,
     NONE, NONE),
    ("jnp_threefry_nm_bm2", {**NM, **BM2, "fast_rng": False}, "two_phase",
     "backward", False, NONE, "fast_rng"),
    ("pallas", PALLAS, "off", "off", False, READS, CONV_READS),
    ("pallas_nm", {**PALLAS, **NM}, "off", "backward", False,
     READS, CONV_READS),
    ("pallas_nm_forward", {**PALLAS, **NM, "nm_forward": True}, "off",
     "both", False, READS, CONV_READS),
    ("pallas_bm2", {**PALLAS, **BM2}, "two_phase", "off", False,
     READS, CONV_READS),
    ("pallas_bm_iter", {**PALLAS, **BMI}, "iterative", "off", False,
     RAW, RAW),
    ("pallas_nm_bm2", {**PALLAS, **NM, **BM2}, "two_phase", "backward",
     False, READS, CONV_READS),
    ("pallas_nm_bm_iter", {**PALLAS, **NM, **BMI}, "iterative", "backward",
     False, RAW, RAW),
    ("pallas_bm_unbounded", {**PALLAS, **BMI, "out_bound": float("inf")},
     "off", "off", False, READS, CONV_READS),
    ("pallas_threefry_nm_forward",
     {**PALLAS, **NM, "nm_forward": True, "fast_rng": False}, "off", "both",
     False, READS, "fast_rng"),
    ("fused", FUSE, "off", "off", False, FUSED, CONV_FUSED),
    ("fused_nm_bm2", {**FUSE, **NM, **BM2}, "two_phase", "backward", False,
     FUSED, CONV_FUSED),
    ("fused_nm_bm_iter", {**FUSE, **NM, **BMI}, "iterative", "backward",
     False, RAW, RAW),
    ("fused_threefry_bm2", {**FUSE, **BM2, "fast_rng": False}, "two_phase",
     "off", False, READS, "fast_rng"),
    ("fused_without_pallas", {"fuse_bwd_update": True, **BM2}, "two_phase",
     "off", False, NONE, NONE),
    ("grid11_nm_bm2", {**PALLAS, **NM, **BM2, "tile_grid": (1, 1)},
     "two_phase", "backward", False, READS, CONV_READS),
    ("grid21_nm_bm2", {**PALLAS, **NM, **BM2, "tile_grid": (2, 1)},
     "two_phase", "backward", True, GRID, GRID),
    ("grid11_fused_bm2", {**FUSE, **BM2, "tile_grid": (1, 1)}, "two_phase",
     "off", False, FUSED, CONV_FUSED),
    ("grid21_fused_bm2", {**FUSE, **BM2, "tile_grid": (2, 1)}, "two_phase",
     "off", True, GRID, GRID),
    ("grid21_jnp_nm_bm_iter", {**NM, **BMI, "tile_grid": (2, 1)},
     "iterative", "backward", True, NONE, NONE),
]


def _kinds(fn, *args):
    return jaxpr_audit.audit_fn(fn, *args).launches_by_kind


def _dense_kinds(cfg):
    st = al.init(jax.random.key(0), 12, 7, cfg)

    def loss(w, x):
        s = TileState(w=w, maps=st.maps, seed=st.seed)
        return jnp.sum(al.apply(s, x, jax.random.key(1), cfg, 0.01))

    return _kinds(jax.grad(loss, argnums=(0, 1)), st.w, jnp.ones((4, 12)))


def _conv_kinds(cfg):
    st = cm.init(jax.random.key(0), 1, 16, 5, cfg)

    def loss(w, x):
        s = TileState(w=w, maps=st.maps, seed=st.seed)
        return jnp.sum(cm.apply(s, x, jax.random.key(1), cfg, 0.01,
                                kernel=5))

    return _kinds(jax.grad(loss, argnums=(0, 1)), st.w,
                  jnp.ones((2, 9, 9, 1)))


def test_routing_table_covers_every_switch():
    cfgs = [RPUConfig(**kw) for _, kw, *_ in ROUTES]
    seen = {(c.use_pallas, c.fuse_bwd_update, c.fast_rng, c.tile_grid,
             c.bound_management and c.bm_mode, c.noise_management,
             c.nm_forward, c.out_bound) for c in cfgs}
    assert len(seen) == len(ROUTES)
    for field, values in (("use_pallas", {False, True}),
                          ("fuse_bwd_update", {False, True}),
                          ("fast_rng", {False, True}),
                          ("tile_grid", {None, (1, 1), (2, 1)}),
                          ("nm_forward", {False, True})):
        assert {getattr(c, field) for c in cfgs} == values, field
    assert {r[2] for r in ROUTES} == {"off", "two_phase", "iterative"}
    assert {r[3] for r in ROUTES} == {"off", "backward", "both"}


def test_dense_layer_routes():
    for name, kw, *_, dense, _conv in ROUTES:
        assert _dense_kinds(RPUConfig(**kw)) == dense, name


def test_conv_layer_routes():
    for name, kw, *_, conv in ROUTES:
        cfg = RPUConfig(**kw)
        if isinstance(conv, str):
            with pytest.raises(ValueError, match=conv):
                _conv_kinds(cfg)
        else:
            assert _conv_kinds(cfg) == conv, name


def test_derived_rules():
    for name, kw, bm, nm, grid, *_ in ROUTES:
        cfg = RPUConfig(**kw)
        got = (cfg.bm_active, cfg.bm_iterative, cfg.grid_routed,
               cfg.nm_active(backward=False), cfg.nm_active(backward=True))
        want = (bm != "off", bm == "iterative", grid, nm == "both",
                nm != "off")
        assert got == want, name


def test_read_sigma_per_direction():
    for fwd, bwd in ((True, True), (True, False), (False, True),
                     (False, False)):
        cfg = RPUConfig(read_noise=0.06, noise_forward=fwd,
                        noise_backward=bwd)
        assert cfg.read_sigma(transpose=False) == (0.06 if fwd else 0.0)
        assert cfg.read_sigma(transpose=True) == (0.06 if bwd else 0.0)
    assert RPUConfig().without_read_noise().read_sigma(False) == 0.0


def _imported_modules(tree):
    for node in ast.walk(tree):
        if isinstance(node, ast.Import):
            yield from (a.name for a in node.names)
        elif isinstance(node, ast.ImportFrom) and node.module:
            yield from (f"{node.module}.{a.name}" for a in node.names)


def test_kernels_import_only_core_device():
    """Core decides, kernels launch: no module under ``repro/kernels``
    imports anything of ``repro.core`` but ``repro.core.device``."""
    import repro.kernels
    root = pathlib.Path(repro.kernels.__file__).parent
    bad = []
    for path in sorted(root.rglob("*.py")):
        for mod in _imported_modules(ast.parse(path.read_text())):
            if (mod.startswith("repro.core")
                    and mod != "repro.core.device"
                    and not mod.startswith("repro.core.device.")):
                bad.append(f"{path.name}: {mod}")
    assert not bad, bad
