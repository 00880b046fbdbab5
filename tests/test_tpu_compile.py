"""The main path's Pallas kernels compile for a TPU v5e — without a chip.

The TPU compiler ships with the TPU runtime; it compiles for a chip that is
described (``get_topology_desc``) rather than attached.  These tests lower
each kernel of the main path at its real widths for one v5e chip: what
Mosaic refuses here (a block not aligned to the tiling, a cast it lacks,
more VMEM than a kernel may claim) would otherwise surface only on the chip.
Interpret mode, which every other kernel test runs in, sees none of it.

The topology is described inside a module-scoped fixture, never at import:
only one process may load the TPU runtime at a time, and every test worker
imports this file.  The persistent compilation cache is off around the
compiles (an entry written for a described chip cannot be read back).
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest

from repro.analysis.hlo import pallas_kernel_names
from repro.core import conv_mapping as cm
from repro.core import device as dev

BATCH = 8


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was_enabled = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_enabled)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    """Route ``kernels.ops`` to compiled (not interpreted) kernels."""
    from repro.kernels import ops
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)


def _put(tree, sharding):
    return jax.tree_util.tree_map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding),
        tree)


def _sds(shape, sharding, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


def _compile(fn, *args):
    return jax.jit(fn).lower(*args).compile()


def _n_kernels(compiled) -> int:
    return len(pallas_kernel_names(compiled.as_text()))


def _key(sharding):
    return _put(jax.eval_shape(lambda: jax.random.key(0)), sharding)


_MANAGED = dataclasses.replace(dev.rpu_nm_bm_um_bl1(), bm_mode="two_phase",
                               use_pallas=True, fuse_bwd_update=True)


@pytest.mark.parametrize("m, n, d_avg, transpose", [
    (128, 513, 1, False),       # LeNet W3 forward read
    (128, 513, 1, True),        # LeNet W3 transpose read
    (13 * 32, 401, 13, False),  # LeNet K2 (13 devices) on the gather path
    (11008, 4096, 1, False),    # deepseek_7b MLP up/gate projection
    (16640, 4096, 1, False),    # the widest output the VMEM gate admits
])
def test_managed_read_compiles(one_chip, compiled_kernels, m, n, d_avg,
                               transpose):
    from repro.core import tile
    cfg = dataclasses.replace(_MANAGED, devices_per_weight=d_avg)
    c = _compile(lambda w, x, k: tile.managed_mvm(w, x, k, cfg,
                                                  transpose=transpose),
                 _sds((m, n), one_chip),
                 _sds((BATCH, m if transpose else n), one_chip),
                 _key(one_chip))
    assert _n_kernels(c) == 1


@pytest.mark.parametrize("m, n, rows, transpose", [
    (11008, 4096, 256, False),   # deepseek_7b wi/wg forward, two row blocks
    (11008, 4096, 8192, False),  # ... from a 4 x 2048-token train step
    (4096, 4096, 256, False),    # q, k, v, o forward
    (4096, 4096, 8192, False),
    (11008, 4096, 8192, True),   # wi/wg transpose read, 3 array segments
    (4096, 11008, 8192, False),  # wo forward read, 3 array segments
    (16640, 4096, 256, False),   # the widest output the VMEM gate admits
])
def test_managed_read_compiles_from_many_rows(one_chip, compiled_kernels, m,
                                              n, rows, transpose):
    """With more than one 128-row block the output block's buffer pair is
    live beside the epilogue (``managed_read_vmem``): the LM train step's
    reads compile, each to one kernel."""
    from repro.core import tile
    c = _compile(lambda w, x, k: tile.managed_mvm(w, x, k, _MANAGED,
                                                  transpose=transpose,
                                                  backward=transpose),
                 _sds((m, n), one_chip),
                 _sds((rows, m if transpose else n), one_chip),
                 _key(one_chip))
    assert _n_kernels(c) == 1


@pytest.mark.parametrize("kind, admitted, refused", [
    ("managed_read", 16640, 16768),          # output columns
    ("bwd_update", 1792, 1920),              # square tile side
    ("managed_read_conv", 17152, 17280),     # K2-geometry output channels
])
def test_gate_maxima_are_the_compiled_shapes(kind, admitted, refused):
    """The VMEM gates admit no shape wider than the largest one compiled
    above: widening an estimate must come with a compile case at its new
    maximum."""
    from repro.kernels import bwd_update_mvm as bu
    from repro.kernels import conv_mvm as cv
    from repro.kernels import managed_mvm as mm
    k2 = cm.conv_geometry((BATCH, 12, 12, 16), 5)
    fits = {
        "managed_read": lambda o: mm.fits_vmem(mm.managed_read_vmem(
            128, 128, mm.pad_to(o, 128), mm.pad_to(o, 128))),
        "bwd_update": lambda n: mm.fits_vmem(bu._dense_vmem(
            128, 128, mm.pad_to(n, 128), mm.pad_to(n, 128))),
        "managed_read_conv": lambda c: mm.fits_vmem(
            cv._conv_read_vmem(k2, 1, c)),
    }[kind]
    assert fits(admitted) and not fits(refused)


def test_managed_read_too_wide_raises(compiled_kernels):
    """A read whose blocks cannot fit VMEM (a 102400-wide unembed) raises
    by name instead of compiling or taking another path."""
    from repro.core import tile
    with pytest.raises(ValueError, match="VMEM"):
        jax.eval_shape(lambda w, x, k: tile.managed_mvm(w, x, k, _MANAGED),
                       jax.ShapeDtypeStruct((102400, 4096), jnp.float32),
                       jax.ShapeDtypeStruct((BATCH, 4096), jnp.float32),
                       jax.eval_shape(lambda: jax.random.key(0)))


@pytest.mark.parametrize("shape, cout, d_avg", [
    ((BATCH, 28, 28, 1), 16, 1),     # LeNet K1
    ((BATCH, 12, 12, 16), 32, 13),   # LeNet K2, 13 devices per weight
    ((BATCH, 12, 12, 16), 17152, 1),  # K2 geometry, widest gate admits
])
def test_managed_read_conv_compiles(one_chip, shape, cout, d_avg):
    from repro.kernels.conv_mvm import conv_managed_mvm_pallas
    geom = cm.conv_geometry(shape, 5)
    c = _compile(lambda w, x, s, sd: conv_managed_mvm_pallas(
                     w, x, s, sd, geom=geom, sigma=0.06, alpha=12.0,
                     two_phase=True, d_avg=d_avg),
                 _sds((cout * d_avg, geom.cols), one_chip),
                 _sds(shape, one_chip),
                 _sds((geom.positions, 1), one_chip),
                 _sds((2,), one_chip, jnp.uint32))
    assert _n_kernels(c) == 1


@pytest.mark.parametrize("m, n", [
    (128, 513), (10, 129), (1024, 1024),
    (1792, 1792),                    # the largest square tile admitted
    (4096, 513),                     # max_array_rows at W3's width
])
def test_bwd_update_dense_compiles(one_chip, m, n):
    from repro.kernels.bwd_update_mvm import bwd_update_mvm_pallas
    c = _compile(lambda *a: bwd_update_mvm_pallas(
                     *a, sigma=0.06, alpha=12.0, two_phase=True, bl=10),
                 _sds((m, n), one_chip), _sds((BATCH, m), one_chip),
                 _sds((BATCH, n), one_chip), _sds((BATCH, 1), one_chip),
                 _sds((2,), one_chip, jnp.uint32),
                 _sds((3,), one_chip, jnp.uint32), _sds((2,), one_chip))
    assert _n_kernels(c) == 1


@pytest.mark.parametrize("shape, cout", [
    ((BATCH, 28, 28, 1), 16),        # LeNet K1
    ((BATCH, 12, 12, 16), 32),       # LeNet K2
    ((BATCH, 28, 28, 1), 4096),      # K1 geometry at max_array_rows
    ((BATCH, 12, 12, 16), 4096),     # K2 geometry at max_array_rows
])
def test_bwd_update_conv_compiles(one_chip, shape, cout):
    from repro.kernels.bwd_update_mvm import conv_bwd_update_pallas
    geom = cm.conv_geometry(shape, 5)
    c = _compile(lambda *a: conv_bwd_update_pallas(
                     *a, geom=geom, sigma=0.06, alpha=12.0, two_phase=True,
                     bl=1),
                 _sds((cout, geom.cols), one_chip), _sds(shape, one_chip),
                 _sds((geom.positions, cout), one_chip),
                 _sds((geom.positions, 1), one_chip),
                 _sds((2,), one_chip, jnp.uint32),
                 _sds((2,), one_chip, jnp.uint32), _sds((2,), one_chip))
    assert _n_kernels(c) == 1


@pytest.mark.parametrize("m, n, transpose", [(128, 513, False),
                                             (416, 401, True)])
def test_noisy_read_compiles(one_chip, compiled_kernels, m, n, transpose):
    """The per-retry read of iterative bound management, saturation flags
    included (their output block must be lane-aligned)."""
    from repro.kernels import ops
    cfg = dev.rpu_nm_bm()
    c = _compile(lambda w, x, k: ops.noisy_mvm(w, x, k, cfg,
                                               transpose=transpose),
                 _sds((m, n), one_chip),
                 _sds((BATCH, m if transpose else n), one_chip),
                 _key(one_chip))
    assert _n_kernels(c) == 1


def test_pulse_counts_compiles(one_chip, compiled_kernels):
    from repro.kernels import ops
    t = BATCH * 10                         # batch x BL stream slots
    c = _compile(ops.pulse_counts, _sds((t, 416), one_chip),
                 _sds((t, 401), one_chip))
    assert _n_kernels(c) == 1


@pytest.mark.parametrize("m, n", [(11008, 4096), (4096, 11008)])
def test_pulse_counts_compiles_at_lm_widths(one_chip, compiled_kernels, m, n):
    """The LM cell's counts launch (4 x 2048 stream slots, deepseek_7b's
    MLP tiles) under the cell's highest ambient precision: 512-blocks of
    bf16 streams, one kernel."""
    from repro.kernels import ops
    t = 8192
    with jax.default_matmul_precision("highest"):
        c = _compile(ops.pulse_counts, _sds((t, m), one_chip),
                     _sds((t, n), one_chip))
    assert _n_kernels(c) == 1


def test_fused_lenet_step_compiles_to_eight_kernels(one_chip,
                                                    compiled_kernels):
    """The audited fused LeNet train step compiles for the chip with
    exactly its launch budget (analysis/budgets/lenet.json): two managed
    reads, two implicit-im2col conv reads and one fused backward+update per
    layer — no layer quietly on the reference path."""
    from repro.analog.presets import parse_policy
    from repro.analysis.targets import LENET_POLICY
    from repro.models import lenet
    from repro.optim import analog_sgd
    from repro.train import engine

    cfg = lenet.LeNetConfig.from_policy(parse_policy(LENET_POLICY))
    opt = analog_sgd()
    key = _key(one_chip)
    params = _put(jax.eval_shape(lambda k: lenet.init(k, cfg), key),
                  one_chip)
    opt_state = _put(jax.eval_shape(opt.init, params), one_chip)
    c = _compile(engine.make_cnn_step_fn(cfg, opt), params, opt_state,
                 _sds((BATCH, 28, 28, 1), one_chip),
                 _sds((BATCH,), one_chip, jnp.int32), key)
    names = pallas_kernel_names(c.as_text())
    assert len(names) == 8
    assert sorted(names) == sorted(["managed_read"] * 2
                                   + ["managed_read_conv"] * 2
                                   + ["bwd_update"] * 2
                                   + ["bwd_update_conv"] * 2)
