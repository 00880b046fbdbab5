"""Per-kernel validation: Pallas (interpret mode on CPU) vs pure-jnp oracle.

Sweeps shapes / segment counts / transpose / noise settings per the kernel
deliverable contract; the on-chip counter-hash RNG is bit-compatible with the
reference, so tolerances are matmul-reassociation-level only.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.device import RPUConfig, sample_device_maps
from repro.core import tile as tl
from repro.core import update as update_lib
from repro.kernels import ops as kops
from repro.kernels.noisy_mvm import noisy_mvm_pallas
from repro.kernels.pulse_update import pulse_counts_pallas
from repro.utils import fastrng


MVM_CASES = [
    # (rows, cols, batch, sigma, alpha, n_seg, transpose)
    (16, 26, 8, 0.06, 12.0, 1, False),       # the paper's K1 tile
    (32, 401, 64, 0.06, 12.0, 1, False),     # K2
    (10, 129, 8, 0.06, 12.0, 1, True),       # W4 transpose read
    (200, 300, 100, 0.06, 12.0, 3, False),   # contraction split x3
    (300, 200, 50, 0.06, 12.0, 2, True),     # transpose + split
    (128, 128, 128, 0.0, float("inf"), 1, False),   # ideal device
    (257, 129, 33, 0.06, 2.0, 1, False),     # heavy saturation, odd dims
]


@pytest.mark.parametrize("r,c,b,sigma,alpha,n_seg,tr", MVM_CASES)
def test_noisy_mvm_matches_reference(r, c, b, sigma, alpha, n_seg, tr):
    key = jax.random.key(hash((r, c, b, n_seg, tr)) % (2 ** 31))
    w = jax.random.normal(jax.random.key(1), (r, c)) * 0.2
    k_in = r if tr else c
    x = jax.random.normal(jax.random.key(2), (b, k_in)) * 0.5

    cfg = RPUConfig(
        read_noise=sigma, out_bound=alpha,
        max_array_cols=10 ** 9 if tr else -(-c // n_seg),
        max_array_rows=-(-r // n_seg) if tr else 10 ** 9)
    y_ref, sat_ref = tl.analog_mvm_reference(w, x, key, cfg, transpose=tr)
    y_k, sat_blk = noisy_mvm_pallas(
        w, x, fastrng.key_to_seed(key), sigma=sigma, alpha=alpha,
        n_seg=n_seg, transpose=tr, interpret=True)
    np.testing.assert_allclose(np.asarray(y_ref), np.asarray(y_k),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(
        np.asarray(sat_ref), np.asarray(jnp.any(sat_blk > 0, axis=-1)))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_noisy_mvm_dtypes(dtype):
    w = (jax.random.normal(jax.random.key(1), (64, 96)) * 0.2)
    x = (jax.random.normal(jax.random.key(2), (32, 96)) * 0.5).astype(dtype)
    key = jax.random.key(9)
    cfg = RPUConfig(dtype=dtype)
    y_ref, _ = tl.analog_mvm_reference(w.astype(dtype), x, key, cfg)
    y_k, _ = noisy_mvm_pallas(
        w.astype(dtype), x, fastrng.key_to_seed(key),
        sigma=cfg.read_noise, alpha=cfg.out_bound, interpret=True)
    tol = 1e-5 if dtype == jnp.float32 else 1e-1
    np.testing.assert_allclose(np.asarray(y_ref, np.float32),
                               np.asarray(y_k, np.float32),
                               rtol=tol, atol=tol)


PULSE_CASES = [
    # (m, n, batch, bl, ctoc)
    (16, 26, 8, 10, 0.3),
    (32, 401, 16, 1, 0.3),
    (128, 513, 4, 10, 0.0),
    (130, 260, 64, 2, 0.3),    # non-128-aligned
    (10, 129, 1, 40, 0.3),     # single sample, long stream
]


@pytest.mark.parametrize("m,n,b,bl,ctoc", PULSE_CASES)
def test_pulse_update_matches_reference(m, n, b, bl, ctoc):
    cfg_ref = RPUConfig(bl=bl, dw_min_ctoc=ctoc, use_pallas=False)
    cfg_ker = RPUConfig(bl=bl, dw_min_ctoc=ctoc, use_pallas=True)
    maps = sample_device_maps(jax.random.key(3), m, n, cfg_ref)
    w = jax.random.normal(jax.random.key(1), (m, n)) * 0.1
    x = jax.random.normal(jax.random.key(2), (b, n)) * 0.3
    d = jax.random.normal(jax.random.key(4), (b, m)) * 0.1
    key = jax.random.key(77)
    w_ref = update_lib.pulse_update(w, maps, x, d, key, cfg_ref, 0.01)
    w_ker = update_lib.pulse_update(w, maps, x, d, key, cfg_ker, 0.01)
    np.testing.assert_allclose(np.asarray(w_ref), np.asarray(w_ker),
                               rtol=1e-5, atol=1e-6)


def test_pulse_update_respects_bounds():
    cfg = RPUConfig(bl=10, use_pallas=True)
    maps = sample_device_maps(jax.random.key(3), 32, 48, cfg)
    w = jnp.clip(jax.random.normal(jax.random.key(1), (32, 48)),
                 -maps.bound, maps.bound)
    x = jnp.ones((64, 48))
    d = jnp.ones((64, 32))
    new_w = update_lib.pulse_update(w, maps, x, d, jax.random.key(5), cfg, 0.5)
    assert bool(jnp.all(jnp.abs(new_w) <= maps.bound + 1e-6))


def test_ops_wrapper_batch_shapes():
    cfg = RPUConfig(use_pallas=True)
    w = jax.random.normal(jax.random.key(1), (40, 30)) * 0.2
    x = jax.random.normal(jax.random.key(2), (4, 7, 30))
    y, sat = kops.noisy_mvm(w, x, jax.random.key(5), cfg)
    assert y.shape == (4, 7, 40)
    assert sat.shape == (4, 7)


# (T, M, N) of the counts launches: LeNet's K1, K2 (13 devices a weight),
# W3 and W4 at batch 8, and an LM tile cut to 4480 rows, where a 512-row
# block pads 128 rows and a 512-column block pads 24 columns.
COUNT_SHAPES = [(4608, 16, 26), (512, 416, 401), (8, 128, 513),
                (8, 10, 129), (1024, 4480, 1000)]


@pytest.mark.parametrize("precision", ["default", "highest"])
@pytest.mark.parametrize("t,m,n", COUNT_SHAPES)
def test_pulse_counts_exact(t, m, n, precision):
    """The counts kernel equals the f32 reference contraction bit for bit
    on sampled streams, under the default and the highest ambient matmul
    precision alike."""
    k_x, k_d, k_a, k_b = jax.random.split(jax.random.key(t + m + n), 4)
    x = jax.random.normal(k_x, (t, n))
    d = jax.random.normal(k_d, (t, m))
    cols = update_lib.sample_signed_streams(k_a, x, jnp.float32(0.8), 1)
    rows = update_lib.sample_signed_streams(k_b, d, jnp.float32(0.8), 1)
    with jax.default_matmul_precision(precision):
        up, dn = kops.pulse_counts(rows, cols)
    up_ref, dn_ref = update_lib.coincidence_counts(rows, cols)
    assert up.dtype == dn.dtype == jnp.float32
    assert float(jnp.max(up_ref)) > 0 and float(jnp.max(dn_ref)) > 0
    np.testing.assert_array_equal(np.asarray(up), np.asarray(up_ref))
    np.testing.assert_array_equal(np.asarray(dn), np.asarray(dn_ref))


def test_pulse_counts_one_bf16_pass_under_highest():
    """The kernel's products take bf16 operands at an explicit default
    precision, so the ambient highest precision does not reach them."""
    rows = jnp.zeros((64, 16), jnp.float32)
    cols = jnp.zeros((64, 128), jnp.float32)
    with jax.default_matmul_precision("highest"):     # traced, not lowered
        jaxpr = jax.make_jaxpr(lambda r, c: pulse_counts_pallas(
            r, c, interpret=False))(rows, cols)

    def eqns(jx):
        for e in jx.eqns:
            yield e
            for v in e.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    inner = getattr(sub, "jaxpr", sub)
                    if hasattr(inner, "eqns"):
                        yield from eqns(inner)

    kernels = [e for e in eqns(jaxpr.jaxpr) if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    dots = [e for e in eqns(kernels[0].params["jaxpr"])
            if e.primitive.name == "dot_general"]
    assert len(dots) == 2
    for e in dots:
        assert e.params["precision"] in (
            jax.lax.Precision.DEFAULT,
            (jax.lax.Precision.DEFAULT, jax.lax.Precision.DEFAULT))
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert e.params["preferred_element_type"] == jnp.float32


# The counts launches of the two chip cells, by tile: LeNet at batch 8 and
# deepseek_7b's seven projections at 4 x 2048 tokens a pulse update.
CELL_LAUNCHES = {
    "K1": (4608, 16, 26), "K2": (512, 416, 401), "W3": (8, 128, 513),
    "W4": (8, 10, 129),
    "q": (8192, 4096, 4096), "k": (8192, 4096, 4096),
    "v": (8192, 4096, 4096), "o": (8192, 4096, 4096),
    "wi": (8192, 11008, 4096), "wg": (8192, 11008, 4096),
    "wo": (8192, 4096, 11008),
}


def _counts_vmem_bytes(bt, bm, bn):
    """VMEM of a counts launch: double-buffered bf16 stream blocks and f32
    count blocks, the blocks' absolute values and a step's two products."""
    streams = 2 * (bm * bt + bt * bn)
    counts = 2 * 4 * bm * bn
    return 2 * (streams + counts) + streams + counts


@pytest.mark.parametrize("tile", sorted(CELL_LAUNCHES))
def test_pulse_counts_blocks(tile):
    """Blocks follow the shapes: no LeNet axis pads beyond the 128-rounded
    extent that fixed 128-blocks padded it to, an LM axis by at most 3% more
    (11008 rows in 512-blocks: 11264), the launch fits the default scoped
    VMEM, and LM tiles get blocks of at least 256 on every axis."""
    from repro.kernels import pulse_update as pu
    t, m, n = CELL_LAUNCHES[tile]
    bt, bm, bn = pu.counts_blocks(t, m, n)
    room = 1.03 if t == 8192 else 1.0
    for extent, block in ((t, bt), (m, bm), (n, bn)):
        assert pu._round_up(extent, block) <= room * pu._round_up(extent, 128)
    assert bt % 16 == 0                             # bf16 sublane tile
    for extent, block in ((m, bm), (n, bn)):        # lane axes
        assert block == extent or block % 128 == 0
    assert _counts_vmem_bytes(bt, bm, bn) <= 16 * 2 ** 20   # v5e's default
    if t == 8192:
        assert min(bt, bm, bn) >= 256
