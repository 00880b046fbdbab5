"""Row blocks of the fused managed read, chosen from the shapes, and the
column-slab epilogue they run with.

``managed_read_blocks`` picks the largest of 512, 256 and 128 rows that pads
little and fits VMEM; the epilogue draws, clips, selects and averages in
column slabs.  Both are elementwise or order-free over the same values, and
``bk`` stays 128, so a read with the chosen blocks and slabs equals a
128-row launch with a whole-width epilogue bit for bit (interpret mode
here; the same holds on the chip, where the benchmark's checks compare
against the parent's).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import managed_mvm as mm

LM_ROWS = 4 * 2048          # the LM cell: 4 x 2048 tokens a step


@pytest.mark.parametrize("read, outp, n_seg, bm", [
    ("q k v o forward", 4096, 1, 512),
    ("wi wg forward", 11008, 1, 256),
    ("wo forward", 4096, 3, 512),
    ("q k v o transpose", 4096, 1, 512),
    ("wi wg transpose", 4096, 3, 512),
    ("wo transpose", 11008, 1, 256),
])
def test_blocks_of_the_lm_reads(read, outp, n_seg, bm):
    """8192 rows: 4096-wide reads take 512; the 11008-wide ones take 256,
    as 512 rows would hold 21.5 MiB per full-width block."""
    assert mm.managed_read_blocks(LM_ROWS, outp, n_seg) == bm
    assert mm.fits_vmem(mm.managed_read_vmem(bm, 128, outp, outp, n_seg))


@pytest.mark.parametrize("rows, outp, n_seg, bm", [
    (8, 4096, 1, 128),          # serving decode: one 128-row block
    (128, 11008, 1, 128),
    (256, 4096, 1, 256),        # 512 would double the padded rows
    (256, 11008, 3, 256),
    (1000, 640, 1, 512),        # pads to 1024, which 512 divides
    (7900, 4096, 1, 256),       # 7936 rows: 512 would pad 3.2%
    (1100, 4096, 3, 128),       # 1152 rows: 256 would pad 11%
])
def test_blocks_follow_the_rows(rows, outp, n_seg, bm):
    assert mm.managed_read_blocks(rows, outp, n_seg) == bm


def test_too_wide_keeps_128_rows():
    """A read no block fits keeps 128 rows, and the launch raises by name
    (``test_tpu_compile.test_managed_read_too_wide_raises``)."""
    assert mm.managed_read_blocks(LM_ROWS, 102400, 1) == 128
    assert not mm.fits_vmem(mm.managed_read_vmem(128, 128, 102400, 102400,
                                                 1))


def _read(out_f, k, n_seg, d_avg, transpose, two_phase, rows, bm):
    out_phys = out_f * d_avg
    w = jax.random.normal(jax.random.key(1),
                          (k, out_phys) if transpose else (out_phys, k))
    # rows from well inside the bound to saturating both reads
    scale = jnp.logspace(-2.0, 1.5, rows)[:, None]
    x = jax.random.normal(jax.random.key(2), (rows, k)) * 0.3 * scale
    nm = jnp.ones((rows, 1), jnp.float32)
    seeds = jnp.array([1234567, 7654321], jnp.uint32)
    y, sat = mm.managed_mvm_pallas(
        w, x, nm, seeds, sigma=0.06, alpha=4.0, n_seg=n_seg,
        transpose=transpose, two_phase=two_phase, d_avg=d_avg, bm=bm,
        interpret=True)
    return np.asarray(y), np.asarray(sat)


@pytest.mark.parametrize("two_phase", [True, False])
@pytest.mark.parametrize("out_f, k, n_seg, d_avg, transpose", [
    (600, 300, 1, 1, False),    # 640 lanes: one slab and a 128-lane tail
    (600, 300, 3, 1, False),
    (20, 200, 1, 13, False),    # 13 replicas of 128 lanes averaged
    (40, 300, 3, 13, False),
    (600, 300, 3, 2, False),    # replicas wider than a slab
    (1100, 260, 3, 1, True),    # transpose read, 1152 lanes
])
def test_chosen_blocks_match_128_rows_bit_for_bit(monkeypatch, out_f, k,
                                                  n_seg, d_avg, transpose,
                                                  two_phase):
    rows = 1024
    outp = d_avg * mm.pad_to(out_f, 128)
    assert mm.managed_read_blocks(rows, outp, n_seg) == 512
    y, sat = _read(out_f, k, n_seg, d_avg, transpose, two_phase, rows, None)
    # 128 rows with one slab as wide as the output: the whole-width epilogue
    monkeypatch.setattr(mm, "SLAB", 1 << 30)
    try:
        y128, sat128 = _read(out_f, k, n_seg, d_avg, transpose, two_phase,
                             rows, 128)
    finally:
        jax.clear_caches()      # no trace with the patched slab is reused
    np.testing.assert_array_equal(y.view(np.uint32), y128.view(np.uint32))
    np.testing.assert_array_equal(sat, sat128)
    # the rows span unsaturated, recovered and residual reads
    assert 0 < sat.sum() < rows
