"""Device-level models for Resistive Processing Unit (RPU) cross-point arrays.

Implements Table 1 of Gokmen, Onen & Haensch (2017): per-device minimal
conductance-change maps (``dw_min`` with device-to-device variation), up/down
update imbalance (``dw_min_up / dw_min_dn`` ratio with 2% device variation),
per-device weight bounds (conductance saturation), and the *multi-device
mapping* technique (section "Sensitivity to Device Variations") where one
logical weight is realised by ``devices_per_weight`` physical cross-points and
the replicas are summed/averaged in the digital domain.

Two storage strategies are supported:

* **materialized** — the per-device maps are sampled once at tile creation and
  stored as arrays alongside the weights (faithful to a fabricated chip whose
  device population is fixed).  This is what the paper simulates.
* **seeded** — the maps are *regenerated on the fly* from a counter-based RNG
  key folded with the tile id.  Statistically identical device population
  (fixed across steps because the key is fixed), but removes the 2-3x memory
  overhead of storing the maps.  This is our beyond-paper memory optimization
  used for billion-parameter analog LM experiments (DESIGN.md section 9).
"""

from __future__ import annotations

import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp


@dataclasses.dataclass(frozen=True)
class RPUConfig:
    """All analog-hardware parameters of the RPU-baseline model (Table 1)
    plus the digitally-programmable management techniques.

    Defaults reproduce the paper's RPU-baseline exactly.
    """

    # --- update (stochastic pulse) parameters -------------------------------
    bl: int = 10                       # stochastic bit-stream length BL
    dw_min: float = 0.001              # mean single-coincidence weight change
    dw_min_dtod: float = 0.3           # device-to-device variation of dw_min (30%)
    dw_min_ctoc: float = 0.3           # cycle-to-cycle variation of dw_min (30%)
    imbalance_dtod: float = 0.02       # device-to-device var. of dw+ / dw- ratio (2%)
    # --- weight bounds (conductance saturation) -----------------------------
    w_bound: float = 0.6               # mean |w_ij| bound
    w_bound_dtod: float = 0.3          # device-to-device variation of the bound (30%)
    # --- analog MVM (forward/backward read) ---------------------------------
    read_noise: float = 0.06           # additive Gaussian sigma on MVM results
    noise_forward: bool = True         # apply read noise in the forward cycle
    noise_backward: bool = True        # apply read noise in the backward cycle
                                       # (Fig. 3A ablates backward noise alone)
    out_bound: float = 12.0            # |alpha| signal saturation of the integrator
    # --- digitally-programmable management techniques ------------------------
    noise_management: bool = False     # NM, Eq. (3) — applied on backward inputs
    nm_forward: bool = False           # NM also on forward (paper: fwd inputs already in [-1,1])
    bound_management: bool = False     # BM, Eq. (4) — iterative halve-and-retry
    bm_max_iters: int = 10             # effective bound becomes 2^n * alpha
    bm_mode: str = "iterative"         # 'iterative' (paper) | 'two_phase'
                                       # (beyond-paper: one unconditional
                                       # retry at 1/16 scale -> fixed 2-read
                                       # latency, effective bound 16*alpha,
                                       # no data-dependent control flow)
    update_management: bool = False    # UM — rebalance Cx / Cdelta by sqrt(dmax/xmax)
    # --- multi-device mapping (variability reduction) ------------------------
    devices_per_weight: int = 1        # #_d physical devices per logical weight
    # --- physical array-size limit (Discussion: max 4096x4096) --------------
    max_array_rows: int = 4096
    max_array_cols: int = 4096
    # --- sharded tile grid (core/tile_grid.py) -------------------------------
    # (row_blocks, col_blocks): decompose the physical array into a grid of
    # sub-tiles placed on a 2-D 'array_row' x 'array_col' device mesh
    # (distributed.sharding.crossbar_mesh).  None or (1, 1) keeps the
    # single-tile path; with fewer devices than blocks the grid runs as the
    # serial single-device oracle (identical numerics, no shard_map).
    tile_grid: Optional[Tuple[int, int]] = None
    # --- streaming chunk sizes (constant-memory conv/update pipeline) -------
    # update_chunk: number of (sample x position) vector pairs whose pulse
    # streams are materialized at once in the update cycle; the per-chunk
    # coincidence counts accumulate exactly (integer sums), so any chunk
    # size is bit-identical to the unchunked cycle (None).  Caps the
    # ~BL x activation blowup of the signed stream tensors.
    update_chunk: Optional[int] = None
    # conv_stream_chunk: number of im2col position columns streamed through
    # the array per chunk in the conv forward/backward read cycles — the
    # digital analogue of the paper's serial column streaming.  None
    # materializes all positions at once (one chunk).  Bit-identical to
    # None for fixed-latency BM; iterative BM's retry loop becomes
    # chunk-local (see with_streaming).
    conv_stream_chunk: Optional[int] = None
    # --- fused backward+update launch (kernels/bwd_update_mvm.py) -----------
    # One Pallas launch per layer runs the transpose (backward) read AND
    # generates the signed pulse streams in VMEM, accumulating the integer
    # coincidence counts on-chip; only ``update.finalize_counts`` (maps +
    # ctoc + bound clip) stays digital.  Bit-exact vs the separate-launch
    # path for the fixed-latency BM modes (off / two_phase); iterative BM
    # keeps its multi-launch retry loop and ignores this flag.  Requires
    # ``use_pallas``.
    fuse_bwd_update: bool = False
    # --- implementation switches ---------------------------------------------
    seeded_maps: bool = False          # regenerate device maps from RNG (see module doc)
    dtype: jnp.dtype = jnp.float32     # simulation dtype for weights / MVMs
    use_pallas: bool = False           # route MVM/update through Pallas kernels
    fast_rng: bool = True              # counter-hash RNG for bulk pulse streams
                                       # (mirrors the TPU kernel's on-chip PRNG)

    # Derived rules: each defined here and read everywhere else ------------
    @property
    def bm_active(self) -> bool:
        """BM (Eq. 4) acts: requested, and the integrator bound is finite."""
        return self.bound_management and self.out_bound != float("inf")

    @property
    def bm_iterative(self) -> bool:
        """BM runs the paper's data-dependent halve-and-retry loop — one
        raw read per retry, which no fused launch can take."""
        return self.bm_active and self.bm_mode != "two_phase"

    @property
    def grid_routed(self) -> bool:
        """The cycles route through the sub-tile grid
        (``core/tile_grid.py``).  The trivial (1, 1) grid stays on the
        single-tile path, which is bit-identical and keeps the fused
        launches."""
        return self.tile_grid is not None and tuple(self.tile_grid) != (1, 1)

    def nm_active(self, backward: bool) -> bool:
        """NM (Eq. 3) scales this cycle's inputs: the backward cycle's
        whenever NM is on, the forward cycle's only with ``nm_forward``."""
        return self.noise_management and (backward or self.nm_forward)

    def read_sigma(self, transpose: bool) -> float:
        """Additive read-noise sigma of a forward (``transpose=False``) or
        transpose read."""
        on = self.noise_backward if transpose else self.noise_forward
        return self.read_noise if on else 0.0

    # Ideal-device toggles used by the Fig. 3 / Fig. 4 ablations ------------
    def without_variations(self) -> "RPUConfig":
        """Eliminate device-to-device & cycle-to-cycle variations (Fig. 4 black)."""
        return dataclasses.replace(
            self, dw_min_dtod=0.0, dw_min_ctoc=0.0, imbalance_dtod=0.0,
            w_bound_dtod=0.0)

    def without_imbalance(self) -> "RPUConfig":
        """Eliminate only the up/down imbalance variation (Fig. 4 red)."""
        return dataclasses.replace(self, imbalance_dtod=0.0)

    def without_read_noise(self) -> "RPUConfig":
        return dataclasses.replace(self, read_noise=0.0)

    def without_out_bound(self) -> "RPUConfig":
        return dataclasses.replace(self, out_bound=float("inf"))

    def with_management(self, nm: bool = True, bm: bool = True,
                        um: bool = False, bl: Optional[int] = None) -> "RPUConfig":
        kw = dict(noise_management=nm, bound_management=bm, update_management=um)
        if bl is not None:
            kw["bl"] = bl
        return dataclasses.replace(self, **kw)

    def with_tile_grid(self, rows: int, cols: int) -> "RPUConfig":
        """Decompose the tile into a (rows x cols) sub-tile grid (see
        ``core/tile_grid.py``; sharded over ``crossbar_mesh`` when enough
        devices exist)."""
        if rows < 1 or cols < 1:
            raise ValueError(f"tile_grid must be >= (1, 1), got {(rows, cols)}")
        return dataclasses.replace(self, tile_grid=(rows, cols))

    def with_streaming(self, update_chunk: Optional[int] = None,
                       conv_stream_chunk: Optional[int] = None
                       ) -> "RPUConfig":
        """Enable the constant-memory streaming pipeline: chunk the update
        cycle's pulse streams and/or the conv position columns.  A field
        left ``None`` keeps its current value (to disable a chunk again use
        ``dataclasses.replace(cfg, update_chunk=None)``).

        Chunked training is bit-identical to the materialized paths for
        the fixed-latency BM modes (off / two-phase); iterative BM's
        retry loop becomes chunk-local — distribution-identical, and
        bit-exact only when read noise is off (docs/architecture.md).
        Requires ``fast_rng`` — the chunks' noise uses counter-offset
        draws."""
        for name, v in (("update_chunk", update_chunk),
                        ("conv_stream_chunk", conv_stream_chunk)):
            if v is not None and v < 1:
                raise ValueError(f"{name} must be >= 1, got {v}")
        if (update_chunk or conv_stream_chunk) and not self.fast_rng:
            raise ValueError(
                "streaming chunks require fast_rng=True (threefry draws "
                "cannot be counter-offset for chunk bit-parity)")
        return dataclasses.replace(
            self,
            update_chunk=(self.update_chunk if update_chunk is None
                          else update_chunk),
            conv_stream_chunk=(self.conv_stream_chunk
                               if conv_stream_chunk is None
                               else conv_stream_chunk))

    def normalized_for_lm(self) -> "RPUConfig":
        """Canonical normalization for LM dense tiles (the one place the
        ``dtype=f32 + seeded_maps`` rule lives — it used to be copy-pasted
        in both ``layers.dense_init`` and ``dense_apply``): simulate in
        float32 regardless of the model's param dtype, and regenerate the
        device population from the tile seed instead of storing the maps
        (2-3x HBM saving at billion-parameter scale, module docstring)."""
        return dataclasses.replace(self, dtype=jnp.float32,
                                   seeded_maps=True)

    @property
    def amplification(self) -> None:
        raise AttributeError("use update.amplification_factors(cfg, lr)")


# The paper's four named model variants (Results Summary / Fig. 6) ----------
def rpu_baseline() -> RPUConfig:
    """Table 1 verbatim: BL=10, no management — the model that fails (>10% err)."""
    return RPUConfig()


def rpu_nm_bm() -> RPUConfig:
    """RPU baseline + noise & bound management (Fig. 6 ~1.7%)."""
    return rpu_baseline().with_management(nm=True, bm=True)


def rpu_nm_bm_um_bl1() -> RPUConfig:
    """+ update management with BL=1 (Fig. 6 ~1.1%)."""
    return rpu_baseline().with_management(nm=True, bm=True, um=True, bl=1)


def rpu_full(devices_per_weight: int = 13) -> RPUConfig:
    """+ multi-device mapping (paper: 13x on K2 -> FP parity, ~0.8%)."""
    return dataclasses.replace(
        rpu_nm_bm_um_bl1(), devices_per_weight=devices_per_weight)


# ---------------------------------------------------------------------------
# Device map sampling
# ---------------------------------------------------------------------------

@jax.tree_util.register_pytree_node_class
class DeviceMaps:
    """Per-physical-device parameter maps for one crossbar tile.

    Shapes are ``(rows_phys, cols_phys)`` where ``rows_phys = devices_per_weight
    * rows_logical`` (the multi-device replicas are extra physical rows, like
    the paper's 416x401 example for 13-device mapping of the 32x401 K2 array).
    """

    __slots__ = ("dw_up", "dw_dn", "bound")

    def __init__(self, dw_up: jax.Array, dw_dn: jax.Array, bound: jax.Array):
        self.dw_up = dw_up
        self.dw_dn = dw_dn
        self.bound = bound

    def tree_flatten(self):
        return (self.dw_up, self.dw_dn, self.bound), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def shape(self) -> Tuple[int, int]:
        return self.dw_up.shape


def sample_device_maps(key: jax.Array, rows_phys: int, cols: int,
                       cfg: RPUConfig) -> DeviceMaps:
    """Sample the fabrication-time device population for a tile.

    * ``dw_min``: mean ``cfg.dw_min`` with ``dw_min_dtod`` relative Gaussian
      device-to-device spread (clipped at a small positive floor — a device
      cannot have a negative minimal update).
    * up/down imbalance: ratio r = dw_up/dw_dn with mean 1 and
      ``imbalance_dtod`` spread, applied geometrically so E[log r] = 0.
    * ``bound``: mean ``cfg.w_bound`` with ``w_bound_dtod`` spread, floored.
    """
    k_dw, k_imb, k_bound = jax.random.split(key, 3)
    shape = (rows_phys, cols)
    dt = cfg.dtype

    dw = cfg.dw_min * (1.0 + cfg.dw_min_dtod
                       * jax.random.normal(k_dw, shape, dtype=dt))
    dw = jnp.maximum(dw, 0.01 * cfg.dw_min)

    # ratio r ~ 1 + imbalance_dtod * N(0,1); split geometrically so that the
    # *average step magnitude* stays dw while dw_up/dw_dn = r.
    r = 1.0 + cfg.imbalance_dtod * jax.random.normal(k_imb, shape, dtype=dt)
    r = jnp.clip(r, 0.5, 2.0)
    sqrt_r = jnp.sqrt(r)
    dw_up = dw * sqrt_r
    dw_dn = dw / sqrt_r

    bound = cfg.w_bound * (1.0 + cfg.w_bound_dtod
                           * jax.random.normal(k_bound, shape, dtype=dt))
    bound = jnp.maximum(bound, 0.1 * cfg.w_bound)
    return DeviceMaps(dw_up=dw_up, dw_dn=dw_dn, bound=bound)


def seeded_device_maps(seed_key: jax.Array, rows_phys: int, cols: int,
                       cfg: RPUConfig) -> DeviceMaps:
    """Regenerate the (fixed) device population from a tile-specific key.

    Because the key is a pure function of the tile identity, calling this in
    every step yields the *same* device population each time without storing
    it — trading HBM bytes for (cheap, VPU) RNG recompute.  Beyond-paper
    optimization; statistically identical to :func:`sample_device_maps`.
    """
    return sample_device_maps(seed_key, rows_phys, cols, cfg)


def effective_dtod_reduction(devices_per_weight: int) -> float:
    """Paper: #_d devices per weight reduce device variability ~ sqrt(#_d)."""
    return float(devices_per_weight) ** 0.5
