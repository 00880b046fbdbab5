"""Pallas TPU kernels for the analog hot spots.

noisy_mvm.py     - fused raw array read: matmul + on-chip Gaussian + bound
                   clip, with physical array-split segment semantics (one
                   launch per physical read — the iterative-BM retry unit).
managed_mvm.py   - fused *managed* read: NM scale + two-phase BM (both reads
                   share one launch; the 1/16 retry reuses the MXU product) +
                   select-on-saturation + clip + #_d replica average, all in
                   one VMEM-resident pass.
conv_mvm.py      - implicit-im2col managed conv read: the patch tiles are
                   assembled in VMEM from the activation volume.
bwd_update_mvm.py - fused backward + update: the managed transpose read and
                   the on-chip pulse streams' coincidence counts in one
                   launch (dense and conv variants).
pulse_update.py  - the update cycle's coincidence counts (both pulse-slot
                   matmuls, one bf16 MXU pass).
flash_attention.py - fused attention forward (online softmax in VMEM) for
                   the serving path; realises the roofline's
                   'fused-attention projection' (EXPERIMENTS.md §Roofline).
ops.py           - launch wrappers: take the operands ``repro.core``
                   decided, name the launch, auto-interpret on non-TPU
                   backends (evaluated per call).

The kernels import nothing of ``repro.core`` but ``core.device``; their
pure-jnp oracles are the simulator's default path
(``core.tile.analog_mvm_reference``, ``managed_mvm_reference``,
``core.update.coincidence_counts``).
"""
