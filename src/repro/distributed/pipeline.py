"""GPipe-style pipeline parallelism via shard_map + collective_permute.

The layer stack is split into S stages; stage s's parameters live only on
the devices of pipeline rank s (stacked leading axis sharded over the
``pipe`` mesh axis).  M microbatches flow through the classic GPipe schedule
(S + M - 1 ticks); at every tick each stage runs its block on its current
activation and ``ppermute``s the result to the next stage, so compute and
the inter-stage transfer overlap across ticks.  Bubble fraction =
(S - 1) / (S + M - 1) — choose M >> S.

This composes with the DP/TP rules: the mesh for a PP run is
``(pipe, data, model)`` — or the nested
``('pipe', 'data', 'array_row', 'array_col')`` mesh from
``sharding.nested_mesh``, in which case ``data_axis='data'`` additionally
shards each microbatch over the data replicas inside the *same* shard_map
— and the per-stage block uses the same logical-axis annotations as the
non-PP path.  Provided as an opt-in alternative to the default DP+FSDP+TP
preset (DESIGN.md §5); the GPipe schedule is pinned against the serial
layer-stack oracle across S x M grids (including M < S and M == 1) and on
the nested pipe x data mesh by ``test_pipeline_schedule_grid`` /
``test_pipeline_on_nested_mesh_with_data_axis`` in
``tests/test_distributed.py`` (forced multi-device host subprocesses).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, PartitionSpec as P

Array = jax.Array


def pipeline_apply(block_fn: Callable[[Any, Array], Array],
                   stage_params: Any, microbatches: Array, mesh: Mesh,
                   axis: str = "pipe",
                   data_axis: Optional[str] = None) -> Array:
    """Run ``microbatches`` (M, mb, ...) through S pipeline stages.

    ``stage_params``: pytree with leading stage axis S (sharded over
    ``axis``); ``block_fn(params_one_stage, x) -> y`` must keep x's shape
    (homogeneous stages — the usual transformer-layer-group case).

    ``data_axis``: name of a data-parallel mesh axis to additionally shard
    the per-microbatch batch dim (axis 1) over — the nested pipe x data
    composition (``sharding.nested_mesh``).  Each data shard then runs the
    full GPipe schedule on its batch slice inside the *same* shard_map;
    stage parameters stay replicated over ``data_axis``.  ``None`` keeps
    the pipe-only behaviour on any mesh.

    Returns (M, mb, ...) outputs from the final stage.
    """
    n_stages = mesh.shape[axis]
    m = microbatches.shape[0]
    assert m >= 1, "need at least one microbatch"
    ticks = n_stages + m - 1

    mb_spec = (P(None, data_axis) if data_axis is not None
               else P())     # microbatches replicated across stages
    in_specs = (jax.tree_util.tree_map(lambda x: P(axis), stage_params),
                mb_spec)
    out_specs = mb_spec

    def per_stage(params_local, mb_all):
        # params_local leaves: (1, ...) — this stage's slice
        params_one = jax.tree_util.tree_map(lambda x: x[0], params_local)
        stage_id = jax.lax.axis_index(axis)
        mb_shape = mb_all.shape[1:]

        def tick(carry, t):
            buf, outputs = carry
            # stage 0 ingests microbatch t (if any) — others use buf
            feed = jnp.where(t < m, t, 0)
            x_in = jnp.where(stage_id == 0, mb_all[feed], buf)
            active = (t >= stage_id) & (t - stage_id < m)
            y = block_fn(params_one, x_in)
            y = jnp.where(active, y, buf)
            # collect finished microbatch at the last stage
            out_idx = t - (n_stages - 1)
            is_out = (stage_id == n_stages - 1) & (out_idx >= 0) & \
                (out_idx < m)
            outputs = jax.lax.cond(
                is_out,
                lambda o: jax.lax.dynamic_update_index_in_dim(
                    o, y, jnp.maximum(out_idx, 0), 0),
                lambda o: o, outputs)
            # shift activations downstream
            buf = jax.lax.ppermute(
                y, axis, [(i, (i + 1) % n_stages)
                          for i in range(n_stages)])
            return (buf, outputs), None

        buf0 = jnp.zeros(mb_shape, mb_all.dtype)
        out0 = jnp.zeros((m,) + mb_shape, mb_all.dtype)
        (_, outputs), _ = jax.lax.scan(
            tick, (buf0, out0), jnp.arange(ticks))
        # every stage returns its 'outputs'; only the last stage's is real.
        # psum_scatter-free trick: broadcast last stage's buffer via ppermute
        # ring is overkill — use psum of masked outputs (zeros elsewhere).
        outputs = jnp.where(stage_id == n_stages - 1, outputs, 0.0)
        return jax.lax.psum(outputs, axis)

    fn = jax.shard_map(per_stage, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(stage_params, microbatches)


def split_layers_to_stages(stacked_params: Any, n_stages: int) -> Any:
    """(L, ...) stacked layer params -> (S, L/S, ...) stage-major layout."""
    def f(x):
        l = x.shape[0]
        assert l % n_stages == 0, (l, n_stages)
        return x.reshape(n_stages, l // n_stages, *x.shape[1:])
    return jax.tree_util.tree_map(f, stacked_params)


def stage_block_fn(cfg, layers_per_stage: int):
    """Standard stage body: scan `layers_per_stage` transformer blocks."""
    from repro.models import transformer

    def block_fn(stage_params, x):
        positions = jnp.arange(x.shape[1])[None]

        def body(xx, layer_p):
            yy, _ = transformer._block_apply(layer_p, xx, cfg,
                                             positions=positions)
            return yy, None

        y, _ = jax.lax.scan(body, x, stage_params)
        return y

    return block_fn
