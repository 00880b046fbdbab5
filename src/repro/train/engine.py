"""Scan-fused, device-resident training engine.

The paper's premise is that all three backprop cycles run in constant time
*on the array*; the simulation must therefore not spend its wall-clock in
per-step Python dispatch.  This module replaces the per-minibatch Python
loop with a single jitted **epoch** program:

* the shuffled epoch data stays on device — the permutation, the gather
  into ``(steps, batch, ...)`` minibatches and every train step live inside
  one XLA computation;
* per-step PRNG keys are derived with ``jax.random.fold_in`` *inside* the
  scan (batched via ``vmap`` over the step index), reproducing bit-for-bit
  the key schedule of the legacy Python loop so the two engines are
  interchangeable oracles for each other;
* the whole epoch is jitted with ``donate_argnums`` on (params, opt_state)
  so the carry buffers are reused in place across epochs;
* an opt-in ``jax.shard_map`` data-parallel path splits the batch axis over
  the ``'data'`` mesh axis (``distributed.sharding.data_mesh``) and psums
  the float gradients.  For digital mode this is exact (the loss is summed
  over the batch); for analog mode the per-shard pulse-update deltas are
  summed, which approximates the serial full-batch update stream to within
  the device-bound clip.

The legacy loop is kept in :mod:`repro.train.cnn` behind ``engine="python"``
as a correctness oracle; the parity test in ``tests/test_train_engine.py``
pins the two engines to identical parameters.

The streaming conv/update pipeline (``RPUConfig.update_chunk`` /
``conv_stream_chunk``, see ``core/conv_mapping.py``) composes with both
engines transparently: the chunk loops are ``fori_loop``s inside the layer
cycles, so the scanned epoch program holds only one chunk of im2col
columns / pulse streams live per conv layer at any point — the epoch's
peak live bytes stop scaling with ``BL x positions``.  Chunked training is
bit-identical to the materialized configuration, so the engine parity
suites hold unchanged under streaming (tests/test_conv_stream.py pins the
cross product).
"""

from __future__ import annotations

import functools
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.optim import Optimizer

PyTree = Any
Array = jax.Array


def fold_in_keys(key: Array, indices: Array) -> Array:
    """Batched ``fold_in``: one key per index.

    This is THE key schedule shared by the scan engines, the legacy Python
    loops and the LM driver — all derive the step-``i`` key as
    ``fold_in(base_key, i)``, which is what makes the engines bit-exact
    oracles for each other.  Change it in one place or not at all.
    """
    return jax.vmap(lambda i: jax.random.fold_in(key, i))(indices)


# ---------------------------------------------------------------------------
# Data-parallel gradient wrapper (opt-in shard_map over the batch axis)
# ---------------------------------------------------------------------------

def _sanitize_grads(params: PyTree, grads: PyTree) -> PyTree:
    """float0 / None cotangents (tile seeds) -> rank-0 zero sentinels.

    float0 numpy arrays cannot cross a ``shard_map`` boundary; the
    optimizers skip non-float *params* regardless of the cotangent value,
    so a scalar placeholder is semantically equivalent.
    """
    def f(p, g):
        if g is None or getattr(g, "dtype", None) == jax.dtypes.float0:
            return jnp.zeros(())
        return g

    return jax.tree_util.tree_map(f, params, grads)


def data_parallel_grads(grads_fn: Callable) -> Callable:
    """Wrap ``grads_fn(params, *batched_args, key)`` in a shard_map that
    splits the leading (batch) axis of the batched args over the ``'data'``
    mesh axis and psums the float gradients.

    The trailing arg must be the PRNG key; it is folded with the shard
    index so analog noise decorrelates across shards.
    """
    from jax.sharding import PartitionSpec as P

    from repro.distributed import sharding as shd

    mesh = shd.data_mesh()

    def wrapped(params, *args):
        *batched, key = args
        kd = jax.random.key_data(key)   # extended dtypes stay out of smap

        def body(p, kd, *bs):
            k = jax.random.wrap_key_data(kd)
            k = jax.random.fold_in(k, jax.lax.axis_index("data"))
            g = _sanitize_grads(p, grads_fn(p, *bs, k))
            # psum real (rank>0 float) grads; rank-0 sentinels pass through
            return jax.tree_util.tree_map(
                lambda t: jax.lax.psum(t, "data")
                if t.ndim > 0 and jnp.issubdtype(t.dtype, jnp.floating)
                else t, g)

        in_specs = (P(), P()) + (P("data"),) * len(batched)
        f = jax.shard_map(body, mesh=mesh, in_specs=in_specs,
                          out_specs=P(), check_vma=False)
        return f(params, kd, *batched)

    return wrapped


def _reject_crossbar_mesh_conflict(cfg) -> None:
    """Fail fast when data-parallel shard_map and a *sharded* crossbar tile
    grid would claim the same devices.

    ``data_parallel_grads`` spans ALL healthy devices with the 1-D 'data'
    mesh; a tile grid that can place its 'array_row' x 'array_col' mesh
    would nest a second shard_map over the same devices inside the first.
    The composition rules live in one place —
    ``distributed.sharding.MeshPlan.validate`` — this check phrases each
    offending layer's placement as a ``MeshPlan(data=<pool>, tile=<grid>)``
    and surfaces the plan's verdict.  A grid the pool cannot hold composes
    fine: it runs its bit-identical serial oracle on every data shard.
    """
    if getattr(cfg, "mode", None) != "analog" or not hasattr(
            cfg, "resolved"):
        return
    from repro.distributed import elastic
    from repro.distributed import sharding as shd
    from repro.models.lenet import LAYERS
    n = elastic.n_healthy()
    errors = []
    for layer in LAYERS:
        c = cfg.resolved(layer)
        if c is None or getattr(c, "tile_grid", None) is None:
            continue
        try:
            shd.MeshPlan(data=max(n, 1), tile=c.tile_grid).validate(n)
        except ValueError as e:
            errors.append(f"{layer}: {e}")
    if errors:
        raise ValueError(
            "data-parallel shard_map cannot compose with sharded crossbar "
            "tile grids:\n  " + "\n  ".join(errors))


# ---------------------------------------------------------------------------
# Scan-fused CNN epoch
# ---------------------------------------------------------------------------

def make_cnn_step_fn(cfg, opt: Optimizer, *,
                     data_parallel: bool = False) -> Callable:
    """The single train step the epoch scan iterates.

    ``step(params, opt_state, x, y, key) -> (params, opt_state)`` —
    returned *unjitted* so :mod:`repro.analysis` can trace it abstractly
    (launch/collective budgets audit the exact body the epoch program
    runs, not a lookalike).
    """
    from repro.models import lenet

    def grads_of(params, xb, yb, key):
        return jax.grad(lenet.loss_fn, allow_int=True)(
            params, xb, yb, key, cfg)

    grads_fn = data_parallel_grads(grads_of) if data_parallel else grads_of

    def step(params, opt_state, x, y, key):
        g = grads_fn(params, x, y, key)
        return opt.update(g, opt_state, params)

    return step


def make_cnn_epoch_fn(cfg, opt: Optimizer, *, batch: int,
                      data_parallel: bool = False) -> Callable:
    """Build the jitted epoch program for the LeNet/MNIST trainer.

    Returns ``run_epoch(params, opt_state, xs, ys, k_data, k_train, epoch)
    -> (params, opt_state)`` where ``xs/ys`` is the full (device-resident)
    training split and ``epoch`` the epoch index.  params/opt_state are
    donated: the caller must thread the returned values.
    """
    if data_parallel:
        _reject_crossbar_mesh_conflict(cfg)

    step_fn = make_cnn_step_fn(cfg, opt, data_parallel=data_parallel)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_epoch(params, opt_state, xs, ys, k_data, k_train, epoch):
        n = xs.shape[0]
        spe = n // batch                       # steps per epoch
        used = spe * batch
        perm = jax.random.permutation(
            jax.random.fold_in(k_data, epoch), n)[:used]
        xb = xs[perm].reshape(spe, batch, *xs.shape[1:])
        yb = ys[perm].reshape(spe, batch, *ys.shape[1:])
        keys = fold_in_keys(k_train, epoch * spe + jnp.arange(spe))

        def body(carry, inp):
            p, s = carry
            x, y, k = inp
            p, s = step_fn(p, s, x, y, k)
            return (p, s), ()

        (params, opt_state), _ = jax.lax.scan(
            body, (params, opt_state), (xb, yb, keys))
        return params, opt_state

    return run_epoch


def make_cnn_eval_fn(cfg, *, batch: int = 256) -> Callable:
    """Scan-fused evaluation: one dispatch for the whole test split.

    Returns ``evaluate(params, xs, ys, key) -> error`` (a device scalar).
    The split is padded to a batch multiple with weight-0 samples, and the
    per-batch keys are ``fold_in(key, batch_start_offset)`` — the same
    schedule the historical per-batch loop used, so batch-aligned splits
    report identical errors.  (Padding adds extra read-noise draws on
    non-aligned analog splits; the weighted count is unaffected in
    digital mode.)
    """
    from repro.models import lenet

    @functools.partial(jax.jit, static_argnums=())
    def evaluate(params, xs, ys, key):
        n = xs.shape[0]
        nb = -(-n // batch)
        pad = nb * batch - n
        xs = jnp.pad(xs, ((0, pad),) + ((0, 0),) * (xs.ndim - 1))
        ys = jnp.pad(ys, ((0, pad),))
        w = jnp.pad(jnp.ones((n,), jnp.float32), ((0, pad),))
        xb = xs.reshape(nb, batch, *xs.shape[1:])
        yb = ys.reshape(nb, batch)
        wb = w.reshape(nb, batch)
        keys = fold_in_keys(key, jnp.arange(nb) * batch)

        def body(acc, inp):
            x, y, wgt, k = inp
            logits = lenet.apply(params, x, k, cfg)
            hit = (jnp.argmax(logits, -1) == y).astype(jnp.float32)
            return acc + jnp.sum(hit * wgt), ()

        correct, _ = jax.lax.scan(body, jnp.zeros(()), (xb, yb, wb, keys))
        return 1.0 - correct / n

    return evaluate


# ---------------------------------------------------------------------------
# Scan-fused recurrent (sequence) epoch: scan-over-time nested in
# scan-over-steps
# ---------------------------------------------------------------------------

def make_seq_step_fn(cfg, opt: Optimizer) -> Callable:
    """Single sequence-model train step (``repro.recurrent.model``).

    ``step(params, opt_state, tokens, targets, key) -> (params,
    opt_state)``.  The backward pass runs the cell's temporal-reuse VJP:
    per-timestep transpose reads, coincidence counts accumulated across
    the whole unrolled sequence, ONE ``finalize_counts`` per tile.
    Returned unjitted for :mod:`repro.analysis` traceability, mirroring
    :func:`make_cnn_step_fn`.
    """
    from repro.recurrent import model as seq_model

    def step(params, opt_state, tokens, targets, key):
        g = jax.grad(seq_model.loss_fn, allow_int=True)(
            params, tokens, targets, key, cfg)
        return opt.update(g, opt_state, params)

    return step


def make_seq_epoch_fn(cfg, opt: Optimizer, *, batch: int) -> Callable:
    """Jitted epoch program for the sequence-copy trainer.

    ``run_epoch(params, opt_state, tokens, targets, k_data, k_train,
    epoch) -> (params, opt_state)`` — the outer ``lax.scan`` walks
    minibatches while each step's loss runs the cell's inner
    scan-over-time, with (params, opt_state) donated exactly like the CNN
    epoch.  Key schedule: ``fold_in(k_train, epoch * spe + i)`` — the
    repo-wide contract from :func:`fold_in_keys`.
    """
    step_fn = make_seq_step_fn(cfg, opt)

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def run_epoch(params, opt_state, tokens, targets, k_data, k_train,
                  epoch):
        n = tokens.shape[0]
        spe = n // batch
        used = spe * batch
        perm = jax.random.permutation(
            jax.random.fold_in(k_data, epoch), n)[:used]
        tb = tokens[perm].reshape(spe, batch, *tokens.shape[1:])
        gb = targets[perm].reshape(spe, batch, *targets.shape[1:])
        keys = fold_in_keys(k_train, epoch * spe + jnp.arange(spe))

        def body(carry, inp):
            p, s = carry
            t, g, k = inp
            p, s = step_fn(p, s, t, g, k)
            return (p, s), ()

        (params, opt_state), _ = jax.lax.scan(
            body, (params, opt_state), (tb, gb, keys))
        return params, opt_state

    return run_epoch


def make_seq_eval_fn(cfg, *, batch: int = 256) -> Callable:
    """Scan-fused answer-span accuracy over a token split.

    ``evaluate(params, tokens, targets, key) -> accuracy`` (device
    scalar); inference runs the same noisy analog forward as training.
    """
    from repro.recurrent import model as seq_model

    @jax.jit
    def evaluate(params, tokens, targets, key):
        n = tokens.shape[0]
        nb = -(-n // batch)
        pad = nb * batch - n
        tokens = jnp.pad(tokens, ((0, pad), (0, 0)))
        # padded rows carry all-IGNORE targets: they add no answer span
        targets = jnp.pad(targets, ((0, pad), (0, 0)),
                          constant_values=-1)
        tb = tokens.reshape(nb, batch, -1)
        gb = targets.reshape(nb, batch, -1)
        keys = fold_in_keys(key, jnp.arange(nb) * batch)

        def body(acc, inp):
            t, g, k = inp
            logits = seq_model.apply(params, t, k, cfg)   # (T, B, V)
            tgt = g.T
            mask = tgt >= 0
            hit = (jnp.argmax(logits, -1) == tgt) & mask
            return (acc[0] + jnp.sum(hit.astype(jnp.float32)),
                    acc[1] + jnp.sum(mask.astype(jnp.float32))), ()

        (correct, total), _ = jax.lax.scan(
            body, (jnp.zeros(()), jnp.zeros(())), (tb, gb, keys))
        return correct / jnp.maximum(total, 1.0)

    return evaluate


# ---------------------------------------------------------------------------
# Generic multi-step scan (LM training chunks)
# ---------------------------------------------------------------------------

def scan_steps(step_fn: Callable) -> Callable:
    """Lift a single train step into a scanned multi-step program.

    ``step_fn(params, opt_state, batch, key) -> (params, opt_state,
    metrics)`` becomes ``multi(params, opt_state, batches, keys)`` where
    every leaf of ``batches`` (and ``keys``) carries a leading chunk axis;
    metrics come back stacked along that axis.  Jit the result with
    ``donate_argnums=(0, 1)`` to reuse the carry buffers across chunks.
    """
    def multi(params, opt_state, batches, keys):
        def body(carry, inp):
            p, s = carry
            b, k = inp
            p, s, m = step_fn(p, s, b, k)
            return (p, s), m

        (params, opt_state), metrics = jax.lax.scan(
            body, (params, opt_state), (batches, keys))
        return params, opt_state, metrics

    return multi
