"""Named audit targets: the traced programs the CI budgets pin.

Each target is a zero-argument callable returning ``{program_name:
json-able report}``.  Programs are traced abstractly (ShapeDtypeStruct
inputs) — nothing trains, nothing allocates device buffers beyond what
compilation itself needs — and every launch-bearing trace is preceded by
``jax.clear_caches()`` so jit caches from earlier traces cannot freeze
stale kernel names into the jaxpr (launch labels are static jit arguments
of the kernel wrappers, but intermediate jit boundaries above them would
otherwise replay unlabeled traces).

The ``lenet_tile_grid`` target shards over the crossbar mesh and needs at
least ``grid rows x cols`` devices — run under
``XLA_FLAGS=--xla_force_host_platform_device_count=8`` (scripts/audit.py
--force-devices does this before importing jax).
"""

from __future__ import annotations

from typing import Any, Callable, Dict

import jax
import jax.numpy as jnp

from repro.analysis.jaxpr_audit import audit_donation, audit_fn

#: audited LeNet policy: fixed-latency managed reads AND the fused
#: backward+update megakernel — each analog layer's whole backward
#: cycle-pair is ONE ``bwd_update`` launch (pinned per layer below)
LENET_POLICY = ("managed:use_pallas=true:bm_mode=two_phase"
                ":fuse_bwd_update=true")
LENET_BATCH = 8

#: serving audit policy: the managed LM preset with the fixed-latency BM
#: mode, so the whole managed read fuses into ONE Pallas launch per
#: converted site (iterative BM cannot fuse — management.launch_args
#: rejects it)
SERVE_POLICY = "lm_managed:use_pallas=true:bm_mode=two_phase"

GRID = (2, 2)
GRID_ROWS, GRID_COLS = 16, 12          # logical tile audited on the grid
GRID_BATCH = 8
GRID_CHUNK = 4                          # stream chunk (rows per round)


def _key_struct():
    return jax.eval_shape(lambda: jax.random.key(0))  # lint: fresh-key-ok


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


# ---------------------------------------------------------------------------
# LeNet scan-engine step (single device)
# ---------------------------------------------------------------------------

def _lenet_setup():
    from repro import optim
    from repro.analog.presets import parse_policy
    from repro.models import lenet
    from repro.train import engine

    cfg = lenet.LeNetConfig.from_policy(parse_policy(LENET_POLICY))
    opt = optim.sgd(cfg.lr)
    params = jax.eval_shape(lambda k: lenet.init(k, cfg), _key_struct())
    opt_state = jax.eval_shape(opt.init, params)
    step = engine.make_cnn_step_fn(cfg, opt)
    x = _sds((LENET_BATCH, 28, 28, 1))
    y = _sds((LENET_BATCH,), jnp.int32)
    return cfg, params, opt_state, step, x, y


def lenet_target() -> Dict[str, Any]:
    """Full train step + per-layer isolated forward reads + donation.

    The per-layer programs trace one layer's analog forward read under
    ``ops.launch_label(layer)``; the managed-read pin (exactly ONE fused
    launch per analog layer, PR 2's contract) lives there.  The full-step
    program pins totals by kind across all three cycles of all layers.
    """
    from repro.analog.modules import AnalogConv2d, AnalogLinear
    from repro.kernels import ops
    from repro.models import lenet

    cfg, params, opt_state, step, x, y = _lenet_setup()
    out: Dict[str, Any] = {}

    jax.clear_caches()
    rep = audit_fn(step, params, opt_state, x, y, _key_struct())
    out["step"] = rep.to_json()

    apply_of = {"conv": AnalogConv2d.apply, "linear": AnalogLinear.apply}
    layer_inputs = _lenet_layer_inputs(cfg, params)
    for layer in lenet.LAYERS:
        state = params[layer]
        fn = apply_of[state.meta.kind]
        jax.clear_caches()
        with ops.launch_label(layer):
            rep = audit_fn(
                lambda s, xv, k: fn(s, xv, k, mode=cfg.layer_mode(layer)),
                state, layer_inputs[layer], _key_struct())
        out[f"read__{layer}"] = rep.to_json()

    # Per-layer vjp: forward read + the fused backward+update — the
    # PR 9 pin is exactly ONE ``bwd_update`` launch per analog layer
    # (no separate transpose read, no pulse-counts launch).
    for layer, rep in lenet_layer_cycles(cfg, params).items():
        out[f"bwd_update__{layer}"] = rep.to_json()

    jax.clear_caches()
    don = audit_donation(step, (params, opt_state, x, y, _key_struct()),
                         donate_argnums=(0, 1))
    out["donation__step"] = don.to_json()
    return out


def _dense_out(state) -> tuple:
    """Logical output width of a dense analog state (replica-averaged)."""
    m_phys = state.w.shape[0]
    d = state.meta.cfg.devices_per_weight
    return (m_phys // d,)


def _lenet_layer_inputs(cfg, params) -> Dict[str, Any]:
    """Abstract input of each LeNet tile at the audited batch size."""
    from repro.models import lenet
    p1, _p2, flat = lenet.feature_sizes(cfg)
    return {"K1": _sds((LENET_BATCH, 28, 28, 1)),
            "K2": _sds((LENET_BATCH, p1[0], p1[1], 16)),
            "W3": _sds((LENET_BATCH, flat)),
            "W4": _sds((LENET_BATCH,) + _dense_out(params["W3"]))}


def lenet_layer_cycles(cfg, params) -> Dict[str, Any]:
    """Each LeNet tile's forward read + backward/update program, traced
    abstractly under ``ops.launch_label(layer)``: ``{layer: JaxprReport}``
    — the kernels each layer's three cycles actually route through."""
    from repro.analog.modules import AnalogConv2d, AnalogLinear
    from repro.kernels import ops
    from repro.models import lenet

    apply_of = {"conv": AnalogConv2d.apply, "linear": AnalogLinear.apply}
    layer_inputs = _lenet_layer_inputs(cfg, params)
    reports = {}
    for layer in lenet.LAYERS:
        state = params[layer]
        fn = apply_of[state.meta.kind]
        mode = cfg.layer_mode(layer)

        def cycle(s, xv, k, fn=fn, mode=mode):
            return jnp.sum(fn(s, xv, k, mode=mode) ** 2)

        jax.clear_caches()
        with ops.launch_label(layer):
            reports[layer] = audit_fn(
                jax.grad(cycle, argnums=(0, 1), allow_int=True),
                state, layer_inputs[layer], _key_struct())
    return reports


# ---------------------------------------------------------------------------
# Sharded tile grid: chunked streaming read + streaming update
# ---------------------------------------------------------------------------

def _grid_cfg():
    from repro.core.device import RPUConfig
    # raw sharded read: management stays digital around it, so BM off and
    # each chunk round is exactly one read -> one collective round
    return RPUConfig(tile_grid=GRID, bound_management=False,
                     noise_management=False, update_management=False)


def _require_grid_devices() -> None:
    need = GRID[0] * GRID[1]
    have = len(jax.devices())
    if have < need:
        raise RuntimeError(
            f"tile-grid target needs >= {need} devices, have {have}; run "
            "under XLA_FLAGS=--xla_force_host_platform_device_count=8 "
            "(scripts/audit.py --force-devices 8 sets this before "
            "importing jax)")


def lenet_tile_grid_target() -> Dict[str, Any]:
    """Sharded-grid invariants: psum structure of reads, silence of updates.

    * ``grid_read`` — one raw sharded read: 2 psum equations (the partial-y
      reduction along the contraction axis and the global saturation-flag
      OR), ONE dependency round.
    * ``streamed_read`` — a chunk loop of sharded reads (the streaming conv
      forward's shape): the budget pins ``collective_rounds_per_iter == 1``
      on the chunk loop — PR 4's "one psum per chunk round" contract.
    * ``streamed_update`` — the streamed grid update cycle: chunk loops run
      per device with ZERO collectives (counts accumulate shard-locally;
      only finalize touches the blocks).
    """
    from repro.core import tile as tile_lib
    from repro.core import tile_grid, update

    _require_grid_devices()
    cfg = _grid_cfg()
    m, n = GRID_ROWS, GRID_COLS
    w = _sds((m, n))
    key = _key_struct()
    out: Dict[str, Any] = {}

    def grid_read(wv, xv, k):
        return tile_grid.grid_analog_mvm_sharded(wv, xv, k, cfg)

    jax.clear_caches()
    out["grid_read"] = audit_fn(
        grid_read, w, _sds((GRID_BATCH, n)), key).to_json()

    def streamed_read(wv, xv, k):
        total = xv.shape[0]
        nchunks = total // GRID_CHUNK

        def body(c, acc):
            start = c * GRID_CHUNK
            xc = jax.lax.dynamic_slice_in_dim(xv, start, GRID_CHUNK, 0)
            y, _sat = tile_grid.grid_analog_mvm_sharded(
                wv, xc, k, cfg, row_offset=start, total_rows=total)
            return jax.lax.dynamic_update_slice_in_dim(acc, y, start, 0)

        acc = jnp.zeros((total, m), jnp.float32)
        return jax.lax.fori_loop(0, nchunks, body, acc)

    jax.clear_caches()
    out["streamed_read"] = audit_fn(
        streamed_read, w, _sds((GRID_BATCH, n)), key).to_json()

    maps = jax.eval_shape(
        lambda k: tile_lib.init_tile(k, m, n, cfg).maps, _key_struct())
    total = GRID_BATCH
    x_all = _sds((total, n))
    d_all = _sds((total, m))

    def get_chunk(src, start, chunk):
        xs, ds = src
        return (jax.lax.dynamic_slice_in_dim(xs, start, chunk, 0),
                jax.lax.dynamic_slice_in_dim(ds, start, chunk, 0))

    def streamed_update(wv, mp, xs, ds, k):
        return update.pulse_update_streamed(
            wv, mp, (xs, ds), get_chunk, k, cfg, 0.01,
            total=total, chunk=GRID_CHUNK)

    jax.clear_caches()
    out["streamed_update"] = audit_fn(
        streamed_update, w, maps, x_all, d_all, key).to_json()
    return out


# ---------------------------------------------------------------------------
# Analog recurrent (LSTM copy-task) train step
# ---------------------------------------------------------------------------

#: audited recurrent policy: NM + fixed-latency BM (UM is structurally
#: incompatible with temporal accumulation — the cell rejects it) with the
#: fused per-timestep backward+update megakernel
LSTM_POLICY = ("nm_bm:use_pallas=true:bm_mode=two_phase"
               ":fuse_bwd_update=true")
LSTM_BATCH = 8


def lstm_copy_target() -> Dict[str, Any]:
    """Scan-over-time analog LSTM train step on the copy task.

    Pins the temporal weight-reuse invariants: the whole BPTT sweep is
    lax.scan'd (launch counts stay flat in sequence length — per-timestep
    launches live inside while-loop bodies and are counted once), the
    update finalize runs ONCE per tile per step, and the fused config
    carries the ``bwd_update`` megakernel per timestep-chunk instead of
    separate transpose-read + counts launches.
    """
    from repro.analog.convert import convert_to_analog
    from repro.analog.presets import parse_policy
    from repro.optim import optimizers
    from repro.recurrent import model as seq_model
    from repro.train import engine

    scfg = seq_model.SeqConfig(kind="lstm", hidden=32, seq_len=4, delay=2,
                               time_chunk=2, lr=0.05)
    pol = parse_policy(LSTM_POLICY)

    def build(k):
        p, a = seq_model.init(k, scfg)
        p, _ = convert_to_analog(p, a, pol, key=k)
        return p

    params = jax.eval_shape(build, _key_struct())
    opt = optimizers.mixed_analog(optimizers.sgd(scfg.lr))
    opt_state = jax.eval_shape(opt.init, params)
    step = engine.make_seq_step_fn(scfg, opt)
    toks = _sds((LSTM_BATCH, scfg.t_total), jnp.int32)
    tgts = _sds((LSTM_BATCH, scfg.t_total), jnp.int32)
    out: Dict[str, Any] = {}

    jax.clear_caches()
    out["step"] = audit_fn(step, params, opt_state, toks, tgts,
                           _key_struct()).to_json()

    jax.clear_caches()
    out["donation__step"] = audit_donation(
        step, (params, opt_state, toks, tgts, _key_struct()),
        donate_argnums=(0, 1)).to_json()
    return out


# ---------------------------------------------------------------------------
# DeepSeek smoke LM step + serve decode
# ---------------------------------------------------------------------------

def deepseek_smoke_target() -> Dict[str, Any]:
    """LM scan-step and serve programs on the reduced DeepSeek config."""
    from repro.configs import registry
    from repro.serve import engine as serve
    from repro.train import lm

    cfg = registry.get_config("deepseek_7b", smoke=True)
    params, opt_state, _axes = lm.abstract_train_state(_key_struct(), cfg)
    multi, opt = lm.make_scan_train_step(cfg)
    steps, bsz, seq = 4, 2, 16
    batches = {"tokens": _sds((steps, bsz, seq + 1), jnp.int32)}
    keys = jax.eval_shape(
        lambda k: jax.vmap(lambda i: jax.random.fold_in(k, i))(
            jnp.arange(steps)), _key_struct())
    out: Dict[str, Any] = {}

    jax.clear_caches()
    out["scan_steps"] = audit_fn(
        multi, params, opt_state, batches, keys).to_json()

    jax.clear_caches()
    out["donation__scan_steps"] = audit_donation(
        multi, (params, opt_state, batches, keys),
        donate_argnums=(0, 1)).to_json()

    max_seq = 32
    cache = jax.eval_shape(lambda: serve.init_cache(cfg, 1, max_seq))
    tok = _sds((1, 1), jnp.int32)

    def decode(p, t, c):
        return serve.serve_step(p, t, c, cfg)

    jax.clear_caches()
    out["serve_decode"] = audit_fn(decode, params, tok, cache).to_json()
    return out


def deepseek_smoke_serve_target() -> Dict[str, Any]:
    """Analog decode-hot-loop invariants (the continuous-batching inner
    step traced by itself, single replica):

    * ``serve_decode_analog`` — one batched ``serve_step`` over
      policy-converted params under ``SERVE_POLICY``: the per-layer scan
      must carry exactly ONE fused ``managed_read__decode`` launch per
      converted projection per iteration (7 sites in the DeepSeek block) +
      one for the unembed outside the scan, and ZERO collectives — a
      single-replica decode step never leaves the device.
    * ``donation__serve_decode`` — the carried cache is donated across
      steps (the scheduler jits with ``donate_argnums`` on the cache), so
      steady-state decode holds one live cache buffer, never two.
    """
    import dataclasses
    from repro.configs import registry
    from repro.kernels import ops
    from repro.models import transformer
    from repro.serve import engine as serve

    cfg = registry.get_config("deepseek_7b", smoke=True,
                              analog_policy=SERVE_POLICY)
    cfg = dataclasses.replace(cfg, param_dtype=jnp.float32)
    params = jax.eval_shape(
        lambda k: transformer.init_lm(k, cfg)[0], _key_struct())
    max_seq = 32
    cache = jax.eval_shape(lambda: serve.init_cache(cfg, 1, max_seq))
    tok = _sds((1, 1), jnp.int32)
    akey = _key_struct()
    out: Dict[str, Any] = {}

    def decode(p, t, c, k):
        return serve.serve_step(p, t, c, cfg, akey=k)

    jax.clear_caches()
    with ops.launch_label("decode"):
        out["serve_decode_analog"] = audit_fn(
            decode, params, tok, cache, akey).to_json()

    jax.clear_caches()
    out["donation__serve_decode"] = audit_donation(
        decode, (params, tok, cache, akey),
        donate_argnums=(2,)).to_json()
    return out


TARGETS: Dict[str, Callable[[], Dict[str, Any]]] = {
    "lenet": lenet_target,
    "lenet_tile_grid": lenet_tile_grid_target,
    "lstm_copy": lstm_copy_target,
    "deepseek_smoke": deepseek_smoke_target,
    "deepseek_smoke_serve": deepseek_smoke_serve_target,
}
