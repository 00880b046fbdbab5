"""Production LM training driver.

Composes every substrate: mesh + logical sharding rules, deterministic
resumable data pipeline, scan-fused multi-step dispatch (``--engine scan``,
default — up to ``--scan-chunk`` train steps per XLA dispatch with donated
carries; ``--engine python`` keeps the legacy one-dispatch-per-step loop as
the oracle), digital AdamW or per-layer analog training
(``--analog-policy '*attn*=managed,*mlp*=rpu_baseline'`` — first-match-wins
rules over layer paths, presets with per-rule knob modifiers like
``managed:bm_mode=two_phase:tile_grid=2x2``; bare ``--analog`` keeps the
historical uniform-managed behaviour; either way the resolved per-layer
table prints at startup — see docs/architecture.md "Analog API" and
docs/scaling.md for tile-grid sharding), async sharded checkpointing,
straggler watchdog,
preemption-safe shutdown, restart-with-retry, optional gradient compression
for the DP all-reduce.

  PYTHONPATH=src python -m repro.launch.train --arch deepseek_7b \
      --smoke --steps 20 --batch 8 --seq 128 --ckpt-dir /tmp/ckpt

On a real TPU pod the same entry point runs the full config on the
production mesh (remove --smoke; device count comes from the runtime).
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.checkpoint import store
from repro.configs import registry
from repro.data.tokens import SyntheticTokenSource, TokenPipelineConfig
from repro.distributed import elastic
from repro.distributed import fault as fault_lib
from repro.distributed import sharding as shd
from repro.distributed.fault import (DeviceLossError, FaultInjector,
                                     PreemptionHandler, StragglerWatchdog)
from repro.train import engine as engine_lib
from repro.train import lm
from repro.utils.compile_cache import use_compile_cache


def build_mesh_and_rules(smoke: bool, multi_pod: bool):
    n = elastic.n_healthy()
    if smoke or n < 4:
        return None, None
    from repro.launch.mesh import make_production_mesh
    mesh = make_production_mesh(multi_pod=multi_pod)
    return mesh, shd.tp_fsdp_rules(multi_pod)


def _build_batch(cfg, toks, seq):
    """Assemble the train-step batch dict; ``toks`` is (B, S) or, for a
    scanned chunk, (chunk, B, S) — extra streams follow the leading axes."""
    lead = toks.shape[:-1]
    batch_d = {"tokens": toks}
    if cfg.family == "vlm":
        batch_d["frontend_embeds"] = jnp.zeros(
            (*lead, cfg.frontend_tokens, cfg.d_model), cfg.act_dtype)
    if cfg.family == "audio":
        batch_d["enc_embeds"] = jnp.zeros(
            (*lead, max(seq // 2, 8), cfg.d_model), cfg.act_dtype)
    return batch_d


def _parse_tile_mesh(tile_mesh: Optional[str]):
    if not tile_mesh:
        return None
    try:
        gr, gc = (int(v) for v in tile_mesh.split(","))
    except ValueError:
        raise ValueError(
            f"--tile-mesh expects 'R,C' (two comma-separated "
            f"integers), got {tile_mesh!r}") from None
    from repro.core import tile_grid
    from repro.core.device import RPUConfig
    placed = tile_grid.grid_is_sharded(RPUConfig(tile_grid=(gr, gc)))
    print(f"[train] tile grid {gr}x{gc}: "
          + (f"sharded over crossbar_mesh({gr},{gc})" if placed else
             f"serial oracle ({jax.device_count()} device(s) "
             f"< {gr * gc} sub-tiles)"))
    return gr, gc


def _build_analog_policy(analog_policy: str, bm_mode: str,
                         use_pallas: bool, tile_mesh: Optional[str],
                         update_chunk: Optional[int],
                         fuse_bwd_update: bool = False):
    """Resolve the per-layer policy for ``--analog-policy``.

    The spec takes a preset name (with optional ``:field=value``
    modifiers), inline ``pattern=preset`` rules, or a JSON rules file
    (``repro.analog.presets.parse_policy``).  The deprecated global knobs
    (--bm-mode/--use-pallas/--tile-mesh/--update-chunk) are applied to
    every rule, but only the knobs that were *explicitly set* — a default
    --bm-mode never clobbers a per-rule ``:bm_mode=...`` modifier.
    """
    import dataclasses
    from repro.analog import presets

    pol = presets.parse_policy(analog_policy)
    grid = _parse_tile_mesh(tile_mesh)
    if update_chunk:
        print(f"[train] streaming update cycle: chunk={update_chunk} "
              "(bit-identical, constant pulse-stream memory)")

    def override(c):
        if bm_mode != "iterative":
            c = dataclasses.replace(c, bm_mode=bm_mode)
        if use_pallas:
            c = dataclasses.replace(c, use_pallas=True)
        if fuse_bwd_update:
            c = dataclasses.replace(c, fuse_bwd_update=True)
        if update_chunk:
            c = c.with_streaming(update_chunk=update_chunk)
        if grid:
            c = c.with_tile_grid(*grid)
        return c

    if (bm_mode != "iterative" or use_pallas or fuse_bwd_update
            or update_chunk or grid):
        pol = pol.map_configs(override)
    return pol


def _print_policy_table(params) -> None:
    """Resolved per-layer policy table (satisfies 'no silent single-bool')."""
    from repro.analog.convert import conversion_plan
    from repro.analog.presets import describe_cfg
    rows = conversion_plan(params)
    print("[train] resolved analog policy (layer -> rule -> knobs):")
    for path, label, c in rows:
        print(f"  {path:<34} {label:<28} {describe_cfg(c)}")


def _policy_tile_grids(cfg):
    """Distinct tile grids any analog rule of ``cfg`` could route through."""
    grids = set()
    pol = getattr(cfg, "analog_policy", None)
    if pol is not None:
        for rule in pol.rules:
            if rule.cfg is not None and rule.cfg.tile_grid is not None:
                grids.add(rule.cfg.tile_grid)
    c = getattr(cfg, "analog", None)
    if c is not None and c.tile_grid is not None:
        grids.add(c.tile_grid)
    return sorted(grids)


def _reject_mesh_grid_conflict(cfg, mesh) -> None:
    """The production (data, model) LM mesh spans every healthy device; an
    analog rule whose tile grid could also place its crossbar mesh would
    nest a second shard_map over the same devices.  Delegates to the
    composition rules in ``sharding.MeshPlan.validate`` (data x
    sharded-tile); grids the pool cannot hold compose fine through the
    serial oracle."""
    if mesh is None:
        return
    n = elastic.n_healthy()
    errors = []
    for grid in _policy_tile_grids(cfg):
        try:
            shd.MeshPlan(data=max(n, 1), tile=grid).validate(n)
        except ValueError as e:
            errors.append(str(e))
    if errors:
        raise ValueError(
            "the production mesh cannot compose with sharded crossbar tile "
            "grids:\n  " + "\n  ".join(errors))


def train_sequence(kind: str, *, steps: int, batch: int, seq: int,
                   smoke: bool, analog: bool = False,
                   analog_policy: Optional[str] = None, lr: float = 0.01,
                   bm_mode: str = "iterative", use_pallas: bool = False,
                   fuse_bwd_update: bool = False, time_chunk: int = 1,
                   seed: int = 0, log_every: int = 1):
    """Analog recurrent trainer: LSTM/GRU on the delayed-copy task.

    ``--steps`` counts *epochs* over a fixed synthetic split (the copy
    task is tiny); each epoch is one scan-over-steps dispatch whose every
    step runs the cell's scan-over-time — temporal weight reuse on the
    same tiles every timestep, one accumulated pulse update per sequence
    batch (1806.00166's setting on this codebase's RPU substrate).
    """
    import dataclasses
    from repro.analog import presets
    from repro.analog.convert import convert_to_analog
    from repro.analog.policy import AnalogPolicy, AnalogRule
    from repro.core.device import rpu_nm_bm
    from repro.data import sequences
    from repro.optim import optimizers
    from repro.recurrent import model as seq_model

    seq_len = 4 if smoke else max(2, min(seq, 16))
    scfg = seq_model.SeqConfig(kind=kind, seq_len=seq_len, lr=lr,
                               hidden=16 if smoke else 32,
                               time_chunk=time_chunk)
    n_train = batch * (2 if smoke else 25)
    n_eval = max(batch, 64)
    tokens, targets = sequences.copy_task(
        n_train, seq_len=scfg.seq_len, delay=scfg.delay,
        vocab=scfg.vocab, seed=seed)
    ev_tok, ev_tgt = sequences.copy_task(
        n_eval, seq_len=scfg.seq_len, delay=scfg.delay,
        vocab=scfg.vocab, seed=seed + 1)

    params, axes = seq_model.init(jax.random.key(seed), scfg)
    if analog_policy:
        pol = presets.parse_policy(analog_policy)
        analog = True
    elif analog:
        # recurrent default: NM+BM without UM — update management needs
        # global error extrema, which a streamed temporal accumulation
        # never materializes (the cell rejects UM configs loudly)
        rpu = dataclasses.replace(rpu_nm_bm(), bm_mode=bm_mode,
                                  use_pallas=use_pallas,
                                  fuse_bwd_update=fuse_bwd_update)
        pol = AnalogPolicy(rules=(AnalogRule("*", rpu, "nm_bm"),))
    if analog:
        params, _ = convert_to_analog(params, axes, pol,
                                      key=jax.random.key(seed))
        opt = optimizers.mixed_analog(optimizers.sgd(lr))
    else:
        opt = optimizers.sgd(lr)
    opt_state = opt.init(params)

    run_epoch = engine_lib.make_seq_epoch_fn(scfg, opt, batch=batch)
    evaluate = engine_lib.make_seq_eval_fn(scfg, batch=max(batch, 64))
    key_base = jax.random.key(seed + 1)
    k_data, k_train, k_eval = jax.random.split(key_base, 3)

    tokens, targets = jnp.asarray(tokens), jnp.asarray(targets)
    ev_tok, ev_tgt = jnp.asarray(ev_tok), jnp.asarray(ev_tgt)
    accs = []
    for epoch in range(steps):
        params, opt_state = run_epoch(params, opt_state, tokens, targets,
                                      k_data, k_train,
                                      jnp.asarray(epoch))
        acc = float(evaluate(params, ev_tok, ev_tgt,
                             jax.random.fold_in(k_eval, epoch)))
        accs.append(acc)
        if epoch % log_every == 0 or epoch == steps - 1:
            print(f"[train {kind}] epoch {epoch} copy-task accuracy "
                  f"{acc:.3f}", flush=True)
    return {"losses": [1.0 - a for a in accs],
            "final_loss": 1.0 - accs[-1] if accs else None,
            "accuracies": accs}


def train(arch: str, *, steps: int, batch: int, seq: int, smoke: bool,
          analog: bool = False, analog_policy: Optional[str] = None,
          ckpt_dir: Optional[str] = None,
          ckpt_every: int = 50, multi_pod: bool = False,
          lr: float = 3e-4, log_every: int = 1, seed: int = 0,
          engine: str = "scan", scan_chunk: int = 10,
          bm_mode: str = "iterative", use_pallas: bool = False,
          fuse_bwd_update: bool = False,
          tile_mesh: Optional[str] = None,
          update_chunk: Optional[int] = None,
          time_chunk: int = 1,
          max_restarts: int = 0):
    import dataclasses
    if arch in ("lstm", "gru"):
        return train_sequence(
            arch, steps=steps, batch=batch, seq=seq, smoke=smoke,
            analog=analog, analog_policy=analog_policy, lr=lr,
            bm_mode=bm_mode, use_pallas=use_pallas,
            fuse_bwd_update=fuse_bwd_update, time_chunk=time_chunk,
            seed=seed, log_every=log_every)
    cfg = registry.get_config(arch, smoke=smoke)
    if fuse_bwd_update and not use_pallas and not analog_policy:
        raise ValueError("--fuse-bwd-update requires --use-pallas (the "
                         "fused backward+update cycle is a Pallas launch)")
    if analog_policy:
        pol = _build_analog_policy(analog_policy, bm_mode, use_pallas,
                                   tile_mesh, update_chunk,
                                   fuse_bwd_update=fuse_bwd_update)
        cfg = dataclasses.replace(cfg, analog_policy=pol,
                                  param_dtype=jnp.float32)
        analog = True
    elif analog:
        # bare --analog: the exact historical semantics — the uniform
        # 'managed' config on the block projections (ModelConfig.analog
        # legacy scope: never unembed/adapter) trained with pure
        # analog_sgd — but now with the resolved table printed at startup.
        from repro.core.device import rpu_nm_bm_um_bl1
        rpu = dataclasses.replace(rpu_nm_bm_um_bl1(), bm_mode=bm_mode,
                                  use_pallas=use_pallas,
                                  fuse_bwd_update=fuse_bwd_update)
        if update_chunk:
            rpu = rpu.with_streaming(update_chunk=update_chunk)
            print(f"[train] streaming update cycle: chunk={update_chunk} "
                  "(bit-identical, constant pulse-stream memory)")
        grid = _parse_tile_mesh(tile_mesh)
        if grid:
            rpu = rpu.with_tile_grid(*grid)
        cfg = dataclasses.replace(cfg, analog=rpu,
                                  param_dtype=jnp.float32)
    elif tile_mesh:
        raise ValueError("--tile-mesh requires --analog (it shards the "
                         "analog crossbar tiles, not fp weights)")
    elif update_chunk:
        raise ValueError("--update-chunk requires --analog (it chunks the "
                         "pulse-stream update cycle)")

    pipeline = SyntheticTokenSource(TokenPipelineConfig(
        vocab=cfg.vocab, seq_len=seq, global_batch=batch, seed=seed))

    opt = lm.default_optimizer(cfg, lr)
    watchdog = StragglerWatchdog()
    preempt = PreemptionHandler().install()
    injector = FaultInjector.from_env()
    key_base = jax.random.key(seed + 1)

    # Per-step losses survive restarts: a step re-run after rolling back to
    # the latest checkpoint just overwrites its own slot.
    losses_by_step = {}
    printed_policy = []

    def make_state():
        """(Re)build everything placement-dependent — called per attempt.

        Fresh closures mean fresh jit caches, so after ``elastic.mark_lost``
        the serial-vs-sharded tile-grid dispatch and the mesh placement
        re-resolve against the *current* healthy pool at trace time; the
        newest complete checkpoint (if any) is restored and re-placed."""
        mesh, rules = build_mesh_and_rules(smoke, multi_pod)
        _reject_mesh_grid_conflict(cfg, mesh)
        if engine == "scan":
            fn, _ = lm.make_scan_train_step(cfg, opt)
        else:
            fn, _ = lm.make_train_step(cfg, opt)
        step_fn = jax.jit(fn, donate_argnums=(0, 1))

        ctx = shd.use_sharding(mesh, rules) if mesh is not None else _null()
        with ctx:
            params, opt_state, axes = lm.init_train_state(
                jax.random.key(seed), cfg, opt)
            start = 0
            if ckpt_dir:
                latest = store.latest_step(ckpt_dir)
                if latest is not None:
                    shardings = (shd.tree_shardings(axes, mesh, rules,
                                                    like=params)
                                 if mesh is not None else None)
                    (params, opt_state), meta = store.restore(
                        ckpt_dir, latest, (params, opt_state),
                        shardings=(shardings, None) if shardings else None)
                    start = latest
                    print(f"[train] restored step {latest}")
            if analog:
                from repro.analog.convert import reshard_analog
                params = reshard_analog(params)
                if not printed_policy:
                    _print_policy_table(params)
                    printed_policy.append(True)
        return {"mesh": mesh, "rules": rules, "step_fn": step_fn,
                "params": params, "opt_state": opt_state, "start": start,
                "ckpt": store.AsyncCheckpointer(ckpt_dir)
                if ckpt_dir else None}

    def run(state):
        mesh, rules = state["mesh"], state["rules"]
        step_fn, ckpt = state["step_fn"], state["ckpt"]
        params, opt_state = state["params"], state["opt_state"]
        ctx = shd.use_sharding(mesh, rules) if mesh is not None else _null()
        with ctx:
            step = state["start"]
            while step < steps:
                t0 = time.time()
                if engine == "scan":
                    # Scanned chunk: one dispatch for up to ``scan_chunk``
                    # steps, clipped so checkpoints land exactly on the
                    # ``ckpt_every`` cadence and injected faults fire at
                    # their exact step boundary.
                    chunk = min(scan_chunk, steps - step)
                    if ckpt and ckpt_every > 0:
                        chunk = min(chunk, ckpt_every - (step % ckpt_every))
                    if injector and step < injector.fault_step:
                        chunk = min(chunk, injector.fault_step - step)
                    toks = jnp.asarray(np.stack(
                        [pipeline.batch_at(i)
                         for i in range(step, step + chunk)]))
                    batch_d = _build_batch(cfg, toks, seq)
                    keys = engine_lib.fold_in_keys(
                        key_base, jnp.arange(step, step + chunk))
                    params, opt_state, metrics = step_fn(
                        params, opt_state, batch_d, keys)
                    chunk_losses = np.asarray(metrics["loss"]).tolist()
                else:
                    chunk = 1
                    toks = jnp.asarray(pipeline.batch_at(step))
                    batch_d = _build_batch(cfg, toks, seq)
                    key = jax.random.fold_in(key_base, step)
                    params, opt_state, metrics = step_fn(params, opt_state,
                                                         batch_d, key)
                    chunk_losses = [float(metrics["loss"])]
                for i, v in enumerate(chunk_losses):
                    losses_by_step[step + i] = v
                loss = chunk_losses[-1]
                step += chunk
                rep = watchdog.observe(step - 1, (time.time() - t0) / chunk)
                if (step - chunk) % log_every == 0 or chunk > 1:
                    flag = " STRAGGLER" if rep.is_straggler else ""
                    print(f"[train {arch}] step {step - 1} loss {loss:.4f} "
                          f"({rep.step_time * 1e3:.0f} ms/step){flag}",
                          flush=True)
                if ckpt and (step % ckpt_every == 0
                             or preempt.preemption_requested()
                             or step == steps):
                    ckpt.save(step, (params, opt_state),
                              {"arch": arch, "loss": loss})
                    if injector:
                        injector.check(step, saving=True)
                if injector:
                    injector.check(step, flush=ckpt)
                if preempt.preemption_requested():
                    print("[train] preemption requested -> checkpointed, "
                          "exiting")
                    break
            if ckpt:
                ckpt.wait()

    def on_restart(attempt, exc):
        if isinstance(exc, DeviceLossError):
            n = elastic.mark_lost(exc.n_lost)
            print(f"[train] lost {exc.n_lost} device(s), {n} healthy -> "
                  f"elastic restart {attempt}/{max_restarts}", flush=True)
            for grid in _policy_tile_grids(cfg):
                gp = elastic.grid_plan(n, grid)
                print(f"[train] tile grid {grid[0]}x{grid[1]} -> "
                      + ("sharded" if gp.sharded else "serial oracle"),
                      flush=True)
        else:
            print(f"[train] restart {attempt}/{max_restarts} after "
                  f"{type(exc).__name__}: {exc}", flush=True)
        # the surviving pool has a different steady-state step time; don't
        # judge it against the pre-failure EWMA
        watchdog.reset()

    fault_lib.run_with_restarts(make_state, run, max_restarts=max_restarts,
                                on_restart=on_restart)
    losses = [losses_by_step[i] for i in sorted(losses_by_step)]
    return {"losses": losses, "final_loss": losses[-1] if losses else None}


class _null:
    def __enter__(self):
        return self

    def __exit__(self, *a):
        return False


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--analog", action="store_true",
                    help="train projections on analog RPU tiles; without "
                         "--analog-policy this keeps the historical "
                         "semantics (managed preset on the block "
                         "projections, pure analog pulse-SGD)")
    ap.add_argument("--analog-policy", type=str, default=None,
                    metavar="SPEC",
                    help="per-layer analog policy (implies --analog): a "
                         "preset name ('managed', 'rpu_baseline', ...), "
                         "inline first-match-wins rules like "
                         "'*attn*=managed,*mlp*=rpu_baseline' (unmatched "
                         "layers stay digital; presets take "
                         "':field=value' modifiers, e.g. "
                         "'managed:bm_mode=two_phase:tile_grid=2x2'), or "
                         "a JSON rules file — see repro.analog.presets")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--ckpt-dir", type=str, default=None)
    ap.add_argument("--ckpt-every", type=int, default=50)
    ap.add_argument("--max-restarts", type=int, default=0,
                    help="restart-with-retry budget: on a failure (e.g. a "
                         "simulated device loss) rebuild the step functions "
                         "on the surviving healthy pool, restore the newest "
                         "complete checkpoint and continue, up to this many "
                         "times (see docs/scaling.md, fault tolerance)")
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--engine", choices=("scan", "python"), default="scan",
                    help="scan: fused multi-step dispatch; python: legacy "
                         "per-step loop (correctness oracle)")
    ap.add_argument("--scan-chunk", type=int, default=10,
                    help="steps fused per dispatch with --engine scan")
    ap.add_argument("--bm-mode", choices=("iterative", "two_phase"),
                    default="iterative",
                    help="[deprecated: use a ':bm_mode=...' rule modifier "
                         "in --analog-policy] global bound-management mode "
                         "for --analog: the paper's halve-and-retry loop, "
                         "or the fixed-latency two-phase retry (fusable "
                         "into one managed-read launch with --use-pallas)")
    ap.add_argument("--use-pallas", action="store_true",
                    help="[deprecated: use ':use_pallas=true' rule "
                         "modifiers in --analog-policy] route analog "
                         "reads/updates through the Pallas kernels (fused "
                         "managed read for two_phase/off BM)")
    ap.add_argument("--fuse-bwd-update", action="store_true",
                    help="[or ':fuse_bwd_update=true' rule modifiers in "
                         "--analog-policy] fuse each analog layer's "
                         "backward transpose read and stochastic-pulse "
                         "update into ONE Pallas launch (requires "
                         "--use-pallas + fast_rng and a fixed-latency BM "
                         "mode; bit-identical to the separate-launch "
                         "cycles, which remain the oracle)")
    ap.add_argument("--tile-mesh", type=str, default=None, metavar="R,C",
                    help="[deprecated: use ':tile_grid=RxC' rule "
                         "modifiers in --analog-policy] "
                         "with --analog: decompose every analog tile into an "
                         "RxC sub-tile grid on the 'array_row' x 'array_col' "
                         "crossbar device mesh (serial oracle when fewer "
                         "than R*C devices; see docs/scaling.md)")
    ap.add_argument("--time-chunk", type=int, default=1,
                    help="with --arch lstm|gru: timesteps per backward "
                         "accumulation chunk (must divide the unrolled "
                         "length; counts are bit-identical for any value "
                         "via counter-offset pulse streams)")
    ap.add_argument("--update-chunk", type=int, default=None,
                    help="[deprecated: use ':update_chunk=N' rule "
                         "modifiers in --analog-policy] "
                         "with --analog: stream the update cycle's pulse "
                         "streams in chunks of this many (sample) vector "
                         "pairs — bit-identical to the materialized cycle, "
                         "caps the ~BL x activation stream memory "
                         "(docs/architecture.md, streaming pipeline)")
    args = ap.parse_args()
    res = train(args.arch, steps=args.steps, batch=args.batch, seq=args.seq,
                smoke=args.smoke, analog=args.analog,
                analog_policy=args.analog_policy,
                ckpt_dir=args.ckpt_dir, ckpt_every=args.ckpt_every,
                multi_pod=args.multi_pod, lr=args.lr, engine=args.engine,
                scan_chunk=args.scan_chunk, bm_mode=args.bm_mode,
                use_pallas=args.use_pallas,
                fuse_bwd_update=args.fuse_bwd_update,
                tile_mesh=args.tile_mesh,
                update_chunk=args.update_chunk,
                time_chunk=args.time_chunk,
                max_restarts=args.max_restarts)
    print(f"[train] done; final loss {res['final_loss']:.4f}")


if __name__ == "__main__":
    main()
