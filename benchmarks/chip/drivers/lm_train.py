"""Driver: LM training with its block projections on analog tiles, through
the scan engine.

The timed object is the program's own scanned train step
(``repro.train.lm.make_scan_train_step``, as ``launch/train.py --engine
scan`` builds it), jitted with the donated (params, opt_state) carry, for
``registry.get_config(arch, analog_policy=...)`` with the configuration's
sizes.  Its initial state is drawn by the reference
(``lm_analog.make_state``, from the seed: weights, device-map seeds,
embedding, norms, head) and handed to the program in the tree
``lm.init_train_state`` would make.  Call ``c`` runs ``steps_per_call``
steps on fresh token ids (``batch`` sequences of ``seq + 1`` ids from the
vocabulary slice) with step ``g`` keyed ``fold_in(k_train, g)``, and ends
in ``block_until_ready``.  The program is traced and run at the
configuration's ``matmul_precision``.

Set-up makes the ids (host) and the state (device), then drives calls
0..2, the first of which compiles, keeping the tiles after calls 0 and 2
and the losses of call 0.  The window runs calls 3, 4, ... until
``seconds`` have passed; with ``--trace 1`` the first call(s) are traced
and reduced by program scope (projection x RPU cycle).  After the window
the program is freed and the plain reference follows calls 0..2 from the
same state, ids and keys, layer by layer.  The numbers compared: step 0's
loss (relative gap), and the gaps of ``cnn_train``, each the worst tile of
a number over ``max(norm(reference change), median tile)``:
``|norm(program change) - norm(reference change)|`` (``*_change_gap``) and
``norm(program - reference)`` (``*_diff_gap``), after calls 0 and 2.  With
``run.control`` the reference computed in bfloat16 takes the program's
place in that comparison.
"""

from __future__ import annotations

import contextlib
import dataclasses
import gc
import glob
import os
import time

import numpy as np

from benchlib import common as C

PROJ = ("q", "k", "v", "o", "wi", "wg", "wo")
N_REF_CALLS = 3
#: calls whose ids and keys are made in set-up (the window reuses them
#: cyclically past that)
POOL_CALLS = 32


def model_config(conf, policy):
    """The program's ModelConfig: the repository's architecture with the
    configuration file's sizes."""
    import jax.numpy as jnp
    from repro.configs import registry
    cfg = registry.get_config(conf["arch"], analog_policy=policy)
    return dataclasses.replace(
        cfg, n_layers=int(conf["num_hidden_layers"]),
        d_model=int(conf["hidden_size"]),
        n_heads=int(conf["num_attention_heads"]),
        n_kv_heads=int(conf["num_key_value_heads"]),
        d_ff=int(conf["intermediate_size"]), vocab=int(conf["vocab_size"]),
        norm_eps=float(conf["rms_norm_eps"]),
        rope_theta=float(conf["rope_theta"]),
        tie_embeddings=bool(conf["tie_word_embeddings"]),
        param_dtype=jnp.dtype(conf["param_dtype"]),
        act_dtype=jnp.dtype(conf["param_dtype"]))


def inputs(cell, seed: int):
    """Token ids of every call (host), and the keys of the initial state
    and of the steps."""
    tr, conf = cell.traffic, cell.config
    shape = (POOL_CALLS, int(tr["steps_per_call"]), int(tr["batch"]),
             int(tr["seq"]) + 1)
    tokens = C.host_rng(seed, 1).integers(0, int(conf["vocab_size"]), shape,
                                          dtype=np.int32)
    return tokens, C.seed_key(seed, 2), C.seed_key(seed, 3)


def step_keys(k_train, n_calls: int, steps: int):
    """(n_calls, steps) keys: step ``g`` of the run is ``fold_in(k_train,
    g)``."""
    import jax
    import jax.numpy as jnp
    g = jnp.arange(n_calls * steps).reshape(n_calls, steps)
    return jax.jit(jax.vmap(jax.vmap(
        lambda i: jax.random.fold_in(k_train, i))))(g)


def to_program(shape, st):
    """The program's parameter tree holding the reference's initial state
    ``st`` (device arrays, :func:`lm_analog.make_state`'s layout);
    ``shape`` is the tree's abstract form, which the result must match
    leaf for leaf."""
    import jax
    lay = shape["layers"]
    params = {"embed": {"table": st["embed"]},
              "final_norm": {"scale": st["final_norm"]},
              "unembed": {"w": st["unembed"]},
              "layers": {"ln_attn": {"scale": st["ln_attn"]},
                         "ln_ffn": {"scale": st["ln_ffn"]},
                         "attn": {}, "mlp": {}}}
    for n in PROJ:
        seed = jax.random.wrap_key_data(st["seeds"][n], impl="threefry2x32")
        params["layers"]["attn" if n in ("q", "k", "v", "o") else "mlp"][n] \
            = jax.tree_util.tree_unflatten(
                jax.tree_util.tree_structure(_tile(lay, n)),
                [st["tiles"][n], seed])
    want, got = (jax.tree_util.tree_flatten(t) for t in (shape, params))
    if want[1] != got[1] or [(a.shape, a.dtype) for a in want[0]] != [
            (a.shape, a.dtype) for a in got[0]]:
        raise ValueError("the reference's state does not fit the program's")
    return params


def _tile(layers, n):
    return layers["attn" if n in ("q", "k", "v", "o") else "mlp"][n]


def tile_leaves(params):
    """Host copies of every tile's weights, (layers, out, in) by
    projection."""
    return {n: np.asarray(_tile(params["layers"], n).w) for n in PROJ}


def _by_tile(tiles):
    """{projection: (L, out, in)} -> {projection: {layer: (out, in)}}, the
    leaves ``cnn_train``'s gaps compare."""
    return {n: {str(i): w[i] for i in range(w.shape[0])}
            for n, w in tiles.items()}


def _program(cfg, key):
    """The jitted scanned step, its optimiser and the params' abstract
    form."""
    import jax
    from repro.train import lm
    multi, opt = lm.make_scan_train_step(cfg)
    shape = lm.abstract_train_state(key, cfg, opt)[0]
    return jax.jit(multi, donate_argnums=(0, 1)), opt, shape


def _trace_scopes(run):
    """Device time by projection and cycle of the traced calls, read from
    the profiler's file before the harness reduces and removes it."""
    from benchlib import scopes
    files = glob.glob(os.path.join(run.trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return scopes.reduce_file(max(files, key=os.path.getmtime), PROJ,
                              run.cell.chips)


def run(run):
    import jax
    conf = run.cell.config
    prec = conf.get("matmul_precision")
    with (jax.default_matmul_precision(prec) if prec
          else contextlib.nullcontext()):
        _run(run)


def _run(run):
    import jax
    import jax.numpy as jnp

    tr, conf = run.cell.traffic, run.cell.config
    ref = C.load_module(C.bench_file(conf["reference"]))
    gaps = C.load_module(C.bench_file("drivers", "cnn_train.py"))
    batch, steps = int(tr["batch"]), int(tr["steps_per_call"])

    # --- set-up: ids (host), state (device), the program ----------------
    tokens, k_state, k_train = inputs(run.cell, run.seed)
    keys = step_keys(k_train, POOL_CALLS, steps)
    tokens_dev = jax.device_put(tokens)
    cfg = model_config(conf, tr["policy"])
    step, opt, shape = _program(cfg, k_state)
    st = ref.make_state(k_state, conf)
    state0 = jax.tree_util.tree_map(np.asarray, st)
    params = to_program(shape, st)
    del st
    opt_state = jax.jit(opt.init)(params)

    if run.fault == "unchanged":           # a step that returns its state
        def call(p, s, c):
            return p, s, {"loss": jnp.zeros((steps,), jnp.float32)}
    elif run.fault == "half_batch":        # half of each batch left out
        half = batch // 2

        def call(p, s, c):
            t = tokens_dev[c % POOL_CALLS]
            t = jnp.concatenate([t[:, :half], t[:, :batch - half]], axis=1)
            return step(p, s, {"tokens": t}, keys[c % POOL_CALLS])
    else:
        def call(p, s, c):
            return step(p, s, {"tokens": tokens_dev[c % POOL_CALLS]},
                        keys[c % POOL_CALLS])

    kept, losses0 = {}, None
    for c in range(N_REF_CALLS):
        params, opt_state, metrics = call(params, opt_state, c)
        jax.block_until_ready(params)
        if c == 0:
            losses0 = np.asarray(metrics["loss"])
        if c in (0, N_REF_CALLS - 1):
            kept[c] = tile_leaves(params)
    run.setup_done()
    run.log(f"set-up {run.e2e['setup_s']:.1f} s")

    # --- window ----------------------------------------------------------
    trace_s = float(tr.get("trace_seconds", 1.0))
    calls, c = 0, N_REF_CALLS
    traced = None                          # (calls traced, resumed at)
    run.compiles.active = True
    t0 = time.perf_counter()
    run.start_trace()
    while True:
        with run.spans.span("train_call"):
            params, opt_state, _m = call(params, opt_state, c)
            jax.block_until_ready(params)
        c += 1
        calls += 1
        now = time.perf_counter()
        if run.trace and traced is None and now - t0 >= trace_s:
            run.stop_trace()
            traced = (calls, time.perf_counter())
        if now - t0 >= run.seconds:
            break
    t1 = now
    run.stop_trace()
    run.compiles.active = False
    window = t1 - t0
    samples = calls * steps * batch
    run.attempted, run.failed = samples, 0
    run.e2e["train_samples_per_s"] = samples / window
    # a traced run's rate is read after the trace stops (tracing slows it)
    rate = samples / window
    if traced is not None and calls > traced[0] and t1 > traced[1]:
        rate = (calls - traced[0]) * steps * batch / (t1 - traced[1])
    n_traced = (traced[0] if traced else calls) * steps
    run.readings.update({
        "window_s": window, "steps": calls * steps, "batch": batch,
        "compiles_in_window": run.compiles.count,
        "samples_per_s": rate, "traced_steps": n_traced,
        "model_flops_per_sample": model_flops_per_sample(conf, tr),
        "traced_launches": {
            kind: [[n_traced, launch] for launch in ls]
            for kind, ls in launches_per_step(conf, tr).items()},
    })
    if run.trace:
        red = run.readings["scopes"] = _trace_scopes(run)
        if red:
            run.log("device us per step by scope: " + ", ".join(
                f"{k} {1e6 * v / n_traced:.1f}" for k, v in sorted(
                    red["scope_s"].items(), key=lambda kv: -kv[1])))
    run.log(f"window {window:.3f} s: {calls} calls, {calls * steps} steps, "
            f"{run.compiles.count} compiles in the window")
    run.readings["device"] = C.device_info(run.cell.chips)

    # --- check: the reference follows calls 0..2 -----------------------
    del params, opt_state, step, call, tokens_dev, metrics, _m
    gc.collect()
    ref_calls = [(tokens[c], np.asarray(jax.random.key_data(keys[c])))
                 for c in range(N_REF_CALLS)]
    keep = (0, N_REF_CALLS - 1)
    t_ref = time.perf_counter()
    ref_tiles, ref_losses = ref.train_calls(state0, ref_calls, conf,
                                            keep=keep)
    run.log(f"reference: {time.perf_counter() - t_ref:.1f} s")
    p0 = _by_tile(state0["tiles"])
    ref_t = {c: _by_tile(t) for c, t in zip(keep, ref_tiles)}
    prog_t = {c: _by_tile(kept[c]) for c in keep}
    loss_gap = abs(float(losses0[0]) - ref_losses[0]) / abs(ref_losses[0])

    def numbers(tiles, lgap):
        return {"first_step_loss_gap": lgap,
                "first_call_change_gap": gaps.norm_gap(p0, tiles[0],
                                                       ref_t[0]),
                "first_call_diff_gap": gaps.diff_gap(p0, tiles[0], ref_t[0]),
                "three_call_change_gap": gaps.norm_gap(
                    p0, tiles[keep[1]], ref_t[keep[1]]),
                "three_call_diff_gap": gaps.diff_gap(
                    p0, tiles[keep[1]], ref_t[keep[1]])}

    got = numbers(prog_t, loss_gap)
    run.log(f"step losses, timed path / reference: {losses0.tolist()} / "
            f"{ref_losses[:steps]}")
    if run.control:                        # the control in the program's place
        run.readings["program"] = got
        del prog_t, kept
        ctl_tiles, ctl_losses = ref.train_calls(state0, ref_calls, conf,
                                                keep=keep,
                                                dtype=jnp.bfloat16)
        got = numbers({c: _by_tile(t) for c, t in zip(keep, ctl_tiles)},
                      abs(ctl_losses[0] - ref_losses[0])
                      / abs(ref_losses[0]))
    for name, v in got.items():
        run.check(name, v, tr["limits"][name])


def layer_macs(conf) -> int:
    """Multiply-adds of one token through one layer's seven tiles."""
    d, f = int(conf["hidden_size"]), int(conf["intermediate_size"])
    return 4 * d * d + 3 * d * f


def model_flops_per_sample(conf, tr) -> float:
    """6 x the multiply-adds of one sequence: the tiles (forward, backward
    and update each one MAC per weight per token), attention (scores and
    values over all ``seq`` positions, as the program computes them) and
    the head: 6 x seq x (layers x (4 d^2 + 3 d f + 2 seq d) + d vocab).
    ``step_mfu.train`` reads it; for deepseek_7b_analog (d 4096, f 11008,
    4 layers, vocabulary 12800, seq 2048) 6 x 2048 x 929.0M = 11.42
    TFLOP."""
    s, d = int(tr["seq"]), int(conf["hidden_size"])
    n_layers = int(conf["num_hidden_layers"])
    per_token = (n_layers * (layer_macs(conf) + 2 * s * d)
                 + d * int(conf["vocab_size"]))
    return 6.0 * s * per_token


def launches_per_step(conf, tr):
    """Logical shapes of each kernel kind's launches in one train step:
    per layer and tile, the forward read twice (the layer is recomputed in
    the backward; ``wo``'s once, as nothing in the backward reads the
    layer's output), the transpose read once, and the update's counts."""
    d, f = int(conf["hidden_size"]), int(conf["intermediate_size"])
    rows = int(tr["batch"]) * int(tr["seq"])
    shapes = {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
              "wi": (f, d), "wg": (f, d), "wo": (d, f)}
    out = {"managed_read": [], "pulse_counts": []}
    for _layer in range(int(conf["num_hidden_layers"])):
        for n, (o, i) in shapes.items():
            fwd = dict(kind="managed_read", rows=rows, k=i, out=o, tile=n)
            out["managed_read"] += [fwd] * (1 if n == "wo" else 2) + [
                dict(fwd, k=o, out=i, transpose=True)]
            out["pulse_counts"].append(dict(kind="pulse_counts", rows=rows,
                                            k=i, out=o, bl=1, tile=n))
    return out
