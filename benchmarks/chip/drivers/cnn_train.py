"""Driver: CNN training on analog tiles through the scan-fused epoch engine.

The timed object is the program's own epoch program
(``repro.train.engine.make_cnn_epoch_fn``, as ``repro.train.cnn.train``
builds it) with its donated (params, opt_state) carry.  The training set
lives on the device as equal chunks of ``rows_per_call`` images; call ``c``
runs one epoch of the program over chunk ``c mod chunks`` with epoch index
``c`` (so every call draws fresh permutations and step keys), and each call
ends in ``block_until_ready``.

Set-up makes the data (host) and the tiles (device, one jitted call), then
drives calls 0..2, the first of which compiles, keeping the weights after
calls 0 and 2.  The window runs calls 3, 4, ... until ``seconds`` have
passed.  After the window the plain reference follows calls 0..2 from the
same tiles, data and keys, and two numbers are compared, each the worst
leaf of ``|norm(program change) - norm(reference change)|`` over
``max(norm(reference change), median leaf)``: the change after call 0 and
the change after call 2.  With ``run.control`` the reference computed in
bfloat16 takes the program's place in that comparison.
"""

from __future__ import annotations

import gc
import time

import numpy as np

from benchlib import common as C
from benchlib import digits

LAYERS = ("K1", "K2", "W3", "W4")
LEAVES = ("w", "dw_up", "dw_dn", "bound")
N_REF_CALLS = 3


def inputs(cell, seed: int, n_chunks: int):
    """The run's data chunks (made on the host, put on the device) and its
    tile, data-order and step keys, all from ``seed``."""
    import jax
    rows = int(cell.traffic["rows_per_call"])
    images, labels = digits.make(C.host_rng(seed, 1), rows * n_chunks)
    chunks = [(jax.device_put(images[c * rows:(c + 1) * rows]),
               jax.device_put(labels[c * rows:(c + 1) * rows]))
              for c in range(n_chunks)]
    k_data, k_train = jax.random.split(C.seed_key(seed, 3))
    return chunks, C.seed_key(seed, 2), k_data, k_train


def _program(run):
    import jax
    from repro.analog.presets import parse_policy
    from repro.models import lenet
    from repro.optim import analog_sgd
    from repro.train import engine

    tr = run.cell.traffic
    cfg = lenet.LeNetConfig.from_policy(parse_policy(tr["policy"]),
                                        lr=run.cell.config["lr"])
    opt = analog_sgd()
    run_epoch = engine.make_cnn_epoch_fn(cfg, opt, batch=int(tr["batch"]))
    shapes = jax.eval_shape(lambda k: lenet.init(k, cfg), jax.random.key(0))
    return cfg, opt, run_epoch, shapes


def _to_program(state, shapes, seed_key):
    """The benchmark's tiles in the program's parameter tree."""
    import jax
    from repro.analog.modules import AnalogState
    from repro.core.device import DeviceMaps
    out = {}
    for i, n in enumerate(LAYERS):
        t = state[n]
        out[n] = AnalogState(t["w"], DeviceMaps(t["dw_up"], t["dw_dn"],
                                                t["bound"]),
                             jax.random.fold_in(seed_key, i), shapes[n].meta)
    return out


def _host_leaves(params):
    return {n: {leaf: np.asarray(getattr(params[n], "w") if leaf == "w"
                                 else getattr(params[n].maps, leaf))
                for leaf in LEAVES} for n in LAYERS}


def norm_gap(p0, prog, ref) -> float:
    """Worst leaf of |norm(prog - p0) - norm(ref - p0)| over
    max(norm(ref - p0), median leaf norm).  Leaves the reference leaves
    (all but) unmoved — under a thousandth of the median moving leaf —
    are left out."""
    keys = [(n, leaf) for n in p0 for leaf in p0[n]]
    rn = {k: float(np.linalg.norm(ref[k[0]][k[1]] - p0[k[0]][k[1]]))
          for k in keys}
    pn = {k: float(np.linalg.norm(prog[k[0]][k[1]] - p0[k[0]][k[1]]))
          for k in keys}
    moving = [v for v in rn.values() if v > 0]
    if not moving:
        return float("inf")
    med = float(np.median(moving))
    kept = [k for k in keys if rn[k] >= 1e-3 * med]
    return max(abs(pn[k] - rn[k]) / max(rn[k], med) for k in kept)


def diff_gap(p0, prog, ref) -> float:
    """Worst leaf of norm(prog - ref) over max(norm(ref - p0), median
    leaf): how far the two trajectories have parted (a diagnostic)."""
    keys = [(n, leaf) for n in p0 for leaf in p0[n]]
    rn = {k: float(np.linalg.norm(ref[k[0]][k[1]] - p0[k[0]][k[1]]))
          for k in keys}
    moving = [v for v in rn.values() if v > 0]
    med = float(np.median(moving)) if moving else 1.0
    return max(float(np.linalg.norm(prog[k[0]][k[1]] - ref[k[0]][k[1]]))
               / max(rn[k], med) for k in keys if rn[k] >= 1e-3 * med)


def run(run):
    import jax

    tr, conf = run.cell.traffic, run.cell.config
    ref = C.load_module(C.bench_file(conf["reference"]))
    batch, rows = int(tr["batch"]), int(tr["rows_per_call"])
    n_chunks = int(tr["chunks"])

    # --- set-up: data (host), tiles (device), the program ---------------
    chunks, k_tiles, k_data, k_train = inputs(run.cell, run.seed, n_chunks)
    cfg, opt, run_epoch, shapes = _program(run)
    state = jax.jit(lambda k: ref.make_state(k, conf, tr["layers"]))(k_tiles)
    params = _to_program(state, shapes, C.seed_key(run.seed, 4))
    del state
    opt_state = opt.init(params)

    if run.fault == "unchanged":           # a step that returns its state
        def call(p, s, xs, ys, c):
            return p, s
    elif run.fault == "half_batch":        # half of each batch left out
        from repro.train import engine
        half = engine.make_cnn_epoch_fn(cfg, opt, batch=batch // 2)

        def call(p, s, xs, ys, c):
            spe = xs.shape[0] // batch
            idx = (np.arange(spe)[:, None] * batch
                   + np.arange(batch // 2)[None]).reshape(-1)
            return half(p, s, xs[idx], ys[idx], k_data, k_train,
                        np.int32(c))
    else:
        def call(p, s, xs, ys, c):
            return run_epoch(p, s, xs, ys, k_data, k_train, np.int32(c))

    p0 = _host_leaves(params)
    kept = {}
    for c in range(N_REF_CALLS):
        xs, ys = chunks[c % n_chunks]
        params, opt_state = call(params, opt_state, xs, ys, c)
        jax.block_until_ready(params)
        if c in (0, N_REF_CALLS - 1):
            kept[c] = _host_leaves(params)
    run.setup_done()

    # --- window ----------------------------------------------------------
    trace_s = float(tr.get("trace_seconds", 2.0))
    spc = rows // batch                    # steps per call
    calls, c = 0, N_REF_CALLS
    traced = None                          # (calls traced, resumed at)
    run.compiles.active = True
    t0 = time.perf_counter()
    run.start_trace()
    while True:
        xs, ys = chunks[c % n_chunks]
        with run.spans.span("epoch_call"):
            params, opt_state = call(params, opt_state, xs, ys, c)
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
    steps = calls * spc
    run.attempted, run.failed = steps * batch, 0
    run.e2e["train_samples_per_s"] = steps * batch / window
    # a traced run's rate is read after the trace stops (tracing slows it)
    rate = steps * batch / window
    if traced is not None and calls > traced[0] and t1 > traced[1]:
        rate = (calls - traced[0]) * spc * batch / (t1 - traced[1])
    n_traced = (traced[0] if traced else calls) * spc
    run.readings.update({
        "window_s": window, "steps": steps, "batch": batch,
        "compiles_in_window": run.compiles.count,
        "samples_per_s": rate,
        "model_flops_per_sample": model_flops_per_sample(conf),
        "traced_launches": {
            kind: [[n_traced, l] for l in ls]
            for kind, ls in launches_per_step(conf, tr, batch).items()},
    })
    run.log(f"window {window:.3f} s: {calls} calls, {steps} steps, "
            f"{run.compiles.count} compiles in the window")
    run.readings["device"] = C.device_info(run.cell.chips)

    # --- check: the reference follows calls 0..2 -----------------------
    del params, opt_state
    gc.collect()
    state = jax.jit(lambda k: ref.make_state(k, conf, tr["layers"]))(k_tiles)
    with jax.default_matmul_precision("highest"):
        w_ref, _ = ref.train_calls(state, chunks, k_data, k_train, 0,
                                   N_REF_CALLS, batch, conf, tr["layers"])
    ref_leaves = [{n: dict(p0[n], w=w[n]) for n in LAYERS} for w in w_ref]
    if run.control:                        # the control in the program's place
        import jax.numpy as jnp
        run.readings["program"] = {
            "first_call_change_gap": norm_gap(p0, kept[0], ref_leaves[0]),
            "three_call_change_gap": norm_gap(
                p0, kept[N_REF_CALLS - 1], ref_leaves[-1])}
        with jax.default_matmul_precision("highest"):
            w_ctl, _ = ref.train_calls(state, chunks, k_data, k_train, 0,
                                       N_REF_CALLS, batch, conf,
                                       tr["layers"], dtype=jnp.bfloat16)
        kept = {c: {n: dict(p0[n], w=w[n]) for n in LAYERS}
                for c, w in ((0, w_ctl[0]), (N_REF_CALLS - 1, w_ctl[-1]))}
    run.readings["diff"] = {
        "first_diff": diff_gap(p0, kept[0], ref_leaves[0]),
        "three_call_diff": diff_gap(p0, kept[N_REF_CALLS - 1],
                                    ref_leaves[-1])}
    run.log(f"trajectory parting (diagnostic): {run.readings['diff']}")
    for i, c in ((0, 0), (-1, N_REF_CALLS - 1)):
        run.log(f"call {c} change norms, timed path / reference: " + ", ".join(
            f"{n}.w {np.linalg.norm(kept[c][n]['w'] - p0[n]['w']):.5g} / "
            f"{np.linalg.norm(ref_leaves[i][n]['w'] - p0[n]['w']):.5g}"
            for n in LAYERS))
    run.check("first_call_change_gap",
              norm_gap(p0, kept[0], ref_leaves[0]),
              tr["limits"]["first_call_change_gap"])
    run.check("three_call_change_gap",
              norm_gap(p0, kept[N_REF_CALLS - 1],
                       ref_leaves[N_REF_CALLS - 1]),
              tr["limits"]["three_call_change_gap"])


def model_flops_per_sample(conf) -> float:
    """6 x the multiply-adds of one sample through the four tiles (forward,
    backward and update each one MAC per weight per column)."""
    macs = 0
    for n, (rows, cols) in conf["tiles"].items():
        macs += rows * cols * int(conf["positions"][n])
    return 6.0 * macs


def launches_per_step(conf, tr, batch):
    """Logical shapes of each kernel kind's launches in one train step."""
    out = {}
    for n, (rows, cols) in conf["tiles"].items():
        d = int(tr["layers"][n]["devices"])
        pos = int(conf["positions"][n]) * batch
        for kind, each in tr["launches"].get(n, {}).items():
            for dims in (each if isinstance(each, list) else [each]):
                k, m = (d * rows, cols) if dims.get("transpose") else \
                    (cols, d * rows)
                out.setdefault(kind, []).append(dict(
                    dims, kind=kind, rows=pos, k=k, out=m, layer=n))
    return out
