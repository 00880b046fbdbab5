"""Chip smoke test: the main paths, end to end, through the normal entry
points, on a TPU.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chips  # four chips: the sharded tile grid

Phases (one process; every phase runs, and any failure fails the script):

(a) the paper's LeNet at its published tile sizes (K1 16x26, K2 32x401,
    W3 128x513, W4 10x129) under the audited fused policy, trained with
    ``repro.train.cnn.train`` (scan engine).  The compiled train step must
    hold exactly 8 Pallas kernels, one step is compared with the pure-jnp
    reference at highest matmul precision, and the trained net must beat
    chance.
(b) the paper's own recipe — 13 devices per weight on K2, iterative bound
    management everywhere — for a few steps through the same entry point
    (the ``noisy_read`` and ``pulse_counts`` kernels).
(c) analog serving: ``ContinuousBatchingScheduler`` on deepseek_7b at its
    published widths (2 of its 30 layers), attention and MLP projections on
    analog tiles.  The tokens it serves with noise-free kernels are checked
    against the same weights run digital; the managed (noisy) run is held
    to a bound on its logit error.

``--four-chips`` runs only the crossbar tile grid sharded over a 2x2 mesh,
against the same step on the serial single-device oracle.

The script needs a TPU: without one it exits non-zero before any work and
prints no result.  Its last stdout line is the JSON verdict
``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import collections
import dataclasses
import gc
import json
import os
import re
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# --- tolerances, each with its reason -------------------------------------

#: (a) max |logits(kernels) - logits(reference)| over max(1, max |logits|).
#: The kernels run their f32 dots at Mosaic's default MXU precision while
#: the reference runs at "highest"; the read noise is drawn from the same
#: counters on both paths, so it cancels exactly.  A v5e measured 6.5e-4
#: (the bf16 operand roundings, ~2^-9 each, largely cancel over the
#: <= 513-long contractions).  The limit is about 8x that, so any layer
#: error beyond half a percent of the logit scale fails.
LENET_LOGIT_TOL = 5e-3
#: (a) share of weights whose one-step update differs by more than dw_min.
#: Both paths draw the same pulse-stream counters; a coincidence count can
#: differ only where a Bernoulli threshold falls between the two paths'
#: firing probabilities, which differ by the ~1% precision error above.
LENET_DW_SHARE_TOL = 0.01
#: (a) the trained net must beat chance (10 classes) on the test split.
CHANCE_ERROR = 0.9
#: (c) max |logits(noise-free analog kernels) - logits(digital)| over
#: max |logits(digital)|, teacher-forced on the tokens the noise-free
#: scheduler served: the same weights read exactly, so only matmul
#: precision differs (default-precision kernels and unembed against the
#: highest-precision digital reference; ~0.3% of the logit scale per
#: bf16-rounded 4096-long contraction, compounded over two layers).
SERVE_NOISE_FREE_TOL = 0.05
#: (c) the scheduler against the full-sequence forward of the same
#: noise-free kernels: each token it served must be the argmax of the
#: teacher-forced logits up to this share of the logit scale.  The two
#: differ only in f32 summation order (batch-1 prefill, then cached
#: decode over 4 live slots, against one 32-token pass); the default-
#: precision dots round those last-bit differences to bf16, and a v5e
#: measured a largest gap of 7.4e-4 of the scale.  A wrong cache slot or
#: position changes the context, and the served token's gap with it, by
#: O(1) (a decode fed another slot's token gave a gap of 0.25 of the
#: scale on CPU).
SERVE_DECODE_TOL = 2e-3
#: (c) ||logits(managed analog) - logits(digital)|| / ||logits(digital)||.
#: lm_managed adds sigma=0.06 read noise to each of the seven projections
#: per layer without forward noise management, so the error grows with the
#: context: the attention output averages more positions and shrinks while
#: the noise does not.  A v5e measured 0.454 at this 32-token context.  A
#: broken read (the 1/16 retry selected without its x16 rescale, a missing
#: replica average) lands at >= 1.
SERVE_MANAGED_REL_TOL = 0.5

LENET_BATCH = 8
SERVE_POLICY = ("*attn*=lm_managed:use_pallas=true:bm_mode=two_phase,"
                "*mlp*=lm_managed:use_pallas=true:bm_mode=two_phase")
PAPER_RECIPE = ("K2=k2_multi_device:use_pallas=true,"
                "*=managed:use_pallas=true")


def log(phase: str, msg: str) -> None:
    print(f"[{phase}] {msg}", flush=True)


def check(ok: bool, what: str) -> None:
    """A smoke check that holds under ``python -O`` too."""
    if not ok:
        raise RuntimeError(f"check failed: {what}")


def count_by_kind(names) -> dict:
    return dict(sorted(collections.Counter(names).items()))


# ---------------------------------------------------------------------------
# LeNet helpers
# ---------------------------------------------------------------------------

def lenet_path_table(cfg, params) -> dict:
    """Per-layer rule, knobs and kernel launches of one forward +
    backward/update cycle — the routing the code takes, read off the traced
    program (``analysis.targets.lenet_layer_cycles``), not re-derived."""
    from repro.analog.presets import describe_cfg
    from repro.analysis.targets import lenet_layer_cycles
    return {layer: (cfg.label(layer), describe_cfg(cfg.resolved(layer)),
                    dict(sorted(rep.launches_by_kind.items())))
            for layer, rep in lenet_layer_cycles(cfg, params).items()}


def print_path_table(phase: str, table: dict) -> None:
    log(phase, "resolved per-layer path (layer | rule | knobs | launches "
               "per forward+backward+update):")
    for layer, (rule, knobs, launches) in table.items():
        log(phase, f"  {layer:<3} | {rule:<40} | {knobs:<44} | {launches}")


def lenet_batch(seed: int):
    import jax.numpy as jnp
    from repro.data import mnist
    (xtr, ytr), _ = mnist.load_splits(LENET_BATCH, 8, seed=seed,
                                      verbose=False)
    return jnp.asarray(xtr[:LENET_BATCH]), jnp.asarray(ytr[:LENET_BATCH])


def reference_cfg(cfg):
    """The same policy with every layer on the pure-jnp reference path."""
    return dataclasses.replace(cfg, policy=cfg.policy.map_configs(
        lambda c: dataclasses.replace(c, use_pallas=False,
                                      fuse_bwd_update=False)))


def compiled_step(cfg, params, opt_state, x, y, key):
    """Lower + compile the engine's train step (``make_cnn_step_fn``, the
    body the epoch scan iterates) once; returns it with its kernel names."""
    import jax
    from repro.analysis.hlo import pallas_kernel_names
    from repro.optim import analog_sgd
    from repro.train import engine

    step = engine.make_cnn_step_fn(cfg, analog_sgd())
    compiled = jax.jit(step).lower(params, opt_state, x, y, key).compile()
    return compiled, pallas_kernel_names(compiled.as_text())


def final_loss(cfg, params, seed: int) -> float:
    import jax
    from repro.models import lenet
    x, y = lenet_batch(seed + 7)
    loss = jax.jit(lambda p, xx, yy, k: lenet.loss_fn(p, xx, yy, k, cfg))
    return float(loss(params, x, y, jax.random.key(seed + 7)))


# ---------------------------------------------------------------------------
# (a) paper CNN, fused
# ---------------------------------------------------------------------------

def phase_cnn_fused(*, n_train: int = 2400, n_test: int = 512,
                    seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analog.presets import parse_policy
    from repro.analysis.targets import LENET_POLICY
    from repro.models import lenet
    from repro.optim import analog_sgd
    from repro.train import cnn, engine

    ph = "a"
    cfg = lenet.LeNetConfig.from_policy(parse_policy(LENET_POLICY))
    log(ph, f"policy {LENET_POLICY!r}, batch {LENET_BATCH}")
    key = jax.random.key(seed)
    params = lenet.init(key, cfg)
    shapes = {l: tuple(params[l].w.shape) for l in lenet.LAYERS}
    log(ph, f"physical tile shapes {shapes}")
    check(shapes == {"K1": (16, 26), "K2": (32, 401), "W3": (128, 513),
                     "W4": (10, 129)}, f"tile shapes {shapes}")

    table = lenet_path_table(cfg, params)
    print_path_table(ph, table)

    opt = analog_sgd()
    opt_state = opt.init(params)
    x, y = lenet_batch(seed)
    k_step = jax.random.key(seed + 1)
    t0 = time.perf_counter()
    compiled, names = compiled_step(cfg, params, opt_state, x, y, k_step)
    by_kind = count_by_kind(names)
    log(ph, f"compiled train step: {len(names)} tpu_custom_call {by_kind} "
            f"(compile {time.perf_counter() - t0:.1f} s)")
    expected = {"bwd_update": 2, "bwd_update_conv": 2, "managed_read": 2,
                "managed_read_conv": 2}
    check(by_kind == expected, f"expected {expected}, got {by_kind}")

    # one step on the same batch and key: kernels vs pure-jnp reference
    ref = reference_cfg(cfg)
    logits_k = jax.jit(lambda p, xx, k: lenet.apply(p, xx, k, cfg))(
        params, x, k_step)
    new_k, _ = compiled(params, opt_state, x, y, k_step)
    with jax.default_matmul_precision("highest"):
        logits_r = jax.jit(lambda p, xx, k: lenet.apply(p, xx, k, ref))(
            params, x, k_step)
        new_r, _ = jax.jit(engine.make_cnn_step_fn(ref, opt))(
            params, opt_state, x, y, k_step)
    scale = max(1.0, float(jnp.max(jnp.abs(logits_r))))
    dlogit = float(jnp.max(jnp.abs(logits_k - logits_r)))
    log(ph, f"step vs reference: max |d logits| {dlogit:.3e} "
            f"(scale {scale:.3f}, tol {LENET_LOGIT_TOL} x scale)")
    n_diff = n_all = 0
    for l in lenet.LAYERS:
        dw_min = params[l].meta.cfg.dw_min
        dw = np.abs(np.asarray(new_k[l].w) - np.asarray(new_r[l].w))
        moved = np.abs(np.asarray(new_r[l].w) - np.asarray(params[l].w))
        n_diff += int(np.sum(dw > dw_min))
        n_all += dw.size
        log(ph, f"  {l}: weights updated {int(np.sum(moved > 0))}/{dw.size}"
                f", differ by > dw_min {int(np.sum(dw > dw_min))}, "
                f"max |dw| {float(dw.max()):.3e}")
    share = n_diff / n_all
    log(ph, f"share of weights differing by > dw_min: {share:.3e} "
            f"(tol {LENET_DW_SHARE_TOL})")
    check(bool(np.all(np.isfinite(np.asarray(logits_k)))),
          "non-finite logits")
    check(dlogit <= LENET_LOGIT_TOL * scale, f"max |d logits| {dlogit}")
    check(share <= LENET_DW_SHARE_TOL, f"weight-difference share {share}")
    del compiled, new_k, new_r

    steps = n_train // LENET_BATCH
    log(ph, f"training {steps} steps (cnn.train, scan engine)")
    res = cnn.train(cfg, epochs=1, batch=LENET_BATCH, n_train=n_train,
                    n_test=n_test, seed=seed, verbose=False,
                    return_params=True)
    loss = final_loss(cfg, res["params"], seed)
    err = res["final_error"]
    log(ph, f"after {steps} steps: test error {err:.4f} on {n_test} "
            f"images (chance {CHANCE_ERROR}), loss {loss:.4f} on a "
            f"batch of {LENET_BATCH}")
    check(bool(np.isfinite(loss)), f"loss {loss}")
    check(err < CHANCE_ERROR, f"test error {err}")


# ---------------------------------------------------------------------------
# (b) the paper's recipe: 13 devices on K2, iterative BM
# ---------------------------------------------------------------------------

def phase_paper_recipe(*, n_train: int = 64, seed: int = 0) -> None:
    import jax
    import numpy as np
    from repro.analog.presets import parse_policy
    from repro.models import lenet
    from repro.optim import analog_sgd
    from repro.train import cnn

    ph = "b"
    cfg = lenet.LeNetConfig.from_policy(parse_policy(PAPER_RECIPE))
    log(ph, f"policy {PAPER_RECIPE!r}")
    params = lenet.init(jax.random.key(seed), cfg)
    log(ph, f"K2 physical tile {tuple(params['K2'].w.shape)} "
            f"(13 devices per weight)")
    check(params["K2"].w.shape == (13 * 32, 401),
          f"K2 tile {params['K2'].w.shape}")
    table = lenet_path_table(cfg, params)
    print_path_table(ph, table)

    opt = analog_sgd()
    x, y = lenet_batch(seed)
    _compiled, names = compiled_step(cfg, params, opt.init(params), x, y,
                                     jax.random.key(seed + 1))
    by_kind = count_by_kind(names)
    log(ph, f"compiled train step: {len(names)} tpu_custom_call {by_kind}")
    for kind in ("noisy_read", "pulse_counts"):
        check(by_kind.get(kind, 0) > 0, f"no {kind} kernel in {by_kind}")

    steps = n_train // LENET_BATCH
    res = cnn.train(cfg, epochs=1, batch=LENET_BATCH, n_train=n_train,
                    n_test=256, seed=seed, verbose=False, return_params=True)
    loss = final_loss(cfg, res["params"], seed)
    log(ph, f"after {steps} steps: test error {res['final_error']:.4f}, "
            f"loss {loss:.4f}")
    check(bool(np.isfinite(loss)), f"loss {loss}")
    for l in lenet.LAYERS:
        check(bool(np.all(np.isfinite(np.asarray(res["params"][l].w)))),
              f"non-finite weights in {l}")


# ---------------------------------------------------------------------------
# (c) analog serving at deepseek_7b widths
# ---------------------------------------------------------------------------

def serve_cfg(base, policy: str):
    import jax.numpy as jnp
    from repro.analog.presets import parse_policy
    return dataclasses.replace(base, analog_policy=parse_policy(policy),
                               param_dtype=jnp.float32,
                               act_dtype=jnp.float32)


def phase_serve(*, base=None, n_layers: int = 2, n_requests: int = 8,
                prompt_len: int = 16, new_tokens: int = 16, slots: int = 4,
                seed: int = 0) -> None:
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analog.convert import conversion_plan, to_digital
    from repro.analog.modules import AnalogState
    from repro.analog.presets import describe_cfg, resolve_spec
    from repro.configs import registry
    from repro.models import transformer
    from repro.serve import scheduler as sched

    ph = "c"
    if base is None:
        base = registry.get_config("deepseek_7b")
    base = dataclasses.replace(base, n_layers=n_layers)
    cfg = serve_cfg(base, SERVE_POLICY)
    log(ph, f"{base.name}: d_model {base.d_model}, d_ff {base.d_ff}, vocab "
            f"{base.vocab}, {n_layers} of its layers, f32")
    log(ph, f"policy {SERVE_POLICY!r} (the unembed stays digital)")

    t0 = time.perf_counter()
    params = jax.jit(lambda k: transformer.init_lm(k, cfg)[0])(
        jax.random.key(seed))
    jax.block_until_ready(params)
    n_par = sum(int(np.prod(l.shape)) for l in jax.tree_util.tree_leaves(
        params) if jnp.issubdtype(l.dtype, jnp.floating))
    log(ph, f"initialised {n_par / 1e9:.3f} B parameters "
            f"({time.perf_counter() - t0:.1f} s)")
    for path, label, c in conversion_plan(params):
        log(ph, f"  {path:<22} {label:<48} {describe_cfg(c)}")
    check("unembed" not in [r[0] for r in conversion_plan(params)
                            if r[2] is not None], "unembed is analog")

    rng = np.random.default_rng(seed)
    prompts = rng.integers(0, base.vocab, (n_requests, prompt_len),
                           dtype=np.int32)
    reqs = [sched.Request(rid=i, prompt=prompts[i],
                          max_new_tokens=new_tokens)
            for i in range(n_requests)]
    akey = jax.random.key(seed + 1)

    def serve(p, what):
        """Serve every request through the scheduler; the served tokens."""
        s = sched.ContinuousBatchingScheduler(
            p, cfg, slots=slots, max_seq=prompt_len + new_tokens, akey=akey)
        t0 = time.perf_counter()
        done = sorted(s.run(reqs), key=lambda c: c.rid)
        log(ph, f"scheduler ({what}): {len(done)}/{n_requests} requests, "
                f"{sum(len(c.tokens) for c in done)} tokens over {slots} "
                f"slots ({time.perf_counter() - t0:.1f} s incl. compile)")
        check(len(done) == n_requests, f"{len(done)} completions")
        check(all(len(c.tokens) == new_tokens for c in done),
              f"token counts {[len(c.tokens) for c in done]}")
        toks = np.asarray([c.tokens for c in done], np.int32)
        check(toks.min() >= 0 and toks.max() < base.vocab,
              "token out of vocab")
        return toks

    nf = resolve_spec("noise_free:use_pallas=true").normalized_for_lm()
    params_nf = jax.tree_util.tree_map(
        lambda t: t.with_cfg(nf) if isinstance(t, AnalogState) else t,
        params, is_leaf=lambda t: isinstance(t, AnalogState))
    toks_m = serve(params, "managed")
    toks_nf = serve(params_nf, "noise-free kernels")

    # teacher-forced logits over prompt + served tokens: the position that
    # predicted served token j is prompt_len - 1 + j
    sl = slice(prompt_len - 1, prompt_len - 1 + new_tokens)

    def teacher_forced(c):
        fwd = jax.jit(lambda pp, tt, kk: transformer.forward(
            pp, tt, c, akey=kk)[0][:, sl])
        return lambda p, toks, k: np.asarray(fwd(
            p, jnp.asarray(np.concatenate([prompts, toks], axis=1)), k))

    analog_fwd = teacher_forced(cfg)
    l_managed = analog_fwd(params, toks_m, akey)
    l_nf = analog_fwd(params_nf, toks_nf, akey)
    digital = to_digital(params)
    del params, params_nf
    gc.collect()
    digital_fwd = teacher_forced(dataclasses.replace(cfg, analog_policy=None))
    with jax.default_matmul_precision("highest"):
        l_dig_nf = digital_fwd(digital, toks_nf, None)
        l_dig_m = digital_fwd(digital, toks_m, None)

    def gap(logits, toks):
        """Per served token: how far its logit lies below the row's max."""
        return (logits.max(-1)
                - np.take_along_axis(logits, toks[..., None], -1)[..., 0])

    # primary: the noise-free scheduler against digital and its own forward
    scale = float(np.abs(l_dig_nf).max())
    d_nf = float(np.abs(l_nf - l_dig_nf).max())
    gap_own = float(gap(l_nf, toks_nf).max())
    gap_dig = float(gap(l_dig_nf, toks_nf).max())
    # gap_dig <= gap_own + 2 d_nf, so the two checks below bound it too
    dig_bound = 2 * d_nf + SERVE_DECODE_TOL * scale
    log(ph, f"noise-free kernels vs digital: max |d logits| {d_nf:.3e} "
            f"(logit scale {scale:.3f}, tol {SERVE_NOISE_FREE_TOL} x scale)")
    log(ph, f"noise-free served tokens: largest gap to the max of the same "
            f"kernels' teacher-forced logits {gap_own:.3e} (tol "
            f"{SERVE_DECODE_TOL} x scale = {SERVE_DECODE_TOL * scale:.3e})")
    log(ph, f"noise-free served tokens: "
            f"{float(np.mean(l_dig_nf.argmax(-1) == toks_nf)):.3f} equal the "
            f"digital argmax; largest digital logit gap {gap_dig:.3e} (bound "
            f"2 x max |d logits| + {SERVE_DECODE_TOL} x scale = "
            f"{dig_bound:.3e})")
    # secondary: the managed (noisy) scheduler, statistically
    rel = float(np.linalg.norm(l_managed - l_dig_m)
                / np.linalg.norm(l_dig_m))
    err_env = float(np.abs(l_managed - l_dig_m).max())
    gap_m = float(gap(l_dig_m, toks_m).max())
    log(ph, f"managed vs digital: relative L2 logit error {rel:.4f} "
            f"(tol {SERVE_MANAGED_REL_TOL}), max |d logits| {err_env:.3f}; "
            f"{float(np.mean(l_dig_m.argmax(-1) == toks_m)):.3f} of served "
            f"tokens equal the digital argmax, largest digital gap "
            f"{gap_m:.3f} (bound 2 x max |d logits| = {2 * err_env:.3f})")
    for row in toks_nf[:2]:
        log(ph, f"  noise-free tokens {row.tolist()}")
    check(bool(np.all(np.isfinite(l_managed)) and np.all(np.isfinite(l_nf))),
          "non-finite logits")
    check(d_nf <= SERVE_NOISE_FREE_TOL * scale, f"noise-free error {d_nf}")
    check(gap_own <= SERVE_DECODE_TOL * scale,
          f"noise-free served-token gap to its own forward {gap_own}")
    check(rel <= SERVE_MANAGED_REL_TOL, f"managed error {rel}")
    check(gap_m <= 2 * err_env, f"managed served-token gap {gap_m}")


# ---------------------------------------------------------------------------
# --four-chips: sharded crossbar tile grid vs the serial oracle
# ---------------------------------------------------------------------------

#: share of weights whose one-step update may differ by more than dw_min
#: between the sharded grid and the serial oracle.  Bitwise equality is the
#: expectation (it holds on CPU, and on a v5e); the programs differ in how
#: XLA fuses the digital glue around each shard round, and a last-bit
#: difference in an activation can flip one Bernoulli pulse draw, moving
#: that weight by one dw_min.
GRID_DW_SHARE_TOL = 1e-3
#: max |logits(sharded) - logits(serial)| over max(1, max |logits|) when
#: the two are not bitwise equal: the same last-bit differences compounded
#: over four layers stay below 1e-5 of the scale, while a shard summed
#: twice or dropped moves the logits by a sizeable share of it.
GRID_LOGIT_TOL = 1e-4


def phase_tile_grid(*, seed: int = 0) -> None:
    import jax
    import numpy as np
    from repro.analog.presets import parse_policy
    from repro.distributed import elastic
    from repro.models import lenet
    from repro.optim import analog_sgd
    from repro.train import engine

    ph = "grid"
    spec = "nm_bm:use_pallas=true:tile_grid=2x2"
    cfg = lenet.LeNetConfig.from_policy(parse_policy(spec))
    log(ph, f"policy {spec!r} on a 2x2 crossbar mesh, "
            f"{len(jax.devices())} devices")
    opt = analog_sgd()
    params = lenet.init(jax.random.key(seed), cfg)
    opt_state = opt.init(params)
    x, y = lenet_batch(seed)
    key = jax.random.key(seed + 1)

    def run():
        jax.clear_caches()
        step = jax.jit(engine.make_cnn_step_fn(cfg, opt))
        compiled = step.lower(params, opt_state, x, y, key).compile()
        logits = jax.jit(lambda p, xx, k: lenet.apply(p, xx, k, cfg))(
            params, x, key)
        new, _ = compiled(params, opt_state, x, y, key)
        return compiled.as_text(), logits, new

    text_s, logits_s, new_s = run()
    n_ar = len(re.findall(r"= \S+ all-reduce(?:-start)?\(", text_s))
    spans = {l: len(new_s[l].w.sharding.device_set) for l in lenet.LAYERS}
    log(ph, f"sharded step: {n_ar} all-reduce ops; updated weights span "
            f"{spans} devices")
    elastic.mark_lost(jax.devices()[1:])      # serial oracle on device 0
    try:
        text_o, logits_o, new_o = run()
    finally:
        elastic.restore_all()
    log(ph, f"serial oracle: {len(re.findall(r'all-reduce', text_o))} "
            f"all-reduce ops")
    check(n_ar > 0, "no all-reduce in the sharded step")
    check(all(n == 4 for n in spans.values()), f"weights span {spans}")

    bitwise = bool(np.array_equal(np.asarray(logits_s), np.asarray(logits_o)))
    n_diff = n_all = 0
    for l in lenet.LAYERS:
        a, b = np.asarray(new_s[l].w), np.asarray(new_o[l].w)
        bitwise &= bool(np.array_equal(a, b))
        n_diff += int(np.sum(np.abs(a - b) > params[l].meta.cfg.dw_min))
        n_all += a.size
        log(ph, f"  {l}: bitwise {np.array_equal(a, b)}, max |dw| "
                f"{float(np.abs(a - b).max()):.3e}")
    dlogit = float(np.abs(np.asarray(logits_s) - np.asarray(logits_o)).max())
    scale = max(1.0, float(np.abs(np.asarray(logits_o)).max()))
    log(ph, f"sharded vs serial: bitwise {bitwise}; max |d logits| "
            f"{dlogit:.3e} (tol {GRID_LOGIT_TOL} x scale {scale:.3f}); "
            f"share of weights differing by > dw_min {n_diff / n_all:.3e} "
            f"(tol {GRID_DW_SHARE_TOL})")
    check(bitwise or dlogit <= GRID_LOGIT_TOL * scale,
          f"max |d logits| {dlogit}")
    check(bitwise or n_diff / n_all <= GRID_DW_SHARE_TOL,
          f"weight-difference share {n_diff / n_all}")


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the sharded 2x2 tile grid against its "
                         "serial oracle (needs four chips)")
    args = ap.parse_args(argv)

    from repro.utils.compile_cache import use_compile_cache
    cache = use_compile_cache()
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU, JAX found {dev.platform!r}",
              file=sys.stderr)
        return 1
    n_dev = len(jax.devices())
    need = 4 if args.four_chips else 1
    if n_dev < need:
        print(f"chip_smoke: needs {need} chips, found {n_dev}",
              file=sys.stderr)
        return 1
    print(f"device: {dev.platform} {dev.device_kind} x{n_dev}; compile "
          f"cache {cache}", flush=True)

    phases = ([("grid", phase_tile_grid)] if args.four_chips else
              [("a", phase_cnn_fused), ("b", phase_paper_recipe),
               ("c", phase_serve)])
    failed = []
    for name, fn in phases:
        t0 = time.perf_counter()
        try:
            fn()
            log(name, f"PASS ({time.perf_counter() - t0:.1f} s)")
        except Exception:
            traceback.print_exc()
            log(name, f"FAIL ({time.perf_counter() - t0:.1f} s)")
            failed.append(name)
        gc.collect()
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": n_dev}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
