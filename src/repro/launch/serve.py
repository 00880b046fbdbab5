"""Serving driver: static batched decode or continuous batching.

Static (default): prefill a batch of prompts, decode N tokens in one
fused ``greedy_generate`` dispatch.

  PYTHONPATH=src python -m repro.launch.serve --arch mixtral_8x7b --smoke \
      --batch 4 --prompt-len 32 --gen 16

Continuous (``--continuous``): rotate a synthetic request stream through
a fixed pool of cache slots (``serve/scheduler.py``) — requests admitted
mid-decode as slots free up.

Analog serving (``--analog-policy``) takes the same spec language as
``launch/train.py`` — a preset name with optional ``:field=value``
modifiers, inline first-match-wins rules, or a JSON rules file — and
prints the resolved per-layer policy table at startup.  The managed
analog read then runs inside the per-token decode hot loop:

  PYTHONPATH=src python -m repro.launch.serve --arch deepseek_7b --smoke \
      --analog-policy 'lm_managed:use_pallas=true:bm_mode=two_phase' \
      --continuous --slots 4 --requests 16
"""

from __future__ import annotations

import argparse
import time
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import registry
from repro.serve import engine
from repro.utils.compile_cache import use_compile_cache


def _print_policy_table(params) -> None:
    """Resolved per-layer policy table, same shape as launch/train.py's."""
    from repro.analog.convert import conversion_plan
    from repro.analog.presets import describe_cfg
    rows = conversion_plan(params)
    print("[serve] resolved analog policy (layer -> rule -> knobs):")
    for path, label, c in rows:
        print(f"  {path:<34} {label:<28} {describe_cfg(c)}")


def _build_cfg(arch: str, smoke: bool, analog_policy: Optional[str]):
    import dataclasses
    from repro.analog import presets
    cfg = registry.get_config(arch, smoke=smoke)
    if analog_policy:
        pol = presets.parse_policy(analog_policy)
        cfg = dataclasses.replace(cfg, analog_policy=pol,
                                  param_dtype=jnp.float32)
    return cfg


def _init(cfg, seed: int):
    from repro.models import transformer
    params, _ = transformer.init_lm(jax.random.key(seed), cfg)
    if cfg.analog_policy is not None:
        _print_policy_table(params)
    akey = (jax.random.key(seed + 1)
            if cfg.analog_policy is not None else None)
    return params, akey


def serve(arch: str, *, batch: int, prompt_len: int, gen: int,
          smoke: bool, seed: int = 0,
          analog_policy: Optional[str] = None):
    """Static batched decode (one fused dispatch)."""
    cfg = _build_cfg(arch, smoke, analog_policy)
    params, akey = _init(cfg, seed)

    rng = np.random.default_rng(seed)
    prompts = jnp.asarray(
        rng.integers(0, cfg.vocab, (batch, prompt_len)), jnp.int32)
    enc = None
    if cfg.family == "audio":
        enc = jnp.asarray(rng.normal(0, 0.5,
                                     (batch, prompt_len, cfg.d_model)),
                          cfg.act_dtype)

    max_seq = prompt_len + gen
    t0 = time.time()
    out, _ = jax.jit(
        lambda p, x, e, k: engine.greedy_generate(
            p, x, cfg, n_steps=gen, max_seq=max_seq, enc_embeds=e, akey=k),
    )(params, prompts, enc, akey)
    out = np.asarray(out)
    dt = time.time() - t0
    print(f"[serve {arch}] generated {out.shape} in {dt:.1f}s "
          f"({batch * gen / dt:.1f} tok/s incl. compile)")
    return out


def serve_continuous(arch: str, *, slots: int, n_requests: int,
                     prompt_len: int, gen: int, smoke: bool, seed: int = 0,
                     analog_policy: Optional[str] = None,
                     data_mesh: Optional[int] = None):
    """Continuous batching over a synthetic Poisson request stream."""
    from repro.distributed import sharding as shd
    from repro.serve import scheduler as sched

    cfg = _build_cfg(arch, smoke, analog_policy)
    params, akey = _init(cfg, seed)

    plan = None
    if data_mesh and data_mesh > 1:
        plan = sched.validate_serve_plan(cfg, shd.MeshPlan(data=data_mesh))
        print(f"[serve] KV/SSD caches sharded over data mesh "
              f"(plan {plan.shape})")

    rng = np.random.default_rng(seed)
    reqs = [sched.Request(
        rid=i,
        prompt=rng.integers(0, cfg.vocab,
                            size=max(1, int(rng.integers(
                                prompt_len // 2, prompt_len + 1)))
                            ).astype(np.int32),
        max_new_tokens=max(1, int(rng.integers(gen // 2, gen + 1))),
        arrival=int(rng.poisson(1.0) * i // max(1, slots)))
        for i in range(n_requests)]
    max_seq = prompt_len + gen

    s = sched.ContinuousBatchingScheduler(params, cfg, slots=slots,
                                          max_seq=max_seq, akey=akey,
                                          plan=plan)
    t0 = time.time()
    done = s.run(reqs)
    dt = time.time() - t0
    n_tok = sum(len(c.tokens) for c in done)
    print(f"[serve {arch}] continuous: {len(done)}/{n_requests} requests, "
          f"{n_tok} tokens over {slots} slots in {dt:.1f}s "
          f"({len(done) / dt:.1f} req/s, {n_tok / dt:.1f} tok/s incl. "
          "compile)")
    return done


def main():
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--analog-policy", type=str, default=None,
                    metavar="SPEC",
                    help="serve analog-converted params: a preset name "
                         "('lm_managed', 'noise_free', ...; presets take "
                         "':field=value' modifiers, e.g. "
                         "'lm_managed:use_pallas=true:bm_mode=two_phase'), "
                         "inline 'pattern=preset' rules, or a JSON rules "
                         "file — identical semantics to launch/train.py; "
                         "prints the resolved per-layer table at startup")
    ap.add_argument("--continuous", action="store_true",
                    help="continuous batching: admit a synthetic request "
                         "stream mid-decode into freed cache slots "
                         "(serve/scheduler.py) instead of one static batch")
    ap.add_argument("--slots", type=int, default=4,
                    help="cache slots (max concurrent decodes) with "
                         "--continuous")
    ap.add_argument("--requests", type=int, default=16,
                    help="synthetic requests to stream with --continuous")
    ap.add_argument("--data-mesh", type=int, default=None, metavar="N",
                    help="with --continuous: shard the cache slot axis "
                         "over N data-mesh replicas (sharding.MeshPlan; "
                         "validated against the analog tile grids)")
    args = ap.parse_args()
    if args.continuous:
        serve_continuous(args.arch, slots=args.slots,
                         n_requests=args.requests,
                         prompt_len=args.prompt_len, gen=args.gen,
                         smoke=args.smoke,
                         analog_policy=args.analog_policy,
                         data_mesh=args.data_mesh)
    else:
        serve(args.arch, batch=args.batch, prompt_len=args.prompt_len,
              gen=args.gen, smoke=args.smoke,
              analog_policy=args.analog_policy)


if __name__ == "__main__":
    main()
