"""The analog LM train step follows the benchmark's plain reference.

The smoke deepseek_7b (2 layers, d_model 64, d_ff 172, vocabulary 256)
runs through ``lm.make_scan_train_step`` with the LM training cell's policy
(``benchmarks/chip/traffic/lm_train_s2048_b4.json``; kernels interpreted)
and the physical array limit lowered to 64, so that the smoke widths read
in segments as 11008 does against 4096 at the published widths.  The plain
reference (``benchmarks/chip/references/lm_analog.py``, loaded by path; it
imports nothing of the program) follows the same state, ids and keys.

* noise-free tiles: step 0's loss equals the reference's to 1e-6 (float32
  sums in another order);
* the cell's policy: step 0's loss and every tile's weight change follow
  the reference within the cell's own limits;
* the second half of each batch replaced by the first (the harness's
  planted ``half_batch`` fault) is caught.
"""

import dataclasses
import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.train import lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CHIP = os.path.join(ROOT, "benchmarks", "chip")
ARRAY = 64
LIMIT = f":max_array_rows={ARRAY}:max_array_cols={ARRAY}"
BATCH, SEQ, STEPS = 4, 16, 2
ATTN = ("q", "k", "v", "o")
PROJ = ATTN + ("wi", "wg", "wo")


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _json(*parts):
    with open(os.path.join(CHIP, *parts)) as f:
        return json.load(f)


@pytest.fixture(scope="module")
def ref():
    return _load(os.path.join(CHIP, "references", "lm_analog.py"),
                 "lm_analog_reference")


@pytest.fixture(scope="module")
def traffic():
    return _json("traffic", "lm_train_s2048_b4.json")


def _cfg(policy):
    cfg = registry.get_config("deepseek_7b", smoke=True,
                              analog_policy=policy)
    return dataclasses.replace(cfg, param_dtype=jnp.float32,
                               act_dtype=jnp.float32, norm_eps=1e-6)


def _ref_config(cfg, **device):
    conf = _json("configs", "deepseek_7b_analog.json")
    return dict(conf, rms_norm_eps=cfg.norm_eps, rope_theta=cfg.rope_theta,
                num_attention_heads=cfg.n_heads, max_array=ARRAY,
                device_table1=dict(conf["device_table1"], **device))


def _tile(params, n):
    return params["layers"]["attn" if n in ATTN else "mlp"][n]


def _tiles(params):
    return {n: np.asarray(_tile(params, n).w) for n in PROJ}


def _state(params):
    """Host copies (the step donates the program's buffers)."""
    lay = params["layers"]
    return {"embed": np.asarray(params["embed"]["table"]),
            "final_norm": np.asarray(params["final_norm"]["scale"]),
            "unembed": np.asarray(params["unembed"]["w"]),
            "ln_attn": np.asarray(lay["ln_attn"]["scale"]),
            "ln_ffn": np.asarray(lay["ln_ffn"]["scale"]),
            "tiles": _tiles(params),
            "seeds": {n: np.asarray(jax.random.key_data(_tile(params, n).seed))
                      for n in PROJ}}


@functools.lru_cache(maxsize=None)
def _program(policy):
    cfg = _cfg(policy)
    multi, _ = lm.make_scan_train_step(cfg)
    return cfg, jax.jit(multi, donate_argnums=(0, 1))


def _run(policy, tokens, keys):
    """One call of the program: (config, initial reference state, tiles
    after, step losses)."""
    cfg, step = _program(policy)
    params, opt_state, _ = lm.init_train_state(jax.random.key(7), cfg)
    state = _state(params)
    params, _, metrics = step(params, opt_state, {"tokens": tokens}, keys)
    return cfg, state, _tiles(params), np.asarray(metrics["loss"])


@pytest.fixture(scope="module")
def inputs():
    tokens = np.random.default_rng(3).integers(
        0, 256, (STEPS, BATCH, SEQ + 1), dtype=np.int32)
    keys = jax.random.split(jax.random.key(11), STEPS)
    return tokens, keys


def _gaps(p0, prog, ref_tiles):
    """Worst tile of |norm(prog change) - norm(ref change)| and of
    norm(prog - ref), each over max(norm(ref change), median tile)."""
    p0, prog, ref_tiles = (
        {(n, i): t[n][i] for n in PROJ for i in range(t[n].shape[0])}
        for t in (p0, prog, ref_tiles))
    rn = {k: np.linalg.norm(ref_tiles[k] - p0[k]) for k in p0}
    med = float(np.median(list(rn.values())))
    change = max(abs(np.linalg.norm(prog[k] - p0[k]) - rn[k])
                 / max(rn[k], med) for k in p0)
    diff = max(np.linalg.norm(prog[k] - ref_tiles[k]) / max(rn[k], med)
               for k in p0)
    return change, diff


@pytest.fixture(scope="module")
def cell_run(ref, traffic, inputs):
    tokens, keys = inputs
    policy = ",".join(f"{pat}={spec}{LIMIT}" for pat, spec in (
        part.split("=", 1) for part in traffic["policy"].split(",")))
    cfg, state, tiles, losses = _run(policy, tokens, keys)
    ref_tiles, ref_losses = ref.train_calls(
        state, [(tokens, jax.random.key_data(keys))], _ref_config(cfg))
    half = np.concatenate([tokens[:, :BATCH // 2], tokens[:, :BATCH // 2]],
                          axis=1)
    _, _, half_tiles, half_losses = _run(policy, half, keys)
    return {"p0": state["tiles"], "ref": (ref_tiles[0], ref_losses),
            "sound": (tiles, losses), "half_batch": (half_tiles, half_losses)}


def test_noise_free_loss_equals_reference(ref, inputs):
    tokens, keys = inputs
    policy = f"*/attn/*=noise_free:use_pallas=true{LIMIT}," \
             f"*/mlp/*=noise_free:use_pallas=true{LIMIT}"
    cfg = _cfg(policy)
    params, _, _ = lm.init_train_state(jax.random.key(7), cfg)
    batch = {"tokens": jnp.asarray(tokens[0])}
    loss = float(jax.jit(lambda p, k: lm.loss_fn(p, batch, cfg, k)[1]["loss"])(
        params, keys[0]))
    want = ref.forward_loss(
        _state(params), tokens[0], jax.random.key_data(keys[0]),
        _ref_config(cfg, read_noise=0.0, out_bound=float("inf")))
    assert abs(loss - want) <= 1e-6 * abs(want)


def _numbers(run, which):
    tiles, losses = run[which]
    ref_tiles, ref_losses = run["ref"]
    change, diff = _gaps(run["p0"], tiles, ref_tiles)
    return {"first_step_loss_gap":
            abs(losses[0] - ref_losses[0]) / abs(ref_losses[0]),
            "first_call_change_gap": change, "first_call_diff_gap": diff}


def test_cell_policy_follows_reference(cell_run, traffic):
    got = _numbers(cell_run, "sound")
    for name, value in got.items():
        assert value <= traffic["limits"][name], (name, got)


def test_half_batch_is_caught(cell_run, traffic):
    got = _numbers(cell_run, "half_batch")
    assert any(v > traffic["limits"][n] for n, v in got.items()), got
