"""Plain reference: a llama-style LM trained with its block projections on
simulated RPU tiles (deepseek_7b; arXiv:2401.02954).

Pre-norm decoder: RMSNorm, multi-head causal attention with rotate-half
RoPE, a SwiGLU MLP (``silu(h W_g) * (h W_i)``, then ``W_o``), a final RMSNorm
and an untied head; the loss is the mean next-token cross-entropy over the
(sliced) vocabulary.  The seven projections of every block (``q k v o`` and
``wi wg wo``) are crossbar tiles (``w`` of shape (out, in), no bias column)
trained by the paper's three cycles, as the simulator's ``lm_managed``
tiles with two-phase bound management run them:

* forward read: the raw product per physical array segment (at most 4096
  columns each), read twice with counter-hash noise sigma and clipped at
  +-alpha per segment, summed; where the first read saturated anywhere, the
  second (at 1/16 of the input) times 16 is kept;
* transpose read of the error, noise-managed (divided by its per-vector
  max), segmented over the tile's rows, bound-managed the same way;
* pulse update with update management at BL = 1: Bernoulli streams on
  columns and rows, coincidences counted, each device's own up/down step,
  30% cycle-to-cycle spread, clipped at the device's own bound.  The
  device maps are regenerated from each tile's seed as the simulator draws
  them (``seeded`` maps).  The analog step is ``w - (w - w_new)``.

Embedding, norms and head take AdamW (lr 3e-4, betas 0.9/0.95, eps 1e-8).
The initial state (:func:`make_state`) is drawn here, from the seed, and
handed to the program.

Everything is plain ``jax.numpy`` at ``HIGHEST`` matmul precision with the
draws of ``counter_rng`` (the simulator's documented counter layout), so it
follows a run value for value.  It imports nothing of the program.  A step
runs layer by layer: the forward keeps each layer's input, the backward
recomputes one layer at a time, so one layer's activations are live at
once.  ``dtype=bfloat16`` gives the lower-precision control.
"""

from __future__ import annotations

import functools
import os
import sys
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import counter_rng as crng  # noqa: E402

HI = jax.lax.Precision.HIGHEST
ATTN = ("q", "k", "v", "o")
MLP = ("wi", "wg", "wo")
PROJ = ATTN + MLP
EPS = 1e-12
RETRY = 16.0
#: rows of a read or an update computed at once
ROWS = 1024
ADAM = {"lr": 3e-4, "b1": 0.9, "b2": 0.95, "eps": 1e-8}


def _mm(eq, a, b, dt):
    return jnp.einsum(eq, a.astype(dt), b.astype(dt), precision=HI,
                      preferred_element_type=jnp.float32).astype(dt)


def _key(data):
    return jax.random.wrap_key_data(jnp.asarray(data, jnp.uint32),
                                    impl="threefry2x32")


# ---------------------------------------------------------------------------
# Tile cycles
# ---------------------------------------------------------------------------

def device_maps(seed, rows, cols, dev, dt):
    """The tile's device population, drawn from its seed as the simulator
    draws seeded maps."""
    k_dw, k_imb, k_bd = jax.random.split(_key(seed), 3)
    shape = (rows, cols)
    dw = dev["dw_min"] * (1.0 + dev["dw_min_dtod"]
                          * jax.random.normal(k_dw, shape, jnp.float32))
    dw = jnp.maximum(dw, 0.01 * dev["dw_min"])
    r = jnp.clip(1.0 + dev["imbalance_dtod"]
                 * jax.random.normal(k_imb, shape, jnp.float32), 0.5, 2.0)
    bound = dev["w_bound"] * (1.0 + dev["w_bound_dtod"]
                              * jax.random.normal(k_bd, shape, jnp.float32))
    bound = jnp.maximum(bound, 0.1 * dev["w_bound"])
    return (dw * jnp.sqrt(r)).astype(dt), (dw / jnp.sqrt(r)).astype(dt), \
        bound.astype(dt)


def tile_shapes(config: Dict) -> Dict[str, tuple]:
    """(out, in) of each projection's tile."""
    d, f = int(config["hidden_size"]), int(config["intermediate_size"])
    return {"q": (d, d), "k": (d, d), "v": (d, d), "o": (d, d),
            "wi": (f, d), "wg": (f, d), "wo": (d, f)}


@functools.partial(jax.jit, static_argnames=("shape", "dev"))
def _init_tile(k_w, seed, shape, dev):
    """A tile's initial weights: truncated normal on [-2, 2] x in^-1/2,
    clipped to each device's own bound (its map drawn from ``seed``)."""
    w = jax.random.truncated_normal(k_w, -2.0, 2.0, shape, jnp.float32)
    _, _, bound = device_maps(seed, *shape, dict(dev), jnp.float32)
    return jnp.clip(w * shape[1] ** -0.5, -bound, bound)


def make_state(key, config: Dict) -> Dict:
    """The initial state, from ``key``, in :func:`train_calls`' layout (on
    the device; the seeds as threefry key data).  Drawn as the LM path's
    initialiser draws it: each projection of layer ``i`` from
    ``fold_in(fold_in(k_tiles, j), i)`` (``j`` its place in ``q k v o wi
    wg wo``), split into its weights' key and its device-map seed; the
    embedding truncated normal x 0.02, the head x d^-1/2, the norms 1."""
    d, v = int(config["hidden_size"]), int(config["vocab_size"])
    n_layers = int(config["num_hidden_layers"])
    dev = device_cfg(config)
    k_embed, k_head, k_tiles = jax.random.split(key, 3)
    tiles, seeds = {}, {}
    for j, (n, shape) in enumerate(tile_shapes(config).items()):
        ws, ss = [], []
        for i in range(n_layers):
            k_w, k_dev = jax.random.split(
                jax.random.fold_in(jax.random.fold_in(k_tiles, j), i))
            ss.append(jax.random.key_data(k_dev))
            ws.append(_init_tile(k_w, ss[-1], shape, dev))
        tiles[n], seeds[n] = jnp.stack(ws), jnp.stack(ss)
        del ws
    return {"embed": 0.02 * jax.random.truncated_normal(
                k_embed, -2.0, 2.0, (v, d), jnp.float32),
            "unembed": d ** -0.5 * jax.random.truncated_normal(
                k_head, -2.0, 2.0, (d, v), jnp.float32),
            "final_norm": jnp.ones((d,), jnp.float32),
            "ln_attn": jnp.ones((n_layers, d), jnp.float32),
            "ln_ffn": jnp.ones((n_layers, d), jnp.float32),
            "tiles": tiles, "seeds": seeds}


def _blocks(x, rows=ROWS):
    """``x`` (N, K) as (N / b, b, K) blocks of ``b`` = min(rows, N) rows,
    with each block's first row."""
    b = min(rows, x.shape[0])
    assert x.shape[0] % b == 0, (x.shape, b)
    n = x.shape[0] // b
    return jnp.arange(n, dtype=jnp.uint32) * b, x.reshape(n, b, -1)


def managed_read(w, x, key, dev, *, transpose, nm, dt):
    """Two-phase bound-managed read of ``w`` (out, in) by rows ``x``;
    ``transpose`` reads ``W^T`` (the error through the tile).  Computed
    in blocks of rows, each drawing its rows' noise."""
    wt = (w.T if transpose else w).astype(dt)         # (out, K)
    out, k_dim = wt.shape
    n_seg = -(-k_dim // dev["max_array"])
    seg = -(-k_dim // n_seg)
    sigma, alpha = dev["read_noise"], dev["out_bound"]
    k1, k2 = jax.random.split(key)
    seeds = crng.key_seed(k1), crng.key_seed(k2)
    total = x.shape[0] * n_seg * out

    def block(args):
        r0, xb = args
        xb = xb.astype(dt)
        if nm:
            s = jnp.max(jnp.abs(xb), axis=-1, keepdims=True)
            s = jnp.where(s > EPS, s, 1.0).astype(dt)
        else:
            s = jnp.ones((xb.shape[0], 1), dt)
        raw = jnp.stack([_mm("nk,ok->no", xb[:, i * seg:(i + 1) * seg],
                             wt[:, i * seg:(i + 1) * seg], dt)
                         for i in range(n_seg)], axis=1)  # (b, n_seg, out)
        v1 = raw / s[:, :, None]
        counter = crng.flat_counter(raw.shape) + r0 * np.uint32(n_seg * out)

        def read(v, seed):
            v = v + (sigma * crng.normal(seed, counter, total)).astype(dt)
            sat = jnp.any(jnp.abs(v) >= alpha, axis=(1, 2))
            return jnp.sum(jnp.clip(v, -alpha, alpha), axis=1), sat

        y1, sat1 = read(v1, seeds[0])
        y2, _ = read(v1 / RETRY, seeds[1])
        return jnp.where(sat1[:, None], y2 * RETRY, y1) * s

    return jax.lax.map(block, _blocks(x)).reshape(x.shape[0], out)


def pulse_update(w, seed, x, g, key, dev, lr, dt):
    """The update cycle of tile ``w`` by columns ``x`` (N, in) and row
    errors ``-g`` (N, out); returns the new weights.  The coincidences are
    counted in blocks of rows (integer sums, so exactly)."""
    delta = (-g).astype(dt)
    x = x.astype(dt)
    k_a, k_b, k_c = jax.random.split(key, 3)
    c = (jnp.asarray(lr, jnp.float32) / (dev["bl"] * dev["dw_min"])) ** 0.5
    x_max = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), EPS)
    d_max = jnp.maximum(jnp.max(jnp.abs(delta)).astype(jnp.float32), EPS)
    m = jnp.clip(jnp.sqrt(d_max / x_max), 1e-3, 1e3)
    cx, cd = (c * m).astype(dt), (c / m).astype(dt)

    def streams(k, r0, v, gain):                      # BL = 1
        p = jnp.clip(jnp.abs(gain * v), 0.0, 1.0)
        counter = crng.flat_counter(v.shape) + r0 * np.uint32(v.shape[1])
        u = crng.uniform(crng.key_seed(k), counter)
        return (u.astype(dt) < p).astype(dt) * jnp.sign(v)

    def block(counts, args):
        r0, xb, db = args
        a = streams(k_a, r0, xb, cx)
        b = streams(k_b, r0, db, cd)
        return (counts[0] + _mm("tm,tn->mn", b, a, jnp.float32),
                counts[1] + _mm("tm,tn->mn", jnp.abs(b), jnp.abs(a),
                                jnp.float32)), None

    r0s, xs = _blocks(x)
    _, ds = _blocks(delta)
    zeros = jnp.zeros(w.shape, jnp.float32)
    (net, total), _ = jax.lax.scan(block, (zeros, zeros), (r0s, xs, ds))
    up, dn = (0.5 * (total + net)).astype(dt), (0.5 * (total - net)).astype(dt)
    dw_up, dw_dn, bound = device_maps(seed, *w.shape, dev, dt)
    dw = up * dw_up - dn * dw_dn
    xi = crng.normal(crng.key_seed(k_c), crng.flat_counter(dw.shape),
                     dw.size).astype(dt)
    dw = dw + dev["dw_min_ctoc"] * jnp.sqrt(
        up * dw_up ** 2 + dn * dw_dn ** 2) * xi
    w = w.astype(dt)
    return w - (w - jnp.clip(w + dw, -bound, bound))


def _tile_keys(lk):
    """Each projection's key in a layer (``lk``): the attention folds in
    0..3, the MLP splits three (``wg``, ``wi``, ``wo``)."""
    ks = {n: jax.random.fold_in(lk, i) for i, n in enumerate(ATTN)}
    ks["wg"], ks["wi"], ks["wo"] = jax.random.split(lk, 3)
    return {n: jax.random.split(k, 3) for n, k in ks.items()}  # f, b, u


# ---------------------------------------------------------------------------
# The digital parts
# ---------------------------------------------------------------------------

def rmsnorm(x, scale, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * scale.astype(jnp.float32)).astype(x.dtype)


def rope(x, theta):
    """Rotate-half RoPE over (B, S, H, D) at positions 0..S-1."""
    half = x.shape[-1] // 2
    freqs = (1.0 / theta) ** (np.arange(0, half, dtype=np.float32) / half)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def attention(q, k, v, heads, theta, dt):
    """Causal multi-head softmax attention of (B, S, H*D) projections, one
    sequence at a time (recomputed in the backward, so one sequence's
    scores are live at once)."""
    b, s, hd = q.shape
    shape = (s, heads, hd // heads)

    @jax.checkpoint
    def one(qkv):
        q1, k1, v1 = (a.reshape(shape) for a in qkv)
        q1 = rope(q1[None], theta)[0]
        k1 = rope(k1[None], theta)[0]
        sc = _mm("qhd,khd->hqk", q1, k1, jnp.float32) * (hd // heads) ** -0.5
        causal = jnp.tril(jnp.ones((s, s), bool))
        p = jax.nn.softmax(jnp.where(causal, sc, -1e30), axis=-1)
        return _mm("hqk,khd->qhd", p, v1, dt).reshape(s, hd)

    return jax.lax.map(one, (q, k, v))


# ---------------------------------------------------------------------------
# One layer, forward and backward
# ---------------------------------------------------------------------------

def _layer(lp, x, lk, cfg, dev, dt):
    """Forward of one block: its output and the activations the backward
    reads (each projection's input, the attention and MLP inputs)."""
    ks = _tile_keys(lk)
    bsz, s, _ = x.shape

    def read(n, inp):
        return managed_read(lp[n]["w"], inp.reshape(bsz * s, -1), ks[n][0],
                            dev, transpose=False, nm=False,
                            dt=dt).reshape(bsz, s, -1)

    h1 = rmsnorm(x, lp["ln_attn"], cfg["eps"])
    q, k, v = read("q", h1), read("k", h1), read("v", h1)
    att = attention(q, k, v, cfg["heads"], cfg["theta"], dt)
    x2 = x + read("o", att)
    h2 = rmsnorm(x2, lp["ln_ffn"], cfg["eps"])
    gate, up = read("wg", h2), read("wi", h2)
    hh = jax.nn.silu(gate) * up
    acts = {"h1": h1, "q": q, "k": k, "v": v, "att": att, "x2": x2,
            "h2": h2, "gate": gate, "up": up, "hh": hh}
    return x2 + read("wo", hh), acts


@functools.partial(jax.jit, static_argnames=("cfg", "dev", "dt"))
def layer_forward(lp, x, lk, cfg, dev, dt):
    return _layer(lp, x, lk, dict(cfg), dict(dev), dt)[0]


@functools.partial(jax.jit, static_argnames=("cfg", "dev", "dt"))
def layer_activations(lp, x, lk, cfg, dev, dt):
    """The layer recomputed for its backward (same keys, same reads)."""
    return _layer(lp, x, lk, dict(cfg), dict(dev), dt)[1]


@functools.partial(jax.jit, static_argnames=("lr", "dev", "dt"))
def tile_backward(w, seed, x, g, k_b, k_u, lr, dev, dt):
    """One tile's backward: the transpose read of the error ``g`` (B, S,
    out) and the pulse update by its input ``x`` (B, S, in).  Returns (the
    error through the tile, the new weights)."""
    dev = dict(dev)
    bsz, s, _ = g.shape
    g2, x2 = g.reshape(bsz * s, -1), x.reshape(bsz * s, -1)
    new = pulse_update(w, seed, x2, g2, k_u, dev, lr, dt)
    z = managed_read(w, g2, k_b, dev, transpose=True, nm=True, dt=dt)
    return z.reshape(bsz, s, -1), new


@functools.partial(jax.jit, static_argnames=("eps",))
def norm_vjp(x, scale, g, eps):
    return jax.vjp(lambda a, sc: rmsnorm(a, sc, eps), x, scale)[1](g)


@jax.jit
def swiglu_vjp(gate, up, g):
    return jax.vjp(lambda a, b: jax.nn.silu(a) * b, gate, up)[1](g)


@functools.partial(jax.jit, static_argnames=("heads", "theta", "dt"))
def attention_vjp(q, k, v, g, heads, theta, dt):
    return jax.vjp(lambda a, b, c: attention(a, b, c, heads, theta, dt),
                   q, k, v)[1](g)


def layer_backward(lp, x, lk, g_out, cfg_t, dev_t, dt):
    """Recompute the layer, then run its backward one piece at a time (so
    that one tile's update is live at once): each tile's transpose read
    carries the error on, its pulse update makes its new weights.
    Returns (input error, ``ln_attn`` and ``ln_ffn`` gradients, new
    weights by projection)."""
    cfg = dict(cfg_t)
    acts = layer_activations(lp, x, lk, cfg_t, dev_t, dt)
    ks = _tile_keys(lk)
    inputs = {"q": "h1", "k": "h1", "v": "h1", "o": "att", "wg": "h2",
              "wi": "h2", "wo": "hh"}
    new = {}

    def back(n, g):
        z, new[n] = tile_backward(lp[n]["w"], lp[n]["seed"],
                                  acts[inputs[n]], g, ks[n][1], ks[n][2],
                                  cfg["lr"], dev_t, dt)
        return z

    g_gate, g_up = swiglu_vjp(acts["gate"], acts["up"], back("wo", g_out))
    g_x2, g_ln_ffn = norm_vjp(acts["x2"], lp["ln_ffn"],
                              back("wg", g_gate) + back("wi", g_up),
                              cfg["eps"])
    g_x2 = g_x2 + g_out
    g_q, g_k, g_v = attention_vjp(acts["q"], acts["k"], acts["v"],
                                  back("o", g_x2), cfg["heads"],
                                  cfg["theta"], dt)
    del acts["q"], acts["k"], acts["v"], acts["att"]
    g_x, g_ln_attn = norm_vjp(x, lp["ln_attn"],
                              back("q", g_q) + back("k", g_k)
                              + back("v", g_v), cfg["eps"])
    return g_x + g_x2, g_ln_attn, g_ln_ffn, new


@functools.partial(jax.jit, static_argnames=("eps",))
def head_loss(x, final_norm, unembed, targets, eps):
    """Mean next-token cross-entropy and its gradients (x, norm, head)."""
    def loss(x_, fn, un):
        h = rmsnorm(x_, fn, eps)
        logits = _mm("bsd,dv->bsv", h, un, h.dtype)
        logp = jax.nn.log_softmax(logits.astype(jnp.float32))
        nll = -jnp.take_along_axis(logp, targets[..., None], -1)[..., 0]
        return jnp.mean(nll)
    value, grads = jax.value_and_grad(loss, argnums=(0, 1, 2))(
        x, final_norm, unembed)
    return value, grads


@jax.jit
def adamw(p, g, m, v, count):
    c = count.astype(jnp.float32)
    bc1 = 1.0 - ADAM["b1"] ** c
    bc2 = 1.0 - ADAM["b2"] ** c
    g32 = g.astype(jnp.float32)
    m = ADAM["b1"] * m + (1 - ADAM["b1"]) * g32
    v = ADAM["b2"] * v + (1 - ADAM["b2"]) * jnp.square(g32)
    upd = (m / bc1) / (jnp.sqrt(v / bc2) + ADAM["eps"])
    return (p.astype(jnp.float32) - ADAM["lr"] * upd).astype(p.dtype), m, v


# ---------------------------------------------------------------------------
# Training
# ---------------------------------------------------------------------------

def model_cfg(config: Dict) -> tuple:
    """The static sizes the step needs, from the configuration file, and
    the tiles' pulse learning rate, 1.0 on the LM path."""
    return tuple(sorted({
        "eps": float(config["rms_norm_eps"]),
        "heads": int(config["num_attention_heads"]),
        "theta": float(config["rope_theta"]),
        "lr": 1.0}.items()))


def device_cfg(config: Dict) -> tuple:
    dev = dict(config["device_table1"], bl=1,
               max_array=int(config["max_array"]))
    return tuple(sorted(dev.items()))


def _digital(state):
    return {"embed": state["embed"], "final_norm": state["final_norm"],
            "unembed": state["unembed"], "ln_attn": state["ln_attn"],
            "ln_ffn": state["ln_ffn"]}


def forward_loss(state, tokens, key, config, dtype=jnp.float32):
    """Step 0's loss alone: the forward of ``tokens`` (B, S+1) from host
    ``state`` (as :func:`train_calls` takes it) under step key data
    ``key``."""
    dt = jnp.dtype(dtype)
    st = jax.tree_util.tree_map(
        lambda a: jnp.asarray(a).astype(dt)
        if jnp.issubdtype(jnp.asarray(a).dtype, jnp.floating)
        else jnp.asarray(a), state)
    cfg_t, dev_t = model_cfg(config), device_cfg(config)
    x = st["embed"][tokens[:, :-1]]
    for i in range(st["ln_attn"].shape[0]):
        x = layer_forward(_layer_params(st, i), x,
                          jax.random.fold_in(_key(key), i), cfg_t, dev_t, dt)
    loss, _ = head_loss(x, st["final_norm"], st["unembed"], tokens[:, 1:],
                        dict(cfg_t)["eps"])
    return float(loss)


def _layer_params(state, i):
    out = {"ln_attn": state["ln_attn"][i], "ln_ffn": state["ln_ffn"][i]}
    for n in PROJ:
        out[n] = {"w": state["tiles"][n][i], "seed": state["seeds"][n][i]}
    return out


def train_step(state, opt, tokens, key, config, dt):
    """One step on ``tokens`` (B, S+1); returns (state, opt, loss)."""
    cfg = dict(model_cfg(config))
    cfg_t, dev_t = model_cfg(config), device_cfg(config)
    n_layers = state["ln_attn"].shape[0]
    inp, tgt = tokens[:, :-1], tokens[:, 1:]
    x = state["embed"][inp]
    xs = []

    lks = [jax.random.fold_in(key, i) for i in range(n_layers)]
    for i in range(n_layers):
        xs.append(x)
        x = layer_forward(_layer_params(state, i), x, lks[i], cfg_t,
                          dev_t, dt)
    loss, (g, g_fn, g_un) = head_loss(x, state["final_norm"],
                                      state["unembed"], tgt, cfg["eps"])
    del x
    g_la, g_lf = [None] * n_layers, [None] * n_layers
    for i in reversed(range(n_layers)):
        g, g_la[i], g_lf[i], new = layer_backward(
            _layer_params(state, i), xs[i], lks[i], g.astype(dt), cfg_t,
            dev_t, dt)
        xs[i] = None
        for n in PROJ:          # the old weights go as the new ones come
            state["tiles"][n][i] = new[n]
    g_embed = jnp.zeros_like(state["embed"]).at[inp].add(g)
    grads = {"embed": g_embed, "final_norm": g_fn, "unembed": g_un,
             "ln_attn": jnp.stack(g_la), "ln_ffn": jnp.stack(g_lf)}
    count = opt["count"] + 1
    new_state = dict(state)
    new_opt = {"count": count, "mu": {}, "nu": {}}
    for name, p in _digital(state).items():
        new_state[name], new_opt["mu"][name], new_opt["nu"][name] = adamw(
            p, grads[name], opt["mu"][name], opt["nu"][name], count)
    return new_state, new_opt, loss


def train_calls(state, calls, config, keep=None, dtype=jnp.float32):
    """Follow the program's calls from ``state`` (host arrays: ``embed``,
    ``final_norm``, ``unembed``, stacked ``ln_attn``/``ln_ffn``, ``tiles``
    and ``seeds`` by projection, the seeds as threefry key data).
    ``calls``: [(tokens (steps, B, S+1), step keys as key data (steps,
    2))].  Returns the tiles (host, f32, stacked as given) after each call
    in ``keep`` (all by default) and each step's loss.  On the device the
    tiles are held one array per layer, each replaced as its update
    lands."""
    dt = jnp.dtype(dtype)
    keep = range(len(calls)) if keep is None else keep

    def put(a):
        a = jnp.asarray(a)
        return a.astype(dt) if jnp.issubdtype(a.dtype, jnp.floating) else a

    st = {n: put(a) for n, a in _digital(state).items()}
    st["tiles"] = {n: [put(w) for w in state["tiles"][n]] for n in PROJ}
    st["seeds"] = {n: jnp.asarray(state["seeds"][n]) for n in PROJ}
    opt = {"count": jnp.zeros((), jnp.int32),
           "mu": {n: jnp.zeros(p.shape, jnp.float32)
                  for n, p in _digital(st).items()},
           "nu": {n: jnp.zeros(p.shape, jnp.float32)
                  for n, p in _digital(st).items()}}
    tiles, losses = [], []
    for c, (tokens, keys) in enumerate(calls):
        for s in range(tokens.shape[0]):
            st, opt, loss = train_step(st, opt, jnp.asarray(tokens[s]),
                                       _key(keys[s]), config, dt)
            losses.append(float(loss))
        if c in keep:
            tiles.append({n: np.stack([np.asarray(w.astype(jnp.float32))
                                       for w in st["tiles"][n]])
                          for n in PROJ})
    return tiles, losses
