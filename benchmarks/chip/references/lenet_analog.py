"""Plain reference: the paper's LeNet trained on simulated RPU tiles.

Gokmen, Onen & Haensch 2017 (arXiv:1705.08014): conv 5x5x16 + tanh + 2x2
max-pool, conv 5x5x32 + tanh + 2x2 max-pool, FC 128 + tanh, FC 10, summed
softmax cross-entropy, SGD at eta = 0.01.  Every weight matrix (bias as an
always-on extra column) lives on a crossbar tile and is trained by the three
cycles of the paper:

* forward read ``y = clip(W x + sigma xi, +-alpha)`` with bound management
  (two-phase: a second read at 1/16 input scale replaces saturated rows;
  iterative: halve-and-retry until no row saturates, at most 10 times);
* backward (transpose) read of the error, noise-managed (input scaled by
  its max) and bound-managed like the forward;
* stochastic pulse update (Eq. 1): Bernoulli pulse streams of length BL on
  columns and rows with update management, coincidences counted per
  device, each applied with the device's own up/down step and a 30%
  cycle-to-cycle spread, then clipped at the device's own bound.

A conv layer is the same tile read over im2col columns (channel-major
feature order, positions in (image, row, column) order).  With ``devices``
> 1 a weight is that many physical rows whose reads are averaged.

Written in plain ``jax.numpy`` at ``HIGHEST`` matmul precision, with the
draws of ``counter_rng`` (the simulator's documented counter layout), so it
follows a run value for value.  It imports nothing of the program.
``dtype=bfloat16`` gives the lower-precision control.
"""

from __future__ import annotations

import os
import sys
from typing import Dict

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import counter_rng as crng  # noqa: E402

HI = jax.lax.Precision.HIGHEST
LAYERS = ("K1", "K2", "W3", "W4")
EPS = 1e-12
RETRY = 16.0


def _mm(eq, a, b, dt):
    return jnp.einsum(eq, a.astype(dt), b.astype(dt), precision=HI,
                      preferred_element_type=jnp.float32).astype(dt)


# ---------------------------------------------------------------------------
# The fabricated tiles (made by the benchmark from the seed; the program is
# handed the same arrays)
# ---------------------------------------------------------------------------

def make_state(key, config: Dict, layers: Dict) -> Dict[str, Dict]:
    """Initial weights and device maps of every tile, from ``key``.  The
    weights are drawn as the simulator's initialiser draws them: uniform
    over +-min(1/sqrt(columns), w_bound/2), the same on every device of a
    weight, clipped to each device's own bound."""
    dev = config["device_table1"]
    out = {}
    for i, name in enumerate(LAYERS):
        rows, cols = config["tiles"][name]
        d = int(layers[name]["devices"])
        k_w, k_dw, k_imb, k_bd = jax.random.split(jax.random.fold_in(key, i),
                                                  4)
        scale = min(cols ** -0.5, dev["w_bound"] / 2.0)
        w = jax.random.uniform(k_w, (rows, cols), jnp.float32, -scale, scale)
        w = jnp.tile(w, (d, 1))
        shape = (d * rows, cols)
        dw = dev["dw_min"] * (1.0 + dev["dw_min_dtod"]
                              * jax.random.normal(k_dw, shape))
        dw = jnp.maximum(dw, 0.01 * dev["dw_min"])
        r = jnp.clip(1.0 + dev["imbalance_dtod"]
                     * jax.random.normal(k_imb, shape), 0.5, 2.0)
        bound = dev["w_bound"] * (1.0 + dev["w_bound_dtod"]
                                  * jax.random.normal(k_bd, shape))
        bound = jnp.maximum(bound, 0.1 * dev["w_bound"])
        out[name] = {"w": jnp.clip(w, -bound, bound),
                     "dw_up": dw * jnp.sqrt(r), "dw_dn": dw / jnp.sqrt(r),
                     "bound": bound}
    return out


# ---------------------------------------------------------------------------
# Tile cycles
# ---------------------------------------------------------------------------

def _raw_read(w, x, key, sigma, alpha, dt):
    """One physical read of ``w`` (out, K) by rows ``x`` (N, K)."""
    y = _mm("nk,ok->no", x, w, dt)
    if sigma > 0:
        xi = crng.normal(crng.key_seed(key), crng.flat_counter(y.shape),
                         y.size)
        y = y + (sigma * xi).astype(dt)
    sat = jnp.any(jnp.abs(y) >= alpha, axis=-1)
    return jnp.clip(y, -alpha, alpha), sat


def managed_read(w, x, key, lay: Dict, dev: Dict, *, transpose: bool, dt):
    """Noise- and bound-managed read; ``transpose`` reads ``W^T``."""
    wt = w.T if transpose else w
    sigma, alpha = dev["read_noise"], dev["out_bound"]
    x = x.astype(dt)
    if lay["nm"] and (transpose or lay.get("nm_forward", False)):
        s0 = jnp.max(jnp.abs(x), axis=-1, keepdims=True)
        s0 = jnp.where(s0 > EPS, s0, 1.0).astype(dt)
    else:
        s0 = jnp.ones((x.shape[0], 1), dt)
    bm = lay["bm"]
    if bm == "two_phase":
        k1, k2 = jax.random.split(key)
        y1, sat1 = _raw_read(wt, x / s0, k1, sigma, alpha, dt)
        y2, _ = _raw_read(wt, x / (RETRY * s0), k2, sigma, alpha, dt)
        return jnp.where(sat1[:, None], y2 * RETRY, y1) * s0
    if bm == "iterative":
        key, k0 = jax.random.split(key)
        scale = s0[:, 0]
        y, sat = _raw_read(wt, x / scale[:, None], k0, sigma, alpha, dt)
        y = y * scale[:, None]

        def cond(c):
            n, _s, _y, sat, _k = c
            return jnp.logical_and(jnp.any(sat), n < lay["bm_max_iters"])

        def body(c):
            n, scale, _y, sat, k = c
            k, kr = jax.random.split(k)
            scale = jnp.where(sat, scale * 2.0, scale)
            y, sat = _raw_read(wt, x / scale[:, None], kr, sigma, alpha, dt)
            return n + 1, scale, y * scale[:, None], sat, k

        _, _, y, _, _ = jax.lax.while_loop(
            cond, body, (jnp.zeros((), jnp.int32), scale, y, sat, key))
        return y
    y, _ = _raw_read(wt, x / s0, key, sigma, alpha, dt)
    return y * s0


def forward_read(tile, x, key, lay, dev, dt):
    d = int(lay["devices"])
    y = managed_read(tile["w"], x, key, lay, dev, transpose=False, dt=dt)
    if d > 1:
        y = jnp.mean(y.reshape(y.shape[0], d, -1), axis=1)
    return y


def backward_read(tile, g, key, lay, dev, dt):
    d = int(lay["devices"])
    z = managed_read(tile["w"], jnp.tile(g, (1, d)), key, lay, dev,
                     transpose=True, dt=dt)
    return z / d if d > 1 else z


def pulse_update(tile, x, delta, key, lay, dev, lr, dt):
    """Stochastic pulse update by columns ``x`` (T, cols) and row errors
    ``delta`` (T, out); returns the updated physical weights."""
    d, bl = int(lay["devices"]), int(lay["bl"])
    delta = jnp.tile(delta, (1, d)).astype(dt)
    x = x.astype(dt)
    k_a, k_b, k_c = jax.random.split(key, 3)
    c = (jnp.asarray(lr, jnp.float32) / (bl * dev["dw_min"])) ** 0.5
    if lay["um"]:
        x_max = jnp.maximum(jnp.max(jnp.abs(x)).astype(jnp.float32), EPS)
        d_max = jnp.maximum(jnp.max(jnp.abs(delta)).astype(jnp.float32), EPS)
        m = jnp.clip(jnp.sqrt(d_max / x_max), 1e-3, 1e3)
        cx, cd = (c * m).astype(dt), (c / m).astype(dt)
    else:
        cx = cd = c.astype(dt)

    def streams(k, v, gain):
        p = jnp.clip(jnp.abs(gain * v), 0.0, 1.0)
        shape = (v.shape[0], bl, v.shape[1])
        u = crng.uniform(crng.key_seed(k), crng.flat_counter(shape)).astype(dt)
        fire = (u < p[:, None, :]).astype(dt)
        return (fire * jnp.sign(v)[:, None, :]).reshape(-1, v.shape[1])

    a = streams(k_a, x, cx)
    b = streams(k_b, delta, cd)
    net = _mm("tm,tn->mn", b, a, jnp.float32)
    total = _mm("tm,tn->mn", jnp.abs(b), jnp.abs(a), jnp.float32)
    up, dn = (0.5 * (total + net)).astype(dt), (0.5 * (total - net)).astype(dt)
    dw_up, dw_dn = tile["dw_up"].astype(dt), tile["dw_dn"].astype(dt)
    dw = up * dw_up - dn * dw_dn
    if dev["dw_min_ctoc"] > 0:
        xi = crng.normal(crng.key_seed(k_c), crng.flat_counter(dw.shape),
                         dw.size).astype(dt)
        dw = dw + dev["dw_min_ctoc"] * jnp.sqrt(
            up * dw_up ** 2 + dn * dw_dn ** 2) * xi
    bound = tile["bound"].astype(dt)
    return jnp.clip(tile["w"].astype(dt) + dw, -bound, bound)


# ---------------------------------------------------------------------------
# The network
# ---------------------------------------------------------------------------

def im2col(x, k):
    """(B, H, W, C) -> (B, H-k+1, W-k+1, C*k*k), channel-major features."""
    b, h, w, c = x.shape
    oh, ow = h - k + 1, w - k + 1
    taps = [x[:, i:i + oh, j:j + ow, :] for i in range(k) for j in range(k)]
    p = jnp.swapaxes(jnp.stack(taps, axis=-2), -1, -2)
    return p.reshape(b, oh, ow, c * k * k)


def _with_ones(x):
    return jnp.concatenate([x, jnp.ones((*x.shape[:-1], 1), x.dtype)], -1)


def _tanh_pool(a):
    t = jnp.tanh(a)
    b, h, w, c = t.shape
    return t.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def train_step(state, images, labels, key, config, layers, dt):
    """One SGD step on a batch; returns (new state, summed loss)."""
    dev, lr = config["device_table1"], config["lr"]
    lay = {n: layers[n] for n in LAYERS}
    ks = jax.random.split(key, 4)
    kf, kb, ku = {}, {}, {}
    for i, n in enumerate(LAYERS):
        kf[n], kb[n], ku[n] = jax.random.split(ks[i], 3)
    bsz = images.shape[0]
    x = images.astype(dt)

    c1 = _with_ones(im2col(x, 5))                        # (B, 24, 24, 26)
    cols1 = c1.reshape(-1, c1.shape[-1])
    a1 = forward_read(state["K1"], cols1, kf["K1"], lay["K1"], dev, dt)
    a1 = a1.reshape(bsz, 24, 24, -1)
    h1, pool1_vjp = jax.vjp(_tanh_pool, a1)              # (B, 12, 12, 16)
    c2, im2col2_vjp = jax.vjp(lambda v: im2col(v, 5), h1)
    cols2 = _with_ones(c2.reshape(-1, c2.shape[-1]))     # (B*64, 401)
    a2 = forward_read(state["K2"], cols2, kf["K2"], lay["K2"], dev, dt)
    a2 = a2.reshape(bsz, 8, 8, -1)
    h2, pool2_vjp = jax.vjp(_tanh_pool, a2)              # (B, 4, 4, 32)
    x3 = _with_ones(h2.reshape(bsz, -1))                 # (B, 513)
    h3 = jnp.tanh(forward_read(state["W3"], x3, kf["W3"], lay["W3"], dev, dt))
    x4 = _with_ones(h3)                                  # (B, 129)
    logits = forward_read(state["W4"], x4, kf["W4"], lay["W4"], dev, dt)

    logp = jax.nn.log_softmax(logits.astype(jnp.float32))
    loss = -jnp.sum(jnp.take_along_axis(logp, labels[:, None], -1))
    g4 = (jnp.exp(logp) - jax.nn.one_hot(labels, logits.shape[-1])).astype(dt)

    new = dict(state)
    z4 = backward_read(state["W4"], g4, kb["W4"], lay["W4"], dev, dt)
    new["W4"] = dict(state["W4"], w=pulse_update(
        state["W4"], x4, -g4, ku["W4"], lay["W4"], dev, lr, dt))
    g3 = (z4[:, :-1] * (1.0 - h3 ** 2)).astype(dt)
    z3 = backward_read(state["W3"], g3, kb["W3"], lay["W3"], dev, dt)
    new["W3"] = dict(state["W3"], w=pulse_update(
        state["W3"], x3, -g3, ku["W3"], lay["W3"], dev, lr, dt))
    (g2,) = pool2_vjp(z3[:, :-1].reshape(h2.shape).astype(dt))
    g2 = g2.reshape(-1, g2.shape[-1])                    # (B*64, 32)
    z2 = backward_read(state["K2"], g2, kb["K2"], lay["K2"], dev, dt)
    new["K2"] = dict(state["K2"], w=pulse_update(
        state["K2"], cols2, -g2, ku["K2"], lay["K2"], dev, lr, dt))
    (dh1,) = im2col2_vjp(z2[:, :-1].reshape(c2.shape).astype(dt))
    (g1,) = pool1_vjp(dh1.astype(dt))
    g1 = g1.reshape(-1, g1.shape[-1])                    # (B*576, 16)
    new["K1"] = dict(state["K1"], w=pulse_update(
        state["K1"], cols1, -g1, ku["K1"], lay["K1"], dev, lr, dt))
    return new, loss


def train_calls(state, chunks, k_data, k_train, first_epoch: int,
                n_calls: int, batch: int, config, layers,
                dtype=jnp.float32):
    """Follow ``n_calls`` calls of the epoch program: call ``c`` runs one
    epoch over ``chunks[c]`` (a permutation keyed by the epoch index, then
    one step per batch, step ``s`` keyed ``fold_in(k_train, epoch * steps
    + s)``).  Returns the weights after each call and each step's loss."""
    dt = jnp.dtype(dtype)

    @jax.jit
    def epoch(st, xs, ys, ep):
        n = xs.shape[0]
        spe = n // batch
        perm = jax.random.permutation(jax.random.fold_in(k_data, ep),
                                      n)[:spe * batch]
        xb = xs[perm].reshape(spe, batch, *xs.shape[1:])
        yb = ys[perm].reshape(spe, batch)
        keys = jax.vmap(lambda i: jax.random.fold_in(k_train, i))(
            ep * spe + jnp.arange(spe))

        def body(s, inp):
            return train_step(s, *inp, config, layers, dt)

        return jax.lax.scan(body, st, (xb, yb, keys))

    st = jax.tree_util.tree_map(lambda a: a.astype(dt), state)
    weights, losses = [], []
    for c in range(n_calls):
        xs, ys = chunks[first_epoch + c]
        st, loss = epoch(st, xs, ys, jnp.int32(first_epoch + c))
        weights.append({n: np.asarray(st[n]["w"].astype(jnp.float32))
                        for n in LAYERS})
        losses.append(np.asarray(loss))
    return weights, losses
