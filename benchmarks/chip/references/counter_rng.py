"""Counter-hash random numbers, as the simulated RPU tiles draw them.

The simulator's read noise and pulse streams are a documented function of a
JAX key and a flat counter: the key's words are folded into one uint32
seed with a splitmix32 finaliser, each element's counter is its row-major
index in the drawn array, uniforms take the top 24 bits of
``mix(counter ^ mix(seed))`` and normals are Box-Muller over two counter
streams, the second offset by the array's element count.  Reproducing the
draws is what lets a plain reference follow the simulator's noisy reads and
stochastic updates value for value instead of in distribution only.

Seeds and counters here may be arrays: one seed per row, one counter base
per row, so that rows drawn by different calls (prefill and decode ticks)
sit side by side in one reference pass.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

_GOLDEN = np.uint32(0x9E3779B9)
_M1 = np.uint32(0x21F0AAAD)
_M2 = np.uint32(0x735A2D97)


def mix(x):
    x = (x + _GOLDEN).astype(jnp.uint32)
    x = (x ^ (x >> 16)) * _M1
    x = (x ^ (x >> 15)) * _M2
    return x ^ (x >> 15)


def key_seed(key):
    """uint32 seed word of a JAX key (all of its words folded in)."""
    data = jax.random.key_data(key).astype(jnp.uint32)
    seed = jnp.zeros(data.shape[:-1], jnp.uint32)
    for i in range(data.shape[-1]):
        seed = mix(seed ^ data[..., i])
    return seed


def _unit(bits):
    return (bits >> 8).astype(jnp.float32) * (1.0 / (1 << 24))


def uniform(seed, counter):
    """U[0, 1) at ``counter`` (uint32 array) under ``seed``."""
    return _unit(mix(counter ^ mix(seed)))


def normal(seed, counter, total):
    """N(0, 1) at ``counter`` of an array of ``total`` elements."""
    if isinstance(total, (int, np.integer)):
        total = np.uint32(int(total) & 0xFFFFFFFF)
    else:
        total = jnp.asarray(total).astype(jnp.uint32)
    seed_m = mix(seed)
    u1 = jnp.maximum(_unit(mix(counter ^ seed_m)), 1e-7)
    u2 = _unit(mix((counter + total) ^ seed_m))
    return jnp.sqrt(-2.0 * jnp.log(u1)) * jnp.cos((2.0 * np.pi) * u2)


def flat_counter(shape):
    """Row-major flat index of every element of ``shape`` (uint32)."""
    idx = jnp.zeros(shape, jnp.uint32)
    stride = 1
    for d in range(len(shape) - 1, -1, -1):
        idx = idx + jax.lax.broadcasted_iota(jnp.uint32, shape, d) \
            * np.uint32(stride & 0xFFFFFFFF)
        stride *= shape[d]
    return idx
