"""SwiGLU MLP block (dense FFN of every assigned arch)."""

from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import shard
from repro.models import layers as L

Array = jax.Array


def init(key, cfg: ModelConfig, d_ff: int = 0):
    d = cfg.d_model
    f = d_ff or cfg.d_ff
    ks = jax.random.split(key, 3)
    # digital init; analog conversion is policy-driven (repro.analog)
    p: Dict[str, Any] = {}
    a: Dict[str, Any] = {}
    p["wi"], a["wi"] = L.dense_init(ks[0], d, f, ("embed", "mlp"),
                                    cfg.param_dtype)
    p["wg"], a["wg"] = L.dense_init(ks[1], d, f, ("embed", "mlp"),
                                    cfg.param_dtype)
    p["wo"], a["wo"] = L.dense_init(ks[2], f, d, ("mlp", "embed"),
                                    cfg.param_dtype)
    return p, a


def apply(p, x: Array, cfg: ModelConfig, akey=None) -> Array:
    # One batched split instead of three serial fold_ins: the scan engine
    # feeds a fresh key per step, so per-layer keys are pure derivation and
    # a single threefry call covers all three dense reads.
    ks = None if akey is None else jax.random.split(akey, 3)

    def dense(name, xx, i):
        k = None if ks is None else ks[i]
        with jax.named_scope(name):
            return L.dense_apply(p[name], xx, key=k)

    h = jax.nn.silu(dense("wg", x, 0)) * dense("wi", x, 1)
    h = shard(h, "batch", "seq", "mlp")
    y = dense("wo", h, 2)
    return shard(y, "batch", "seq", "embed_act")
