"""The paper's MNIST CNN (LeNet-5-like) on RPU tiles.

Architecture (Results section): conv 5x5x16 + tanh + maxpool 2x2 ->
conv 5x5x32 + tanh + maxpool 2x2 -> flatten(512) -> FC 128 tanh -> FC 10
softmax.  Trainable parameters (incl. biases) live in four crossbar tiles:

    K1: 16 x 26   (5*5*1  + 1)     K2: 32 x 401  (5*5*16 + 1)
    W3: 128 x 513 (512 + 1)        W4: 10 x 129  (128 + 1)

Built on the unified analog API (``repro.analog``): every tile is an
:class:`~repro.analog.modules.AnalogState` initialised through
``AnalogConv2d`` / ``AnalogLinear``, and per-layer device configs resolve
through an :class:`~repro.analog.policy.AnalogPolicy` — the paper's
selective per-layer experiments (Fig. 4: eliminate variations on K1/K2
only, 13-device mapping on K2 only) as ordered pattern rules::

    LeNetConfig.from_policy(parse_policy("K2=k2_multi_device,*=managed"))

A layer a policy resolves to *digital* (explicit ``digital`` rule or no
match) runs the exact FP path while its siblings stay analog.  The legacy
``layer_cfgs`` dict keyed on ``("K1","K2","W3","W4")`` still works as a
deprecated shim (it becomes an exact-name policy internally);
``mode='digital'`` gives the all-FP baseline with standard autodiff + SGD.
"""

from __future__ import annotations

import dataclasses
from typing import Dict, Mapping, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp

from repro.analog.modules import AnalogConv2d, AnalogLinear, AnalogState
from repro.analog.policy import AnalogPolicy
from repro.core import conv_mapping
from repro.core.device import RPUConfig

Array = jax.Array
LAYERS = ("K1", "K2", "W3", "W4")
Padding = Union[str, Sequence[Tuple[int, int]]]


@dataclasses.dataclass(frozen=True)
class LeNetConfig:
    mode: str = "analog"                     # 'analog' | 'digital'
    lr: float = 0.01                         # paper's eta
    # Per-tile device configs, one of (policy wins when both are set):
    #   policy     — AnalogPolicy over the layer names "K1".."W4" (the API)
    #   layer_cfgs — DEPRECATED literal dict shim; becomes an exact-name
    #                policy internally (docs/architecture.md, Analog API)
    policy: Optional[AnalogPolicy] = None
    layer_cfgs: Optional[Mapping[str, RPUConfig]] = None
    # conv padding for K1/K2: the lax names or explicit per-dim pairs
    # ((top, bottom), (left, right)) — e.g. ((2, 2), (2, 2)) trains the
    # SAME-padded 28x28 -> 14x14 -> 7x7 variant; init() sizes W3 from the
    # resulting geometry.  Default reproduces the paper (VALID).
    conv_padding: Padding = "VALID"

    # --- per-layer resolution ------------------------------------------------
    def resolved(self, layer: str) -> Optional[RPUConfig]:
        """Device config for one tile; ``None`` means the layer is digital
        (only possible under a policy — the legacy paths always resolve)."""
        if self.policy is not None:
            return self.policy.resolve(layer)
        if self.layer_cfgs is not None:
            return self.layer_cfgs.get(layer, RPUConfig())
        return RPUConfig()

    def cfg(self, layer: str) -> RPUConfig:
        """Legacy accessor: the tile's config, defaulted for digital
        layers (their state still needs a device population to exist)."""
        r = self.resolved(layer)
        return r if r is not None else RPUConfig()

    def layer_mode(self, layer: str) -> str:
        """'digital' | 'analog' for one tile under the global mode +
        per-layer policy resolution."""
        if self.mode == "digital":
            return "digital"
        if self.policy is not None and self.policy.resolve(layer) is None:
            return "digital"
        return self.mode

    def label(self, layer: str) -> str:
        return self.policy.label_for(layer) if self.policy is not None \
            else layer

    # --- constructors --------------------------------------------------------
    @staticmethod
    def uniform(cfg: RPUConfig, mode: str = "analog",
                lr: float = 0.01) -> "LeNetConfig":
        return LeNetConfig(mode=mode, lr=lr,
                           layer_cfgs={l: cfg for l in LAYERS})

    @staticmethod
    def from_policy(policy: AnalogPolicy, mode: str = "analog",
                    lr: float = 0.01,
                    conv_padding: Padding = "VALID") -> "LeNetConfig":
        return LeNetConfig(mode=mode, lr=lr, policy=policy,
                           conv_padding=conv_padding)

    def replace_layer(self, layer: str, cfg: RPUConfig) -> "LeNetConfig":
        if self.policy is not None:
            return dataclasses.replace(
                self, policy=self.policy.prepend(layer, cfg, layer))
        d = dict(self.layer_cfgs)
        d[layer] = cfg
        return dataclasses.replace(self, layer_cfgs=d)

    def with_stream_chunks(self, update_chunk: Optional[int] = None,
                           conv_stream_chunk: Optional[int] = None
                           ) -> "LeNetConfig":
        """Enable the streaming (constant-memory) pipeline on every tile —
        bit-identical training, bounded pulse-stream/patch live bytes."""
        if self.policy is not None:
            return dataclasses.replace(self, policy=self.policy.map_configs(
                lambda c: c.with_streaming(update_chunk, conv_stream_chunk)))
        d = {l: c.with_streaming(update_chunk, conv_stream_chunk)
             for l, c in (self.layer_cfgs or
                          {l: RPUConfig() for l in LAYERS}).items()}
        return dataclasses.replace(self, layer_cfgs=d)


def _pooled_conv_shape(hw: Tuple[int, int], in_c: int, kernel: int,
                       padding: Padding) -> Tuple[int, int]:
    """(H, W) after one conv (stride 1) + 2x2/2 maxpool."""
    g = conv_mapping.conv_geometry((1, hw[0], hw[1], in_c), kernel,
                                   padding=padding)
    if g.oh % 2 or g.ow % 2:
        raise ValueError(
            f"conv output {g.oh}x{g.ow} (padding {padding!r}) is not "
            "2x2-poolable; pick a padding that yields even dims")
    return g.oh // 2, g.ow // 2


def feature_sizes(cfg: LeNetConfig, hw: Tuple[int, int] = (28, 28)
                  ) -> Tuple[Tuple[int, int], Tuple[int, int], int]:
    """Post-pool spatial dims after K1 and K2, and the W3 fan-in."""
    p1 = _pooled_conv_shape(hw, 1, 5, cfg.conv_padding)
    p2 = _pooled_conv_shape(p1, 16, 5, cfg.conv_padding)
    return p1, p2, p2[0] * p2[1] * 32


def init(key: Array, cfg: LeNetConfig) -> Dict[str, AnalogState]:
    k1, k2, k3, k4 = jax.random.split(key, 4)
    _, _, flat = feature_sizes(cfg)
    pad = cfg.conv_padding
    return {
        "K1": AnalogConv2d.init(k1, 1, 16, 5, cfg.cfg("K1"), padding=pad,
                                label=cfg.label("K1")),
        "K2": AnalogConv2d.init(k2, 16, 32, 5, cfg.cfg("K2"), padding=pad,
                                label=cfg.label("K2")),
        "W3": AnalogLinear.init(k3, flat, 128, cfg.cfg("W3"),
                                label=cfg.label("W3")),
        "W4": AnalogLinear.init(k4, 128, 10, cfg.cfg("W4"),
                                label=cfg.label("W4")),
    }


def _maxpool2(x: Array) -> Array:
    # Reshape-based 2x2/2 pooling: identical to reduce_window forward, but
    # its autodiff transpose is a cheap mask instead of SelectAndScatter
    # (which dominates the backward cycle on XLA:CPU).
    b, h, w, c = x.shape
    return x.reshape(b, h // 2, 2, w // 2, 2, c).max(axis=(2, 4))


def apply(params: Dict[str, AnalogState], images: Array,
          key: Optional[Array], cfg: LeNetConfig) -> Array:
    """images (B, 28, 28, 1) -> logits (B, 10).

    ``key`` seeds the analog read/update noise; it may be ``None`` in
    digital mode (the FP path draws no randomness), which lets the scan
    engine feed batched per-step keys only where they are consumed.
    """
    if key is None:
        if cfg.mode != "digital":
            raise ValueError("analog mode requires a PRNG key")
        key = jax.random.key(0)  # digital; lint: fresh-key-ok
    ks = jax.random.split(key, 4)
    lr = cfg.lr
    # apply-time config/padding overrides keep post-init retrofits
    # (with_stream_chunks on an existing run) and the legacy semantics
    # where the LeNetConfig, not the state, is the source of truth.
    # Each tile runs under a name scope of its layer key, so device ops
    # read ``K2/backward/col2im/...`` in compiled HLO and profiler traces
    # (the cycle and conv-mapping scopes live in core/).
    with jax.named_scope("K1"):
        h = AnalogConv2d.apply(params["K1"], images, ks[0], lr=lr,
                               mode=cfg.layer_mode("K1"), cfg=cfg.cfg("K1"),
                               padding=cfg.conv_padding)
    h = _maxpool2(jnp.tanh(h))                       # (B, 12, 12, 16)
    with jax.named_scope("K2"):
        h = AnalogConv2d.apply(params["K2"], h, ks[1], lr=lr,
                               mode=cfg.layer_mode("K2"), cfg=cfg.cfg("K2"),
                               padding=cfg.conv_padding)
    h = _maxpool2(jnp.tanh(h))                       # (B, 4, 4, 32)
    h = h.reshape(h.shape[0], -1)                    # (B, 512 for VALID)
    with jax.named_scope("W3"):
        h = AnalogLinear.apply(params["W3"], h, ks[2], lr=lr,
                               mode=cfg.layer_mode("W3"), cfg=cfg.cfg("W3"))
    h = jnp.tanh(h)
    with jax.named_scope("W4"):
        logits = AnalogLinear.apply(params["W4"], h, ks[3], lr=lr,
                                    mode=cfg.layer_mode("W4"),
                                    cfg=cfg.cfg("W4"))   # (B, 10)
    return logits


def loss_fn(params, images, labels, key, cfg: LeNetConfig) -> Array:
    """Summed softmax cross-entropy.

    Sum (not mean) over the batch keeps each image's pulse-update magnitude
    identical to the paper's minibatch-of-1 training (each sample's error
    vector delta enters the update cycle unscaled; the batched pulse
    contraction then matches serial per-image updates — DESIGN.md §8).
    """
    logits = apply(params, images, key, cfg)
    logp = jax.nn.log_softmax(logits)
    nll = -jnp.take_along_axis(logp, labels[:, None], axis=-1)
    return jnp.sum(nll)


def accuracy(params, images, labels, key, cfg: LeNetConfig) -> Array:
    """Noisy-forward accuracy — inference runs on the same analog arrays."""
    logits = apply(params, images, key, cfg)
    return jnp.mean((jnp.argmax(logits, -1) == labels).astype(jnp.float32))
