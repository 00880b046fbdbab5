"""The LeNet epoch program names its work by layer, RPU cycle and
conv-mapping stage.

``models/lenet.py`` opens a ``jax.named_scope`` per tile (``K1`` .. ``W4``);
``core/analog_linear.py`` and ``core/conv_mapping.py`` open one per cycle
(``forward``, ``backward``, ``update``, ``backward_update``) and per
conv-mapping stage (``im2col``, ``col2im``).  XLA keeps the name stack as
each op's ``op_name``, which profiler traces show, so device time can be
read per layer and cycle.  The custom-VJP backward rules are traced while
transposing, after the layer's ``with`` block has closed; the name stack
still carries the layer there (``transpose(jvp(K2))/backward/...``).

These tests compile the epoch program on the CPU (kernels interpreted) and
read the scope paths off the compiled HLO.  Launch names and budgets are
guarded by ``tests/test_audit.py``.
"""

import re

import jax
import jax.numpy as jnp
import pytest

from repro.analog.presets import parse_policy
from repro.models import lenet
from repro.optim import analog_sgd
from repro.train import engine

LAYERS = ("K1", "K2", "W3", "W4")
KNOWN = set(LAYERS) | {"forward", "backward", "update", "backward_update",
                       "im2col", "col2im"}
#: the benchmark's recipe cell (benchmarks/chip/traffic/recipe_b8.json)
RECIPE_POLICY = "K2=k2_multi_device:use_pallas=true,*=managed:use_pallas=true"
BATCH = 8
_WRAPPER_RE = re.compile(r"\b(?:jvp|transpose|vmap)\(([^()]*)\)")


def _scope_paths(hlo: str) -> set:
    """Known scope components of every ``op_name`` in compiled HLO text,
    in order, transform wrappers stripped."""
    paths = set()
    for name in re.findall(r'op_name="([^"]*)"', hlo):
        name = name.split(";", 1)[0]
        prev = None
        while prev != name:
            prev, name = name, _WRAPPER_RE.sub(r"\1", name)
        paths.add(tuple(p for p in name.split("/") if p in KNOWN))
    return paths


def _compiled_epoch_paths(policy: str) -> set:
    cfg = lenet.LeNetConfig.from_policy(parse_policy(policy))
    opt = analog_sgd()
    run_epoch = engine.make_cnn_epoch_fn(cfg, opt, batch=BATCH)
    key = jax.eval_shape(lambda: jax.random.key(0))
    params = jax.eval_shape(lambda k: lenet.init(k, cfg), key)
    opt_state = jax.eval_shape(opt.init, params)
    compiled = run_epoch.lower(
        params, opt_state,
        jax.ShapeDtypeStruct((BATCH, 28, 28, 1), jnp.float32),
        jax.ShapeDtypeStruct((BATCH,), jnp.int32), key, key,
        jax.ShapeDtypeStruct((), jnp.int32)).compile()
    return _scope_paths(compiled.as_text())


@pytest.fixture(scope="module")
def recipe_paths():
    return _compiled_epoch_paths(RECIPE_POLICY)


@pytest.fixture(scope="module")
def fused_paths():
    from repro.analysis.targets import LENET_POLICY
    return _compiled_epoch_paths(LENET_POLICY)


def _under(paths, *prefix) -> bool:
    return any(p[:len(prefix)] == prefix for p in paths)


@pytest.mark.parametrize("cycle", ["forward", "update"])
@pytest.mark.parametrize("layer", LAYERS)
def test_recipe_forward_and_update_per_layer(recipe_paths, layer, cycle):
    assert _under(recipe_paths, layer, cycle)


@pytest.mark.parametrize("layer", ["K2", "W3", "W4"])
def test_recipe_backward_per_layer(recipe_paths, layer):
    # K1's backward read may be gone: nothing reads the image gradient
    assert _under(recipe_paths, layer, "backward")


@pytest.mark.parametrize("layer", ["K1", "K2"])
def test_recipe_im2col_under_conv_layers(recipe_paths, layer):
    assert any(p[0] == layer and "im2col" in p for p in recipe_paths if p)


def test_recipe_col2im_under_k2_backward(recipe_paths):
    assert _under(recipe_paths, "K2", "backward", "col2im")


def test_recipe_no_fused_cycle_and_no_stage_outside_convs(recipe_paths):
    """The recipe bypasses the fused kernels, and the dense layers run no
    conv mapping."""
    assert not any("backward_update" in p for p in recipe_paths)
    assert not any(p and p[0] in ("W3", "W4") and
                   {"im2col", "col2im"} & set(p) for p in recipe_paths)


@pytest.mark.parametrize("layer", LAYERS)
def test_fused_backward_update_per_layer(fused_paths, layer):
    """The fused backward+update launch runs under ``backward_update``,
    and the separate cycles do not run."""
    assert _under(fused_paths, layer, "forward")
    assert _under(fused_paths, layer, "backward_update")
    assert not _under(fused_paths, layer, "update")


def test_fused_col2im_under_k2_backward_update(fused_paths):
    assert _under(fused_paths, "K2", "backward_update", "col2im")
