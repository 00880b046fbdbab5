"""The analog LM train step names its work by projection and RPU cycle.

``models/attention.py`` and ``models/mlp.py`` run each block projection
under a ``jax.named_scope`` of its parameter name (``q k v o wi wg wo``);
``core/analog_linear.py`` opens the cycle scopes inside (``forward``,
``backward``, ``update``).  XLA keeps the name stack as each op's
``op_name``, which profiler traces show, so device time can be read per
projection and cycle (``benchmarks/chip/benchlib/scopes.py``).

The smoke deepseek_7b step with the LM training cell's policy is compiled
on the CPU (kernels interpreted) and the scope paths are read off the
compiled HLO.
"""

import dataclasses
import re

import jax
import jax.numpy as jnp
import pytest

from repro.configs import registry
from repro.train import lm

PROJ = ("q", "k", "v", "o", "wi", "wg", "wo")
CYCLES = ("forward", "backward", "update")
KNOWN = set(PROJ) | set(CYCLES) | {"backward_update"}
#: the benchmark's LM training cell (traffic/lm_train_s2048_b4.json)
POLICY = ("*/attn/*=lm_managed:use_pallas=true:bm_mode=two_phase,"
          "*/mlp/*=lm_managed:use_pallas=true:bm_mode=two_phase")
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


@pytest.fixture(scope="module")
def paths():
    cfg = registry.get_config("deepseek_7b", smoke=True,
                              analog_policy=POLICY)
    cfg = dataclasses.replace(cfg, param_dtype=jnp.float32)
    multi, _ = lm.make_scan_train_step(cfg)
    key = jax.eval_shape(lambda: jax.random.key(0))
    params, opt_state = jax.eval_shape(
        lambda k: lm.init_train_state(k, cfg)[:2], key)
    batch = {"tokens": jax.ShapeDtypeStruct((2, 2, 17), jnp.int32)}
    keys = jax.eval_shape(lambda: jax.random.split(jax.random.key(0), 2))
    compiled = jax.jit(multi).lower(params, opt_state, batch,
                                    keys).compile()
    return _scope_paths(compiled.as_text())


@pytest.mark.parametrize("cycle", CYCLES)
@pytest.mark.parametrize("proj", PROJ)
def test_every_projection_names_each_cycle(paths, proj, cycle):
    assert any(p[:2] == (proj, cycle) for p in paths)


def test_cycles_only_under_projections(paths):
    """No cycle runs outside a projection scope: the whole analog work of
    the step is attributed to a projection."""
    assert not any(p and p[0] in CYCLES for p in paths)
