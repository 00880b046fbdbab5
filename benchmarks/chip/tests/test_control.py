"""The control — the plain reference computed in bfloat16, put in the
program's place in the harness's own check — comes out not correct, at the
cell's own size (the batch, the 64-step epoch calls, the tiles).

It runs where the cell runs, on a TPU: on the CPU the same bfloat16
reference reads inside the program's spread (PERF.md, Open questions)."""

import jax
import pytest

from benchlib import common as C
from benchlib import harness as H


@pytest.mark.parametrize("seed", [2 ** 31 + 51, 2 ** 31 + 52, 2 ** 31 + 53])
def test_bf16_control_fails(seed):
    if jax.devices()[0].platform != "tpu":
        pytest.skip("the bfloat16 control is read on the chip the cell runs on")
    run = H.Run(C.load_cell("lenet_recipe_b8"), seed, 2.0, False)
    run.control = True
    H.drive(run)
    res = H.finish(run)
    assert res["correct"] is False, res["checks"]
