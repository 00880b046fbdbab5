"""The LM training cell at a small size on the CPU (Pallas kernels
interpreted): a sound run comes out correct, one with the timed path
broken underneath does not.  The harness's look for a chip is skipped."""

import pytest

from benchlib import common as C
from benchlib import harness as H

ARRAY = 64
#: the smoke deepseek_7b's sizes; the array limit lowered so that 172
#: columns read in segments as 11008 do against 4096
SMALL_CONF = {"hidden_size": 64, "intermediate_size": 172,
              "num_attention_heads": 4, "num_key_value_heads": 4,
              "num_hidden_layers": 2, "vocab_size": 256,
              "max_array": ARRAY}
LIMIT = f":max_array_rows={ARRAY}:max_array_cols={ARRAY}"


def small_run(fault=None, seed=2 ** 31 + 17):
    cell = C.load_cell("ds7b_analog_train_s2048")
    cell.config.update(SMALL_CONF)
    cell.traffic.update({"seq": 32, "batch": 4})
    cell.traffic["policy"] = ",".join(
        f"{p}{LIMIT}" for p in cell.traffic["policy"].split(","))
    run = H.Run(cell, seed, 1.0, False, fault=fault)
    H.drive(run)
    return H.finish(run)


@pytest.mark.parametrize("fault,correct", [
    (None, True), ("unchanged", False), ("half_batch", False)])
def test_lm_fault_fails_sound_passes(fault, correct):
    res = small_run(fault)
    assert res["correct"] is correct, res["checks"]
    assert list(res)[-1] == "checks"
