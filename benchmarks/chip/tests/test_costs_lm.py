"""Operations and bytes of the LM training cell's launches against hand
counts."""

from benchlib import common as C


def cost(kind, **launch):
    mod = C.load_module(C.bench_file("kernel_costs", kind + ".py"))
    return mod.cost(dict(launch, kind=kind))


def test_managed_read_deepseek_up_projection():
    # wi / wg forward read from a 4 x 2048-token step: 8192 rows x 4096
    # columns against 11008 outputs, one product for both two-phase reads;
    # bytes 4 * (array 45,088,768 + inputs 33,554,432 + outputs
    # 90,177,536 + flags 8,192)
    f, b = cost("managed_read", rows=8192, k=4096, out=11008)
    assert f == 2.0 * 8192 * 4096 * 11008 == 738_734_374_912.0
    assert b == 4.0 * (45_088_768 + 33_554_432 + 90_177_536 + 8_192) \
        == 675_315_712.0


def test_managed_read_deepseek_down_projection_transpose():
    # wi's transpose read: the 11008-wide error through the tile to 4096
    # columns, the same array and product as the forward read
    f, b = cost("managed_read", rows=8192, k=11008, out=4096)
    assert f == 738_734_374_912.0
    assert b == 4.0 * (45_088_768 + 8192 * 11008 + 8192 * 4096 + 8192)


def test_lm_driver_launches_per_step():
    """Per layer: 13 forward reads (``wo``'s is not recomputed), 7
    transpose reads and 7 pulse counts, as the step compiled for a v5e
    holds them (20 ``managed_read`` and 7 ``pulse_counts`` launches in the
    layer scans)."""
    drv = C.load_module(C.bench_file("drivers", "lm_train.py"))
    cell = C.load_cell("ds7b_analog_train_s2048")
    per = drv.launches_per_step(cell.config, cell.traffic)
    layers = cell.config["num_hidden_layers"]
    assert len(per["managed_read"]) == 20 * layers
    assert len(per["pulse_counts"]) == 7 * layers
    assert sum(l.get("transpose", False) for l in per["managed_read"]) \
        == 7 * layers
    assert {l["rows"] for l in per["managed_read"]} == {8192}
