"""Operations and bytes per launch against hand counts."""

import pytest

from benchlib import common as C
from benchlib import roofline


def cost(kind, **launch):
    mod = C.load_module(C.bench_file("kernel_costs", kind + ".py"))
    return mod.cost(dict(launch, kind=kind))


# LeNet K2: a 32 x 401 tile on 13 devices (416 physical rows), read by
# 8 images x 8 x 8 positions (512 rows).

def test_noisy_read_k2_13_devices():
    # forward read: 512 rows x 401 columns against 416
    # physical rows; bytes 4 * (array 166816 + inputs 205312 + outputs 212992)
    f, b = cost("noisy_read", rows=512, k=401, out=416)
    assert f == 2.0 * 512 * 401 * 416 == 170_819_584.0
    assert b == 4.0 * (166_816 + 205_312 + 212_992) == 2_340_480.0


def test_pulse_counts_k2_13_devices():
    # streams 512 x (416 + 401), counts 2 x 416 x 401
    f, b = cost("pulse_counts", rows=512, k=401, out=416, bl=1)
    assert f == 4.0 * 416 * 401 * 512
    assert b == 4.0 * (512 * (416 + 401) + 2 * 416 * 401)


@pytest.mark.parametrize("k,out", [(4096, 11008), (11008, 4096)])
def test_noisy_read_deepseek_decode(k, out):
    # 8 decode rows through the up (4096 -> 11008) or down (11008 -> 4096)
    # projection: 721,420,288 flops; the f32 array dominates the bytes
    f, b = cost("noisy_read", rows=8, k=k, out=out)
    assert f == 721_420_288.0
    assert b == 4.0 * (45_088_768 + 8 * k + 8 * out) == 180_838_400.0


def test_noisy_read_deepseek_prefill_is_compute_bound():
    f, b = cost("noisy_read", rows=1024, k=4096, out=11008)
    pk = roofline.peaks("TPU v5 lite")
    assert f / pk["flops_per_s"] > b / pk["hbm_bytes_per_s"]


def test_unknown_device_kind_is_an_error():
    with pytest.raises(C.BenchError):
        roofline.peaks("TPU v99")
