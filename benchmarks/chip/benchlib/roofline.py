"""Peaks by ``device_kind`` and a kernel kind's share of its roofline."""

from __future__ import annotations

from typing import Dict, Optional

from benchlib import common as C


def peaks(device_kind: str) -> Dict[str, float]:
    """The chip's published peaks; a kind missing from the table is an
    error, never a default."""
    table = C.load_json(C.bench_file("peaks.json"))["kinds"]
    if device_kind not in table:
        raise C.BenchError(f"no peaks for device kind {device_kind!r} in "
                           f"peaks.json (known: {sorted(table)})")
    return table[device_kind]


def work(launches) -> tuple:
    """Summed (flops, bytes, launches) of ``[[count, launch], ...]``; each
    launch dict is costed by ``kernel_costs/<kind>.py``."""
    flops = nbytes = n = 0.0
    for count, launch in launches:
        mod = C.load_module(C.bench_file("kernel_costs",
                                         launch["kind"] + ".py"))
        f, b = mod.cost(launch)
        flops += count * f
        nbytes += count * b
        n += count
    return flops, nbytes, n


def kernel_share(readings, kind: str) -> Optional[float]:
    """% of the roofline that ``kind``'s launches in the traced window
    reach: the least time the chip could take for their operations and
    bytes (the larger of the compute and the bandwidth bound) over their
    device time.  Where the trace holds more or fewer launches than the
    host accounted (a call cut by the trace's edge), the work is scaled
    by the ratio of the counts.  None where the trace has no such launch."""
    red = readings.get("trace")
    launches = readings.get("traced_launches", {}).get(kind)
    if not red or not launches or red["kind_s"].get(kind, 0.0) <= 0.0:
        return None
    flops, nbytes, n = work(launches)
    seen = red["kind_launches"].get(kind, 0)
    if n <= 0 or seen <= 0:
        return None
    pk = peaks(readings["device"]["kind"])
    least = max(flops / pk["flops_per_s"], nbytes / pk["hbm_bytes_per_s"])
    return 100.0 * least * (seen / n) / red["kind_s"][kind]
