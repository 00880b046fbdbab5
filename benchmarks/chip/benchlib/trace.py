"""Reduction of a profiler trace (``.xplane.pb``) to device metrics.

* busy: the union of the intervals in which an operation ran on a device,
  inside the traced window, averaged over the devices used;
* per-kind kernel time: the summed device time of the events of each Pallas
  kernel kind (the ``launch_name`` labels of ``kernels/ops.py``: the
  kernel's name precedes ``/pallas_call`` in the op's metadata);
* top device ops by time, and the idle gaps between busy intervals, each
  attributed to the benchmark's host span (``bench:<name>``) that was open
  during it.

The traced window is the host annotation ``bench:window``; device and host
events of one trace share its clock.
"""

from __future__ import annotations

import bisect
import gzip
import os
import re
from collections import defaultdict
from typing import Dict, List, Optional, Tuple

_KIND_RE = re.compile(r"(?:^|/)([a-z][a-z_]*?)(?:__\w+)?/pallas_call")
#: an XLA op named for its Pallas kernel: ``%bwd_update_conv.5 = ...``
_OP_RE = re.compile(r"^%([a-z][a-z_]*?)(?:__\w+)?(?:\.\d+)? = ")
_DEVICE_RE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"


def _strings(ev) -> List[str]:
    out = [ev.name]
    try:
        for _k, v in ev.stats:
            if isinstance(v, str):
                out.append(v)
    except Exception:           # events without readable stats
        pass
    return out


def kernel_kinds() -> set:
    """The kernel kinds the benchmark can cost (``kernel_costs/*.py``)."""
    from benchlib import common as C
    return {os.path.splitext(f)[0] for f in
            os.listdir(C.bench_file("kernel_costs")) if f.endswith(".py")}


def kind_of(ev, kinds=None) -> Optional[str]:
    """The Pallas kernel kind of a device op: the op's own name on the TPU
    (``%noisy_read.79``), else the kernel name before ``/pallas_call`` in
    its metadata."""
    m = _OP_RE.match(ev.name)
    if m and m.group(1) in (kinds if kinds is not None else kernel_kinds()):
        return m.group(1)
    for s in _strings(ev):
        m = _KIND_RE.search(s)
        if m:
            return m.group(1)
    return None


def union(intervals: List[Tuple[float, float]]) -> List[Tuple[float, float]]:
    merged: List[List[float]] = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return [(s, e) for s, e in merged]


def _clip(iv, lo, hi):
    return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


def _label(spans, starts, a, b) -> str:
    """Name of the host span that overlaps ``[a, b]`` most (spans do not
    nest, so only the few starting just before ``b`` can)."""
    i = bisect.bisect_right(starts, b)
    best, label = 0.0, "no host span"
    for s, e, n in spans[max(0, i - 4):i]:
        ov = min(b, e) - max(a, s)
        if ov > best:
            best, label = ov, n
    return label


def short_name(name: str) -> str:
    """``%fusion.12 = f32[...] fusion(...)`` -> ``%fusion.12``."""
    return name.split(" = ", 1)[0][:80]


def self_times(evs) -> List[float]:
    """Each event's duration less its nested children's (ops such as a
    ``while`` hold their body's ops on the same line)."""
    order = sorted(range(len(evs)), key=lambda i: (evs[i][0], -evs[i][1]))
    out = [e - s for s, e, *_ in evs]
    stack: List[int] = []
    for i in order:
        s, e = evs[i][0], evs[i][1]
        while stack and evs[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            out[stack[-1]] -= e - s
        stack.append(i)
    return out


def reduce_events(device_events: Dict[int, List[tuple]],
                  host_spans: List[tuple],
                  window: Tuple[float, float]) -> Dict:
    """Core reduction over plain tuples (testable without a trace file).

    ``device_events``: device id -> [(start_ns, end_ns, name, kind)];
    ``host_spans``: [(start_ns, end_ns, name)]; ``window``: (lo, hi) ns.
    """
    lo, hi = window
    busy = []
    kind_ns: Dict[str, float] = defaultdict(float)
    kind_n: Dict[str, int] = defaultdict(int)
    op_ns: Dict[str, float] = defaultdict(float)
    gaps_by: Dict[str, float] = defaultdict(float)
    first = min(device_events) if device_events else None
    for dev, evs in sorted(device_events.items()):
        iv = _clip([(s, e) for s, e, _n, _k in evs], lo, hi)
        merged = union(iv)
        busy.append(sum(e - s for s, e in merged))
        selfs = self_times(evs)
        for (s, e, name, kind), own in zip(evs, selfs):
            if e <= lo or s >= hi:
                continue
            d = min(e, hi) - max(s, lo)
            if kind:
                kind_ns[kind] += d
                kind_n[kind] += 1
            op_ns[kind or short_name(name)] += min(own, d)
        if dev == first:
            edges = [lo] + [x for se in merged for x in se] + [hi]
            spans = sorted((s, e, n) for s, e, n in host_spans
                           if n != "window")
            starts = [s for s, _e, _n in spans]
            for a, b in zip(edges[0::2], edges[1::2]):
                if b > a:
                    gaps_by[_label(spans, starts, a, b)] += b - a
    n_dev = max(1, len(device_events))
    win = hi - lo
    top = sorted(op_ns.items(), key=lambda kv: -kv[1])[:10]
    gaps = sorted(gaps_by.items(), key=lambda kv: -kv[1])[:10]
    return {"busy_s": sum(busy) / n_dev * 1e-9, "window_s": win * 1e-9,
            "kind_s": {k: v * 1e-9 for k, v in kind_ns.items()},
            "kind_launches": dict(kind_n),
            "top_ops": [[k, v * 1e-9] for k, v in top],
            "idle_gaps": [[k, v * 1e-9] for k, v in gaps]}


def read_file(path: str, n_devices: int):
    """(device_events, host_spans, window) from an ``.xplane.pb``."""
    from jax.profiler import ProfileData
    if path.endswith(".gz"):
        with gzip.open(path, "rb") as f:
            pd = ProfileData.from_serialized_xspace(f.read())
    else:
        pd = ProfileData.from_file(path)
    kinds = kernel_kinds()
    dev_events: Dict[int, List[tuple]] = {}
    host: List[tuple] = []
    for plane in pd.planes:
        m = _DEVICE_RE.match(plane.name)
        if m:
            dev = int(m.group(1))
            if dev >= n_devices:
                continue
            evs = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    s = float(ev.start_ns)
                    evs.append((s, s + float(ev.duration_ns), ev.name,
                                kind_of(ev, kinds)))
            dev_events[dev] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith("bench:"):
                        s = float(ev.start_ns)
                        host.append((s, s + float(ev.duration_ns),
                                     ev.name[len("bench:"):]))
    wins = [(s, e) for s, e, n in host if n == "window"]
    if wins:
        window = (wins[0][0], wins[0][1])
    else:
        allev = [x for evs in dev_events.values() for x in evs]
        window = (min(x[0] for x in allev), max(x[1] for x in allev))
    return dev_events, host, window


def reduce_file(path: str, n_devices: int = 1) -> Dict:
    dev_events, host, window = read_file(path, n_devices)
    if not any(dev_events.values()):
        raise RuntimeError(f"no device ops in trace {path}")
    return reduce_events(dev_events, host, window)
