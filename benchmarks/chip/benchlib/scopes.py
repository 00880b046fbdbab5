"""Device time by program scope: layer x RPU cycle x conv-mapping stage.

The program names its work with ``jax.named_scope``: each tile under its
layer key (``K1`` .. ``W4``), each cycle (``forward``, ``backward``,
``update``, ``backward_update``) and each conv-mapping stage (``im2col``,
``col2im``).  XLA keeps that name stack as every op's ``op_name``, and the
profiler stores it as the ``tf_op`` stat of the op's *event metadata* in
the ``.xplane.pb``.  ``jax.profiler.ProfileData`` exposes event stats but
not the metadata's, so this module reads the few XSpace fields it needs
with a minimal protobuf wire-format reader (no new dependency).

A scope path keeps only the components the benchmark knows (the
configuration's layer names, :data:`CYCLES`, :data:`STAGES`), in order,
after transform wrappers (``jvp(...)``, ``transpose(...)``, ``vmap(...)``)
are stripped: ``jit(run_epoch)/while/body/closed_call/transpose(jvp(K2))/
backward/while/body/closed_call/col2im/iota`` is ``K2/backward/col2im``.
An op with no known component is :data:`UNSCOPED`.  Time is each op's self
time (``trace.self_times``) inside the traced window, so a ``while`` op
and the ops of its body are not counted twice, averaged over the devices
like ``busy_s``: the scope totals add up to the busy time.
``trace_scopes.py`` prints them per train step for a traced run.
"""

from __future__ import annotations

import gzip
import re
from collections import defaultdict
from types import SimpleNamespace
from typing import Dict, Iterable, Iterator, List, Optional, Tuple

CYCLES = ("forward", "backward", "update", "backward_update")
STAGES = ("im2col", "col2im")
UNSCOPED = "unscoped"

_WRAPPER_RE = re.compile(r"\b(?:jvp|transpose|vmap)\(([^()]*)\)")
TF_OP = "tf_op"


# ---------------------------------------------------------------------------
# Scope paths
# ---------------------------------------------------------------------------

def strip_wrappers(name: str) -> str:
    """``transpose(jvp(K1))/update`` -> ``K1/update``."""
    prev = None
    while prev != name:
        prev, name = name, _WRAPPER_RE.sub(r"\1", name)
    return name


def scope_of(tf_op: str, layers: Iterable[str]) -> str:
    """The known scope path of one op's ``tf_op`` (``<op_name>:<type>``;
    its first name where XLA merged several, ``a;b``), or :data:`UNSCOPED`."""
    known = set(layers) | set(CYCLES) | set(STAGES)
    op_name = tf_op.rpartition(":")[0] if ":" in tf_op else tf_op
    first = op_name.split(";", 1)[0]
    path = [p for p in strip_wrappers(first).split("/") if p in known]
    return "/".join(path) if path else UNSCOPED


def reduce_scopes(device_events: Dict[int, List[tuple]],
                  window: Tuple[float, float],
                  layers: Iterable[str]) -> Dict[str, Dict]:
    """Self time (s) and kernel launches by scope path inside ``window``.

    ``device_events``: device id -> [(start_ns, end_ns, tf_op, kind)];
    ``window``: (lo, hi) ns.  Returns ``scope_s`` {path: seconds, averaged
    over the devices} and ``scope_launches`` {kind: {path: launches}}."""
    from benchlib import trace as T
    layers = tuple(layers)
    lo, hi = window
    scope_ns: Dict[str, float] = defaultdict(float)
    launches: Dict[str, Dict[str, int]] = defaultdict(lambda: defaultdict(int))
    for evs in device_events.values():
        for (s, e, tf_op, kind), own in zip(evs, T.self_times(evs)):
            if e <= lo or s >= hi:
                continue
            path = scope_of(tf_op, layers)
            scope_ns[path] += min(own, min(e, hi) - max(s, lo))
            if kind:
                launches[kind][path] += 1
    n_dev = max(1, len(device_events))
    return {"scope_s": {k: v / n_dev * 1e-9 for k, v in scope_ns.items()},
            "scope_launches": {k: dict(v) for k, v in launches.items()}}


def cycle_s(scope_s: Dict[str, float], cycle: str) -> Optional[float]:
    """Seconds of every path with ``cycle`` as a component; None if none."""
    hits = [v for k, v in scope_s.items() if cycle in k.split("/")]
    return sum(hits) if hits else None


def stage_s(scope_s: Dict[str, float]) -> Optional[float]:
    """Seconds of every path under ``im2col`` or ``col2im``; None if none."""
    hits = [v for k, v in scope_s.items()
            if set(STAGES) & set(k.split("/"))]
    return sum(hits) if hits else None


# ---------------------------------------------------------------------------
# XSpace wire format (tsl/profiler/protobuf/xplane.proto)
# ---------------------------------------------------------------------------

def _varint(buf: bytes, i: int) -> Tuple[int, int]:
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        if b < 0x80:
            return out, i
        shift += 7


def _fields(buf: bytes, i: int = 0, end: Optional[int] = None
            ) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; a length-delimited value is
    its (start, end) span in ``buf``, a fixed64 its 8 raw bytes."""
    end = len(buf) if end is None else end
    while i < end:
        tag, i = _varint(buf, i)
        num, wire = tag >> 3, tag & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            n, i = _varint(buf, i)
            v, i = (i, i + n), i + n
        elif wire == 1:
            v, i = buf[i:i + 8], i + 8
        elif wire == 5:
            v, i = buf[i:i + 4], i + 4
        else:
            raise ValueError(f"unsupported protobuf wire type {wire}")
        yield num, v


def _str(buf: bytes, span) -> str:
    return buf[span[0]:span[1]].decode("utf-8", "replace")


def _signed(v: int) -> int:
    return v - (1 << 64) if v >= 1 << 63 else v


def _map_entry(buf: bytes, span) -> Tuple[int, tuple]:
    key, val = 0, (0, 0)
    for num, v in _fields(buf, *span):
        if num == 1:
            key = _signed(v)
        elif num == 2:
            val = v
    return key, val


def _stat(buf: bytes, span, stat_names: Dict[int, str]
          ) -> Tuple[int, Optional[str]]:
    """(XStat.metadata_id, its string value: ``str_value`` or a
    ``ref_value`` naming an interned XStatMetadata)."""
    mid, val = 0, None
    for num, v in _fields(buf, *span):
        if num == 1:
            mid = _signed(v)
        elif num == 5:
            val = _str(buf, v)
        elif num == 7:
            val = stat_names.get(_signed(v))
    return mid, val


def _plane(buf: bytes, span):
    """(name, event-metadata map entries, {stat metadata id: name}, line
    spans) of one XPlane."""
    name, lines, ev_md, stat_md = "", [], [], {}
    for num, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            lines.append(v)
        elif num == 4:
            ev_md.append(v)
        elif num == 5:
            k, s = _map_entry(buf, v)
            for n2, v2 in _fields(buf, *s):
                if n2 == 2:
                    stat_md[k] = _str(buf, v2)
    return name, ev_md, stat_md, lines


def _event_metadata(buf, spans, stat_md) -> Dict[int, Tuple[str, str]]:
    """{event metadata id: (name, tf_op)}."""
    out = {}
    for span in spans:
        k, s = _map_entry(buf, span)
        name, tf_op = "", ""
        for num, v in _fields(buf, *s):
            if num == 2:
                name = _str(buf, v)
            elif num == 5:
                mid, val = _stat(buf, v, stat_md)
                if stat_md.get(mid) == TF_OP and val is not None:
                    tf_op = val
        out[k] = (name, tf_op)
    return out


def _line(buf: bytes, span):
    """(name, timestamp_ns, [(metadata id, offset_ps, duration_ps)])."""
    name, ts, events = "", 0, []
    for num, v in _fields(buf, *span):
        if num == 2:
            name = _str(buf, v)
        elif num == 3:
            ts = _signed(v)
        elif num == 4:
            mid = off = dur = 0
            for n2, v2 in _fields(buf, *v):
                if n2 == 1:
                    mid = _signed(v2)
                elif n2 == 2:
                    off = _signed(v2)
                elif n2 == 3:
                    dur = _signed(v2)
            events.append((mid, off, dur))
    return name, ts, events


def _interval(ts_ns: int, offset_ps: int, duration_ps: int):
    """(start_ns, end_ns) in whole ns, as ProfileData gives them."""
    s = float(ts_ns + offset_ps // 1000)
    return s, s + duration_ps // 1000


def read_file(path: str, n_devices: int = 1):
    """(device_events, window) of an ``.xplane.pb`` (or ``.gz``):
    device id -> [(start_ns, end_ns, tf_op, kind)] on each device's
    ``XLA Ops`` line, and the host span ``bench:window`` (else the span of
    all device ops), on the trace's one clock."""
    from benchlib import trace as T
    opener = gzip.open if path.endswith(".gz") else open
    with opener(path, "rb") as f:
        buf = f.read()
    kinds = T.kernel_kinds()
    dev_events: Dict[int, List[tuple]] = {}
    windows = []
    for num, span in _fields(buf):
        if num != 1:
            continue
        name, ev_md_spans, stat_md, lines = _plane(buf, span)
        m = T._DEVICE_RE.match(name)
        if m and int(m.group(1)) < n_devices:
            md = _event_metadata(buf, ev_md_spans, stat_md)
            evs = []
            for lspan in lines:
                lname, ts, events = _line(buf, lspan)
                if lname != T.OPS_LINE:
                    continue
                for mid, off, dur in events:
                    ev_name, tf_op = md.get(mid, ("", ""))
                    # the op's own name, as ProfileData gives it to
                    # ``trace.read_file``
                    kind = T.kind_of(SimpleNamespace(name=ev_name, stats=[]),
                                     kinds)
                    evs.append((*_interval(ts, off, dur), tf_op, kind))
            dev_events[int(m.group(1))] = evs
        elif name.startswith("/host:"):
            md = dict(_map_entry(buf, s) for s in ev_md_spans)
            win_ids = {k for k, s in md.items()
                       if any(n == 2 and _str(buf, v) == "bench:window"
                              for n, v in _fields(buf, *s))}
            for lspan in lines if win_ids else ():
                _lname, ts, events = _line(buf, lspan)
                windows += [_interval(ts, off, dur)
                            for mid, off, dur in events if mid in win_ids]
    if windows:
        window = min(windows)
    else:
        allev = [x for evs in dev_events.values() for x in evs]
        window = (min(x[0] for x in allev), max(x[1] for x in allev))
    return dev_events, window


def reduce_file(path: str, layers: Iterable[str], n_devices: int = 1
                ) -> Dict[str, Dict]:
    dev_events, window = read_file(path, n_devices)
    if not any(dev_events.values()):
        raise RuntimeError(f"no device ops in trace {path}")
    return reduce_scopes(dev_events, window, layers)

