"""One run of one cell: set-up, measured window, check, result line.

``run.py --workload <name> --seed <n> --seconds <s> --trace <0|1>`` loads
the cell's files, refuses anything but a TPU with enough chips, and hands a
:class:`Run` to the cell's driver (``drivers/<traffic.driver>.py``).  The
driver builds the program, warms every shape its traffic uses, measures for
``seconds``, compares what the timed path produced with the plain
reference, and fills the :class:`Run`.  With ``--trace 1`` part of the window
is traced and each per-layer metric is read by its own reader
(``metrics/<name>.py``); otherwise the end-to-end metrics are printed.

The last lines on standard error are the numbers compared with their
limits; the last line on standard output is the result JSON, with the same
numbers under ``checks``, its last key.
"""

from __future__ import annotations

import argparse
import glob
import json
import math
import os
import shutil
import sys
import time
import traceback
from typing import Any, Dict, List, Optional

from benchlib import common as C


class Run:
    """What a driver gets: the cell, the run's arguments, and the places to
    put what it measured."""

    def __init__(self, cell: C.Cell, seed: int, seconds: float, trace: bool,
                 fault: Optional[str] = None):
        self.cell = cell
        self.seed = int(seed)
        self.seconds = float(seconds)
        self.trace = bool(trace)
        #: a planted fault (tests only): the driver breaks its timed path
        self.fault = fault
        #: judge the control (the reference in a lower precision) in the
        #: program's place; ``control.py`` and tests only, never a
        #: benchmark run
        self.control = False
        self.spans = C.Spans()
        self.compiles = C.CompileCounter()
        self.t_start = time.perf_counter()
        #: end-to-end values by metric name (``setup_s`` included)
        self.e2e: Dict[str, float] = {}
        #: inputs of the per-layer readers (counts, spans, launches, trace)
        self.readings: Dict[str, Any] = {}
        #: (name, value, limit): the numbers that decide ``correct``
        self.checks: List[tuple] = []
        self.attempted = 0
        self.failed = 0
        self.trace_dir = os.path.join(C.ROOT, ".bench_cache", "trace")
        self._trace_t = None

    def log(self, msg: str) -> None:
        print(f"[{self.cell.name}] {msg}", file=sys.stderr, flush=True)

    def setup_done(self) -> None:
        self.e2e["setup_s"] = time.perf_counter() - self.t_start

    def check(self, name: str, value: float, limit: float) -> None:
        self.checks.append((name, float(value), float(limit)))

    # --- tracing ----------------------------------------------------------
    def start_trace(self) -> None:
        """Start the profiler (``--trace 1`` only) and the host spans'
        annotations; the traced window runs until :meth:`stop_trace`."""
        if not self.trace or self._trace_t is not None:
            return
        import jax
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        jax.profiler.start_trace(self.trace_dir)
        self.spans.tracing = True
        self._ann = jax.profiler.TraceAnnotation("bench:window")
        self._ann.__enter__()
        self._trace_t = [time.perf_counter(), None]

    def stop_trace(self) -> None:
        if self._trace_t is None or self._trace_t[1] is not None:
            return
        import jax
        self._trace_t[1] = time.perf_counter()
        self._ann.__exit__(None, None, None)
        self.spans.tracing = False
        jax.profiler.stop_trace()

    def reduce_trace(self) -> Optional[Dict[str, Any]]:
        if self._trace_t is None:
            return None
        from benchlib import trace as T
        files = glob.glob(os.path.join(self.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise C.BenchError("the profiler wrote no trace")
        red = T.reduce_file(max(files, key=os.path.getmtime),
                            n_devices=self.cell.chips)
        shutil.rmtree(self.trace_dir, ignore_errors=True)
        return red


def _finite(v) -> bool:
    return isinstance(v, (int, float)) and math.isfinite(v)


def finish(run: Run) -> Dict[str, Any]:
    """The result line of a finished run."""
    correct = bool(run.checks) and all(
        _finite(v) and v <= lim for _, v, lim in run.checks)
    device = run.readings.get("device") or C.device_info(run.cell.chips)
    out: Dict[str, Any] = {"correct": correct, "attempted": run.attempted,
                           "failed": run.failed}
    metrics: Dict[str, Dict[str, Any]] = {}
    if run.trace:
        red = run.readings.get("trace")
        if red is not None:
            device = dict(device, busy_s=red["busy_s"],
                          window_s=red["window_s"])
            out["breakdown"] = {"device_ops": red["top_ops"],
                                "idle_gaps": red["idle_gaps"]}
        for m in run.cell.per_layer:
            mod = C.load_module(C.bench_file("metrics", m["name"] + ".py"))
            v = mod.read(run.readings)
            if v is not None:
                metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    else:
        for m in run.cell.end_to_end:
            if m["name"] in run.e2e:
                metrics[m["name"]] = {"value": float(run.e2e[m["name"]]),
                                      "unit": m["unit"]}
    out["metrics"] = metrics
    out["device"] = device
    out["checks"] = {n: {"value": v, "limit": lim}
                     for n, v, lim in run.checks}
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description="Run one benchmark cell once.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = C.load_cell(args.workload)
        C.use_compile_cache()
        C.require_chips(cell.chips)
    except (C.BenchError, OSError, KeyError, ValueError) as e:
        print(f"benchmark: {e}", file=sys.stderr)
        return 2
    run = Run(cell, args.seed, args.seconds, bool(args.trace))
    try:
        drive(run)
    except Exception:
        traceback.print_exc()
        print("benchmark: the run failed; no result", file=sys.stderr)
        return 1
    res = finish(run)
    for n, v, lim in run.checks:
        print(f"check {n} = {v!r} (limit {lim!r})", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(res), flush=True)
    return 0


def drive(run: Run) -> None:
    """Run the cell's driver; then reduce the trace, if one was taken."""
    driver = C.load_module(C.bench_file(
        "drivers", run.cell.traffic["driver"] + ".py"))
    driver.run(run)
    if run.trace:
        run.readings["trace"] = run.reduce_trace()
