"""Device time per train step by program scope: layer x RPU cycle x
conv-mapping stage, read from a traced run of a cell on the chip.

    python3 benchmarks/chip/trace_scopes.py --workload <name> \
        --seeds 11,12 [--seconds 3] [--trace-seconds 0.01] [--keep DIR]

For each seed it makes one ``--trace 1`` run of the cell, as a benchmark
run does (``benchlib.harness``: the cell's driver, its set-up, window and
check), and reads the trace with ``benchlib.scopes`` before the harness
removes it.  ``--trace-seconds`` overrides the traffic's traced part of the
window (a value under one call's time traces exactly one call); ``--keep``
copies the trace there, gzipped, as ``<workload>.<seed>.xplane.pb.gz``.
One JSON line per seed: the run's ``correct`` and ``checks``, its
``breakdown``, and under ``scopes`` the busy and per-scope device µs per
traced step with the kernel launches per step by kind and scope.  All
seeds run in one process, so set-up compiles once.  The benchmark's own
runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import glob
import gzip
import json
import os
import shutil
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def _traced_steps(readings) -> int:
    """Steps in the traced window: the driver accounts each kernel launch
    of the window as ``[steps traced, launch]``."""
    launches = next(iter(readings["traced_launches"].values()))
    return int(launches[0][0])


def per_step(red, steps: int, busy_s: float):
    from benchlib import scopes as S
    us = 1e6 / steps
    scope_s = red["scope_s"]
    totals = {"forward_cycle_us": S.cycle_s(scope_s, "forward"),
              "backward_cycle_us": S.cycle_s(scope_s, "backward"),
              "update_cycle_us": S.cycle_s(scope_s, "update"),
              "conv_mapping_us": S.stage_s(scope_s)}
    return {"steps": steps, "busy_us": busy_s * us,
            "scopes_total_us": sum(scope_s.values()) * us,
            **{k: (None if v is None else v * us)
               for k, v in totals.items()},
            "scope_us": {k: v * us for k, v in sorted(
                scope_s.items(), key=lambda kv: -kv[1])},
            "launches_per_step": {
                kind: {k: n / steps for k, n in sorted(c.items())}
                for kind, c in sorted(red["scope_launches"].items())}}


def main(argv=None) -> int:
    from benchlib import common as C
    from benchlib import harness as H
    from benchlib import scopes as S
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=3.0)
    ap.add_argument("--trace-seconds", type=float, default=None)
    ap.add_argument("--keep", default=None)
    args = ap.parse_args(argv)
    cell = C.load_cell(args.workload)
    if args.trace_seconds is not None:
        cell.traffic["trace_seconds"] = args.trace_seconds
    layers = list(cell.config.get("tiles", {}))
    C.use_compile_cache()
    C.require_chips(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = H.Run(cell, seed, args.seconds, True)
        driver = C.load_module(C.bench_file(
            "drivers", cell.traffic["driver"] + ".py"))
        driver.run(run)
        files = glob.glob(os.path.join(run.trace_dir, "**", "*.xplane.pb"),
                          recursive=True)
        if not files:
            raise C.BenchError("the profiler wrote no trace")
        path = max(files, key=os.path.getmtime)
        red = S.reduce_file(path, layers, n_devices=cell.chips)
        if args.keep:
            os.makedirs(args.keep, exist_ok=True)
            out = os.path.join(args.keep,
                               f"{args.workload}.{seed}.xplane.pb.gz")
            with open(path, "rb") as src, gzip.open(out, "wb") as dst:
                shutil.copyfileobj(src, dst)
        run.readings["trace"] = run.reduce_trace()
        res = H.finish(run)
        print(json.dumps({
            "workload": args.workload, "seed": seed,
            "correct": res["correct"], "checks": res["checks"],
            "breakdown": res.get("breakdown"),
            "scopes": per_step(red, _traced_steps(run.readings),
                               run.readings["trace"]["busy_s"]),
            "device": res["device"]}), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
