"""Readings that set a cell's limits: the program's, the control's and the
planted faults', on the chip, at the cell's own size.

    python3 benchmarks/chip/control.py --workload <name> \
        --seeds 11,12,13 --seconds 4 [--fault unchanged|half_batch]

For each seed it makes one run of the cell, as a benchmark run does, with a
short window, and prints one JSON line with the run's ``correct`` and the
numbers its check compared.  Without ``--fault`` the control is judged: the
plain reference computed in bfloat16 (the precision below the
configuration's float32) is put in the program's place in the harness's own
check, so a sound cell prints ``correct`` false; the program's numbers of
the same run are under ``program``.  With ``--fault`` the program runs with
that fault planted.  All seeds run in one process, so set-up compiles once.
The benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))


def main(argv=None) -> int:
    from benchlib import common as C
    from benchlib import harness as H
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=4.0)
    ap.add_argument("--fault", default=None)
    args = ap.parse_args(argv)
    cell = C.load_cell(args.workload)
    C.use_compile_cache()
    C.require_chips(cell.chips)
    for seed in (int(s) for s in args.seeds.split(",")):
        run = H.Run(cell, seed, args.seconds, False, fault=args.fault)
        run.control = args.fault is None
        H.drive(run)
        res = H.finish(run)
        print(json.dumps({
            "workload": args.workload, "seed": seed, "fault": args.fault,
            "judged": "control" if run.control else "program",
            "correct": res["correct"], "checks": res["checks"],
            "program": run.readings.get("program"),
            "diff": run.readings.get("diff"),
            "e2e": run.e2e, "device": res["device"]}), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
