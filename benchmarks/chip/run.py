"""Run one benchmark cell once, on the chip this process finds.

    python3 benchmarks/chip/run.py --workload <name> --seed <n> \
        --seconds <s> --trace <0|1>

Exits non-zero, and prints no result, without a TPU or with fewer chips
than the cell asks for.  See ``benchmarks/chip/README.md``.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(1, os.path.join(os.path.dirname(os.path.dirname(HERE)),
                                "src"))

if __name__ == "__main__":
    from benchlib import harness
    sys.exit(harness.main())
