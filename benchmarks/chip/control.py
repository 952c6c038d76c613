"""Readings from which a cell's correctness limits are set.

  python3 -m benchmarks.chip.control --workload <name> --seeds 1 2 3

For each seed, one job of the cell (no warm-up, no timing) is checked
three ways: as it ran (the program's readings, the lower end of each
limit), with the reference's int8 twin in the program's place (the
control, the upper end), and once with each fault of ``faults.FAULTS``
planted under the job. Prints one JSON line per reading. The
benchmark's own runs do not run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--fault-seeds", type=int, default=None,
                    help="plant the faults on the first N seeds only")
    a = ap.parse_args()
    sys.path.insert(0, str(ROOT))
    from benchmarks.chip import faults, harness

    harness.prepare()
    c = harness.Cell(harness.load_json(ROOT / "BENCHMARK.json"), a.workload)
    modes = [("program", {}), ("control", {"quant": "int8"})]
    planted = [(f"fault:{f}", {"fault": f})
               for f in faults.FAULTS[c.cell["entry"]]]
    for i, seed in enumerate(a.seeds):
        with_faults = a.fault_seeds is None or i < a.fault_seeds
        for mode, kw in modes + (planted if with_faults else []):
            r = harness.measure(c, seed, 0.0, False, warmup=False, **kw)
            row = {"seed": seed, "mode": mode, "correct": r["correct"],
                   "device": r["device"],
                   "checks": {k: v["value"] for k, v in r["checks"].items()}}
            print(json.dumps(row), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
