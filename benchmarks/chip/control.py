#!/usr/bin/env python3
"""Run a cell's control on the chip: the plain reference put in the
program's place with one guarantee broken (``entries/control_short_search.py``:
a full-table search one step short).  Every seed must come out not correct;
the wrong-answer counts are the upper readings that the cell's limit
(0 wrong answers) is set below.

    python3 benchmarks/chip/control.py --workload <cell> --seconds 5 --seeds 11 12 13

One process, one table per seed at the cell's own size and load.  The
benchmark's own runs never run this.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

CONTROL_ENTRY = "control_short_search"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    args = ap.parse_args(argv)
    from benchmarks.chip import manifest, run

    control = manifest.load_module(manifest.bench_file(ROOT, "entries", CONTROL_ENTRY, ".py"))
    readings = []
    for seed in args.seeds:
        r = run.run(args.workload, seed, args.seconds, False, entry_module=control)
        readings.append({"seed": seed, "correct": r["correct"], "attempted": r["attempted"],
                         "wrong_answers": r["checks"]["wrong_answers"]["value"]})
        print(json.dumps({"phase": "control", **readings[-1]}), flush=True)
    failed = all(not x["correct"] for x in readings)
    print(json.dumps({"control_failed_every_seed": failed, "readings": readings}), flush=True)
    return 0 if failed else 1


if __name__ == "__main__":
    sys.exit(main())
