"""Readings for the limits that decide ``correct``.

Runs a cell's harness once per seed in one process, at the cell's own size
and load with a short window, and prints one JSON line per seed: every
number compared as the program gives it, and as the control gives it (the
reference computed in bfloat16, put in the program's place on the same
sampled fields).  ``--fault`` plants a fault in the timed path instead:
``widths`` packs every tier at another width than the configuration
states, ``altered`` moves part of every decoded answer by 64 steps,
``stale`` answers a request from the step before the one it names, and
``stale-relabelled`` does so under the keys that were asked for.

    python3 bench/calibrate.py --workload <cell> --seeds 11,12,13 --seconds 6 [--fault widths]

The benchmark's own runs never run this.  It exits non-zero without a TPU.
"""

import argparse
import contextlib
import json
import os
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "bench"), str(ROOT / "src")]
os.environ.setdefault("TPU_LOG_DIR", "disabled")

from fdbbench import faults  # noqa: E402
from fdbbench.harness import NoDevice, compare, measure, require_tpu  # noqa: E402
from fdbbench.spec import load_cell  # noqa: E402


def main(argv, *, root=ROOT, device_check=require_tpu) -> int:
    ap = argparse.ArgumentParser(prog="bench/calibrate.py", description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=6.0)
    ap.add_argument("--fault", choices=("none", "widths", "altered", "stale", "stale-relabelled"),
                    default="none")
    args = ap.parse_args(argv)
    cell = load_cell(root, args.workload)
    try:
        devices = device_check(cell.chips)
    except NoDevice as e:
        print(f"calibrate: {e}", file=sys.stderr)
        return 1
    tree = faults.swapped_widths(cell.config)["tree"] if args.fault == "widths" else None
    for seed in (int(s) for s in args.seeds.split(",")):
        planted = {"altered": faults.altered_answers,
                   "stale": lambda: faults.stale_reads(relabel=False),
                   "stale-relabelled": lambda: faults.stale_reads(relabel=True)}.get(
                       args.fault, contextlib.nullcontext)()
        with planted:
            o = measure(cell, seed, args.seconds, trace=False, devices=devices,
                        t_start=time.perf_counter(), tree=tree)
        line = {"workload": cell.name, "seed": seed, "fault": args.fault,
                "samples": len(o.loop.samples),
                "requests": {k: len(o.loop.window_records(k)) for k in ("archive", "retrieve")},
                "program": {k: v for k, (v, _) in compare(o).items()},
                "control": {k: v for k, (v, _) in compare(o, control=True).items()}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
