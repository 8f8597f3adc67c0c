"""The control of the comparison that decides ``correct``: the plain
reference put in the program's place and computed in the nearest
precision below the configuration's (``control_rows`` of the job kind's
reference; bfloat16 for the float32 sum). Its output goes through the
same ``compare`` as a run's, which has to reject it.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --events N

runs it on the chip at the cell's own size: N events of the cell's
traffic, as many as a run of the cell takes in, and prints each seed's
readings. The benchmark's own runs never run it.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def control_readings(cell, seed: int, n_events: int) -> dict:
    """The comparison's readings of the control's rows at ``n_events``
    events of the cell's traffic."""
    traffic = cell.traffic()
    ref = cell.ref.reference(seed, cell.job, n_events, traffic)
    rows = cell.ref.control_rows(seed, cell.job, n_events, traffic)
    return cell.ref.compare(rows, ref, cell.job)


def main() -> int:
    from benchmark.run import Cell, require_chips

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--events", type=int, required=True)
    args = ap.parse_args()
    cell = Cell(args.workload)
    require_chips(1)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = control_readings(cell, seed, args.events)
        print(json.dumps({"control": args.workload, "seed": seed,
                          "events": args.events, "readings": out}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
