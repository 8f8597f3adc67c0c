"""The sweep that fixes an open-loop cell's rate, run once on the chip:

    python3 benchmark/sweep.py --workload tumble_sum_1m.rate80 --seed 5 \\
        --seconds 10 --rates 2000000,2500000,3000000

runs the cell's job at each rate in one process and prints, per rate,
the source backlog (events due but not yet handed over) as the window
opens and as it closes, and the result latency. The knee is the highest
rate at which the backlog at the close stays under one batch (what one
poll may hand over), with every lower rate of the sweep under it too;
the cell's traffic file then takes 0.8 times it as ``rate_per_s``.
"""

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main() -> int:
    from benchmark import job as job_mod
    from benchmark.readings import Ctx
    from benchmark.run import CACHE_DIR, Cell, require_chips

    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--rates", required=True)
    args = ap.parse_args()
    os.environ["JAX_COMPILATION_CACHE_DIR"] = CACHE_DIR
    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    cell = Cell(args.workload)
    require_chips(cell.entry["chips"])
    for rate in (int(float(r)) for r in args.rates.split(",")):
        rec = job_mod.run(cell.cfg, cell.traffic(rate_per_s=rate),
                          args.seed, args.seconds)
        ctx = Ctx(cell.cfg, rec, 0.0)
        win, sched = rec["window"], rec["sched"]
        print(json.dumps({
            "rate_per_s": rate,
            "backlog_open": sched.due_by(win.t_open) - sched.warmup,
            "backlog_close": sched.due_by(win.t_close) - rec["n_events"],
            "events_per_s": ctx.events_in_window() / ctx.window_s,
            "result_latency_p50_ms": ctx.latency_pct(50),
            "result_latency_p99_ms": ctx.latency_pct(99),
            "compiles_in_window": win.compiles_in_window[0],
        }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
