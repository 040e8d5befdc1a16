"""Readings that set a cell's limits, many seeds in one process (set-up
compiles once): for each seed, one run of the cell as ``run.py`` makes
it, printing the compared numbers and, on the first ``--control`` seeds,
the float8 control's reading on the same sample and whether the harness
judged the control correct (it must not: the exit code is then 1).

    python3 bench/calibrate.py --workload NAME --seconds 10 \
        --seeds 11 12 13 ... --control 3

One JSON line per seed on standard output.
"""
import argparse
import json
import sys
import time
from pathlib import Path

HERE = Path(__file__).resolve().parent
sys.path[:0] = [str(HERE), str(HERE.parent / "src")]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--rate", type=float, default=None,
                    help="replace the mix's arrival rate (open loop)")
    ap.add_argument("--control", type=int, default=3,
                    help="run the control on this many of the seeds")
    args = ap.parse_args(argv)
    import harness

    passed_controls = []
    for i, seed in enumerate(args.seeds):
        t0 = time.monotonic()
        control = i < args.control
        res = harness.run_cell(args.workload, seed, args.seconds, False,
                               control=control, t_start=t0,
                               mix_overrides=({"rate": args.rate}
                                              if args.rate else None))
        gap = res["checks"]["logit_gap"]["value"]
        line = {"seed": seed, "attempted": res["attempted"],
                "failed": res["failed"],
                "compiles_in_window":
                    res["checks"]["compiles_in_window"]["value"],
                "limit": res["checks"]["logit_gap"]["limit"]}
        if control:
            # the control stood in for the program: ``correct`` is its own
            line.update(logit_gap=res["served_logit_gap"],
                        control_logit_gap=gap,
                        control_correct=res["correct"])
            if res["correct"]:
                passed_controls.append(seed)
        else:
            line.update(logit_gap=gap, correct=res["correct"])
        line.update(metrics={k: v["value"] for k, v in res["metrics"].items()},
                    memory_peak_bytes=res["device"]["memory_peak_bytes"],
                    wall_s=time.monotonic() - t0)
        print(json.dumps(line), flush=True)
    if passed_controls:
        print(f"calibrate: the control came out correct on seeds "
              f"{passed_controls}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
