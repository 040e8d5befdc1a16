"""Find the highest arrival rate a cell's configuration sustains: one run
per rate, in one process, with the mix's rate replaced and no reference
check.  Sustained means the backlog (requests sent and not finished) did
not grow over the window and every request due in it finished.

    python3 bench/sweep.py --workload NAME --seconds 30 --rates 2 3 4 5

One JSON line per rate on standard output.
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
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seed", type=int, default=2**31 + 99)
    args = ap.parse_args(argv)
    import harness

    for rate in args.rates:
        t0 = time.monotonic()
        res = harness.run_cell(args.workload, args.seed, args.seconds, False,
                               t_start=t0, mix_overrides={"rate": rate},
                               check=False)
        print(json.dumps({
            "rate": rate, "attempted": res["attempted"],
            "failed": res["failed"], "backlog": res["backlog"],
            "metrics": {k: v["value"] for k, v in res["metrics"].items()},
            "wall_s": time.monotonic() - t0}), flush=True)


if __name__ == "__main__":
    main()
