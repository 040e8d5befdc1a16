"""Run one cell of the benchmark once, on the chips of this machine.

    python3 bench/run.py --workload minicpm3-4b.longctx-decode \
        --seed 1234 --seconds 30 --trace 0

From the root of a checkout (``BENCHMARK.json`` and ``src/`` beside this
directory).  The last line of standard output is one JSON object:
``correct``, ``attempted``, ``failed``, ``metrics`` (the cell's
end-to-end metrics, or with ``--trace 1`` its per-layer metrics from a
profiled part of the window), ``device``, ``breakdown`` (traced runs)
and ``checks``, the numbers that decided ``correct`` beside their
limits; the last lines of standard error repeat those.  Without as many
TPU chips as the cell asks for, it exits non-zero and prints no result.
"""
import time

T_START = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

HERE = Path(__file__).resolve().parent
SRC = HERE.parent / "src"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None, metavar="DIR",
                    help="also write the reduced trace and a description "
                         "of the raw one into DIR")
    args = ap.parse_args(argv)
    if not (SRC / "repro").is_dir() or \
            not (HERE.parent / "BENCHMARK.json").is_file():
        print(f"bench: the program's sources ({SRC}) or BENCHMARK.json are "
              "not beside this directory", file=sys.stderr)
        return 2
    sys.path[:0] = [str(HERE), str(SRC)]
    import faulthandler
    import harness

    # a run that outlives its allowance leaves every thread's stack behind
    faulthandler.dump_traceback_later(300, repeat=True)

    try:
        res = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(res), flush=True)
    for name, c in res["checks"].items():
        print(f"check {name}: {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    return 0


if __name__ == "__main__":
    code = main()
    sys.stdout.flush()
    sys.stderr.flush()
    # the engine's runtime threads have all been shut down; leave without
    # waiting on interpreter teardown
    os._exit(code)
