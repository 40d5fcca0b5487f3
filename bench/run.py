#!/usr/bin/env python3
"""Run one cell of ``BENCHMARK.json`` once and print its result line.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

From the root of a checkout.  Needs an accelerator: with none (or fewer
chips than the cell asks for) it exits 3 and prints no result.  The last
line of standard output is one JSON object: ``correct``, ``attempted``,
``failed``, ``metrics`` (the cell's end-to-end metrics, or with
``--trace 1`` its per-layer metrics), ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared with its limit
(also the last lines of standard error).

Options for measuring the benchmark itself, not used by its checks:
``--rate`` overrides the traffic's arrival rate (requests/s, for the
sweep that finds the knee); ``--control 1`` puts the comparison's int4
control in the program's place, so a sound harness reports ``correct``
false; ``--keep-trace DIR`` keeps the profiler trace; ``--sweep
r1,r2,..`` serves the lead-in and one window per rate after one set-up
and prints offered against served load.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent.parent))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rate", type=float, default=None)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0)
    ap.add_argument("--keep-trace", default=None)
    ap.add_argument("--sweep", default=None,
                    help="comma-separated rates: one window each, one set-up")
    args = ap.parse_args(argv)

    from bench import harness

    if args.sweep:
        rates = [float(r) for r in args.sweep.split(",")]
        rows = harness.sweep(args.workload, args.seed, args.seconds, rates)
        print(json.dumps({"sweep": rows}), flush=True)
        return 0
    try:
        out = harness.run_cell(args.workload, args.seed, args.seconds,
                               bool(args.trace), t_start=T_START,
                               rate=args.rate, control=bool(args.control),
                               keep_trace=args.keep_trace)
    except harness.NoChip as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
