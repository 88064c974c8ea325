"""``python3 benchmarks/run.py --workload <name> --seed <n> --seconds <s>
--trace <0|1>``: one run of one cell. The last line of standard output
is the result, one JSON object."""

import time

_T_START = time.monotonic()  # before anything heavy is imported

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(
        args.workload, args.seed, args.seconds, bool(args.trace), _T_START
    )
    try:
        result = harness.run_cell(cell)
    except harness.NoChip as e:
        print(f"benchmarks/run.py: {e}; nothing was run.", file=sys.stderr)
        return harness.NO_CHIP
    sys.stdout.flush()
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
