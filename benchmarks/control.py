"""Several seeds of one cell in one process: the readings that the
limits of ``correct`` are set from, and the sweep that finds a rate.

    python3 benchmarks/control.py --workload gpt2-serve-chat \\
        --seeds 11,12,13 --seconds 10 \\
        [--control program|reference|half_batch] \\
        [--set rate_per_s=4.5] [--trace 1] [--describe-trace]

``--control program`` switches the program's own lower-precision path on
(the traffic file's ``correct.control_server``); ``--control reference``
puts the plain reference in the control's precision in the program's
place, ``--control half_batch`` (training) the reference with half of the
batch left out. Each is judged by the cell's own limits and has to come
out as not correct. One JSON line
a seed on standard output: the numbers compared, the end-to-end metrics
and a few percentiles. The benchmark's own runs never come here.
"""

import time

_T_START = time.monotonic()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def _set(tree: dict, assignment: str) -> None:
    key, value = assignment.split("=", 1)
    *path, last = key.split(".")
    for p in path:
        tree = tree[p]
    tree[last] = json.loads(value)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", choices=("program", "reference", "half_batch"))
    ap.add_argument("--set", action="append", default=[])
    ap.add_argument("--sweep", help="key=v1,v2,...: every seed at each value; "
                    "the i-th value's seeds are the given ones plus 100 * i")
    ap.add_argument("--describe-trace", action="store_true")
    ap.add_argument("--manifest")
    ap.add_argument("--rehearse", action="store_true",
                    help="skip the look for a chip (a tiny manifest on the CPU)")
    args = ap.parse_args(argv)

    from benchmarks import harness, stats, tracing

    seeds = [int(s) for s in args.seeds.split(",")]
    sweep = [None]
    if args.sweep:
        key, values = args.sweep.split("=", 1)
        sweep = [f"{key}={v}" for v in values.split(",")]
    first = True
    for point, seed in (
        (p, s + 100 * i) for i, p in enumerate(sweep) for s in seeds
    ):
        t = time.monotonic()
        cell = harness.load_cell(
            args.workload, seed, args.seconds, bool(args.trace),
            _T_START if first else t,
            manifest_path=args.manifest, control=args.control,
            keep_trace=args.describe_trace, require_chip=not args.rehearse,
        )
        first = False
        sets = args.set + ([point] if point else [])
        for a in sets:
            _set(cell.traffic, a)
        try:
            runner = importlib.import_module(
                f"benchmarks.runners.{cell.traffic['kind']}"
            )
            run = runner.run(cell)
        except harness.NoChip as e:
            print(f"benchmarks/control.py: {e}; nothing was run.", file=sys.stderr)
            return harness.NO_CHIP
        line = {
            "workload": args.workload, "seed": seed, "control": args.control,
            "set": sets, "correct": run["correct"],
            "attempted": run["attempted"], "failed": run["failed"],
            "end_to_end": run["end_to_end"],
            "checks": {k: v["value"] for k, v in run["checks"].items()},
            "memory_peak_bytes": run["memory_peak_bytes"],
            "reference_s": run["reference_s"],
            "cache_misses": run["cache_misses"],
            "wall_s": time.monotonic() - t,
        }
        for name, xs in run.get("samples", {}).items():
            if xs:
                line[name] = {
                    "n": len(xs), "p50": stats.percentile(xs, 50),
                    "p95": stats.percentile(xs, 95), "max": max(xs),
                }
        if args.trace:
            result = harness.finish(cell, run)
            line["per_layer"] = {k: v["value"] for k, v in result["metrics"].items()}
            line["device"] = result["device"]
            line["breakdown"] = result.get("breakdown")
        print(json.dumps(line), flush=True)
        if args.describe_trace and args.trace:
            trace_dir = os.path.join(harness.TRACE_DIR, cell.name)
            for row in tracing.describe(tracing.find_xplane(trace_dir)):
                print(row[:600], flush=True)
        del run, cell
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
