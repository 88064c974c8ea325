"""The hand controls of a training cell, each judged as the cell's own
runs are: the plain reference with one thing altered stands in the
program's place, is compared with the sound reference over the cell's
three checked steps by ``runners/train.compare``, and is held to the
traffic file's limits by ``harness.judge``. Every control has to come
out as not correct.

    python3 benchmarks/hand_controls.py \\
        --workload granite-4.0-h-micro-train-t4k --seed 11 \\
        [--only no_decay,half_batch]

The controls are the runner's two (``reference``: the reference's
products in the traffic file's ``correct.control_precision``;
``half_batch``: half of the batch left out) and those the traffic file
names under ``correct.hand_controls``: a name, and the configuration
keys that take one mechanism out of the reference (``{"without":
["decay"]}``, ``{"residual_multiplier": 1.0}``). The program is not run:
the limits of a new cell are set between what its own runs read and what
these read. One JSON line a control on standard output; the exit code is
1 if a control came out as correct.
"""

import argparse
import json
import os
import sys
import time
from typing import Dict, Iterable, Iterator, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def run(cell, only: Optional[Iterable[str]] = None) -> Iterator[Dict]:
    """One line a control of ``cell``: its readings beside the limits,
    ``correct`` and the names of the limits it ``fails``."""
    from benchmarks import harness
    from benchmarks import traffic as trafficlib
    from benchmarks.runners import train

    ref = cell.family("references")
    job, cfg = cell.traffic, cell.config
    rows = int(job["batch_per_chip"]) * cell.chips
    opt, correct = job["optimizer"], job["correct"]
    feed = trafficlib.token_batches(
        cell.seed, rows, int(job["seq_len"]), int(cfg["vocab_size"])
    )
    kept = [next(feed) for _ in range(train.CHECK_STEPS)]

    def follow(config, **altered):
        return ref.train_reference(
            ref.init_params(cfg, cell.seed), kept, config, opt,
            rows_per_block=int(correct["rows_per_block"]), **altered,
        )

    stand_ins = {
        name: (cfg, make(ref, job, rows)) for name, make in train.STAND_INS.items()
    }
    stand_ins.update(
        (name, ({**cfg, **keys}, {}))
        for name, keys in correct.get("hand_controls", {}).items()
    )
    names = list(stand_ins) if only is None else list(only)
    sound = follow(cfg)
    for name in names:
        config, altered = stand_ins[name]
        t = time.monotonic()
        checks = harness.judge(
            train.compare(follow(config, **altered), sound), correct["limits"]
        )
        yield {
            "workload": cell.name, "seed": cell.seed, "control": name,
            "correct": harness.all_within(checks),
            "fails": [k for k, c in checks.items()
                      if c["limit"] is not None and not c["value"] <= c["limit"]],
            "checks": checks, "seconds": time.monotonic() - t,
        }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--only", help="comma-separated names; all of them if left out")
    args = ap.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload, args.seed, 0.0, False, time.monotonic())
    try:
        harness.device_info(cell)
    except harness.NoChip as e:
        print(f"benchmarks/hand_controls.py: {e}; nothing was run.", file=sys.stderr)
        return harness.NO_CHIP
    passed = []
    for line in run(cell, args.only.split(",") if args.only else None):
        print(json.dumps(line), flush=True)
        if line["correct"]:
            passed.append(line["control"])
    if passed:
        print(f"benchmarks/hand_controls.py: came out as correct: {passed}",
              file=sys.stderr)
    return 1 if passed else 0


if __name__ == "__main__":
    sys.exit(main())
