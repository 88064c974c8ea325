"""The training run of a family with routed experts: ``runners/train``
as it stands, and beside its three checked steps two readings of the
routing, which is discontinuous (a choice that flips moves a token to
another expert, and no norm of a gradient says how many did).

On the first batch of the run, under the seed's weights, the program's
model (``programs/<family>.routing_choices``: bf16 products, router in
float32) and the plain reference (float32) each make one forward pass;
compared are

* ``routing_differ_share``: the share of the (layer, position, choice)
  slots in which the two name another expert, and
* ``pairs_held_gap``: the gap between the two counts of (position,
  expert) pairs that fall on the held experts, a layer, against the
  reference's count,

each against its limit in the traffic file, with the train runner's own.
"""

from __future__ import annotations

import sys
import time
from typing import Any, Dict

import numpy as np

from benchmarks import harness
from benchmarks import traffic as trafficlib
from benchmarks.runners import train


def routing_readings(cell: harness.Cell) -> Dict[str, float]:
    import jax

    ref, prog = cell.family("references"), cell.family("programs")
    job, cfg = cell.traffic, cell.config
    rows = int(job["batch_per_chip"]) * cell.chips
    batch = next(trafficlib.token_batches(
        cell.seed, rows, int(job["seq_len"]), int(cfg["vocab_size"])
    ))
    inputs, _, _ = ref.noise_rows(batch[0], cfg)
    params = ref.init_params(cfg, cell.seed)
    ours = prog.routing_choices(cfg, params, inputs)
    chosen = jax.jit(lambda p, x: ref.forward(p, x, cfg)[1])
    block = int(job["correct"]["rows_per_block"])
    theirs = np.concatenate([
        np.asarray(chosen(params, inputs[r:r + block]))
        for r in range(0, rows, block)
    ], axis=1)
    # a position's choices as a set: equal gates may change their order
    differ = np.mean(np.sort(ours, axis=-1) != np.sort(theirs, axis=-1))
    pairs = [float(ref.held_pairs(x, cfg)) for x in (ours, theirs)]
    print(f"  routing: {differ:.3%} of choices differ; pairs on held experts "
          f"a layer: program {pairs[0]:.1f} reference {pairs[1]:.1f}",
          file=sys.stderr)
    return {
        "routing_differ_share": float(differ),
        "pairs_held_gap": abs(pairs[0] - pairs[1]) / pairs[1],
    }


def run(cell: harness.Cell) -> Dict[str, Any]:
    out = train.run(cell)
    t = time.monotonic()
    checks = harness.judge(routing_readings(cell), cell.traffic["correct"]["limits"])
    out["checks"].update(checks)
    out["correct"] = bool(out["correct"] and harness.all_within(checks))
    out["reference_s"] += time.monotonic() - t
    return out
