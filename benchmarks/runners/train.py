"""The training run: ``explicit.setup`` then ``explicit.train_epoch``,
fed by the benchmark's own batches from the seed.

Set-up builds ONE trainer (compiled step and state), drives it through
its first three steps by the window's own call (``train_epoch`` over the
benchmark's feed) and hands the same object to the window. From those
steps it keeps each loss, the per-leaf norm of the first gradient as the
optimizer got it (Adam's first moment after one step, over 1 − beta1)
and the per-leaf norm of the parameters' change after the third. Once
the window has closed and the trainer is freed, the plain reference
follows the same three steps from the same seed and the two are compared.
"""

from __future__ import annotations

import contextlib
import logging
import os
import shutil
import statistics
import sys
import time
from typing import Any, Dict, Iterator, List, Optional

import numpy as np

from benchmarks import harness, tracing
from benchmarks import traffic as trafficlib

CHECK_STEPS = 3

# ``Cell.control`` -> what alters the reference that stands in for the
# program: its products in the control's precision, or the fault "half of
# the batch left out, the mean taken over the rest"
STAND_INS = {
    "reference": lambda ref, job, rows: {
        "cast": ref.CASTS[job["correct"]["control_precision"]]},
    "half_batch": lambda ref, job, rows: {"keep_rows": slice(0, rows // 2)},
}


class Feed:
    """What ``train_epoch`` asks of a dataset (``epoch(i)`` yielding host
    batches), over the generator's endless stream. Keeps the first
    batches for the reference, times itself, and ends an epoch at a step
    count or at a deadline."""

    def __init__(self, batches: Iterator, keep: int):
        self._batches = batches
        self._keep = keep
        self.kept: List[Any] = []
        self.host_s = 0.0
        self.steps = 0
        self.limit: Optional[int] = None
        self.deadline: Optional[float] = None
        self.on_step = None

    def epoch(self, epoch_index: int = 0):  # noqa: ARG002
        n = 0
        while True:
            if self.limit is not None and n >= self.limit:
                return
            if self.deadline is not None and time.monotonic() >= self.deadline:
                return
            if self.on_step is not None:
                self.on_step()
            t = time.perf_counter()
            batch = next(self._batches)
            self.host_s += time.perf_counter() - t
            if len(self.kept) < self._keep:
                self.kept.append(batch)
            n += 1
            self.steps += 1
            yield batch


@contextlib.contextmanager
def logged_losses():
    """The losses ``train_epoch`` logs (its only report of them)."""
    from distributeddeeplearning_tpu.utils.logging import get_logger

    losses: List[float] = []

    class Capture(logging.Handler):
        def emit(self, record):
            if "loss=" in str(record.msg):
                losses.append(float(record.args[1]))

    logger = get_logger().logger
    handler = Capture()
    logger.addHandler(handler)
    try:
        yield losses
    finally:
        logger.removeHandler(handler)


def worst_leaf_gap(prog: Dict[str, float], ref: Dict[str, float],
                   leaves: Optional[List[str]] = None) -> float:
    """The widest gap, over the leaves, between the program's norm and
    the reference's, against the reference's norm of that leaf or of the
    median leaf, whichever is larger."""
    leaves = leaves if leaves is not None else list(ref)
    med = statistics.median(ref[k] for k in leaves)
    gaps = {k: abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves}
    for k in sorted(gaps, key=gaps.get, reverse=True)[:3]:  # for the eye
        print(f"  leaf {k}: program {prog[k]:.6g} reference {ref[k]:.6g} "
              f"gap {gaps[k]:.4g} (median leaf {med:.4g})", file=sys.stderr)
    return max(gaps.values())


def moving_leaves(ref_grad_norms: Dict[str, float]) -> List[str]:
    """Leaves whose reference gradient is not nought to rounding: at
    least a thousandth of the median leaf's. The others (a key's bias
    under softmax) move under Adam by round-off alone."""
    med = statistics.median(ref_grad_norms.values())
    return [k for k, v in ref_grad_norms.items() if v >= 1e-3 * med]


def compare(prog: Dict[str, Any], ref: Dict[str, Any]) -> Dict[str, float]:
    moving = moving_leaves(ref["grad_norms"])
    return {
        "loss_gap": max(
            abs(a - b) / abs(b) for a, b in zip(prog["losses"], ref["losses"])
        ),
        "grad_norm_gap": worst_leaf_gap(prog["grad_norms"], ref["grad_norms"]),
        "delta_norm_gap": worst_leaf_gap(
            prog["delta_norms"], ref["delta_norms"], moving
        ),
        "leaves_left_out": float(len(ref["grad_norms"]) - len(moving)),
    }


def run(cell: harness.Cell) -> Dict[str, Any]:
    import jax
    import jax.numpy as jnp

    device = harness.device_info(cell)
    harness.enable_cache()
    from distributeddeeplearning_tpu.training.warmup import cache_stats

    ref, prog = cell.family("references"), cell.family("programs")
    job, cfg = cell.traffic, cell.config
    rows = int(job["batch_per_chip"]) * cell.chips
    seq_len, vocab = int(job["seq_len"]), int(cfg["vocab_size"])
    opt = job["optimizer"]

    params = ref.init_params(cfg, cell.seed)
    pieces, state = prog.build_trainer(cfg, job, cell.chips, cell.seed, params)
    del params
    theta0 = jax.tree.map(jnp.copy, state.params)  # the step donates its state
    feed = Feed(trafficlib.token_batches(cell.seed, rows, seq_len, vocab), CHECK_STEPS)

    # compile the step ahead against a batch of the window's shape
    blank = (np.zeros((rows, seq_len), np.int32),) * 2
    compiled, _ = pieces.train_step.aot_compile(state, prog.stage_like(pieces, blank))
    temp_bytes = int(compiled.memory_analysis().temp_size_in_bytes)
    if cell.sabotage is not None:
        cell.sabotage(pieces)

    # -- the first steps, through the window's own call and feed -------------
    b1 = float(opt["adam_beta1"])
    with logged_losses() as losses:
        feed.limit = 1
        state = prog.train_epoch(pieces, state, feed, 0, log_every=1)
        grad_norms = {
            k: v / (1.0 - b1)
            for k, v in ref.leaf_norms(prog.first_moment(state.opt_state)).items()
        }
        feed.limit = CHECK_STEPS - 1
        state = prog.train_epoch(pieces, state, feed, 1, log_every=1)
        delta_norms = ref.leaf_norms(jax.jit(
            lambda a, b: jax.tree.map(jnp.subtract, a, b)
        )(state.params, theta0))
    first = {"losses": list(losses), "grad_norms": grad_norms,
             "delta_norms": delta_norms}
    del theta0
    steps_before = feed.steps
    host_before = feed.host_s

    # -- the window -------------------------------------------------------------
    trace_dir = os.path.join(harness.TRACE_DIR, cell.name)
    trace_s = float(job.get("trace_s", 3.0))
    trace_begun = None

    def maybe_trace():
        nonlocal trace_begun
        if (cell.trace and trace_begun is None
                and time.monotonic() >= feed.deadline - trace_s):
            shutil.rmtree(trace_dir, ignore_errors=True)
            tracing.start(trace_dir)
            trace_begun = time.monotonic()

    jax.block_until_ready(state)
    feed.limit = None
    feed.on_step = maybe_trace
    w0 = time.monotonic()
    feed.deadline = w0 + cell.seconds
    with jax.profiler.TraceAnnotation(tracing.HOST_PREFIX + "train_epoch"):
        state = prog.train_epoch(pieces, state, feed, 2)
        jax.block_until_ready(state)
    w1 = time.monotonic()
    trace = None
    trace_window = None
    if trace_begun is not None:
        jax.profiler.stop_trace()
        trace_window = (trace_begun, w1)
        try:
            trace = tracing.load(tracing.find_xplane(trace_dir))
        finally:
            if not cell.keep_trace:
                shutil.rmtree(trace_dir, ignore_errors=True)

    steps = feed.steps - steps_before
    items = steps * rows * (seq_len if job["items"] == "tokens" else 1)
    peak = harness.memory_peak_bytes(cell.chips) + temp_bytes
    hits, misses = cache_stats()
    kept = feed.kept
    host_s = feed.host_s - host_before
    del state, pieces, compiled, feed

    # -- correct: the reference follows the same three steps ------------------
    t_ref = time.monotonic()
    block = int(job["correct"]["rows_per_block"])
    ref_out = ref.train_reference(
        ref.init_params(cfg, cell.seed), kept, cfg, opt, rows_per_block=block,
    )
    readings = compare(first, ref_out)
    reference_s = time.monotonic() - t_ref
    if cell.control in STAND_INS:
        # the control, or a planted fault: the reference, altered, stands
        # in the program's place and is judged by the cell's own limits
        stand_in = ref.train_reference(
            ref.init_params(cfg, cell.seed), kept, cfg, opt, rows_per_block=block,
            **STAND_INS[cell.control](ref, job, rows),
        )
        readings = {
            **compare(stand_in, ref_out),
            **{"program_" + k: v for k, v in readings.items()},
        }
    checks = harness.judge(readings, job["correct"]["limits"])
    correct = harness.all_within(checks) and steps > 0

    run_out: Dict[str, Any] = {
        "device": device,
        "end_to_end": {
            "setup_s": w0 - cell.t_start,
            "train_items_per_s_per_chip": items / (w1 - w0) / cell.chips,
        },
        "memory_peak_bytes": peak, "correct": correct,
        "attempted": steps, "failed": 0, "checks": checks,
        "reference_s": reference_s,
        "cache_misses": misses, "cache_hits": hits,
        "window": (w0, w1), "trace_window": trace_window,
        "input_host_s": host_s, "steps": steps, "cell": cell,
        "samples": {}, "events": [], "ticks": [],
    }
    if harness.attach_trace(cell, run_out, trace):
        n = len(tracing.program_seconds(trace, job["step_program"])) / max(
            len(trace.modules), 1
        )  # one event a step on every device
        run_out["useful_flops_in_trace"] = (
            n * rows * ref.train_flops_per_sequence(cfg, seq_len)
        )
        run_out["trace_seconds"] = tracing.window_seconds(trace)
    return run_out
