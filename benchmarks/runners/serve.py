"""The serving run: ``Server.submit`` / ``Server.step`` over a warmed
``SlotEngine``, under an open loop (arrivals at a fixed rate, from a
thread of their own) or a closed loop (a backlog kept at a fixed depth).

Set-up: weights from the seed on the device, the server built and its
programs compiled, then the load runs for ``lead_s`` seconds so that the
window opens on a full server. The window is ``--seconds`` long. In the
open loop the load stays on after it closes until every request that was
due inside it has finished (up to a minute); a traced run traces its
second then. Only after that does the reference run, on a sample of
finished requests.
"""

from __future__ import annotations

import collections
import contextlib
import gc
import os
import shutil
import threading
import time
from typing import Any, Dict, List, Optional

import numpy as np

from benchmarks import harness, stats, tracing
from benchmarks import traffic as trafficlib

GRACE_S = 60.0  # how long past the close a due answer is waited for
TRACE_AFTER_S = 0.5  # the trace starts this long after the window's close:
# by then every request due inside it has its first token (the queue's
# wait at 4/5 of the knee is tens of ms), so the profiler's start, which
# holds the pump, delays no time to first token that the run reports


class GcClock:
    """When the collector ran, and for how long: a pause of the host
    that shows as device idle time and in the tails."""

    def __init__(self):
        self.pauses: List[tuple] = []
        self._t = None

    def __call__(self, phase, info):  # noqa: ARG002
        if phase == "start":
            self._t = time.monotonic()
        elif self._t is not None:
            self.pauses.append((self._t, time.monotonic() - self._t))

    def seconds(self, a: float, b: float) -> float:
        return sum(d for t, d in self.pauses if a <= t < b)


class Rec:
    """What the benchmark knows of one request."""
    __slots__ = ("req", "due", "sent", "handle", "times", "failed")

    def __init__(self, req: trafficlib.ServeRequest, due: float):
        self.req, self.due = req, due
        self.sent: Optional[float] = None
        self.handle = None
        self.times: List[float] = []  # one per output token, host clock
        self.failed = False

    @property
    def done(self) -> bool:
        return self.handle is not None and self.handle.done.is_set()

    def on_token(self, handle, toks) -> None:  # noqa: ARG002
        t = time.monotonic()
        self.times.extend([t] * len(toks))


def _annotate(name: str):
    import jax

    return jax.profiler.TraceAnnotation(tracing.HOST_PREFIX + name)


def _submit(server, prog, rec: Rec) -> bool:
    from distributeddeeplearning_tpu.serving import QueueFull

    rec.sent = time.monotonic()
    try:
        with _annotate("submit"):
            rec.handle = server.submit(prog.serve_request(
                rec.req.prompt, rec.req.max_new_tokens, rec.on_token
            ))
    except QueueFull:
        rec.failed = True
        return False
    return True


class _Tracer:
    """Traces ``trace_s`` seconds from the window's close, from the pump
    thread. The load stays on meanwhile, so the traced part sees the
    window's traffic; and the profiler's start and stop, which can hold
    the pump for seconds, fall outside the window, so the host-clock
    metrics of a traced run are the window's own."""

    def __init__(self, cell, w1: float):
        self.on = cell.trace
        self.keep = cell.keep_trace
        self.t_start = w1 + TRACE_AFTER_S
        self.seconds = float(cell.traffic.get("trace_s", 1.0))
        self.dir = os.path.join(harness.TRACE_DIR, cell.name)
        self.span = None
        self.window = None
        self.begun = None

    @property
    def pending(self) -> bool:
        """Still to start, or running."""
        return self.on and self.window is None

    def tick(self, now: float) -> None:
        import jax

        if not self.pending:
            return
        if self.span is None and now >= self.t_start:
            shutil.rmtree(self.dir, ignore_errors=True)
            tracing.start(self.dir)
            self.begun = time.monotonic()
            self.span = _annotate("traced_window")
            self.span.__enter__()
        elif self.span is not None and now >= self.begun + self.seconds:
            self.span.__exit__(None, None, None)
            self.span = None
            self.window = (self.begun, time.monotonic())
            jax.profiler.stop_trace()  # writes the file: the pump stands still
            self.stopped = time.monotonic()

    def result(self):
        if self.window is None:
            return None
        try:
            return tracing.load(tracing.find_xplane(self.dir))
        finally:
            if not self.keep:
                shutil.rmtree(self.dir, ignore_errors=True)


def _program_temp_bytes(engine) -> int:
    """The largest temporary of the engine's compiled programs."""
    worst = 0
    for ps in engine.program_specs():
        if ps.installed:
            with contextlib.suppress(Exception):
                worst = max(worst, int(ps._get().memory_analysis().temp_size_in_bytes))
    return worst


def _buckets(mix: dict, cfg: dict) -> List[int]:
    """The prefill buckets this traffic can draw: the default ladder's
    members between the shortest and the longest prompt (the program
    pads a prompt up to the next bucket)."""
    from distributeddeeplearning_tpu.serving.engine import default_buckets

    ladder = default_buckets(int(cfg["n_positions"]))
    lo, hi = int(mix["prompt_len"]["min"]), int(mix["prompt_len"]["max"])
    need = [b for b in ladder if b >= lo]
    top = next(b for b in ladder if b >= hi)
    return [b for b in need if b <= top]


def run(cell: harness.Cell) -> Dict[str, Any]:
    import jax

    device = harness.device_info(cell)
    harness.enable_cache()
    from distributeddeeplearning_tpu import obs
    from distributeddeeplearning_tpu.training.warmup import cache_stats

    bus = obs.configure(None, ring_size=4_000_000)
    ref, prog = cell.family("references"), cell.family("programs")
    mix, cfg = cell.traffic, cell.config
    vocab = int(cfg["vocab_size"])
    closed = mix["loop"] == "closed"

    params = ref.init_params(cfg, cell.seed)
    server_kw = dict(mix["server"])
    if cell.control == "program":
        server_kw.update(mix["correct"]["control_server"])
    server = prog.build_server(cfg, server_kw, params, _buckets(mix, cfg))
    engine = server.engine
    if cell.sabotage is not None:
        cell.sabotage(server)

    seconds = cell.seconds
    lead = float(mix["lead_s"])
    if closed:
        backlog = trafficlib.closed_loop_requests(mix, cell.seed, vocab)
        recs: List[Rec] = []
    else:
        reqs = trafficlib.open_loop_requests(
            mix, cell.seed, seconds, vocab, tail_s=GRACE_S
        )
    jax.block_until_ready(params)
    # what set-up made (modules, programs, the requests) is taken out of
    # the collector's reach, so that a full collection inside the window
    # walks the window's own objects only
    gc.collect()
    gc.freeze()
    gc_clock = GcClock()
    gc.callbacks.append(gc_clock)

    # -- the load ------------------------------------------------------------
    t0 = time.monotonic()
    w0, w1 = t0 + lead, t0 + lead + seconds
    stop = threading.Event()
    if not closed:
        recs = [Rec(r, w0 + r.due_s) for r in reqs]

        def feeder():
            for rec in recs:
                while True:
                    wait = rec.due - time.monotonic()
                    if wait <= 0 or stop.is_set():
                        break
                    time.sleep(min(wait, 0.05))
                if stop.is_set():
                    return
                _submit(server, prog, rec)

        thread = threading.Thread(target=feeder, name="bench-feeder", daemon=True)
        thread.start()
    in_window = lambda r: w0 <= r.due < w1  # noqa: E731

    tracer = _Tracer(cell, w1)
    waiting: collections.deque = collections.deque()
    live: List[Rec] = []
    ticks: List[Dict[str, float]] = []
    outstanding = 0
    seen = 0
    try:
        while True:
            now = time.monotonic()
            tracer.tick(now)
            if closed:
                # top the backlog up; what the queue cannot take yet is
                # offered again after the next tick
                while (outstanding < int(mix["clients"])
                       and server.queued_count < server.queue_limit):
                    rec = Rec(next(backlog), now)
                    _submit(server, prog, rec)
                    recs.append(rec)
                    outstanding += 1
            t_a = time.monotonic()
            with _annotate("server_step"):
                busy = server.step()
            t_b = time.monotonic()
            # which requests got their first token in this tick
            while seen < len(recs) and recs[seen].sent is not None:
                if not recs[seen].failed:
                    waiting.append(recs[seen])
                seen += 1
            admitted = [r for r in waiting if r.times]
            for r in admitted:
                waiting.remove(r)
                live.append(r)
            if live:
                ticks.append({
                    "t0": t_a, "t1": t_b, "rows": float(len(live)),
                    "live_tokens": float(sum(
                        len(r.req.prompt) + len(r.times) - 1 for r in live
                    )),
                    "prefill": [len(r.req.prompt) for r in admitted],
                })
            finished = [r for r in live if r.done]
            if finished:
                live = [r for r in live if not r.done]
                outstanding -= len(finished)
            if now >= w1 and not tracer.pending:
                if closed or all(
                    r.done or r.failed for r in recs if in_window(r)
                ):
                    break
                if now >= (tracer.stopped if tracer.on else w1) + GRACE_S:
                    break
            if not busy:
                time.sleep(0.0005)
    finally:
        stop.set()
        gc.callbacks.remove(gc_clock)
        gc.unfreeze()
        if not closed:
            thread.join(timeout=10)

    # -- what the window held -------------------------------------------------
    peak = harness.memory_peak_bytes(cell.chips) + _program_temp_bytes(engine)
    hits, misses = cache_stats()
    events = [e for e in bus.ring if w0 <= e["t"] < w1]
    num_slots = int(engine.num_slots)
    def whole(r: Rec) -> bool:
        return (not r.failed and r.done and r.handle.status == "done"
                and len(r.times) == r.req.max_new_tokens)

    if closed:
        due = [r for r in recs if r.done and w0 <= r.handle.finished_t < w1]
        failed = sum(1 for r in recs if r.failed)
    else:
        due = [r for r in recs if in_window(r)]
        failed = sum(1 for r in due if not whole(r))
    good = [r for r in due if whole(r)]
    tokens_in_window = sum(
        sum(1 for t in r.times if w0 <= t < w1)
        + (len(r.req.prompt) if r.times and w0 <= r.times[0] < w1 else 0)
        for r in recs
    )
    samples = {
        "tpot_ms": [1e3 * stats.tpot_s(r.times) for r in good],
        "ttft_ms": [1e3 * (r.times[0] - r.due) for r in good],
        "late_ms": [1e3 * (r.sent - r.due) for r in due if r.sent is not None],
        "itl_ms": [1e3 * g for r in recs for g in stats.gaps_s(r.times, w0, w1)],
        "request_ms": [1e3 * (r.times[-1] - r.due) for r in good],
    }
    samples["tick_ms"] = [
        1e3 * (b["t0"] - a["t0"]) for a, b in zip(ticks, ticks[1:])
        if w0 <= a["t0"] and b["t0"] < w1
    ]
    # the window's first half and three quarters alone: how the tail's
    # steadiness grows with the window's length, read from the same run
    for name, share in (("tpot_ms.half", 0.5), ("tpot_ms.three_quarters", 0.75)):
        samples[name] = [
            1e3 * stats.tpot_s(r.times) for r in good
            if r.due < w0 + share * seconds
        ]
    end_to_end = {
        "setup_s": w0 - cell.t_start,
        "serve_tokens_per_s": tokens_in_window / seconds,
    }
    if samples["tpot_ms"]:
        end_to_end["serve_tpot_p95_ms"] = stats.percentile(samples["tpot_ms"], 95)

    trace = tracer.result()
    served = [
        (np.asarray(r.req.prompt), np.asarray(r.handle.new_tokens, np.int32))
        for r in good
    ]
    server.close()
    del server, engine, params, recs, live, waiting
    bus.ring.clear()

    # -- correct: the reference over a sample of what was served ---------------
    t_ref = time.monotonic()
    readings = check_served(cell, ref, served)
    reference_s = time.monotonic() - t_ref
    checks = harness.judge(readings, mix["correct"]["limits"])
    correct = harness.all_within(checks) and failed == 0 and bool(good)

    run_out: Dict[str, Any] = {
        "device": device, "end_to_end": end_to_end,
        "memory_peak_bytes": peak, "correct": correct,
        "attempted": len(due), "failed": failed, "checks": checks,
        "reference_s": reference_s,
        "cache_misses": misses, "cache_hits": hits,
        "samples": samples, "events": events, "ticks": ticks,
        "window": (w0, w1), "trace_window": tracer.window,
        "num_slots": num_slots, "cell": cell,
        "host": {"gc_pause_s.window": gc_clock.seconds(w0, w1)},
    }
    if harness.attach_trace(cell, run_out, trace):
        ta, tb = tracer.window
        in_trace = [k for k in ticks if ta <= k["t0"] and k["t1"] <= tb]
        # is the traced part like the window? its ticks and its pauses
        run_out["host"]["gc_pause_s.traced"] = gc_clock.seconds(ta, tb)
        if len(in_trace) > 1:
            run_out["host"]["tick_ms.traced.mean"] = (
                1e3 * (in_trace[-1]["t0"] - in_trace[0]["t0"]) / (len(in_trace) - 1)
            )
        run_out["trace_ticks"] = in_trace
        run_out["useful_flops_in_trace"] = sum(
            ref.forward_flops(cfg, k["rows"], k["live_tokens"] + k["rows"])
            + sum(ref.forward_flops(cfg, n, ref.causal_pairs(n)) for n in k["prefill"])
            for k in in_trace
        )
        run_out["trace_seconds"] = tb - ta
    return run_out


def check_served(cell: harness.Cell, ref, served) -> Dict[str, float]:
    """The gaps by which served tokens' logits lie below the reference's
    best, over a sample of finished requests drawn from the seed, the
    longest among them. The reference makes its own weights
    from the seed and sees only prompts and served tokens."""
    if not served:
        return {"miss_gap_meansq": float("nan")}
    cfg, mix = cell.config, cell.traffic
    rng = np.random.default_rng([int(cell.seed), 4])
    k = min(int(mix["correct"]["sample"]), len(served))
    longest = max(range(len(served)), key=lambda i: len(served[i][0]) + len(served[i][1]))
    rest = [i for i in rng.permutation(len(served)) if i != longest][: k - 1]
    picked = [longest] + [int(i) for i in rest]
    width = int(cfg["n_positions"])
    rows = np.zeros((len(picked), width), np.int32)
    mask = np.zeros((len(picked), width), bool)
    for j, i in enumerate(picked):
        prompt, out = served[i]
        n = min(len(prompt) + len(out), width)
        rows[j, :n] = np.concatenate([prompt, out])[:n]
        mask[j, len(prompt) - 1: n - 1] = True  # position p judges token p+1
    control = mix["correct"]["control_precision"] if cell.control == "reference" else None
    params = ref.init_params(cfg, cell.seed)
    gaps, ctl = ref.served_gaps(params, rows, cfg, control)
    def read(g, prefix=""):
        g = g[mask]
        missed = g[g > 0]  # served tokens that are not the reference's best
        return {
            # the number compared: how far below the reference's best a
            # served token lies where it is not the best, in the mean
            # square. bf16 misses only near-ties; a lower precision misses
            # by more (PERF.md section 6)
            prefix + "miss_gap_meansq": (
                float(np.square(missed).mean()) if missed.size else 0.0
            ),
            prefix + "token_gap_max": float(g.max()),
            prefix + "token_gap_mean": float(g.mean()),
            prefix + "token_gap_meansq": float(np.square(g).mean()),
            prefix + "token_mismatch_share": float(missed.size / g.size),
        }

    if control is None:
        out = read(gaps)
    else:  # the control stands in the program's place and is judged
        out = {**read(ctl), **read(gaps, "program_")}
    out["tokens_compared"] = float(mask.sum())
    return out
