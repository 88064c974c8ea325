"""Persistent compilation cache + AOT step warmup.

Compiling is the expensive part of a cold start — minutes for a full
model on the TPU — and a compiled executable is reusable by any later
process that asks for the same program on the same device. This module
is the cheap-restart story:

* :func:`enable_compile_cache` is the ONE place that decides where
  JAX's on-disk compilation cache lives. Every entry point
  (``chip_smoke.py``, ``bench.py``, ``loop.fit``, ``Server.build`` /
  ``build_fleet``, the ``scripts/*_bench.py``) calls it before its
  first compile. The directory is part of the cache key's world: a
  path that moves between runs never hits, so the rule has exactly two
  outcomes, both fixed paths.
* :func:`cache_stats` observes the cache's hit/miss monitoring events so
  a warm start can be *proved* (hits > 0 on a second run against the
  same directory) instead of inferred from wall clock.
* :func:`warmup_engine` — backing for ``Engine.warmup()`` — AOT-compiles
  the train (and optionally eval) step before any data flows, logs
  compile seconds and XLA cost-analysis FLOPs, and installs the
  executables on the :class:`~.metrics.StepFn` so the loop's first step
  does not compile again.
"""

from __future__ import annotations

import os
import threading
from pathlib import Path
from typing import Any, Dict, Optional, Tuple

import jax
from jax.experimental.compilation_cache import compilation_cache

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.utils import heartbeat
from distributeddeeplearning_tpu.utils.logging import get_logger

# Where the cache lives when nothing outside says otherwise: inside the
# checkout (listed in .gitignore), so it is the same path on every run
# from this tree.
DEFAULT_CACHE_DIR = str(Path(__file__).resolve().parents[2] / ".jax_cache")

_stats = {"hits": 0, "misses": 0}
_listener_lock = threading.Lock()
_listener_installed = False


def _on_event(event: str, **kw) -> None:
    # jax's monitoring events are the ground truth for persistent-cache
    # behaviour; mirror them onto the event bus so a run report can show
    # warm-vs-cold starts without parsing log lines.
    if event.endswith("/cache_hits"):
        _stats["hits"] += 1
        obs.counter("xla_cache_hit")
    elif event.endswith("/cache_misses"):
        _stats["misses"] += 1
        obs.counter("xla_cache_miss")


def install_cache_listener() -> None:
    """Subscribe to the compilation-cache monitoring events (idempotent)."""
    global _listener_installed
    with _listener_lock:
        if not _listener_installed:
            jax.monitoring.register_event_listener(_on_event)
            _listener_installed = True


def cache_stats() -> Tuple[int, int]:
    """(persistent-cache hits, misses) observed so far this process."""
    return _stats["hits"], _stats["misses"]


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its
    directory. Call before the first compile of the process.

    The rule: when ``JAX_COMPILATION_CACHE_DIR`` is set, whoever runs
    the program has placed the cache — JAX read the variable at import
    and no directory is set here. Otherwise the cache is
    ``<checkout>/.jax_cache``. Every program is cached (JAX's default
    skips compiles under a second, which is all of them on the CPU test
    tier and none of the ones that matter on the TPU; one rule for both).
    """
    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = DEFAULT_CACHE_DIR
        if jax.config.jax_compilation_cache_dir != cache_dir:
            jax.config.update("jax_compilation_cache_dir", cache_dir)
            # jax latches "no cache" at the first compile of the
            # process; a caller that compiled before asking for the
            # cache needs the latch cleared for the directory to count.
            compilation_cache.reset_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    install_cache_listener()
    return cache_dir


def cost_analysis_flops(compiled: Any) -> Optional[float]:
    """FLOPs per execution from XLA's cost analysis (None if the backend
    does not report them — cost analysis is advisory, never load-bearing)."""
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return None
    if isinstance(ca, (list, tuple)):
        ca = ca[0] if ca else {}
    if isinstance(ca, dict):
        flops = ca.get("flops", 0.0)
        return float(flops) if flops else None
    return None


def warmup_engine(
    eng,
    batch: Any,
    *,
    acc: Any = None,
    eval_batch: Any = None,
) -> Dict[str, float]:
    """AOT-compile ``eng``'s steps against ``batch``'s signature.

    ``batch`` is a staged (device-resident) batch or a matching tree of
    ``jax.ShapeDtypeStruct``; ``acc`` non-None warms the accumulating
    train-step variant (what ``loop.fit`` runs). Returns compile seconds,
    cost-analysis FLOPs, and the persistent-cache hit/miss delta, and
    logs a one-line summary.
    """
    log = get_logger()
    hits0, misses0 = cache_stats()
    info: Dict[str, float] = {}

    step = eng.train_step
    # The outer AOT signature is unchanged by in-step accumulation (the
    # [k, micro_b, ...] reshape and the f32 grad accumulator live inside
    # the compiled program), but the program itself differs per
    # accum_steps — report which variant was compiled.
    accum_steps = int(getattr(step, "accum_steps", 1))
    if accum_steps > 1:
        info["accum_steps"] = float(accum_steps)
    if hasattr(step, "aot_compile"):
        # Heartbeat while XLA works: an AOT compile is silent for
        # minutes at pod scale, and the launcher's hang watchdog counts
        # stdout as liveness — without this a healthy, compiling world
        # gets killed at --hang-timeout (utils/heartbeat.py). The
        # `compile` span is StepFn.aot_compile's own.
        with heartbeat.during("aot_compile:train_step"):
            compiled, secs = step.aot_compile(eng.state, batch, acc)
        info["train_compile_sec"] = secs
        flops = cost_analysis_flops(compiled)
        if flops is not None:
            info["train_flops_per_step"] = flops
    if eval_batch is not None and hasattr(eng.eval_step, "aot_compile"):
        with heartbeat.during("aot_compile:eval_step"):
            _, secs = eng.eval_step.aot_compile(eng.state, eval_batch)
        info["eval_compile_sec"] = secs

    hits1, misses1 = cache_stats()
    info["persistent_cache_hits"] = float(hits1 - hits0)
    info["persistent_cache_misses"] = float(misses1 - misses0)
    info["compile_sec"] = info.get("train_compile_sec", 0.0) + info.get(
        "eval_compile_sec", 0.0
    )
    flops = info.get("train_flops_per_step")
    log.info(
        "warmup(%s%s): compiled in %.2fs%s (persistent cache: %d hit, %d miss)",
        eng.name,
        f", accum_steps={accum_steps}" if accum_steps > 1 else "",
        info["compile_sec"],
        f", {flops / 1e9:.2f} GFLOP/step" if flops else "",
        hits1 - hits0,
        misses1 - misses0,
    )
    return info
