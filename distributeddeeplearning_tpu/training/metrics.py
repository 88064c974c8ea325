"""On-device metric accumulation — true epoch means with one host sync.

The loop used to report the *last* step's metrics at each epoch boundary
(anything wanting real epoch statistics had to ``device_get`` mid-epoch
and stall async dispatch). Now every engine's compiled step also threads
a tiny donated accumulator pytree — per-metric running f32 sum plus a
step count — so the epoch mean is computed entirely on device and the
loop materialises exactly ONE small pytree per epoch.

Contract (all four engines — ``train_step.py``, ``pjit_step.py``,
``sp_step.py``, ``pp_step.py`` — return a :class:`StepFn`):

    step(state, batch)          -> (state, metrics)            # as ever
    step(state, batch, acc)     -> (state, metrics, new_acc)   # fused

The accumulating variant is a *separate* compiled program (lazily built:
callers that never pass ``acc`` never pay its compile), and both the
state and the accumulator are donated — the accumulator lives in the
same buffers for the whole epoch.

``METRIC_KEYS`` is the cross-engine metric contract: every train step
emits exactly these scalar metrics, already reduced across the mesh.

In-step gradient accumulation (``ACCUM_STEPS`` — ``training/accum.py``)
keeps this contract intact: a microbatched step emits ONE metric sample
per dispatch (the f32 mean over its k microbatches, with ``grad_norm``
taken on the final mean gradient), so the epoch accumulator below still
counts effective steps and the epoch mean stays a mean over optimizer
updates, exactly as without accumulation.
"""

from __future__ import annotations

import time
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.obs import programs
from distributeddeeplearning_tpu.training.warmup import cache_stats

PyTree = Any

# Every engine's train step emits exactly these (cross-replica-reduced,
# f32 scalar) metrics; the loop sizes the accumulator from this tuple.
METRIC_KEYS: Tuple[str, ...] = ("loss", "accuracy", "grad_norm")


def init_accumulator(mesh=None, keys: Tuple[str, ...] = METRIC_KEYS) -> PyTree:
    """Fresh zeroed accumulator, replicated over ``mesh`` when given
    (the shard_map engines take it with an unsharded ``P()`` in_spec).

    Besides the metric sums + step count it carries ``nonfinite`` — the
    on-device non-finite-loss counter (ISSUE 4's guard): one extra f32
    add per step inside the already-compiled program, materialised with
    the rest of the accumulator at the epoch boundary, so NaN/Inf
    detection costs ZERO additional host syncs."""
    acc = {
        "sums": {k: jnp.zeros((), jnp.float32) for k in keys},
        "count": jnp.zeros((), jnp.float32),
        "nonfinite": jnp.zeros((), jnp.float32),
    }
    if mesh is not None:
        from distributeddeeplearning_tpu.parallel.mesh import (
            replicated_sharding,
        )

        acc = jax.device_put(acc, replicated_sharding(mesh))
    return acc


def accumulate_metrics(acc: PyTree, metrics: Dict[str, jnp.ndarray]) -> PyTree:
    """One fused-into-the-step update: sums += metrics, count += 1 (and
    nonfinite += [loss is NaN/Inf]).

    All math is f32 adds in step order, so the finalized mean is
    bit-identical to a host-side f32 running mean of the same per-step
    values (the oracle in ``tests/test_sync_free_loop.py``)."""
    sums = {
        k: acc["sums"][k] + metrics[k].astype(jnp.float32)
        for k in acc["sums"]
    }
    out = {"sums": sums, "count": acc["count"] + jnp.float32(1.0)}
    if "nonfinite" in acc:  # pre-guard accumulator pytrees pass through
        loss = metrics["loss"].astype(jnp.float32)
        out["nonfinite"] = acc["nonfinite"] + jnp.where(
            jnp.isfinite(loss), jnp.float32(0.0), jnp.float32(1.0)
        )
    return out


def finalize_accumulator(acc: PyTree) -> Dict[str, jnp.ndarray]:
    """Epoch means (device values — the caller owns the one host sync).
    The non-finite step COUNT rides along as ``nonfinite_steps`` (a
    count, not a mean: one poisoned step must trip the guard even in a
    long epoch)."""
    safe = jnp.maximum(acc["count"], jnp.float32(1.0))
    out = {k: v / safe for k, v in acc["sums"].items()}
    if "nonfinite" in acc:
        out["nonfinite_steps"] = acc["nonfinite"]
    return out


class StepFn:
    """Compiled-step façade: arity dispatch + ahead-of-time slots.

    ``resolve(state, with_acc)`` returns the jitted callable for this
    state structure and arity — dp/sp/pjit ignore ``state`` (one
    program each), the pp engine builds per state-structure as before.

    :meth:`aot_compile` lowers + compiles a variant up front and
    *installs* the executable, so the loop's subsequent calls with the
    same signature dispatch straight to the compiled object instead of
    re-entering jit (``.lower().compile()`` does not populate jit's own
    executable cache — without the slot, warmup would compile twice).
    Calls whose batch signature differs (e.g. a padded tail batch) fall
    back to the normal jit path.
    """

    # Probed by loop.fit: wrappers built by the engines all accumulate;
    # a hand-rolled step without the 3-arg form keeps the legacy path.
    accumulates_metrics = True

    def __init__(self, resolve: Callable[[Any, bool], Callable]):
        self._resolve = resolve
        self._aot: Dict[tuple, Any] = {}

    @staticmethod
    def _signature(state, batch, with_acc: bool) -> tuple:
        return (
            with_acc,
            jax.tree_util.tree_structure(state),
            tuple(
                (tuple(x.shape), str(getattr(x, "dtype", type(x))))
                for x in jax.tree_util.tree_leaves(batch)
            ),
        )

    def __call__(self, state, batch, acc: Optional[PyTree] = None):
        with_acc = acc is not None
        if self._aot:
            compiled = self._aot.get(self._signature(state, batch, with_acc))
            if compiled is not None:
                return (
                    compiled(state, batch, acc)
                    if with_acc
                    else compiled(state, batch)
                )
        fn = self._resolve(state, with_acc)
        return fn(state, batch, acc) if with_acc else fn(state, batch)

    def lower(self, state, batch, acc: Optional[PyTree] = None):
        fn = self._resolve(state, acc is not None)
        args = (state, batch) if acc is None else (state, batch, acc)
        return fn.lower(*args)

    def aot_compile(
        self, state, batch, acc: Optional[PyTree] = None
    ) -> Tuple[Any, float]:
        """Compile ahead of time; returns ``(compiled, seconds)`` and
        installs the executable for matching calls.

        Every engine's warm-up comes through here, so this is where the
        ``compile`` span sits (children ``compile.lower``: trace and
        lower; ``compile.backend``: XLA's compile, or the load from the
        persistent cache) and where the program's scope table is
        registered (``obs/programs.py``; nothing is parsed until a
        trace is read against it)."""
        fn = self._resolve(state, acc is not None)
        program = "jit_" + getattr(fn, "__name__", "step")
        signature = self._signature(state, batch, acc is not None)
        hits = cache_stats()[0]
        t0 = time.perf_counter()
        with obs.span("compile", program=program) as labels:
            with obs.span("compile.lower", program=program):
                lowered = self.lower(state, batch, acc)
            with obs.span("compile.backend", program=program):
                compiled = lowered.compile()
            labels["cache_hit"] = cache_stats()[0] > hits
        seconds = time.perf_counter() - t0
        self._aot[signature] = compiled
        programs.register(program, compiled, owner=self, key=signature)
        return compiled, seconds


def dispatch_step(step: Callable, state, batch, acc: Optional[PyTree] = None,
                  **labels: Any):
    """One dispatch of a train step under the ``step`` span: the host's
    time in the call, which is all the host sees of a step. That is the
    time to enqueue it while the device's queue has room, and the
    device's own step time once the queue is full and every dispatch
    waits for a step to leave it. The explicit loop and ``loop.fit``
    both dispatch through here, so the two emit the same name."""
    with obs.span("step", **labels):
        if acc is None:
            return step(state, batch)
        return step(state, batch, acc)


def log_sync(**labels: Any):
    """The span round a loop's logging sync: every ``log_every`` steps
    the host reads a loss back and waits for the device to reach it."""
    return obs.span("step.log_sync", **labels)
