"""In-step microbatched gradient accumulation — shared machinery.

``ACCUM_STEPS=k`` (``TrainConfig.accum_steps``) makes every engine's
compiled step split its per-dispatch batch into ``k`` equal microbatches
*inside* the compiled program: a ``lax.scan`` runs the forward+backward
once per microbatch, summing gradients into an on-device f32 accumulator
(one params-sized buffer, reused across the scan by XLA), and the
optimizer applies the mean gradient ONCE at the end. The effective batch
stays the full dispatch batch while live activation memory scales with
the *microbatch* — the large-batch lever (Goyal et al. 2017) past what
one chip's HBM holds for a full batch of activations
(``scripts/accum_memory.py`` proves the footprint host-side).

Contrast with the pre-existing ``GRAD_ACCUM_STEPS`` (``optax.MultiSteps``,
``training/optimizer.py``): that accumulates across k *host dispatches*
(k dispatch overheads, k× the data-pipeline steps per update, optimizer
state carries the accumulator). ``ACCUM_STEPS`` keeps ONE dispatch per
effective step, so the ISSUE-1 sync-free-loop invariant (≤1 host sync
per epoch) and the dispatch-clock accounting are untouched, and the
accumulator never enters ``TrainState`` (checkpoints are
``accum_steps``-agnostic, ``tests/test_checkpoint.py``).

Semantics:

* gradients are mean-weighted: per-microbatch losses are microbatch
  means, summed grads are divided by ``k`` — ``accum_steps=k`` on batch
  B equals ``accum_steps=1`` on B up to f32 reduction order (the
  batch-dim reductions necessarily re-associate; the scan itself is
  bitwise-identical to sequentially computing and summing the same
  per-microbatch gradients — both asserted in
  ``tests/test_grad_accum.py``).
* metrics (loss, accuracy) are f32 means over the k microbatches, so
  the per-dispatch metric contract (``training/metrics.METRIC_KEYS``)
  and the on-device epoch accumulator are unchanged: one dispatch still
  accumulates one metric sample.
* BatchNorm models get **ghost batch norm** (Hoffer et al. 2017):
  statistics are computed per microbatch, and running statistics fold
  sequentially through the scan carry — identical to k sequential
  unaccumulated steps on the same microbatches (oracle in
  ``tests/test_grad_accum.py``).
* dropout draws independent noise per microbatch (the base per-step key
  is folded with the microbatch index).
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
from jax import lax

PyTree = Any

# The per-microbatch metric scalars every engine's micro-step emits;
# grad_norm is computed once on the final mean gradient (same semantics
# as the unaccumulated step: the norm of THE batch gradient, not a mean
# of microbatch norms).
MICRO_METRIC_KEYS: Tuple[str, ...] = ("loss", "accuracy")


def resolve_accum_steps(config) -> int:
    """``config.accum_steps`` as a validated positive int (configs built
    before the field existed resolve to 1)."""
    raw = getattr(config, "accum_steps", 1)
    k = int(1 if raw is None else raw)
    if k < 1:
        raise ValueError(f"ACCUM_STEPS must be >= 1, got {k}")
    return k


def validate_accum_config(config, mesh=None) -> int:
    """Config-time divisibility validation with every number named.

    The batch each data shard receives per dispatch is
    ``config.batch_size_per_device`` (the dataset is sized as
    ``batch_size_per_device × data-parallel width``); ``accum_steps``
    must divide it, and under ``ENGINE=pp`` each resulting microbatch
    must still split into ``pp_microbatches`` pipeline microbatches.
    Raises ``ValueError`` naming the three numbers; returns ``k``.
    """
    k = resolve_accum_steps(config)
    if k == 1:
        return k
    per_shard = config.batch_size_per_device
    if mesh is not None:
        from distributeddeeplearning_tpu.parallel.mesh import dp_size

        width = dp_size(mesh)
    else:
        width = config.data_parallel_width
    if per_shard % k:
        raise ValueError(
            f"ACCUM_STEPS={k} does not divide the per-shard batch: "
            f"global batch {per_shard * width} over {width} data-parallel "
            f"shard(s) leaves {per_shard} samples per shard, which is not "
            f"divisible by accum_steps={k}. Pick ACCUM_STEPS dividing "
            f"{per_shard}, or raise BATCHSIZE."
        )
    if config.engine == "pp":
        micro = per_shard // k
        if micro % config.pp_microbatches:
            raise ValueError(
                f"ENGINE=pp with ACCUM_STEPS={k}: each accumulation "
                f"microbatch holds {micro} samples per shard "
                f"(per-shard batch {per_shard} / accum_steps {k}), which "
                f"is not divisible by PP_MICROBATCHES="
                f"{config.pp_microbatches}. Pick values so that "
                f"batch_size_per_device / ACCUM_STEPS is a multiple of "
                f"PP_MICROBATCHES."
            )
    return k


def check_local_divisible(
    local_batch: int, k: int, *, dp: int, engine: str
) -> int:
    """Trace-time guard inside the step builders: the *actual* per-shard
    batch must reshape into ``k`` equal microbatches. Returns the
    microbatch size."""
    if local_batch % k:
        raise ValueError(
            f"ENGINE={engine} ACCUM_STEPS={k}: per-shard batch "
            f"{local_batch} (global batch {local_batch * dp} over {dp} "
            f"data-parallel shard(s)) is not divisible by accum_steps={k}"
        )
    return local_batch // k


def split_microbatches(tree: PyTree, k: int) -> PyTree:
    """Reshape every leaf ``[B, ...]`` → ``[k, B//k, ...]`` (leading-axis
    contiguous split — each microbatch is this shard's j-th slice, the
    same rows k sequential small dispatches would have seen)."""

    def split(x):
        b = x.shape[0]
        if b % k:
            raise ValueError(
                f"cannot split leading dim {b} into {k} microbatches"
            )
        return x.reshape(k, b // k, *x.shape[1:])

    return jax.tree.map(split, tree)


def accumulate_microbatches(
    micro_fn: Callable[[PyTree, PyTree, jnp.ndarray], Tuple[PyTree, Dict, PyTree]],
    xs: PyTree,
    k: int,
    grads_like: PyTree,
    *,
    metric_keys: Tuple[str, ...] = MICRO_METRIC_KEYS,
    extra0: PyTree = None,
    vary: Optional[Callable[[PyTree], PyTree]] = None,
    vary_metrics: Optional[Callable[[PyTree], PyTree]] = None,
) -> Tuple[PyTree, Dict[str, jnp.ndarray], PyTree]:
    """The accumulation scan every engine shares.

    ``micro_fn(extra, microbatch, idx) -> (grads, metrics, new_extra)``
    computes one microbatch's raw gradients (pre-collective — cross-mesh
    reductions run ONCE on the mean, after the scan) plus its scalar
    ``metric_keys`` values; ``extra`` threads engine state through the
    scan (the dp engine's ghost-BN running statistics; ``None``
    elsewhere). ``xs`` is the ``[k, micro_b, ...]`` microbatch tree from
    :func:`split_microbatches`.

    Gradients accumulate in f32 regardless of param dtype and the mean
    (``Σ/k``) is cast back to each ``grads_like`` leaf's dtype; metrics
    accumulate in f32 and come back as means. Under ``shard_map`` the
    zero-initialised carries must match the body outputs' varying axes —
    ``vary`` (grads + extra) and ``vary_metrics`` (metric scalars, which
    may be invariant over e.g. the pipe axis after an in-body psum) pcast
    them.
    """
    gacc0 = jax.tree.map(
        lambda g: jnp.zeros(jnp.shape(g), jnp.float32), grads_like
    )
    macc0 = {m: jnp.zeros((), jnp.float32) for m in metric_keys}
    if vary is not None:
        gacc0 = vary(gacc0)
        if extra0 is not None:
            extra0 = vary(extra0)
    if vary_metrics is not None:
        macc0 = vary_metrics(macc0)
    elif vary is not None:
        macc0 = vary(macc0)

    def body(carry, sl):
        gacc, macc, extra = carry
        mb, idx = sl
        grads, metrics, extra = micro_fn(extra, mb, idx)
        gacc = jax.tree.map(
            lambda a, g: a + g.astype(jnp.float32), gacc, grads
        )
        macc = {
            m: macc[m] + metrics[m].astype(jnp.float32) for m in macc
        }
        return (gacc, macc, extra), None

    (gacc, macc, extra), _ = lax.scan(
        body, (gacc0, macc0, extra0), (xs, jnp.arange(k))
    )
    grads = jax.tree.map(
        lambda a, g: (a / k).astype(jnp.result_type(g)), gacc, grads_like
    )
    metrics = {m: v / k for m, v in macc.items()}
    return grads, metrics, extra
