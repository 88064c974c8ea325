"""BatchNorm with per-replica semantics under any engine.

SURVEY.md §7 hard part (b): the reference's Horovod training normalizes
every worker's activations with that worker's LOCAL batch statistics
(non-sync BN). The shard_map (dp) engine reproduces this for free —
``nn.BatchNorm`` runs on the local shard. Under the pjit engine the
model sees the GLOBAL batch, so a plain ``nn.BatchNorm``'s reductions
become sync-BN: different training semantics, non-comparable
checkpoints. Round 3 refused BN models under pjit; this module closes
the gap (VERDICT r3 #4) with *batch-split* BN:

* :func:`per_replica_bn` (a trace-time context, entered by
  ``make_pjit_train_step`` around the forward) declares how many
  data shards the global batch is split across.
* :class:`BatchNorm` — inside that context, with G > 1 groups, it
  reshapes ``[B, ...]`` to ``[G, B/G, ...]`` and computes statistics
  per group. The group axis is annotated with the ``batch`` logical
  axis, so under GSPMD each group's reduction is local to its data
  shard — no cross-shard stats collectives. Each group's rows match
  exactly the rows the dp engine would place on one device
  (``shard_batch`` shards the leading axis contiguously), so the
  math equals the dp engine's per-replica BN.
* Running statistics update with the across-group mean of the group
  statistics — exactly the dp engine's ``pmean`` of per-replica
  updates (``training/train_step.py``), keeping state device-invariant.

The class is deliberately named ``BatchNorm``: flax auto-names modules
by class name, so the parameter/batch_stats tree stays ``BatchNorm_k``
— bit-compatible with ``nn.BatchNorm`` checkpoints. Outside the
context (G == 1), at init, and in eval mode it defers to
``nn.BatchNorm`` unchanged. The grouped statistics/normalization reuse
flax's own ``_compute_stats`` / ``_normalize`` so the per-group math is
the same code path the dp engine runs per shard.
"""

from __future__ import annotations

import contextlib
import contextvars
import inspect
import warnings

import flax.linen as nn
import jax
import jax.numpy as jnp
from flax.linen import module as flax_module
from flax.linen import normalization as flax_norm

# ContextVar, not a module global: the group count is trace-local state,
# and concurrent traces (train + eval compiled from different threads)
# must each observe their own context (ADVICE r4).
_GROUPS: contextvars.ContextVar[int] = contextvars.ContextVar(
    "per_replica_bn_groups", default=1
)


_FLAX_API_CHECKED = False


def _check_flax_private_api() -> None:
    """The grouped path reuses flax's private ``_compute_stats`` /
    ``_normalize`` so per-group math is bit-identical to what
    ``nn.BatchNorm`` runs per shard under the dp engine. Private API can
    drift between flax minors — verify the parameter names we pass (all
    passed by keyword below) at the FIRST GROUPED USE, so a signature
    break fails here with an actionable message instead of mid-call-
    convention breakage (ADVICE r4) — and only for users of this path:
    checking at import would make the whole models package unimportable
    for e.g. LM inference, which never groups."""
    global _FLAX_API_CHECKED
    if _FLAX_API_CHECKED:
        return
    need_stats = {"x", "axes", "dtype", "use_fast_variance",
                  "force_float32_reductions"}
    # force_float32_reductions is OPTIONAL in _normalize: flax 0.10.x
    # does the dtype promotion internally and has no such parameter —
    # _ffr_kwargs() below omits it there.
    need_norm = {"mdl", "x", "mean", "var", "reduction_axes", "feature_axes",
                 "dtype", "param_dtype", "epsilon", "use_bias", "use_scale",
                 "bias_init", "scale_init"}
    have_stats = set(inspect.signature(flax_norm._compute_stats).parameters)
    have_norm = set(inspect.signature(flax_norm._normalize).parameters)
    missing = (need_stats - have_stats) | (need_norm - have_norm)
    if missing:
        import flax

        raise RuntimeError(
            f"flax {flax.__version__} changed the private normalization API "
            f"the grouped-BN path relies on (missing params: "
            f"{sorted(missing)}). Re-check models/norm.py against "
            "flax.linen.normalization."
        )
    _FLAX_API_CHECKED = True


def _ffr_kwargs(fn, value) -> dict:
    """``{"force_float32_reductions": value}`` when ``fn`` accepts it,
    else empty (flax 0.10.x ``_normalize`` promotes dtypes internally)."""
    if "force_float32_reductions" in inspect.signature(fn).parameters:
        return {"force_float32_reductions": value}
    return {}


@contextlib.contextmanager
def per_replica_bn(groups: int):
    """Trace-time context: BatchNorm computes statistics per batch-split
    group (one group per data shard). ``groups=1`` is a no-op."""
    token = _GROUPS.set(int(groups))
    try:
        yield
    finally:
        _GROUPS.reset(token)


def active_groups() -> int:
    return _GROUPS.get()


class BatchNorm(nn.BatchNorm):
    """``nn.BatchNorm`` with batch-split per-replica statistics when a
    :func:`per_replica_bn` context is active (see module docstring).
    Only the default ``axis=-1`` feature layout participates in
    grouping; anything else defers to the flax implementation."""

    @nn.compact
    def __call__(self, x, use_running_average=None, *, mask=None):
        use_ra = flax_module.merge_param(
            "use_running_average", self.use_running_average, use_running_average
        )
        groups = _GROUPS.get()
        expected_fallback = groups <= 1 or use_ra or self.is_initializing()
        if expected_fallback or (
            mask is not None
            or self.axis != -1
            # explicit cross-device stat sync requested — honour it
            or self.axis_name is not None
            or self.axis_index_groups is not None
            or x.ndim < 2
            or x.shape[0] % groups
        ):
            if not expected_fallback:
                # A per-replica context is ACTIVE but this layer cannot
                # group (e.g. traced batch not divisible by dp shards):
                # statistics silently become global-batch (sync-BN) —
                # different training semantics than the engine believes.
                # Surface it once per gating reason (ADVICE r4).
                warnings.warn(
                    f"per_replica_bn({groups}) active but BatchNorm "
                    f"'{self.name}' fell back to global-batch statistics "
                    f"(x.shape={x.shape}, axis={self.axis}, "
                    f"axis_name={self.axis_name}, mask={mask is not None}) "
                    "— training semantics are sync-BN for this layer.",
                    stacklevel=2,
                )
            return super().__call__(
                x, use_running_average=use_running_average, mask=mask
            )

        _check_flax_private_api()
        xg = x.reshape(groups, x.shape[0] // groups, *x.shape[1:])
        # Pin the group axis to the batch mesh axes: each group's
        # statistics reduction stays local to its data shard.
        xg = nn.with_logical_constraint(
            xg, ("batch",) + (None,) * (xg.ndim - 1)
        )
        reduction_axes = tuple(range(1, xg.ndim - 1))
        mean, var = flax_norm._compute_stats(
            x=xg,
            axes=reduction_axes,
            dtype=self.dtype,
            use_fast_variance=self.use_fast_variance,
            **_ffr_kwargs(
                flax_norm._compute_stats, self.force_float32_reductions
            ),
        )  # [G, C] each

        running_dtype = (
            jnp.float32 if self.force_float32_reductions else self.param_dtype
        )
        c = x.shape[-1]
        ra_mean = self.variable(
            "batch_stats", "mean", lambda: jnp.zeros((c,), running_dtype)
        )
        ra_var = self.variable(
            "batch_stats", "var", lambda: jnp.ones((c,), running_dtype)
        )
        m = self.momentum
        # = the dp engine's pmean over per-replica updated stats.
        ra_mean.value = m * ra_mean.value + (1 - m) * jnp.mean(mean, axis=0)
        ra_var.value = m * ra_var.value + (1 - m) * jnp.mean(var, axis=0)

        y = flax_norm._normalize(
            mdl=self,
            x=xg,
            mean=mean,
            var=var,
            reduction_axes=reduction_axes,
            feature_axes=(xg.ndim - 1,),
            dtype=self.dtype,
            param_dtype=self.param_dtype,
            epsilon=self.epsilon,
            use_bias=self.use_bias,
            use_scale=self.use_scale,
            bias_init=self.bias_init,
            scale_init=self.scale_init,
            **_ffr_kwargs(flax_norm._normalize, self.force_float32_reductions),
        )
        return y.reshape(x.shape)
