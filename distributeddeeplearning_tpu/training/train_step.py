"""The jitted data-parallel training step — the framework's hot loop.

This is the TPU-native replacement for the reference's entire runtime tier
(SURVEY.md §3): where Horovod hooks a per-tensor NCCL ring-allreduce into
backward (``hvd.DistributedOptimizer``, PyTorch ``:334-338``; TF
``:149-156``; Keras ``:162``), here forward, backward, gradient
``pmean``, and the optimizer update are ONE compiled XLA program laid out
over the device mesh with ``shard_map``. XLA schedules the ICI collectives
and overlaps them with backward compute; nothing crosses the host between
steps.

Semantics parity notes:
* **Per-replica BatchNorm** in the forward pass: each mesh slot
  normalises with its *local* batch statistics, exactly like the
  reference's non-sync BN under Horovod (SURVEY.md §7 hard part (b)).
  The *running* statistics are ``pmean``-averaged before being stored so
  the replicated state stays device-invariant (strictly better than the
  reference, which silently keeps rank-0's stats at checkpoint time).
* **Loss** = sparse softmax CE (TF ``:197-200``) + optional label
  smoothing + L2(5e-5) on kernels (Keras ``_create_model`` surgery,
  ``:97-116``).
* **Metrics** (loss, top-1 accuracy) are ``pmean``-averaged in-step —
  the reference needed a MetricAverageCallback (Keras ``:207``) /
  explicit ``hvd.allreduce`` (``:348``) to do this on the host.

**Names on the device's time.** Flax scopes every model operation by
module path; the step adds ``jax.named_scope`` for what no module
holds: ``loss`` (:func:`loss_and_hits`), the gradient reduction
(``training/overlap.OVERLAP_SCOPE``), ``optimizer`` (the update and its
application) and ``metrics`` (the accuracy from the loss's hits, the
gradient norm). They are metadata alone — no operation changes — and
``obs/programs.py`` sums a device trace by them.

The same step function runs on a 1-device mesh, an 8-device CPU test mesh
(the reference's ``mpirun -np 2`` smoke analogue, §4.2), and a multi-host
pod mesh — no code forks (§7 hard part (d)).
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.ops.attention import kernel_interpreted
from distributeddeeplearning_tpu.parallel.mesh import batch_axes, replicated_sharding
from distributeddeeplearning_tpu.training.overlap import overlap_scope
from distributeddeeplearning_tpu.training.state import TrainState

PyTree = Any
Batch = Tuple[jnp.ndarray, jnp.ndarray]  # (images NHWC, int labels)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def _sparse_softmax_ce(logits, labels, label_smoothing):
    """Per-example sparse softmax CE and the hit, ``[N, V] × [N] → ([N],
    [N])`` float32, reading the logits **in the dtype the model emitted**
    (bfloat16 from the LMs, float32 from the image models): every
    element is upcast inside the reductions that read it, so nothing of
    shape ``[N, V]`` in float32 is written. The target's logit is a
    masked sum (``where(iota == label, x, 0)``), not a gather, so it
    rides in the pass that sums the exponentials, as do the sum of the
    logits (label smoothing) and the hit: ``label ==`` the first index
    of the row's maximum, ``jnp.argmax``'s rule with its ties (a row
    holding a NaN counts as a miss). The hit carries no gradient.

    The backward is hand-written: ``d_logits = g·(softmax − targets)``
    from the upcast logits, the float32 ``lse [N]`` and an ``iota ==
    label`` comparison, written once, in the logits' dtype (the
    cotangent of a bfloat16 tensor is bfloat16 either way). The
    residual is the logits as they came. AD of a ``take_along_axis``
    instead lowers to a scatter-add over a fresh zeros ``[N, V]`` f32
    buffer: at LM scale (T=32k, V=32k) 3.9 GB."""
    loss, hit, _ = _sparse_ce_primal(logits, labels, label_smoothing)
    return loss, hit


def _sparse_ce_primal(logits, labels, label_smoothing):
    """One place for the loss formula (primal and fwd share it):
    ``(loss, hit, lse)``, each ``[N]`` float32."""
    x = logits.astype(jnp.float32)
    v = x.shape[-1]
    col = lax.broadcasted_iota(jnp.int32, x.shape, 1)
    top = jnp.max(x, axis=-1)
    # as jax.nn.logsumexp: a row whose maximum is not finite shifts by 0
    shift = jnp.where(jnp.isfinite(top), top, 0.0)
    lse = shift + jnp.log(jnp.sum(jnp.exp(x - shift[:, None]), axis=-1))
    picked = jnp.sum(jnp.where(col == labels[:, None], x, 0.0), axis=-1)
    # the label is the maximum's first index: it holds the maximum and no
    # column before it does. A sum like the others, so one pass takes all.
    tied_before = jnp.sum(
        jnp.where((x == top[:, None]) & (col < labels[:, None]), 1.0, 0.0), axis=-1
    )
    hit = ((picked == top) & (tied_before == 0.0)).astype(jnp.float32)
    if label_smoothing > 0.0:
        on = 1.0 - label_smoothing
        off = label_smoothing / (v - 1)
        # -Σ targets·logp with targets = onehot·(on−off) + off
        return lse - (on - off) * picked - off * jnp.sum(x, axis=-1), hit, lse
    return lse - picked, hit, lse


def _sparse_softmax_ce_fwd(logits, labels, label_smoothing):
    loss, hit, lse = _sparse_ce_primal(logits, labels, label_smoothing)
    return (loss, hit), (logits, labels, lse)


def _sparse_softmax_ce_bwd(label_smoothing, res, g):
    logits, labels, lse = res
    g_loss, _ = g  # the hit has no gradient
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    at_label = lax.broadcasted_iota(jnp.int32, logits.shape, 1) == labels[:, None]
    if label_smoothing > 0.0:
        off = label_smoothing / (logits.shape[-1] - 1)
        targets = jnp.where(at_label, 1.0 - label_smoothing, off)
    else:
        targets = at_label.astype(jnp.float32)
    return (((p - targets) * g_loss[:, None]).astype(logits.dtype), None)


_sparse_softmax_ce.defvjp(_sparse_softmax_ce_fwd, _sparse_softmax_ce_bwd)


def loss_and_hits(
    logits: jnp.ndarray,
    labels: jnp.ndarray,
    label_smoothing: float = 0.0,
    weights: Optional[jnp.ndarray] = None,
) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The step's loss and, beside it, which positions the model's first
    choice got right: ``(loss, hits)``, ``hits`` float32 over the labels'
    leading dims and without gradient, so the accuracy costs no pass of
    its own over the logits.

    ``logits`` may carry any leading dims (``[B, C]`` classification,
    ``[B, T, C]`` token prediction) and come in the dtype the model
    emitted; the loss math is float32 (:func:`_sparse_softmax_ce`).
    Without ``weights`` the loss is the mean sparse softmax
    cross-entropy (reference TF ``:197-200``); one-hot (float,
    rank-of-logits) labels are accepted too — the reference Keras
    path's ``categorical_crossentropy`` with its one-hot
    ``FakeDataGenerator`` (``imagenet_keras_horovod.py:307``,
    ``data_generator.py:48-53``). With ``weights`` it is ``Σ w·CE / N``
    over the positions whose label is not −1, ``N`` counting every
    position, ignored or not: the per-token weighted objective (block
    diffusion: the masked positions, each by ``1/t``). An ignored
    position adds nothing, forward or backward; its hit means nothing."""
    num_classes = logits.shape[-1]
    # The `loss` scope names these operations, forward and backward, in
    # every engine's compiled step (obs/programs.py groups by it).
    with jax.named_scope("loss"):
        onehot = weights is None and labels.ndim == logits.ndim
        obs.counter(
            f"loss.impl.{'onehot' if onehot else 'xla'}",
            dtype=str(logits.dtype), shape=list(logits.shape),
        )
        if onehot:
            logits = logits.astype(jnp.float32)
            targets = labels.astype(jnp.float32)
            hits = jnp.argmax(logits, -1) == jnp.argmax(targets, -1)
            if label_smoothing > 0.0:
                on = 1.0 - label_smoothing
                off = label_smoothing / (num_classes - 1)
                targets = targets * (on - off) + off
            log_probs = jax.nn.log_softmax(logits)
            loss = -jnp.mean(jnp.sum(targets * log_probs, axis=-1))
            return loss, hits.astype(jnp.float32)
        flat_labels = labels.reshape(-1)
        per_example, hits = _sparse_softmax_ce(
            logits.reshape(-1, num_classes),
            jnp.maximum(flat_labels, 0),
            float(label_smoothing),
        )
        if weights is None:
            loss = jnp.mean(per_example)
        else:
            kept = jnp.where(flat_labels >= 0, per_example * weights.reshape(-1), 0.0)
            loss = jnp.sum(kept) / flat_labels.size
        return loss, hits.reshape(labels.shape)


def cross_entropy_loss(
    logits: jnp.ndarray, labels: jnp.ndarray, label_smoothing: float = 0.0
) -> jnp.ndarray:
    """Mean sparse softmax cross-entropy: :func:`loss_and_hits`' loss
    (the hits' reductions, unread, compile away)."""
    return loss_and_hits(logits, labels, label_smoothing)[0]


def weighted_cross_entropy_loss(
    logits: jnp.ndarray,
    targets: jnp.ndarray,
    weights: jnp.ndarray,
    label_smoothing: float = 0.0,
) -> jnp.ndarray:
    """``Σ w·CE / N`` over the positions whose target is not −1:
    :func:`loss_and_hits`' loss under per-token weights."""
    return loss_and_hits(logits, targets, label_smoothing, weights)[0]


def sown_stats(mutated: PyTree) -> Dict[str, jnp.ndarray]:
    """What the model's layers sowed into the ``"stats"`` collection
    (``models/decoder.STATS``: an expert layer's pair counts), each name's
    mean over the layers that sowed it. Empty for every other model."""
    by_name: Dict[str, list] = {}
    for path, leaf in jax.tree_util.tree_leaves_with_path(mutated.get("stats", {})):
        name = next(
            k.key for k in reversed(path) if isinstance(getattr(k, "key", None), str)
        )
        by_name.setdefault(name, []).append(jnp.asarray(leaf, jnp.float32))
    return {name: sum(xs) / len(xs) for name, xs in by_name.items()}


def sown_aux_loss(mutated: PyTree) -> jnp.ndarray:
    """Sum of everything the model sowed into the ``"losses"`` collection
    (e.g. the MoE load-balance loss, ``models/moe.py``). Zero for models
    that sow nothing — every engine adds this term unconditionally."""
    leaves = jax.tree_util.tree_leaves(mutated.get("losses", {}))
    total = jnp.zeros((), jnp.float32)
    for leaf in leaves:
        total = total + jnp.asarray(leaf, jnp.float32)
    return total


def l2_kernel_penalty(params: PyTree, weight_decay: float) -> jnp.ndarray:
    """L2 on conv/dense kernels only — parity with the Keras path's
    injected ``l2(5e-5)`` kernel regularizer (``imagenet_keras_horovod.py:
    97-116``); biases and BN scales are exempt, as there."""
    if weight_decay == 0.0:
        return jnp.zeros((), jnp.float32)
    leaves = [
        jnp.sum(jnp.square(v.astype(jnp.float32)))
        for path, v in jax.tree_util.tree_leaves_with_path(params)
        if path and getattr(path[-1], "key", None) == "kernel"
    ]
    return weight_decay * sum(leaves)


def init_kept(model, rng, x):
    """``model.init``'s variables that a train state keeps. A model that
    sows statistics of its forward pass (an expert layer's counts,
    ``models/decoder.STATS``) hands them back from ``init`` too, and
    computing them is the whole forward at the run's sequence length:
    the einsum attention over 16,384 positions is 14 GiB that no step
    ever needs. Left out of a jitted function's result, XLA drops the
    forward and the draw alone is left."""
    variables = model.init(rng, x, train=False)
    return {k: variables[k] for k in ("params", "batch_stats") if k in variables}


def create_train_state(
    model,
    config: TrainConfig,
    tx,
    rng: Optional[jax.Array] = None,
    input_shape: Optional[Tuple[int, ...]] = None,
    input_dtype=None,
) -> TrainState:
    """Deterministic seeded init — every process computes identical params,
    which *is* the broadcast (SURVEY.md §7: preferred over the reference's
    ``BroadcastGlobalVariablesHook``).

    ``input_shape``/``input_dtype`` default to the image contract
    (``None`` → float32 images); token models init with
    ``((1, seq_len), jnp.int32)``.
    """
    rng = rng if rng is not None else jax.random.PRNGKey(config.seed)
    shape = input_shape or (1, config.image_size, config.image_size, 3)
    variables = jax.jit(functools.partial(init_kept, model))(
        rng,
        jnp.zeros(shape, input_dtype if input_dtype is not None else jnp.float32),
    )
    # Unbox nn.with_logical_partitioning metadata: boxed leaves would hide
    # the `kernel` path component from l2_kernel_penalty. Both engines
    # unbox — the pjit engine reads the logical axes off an eval_shape
    # BEFORE unboxing (pjit_step.logical_shardings), never from the state.
    import flax.linen as nn

    return TrainState.create(
        params=nn.unbox(variables["params"]),
        batch_stats=variables.get("batch_stats", {}),
        tx=tx,
    )


def flat_axis_index(mesh: Mesh, axes) -> jnp.ndarray:
    """Row-major flat index of this shard across ``axes`` (shared by the
    DP and SP engines for per-device rng derivation)."""
    idx = jnp.zeros((), jnp.int32)
    for a in axes:
        idx = idx * mesh.shape[a] + lax.axis_index(a)
    return idx


def make_train_step(
    model,
    tx,
    mesh: Mesh,
    config: Optional[TrainConfig] = None,
    donate_state: bool = True,
    check_vma: Optional[bool] = None,
) -> Callable[[TrainState, Batch], Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Build the compiled DP train step over ``mesh``.

    Returns a :class:`~.metrics.StepFn`:
    ``step(state, (images, labels)) -> (state, metrics)`` — ``state``
    replicated, batch sharded on its leading axis over the mesh's batch
    axes, metrics already cross-replica means — and
    ``step(state, batch, acc) -> (state, metrics, new_acc)``, the
    accumulating variant the training loop runs (metric sums build up
    on device; ``acc`` is donated).

    ``config.accum_steps > 1`` compiles the microbatched step instead:
    a ``lax.scan`` over k per-shard microbatches with an on-device f32
    gradient accumulator, one optimizer update per dispatch — activation
    memory ∝ microbatch, same dispatch/sync contract (``training/
    accum.py``). BatchNorm statistics become ghost-batch (per-microbatch,
    folded sequentially into the running stats).

    ``check_vma=None`` auto-resolves: on except for interpreter-mode
    Pallas attention (``ops/attention.kernel_interpreted``).
    """
    from distributeddeeplearning_tpu.training import accum

    cfg = config or TrainConfig()
    accum_steps = accum.resolve_accum_steps(cfg)
    if check_vma is None:
        check_vma = not kernel_interpreted(getattr(model, "attn_impl", None))
    axes = batch_axes(mesh)
    if not axes:
        raise ValueError(f"mesh {mesh.axis_names} has no batch axis")
    axis = axes if len(axes) > 1 else axes[0]
    base_rng = jax.random.PRNGKey(cfg.seed)

    def _pmean_batch(tree):
        # Hybrid DCN×ICI mesh (axes "replica","data"): stage the reduction
        # in-slice first so only slice-reduced tensors cross DCN
        # (collectives.hierarchical_allreduce_gradients). Single-axis
        # meshes keep the flat pmean.
        if isinstance(axis, tuple) and axis[0] == "replica":
            from distributeddeeplearning_tpu.parallel.collectives import (
                hierarchical_allreduce_gradients,
            )

            inner = axis[1:]
            return hierarchical_allreduce_gradients(
                tree, ici_axis=inner if len(inner) > 1 else inner[0]
            )
        return lax.pmean(tree, axis)

    def _device_index():
        return flat_axis_index(mesh, axes)

    def local_step(state: TrainState, batch: Batch):
        # (inputs, labels), or with per-token weights beside them
        # (inputs, targets with −1 = ignore, weights): the weighted
        # objective of `weighted_cross_entropy_loss`
        images, labels, *rest = batch
        weights = rest[0] if rest else None
        # uint8 staging: normalization folds into the first device pass
        from distributeddeeplearning_tpu.data.pipeline import (
            normalize_staged_images,
        )

        images = normalize_staged_images(images)
        # Per-step, per-device dropout key: stochastic models (EfficientNet
        # drop-path/dropout, ViT with dropout>0) draw independent noise on
        # every device and every step, like the reference's per-worker
        # torch/keras RNG streams.
        dropout_rng = jax.random.fold_in(
            jax.random.fold_in(base_rng, state.step), _device_index()
        )
        # Cast replicated params to device-varying before differentiating.
        # Without this, shard_map's vma transpose rule auto-inserts a psum
        # into the backward pass (grad w.r.t. an unvarying input sums over
        # the axis), and the pmean below would silently no-op on an
        # already-invariant value — an 8x gradient at 8 devices. With the
        # cast, grads stay per-device and the pmean below IS the allreduce.
        params_v = jax.tree.map(
            lambda p: lax.pcast(p, axis, to="varying"), state.params
        )

        def loss_fn(params):
            logits, mutated = model.apply(
                {"params": params, "batch_stats": state.batch_stats},
                images,
                train=True,
                mutable=["batch_stats", "losses", "stats"],
                rngs={"dropout": dropout_rng},
            )
            loss, hit = loss_and_hits(
                logits, labels, cfg.label_smoothing, weights
            )
            loss = loss + l2_kernel_penalty(params, cfg.weight_decay)
            loss = loss + sown_aux_loss(mutated)
            return loss, (
                hit, mutated.get("batch_stats", {}), sown_stats(mutated)
            )

        (loss, (hit, new_bs, stats)), grads = jax.value_and_grad(
            loss_fn, has_aux=True
        )(params_v)
        # THE collective: Horovod's per-tensor ring allreduce becomes one
        # in-step pmean that XLA schedules onto ICI (staged ICI→DCN on
        # hybrid multi-slice meshes).
        with overlap_scope(cfg.async_collectives):
            grads = _pmean_batch(grads)
        new_bs = _pmean_batch(new_bs)  # keep replicated state invariant

        with jax.named_scope("optimizer"):
            updates, new_opt_state = tx.update(
                grads, state.opt_state, state.params
            )
            new_params = jax.tree.map(lambda p, u: p + u, state.params, updates)

        with jax.named_scope("metrics"):
            extra = dict(stats)
            if weights is None:
                accuracy = jnp.mean(hit)
            else:  # over the positions the loss is over
                kept = (labels >= 0).astype(jnp.float32)
                accuracy = jnp.sum(hit * kept) / jnp.maximum(jnp.sum(kept), 1.0)
                extra["loss.masked_share"] = jnp.mean(kept)
            metrics = _pmean_batch({
                "loss": loss, "accuracy": accuracy,
                "grad_norm": optax.global_norm(grads), **extra,
            })
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_bs,
            opt_state=new_opt_state,
        )
        return new_state, metrics

    def local_step_microbatched(state: TrainState, batch: Batch):
        """ACCUM_STEPS>1: the same step math, scanned over k per-shard
        microbatches — grads accumulate in f32 on device, the optimizer
        applies their mean once, BN running stats fold per microbatch
        (ghost batch norm). Collectives (grad/stat pmean) run ONCE on
        the accumulated means, exactly where the plain step runs them."""
        images, labels = batch
        dp = 1
        for a in axes:
            dp *= mesh.shape[a]
        accum.check_local_divisible(
            images.shape[0], accum_steps, dp=dp, engine="dp"
        )
        xs = accum.split_microbatches((images, labels), accum_steps)
        # Per-step, per-device base key as in the plain step; each
        # microbatch folds its index in for independent dropout noise.
        step_rng = jax.random.fold_in(
            jax.random.fold_in(base_rng, state.step), _device_index()
        )
        params_v = jax.tree.map(
            lambda p: lax.pcast(p, axis, to="varying"), state.params
        )

        def micro(bs, mb, idx):
            mb_images, mb_labels = mb
            from distributeddeeplearning_tpu.data.pipeline import (
                normalize_staged_images,
            )

            def loss_fn(params):
                logits, mutated = model.apply(
                    {"params": params, "batch_stats": bs},
                    # normalize INSIDE the scan body: the staged (possibly
                    # uint8) batch is the only full-batch buffer alive;
                    # the normalized copy exists per microbatch.
                    normalize_staged_images(mb_images),
                    train=True,
                    mutable=["batch_stats", "losses"],
                    rngs={"dropout": jax.random.fold_in(step_rng, idx)},
                )
                loss, hit = loss_and_hits(
                    logits, mb_labels, cfg.label_smoothing
                )
                loss = loss + l2_kernel_penalty(params, cfg.weight_decay)
                loss = loss + sown_aux_loss(mutated)
                return loss, (hit, mutated.get("batch_stats", bs))

            (loss, (hit, new_bs)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(params_v)
            with jax.named_scope("metrics"):
                accuracy = jnp.mean(hit)
            return grads, {"loss": loss, "accuracy": accuracy}, new_bs

        def vary(tree):
            return jax.tree.map(
                lambda x: lax.pcast(x, axis, to="varying"), tree
            )

        grads, micro_metrics, new_bs = accum.accumulate_microbatches(
            micro,
            xs,
            accum_steps,
            params_v,
            extra0=state.batch_stats,
            vary=vary,
        )
        with overlap_scope(cfg.async_collectives):
            grads = _pmean_batch(grads)
        new_bs = _pmean_batch(new_bs)  # keep replicated state invariant

        with jax.named_scope("optimizer"):
            updates, new_opt_state = tx.update(
                grads, state.opt_state, state.params
            )
            new_params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        with jax.named_scope("metrics"):
            metrics = _pmean_batch(
                {
                    "loss": micro_metrics["loss"],
                    "accuracy": micro_metrics["accuracy"],
                    "grad_norm": optax.global_norm(grads),
                }
            )
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_bs,
            opt_state=new_opt_state,
        )
        return new_state, metrics

    if accum_steps > 1:
        local_step = local_step_microbatched

    from distributeddeeplearning_tpu.training.metrics import (
        StepFn,
        accumulate_metrics,
    )

    def local_step_acc(state: TrainState, batch: Batch, acc):
        new_state, metrics = local_step(state, batch)
        return new_state, metrics, accumulate_metrics(acc, metrics)

    batch_spec = P(axis if isinstance(axis, str) else tuple(axes))
    sharded = jax.shard_map(
        local_step,
        mesh=mesh,
        in_specs=(P(), batch_spec),  # every element of the batch tuple
        out_specs=(P(), P()),
        check_vma=check_vma,
    )
    # Accumulating variant (loop.fit's hot path): the donated replicated
    # accumulator rides the same compiled program — epoch statistics
    # build up on device, no mid-epoch host sync. Lazily compiled: only
    # the arity a caller actually uses pays its compile.
    sharded_acc = jax.shard_map(
        local_step_acc,
        mesh=mesh,
        in_specs=(P(), batch_spec, P()),
        out_specs=(P(), P(), P()),
        check_vma=check_vma,
    )
    jit2 = jax.jit(sharded, donate_argnums=(0,) if donate_state else ())
    jit3 = jax.jit(
        sharded_acc, donate_argnums=(0, 2) if donate_state else (2,)
    )
    step = StepFn(lambda state, with_acc: jit3 if with_acc else jit2)
    step.accum_steps = accum_steps
    return step


def eval_metrics_fn(
    logits: jnp.ndarray, labels: jnp.ndarray, weights: jnp.ndarray
) -> Dict[str, jnp.ndarray]:
    """Per-shard weighted metric sums (shared by the DP and pjit engines).

    ``weights`` ∈ {0, 1} marks real vs padded samples, so a final partial
    validation batch can be padded to the static shape and masked out —
    every sample counts exactly once, unlike the reference's
    floor+modulo-wrap eval (and its ``validate()`` which simply drops the
    tail).

    Token models (``[B, T, V]`` logits): flattened to per-token metrics,
    with each sample's weight applied to all its tokens. One-hot labels
    (the categorical_crossentropy mode) are reduced to hard labels for
    top-k and used directly for the CE term.
    """
    one_hot = labels.ndim == logits.ndim
    logits = logits.astype(jnp.float32)  # metric math in f32 regardless
    if logits.ndim == 3:
        b, t, v = logits.shape
        logits = logits.reshape(b * t, v)
        labels = labels.reshape((b * t, v) if one_hot else (b * t,))
        weights = jnp.repeat(weights, t)
    w = weights.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits)
    if one_hot:
        per_ex = -jnp.sum(labels.astype(jnp.float32) * logp, axis=-1)
        labels = jnp.argmax(labels, axis=-1)
    else:
        per_ex = -jnp.take_along_axis(logp, labels[:, None], axis=-1)[:, 0]
    top1 = (jnp.argmax(logits, -1) == labels).astype(jnp.float32)
    top5 = jnp.any(
        jnp.argsort(logits, axis=-1)[:, -5:] == labels[:, None], axis=-1
    ).astype(jnp.float32)
    return {
        "loss": jnp.sum(per_ex * w),
        "top1": jnp.sum(top1 * w),
        "top5": jnp.sum(top5 * w),
        "count": jnp.sum(w),
    }


def make_eval_step(
    model, mesh: Mesh, check_vma: Optional[bool] = None
) -> Callable[[TrainState, Batch], Dict[str, jnp.ndarray]]:
    """Compiled eval step: running-stats BN, cross-replica-summed weighted
    metrics (reference eval: TF ``:203-213``, Keras ``hvd.allreduce(score)``
    ``:344-353``).

    Accepts ``(images, labels)`` or ``(images, labels, weights)``; returns
    per-batch means ``{loss, top1, top5}`` plus ``count``, the number of
    *real* (weight-1) samples — exact-coverage eval divides accumulated
    ``metric·count`` sums by accumulated counts (``loop._run_eval``).
    """
    axes = batch_axes(mesh)
    if not axes:
        raise ValueError(f"mesh {mesh.axis_names} has no batch axis")
    axis = axes if len(axes) > 1 else axes[0]
    if check_vma is None:
        check_vma = not kernel_interpreted(getattr(model, "attn_impl", None))

    def local_eval(state: TrainState, batch):
        images, labels, weights = batch
        from distributeddeeplearning_tpu.data.pipeline import (
            normalize_staged_images,
        )

        images = normalize_staged_images(images)
        logits = model.apply(
            {"params": state.params, "batch_stats": state.batch_stats},
            images,
            train=False,
        )
        sums = lax.psum(eval_metrics_fn(logits, labels, weights), axis)
        count = sums.pop("count")
        safe = jnp.maximum(count, 1.0)  # all-padding batch
        out = {k: v / safe for k, v in sums.items()}
        out["count"] = count
        return out

    from distributeddeeplearning_tpu.training.metrics import StepFn

    batch_spec = P(axis if isinstance(axis, str) else tuple(axes))
    sharded = jax.jit(
        jax.shard_map(
            local_eval,
            mesh=mesh,
            in_specs=(P(), (batch_spec, batch_spec, batch_spec)),
            out_specs=P(),
            check_vma=check_vma,
        )
    )
    inner = StepFn(lambda state, with_acc: sharded)

    def _normalize(batch):
        if len(batch) == 2:
            # Convenience (single-host tests): all samples real.
            if jax.process_count() > 1:
                raise ValueError(
                    "multi-host eval requires (images, labels, weights) "
                    "batches — use an exact-eval dataset (train=False)"
                )
            images, labels = batch
            weights = jnp.ones(labels.shape[:1], jnp.float32)
            batch = (images, labels, weights)
        return batch

    def step(state: TrainState, batch):
        return inner(state, _normalize(batch))

    step.aot_compile = lambda state, batch: inner.aot_compile(
        state, _normalize(batch)
    )
    return step


def replicate_state(state: TrainState, mesh: Mesh) -> TrainState:
    """Place a host-side state replicated across the mesh.

    Multi-process: every process already computed the identical value
    (deterministic seeded init ≙ the broadcast; checkpoint restore
    places identical shards), so the state is materialised to host numpy
    and assembled with ``host_local_array_to_global_array`` — each
    process uploads its local copy, no cross-process traffic at all.
    The naive ``device_put(state, non_addressable_sharding)`` instead
    runs a per-leaf ``multihost_utils.assert_equal`` — a full-data
    broadcast per leaf — whose gloo ops interleave and collide on the
    CPU backend (``op.preamble.length <= op.nbytes`` aborts that killed
    every 2-process world at engine build). One boundary-time host trip,
    before training starts — the hot loop's sync accounting is untouched.
    """
    if jax.process_count() > 1:
        from jax.experimental import multihost_utils

        host_state = jax.tree.map(
            lambda x: np.asarray(x) if hasattr(x, "addressable_data") else x,
            state,
        )
        return multihost_utils.host_local_array_to_global_array(
            host_state, mesh, P()
        )
    return jax.device_put(state, replicated_sharding(mesh))
