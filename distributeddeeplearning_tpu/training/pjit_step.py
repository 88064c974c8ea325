"""GSPMD (pjit) train-step engine — tensor/sequence-parallel path.

The shard_map engine (``training/train_step.py``) is the reference-parity
data-parallel runtime. This engine is the scale-up path the reference
never had (its README names model parallelism as future work,
``/root/reference/README.md:21``): models annotate weights with *logical*
axes (``nn.with_logical_partitioning`` — see ``models/vit.py``), a rules
table maps logical axes onto mesh axes (``models/sharding.py``),
and XLA's SPMD partitioner inserts the collectives implied by the
shardings — Megatron-style column/row-parallel matmuls become
all-reduce / reduce-scatter pairs on ICI without any hand-written
communication.

How sharding flows:
  1. ``logical_shardings`` eval_shapes ``model.init``, reads the logical
     axis names off the boxed params, and maps them to ``NamedSharding``s
     via ``nn.logical_to_mesh_sharding(rules)``.
  2. ``create_sharded_train_state`` jit-initialises with a
     ``with_sharding_constraint`` on params; the optimizer state is
     created *from the constrained params* inside the same jit, so XLA
     propagates the shardings into momentum/etc. — sharded params never
     exist replicated, even transiently (critical for models that don't
     fit one chip).
  3. ``make_pjit_train_step`` is a plain ``jax.jit``: committed input
     shardings (state from step 2, batch from ``shard_batch``) drive the
     partitioner; gradients of a batch-sharded loss w.r.t.
     replicated-or-sharded params come out correctly reduced — the
     explicit ``pmean`` of the shard_map engine is implicit here.

Same loss/metric semantics as the DP engine, including BatchNorm: the
train step splits the global batch into one group per data shard
(``models/norm.py`` ``per_replica_bn``) so BN statistics match the
shard_map engine's (and the reference's) per-replica semantics exactly;
``ALLOW_SYNC_BN=1`` opts into global-batch (sync) statistics instead.
"""

from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp
import optax
from jax import lax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddeeplearning_tpu.parallel.mesh import (
    batch_sharding as _mesh_batch_sharding,
)
from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.training import overlap
from distributeddeeplearning_tpu.training.state import TrainState
from distributeddeeplearning_tpu.training.train_step import (
    Batch,
    cross_entropy_loss,
    l2_kernel_penalty,
    sown_aux_loss,
)

PyTree = Any

# Default rules: every logical axis replicated except batch — pure DP,
# any model, no annotations required.
DEFAULT_RULES: Tuple[Tuple[str, Any], ...] = (("batch", ("replica", "data")),)


def logical_shardings(
    model,
    mesh: Mesh,
    rules: Sequence[Tuple[str, Any]],
    input_shape: Tuple[int, ...],
    rng: Optional[jax.Array] = None,
    input_dtype=None,
) -> Tuple[PyTree, PyTree]:
    """(abstract_variables, NamedSharding tree for ``params``).

    Reads ``nn.with_logical_partitioning`` annotations off an abstract
    init; unannotated params (ResNet et al.) come back fully replicated.
    """
    from distributeddeeplearning_tpu.models.sharding import rules_for_mesh

    rng = rng if rng is not None else jax.random.PRNGKey(0)
    # Project the rules onto THIS mesh: a rule targeting an absent mesh
    # axis (e.g. "expert" on a plain data mesh) degrades to replicated
    # instead of erroring — one table serves every topology.
    rules = rules_for_mesh(mesh, tuple(rules))
    # input_dtype=None -> float32 (jnp.zeros' own default)
    abstract = jax.eval_shape(
        functools.partial(model.init, train=False),
        rng,
        jnp.zeros(input_shape, input_dtype),
    )
    logical_spec = nn.get_partition_spec(abstract)
    try:
        shardings = nn.logical_to_mesh_sharding(logical_spec, mesh, list(rules))
    except ValueError as e:
        raise ValueError(
            f"model's logical axes don't fit mesh axes {mesh.axis_names}: "
            f"{e}. The pjit engine with an annotated model needs a 'model' "
            "axis — create_mesh(axes=('data', 'model'), shape=(d, m)) or "
            "set MESH_AXES=data,model MESH_SHAPE=d,m"
        ) from e
    return abstract, shardings["params"]


def create_sharded_train_state(
    model,
    config: TrainConfig,
    tx,
    mesh: Mesh,
    rules: Sequence[Tuple[str, Any]] = DEFAULT_RULES,
    *,
    input_shape: Optional[Tuple[int, ...]] = None,
    rng: Optional[jax.Array] = None,
    input_dtype=None,
    param_shardings: Optional[PyTree] = None,
) -> TrainState:
    """Seeded init, sharded at birth (no replicated intermediate).
    ``input_shape``/``input_dtype``: token models pass ((1, T), int32);
    ``None`` dtype means float32 images. ``param_shardings``: pass the
    tree from an earlier :func:`logical_shardings` call to skip the
    abstract re-trace (``build_pjit_state`` does)."""
    rng = rng if rng is not None else jax.random.PRNGKey(config.seed)
    shape = input_shape or (1, config.image_size, config.image_size, 3)
    if param_shardings is None:
        _, param_shardings = logical_shardings(
            model, mesh, rules, shape, rng, input_dtype=input_dtype
        )

    from distributeddeeplearning_tpu.models.sharding import rules_for_mesh

    active_rules = list(rules_for_mesh(mesh, tuple(rules)))

    def init_fn(r):
        with nn.logical_axis_rules(active_rules):
            variables = model.init(r, jnp.zeros(shape, input_dtype), train=False)
        params = lax.with_sharding_constraint(
            nn.unbox(variables["params"]), param_shardings
        )
        state = TrainState.create(
            params=params,
            batch_stats=variables.get("batch_stats", {}),
            tx=tx,
        )
        # XLA does NOT propagate the params constraint into tx.init's
        # zeros_like leaves — momentum etc. would come out replicated and
        # blow memory at TP scale. Constrain every params-shaped subtree
        # of the optimizer state to the params shardings.
        return state.replace(
            opt_state=_constrain_params_like(
                state.opt_state, params, param_shardings
            )
        )

    with mesh:
        return jax.jit(init_fn)(rng)


def _constrain_params_like(opt_state, params, param_shardings):
    """Apply ``param_shardings`` to every subtree of ``opt_state`` whose
    pytree structure equals the params structure (optax momentum / EMA /
    Adam moments all mirror it)."""
    params_def = jax.tree_util.tree_structure(params)

    def is_params_like(node):
        try:
            return jax.tree_util.tree_structure(node) == params_def
        except Exception:
            return False

    return jax.tree_util.tree_map(
        lambda sub: jax.tree.map(lax.with_sharding_constraint, sub, param_shardings)
        if is_params_like(sub)
        else sub,
        opt_state,
        is_leaf=is_params_like,
    )


def make_pjit_train_step(
    model,
    tx,
    mesh: Mesh,
    config: Optional[TrainConfig] = None,
    *,
    donate_state: bool = True,
) -> Callable[[TrainState, Batch], Tuple[TrainState, Dict[str, jnp.ndarray]]]:
    """Compiled GSPMD train step. Shardings ride in on the arguments
    (committed state + batch), so the same function serves DP, TP and
    DP×TP meshes.

    ``config.accum_steps > 1`` compiles the microbatched variant: the
    global batch is re-sliced into k *device-interleaved* microbatches
    (each microbatch takes every data shard's j-th local slice — purely
    local data movement, and the same rows per shard the dp engine's
    split produces), scanned with an on-device f32 gradient accumulator
    (``training/accum.py``)."""
    from distributeddeeplearning_tpu.models.sharding import (
        rules_for_mesh,
        rules_table,
    )

    from distributeddeeplearning_tpu.models.norm import per_replica_bn
    from distributeddeeplearning_tpu.parallel.mesh import (
        batch_axes as _mesh_batch_axes,
        dp_size,
    )
    from distributeddeeplearning_tpu.training import accum

    cfg = config or TrainConfig()
    accum_steps = accum.resolve_accum_steps(cfg)
    base_rng = jax.random.PRNGKey(cfg.seed)
    batch_sharding = _mesh_batch_sharding(mesh)
    rules = list(rules_for_mesh(mesh, rules_table(cfg.param_sharding)))
    # Per-replica BN (SURVEY §7 hard part (b)): split the global batch
    # into one group per data shard so BatchNorm statistics match the dp
    # engine's per-replica semantics. ALLOW_SYNC_BN=1 keeps global-batch
    # (sync) statistics instead.
    bn_groups = 1 if cfg.allow_sync_bn else dp_size(mesh)

    def step(state: TrainState, batch: Batch):
        from distributeddeeplearning_tpu.data.pipeline import (
            normalize_staged_images,
        )

        images, labels = batch
        # Bind the step to ITS mesh: a batch committed to a different
        # mesh/layout errors here instead of silently resharding.
        images = lax.with_sharding_constraint(images, batch_sharding)
        labels = lax.with_sharding_constraint(labels, batch_sharding)
        images = normalize_staged_images(images)  # uint8 staging
        dropout_rng = jax.random.fold_in(base_rng, state.step)

        def loss_fn(params):
            # The rules context makes in-model nn.with_logical_constraint
            # calls real (MoE's expert-major activation layout — the
            # all-to-all boundary); without it they are silent no-ops.
            with mesh, nn.logical_axis_rules(rules), per_replica_bn(bn_groups):
                logits, mutated = model.apply(
                    {"params": params, "batch_stats": state.batch_stats},
                    images,
                    train=True,
                    mutable=["batch_stats", "losses"],
                    rngs={"dropout": dropout_rng},
                )
            loss = cross_entropy_loss(logits, labels, cfg.label_smoothing)
            loss = loss + l2_kernel_penalty(params, cfg.weight_decay)
            loss = loss + sown_aux_loss(mutated)
            return loss, (logits, mutated.get("batch_stats", {}))

        # Under GSPMD the gradient all-reduce is implicit in the
        # backward pass; the overlap tag lands on those reductions so
        # the TPU async-collective flags can split them into start/done
        # pairs and hlo_audit can prove the tag (training/overlap.py).
        with overlap.overlap_scope(cfg.async_collectives):
            (loss, (logits, new_bs)), grads = jax.value_and_grad(
                loss_fn, has_aux=True
            )(state.params)
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        hard = jnp.argmax(labels, -1) if labels.ndim == logits.ndim else labels
        accuracy = jnp.mean((jnp.argmax(logits, -1) == hard).astype(jnp.float32))
        metrics = {
            "loss": loss,
            "accuracy": accuracy,
            "grad_norm": optax.global_norm(grads),
        }
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_bs,
            opt_state=new_opt_state,
        )
        return new_state, metrics

    def step_microbatched(state: TrainState, batch: Batch):
        """ACCUM_STEPS>1 (global-view): scan over device-interleaved
        microbatches; grads/metrics mean-weighted, optimizer once."""
        from distributeddeeplearning_tpu.data.pipeline import (
            normalize_staged_images,
        )

        images, labels = batch
        images = lax.with_sharding_constraint(images, batch_sharding)
        labels = lax.with_sharding_constraint(labels, batch_sharding)
        d = dp_size(mesh)
        bt = _mesh_batch_axes(mesh)
        lead = (bt if len(bt) > 1 else bt[0]) if bt else None
        accum.check_local_divisible(
            images.shape[0] // max(d, 1), accum_steps, dp=d, engine="pjit"
        )

        def interleave(x):
            # [B, ...] -> [k, B/k, ...] where microbatch j concatenates
            # every data shard's j-th local slice: reshape/transpose are
            # local under the pinned shardings (no cross-shard traffic),
            # and each microbatch stays sharded over all data shards.
            b = x.shape[0]
            x = x.reshape(d, accum_steps, b // (d * accum_steps), *x.shape[1:])
            x = lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(lead))
            )
            x = jnp.swapaxes(x, 0, 1)
            x = lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(None, lead))
            )
            x = x.reshape(accum_steps, b // accum_steps, *x.shape[3:])
            return lax.with_sharding_constraint(
                x, NamedSharding(mesh, P(None, lead))
            )

        xs = (interleave(images), interleave(labels))
        step_rng = jax.random.fold_in(base_rng, state.step)

        def micro(bs, mb, idx):
            mb_images, mb_labels = mb

            def loss_fn(params):
                with mesh, nn.logical_axis_rules(rules), \
                        per_replica_bn(bn_groups):
                    logits, mutated = model.apply(
                        {"params": params, "batch_stats": bs},
                        normalize_staged_images(mb_images),
                        train=True,
                        mutable=["batch_stats", "losses"],
                        rngs={
                            "dropout": jax.random.fold_in(step_rng, idx)
                        },
                    )
                loss = cross_entropy_loss(
                    logits, mb_labels, cfg.label_smoothing
                )
                loss = loss + l2_kernel_penalty(params, cfg.weight_decay)
                loss = loss + sown_aux_loss(mutated)
                return loss, (logits, mutated.get("batch_stats", bs))

            # Accum microbatch backward: same overlap tag (see above).
            with overlap.overlap_scope(cfg.async_collectives):
                (loss, (logits, new_bs)), grads = jax.value_and_grad(
                    loss_fn, has_aux=True
                )(state.params)
            hard = (
                jnp.argmax(mb_labels, -1)
                if mb_labels.ndim == logits.ndim
                else mb_labels
            )
            accuracy = jnp.mean(
                (jnp.argmax(logits, -1) == hard).astype(jnp.float32)
            )
            return grads, {"loss": loss, "accuracy": accuracy}, new_bs

        grads, micro_metrics, new_bs = accum.accumulate_microbatches(
            micro, xs, accum_steps, state.params, extra0=state.batch_stats
        )
        updates, new_opt_state = tx.update(grads, state.opt_state, state.params)
        new_params = jax.tree.map(lambda p, u: p + u, state.params, updates)
        metrics = {
            "loss": micro_metrics["loss"],
            "accuracy": micro_metrics["accuracy"],
            "grad_norm": optax.global_norm(grads),
        }
        new_state = state.replace(
            step=state.step + 1,
            params=new_params,
            batch_stats=new_bs,
            opt_state=new_opt_state,
        )
        return new_state, metrics

    if accum_steps > 1:
        step = step_microbatched

    from distributeddeeplearning_tpu.training.metrics import (
        StepFn,
        accumulate_metrics,
    )

    def step_acc(state: TrainState, batch: Batch, acc):
        new_state, metrics = step(state, batch)
        return new_state, metrics, accumulate_metrics(acc, metrics)

    # Accumulating variant (see train_step.make_train_step): under GSPMD
    # the scalar accumulator is replicated by construction; both it and
    # the state are donated.
    jit2 = jax.jit(step, donate_argnums=(0,) if donate_state else ())
    jit3 = jax.jit(step_acc, donate_argnums=(0, 2) if donate_state else (2,))
    wrapped = StepFn(lambda state, with_acc: jit3 if with_acc else jit2)
    wrapped.accum_steps = accum_steps
    return wrapped


def make_pjit_eval_step(
    model, mesh: Mesh, config: Optional[TrainConfig] = None
) -> Callable[[TrainState, Batch], Dict[str, jnp.ndarray]]:
    """Same eval contract as the DP engine (``train_step.make_eval_step``):
    accepts ``(images, labels[, weights])``, returns weighted batch means
    plus the real-sample ``count`` — with GSPMD the weighted sums are
    plain global reductions, no explicit psum needed.

    ``config`` selects the same ``param_sharding`` rules table the train
    step uses, so eval activations are constrained under the identical
    layout (TP vs FSDP vs DP must not diverge between the two)."""
    from distributeddeeplearning_tpu.models.sharding import (
        rules_for_mesh,
        rules_table,
    )
    from distributeddeeplearning_tpu.training.train_step import eval_metrics_fn

    cfg = config or TrainConfig()
    batch_sharding = _mesh_batch_sharding(mesh)
    rules = list(rules_for_mesh(mesh, rules_table(cfg.param_sharding)))

    def eval_step(state: TrainState, batch):
        from distributeddeeplearning_tpu.data.pipeline import (
            normalize_staged_images,
        )

        images, labels, weights = batch
        images = lax.with_sharding_constraint(images, batch_sharding)
        labels = lax.with_sharding_constraint(labels, batch_sharding)
        weights = lax.with_sharding_constraint(weights, batch_sharding)
        images = normalize_staged_images(images)  # uint8 staging
        with mesh, nn.logical_axis_rules(rules):
            logits = model.apply(
                {"params": state.params, "batch_stats": state.batch_stats},
                images,
                train=False,
            )
        sums = eval_metrics_fn(logits, labels, weights)
        count = sums.pop("count")
        safe = jnp.maximum(count, 1.0)
        out = {k: v / safe for k, v in sums.items()}
        out["count"] = count
        return out

    from distributeddeeplearning_tpu.training.metrics import StepFn

    jitted = jax.jit(eval_step)
    inner = StepFn(lambda state, with_acc: jitted)

    def _normalize(batch):
        if len(batch) == 2:
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


def build_pjit_state(
    model,
    config: TrainConfig,
    tx,
    mesh: Mesh,
    *,
    input_shape: Optional[Tuple[int, ...]] = None,
    input_dtype=None,
) -> TrainState:
    """One construction point for engine='pjit' state (used by loop.fit,
    the explicit front-end, and Keras load_weights): sharded-at-birth
    init under the rules table ``config.param_sharding`` names ("tp" —
    the model-neutral default; "fsdp" — ZeRO-3 over the data axis;
    "dp" — replicated).

    BN semantics (SURVEY §7 hard part (b)): the train step runs
    batch_stats models with batch-split per-replica statistics
    (``models/norm.py``) — dp-identical semantics, oracle-tested against
    the dp engine — unless ``config.allow_sync_bn`` (env
    ``ALLOW_SYNC_BN=1``) opts into GLOBAL-batch (sync) statistics.
    """
    from distributeddeeplearning_tpu.models.sharding import rules_table

    rules = rules_table(config.param_sharding)
    shape = input_shape or (1, config.image_size, config.image_size, 3)
    # ONE abstract trace serves both the BN guard and the shardings.
    abstract, param_shardings = logical_shardings(
        model, mesh, rules, shape, input_dtype=input_dtype
    )
    if not config.allow_sync_bn and jax.tree.leaves(
        abstract.get("batch_stats", {})
    ):
        # Only models whose norm layers are the group-capable subclass
        # (models/norm.py) get per-replica semantics from the train
        # step's per_replica_bn context; plain nn.BatchNorm would
        # silently train sync-BN, so anything not declaring capability
        # is still refused (the round-2 guard, now narrowed).
        if not getattr(model, "per_replica_bn_capable", False):
            raise ValueError(
                f"model {type(model).__name__!r} carries batch_stats but "
                "does not declare per_replica_bn_capable: under "
                "ENGINE=pjit its statistics would be GLOBAL-batch "
                "(sync-BN), not the per-replica statistics the dp engine "
                "(and the reference) uses. Build its norm layers with "
                "models.norm.BatchNorm and set per_replica_bn_capable = "
                "True, use ENGINE=dp, or set ALLOW_SYNC_BN=1 to accept "
                "sync-BN."
            )

    return create_sharded_train_state(
        model,
        config,
        tx,
        mesh,
        rules,
        input_shape=input_shape,
        input_dtype=input_dtype,
        param_shardings=param_shardings,
    )
