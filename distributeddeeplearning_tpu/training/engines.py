"""Engine construction — ONE dispatch point for dp / pjit / pp / sp.

The framework's defining contract (SURVEY.md §1, §7) is "3 API styles
over one runtime, selected by env vars": the same script runs data-
parallel, GSPMD tensor-parallel, pipeline-parallel, or sequence-parallel
purely via ``ENGINE``/``MESH_*``. This module is where that contract is
honoured: every front-end (``loop.fit``, ``frontends/explicit.setup``,
and through ``fit`` the keras/estimator skins) builds its state and
compiled steps here, so a strategy can never be "library-only".

Engine → what changes:

============ ==================== ========================== ==============
engine       state                steps                      batch sharding
============ ==================== ========================== ==============
``dp``       replicated           ``train_step.make_*``      ``P(data)``
``pjit``     sharded at birth     ``pjit_step.make_pjit_*``  ``P(data)``
``pp``       stages over ``pipe`` ``pp_step.make_pp_*``      ``P(data)``
``sp``       replicated           ``sp_step.make_sp_*``      ``P(data,seq)``
============ ==================== ========================== ==============

``pp`` and ``sp`` adapt the model the front-end built: a dense
``TransformerLM`` is stage-partitioned into a ``PipelineLM`` (pp) or
cloned with ``attn_impl="ring", seq_axis="seq"`` (sp) — the user asks
for a model and a strategy, not a strategy-specific model class.
"""

from __future__ import annotations

import dataclasses
from typing import Any, Callable, Optional, Tuple

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.training.state import TrainState

SEQ_AXIS = "seq"
PIPE_AXIS = "pipe"

ENGINES = ("dp", "pjit", "pp", "sp")


@dataclasses.dataclass
class Engine:
    """The compiled artifacts one engine choice implies."""

    name: str
    mesh: Mesh
    model: Any  # engine-adapted model (ring clone / PipelineLM / as given)
    state: TrainState
    train_step: Callable
    eval_step: Callable
    # Per-batch sharding resolver for host→device staging, or None for
    # the default ``batch_sharding(mesh)`` (leading-axis over data).
    batch_sharding: Optional[Callable] = None

    def warmup(self, batch, *, acc=None, eval_batch=None):
        """AOT-compile the steps against ``batch``'s signature before
        any data flows: logs compile seconds + XLA cost-analysis FLOPs,
        installs the executables so the first real step doesn't compile
        again, and (with a persistent compilation cache enabled) reports
        the cache hit/miss delta. See ``training/warmup.py``."""
        from distributeddeeplearning_tpu.training.warmup import warmup_engine

        return warmup_engine(self, batch, acc=acc, eval_batch=eval_batch)


def _seq_len_from(input_shape, model) -> Optional[int]:
    if input_shape is not None and len(input_shape) == 2:
        return int(input_shape[1])
    return getattr(model, "max_seq_len", None)


def adapt_model(model, engine: str, mesh: Mesh, config: TrainConfig):
    """Return the model the engine actually runs (see module docstring)."""
    if engine == "sp":
        if (
            getattr(model, "attn_impl", None) == "ring"
            and getattr(model, "seq_axis", None) == SEQ_AXIS
        ):
            return model
        if not hasattr(model, "attn_impl") or not hasattr(model, "seq_axis"):
            raise ValueError(
                f"ENGINE=sp needs a sequence model with attn_impl/seq_axis "
                f"fields (the LM family); got {type(model).__name__}"
            )
        return model.clone(attn_impl="ring", seq_axis=SEQ_AXIS)
    if engine == "pp":
        from distributeddeeplearning_tpu.models.pipeline_lm import PipelineLM
        from distributeddeeplearning_tpu.models.transformer_lm import (
            _VARIANTS,
            TransformerLM,
        )

        if isinstance(model, PipelineLM):
            return model
        if not isinstance(model, TransformerLM):
            raise ValueError(
                f"ENGINE=pp supports the LM family (TransformerLM or a "
                f"pre-built PipelineLM); got {type(model).__name__}"
            )
        if model.moe_experts:
            raise ValueError(
                "ENGINE=pp supports the dense LM family; routed (MoE) FFNs "
                "are not stage-partitioned — use ENGINE=pjit with an "
                "'expert' mesh axis for expert parallelism"
            )
        stages = mesh.shape[PIPE_AXIS]
        depth = _VARIANTS[model.variant][1]
        n_layers = -(-depth // stages) * stages  # round up to equal stages
        if n_layers != depth:
            from distributeddeeplearning_tpu.utils.logging import get_logger

            get_logger().warning(
                "ENGINE=pp: %s depth %d is not divisible by %d stages — "
                "building %d layers (a deeper model than the dense %s; "
                "not comparable to its baseline)",
                model.variant, depth, stages, n_layers, model.variant,
            )
        return PipelineLM(
            variant=model.variant,
            vocab_size=model.vocab_size,
            max_seq_len=model.max_seq_len,
            num_stages=stages,
            n_layers=n_layers,
            dtype=model.dtype,
            # ring is the SP impl, and "auto" has not been measured inside
            # a stage's scan: plain attention unless a kernel is asked for
            attn_impl=(
                "xla" if model.attn_impl in ("ring", "auto") else model.attn_impl
            ),
            dropout=model.dropout,
            remat=model.remat,
        )
    return model


def _sp_sharding(mesh: Mesh):
    spec2 = NamedSharding(mesh, P("data", SEQ_AXIS))
    spec_w = NamedSharding(mesh, P("data"))

    def resolve(batch):
        n = len(batch)
        return (spec2,) * 2 if n == 2 else (spec2, spec2, spec_w)

    return resolve


def build_engine(
    model,
    config: TrainConfig,
    tx,
    mesh: Mesh,
    *,
    input_shape: Optional[Tuple[int, ...]] = None,
    input_dtype=None,
    state: Optional[TrainState] = None,
) -> Engine:
    """Build (state, train_step, eval_step, batch staging) for
    ``config.engine`` over ``mesh``. ``state`` (e.g. carried across
    ``fit`` calls by the keras skin) is placed, not re-initialised."""
    engine = config.engine
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r} (have {ENGINES})")
    # Every front-end (loop.fit, explicit.setup, the keras/estimator
    # skins) builds its engine here, and the state init below is the
    # process's first compile: re-runs of the same program deserialize
    # executables instead of re-invoking XLA.
    from distributeddeeplearning_tpu.training.warmup import (
        enable_compile_cache,
    )

    enable_compile_cache()
    # In-step gradient accumulation (ACCUM_STEPS) divisibility — checked
    # here, the one dispatch point, so every front-end fails with the
    # actionable message before any compile (training/accum.py).
    from distributeddeeplearning_tpu.training.accum import (
        validate_accum_config,
    )

    validate_accum_config(config, mesh)
    model = adapt_model(model, engine, mesh, config)

    if engine == "pjit":
        from distributeddeeplearning_tpu.training.pjit_step import (
            build_pjit_state,
            make_pjit_eval_step,
            make_pjit_train_step,
        )

        if state is None:
            state = build_pjit_state(
                model, config, tx, mesh,
                input_shape=input_shape, input_dtype=input_dtype,
            )
        return Engine(
            name=engine, mesh=mesh, model=model, state=state,
            train_step=make_pjit_train_step(model, tx, mesh, config),
            eval_step=make_pjit_eval_step(model, mesh, config),
        )

    if engine == "pp":
        from distributeddeeplearning_tpu.training.pp_step import (
            create_pp_state,
            make_pp_eval_step,
            make_pp_train_step,
        )

        seq_len = _seq_len_from(input_shape, model)
        if seq_len is None:
            raise ValueError(
                "ENGINE=pp needs the token signature — a dataset with a "
                "seq_len attribute or input_shape=(1, seq_len)"
            )
        if state is None:
            state = create_pp_state(model, config, tx, mesh, seq_len)
        return Engine(
            name=engine, mesh=mesh, model=model, state=state,
            train_step=make_pp_train_step(
                model, tx, mesh, config,
                num_microbatches=config.pp_microbatches,
                schedule=config.pp_schedule,
            ),
            eval_step=make_pp_eval_step(model, mesh),
        )

    # Replicated-state engines: dp and sp.
    from distributeddeeplearning_tpu.training.train_step import (
        create_train_state,
        make_eval_step,
        make_train_step,
        replicate_state,
    )

    if state is None:
        state = create_train_state(
            model, config, tx, input_shape=input_shape, input_dtype=input_dtype
        )
    state = replicate_state(state, mesh)

    if engine == "sp":
        from distributeddeeplearning_tpu.training.sp_step import (
            make_sp_eval_step,
            make_sp_train_step,
        )

        return Engine(
            name=engine, mesh=mesh, model=model, state=state,
            train_step=make_sp_train_step(model, tx, mesh, config),
            eval_step=make_sp_eval_step(model, mesh),
            batch_sharding=_sp_sharding(mesh),
        )

    return Engine(
        name=engine, mesh=mesh, model=model, state=state,
        train_step=make_train_step(model, tx, mesh, config),
        eval_step=make_eval_step(model, mesh),
    )


def build_eval_step(model, config: TrainConfig, mesh: Mesh):
    """Eval-only dispatch (``loop.evaluate`` with an existing state):
    returns ``(adapted_model, eval_step, batch_sharding_fn)``."""
    engine = config.engine
    if engine not in ENGINES:
        raise ValueError(f"unknown engine {engine!r} (have {ENGINES})")
    # Every front-end (loop.fit, explicit.setup, the keras/estimator
    # skins) builds its engine here, and the state init below is the
    # process's first compile: re-runs of the same program deserialize
    # executables instead of re-invoking XLA.
    from distributeddeeplearning_tpu.training.warmup import (
        enable_compile_cache,
    )

    enable_compile_cache()
    model = adapt_model(model, engine, mesh, config)
    if engine == "pjit":
        from distributeddeeplearning_tpu.training.pjit_step import (
            make_pjit_eval_step,
        )

        return model, make_pjit_eval_step(model, mesh, config), None
    if engine == "pp":
        from distributeddeeplearning_tpu.training.pp_step import (
            make_pp_eval_step,
        )

        return model, make_pp_eval_step(model, mesh), None
    if engine == "sp":
        from distributeddeeplearning_tpu.training.sp_step import (
            make_sp_eval_step,
        )

        return model, make_sp_eval_step(model, mesh), _sp_sharding(mesh)
    from distributeddeeplearning_tpu.training.train_step import make_eval_step

    return model, make_eval_step(model, mesh), None
