"""The one training engine behind all three front-ends.

The reference ships three parallel runtimes (tf.estimator's hidden loop,
Keras ``fit_generator``, PyTorch's hand-written loop — SURVEY.md §3);
here there is ONE engine and the front-ends are thin API skins (§7:
"3 API styles over one runtime"). The engine owns: state init/resume,
per-epoch iteration with device prefetch, the compiled train/eval steps,
callbacks, checkpointing, and the canonical throughput summary.
"""

from __future__ import annotations

import contextlib
import dataclasses
import itertools
import time
from typing import Any, Dict, Iterable, List, Optional, Protocol, Sequence, Tuple

import jax
import numpy as np
import optax

from distributeddeeplearning_tpu import faults, obs
from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.pipeline import prefetch_to_device
from distributeddeeplearning_tpu.parallel import collectives
from distributeddeeplearning_tpu.training.callbacks import (
    Callback,
    CallbackList,
    LoggerCallback,
)
from distributeddeeplearning_tpu.training.checkpoint import (
    CheckpointManager,
    build_manifest,
)
from distributeddeeplearning_tpu.training.metrics import (
    dispatch_step,
    finalize_accumulator,
    init_accumulator,
    log_sync,
)
from distributeddeeplearning_tpu.training.optimizer import create_optimizer
from distributeddeeplearning_tpu.training.state import TrainState
from distributeddeeplearning_tpu.utils import heartbeat, hostsync
from distributeddeeplearning_tpu.utils.logging import get_logger, log_summary
from distributeddeeplearning_tpu.utils.timer import Timer


class EpochDataset(Protocol):
    """The engine's dataset protocol (synthetic + ImageNet both satisfy it)."""

    steps_per_epoch: int

    def epoch(self, epoch_index: int) -> Iterable[Tuple[np.ndarray, np.ndarray]]: ...

    def __len__(self) -> int: ...


@dataclasses.dataclass
class FitResult:
    state: TrainState
    history: List[Dict[str, float]]
    images_per_sec: float
    # Host-sync accounting for the run (utils/hostsync.py): step-dispatch
    # p50/p99, wait time, host_sync_count, plus warmup compile_sec when
    # AOT warmup ran. Informational — never load-bearing for training.
    perf: Dict[str, float] = dataclasses.field(default_factory=dict)


def resolve_engine(config, mesh=None):
    """Validate ``config.engine`` and resolve the mesh (explicit arg wins;
    else ``config.mesh_axes``/``mesh_shape``; else an engine-appropriate
    default over all devices). Returns ``(engine_name, mesh)`` — one
    helper for every entry point so an unknown engine can never fall
    through to the wrong step."""
    from distributeddeeplearning_tpu.parallel.mesh import (
        create_mesh,
        mesh_from_config,
    )
    from distributeddeeplearning_tpu.training.engines import ENGINES

    if config.engine not in ENGINES:
        raise ValueError(
            f"unknown engine {config.engine!r} (have {', '.join(ENGINES)})"
        )
    # Validate the rules-table name eagerly (raises for unknown values),
    # and refuse a non-default PARAM_SHARDING under the dp engine — the
    # shard_map engine replicates params, so the user would silently NOT
    # get the ZeRO-3 memory savings they asked for.
    from distributeddeeplearning_tpu.models.sharding import rules_table

    rules_table(config.param_sharding)
    # Only "fsdp" is meaningless under the shard_map engines ("dp" rules =
    # replicated params, which is exactly what they do).
    if config.engine != "pjit" and config.param_sharding == "fsdp":
        raise ValueError(
            f"PARAM_SHARDING={config.param_sharding!r} requires ENGINE=pjit "
            f"(the {config.engine} engine keeps parameters replicated)"
        )
    if config.pp_schedule not in ("gpipe", "1f1b"):
        raise ValueError(
            f"unknown PP_SCHEDULE {config.pp_schedule!r} (have gpipe, 1f1b)"
        )
    # ACCUM_STEPS sanity that needs no mesh (>= 1); divisibility against
    # the resolved mesh is validated in engines.build_engine.
    from distributeddeeplearning_tpu.training.accum import resolve_accum_steps

    resolve_accum_steps(config)
    if config.nonfinite_action not in ("abort", "warn", "off"):
        raise ValueError(
            f"NONFINITE_ACTION={config.nonfinite_action!r} "
            "(have abort, warn, off)"
        )
    if config.data_topology not in ("process", "global"):
        raise ValueError(
            f"DATA_TOPOLOGY={config.data_topology!r} (have process, global)"
        )
    if config.stream_shuffle_block < 1:
        raise ValueError(
            f"STREAM_SHUFFLE_BLOCK must be >= 1, got "
            f"{config.stream_shuffle_block}"
        )
    if config.prefetch_host_batches < 0:
        raise ValueError(
            f"PREFETCH_HOST_BATCHES must be >= 0, got "
            f"{config.prefetch_host_batches}"
        )
    if config.lr_world_size is not None and config.lr_world_size < 1:
        raise ValueError(
            f"LR_WORLD_SIZE must be >= 1, got {config.lr_world_size}"
        )
    if config.checkpoint_every_steps < 0:
        raise ValueError(
            f"CHECKPOINT_EVERY_STEPS must be >= 0, got "
            f"{config.checkpoint_every_steps}"
        )
    if config.checkpoint_keep < 1:
        raise ValueError(
            f"CHECKPOINT_KEEP must be >= 1, got {config.checkpoint_keep}"
        )
    if mesh is None:
        # Engine-appropriate default topology when the user named an
        # engine but no mesh at all: ENGINE=pp → (data, pipe) with
        # PP_STAGES on pipe (all devices if unset); ENGINE=sp → all
        # devices on seq. An explicit MESH_AXES/MESH_SHAPE always wins
        # (and is validated below).
        unset = config.mesh_shape is None and tuple(config.mesh_axes) == ("data",)
        if config.engine == "pp" and unset:
            stages = config.pp_stages or len(jax.devices())
            mesh = create_mesh(axes=("data", "pipe"), shape=(-1, stages))
        elif config.engine == "sp" and unset:
            mesh = create_mesh(axes=("data", "seq"), shape=(1, -1))
        else:
            mesh = mesh_from_config(config)
    if config.engine == "pp":
        if "pipe" not in mesh.axis_names:
            raise ValueError(
                f"ENGINE=pp needs a 'pipe' mesh axis; got {mesh.axis_names} "
                "(set MESH_AXES=data,pipe MESH_SHAPE=<dp>,<stages>)"
            )
        if config.pp_stages and mesh.shape["pipe"] != config.pp_stages:
            raise ValueError(
                f"PP_STAGES={config.pp_stages} != mesh pipe axis "
                f"{mesh.shape['pipe']}"
            )
    if config.engine == "sp" and "seq" not in mesh.axis_names:
        raise ValueError(
            f"ENGINE=sp needs a 'seq' mesh axis; got {mesh.axis_names} "
            "(set MESH_AXES=data,seq MESH_SHAPE=<dp>,<sp>)"
        )
    return config.engine, mesh


def _init_spec(data):
    """Infer the model-init input signature from the dataset so every
    front-end can train token models: a dataset exposing ``seq_len``
    (SyntheticTokenDataset) inits with ``(1, seq_len)`` int32 tokens;
    otherwise the image contract applies (``create_train_state``
    defaults)."""
    import jax.numpy as jnp

    seq_len = getattr(data, "seq_len", None)
    if seq_len is not None:
        return (1, int(seq_len)), jnp.int32
    return None, None


def fit(
    model,
    config: TrainConfig,
    train_data: EpochDataset,
    *,
    mesh=None,
    tx: Optional[optax.GradientTransformation] = None,
    epochs: Optional[int] = None,
    callbacks: Sequence[Callback] = (),
    eval_data: Optional[EpochDataset] = None,
    checkpoint_manager: Optional[CheckpointManager] = None,
    add_default_logger: bool = True,
    state: Optional[TrainState] = None,
    initial_epoch: int = 0,
) -> FitResult:
    """Train ``model`` for ``epochs`` over ``train_data`` on ``mesh``.

    Mirrors, in one place, the reference's three mainlines: builds state
    (deterministic seeded init ≙ broadcast), resumes from checkpoint if
    present (Keras ``:323-341``), runs epochs with device-prefetched
    batches, fires callbacks, optionally evaluates (metrics in-step
    averaged, Keras ``:344-353``), and prints the ``_log_summary`` block.
    """
    log = get_logger()
    # Event bus: OBS_DIR turns on JSONL capture (per-process file, flight
    # recorder armed); without it the bus stays ring-only and every emit
    # below is a host-side dict append. Either way: zero device work.
    bus = obs.configure_from_env()
    from distributeddeeplearning_tpu.obs import trace as obs_trace

    tracer = obs_trace.from_env()
    engine_name, mesh = resolve_engine(config, mesh)
    epochs = epochs if epochs is not None else config.epochs
    steps_per_epoch = train_data.steps_per_epoch

    # Batch-shard count from the RESOLVED mesh (an explicit `mesh` arg
    # may differ from the topology config describes): drives the LR
    # linear-scaling rule and the throughput accounting below.
    from distributeddeeplearning_tpu.parallel.mesh import dp_size

    n_batch_shards = dp_size(mesh)
    if tx is None:
        # Elastic worlds pin LR_WORLD_SIZE to the FULL world so the LR
        # schedule (linear-scaling rule) is identical on any resized
        # relaunch; otherwise the resolved mesh's shard count applies.
        tx, _ = create_optimizer(
            config,
            steps_per_epoch,
            world_size=config.lr_world_size or n_batch_shards,
        )
    from distributeddeeplearning_tpu.training.engines import build_engine

    shape, dtype = _init_spec(train_data)
    eng = build_engine(
        model, config, tx, mesh,
        input_shape=shape, input_dtype=dtype, state=state,
    )
    state, model = eng.state, eng.model

    from distributeddeeplearning_tpu.training.callbacks import (
        ModelCheckpointCallback,
    )

    cbs = list(callbacks)
    if add_default_logger and not any(isinstance(c, LoggerCallback) for c in cbs):
        cbs.append(LoggerCallback())
    callback_list = CallbackList(
        cbs,
        context={
            "config": config,
            "mesh": mesh,
            "steps_per_epoch": steps_per_epoch,
            "checkpoint_manager": checkpoint_manager,
        },
    )

    # Exactly ONE orbax manager per directory: two managers saving the same
    # step race/crash. Priority: explicit manager > the callback's manager
    # (shared — engine resumes from it, callback saves to it) > auto from
    # config.model_dir. The callback defers to context["checkpoint_manager"]
    # so an explicit manager is shared too.
    ckpt_cb = next(
        (c for c in cbs if isinstance(c, ModelCheckpointCallback)), None
    )
    ckpt = checkpoint_manager
    if ckpt is None and ckpt_cb is not None:
        ckpt = ckpt_cb.manager()
    if ckpt is None and config.model_dir:
        ckpt = CheckpointManager(
            config.model_dir,
            max_to_keep=config.checkpoint_keep,
            save_every_epochs=config.checkpoint_every_epochs,
            save_every_steps=config.checkpoint_every_steps,
            async_save=config.checkpoint_async,
        )
    engine_saves = ckpt is not None and ckpt_cb is None

    # Keras resume contract (reference :323-341): load_weights +
    # initial_epoch skips completed epochs and keeps the LR schedule
    # position. Checkpoint-derived position wins if it is further along.
    # Step-granular checkpoints (CHECKPOINT_EVERY_STEPS) resume
    # MID-epoch: the first skip_steps batches of the resume epoch were
    # already trained and are skipped below, so a preemption loses
    # minutes, not an epoch (docs/ROBUSTNESS.md).
    start_epoch = initial_epoch
    skip_steps = 0
    if ckpt is not None and ckpt.enabled and config.resume:
        state, ckpt_epoch, ckpt_skip = ckpt.maybe_restore_at(
            state, steps_per_epoch
        )
        # Accum-rescale math contract (docs/ROBUSTNESS.md elasticity):
        # the manifest records the effective batch the trajectory was
        # trained at; a resumed world — on ANY topology — must deliver
        # the same one (batch_size_per_device × batch shards; the
        # elastic supervisor holds it constant by rescaling BATCHSIZE
        # and ACCUM_STEPS together). ELASTIC=1 enforces; otherwise an
        # intentional batch change only warns.
        manifest = getattr(ckpt, "last_manifest", None)
        if manifest and manifest.get("effective_batch"):
            saved_eff = int(manifest["effective_batch"])
            have_eff = config.batch_size_per_device * n_batch_shards
            if saved_eff != have_eff:
                msg = (
                    f"checkpoint was trained at effective batch "
                    f"{saved_eff} (world {manifest.get('world_size')}, "
                    f"accum {manifest.get('accum_steps')}) but this "
                    f"topology delivers {have_eff} "
                    f"({config.batch_size_per_device}/device x "
                    f"{n_batch_shards} shards) — rescale BATCHSIZE and "
                    f"ACCUM_STEPS together to hold the effective batch "
                    f"constant"
                )
                if config.elastic:
                    raise ValueError(f"ELASTIC resume refused: {msg}")
                log.warning("%s (continuing: ELASTIC is off)", msg)
            elif (
                manifest.get("steps_per_epoch")
                and int(manifest["steps_per_epoch"]) != steps_per_epoch
                and config.elastic
            ):
                raise ValueError(
                    f"ELASTIC resume refused: checkpoint epoch geometry "
                    f"is {manifest['steps_per_epoch']} steps/epoch, this "
                    f"dataset delivers {steps_per_epoch} — the data "
                    f"cursor would be meaningless"
                )
        if (ckpt_epoch, ckpt_skip) > (start_epoch, 0):
            start_epoch, skip_steps = ckpt_epoch, ckpt_skip
        if start_epoch or skip_steps:
            log.info(
                "resuming from epoch %d step %d", start_epoch, skip_steps
            )
            bus.point("resume", epoch=start_epoch, step_in_epoch=skip_steps)
    # Host-side count of completed optimizer steps — the checkpoint key
    # and the fault-plan clock. Assumes the dataset honours its declared
    # steps_per_epoch (every repo dataset does).
    global_step = start_epoch * steps_per_epoch + skip_steps
    injector = faults.FaultInjector.from_env()

    # Checkpointable-stream contract (data/stream/, docs/DATA.md): a
    # dataset exposing epoch_at + cursor seeks to any (epoch, step) in
    # O(1) and serializes its position into the manifest's data_cursor,
    # so mid-epoch resume skips the O(step) prefix replay entirely.
    supports_cursor = callable(
        getattr(train_data, "epoch_at", None)
    ) and callable(getattr(train_data, "cursor", None))
    if supports_cursor and ckpt is not None and config.resume:
        saved_cursor = (getattr(ckpt, "last_manifest", None) or {}).get(
            "data_cursor"
        )
        if saved_cursor:
            live = train_data.cursor(start_epoch, skip_steps)
            drift = {
                k: (saved_cursor.get(k), live.get(k))
                for k in ("seed", "records", "shuffle_block", "global_batch")
                if saved_cursor.get(k) is not None
                and saved_cursor.get(k) != live.get(k)
            }
            if drift:
                log.warning(
                    "checkpoint data_cursor describes a different stream "
                    "(%s) — resume position is kept, but the continued "
                    "stream is NOT the one the checkpoint was trained on",
                    ", ".join(
                        f"{k}: saved {a} != live {b}"
                        for k, (a, b) in drift.items()
                    ),
                )

    def make_manifest(step_key: int):
        """Topology-independence record for a checkpoint at ``step_key``
        (training/checkpoint.build_manifest). Returned as a zero-arg
        callable so the manager only builds it for saves that are DUE —
        the per-step path stays dict-free (and, like everything here,
        host-int-only: zero device syncs)."""

        def _build():
            return build_manifest(
                global_step=step_key,
                steps_per_epoch=steps_per_epoch,
                effective_batch=int(global_batch),
                accum_steps=int(
                    getattr(train_step, "accum_steps", config.accum_steps)
                ),
                # Streamed datasets (data/stream/): the O(1)-seekable
                # stream position at this step — host ints only.
                data_cursor=(
                    train_data.cursor(
                        step_key // steps_per_epoch,
                        step_key % steps_per_epoch,
                    )
                    if supports_cursor
                    else None
                ),
                # The RESOLVED mesh's device count (not the process-wide
                # jax.device_count()): a sub-mesh world is smaller than
                # the host's device pool, and world_size is what the
                # cross-topology restore telemetry compares against.
                world_size=int(mesh.devices.size),
            )

        return _build

    train_step = eng.train_step
    eval_step = eng.eval_step if eval_data is not None else None
    # All engine-built steps carry the metric-accumulator contract
    # (training/metrics.StepFn); a hand-rolled step without it keeps the
    # legacy last-step-metrics epoch summary.
    accumulates = getattr(train_step, "accumulates_metrics", False)
    clock = hostsync.StepClock()
    sync_start = hostsync.accountant().count
    warmup_pending = config.aot_warmup
    warmup_info: Dict[str, float] = {}

    # Host read-ahead applies to datasets that opt in (the streamed
    # shard readers set the marker; in-memory synthetic pools gain
    # nothing from an extra thread).
    host_prefetch_depth = (
        config.prefetch_host_batches
        if getattr(train_data, "host_prefetch", False)
        else 0
    )
    if host_prefetch_depth:
        from distributeddeeplearning_tpu.data.stream import (
            prefetch as stream_prefetch,
        )

    history: List[Dict[str, float]] = []
    # Throughput accounting counts what the dataset actually delivers
    # (read off the staged batch's leading dim — shape metadata, no host
    # sync), not a config-derived figure that can disagree with it.
    global_batch = config.batch_size_per_device * n_batch_shards
    run_timer = Timer().start()
    total_images = 0
    callback_list.on_train_begin({"state": state})

    bus.point(
        "run_begin",
        engine=engine_name,
        model=config.model,
        epochs=epochs,
        start_epoch=start_epoch,
        start_step_in_epoch=skip_steps,
        steps_per_epoch=steps_per_epoch,
        devices=jax.device_count(),
        accum_steps=getattr(train_step, "accum_steps", config.accum_steps),
    )
    metrics = {}
    first_dispatch = True
    for epoch in range(start_epoch, epochs):
        if tracer is not None:
            tracer.maybe_start(epoch)
        epoch_t0 = time.monotonic()
        callback_list.on_epoch_begin(epoch)
        step_in_epoch = 0
        # Fresh on-device accumulator per epoch: metric sums + step count
        # ride the compiled step (donated), so epoch statistics build up
        # in HBM and the loop stays sync-free between epoch boundaries.
        acc = init_accumulator(mesh) if accumulates else None
        if epoch == start_epoch and skip_steps and supports_cursor:
            # Checkpointable stream (data/stream/, docs/DATA.md): the
            # manifest's data_cursor decodes to (epoch, step) and the
            # dataset SEEKS there — a pure index computation, zero
            # skipped records read, zero prefix replay. The gauge the
            # legacy path fills with the replayed-batch count reports 0
            # here by design: that 0 IS the O(1)-resume contract the
            # oracle (tests/test_stream.py) pins.
            seek_t0 = time.monotonic()
            batches = train_data.epoch_at(epoch, skip_steps)
            seek_s = time.monotonic() - seek_t0
            bus.span_event(
                "data.resume_seek", seek_s, epoch=epoch, offset=skip_steps
            )
            bus.gauge("data.resume_skip_batches", 0.0)
            bus.gauge("data.resume_skip_ms", seek_s * 1000.0)
            bus.point("resume_seek", epoch=epoch, offset=skip_steps)
            log.info(
                "resume sought to epoch %d step %d in %.2f ms "
                "(O(1) stream cursor; no prefix replay — docs/DATA.md)",
                epoch, skip_steps, seek_s * 1000.0,
            )
        else:
            batches = train_data.epoch(epoch)
            if epoch == start_epoch and skip_steps:
                # Mid-epoch resume, legacy datasets: the epoch stream is
                # deterministic in (seed, epoch), so dropping the first
                # k batches — before any staging — replays exactly the
                # part of the epoch the checkpoint had not yet covered.
                # The skip is consumed EAGERLY and timed: replaying an
                # epoch prefix is O(step-in-epoch) host work, and the
                # data.resume_skip span/gauges make that cost visible
                # instead of smearing it into the first step.
                skip_t0 = time.monotonic()
                batches = iter(batches)
                skipped = sum(
                    1 for _ in itertools.islice(batches, skip_steps)
                )
                skip_s = time.monotonic() - skip_t0
                bus.span_event(
                    "data.resume_skip", skip_s, epoch=epoch, skipped=skipped
                )
                bus.gauge("data.resume_skip_batches", float(skipped))
                bus.gauge("data.resume_skip_ms", skip_s * 1000.0)
                bus.point("resume_skip", epoch=epoch, skipped=skip_steps)
                log.info(
                    "resume replayed %d skipped batch(es) in %.1f ms "
                    "(O(step) epoch-prefix replay; docs/DATA.md)",
                    skipped, skip_s * 1000.0,
                )
        if host_prefetch_depth:
            # Host-overlapped read-ahead (data/stream/prefetch.py): the
            # shard-read/assemble leg runs on a background thread,
            # instrumented as data.wait / data.buffer_depth /
            # data.bytes_per_s; prefetch_to_device below still owns the
            # host->HBM staging leg.
            batches = stream_prefetch.host_prefetch(
                batches, depth=host_prefetch_depth
            )
        for batch in prefetch_to_device(
            batches, mesh, size=config.prefetch_batches,
            sharding=eng.batch_sharding,
        ):
            global_batch = int(jax.tree.leaves(batch)[0].shape[0])
            if warmup_pending:
                # AOT-compile against the real staged signature, OUTSIDE
                # the dispatch clock — compile time is reported as
                # compile_sec, not smeared into step time.
                warmup_info = eng.warmup(batch, acc=acc)
                warmup_pending = False
            if injector is not None:
                # Deterministic NaN injection (FAULT_PLAN nan:step=N):
                # poisons the batch whose dispatch completes step N —
                # an on-device multiply, no host sync.
                batch = injector.poison(global_step + 1, batch)
            t0 = time.perf_counter()
            # The run's first dispatch compiles when AOT warmup is off;
            # heartbeat through it so the launcher's hang watchdog does
            # not mistake a long silent compile for a dead world.
            with (
                heartbeat.during("first_step_compile")
                if first_dispatch
                else contextlib.nullcontext()
            ):
                # The `step` span (dispatch_step) times the same
                # dispatch on the bus, and on the profiler's clock when
                # a capture runs; no device value is materialised.
                if accumulates:
                    state, metrics, acc = dispatch_step(
                        train_step, state, batch, acc, epoch=epoch
                    )
                else:
                    state, metrics = dispatch_step(
                        train_step, state, batch, epoch=epoch
                    )
            first_dispatch = False
            dispatch_s = time.perf_counter() - t0
            clock.note_dispatch(dispatch_s)
            step_in_epoch += 1
            global_step += 1
            if ckpt is not None and ckpt.step_granular:
                # Step-granular checkpoint (CHECKPOINT_EVERY_STEPS): a
                # due save materialises the state — the documented
                # durability-vs-sync trade; off (the default) the loop
                # keeps its ≤1-sync/epoch contract. Runs for callback-
                # owned managers too (the callback only covers the epoch
                # boundary; save_step is idempotent per key). The
                # manifest (host ints only — no device work) makes the
                # checkpoint topology-independent: any world size can
                # decode the data cursor and validate the effective
                # batch.
                ckpt.save_step(
                    global_step, state, manifest=make_manifest(global_step)
                )
            if injector is not None and injector.due_after(global_step):
                # Make pending saves durable first so the kill point is
                # deterministic relative to the resume point, then die.
                if ckpt is not None:
                    ckpt.wait()
                bus.flush()
                injector.fire_after(global_step)
            if (
                config.log_every_steps
                and step_in_epoch % config.log_every_steps == 0
            ):
                # Metrics/accumulator stay device-resident on purpose: a
                # callback that float()s them pays (and owns) that sync;
                # the span shows what it paid.
                with log_sync(epoch=epoch):
                    callback_list.on_step_end(
                        step_in_epoch,
                        {
                            "metrics": metrics,
                            "state": state,
                            "metric_accumulator": acc,
                        },
                    )
        epoch_images = step_in_epoch * global_batch
        total_images += epoch_images
        # THE one host sync per epoch: materialise the on-device epoch
        # means (or, for a legacy step without the accumulator contract,
        # the last step's metrics) in a single device_get.
        epoch_values = finalize_accumulator(acc) if accumulates else metrics
        with clock.waiting(), bus.span("epoch_materialize", epoch=epoch):
            epoch_logs: Dict[str, Any] = {
                k: float(v)
                for k, v in hostsync.device_get(
                    epoch_values, label="epoch_metrics"
                ).items()
            }
        # Non-finite guard: the accumulator counted NaN/Inf-loss steps ON
        # DEVICE; the count arrived inside the one materialisation above,
        # so detection costs zero extra host syncs. Legacy steps without
        # the accumulator are checked on the loss float just landed.
        nonfinite_steps = int(epoch_logs.pop("nonfinite_steps", 0.0))
        if not accumulates:
            loss_v = epoch_logs.get("loss")
            nonfinite_steps = int(
                loss_v is not None and not np.isfinite(loss_v)
            )
        if nonfinite_steps and config.nonfinite_action != "off":
            bus.point(
                "nonfinite_loss",
                epoch=epoch,
                steps=nonfinite_steps,
                action=config.nonfinite_action,
            )
            bus.flush()
            if config.nonfinite_action == "abort":
                log.error(
                    "non-finite loss in %d step(s) of epoch %d — aborting "
                    "with exit %d (non-retryable: a resume would replay "
                    "the same batches into the same NaN)",
                    nonfinite_steps, epoch, faults.EXIT_NONFINITE,
                )
                if bus.directory:
                    bus.dump_flight("nonfinite_loss")
                if ckpt is not None:
                    ckpt.wait()
                raise faults.NonFiniteLossError(epoch, nonfinite_steps)
            log.warning(
                "non-finite loss in %d step(s) of epoch %d "
                "(NONFINITE_ACTION=warn: continuing)",
                nonfinite_steps, epoch,
            )
        epoch_logs["epoch_images"] = epoch_images
        epoch_logs["global_step"] = global_step

        if eval_step is not None and eval_data is not None and config.validation:
            eval_metrics = _run_eval(
                eval_step, state, eval_data, mesh, config,
                sharding=eng.batch_sharding,
            )
            epoch_logs.update({f"val_{k}": v for k, v in eval_metrics.items()})

        history.append({k: v for k, v in epoch_logs.items() if k != "state"})
        # Epoch metrics enter the bus HERE — at the existing boundary,
        # from host floats already materialised above (no extra sync).
        for k, v in epoch_logs.items():
            if isinstance(v, (int, float)):
                bus.gauge(f"epoch.{k}", float(v), epoch=epoch)
        epoch_logs["state"] = state
        # Callback-owned checkpoint managers save through on_epoch_end:
        # hand them the same lazy manifest the engine-owned path uses.
        epoch_logs["ckpt_manifest"] = make_manifest(global_step)
        callback_list.on_epoch_end(epoch, epoch_logs)
        if engine_saves:
            # One call for either keying: epoch-keyed saves as ever, or
            # the boundary's global-step key under CHECKPOINT_EVERY_STEPS.
            ckpt.save_epoch_end(
                epoch, state, global_step=global_step,
                manifest=make_manifest(global_step),
            )
        bus.span_event(
            "epoch",
            time.monotonic() - epoch_t0,
            t=epoch_t0,
            epoch=epoch,
            steps=step_in_epoch,
        )
        if tracer is not None:
            tracer.maybe_stop(epoch)
        bus.flush()  # epoch boundary: the one place events hit disk

    run_timer.stop()
    callback_list.on_train_end({"state": state})
    if ckpt is not None:
        ckpt.wait()

    perf = clock.summary()
    perf["host_sync_count"] = float(
        hostsync.accountant().count - sync_start
    )
    perf.update(warmup_info)
    # Effective-batch accounting: one dispatch == one optimizer step on
    # the whole staged batch, with or without in-step accumulation —
    # every image above was counted exactly once, and the dataset's
    # delivered batch IS the effective batch. accum_steps only changes
    # the in-step microbatch (global_batch / accum_steps / dp).
    accum_steps = int(getattr(train_step, "accum_steps", config.accum_steps))
    perf["accum_steps"] = float(accum_steps)
    perf["effective_batch"] = float(global_batch)
    extra: Dict[str, Any] = {
        "host_sync_count": int(perf["host_sync_count"]),
        "dispatch_p50_ms": round(perf["dispatch_p50_ms"], 3),
        "dispatch_p99_ms": round(perf["dispatch_p99_ms"], 3),
    }
    if accum_steps > 1:
        extra["accum_steps"] = accum_steps
        extra["effective_batch"] = int(global_batch)
    if "compile_sec" in perf:
        extra["compile_sec"] = round(perf["compile_sec"], 3)
    images_per_sec = log_summary(
        data_length=total_images,
        duration_s=run_timer.elapsed,
        batch_size_per_device=config.batch_size_per_device,
        num_devices=jax.device_count(),
        dataset_kind="synthetic" if config.fake else "real",
        extra_fields=extra,
    )
    # FitResult.perf, machine-readable: the same numbers the stdout
    # summary prints, queryable from the merged run report.
    for k, v in perf.items():
        bus.gauge(f"perf.{k}", float(v))
    bus.point("run_end", images_per_sec=round(images_per_sec, 1))
    bus.flush()
    return FitResult(
        state=state,
        history=history,
        images_per_sec=images_per_sec,
        perf=perf,
    )


def _run_eval(
    eval_step, state, eval_data, mesh, config, sharding=None
) -> Dict[str, float]:
    """Sample-exact evaluation: each batch's means are re-weighted by its
    real-sample ``count``, so padded tail batches (exact-coverage datasets)
    and full batches combine into metrics over exactly the dataset."""
    totals: Dict[str, float] = {}
    samples = 0.0
    for batch in prefetch_to_device(
        eval_data.epoch(0), mesh, size=config.prefetch_batches,
        sharding=sharding,
    ):
        # One materialisation per eval batch (boundary work, not the hot
        # loop) — a single device_get of the whole metric dict.
        m = {
            k: float(v)
            for k, v in hostsync.device_get(
                eval_step(state, batch), label="eval_batch"
            ).items()
        }
        count = m.pop("count", None)
        if count is None:  # legacy eval step: unweighted batch means
            count = 1.0
        samples += count
        for k, v in m.items():
            totals[k] = totals.get(k, 0.0) + v * count
    out = {k: v / max(samples, 1.0) for k, v in totals.items()}
    out["samples"] = samples
    return out


def evaluate(
    model,
    config: TrainConfig,
    eval_data: EpochDataset,
    state: TrainState,
    *,
    mesh=None,
) -> Dict[str, float]:
    """Standalone evaluation (reference ``validate()`` PyTorch ``:224-239``).

    Dispatches on ``config.engine`` like ``fit`` — a TP-sharded state
    must not pass through the shard_map step's replicated in_spec (it
    would all-gather the params on every device)."""
    from distributeddeeplearning_tpu.training.engines import build_eval_step

    _, mesh = resolve_engine(config, mesh)
    _, eval_step, sharding = build_eval_step(model, config, mesh)
    return _run_eval(eval_step, state, eval_data, mesh, config, sharding=sharding)
