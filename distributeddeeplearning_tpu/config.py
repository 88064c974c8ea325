"""Typed training configuration with reference env-var compatibility.

The reference configures its trainers entirely through env vars parsed ad
hoc in each script (``DISTRIBUTED``, ``FAKE``, ``FAKE_DATA_LENGTH``,
``EPOCHS``, ``VALIDATION`` plus Keras-only worker knobs — SURVEY.md §5
"Config / flag system"; ``imagenet_estimator_tf_horovod.py:36-48``) and
module constants (``_LR = 0.001``, ``_BATCHSIZE = 64``, ``:24-33``). Here
configuration is a typed dataclass with an env-var compatibility
constructor so the reference's operational contract (same script local and
on-cluster, configured by the launcher via env) still works.

Reference defects fixed (SURVEY.md §2c):
- #2: ``EPOCHS`` env var returned ``str`` and broke arithmetic — all
  numeric env vars are parsed to int/float here.
- permissive ``_str_to_bool`` (``"t" in value.lower()``, so "false" →
  True-ish behavior on words containing t) replaced by an explicit set.
"""

from __future__ import annotations

import dataclasses
import os
from typing import Mapping, Optional, Sequence, Tuple

# ImageNet preprocessing constants, matching the reference exactly:
# per-channel means (imagenet_estimator_tf_horovod.py:30-32) and the
# torchvision mean/sd pair (imagenet_pytorch_horovod.py:41-42).
IMAGENET_RGB_MEAN_255 = (123.68, 116.78, 103.94)
IMAGENET_RGB_MEAN = (0.485, 0.456, 0.406)
IMAGENET_RGB_SD = (0.229, 0.224, 0.225)
IMAGENET_TRAIN_LENGTH = 1_281_167  # FAKE_DATA_LENGTH default, TF :45-47


def _str_to_bool(value: str) -> bool:
    """Strict boolean env parsing (fixes the reference's ``"t" in v`` rule)."""
    return value.strip().lower() in {"1", "true", "t", "yes", "y", "on"}


def _env(env: Optional[Mapping[str, str]]) -> Mapping[str, str]:
    return os.environ if env is None else env


@dataclasses.dataclass
class TrainConfig:
    """Everything a training run needs, in one typed object."""

    # Model / task
    model: str = "resnet50"
    num_classes: int = 1000
    image_size: int = 224
    compute_dtype: str = "bfloat16"  # MXU-native; params stay float32
    # Host→device image staging dtype (env INPUT_STAGING):
    #   "auto"     — the compute dtype (bf16 halves PCIe bytes)
    #   "uint8"    — raw RGB bytes, normalize ON DEVICE (engines fold
    #                (x/255 − mean)/sd into the first pass): half of even
    #                the bf16 transfer — the real-data e2e lever
    #                (PROFILE.md round-4 decomposition)
    #   "float32" | "bfloat16" — explicit overrides
    input_staging: str = "auto"
    # Attention core of the attention models (ViT, the LM families):
    # "auto" (the default) lets each call choose from what it can see
    # (ops/attention.resolve_impl): on a TPU with local operands
    # the packed kernel at T <= 512, the streaming flash kernel from
    # T = 640 on, the XLA einsum elsewhere. "xla" | "pallas" (flash) |
    # "fused" (packed) | "ring" (sequence-parallel) force a path.
    attn_impl: str = "auto"
    # Mixture-of-Experts width for MoE-capable models (the LM families):
    # None keeps each model's own default (8 for lm_moe_*, dense for lm_*).
    moe_experts: Optional[int] = None
    # Gradient checkpointing for block-structured models (ViT/LM/pipeline
    # stages): recompute activations in backward — O(depth) memory.
    remat: bool = False

    # Training objective of a token model. "next_token": the dataset's
    # (tokens, labels) batches as they are. "block_diffusion" (BD3-LM's
    # masked objective; models with a block-diffusion mask, models/
    # decoder.py): the staging noises each clean row by blocks of
    # `diffusion_block` tokens at a level t ~ U(`diffusion_t_min`, 1) a
    # block and hands the step ([noised ‖ clean], targets, weights 1/t)
    # (data/noise.py). `mask_token_id` None = the vocabulary's last id.
    objective: str = "next_token"
    diffusion_block: int = 4
    diffusion_t_min: float = 0.125
    mask_token_id: Optional[int] = None

    # Optimization — reference constants: LR 0.001 × world size
    # (TF :154, PyTorch :333), momentum 0.9, L2 5e-5 (Keras :97-116),
    # warmup 5 epochs + ×0.1 decay @30/60/80 (Keras :211-224, arXiv:1706.02677).
    batch_size_per_device: int = 64
    base_lr: float = 0.001
    # "sgd" (reference parity) | "adamw" (LM-tier convention: decoupled
    # weight decay on kernels, betas below).
    optimizer: str = "sgd"
    momentum: float = 0.9
    adam_beta1: float = 0.9
    adam_beta2: float = 0.95  # LM-training convention; 0.999 for vision
    adam_eps: float = 1e-8
    # Decoupled weight decay (adamw only; applied to kernel params). The
    # L2-in-loss `weight_decay` below is the reference's Keras semantics —
    # set it to 0 when using adamw to avoid double regularization.
    decoupled_weight_decay: float = 0.0
    # Gradient accumulation: optimizer updates every k calls with the
    # mean of the last k gradients (k× the effective batch without k×
    # the memory). Works under every engine.
    grad_accum_steps: int = 1
    # In-step microbatched accumulation (env ACCUM_STEPS): every engine's
    # compiled step scans over k microbatches with an on-device f32
    # gradient accumulator — activation memory scales with the MICRObatch
    # while one host dispatch still covers one effective step (unlike
    # grad_accum_steps above, which spends k dispatches per update).
    # Must divide batch_size_per_device (and, under ENGINE=pp, leave each
    # microbatch divisible by pp_microbatches) — validated with the
    # numbers named in training/accum.validate_accum_config.
    accum_steps: int = 1
    weight_decay: float = 5e-5
    label_smoothing: float = 0.0
    epochs: int = 1
    warmup_epochs: int = 5
    # "step" (reference ×0.1 @30/60/80) | "cosine" (warmup → cosine to 0
    # over `epochs`) | "constant" (warmup → flat peak).
    lr_schedule: str = "step"
    lr_decay_epochs: Tuple[int, ...] = (30, 60, 80)
    lr_decay_factor: float = 0.1
    # Optional per-boundary multiplicative factors (same length as
    # lr_decay_epochs); overrides the uniform lr_decay_factor when set.
    lr_decay_factors: Optional[Tuple[float, ...]] = None
    scale_lr_by_world_size: bool = True

    # Data
    fake: bool = True
    fake_data_length: int = IMAGENET_TRAIN_LENGTH
    data_dir: Optional[str] = None
    val_data_dir: Optional[str] = None
    # Real-data pipeline: "auto" detects stream shards (a
    # stream_index.json in DATA_DIR) vs TFRecord shards vs an
    # ImageFolder tree; force with "stream" (sharded streaming reader
    # with the O(1) checkpointable shuffle cursor, data/stream/) |
    # "imagefolder" | "tfrecord" (tf.data reader) | "tfrecord-native"
    # (first-party TF-free reader, native/ tier).
    data_format: str = "auto"
    # Streamed-shard shuffle block (env STREAM_SHUFFLE_BLOCK,
    # docs/DATA.md): the block-permutation granularity of the
    # checkpointable global shuffle — records mix globally at block
    # granularity and exactly within blocks; >= the record count
    # degenerates to one exact global permutation.
    stream_shuffle_block: int = 256
    # Host-side read-ahead for streamed shards (env
    # PREFETCH_HOST_BATCHES; 0 = off): a background thread keeps this
    # many ASSEMBLED host batches ahead of staging, overlapping shard
    # reads with compute and reporting the data.* gauges
    # (docs/OBSERVABILITY.md). Distinct from prefetch_batches, which
    # stages already-assembled batches into HBM.
    prefetch_host_batches: int = 2
    validation: bool = False
    num_workers: int = 4  # Keras NUM_WORKERS (:44-46)
    # "thread" | "process" — the reference Keras MULTIPROCESSING knob
    # (:44-46): process workers sidestep the GIL for Python-side
    # decode/augment on many-core hosts.
    worker_mode: str = "thread"
    prefetch_batches: int = 2

    # Distribution
    distributed: bool = False
    mesh_shape: Optional[Tuple[int, ...]] = None  # None → all devices on 'data'
    mesh_axes: Tuple[str, ...] = ("data",)
    # Training engine: "dp" = shard_map data-parallel (reference-parity
    # runtime); "pjit" = GSPMD engine consuming logical-axis annotations
    # (tensor parallelism over a mesh with a "model" axis); "pp" =
    # pipeline parallelism (GPipe/1F1B over a "pipe" mesh axis, LM tier);
    # "sp" = sequence parallelism (ring attention over a "seq" axis).
    engine: str = "dp"
    # Pipeline-engine knobs (ENGINE=pp): stage count (None → the mesh's
    # pipe axis, or all devices), microbatches per step, and the schedule
    # ("gpipe" fill-drain | "1f1b" one-forward-one-backward).
    pp_stages: Optional[int] = None
    pp_microbatches: int = 4
    pp_schedule: str = "gpipe"
    # Parameter-sharding rules for the pjit engine: "tp" (Megatron-style
    # over a 'model'/'expert' axis — the default), "fsdp" (ZeRO-3:
    # weights sharded over the data axis itself), "dp" (replicated).
    param_sharding: str = "tp"
    # BatchNorm semantics under ENGINE=pjit: by default the train step
    # batch-splits BN statistics per data shard (models/norm.py), which
    # equals the dp engine's (and the reference's) per-replica BN —
    # oracle-tested. This opt-in switches to GLOBAL-batch (sync-BN)
    # statistics instead.
    allow_sync_bn: bool = False

    # AOT warmup (env AOT_WARMUP): compile the train step before the
    # first batch flows, logging compile seconds + cost-analysis FLOPs
    # (training/warmup.py, which also places the persistent compile
    # cache — JAX_COMPILATION_CACHE_DIR, else <checkout>/.jax_cache).
    aot_warmup: bool = False

    # Bookkeeping
    seed: int = 42  # reference _SEED=42 (PyTorch :274-277, TF fake data :284)
    model_dir: Optional[str] = None  # AZ_BATCHAI_OUTPUT_MODEL equivalent
    checkpoint_every_epochs: int = 1
    # Step-granular checkpointing (env CHECKPOINT_EVERY_STEPS; 0 = epoch
    # boundaries only): save every k optimizer steps so a preemption
    # loses minutes, not an epoch. Checkpoint keys become global step
    # counts and resume re-enters mid-epoch, skipping the completed
    # batches (docs/ROBUSTNESS.md). Each due save materialises the state
    # (a deliberate host sync — durability traded against the sync-free
    # loop; the ≤1-sync/epoch contract applies at k=0).
    checkpoint_every_steps: int = 0
    # How many checkpoints the manager retains (env CHECKPOINT_KEEP;
    # orbax max_to_keep). The default 3 suits epoch keying; step-granular
    # elastic runs that roll back across resizes want a deeper history.
    checkpoint_keep: int = 3
    # env CHECKPOINT_ASYNC (default on): off makes every save durable
    # before it returns — what the deterministic fault oracles need so
    # "killed after step N" implies "checkpoint N committed".
    checkpoint_async: bool = True
    # Collective/compute overlap (env ASYNC_COLLECTIVES, default on):
    # the step builders tag the gradient all-reduces with the
    # training/overlap.py named scope so (a) the TPU async-collective
    # XLA flags (overlap.XLA_TPU_FLAGS) can split them into
    # all-reduce-start/done pairs that hide under the next layer's
    # matmul, and (b) analysis/hlo_audit.py can prove the tag/pairing at
    # HLO level. Off = untagged synchronous reductions (debug baseline).
    async_collectives: bool = True
    resume: bool = True  # env RESUME (the supervisor re-asserts it)
    # Elastic worlds (env ELASTIC; docs/ROBUSTNESS.md elasticity
    # section): this run may be a shrunken/regrown relaunch of a larger
    # world. The loop then ENFORCES the accum-rescale math contract at
    # resume — the checkpoint manifest's effective batch must equal
    # batch_size_per_device × batch shards on the new topology (the
    # supervisor holds it constant by rescaling BATCHSIZE and
    # ACCUM_STEPS together) — instead of merely warning.
    elastic: bool = False
    # Peak-LR world size override (env LR_WORLD_SIZE): the linear-
    # scaling rule normally tracks the resolved mesh's batch-shard
    # count, which would silently change the schedule when an elastic
    # relaunch runs on fewer devices. The supervisor pins it to the
    # FULL world so the trajectory is preserved across resizes.
    lr_world_size: Optional[int] = None
    # Synthetic-data sharding topology (env DATA_TOPOLOGY):
    #   "process" — each process draws a disjoint per-process stream
    #     (DistributedSampler parity; the historical default), which
    #     makes the delivered GLOBAL batch depend on the process count;
    #   "global"  — one process-count-independent global stream, each
    #     process slicing its contiguous share of every global batch.
    #     Required for elastic resizes to preserve the math
    #     (docs/DATA.md).
    data_topology: str = "process"
    # On-device non-finite-loss guard (env NONFINITE_ACTION): the metric
    # accumulator counts NaN/Inf-loss steps on device (zero extra host
    # syncs); at the epoch boundary "abort" raises faults.
    # NonFiniteLossError (exit 121, supervisor-non-retryable), "warn"
    # logs and continues, "off" ignores the counter.
    nonfinite_action: str = "abort"
    log_every_steps: int = 100  # PyTorch logs per-100-steps (:219-221)

    def model_kwargs(self) -> dict:
        """The ``get_model`` kwargs this config implies — one construction
        point shared by every front-end (keras/estimator/explicit)."""
        kw = dict(
            num_classes=self.num_classes,
            dtype=self.compute_dtype,
            attn_impl=self.attn_impl,
        )
        if self.moe_experts is not None:
            kw["moe_experts"] = self.moe_experts
        if self.remat:
            kw["remat"] = True
        return kw

    @property
    def data_parallel_width(self) -> int:
        """How many batch shards the topology THIS CONFIG DESCRIBES
        carries (for dataset sizing before any mesh exists). Under the
        dp/pjit engines every device is a batch slot (reference
        semantics; the pjit engine's TP axes still consume replicated
        batches). Under pp/sp only the ``replica``/``data`` axes shard
        the batch — pipe/seq partition the model/sequence instead.

        Callers holding a *resolved* mesh (which may have been passed
        explicitly and differ from the config) must use
        ``parallel.mesh.dp_size(mesh)`` instead — ``loop.fit`` and the
        front-ends do, for LR scaling and throughput accounting."""
        import jax

        n = jax.device_count()
        if self.engine not in ("pp", "sp"):
            return n
        if self.mesh_shape is not None:
            from distributeddeeplearning_tpu.parallel.mesh import MeshConfig

            shape = MeshConfig(
                axes=tuple(self.mesh_axes), shape=tuple(self.mesh_shape)
            ).resolve_shape(n)
            width = 1
            for axis, size in zip(self.mesh_axes, shape):
                if axis in ("replica", "data"):
                    width *= size
            return width
        # Engine-default meshes (loop.resolve_engine): pp puts PP_STAGES
        # (or everything) on pipe; sp puts everything on seq.
        if self.engine == "pp":
            return n // (self.pp_stages or n)
        return 1

    @property
    def global_batch_size(self) -> int:
        return self.batch_size_per_device * self.data_parallel_width

    def steps_per_epoch(self, data_length: Optional[int] = None) -> int:
        n = data_length if data_length is not None else self.fake_data_length
        return max(n // self.global_batch_size, 1)

    @classmethod
    def from_env(cls, env: Optional[Mapping[str, str]] = None, **overrides) -> "TrainConfig":
        """Build a config from the reference's env-var contract.

        Recognized vars (reference docstrings, e.g.
        ``imagenet_estimator_tf_horovod.py:1-9`` and ``:36-52``):
        ``DISTRIBUTED``, ``FAKE``, ``FAKE_DATA_LENGTH``, ``EPOCHS``,
        ``VALIDATION``, ``BATCHSIZE``, ``LR``, ``NUM_WORKERS``, ``MODEL``,
        ``SEED``, plus the Batch-AI-style path contract
        ``AZ_BATCHAI_INPUT_TRAIN``/``AZ_BATCHAI_INPUT_TEST``/
        ``AZ_BATCHAI_OUTPUT_MODEL`` and their plain spellings
        ``DATA_DIR``/``VAL_DATA_DIR``/``MODEL_DIR``.
        """
        e = _env(env)
        kw = {}
        if "DISTRIBUTED" in e:
            kw["distributed"] = _str_to_bool(e["DISTRIBUTED"])
        if "FAKE" in e:
            kw["fake"] = _str_to_bool(e["FAKE"])
        if "VALIDATION" in e:
            kw["validation"] = _str_to_bool(e["VALIDATION"])
        if "FAKE_DATA_LENGTH" in e:
            kw["fake_data_length"] = int(e["FAKE_DATA_LENGTH"])
        if "EPOCHS" in e:
            kw["epochs"] = int(e["EPOCHS"])  # fixes reference defect §2c.2
        if "BATCHSIZE" in e:
            kw["batch_size_per_device"] = int(e["BATCHSIZE"])
        if "LR" in e:
            kw["base_lr"] = float(e["LR"])
        if "NUM_WORKERS" in e:
            kw["num_workers"] = int(e["NUM_WORKERS"])
        if "WORKER_MODE" in e:
            kw["worker_mode"] = e["WORKER_MODE"]
        elif "MULTIPROCESSING" in e:  # reference Keras spelling (:44-46)
            kw["worker_mode"] = (
                "process" if _str_to_bool(e["MULTIPROCESSING"]) else "thread"
            )
        if "MODEL" in e:
            kw["model"] = e["MODEL"]
        if "COMPUTE_DTYPE" in e:
            kw["compute_dtype"] = e["COMPUTE_DTYPE"]
        if "ATTN_IMPL" in e:
            kw["attn_impl"] = e["ATTN_IMPL"]
        if "MOE_EXPERTS" in e:
            kw["moe_experts"] = int(e["MOE_EXPERTS"])
        if "OBJECTIVE" in e:
            kw["objective"] = e["OBJECTIVE"]
        if "DIFFUSION_BLOCK" in e:
            kw["diffusion_block"] = int(e["DIFFUSION_BLOCK"])
        if "DIFFUSION_T_MIN" in e:
            kw["diffusion_t_min"] = float(e["DIFFUSION_T_MIN"])
        if "MASK_TOKEN_ID" in e:
            kw["mask_token_id"] = int(e["MASK_TOKEN_ID"])
        if "REMAT" in e:
            kw["remat"] = _str_to_bool(e["REMAT"])
        if "DATA_FORMAT" in e:
            kw["data_format"] = e["DATA_FORMAT"]
        if "STREAM_SHUFFLE_BLOCK" in e:
            kw["stream_shuffle_block"] = int(e["STREAM_SHUFFLE_BLOCK"])
        if "PREFETCH_HOST_BATCHES" in e:
            kw["prefetch_host_batches"] = int(e["PREFETCH_HOST_BATCHES"])
        if "OPTIMIZER" in e:
            kw["optimizer"] = e["OPTIMIZER"]
        if "LR_SCHEDULE" in e:
            kw["lr_schedule"] = e["LR_SCHEDULE"]
        if "INPUT_STAGING" in e:
            kw["input_staging"] = e["INPUT_STAGING"]
        if "PREFETCH_BATCHES" in e:
            kw["prefetch_batches"] = int(e["PREFETCH_BATCHES"])
        if "GRAD_ACCUM_STEPS" in e:
            kw["grad_accum_steps"] = int(e["GRAD_ACCUM_STEPS"])
        if "ACCUM_STEPS" in e:
            kw["accum_steps"] = int(e["ACCUM_STEPS"])
        if "WEIGHT_DECAY" in e:
            kw["weight_decay"] = float(e["WEIGHT_DECAY"])
        if "DECOUPLED_WEIGHT_DECAY" in e:
            kw["decoupled_weight_decay"] = float(e["DECOUPLED_WEIGHT_DECAY"])
        if "ENGINE" in e:
            kw["engine"] = e["ENGINE"]
        if "PP_STAGES" in e:
            kw["pp_stages"] = int(e["PP_STAGES"])
        if "PP_MICROBATCHES" in e:
            kw["pp_microbatches"] = int(e["PP_MICROBATCHES"])
        if "PP_SCHEDULE" in e:
            kw["pp_schedule"] = e["PP_SCHEDULE"]
        if "PARAM_SHARDING" in e:
            kw["param_sharding"] = e["PARAM_SHARDING"]
        if "ALLOW_SYNC_BN" in e:
            kw["allow_sync_bn"] = _str_to_bool(e["ALLOW_SYNC_BN"])
        # Mesh topology (e.g. ENGINE=pjit MESH_AXES=data,model MESH_SHAPE=2,4)
        if "MESH_AXES" in e:
            kw["mesh_axes"] = tuple(
                a.strip() for a in e["MESH_AXES"].split(",") if a.strip()
            )
        if "MESH_SHAPE" in e:
            kw["mesh_shape"] = tuple(
                int(s) for s in e["MESH_SHAPE"].split(",") if s.strip()
            )
        if "AOT_WARMUP" in e:
            kw["aot_warmup"] = _str_to_bool(e["AOT_WARMUP"])
        if "SEED" in e:
            kw["seed"] = int(e["SEED"])
        # Robustness contract (docs/ROBUSTNESS.md): step-granular
        # checkpointing, save durability, resume toggle, NaN guard.
        if "CHECKPOINT_EVERY_STEPS" in e:
            kw["checkpoint_every_steps"] = int(e["CHECKPOINT_EVERY_STEPS"])
        if "CHECKPOINT_KEEP" in e:
            kw["checkpoint_keep"] = int(e["CHECKPOINT_KEEP"])
        if "CHECKPOINT_ASYNC" in e:
            kw["checkpoint_async"] = _str_to_bool(e["CHECKPOINT_ASYNC"])
        if "ASYNC_COLLECTIVES" in e:
            kw["async_collectives"] = _str_to_bool(e["ASYNC_COLLECTIVES"])
        if "RESUME" in e:
            kw["resume"] = _str_to_bool(e["RESUME"])
        if "NONFINITE_ACTION" in e:
            kw["nonfinite_action"] = e["NONFINITE_ACTION"]
        # Elastic-worlds contract (docs/ROBUSTNESS.md): the supervisor
        # exports these on every resized relaunch.
        if "ELASTIC" in e:
            kw["elastic"] = _str_to_bool(e["ELASTIC"])
        if "LR_WORLD_SIZE" in e:
            kw["lr_world_size"] = int(e["LR_WORLD_SIZE"])
        if "DATA_TOPOLOGY" in e:
            kw["data_topology"] = e["DATA_TOPOLOGY"]
        # Smoke-test knobs (not in the reference contract): shrink the
        # problem so the identical code path runs fast on CPU.
        if "IMAGE_SIZE" in e:
            kw["image_size"] = int(e["IMAGE_SIZE"])
        if "NUM_CLASSES" in e:
            kw["num_classes"] = int(e["NUM_CLASSES"])
        # Path contract: Batch AI spellings take precedence (same decoupling
        # the reference relies on — SURVEY.md §1 env-var boundary).
        data_dir = e.get("AZ_BATCHAI_INPUT_TRAIN") or e.get("DATA_DIR")
        val_dir = e.get("AZ_BATCHAI_INPUT_TEST") or e.get("VAL_DATA_DIR")
        model_dir = e.get("AZ_BATCHAI_OUTPUT_MODEL") or e.get("MODEL_DIR")
        if data_dir:
            kw["data_dir"] = data_dir
        if val_dir:
            kw["val_data_dir"] = val_dir
        if model_dir:
            kw["model_dir"] = model_dir
        kw.update(overrides)
        return cls(**kw)

    def replace(self, **kw) -> "TrainConfig":
        return dataclasses.replace(self, **kw)
