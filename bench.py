"""Benchmark harness — emits ONE JSON line with the canonical metric.

Measures the reference's canonical metric (SURVEY.md §6): ``Total
images/sec`` for ResNet50 training on seeded synthetic ImageNet-shaped
data (the reference's ``FAKE=True`` IO-free upper-bound protocol,
``01_CreateResources.ipynb`` cell 2) on the attached TPU.

A record is a statement about the TPU, so the harness refuses to emit
one from anything else: no accelerator means a non-zero exit and no
record (:func:`require_device`). The one exception is a CPU run asked
for by name (``JAX_PLATFORMS=cpu`` — the test tier): its record names
the device and its metric is renamed ``cpu_smoke.<metric>``, so a CPU
number can never be read as the device metric. The batch is the batch
that was asked for and an error is an error: nothing here retries at a
smaller size or on another platform.

``vs_baseline``: the reference publishes no numbers (BASELINE.md), so the
comparison point is the reference-era per-GPU estimate for its exact stack
(ResNet50 fp32, per-GPU batch 64, Horovod/V100): ~325 images/sec/GPU.
``vs_baseline`` = our images/sec *per chip* / 325.

Every train-protocol line also carries ``compile_sec`` (AOT compile time,
measured apart from the hot loop; the persistent compile cache —
``training/warmup.enable_compile_cache`` — makes re-runs deserialize
instead of recompiling) and ``host_sync_count`` (host materialisations
inside the measured region; exactly 1 — the closing fence — when the
loop is sync-free).

``--events`` (or ``OBS_DIR`` in the env) additionally routes every
record and the compile/measure spans through the structured event bus
(``distributeddeeplearning_tpu/obs/``): the one JSON line on stdout
stays the driver protocol, but the same record lands in the run's
``events-p0.jsonl`` where ``scripts/obs_report.py`` can merge it with
training-loop and launcher events.
"""

from __future__ import annotations

import json
import sys
import time

import jax
import numpy as np

REFERENCE_IMAGES_PER_SEC_PER_DEVICE = 325.0  # V100 fp32 ResNet50, reference stack
WARMUP_STEPS = 3
MEASURE_STEPS = 20

CPU_METRIC_PREFIX = "cpu_smoke."


def require_device() -> dict:
    """The device this run measures, as JAX reports it — or exit.

    Exits non-zero, before anything is compiled or printed, unless the
    platform is ``tpu`` or a CPU run was asked for by name
    (``JAX_PLATFORMS=cpu``). JAX itself falls back to the CPU with a
    warning when it finds no accelerator; a benchmark must not."""
    import os

    dev = jax.devices()[0]
    asked_cpu = os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu"
    if dev.platform != "tpu" and not (dev.platform == "cpu" and asked_cpu):
        raise SystemExit(
            f"bench.py: no TPU — JAX found platform {dev.platform!r} "
            f"({dev.device_kind}). Nothing was measured and no record is "
            "emitted. For a CPU smoke run ask for it by name: "
            "JAX_PLATFORMS=cpu."
        )
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
    }


def _emit_record(record: dict, device: dict) -> None:
    """THE output path for every protocol record: the canonical JSON
    line on stdout (the driver's contract) plus the same record as a
    ``bench_result`` event on the bus — ring-only when events mode is
    off, persisted when ``--events``/``OBS_DIR`` is on. Every record
    names its device; off the TPU the metric is renamed so it cannot
    pass for the device metric. Train-protocol records carrying
    accumulation fields also land as gauges so run reports can plot
    effective batch vs throughput."""
    record = {**record, "device": device}
    if device["platform"] != "tpu":
        record["metric"] = CPU_METRIC_PREFIX + record["metric"]
    print(json.dumps(record), flush=True)
    from distributeddeeplearning_tpu import obs

    bus = obs.get_bus()
    bus.point("bench_result", **record)
    if "accum_steps" in record:
        bus.gauge("bench.accum_steps", float(record["accum_steps"]))
    if "effective_batch" in record:
        bus.gauge("bench.effective_batch", float(record["effective_batch"]))
    bus.flush()


def _accum_steps_env() -> int:
    """ACCUM_STEPS for the bench protocols (in-step microbatched
    accumulation — the compiled step scans k microbatches per dispatch;
    activation memory ∝ microbatch). Resolved once so the JSON record
    can never disagree with the program that ran."""
    import os

    return max(int(os.environ.get("ACCUM_STEPS", "1")), 1)


def run_bench(
    per_device_batch: int,
    devices=None,
    profile_dir=None,
    *,
    model_name=None,
    depth: int = 50,
    image_size: int = 224,
):
    import jax.numpy as jnp
    import ml_dtypes
    import optax

    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.data.pipeline import shard_batch
    from distributeddeeplearning_tpu.models.resnet import ResNet
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh
    from distributeddeeplearning_tpu.training import (
        create_optimizer,
        create_train_state,
        make_train_step,
    )
    from distributeddeeplearning_tpu.training.train_step import replicate_state

    n_dev = devices if devices is not None else jax.device_count()
    global_batch = per_device_batch * n_dev
    cfg = TrainConfig(
        batch_size_per_device=per_device_batch, image_size=image_size,
        accum_steps=_accum_steps_env(),
    )
    # model_name (a vision-zoo registry name) measures that model under
    # the same protocol (BASELINE configs: vit_b16, efficientnet_b4);
    # default = the canonical ResNet50 line. All knobs are parsed once in
    # main() and passed through so the metric name can never desync from
    # the model actually benchmarked.
    if model_name:
        from distributeddeeplearning_tpu.models import get_model

        model = get_model(model_name, num_classes=1000, dtype=jnp.bfloat16)
    else:
        model = ResNet(depth=depth, num_classes=1000, dtype=jnp.bfloat16)
    mesh = data_parallel_mesh(n_dev)
    tx, _ = create_optimizer(cfg, steps_per_epoch=cfg.steps_per_epoch())
    state = replicate_state(create_train_state(model, cfg, tx), mesh)
    step = make_train_step(model, tx, mesh, cfg)

    from distributeddeeplearning_tpu.utils import hostsync

    rng = np.random.RandomState(42)
    host_batch = (
        # Staged bf16 (PROFILE.md): model compute dtype, half the transfer.
        rng.uniform(-1, 1, size=(global_batch, image_size, image_size, 3)).astype(
            ml_dtypes.bfloat16
        ),
        rng.randint(0, 1000, size=(global_batch,)).astype(np.int32),
    )
    batch = shard_batch(host_batch, mesh)

    # AOT compile, separately timed: compile cost must never smear into
    # the measured region, and with the persistent compilation cache
    # warm, re-runs deserialize instead of recompiling.
    from distributeddeeplearning_tpu import obs

    _, compile_sec = step.aot_compile(state, batch)  # emits the `compile` span

    for _ in range(WARMUP_STEPS):
        state, metrics = step(state, batch)
    # host readback: drains the device queue
    float(hostsync.device_get(metrics["loss"], label="bench_fence"))

    # The closing fence is a host readback of a value that depends on
    # every step in the chain (booked by the sync accountant).
    import contextlib

    prof = (
        jax.profiler.trace(profile_dir)
        if profile_dir
        else contextlib.nullcontext()
    )
    sync0 = hostsync.accountant().count
    with prof, obs.span("bench_measure", steps=MEASURE_STEPS):
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            state, metrics = step(state, batch)
        assert np.isfinite(
            float(hostsync.device_get(metrics["loss"], label="bench_fence"))
        )
        dt = time.perf_counter() - t0

    images_per_sec = MEASURE_STEPS * global_batch / dt
    perf = {
        "compile_sec": round(compile_sec, 3),
        # syncs inside the measured region: exactly the closing fence
        "host_sync_count": int(hostsync.accountant().count - sync0),
        "accum_steps": cfg.accum_steps,
        "effective_batch": global_batch,
    }
    return images_per_sec, n_dev, perf


def run_lm_bench(
    model_name: str,
    per_device_batch: int,
    seq_len: int,
    attn_impl: str,
    profile_dir=None,
):
    """Long-context tier protocol: tokens/sec for a decoder LM (dense or
    MoE) on synthetic tokens, DP over all attached devices. Selected via
    ``BENCH_MODEL=lm_small`` etc.; the default ResNet50 protocol (the
    driver's canonical line) is untouched."""
    import contextlib
    import os

    import jax.numpy as jnp

    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.data.pipeline import shard_batch
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh
    from distributeddeeplearning_tpu.training import (
        create_optimizer,
        create_train_state,
        make_train_step,
    )
    from distributeddeeplearning_tpu.training.train_step import replicate_state

    vocab = int(os.environ.get("BENCH_VOCAB", "32000"))
    n_dev = jax.device_count()
    global_batch = per_device_batch * n_dev
    cfg = TrainConfig(
        model=model_name,
        batch_size_per_device=per_device_batch,
        attn_impl=attn_impl,
        num_classes=vocab,
        accum_steps=_accum_steps_env(),
    )
    model = get_model(model_name, **cfg.model_kwargs(), max_seq_len=seq_len)
    mesh = data_parallel_mesh(n_dev)
    tx, _ = create_optimizer(cfg, steps_per_epoch=64)
    state = replicate_state(
        create_train_state(
            model, cfg, tx, input_shape=(1, seq_len), input_dtype=jnp.int32
        ),
        mesh,
    )
    from distributeddeeplearning_tpu.utils import hostsync

    step = make_train_step(model, tx, mesh, cfg)
    rng = np.random.RandomState(42)
    rows = rng.randint(0, vocab, size=(global_batch, seq_len + 1)).astype(np.int32)
    batch = shard_batch((rows[:, :-1], rows[:, 1:]), mesh)

    from distributeddeeplearning_tpu import obs

    _, compile_sec = step.aot_compile(state, batch)  # see run_bench

    for _ in range(WARMUP_STEPS):
        state, metrics = step(state, batch)
    # fence (see run_bench)
    float(hostsync.device_get(metrics["loss"], label="bench_fence"))

    prof = (
        jax.profiler.trace(profile_dir) if profile_dir else contextlib.nullcontext()
    )
    sync0 = hostsync.accountant().count
    with prof, obs.span("bench_measure", steps=MEASURE_STEPS):
        t0 = time.perf_counter()
        for _ in range(MEASURE_STEPS):
            state, metrics = step(state, batch)
        assert np.isfinite(
            float(hostsync.device_get(metrics["loss"], label="bench_fence"))
        )
        dt = time.perf_counter() - t0
    tokens_per_sec = MEASURE_STEPS * global_batch * seq_len / dt
    perf = {
        "compile_sec": round(compile_sec, 3),
        "host_sync_count": int(hostsync.accountant().count - sync0),
        "accum_steps": cfg.accum_steps,
        "effective_batch": global_batch,
    }
    return tokens_per_sec, n_dev, perf


def run_decode_bench(model_name: str, batch: int, prompt_len: int, new_tokens: int):
    """Inference tier: generated tokens/sec through the KV-cache sampler
    (``inference.generate``) — selected via ``BENCH_DECODE=1``."""
    import os

    import flax.linen as nn
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.inference import generate
    from distributeddeeplearning_tpu.models import get_model

    vocab = int(os.environ.get("BENCH_VOCAB", "32000"))
    max_len = prompt_len + new_tokens
    model = get_model(model_name, num_classes=vocab, max_seq_len=max_len)
    variables = jax.jit(model.init, static_argnames=("train",))(
        jax.random.PRNGKey(0), jnp.zeros((batch, max_len), jnp.int32),
        train=False,
    )
    params = nn.unbox(variables["params"])
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, vocab, size=(batch, prompt_len)).astype(np.int32)
    kw = dict(max_new_tokens=new_tokens, temperature=0.8, top_k=40,
              rng=jax.random.PRNGKey(1))
    out = generate(model, params, prompt, **kw)  # compile + warmup
    int(np.asarray(out)[0, -1])
    t0 = time.perf_counter()
    reps = 3
    for i in range(reps):
        out = generate(model, params, prompt,
                       **{**kw, "rng": jax.random.PRNGKey(2 + i)})
    int(np.asarray(out)[0, -1])  # fence
    dt = time.perf_counter() - t0
    return reps * batch * new_tokens / dt


def decode_main(device: dict) -> int:
    import os

    model_name = os.environ.get("BENCH_MODEL", "lm_small")
    batch = int(os.environ.get("BENCH_BATCH", "8"))
    prompt_len = int(os.environ.get("BENCH_PROMPT_LEN", "128"))
    new_tokens = int(os.environ.get("BENCH_NEW_TOKENS", "128"))
    tps = run_decode_bench(model_name, batch, prompt_len, new_tokens)
    _emit_record({
        "metric": f"{model_name}_decode_tokens_per_sec",
        "value": round(tps, 1),
        "unit": "tokens/sec",
        "vs_baseline": 0.0,  # the reference has no inference path
        "detail": {
            "batch": batch, "prompt_len": prompt_len,
            "new_tokens": new_tokens,
        },
    }, device)
    return 0


def lm_main(device: dict) -> int:
    import os

    model_name = os.environ["BENCH_MODEL"]
    seq_len = int(os.environ.get("BENCH_SEQ_LEN", "1024"))
    attn_impl = os.environ.get(
        "ATTN_IMPL", "pallas" if device["platform"] == "tpu" else "xla"
    )
    per_device_batch = int(os.environ.get("BENCH_BATCH", "8"))
    profile_dir = os.environ.get("BENCH_PROFILE") or None
    tps, n_dev, perf = run_lm_bench(
        model_name, per_device_batch, seq_len, attn_impl, profile_dir
    )
    _emit_record(
        {
            "metric": f"{model_name}_synthetic_train_tokens_per_sec",
            "value": round(tps, 1),
            # no reference point: the reference is vision-only
            "unit": "tokens/sec",
            "vs_baseline": 0.0,
            "compile_sec": perf["compile_sec"],
            "host_sync_count": perf["host_sync_count"],
            "accum_steps": perf["accum_steps"],
            "effective_batch": perf["effective_batch"],
            "detail": {
                "devices": n_dev,
                "per_device_batch": per_device_batch,
                "seq_len": seq_len,
                "attn_impl": attn_impl,
                "tokens_per_sec_per_device": round(tps / n_dev, 1),
            },
        },
        device,
    )
    return 0


def _vision_protocol():
    """Resolve the vision-mode knobs from env ONCE — the metric name
    must be derived in exactly one place (ADVICE r4)."""
    import os

    depth = int(os.environ.get("BENCH_DEPTH", "50"))
    image_size = int(os.environ.get("BENCH_IMAGE_SIZE", "224"))
    vision_model = os.environ.get("BENCH_MODEL") or None
    if vision_model == "resnet50":
        # the canonical protocol by its registry name: keep the canonical
        # metric name + vs_baseline instead of demoting the run
        vision_model = None
    canonical = depth == 50 and image_size == 224 and not vision_model
    if canonical:
        metric = "resnet50_synthetic_train_images_per_sec"
    elif vision_model:
        metric = f"{vision_model}_{image_size}px_images_per_sec"
    else:
        metric = f"resnet{depth}_{image_size}px_smoke_images_per_sec"
    return vision_model, depth, image_size, canonical, metric


def main():
    import os

    if "--events" in sys.argv[1:] or os.environ.get("OBS_DIR"):
        # Route the bus to OBS_DIR (or a fresh runs/bench-* dir): the
        # spans and the result record below then persist as JSONL.
        from distributeddeeplearning_tpu import obs

        if not os.environ.get("OBS_DIR"):
            os.environ["OBS_DIR"] = os.path.join(
                "runs", f"bench-{int(time.time())}"
            )
        obs.configure_from_env()
    device = require_device()
    from distributeddeeplearning_tpu.training.warmup import (
        enable_compile_cache,
    )

    enable_compile_cache()
    if os.environ.get("BENCH_DECODE", "") == "1":
        return decode_main(device)
    if os.environ.get("BENCH_MODEL", "").startswith("lm_"):
        return lm_main(device)

    profile_dir = os.environ.get("BENCH_PROFILE") or None
    scaling = os.environ.get("BENCH_SCALING", "") == "1"
    per_device_batch = int(os.environ.get("BENCH_BATCH", "256"))
    vision_model, depth, image_size, canonical, metric = _vision_protocol()
    bench_kw = dict(model_name=vision_model, depth=depth, image_size=image_size)
    ips, n_dev, perf = run_bench(
        per_device_batch, profile_dir=profile_dir, **bench_kw
    )
    per_chip = ips / n_dev
    detail = {
        "devices": n_dev,
        # world_size mirrors devices for bench_trend's world_change
        # protocol skip: an elastic-era resize is a new baseline, not a
        # regression (scripts/bench_trend.py)
        "world_size": n_dev,
        "per_device_batch": per_device_batch,
        "images_per_sec_per_device": round(per_chip, 1),
        "image_size": image_size,
    }
    if vision_model:
        # no baseline field: the V100 number is a ResNet50 reference
        # and means nothing for other architectures
        detail["model"] = vision_model
    else:
        detail["model_depth"] = depth
        detail["baseline_images_per_sec_per_device"] = (
            REFERENCE_IMAGES_PER_SEC_PER_DEVICE
        )
        if not canonical:
            detail["smoke_overrides"] = True
    if scaling and n_dev > 1:
        # Scaling-efficiency path (BASELINE >90% target, 8→64):
        # images/sec/chip at 1 device vs all attached devices.
        ips1, _, _ = run_bench(per_device_batch, devices=1, **bench_kw)
        detail["images_per_sec_1_device"] = round(ips1, 1)
        detail["scaling_efficiency"] = round(per_chip / ips1, 4)
    _emit_record(
        {
            "metric": metric,
            "value": round(ips, 1),
            "unit": "images/sec",
            # vs_baseline only means something for the canonical
            # ResNet50@224 protocol
            "vs_baseline": round(
                per_chip / REFERENCE_IMAGES_PER_SEC_PER_DEVICE, 3
            )
            if canonical
            else 0.0,
            "compile_sec": perf["compile_sec"],
            "host_sync_count": perf["host_sync_count"],
            "accum_steps": perf["accum_steps"],
            "effective_batch": perf["effective_batch"],
            "detail": detail,
        },
        device,
    )
    return 0


if __name__ == "__main__":
    sys.exit(main())
