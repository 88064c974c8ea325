#!/usr/bin/env python3
"""The quickest proof that the system still starts on the chip.

Drives the main paths once on the attached TPU, through the entry points
a user calls, with random weights made from a seed: the trainer takes a
few steps at full width (ResNet50, a long-context LM on the flash kernel,
ViT on the packed kernel) and the server answers a few requests (the
largest LM's widths at a cut depth; dense, paged and fused-kernel
engines). Each phase checks what came out by the repo's own means; a
phase that fails fails the run — nothing here catches an exception.

One process, the only one that touches JAX. With no TPU it exits
non-zero in seconds and prints nothing that looks like a result. The
last two lines of standard output are the summary and the verdict::

    chip_smoke: summary {"ok": true, "device": {...}, "phases": {...},
                         ..., "claim": null}
    {"ok": true, "device": {"platform": "tpu", "kind": ..., "count": 1}}

The verdict — the last line — is one JSON object with exactly those keys,
the device as JAX reports it; the driver's chip check reads it. Every
number in the summary is a start-up check printed for the eye (compile
and run seconds per phase, cache hits), not a benchmark: nothing is
claimed.
"""

from __future__ import annotations

import contextlib
import json
import logging
import sys
import time

# The workloads, at the full width of models the repo supports. (The
# builder's CPU dry run of this script's logic swaps in tiny ones.)
RESNET, RESNET_BATCH, STEPS_RESNET = "resnet50", 256, 8
VIT, VIT_BATCH = "vit_b16", 64
IMAGE_SIZE = 224
LM_TRAIN, SEQ_LEN = "lm_small", 8192
STEPS_KERNEL = 3  # the LM-flash, ViT-packed and four-chip phases
VOCAB = 32_000
SERVE_MODEL = "lm_large"
# lm_large's widths (1536 hidden, 16 heads of 96, 6144 MLP, 32k vocab) at
# a cut depth: serving compile time is per program, not per layer, and
# the smoke builds four engines inside its time limit.
SERVE_DEPTH = 4
SERVE_NEW_TOKENS = 64
SERVE_PROMPT_LENS = (64, 128, 200, 333, 512, 700, 900, 1024)
# Fused-vs-XLA decode logits, as a share of the largest logit: the two
# paths round bf16 activations at different points and a logit is stored
# in bf16, whose neighbours are 2**-8 to 2**-7 of its value apart —
# allow four such steps.
FUSED_LOGIT_TOL = 2.0 ** -5
# Loss of the four-chip run against one chip accumulating the same
# global batch: the tolerance tests/test_train_step.py uses for its
# sharded-vs-single-device loss.
FOUR_CHIP_LOSS_RTOL = 1e-4
TPU_CUSTOM_CALL = "tpu_custom_call"


def _device_or_exit() -> dict:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(
            f"chip_smoke: no TPU — JAX found platform {dev.platform!r} "
            f"({dev.device_kind}); nothing was run.",
            file=sys.stderr,
        )
        raise SystemExit(1)
    return {
        "platform": dev.platform,
        "kind": dev.device_kind,
        "count": jax.device_count(),
    }


@contextlib.contextmanager
def _logged_losses():
    """The losses ``explicit.train_epoch`` logs (``step %d loss=%.4f …``)
    — the explicit front-end's only report of them — as floats."""
    losses = []

    class Capture(logging.Handler):
        def emit(self, record):
            if "loss=" in str(record.msg):
                losses.append(float(record.args[1]))

    from distributeddeeplearning_tpu.utils.logging import get_logger

    logger = get_logger().logger  # configured on first use, so ask first
    handler = Capture()
    logger.addHandler(handler)
    try:
        yield losses
    finally:
        logger.removeHandler(handler)


def _train(model, config, data, *, mesh=None, input_shape=None,
           input_dtype=None, log_every=None) -> dict:
    """What ``examples/imagenet_explicit_tpu.py`` and
    ``examples/lm_synthetic_tpu.py`` do after building model and data:
    ``explicit.setup`` then ``explicit.train_epoch`` — with the step
    compiled ahead against the first staged batch so compile and step
    seconds are reported apart."""
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.data.pipeline import prefetch_to_device
    from distributeddeeplearning_tpu.frontends import explicit
    from distributeddeeplearning_tpu.utils import hostsync

    steps = data.steps_per_epoch
    pieces, state = explicit.setup(
        model, config, mesh=mesh, steps_per_epoch=steps,
        input_shape=input_shape, input_dtype=input_dtype,
    )
    batch = next(iter(prefetch_to_device(
        data.epoch(0), pieces.mesh, size=0, sharding=pieces.batch_sharding
    )))
    compiled, compile_sec = pieces.train_step.aot_compile(state, batch)
    hlo = compiled.as_text()

    syncs0 = hostsync.accountant().count
    with _logged_losses() as losses, hostsync.track():
        t0 = time.perf_counter()
        state = explicit.train_epoch(
            pieces, state, data, 0, log_every=log_every or steps
        )
        jax.block_until_ready(state.step)
        run_sec = time.perf_counter() - t0
    host_syncs = hostsync.accountant().count - syncs0
    memory = [d.memory_stats() for d in pieces.mesh.devices.flat]

    assert int(state.step) == steps, (int(state.step), steps)
    assert losses and np.all(np.isfinite(losses)), losses
    return {
        "steps": steps,
        "compile_sec": round(compile_sec, 2),
        "run_sec": round(run_sec, 3),
        "losses": [round(x, 6) for x in losses],
        "host_syncs": host_syncs,
        # per device of the mesh, with the trained state still resident
        # (the runtime's counters do not see a program's temporaries)
        "hbm_in_use_gib": [
            round(m["bytes_in_use"] / 2**30, 2) for m in memory
        ],
        "_bytes_in_use": [m["bytes_in_use"] for m in memory],
        "_batch": batch,
        "_hlo": hlo,
    }


def _peak_rss_gib() -> float:
    import resource

    return resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20


def _public(result: dict) -> dict:
    return {k: v for k, v in result.items() if not k.startswith("_")}


def _image_config(model: str, batch: int, steps: int, **kw):
    import jax

    from distributeddeeplearning_tpu.config import TrainConfig

    return TrainConfig(
        model=model, fake=True, epochs=1, batch_size_per_device=batch,
        image_size=IMAGE_SIZE,
        fake_data_length=steps * batch * jax.device_count(), **kw,
    )


def train_resnet50() -> dict:
    """The main path at full width: ResNet50, 224 px, bf16 compute,
    per-device batch 256, synthetic data."""
    from distributeddeeplearning_tpu.data import make_dataset
    from distributeddeeplearning_tpu.models import get_model

    config = _image_config(RESNET, RESNET_BATCH, STEPS_RESNET)
    model = get_model(config.model, **config.model_kwargs())
    rss0 = _peak_rss_gib()
    t0 = time.perf_counter()
    data = make_dataset(config, train=True)
    pool_sec = time.perf_counter() - t0
    # host memory the synthetic pool cost at its peak (20 physical
    # batches staged in bf16, filled a slab at a time)
    pool_gib = _peak_rss_gib() - rss0
    out = _train(model, config, data)
    assert out["host_syncs"] <= 1, out["host_syncs"]
    out["pool_sec"] = round(pool_sec, 1)
    out["pool_host_peak_gib"] = round(pool_gib, 2)
    # for the eye, against the old ~2,500 img/s control — not a metric
    out["images_per_sec"] = round(
        out["steps"] * config.global_batch_size / out["run_sec"], 1
    )
    return _public(out)


def train_lm_flash() -> dict:
    """lm_small at T=8,192 with no ``ATTN_IMPL``: the default ``"auto"``
    resolves to the flash kernel here (``ops/attention.resolve_impl``), its
    forward and both backward kernels compiled by Mosaic."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.data.synthetic import SyntheticTokenDataset
    from distributeddeeplearning_tpu.models import get_model

    config = TrainConfig(
        model=LM_TRAIN, num_classes=VOCAB, batch_size_per_device=1, epochs=1,
        fake_data_length=STEPS_KERNEL * jax.device_count(),
    )
    model = get_model(
        config.model, **config.model_kwargs(), max_seq_len=SEQ_LEN
    )
    data = SyntheticTokenDataset(
        length=config.fake_data_length,
        global_batch_size=config.global_batch_size,
        seq_len=SEQ_LEN, vocab_size=VOCAB, seed=config.seed,
    )
    out = _train(
        model, config, data, input_shape=(1, SEQ_LEN), input_dtype=jnp.int32
    )
    # an interpreted kernel leaves no custom call behind
    out["tpu_custom_calls"] = out["_hlo"].count(TPU_CUSTOM_CALL)
    assert out["tpu_custom_calls"] > 0
    return _public(out)


def train_vit_packed() -> dict:
    """ViT-B/16 with the default ``attn_impl="auto"``: on a TPU
    ``ops/attention.resolve_impl`` picks the packed kernel (T=197, d=64,
    ragged last block), which no CPU test ever compiles."""
    from distributeddeeplearning_tpu.data import make_dataset
    from distributeddeeplearning_tpu.models import get_model

    config = _image_config(VIT, VIT_BATCH, STEPS_KERNEL)
    model = get_model(config.model, **config.model_kwargs())
    out = _train(model, config, make_dataset(config, train=True))
    out["tpu_custom_calls"] = out["_hlo"].count(TPU_CUSTOM_CALL)
    assert out["tpu_custom_calls"] > 0
    return _public(out)


def _serving_model():
    import flax.linen as nn
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models import get_model

    model = get_model(SERVE_MODEL, num_classes=VOCAB, depth=SERVE_DEPTH)
    variables = jax.jit(model.init, static_argnames=("train",))(
        jax.random.PRNGKey(0),
        jnp.zeros((1, model.max_seq_len), jnp.int32),
        train=False,
    )
    return model, nn.unbox(variables["params"])


def _serve(model, params, config, prompt_lens, new_tokens) -> dict:
    """``Server.build`` + ``engine.warmup()``, then the requests through
    ``submit`` / ``drain``."""
    import numpy as np

    from distributeddeeplearning_tpu.serving import Request, Server

    t0 = time.perf_counter()
    server = Server.build(model, params, config)
    engine = server.engine
    engine.warmup()
    compile_sec = time.perf_counter() - t0
    assert engine.compile_count == engine.programs_expected

    rng = np.random.RandomState(7)
    prompts = [
        rng.randint(0, VOCAB, size=(n,)).astype(np.int32) for n in prompt_lens
    ]
    t0 = time.perf_counter()
    handles = [
        server.submit(Request(prompt=p, max_new_tokens=new_tokens))
        for p in prompts
    ]
    server.drain()
    run_sec = time.perf_counter() - t0
    server.close()

    for h in handles:
        assert h.status == "done", (h.id, h.status)
        assert len(h.new_tokens) == new_tokens, (h.id, len(h.new_tokens))
        assert all(0 <= t < VOCAB for t in h.new_tokens), h.id
    # the closed program set stayed closed while serving
    assert engine.compile_count == engine.programs_expected
    return {
        "programs": engine.compile_count,
        "compile_sec": round(compile_sec, 2),
        "run_sec": round(run_sec, 3),
        "requests": len(handles),
        "_streams": [list(h.new_tokens) for h in handles],
        "_prompts": prompts,
        "_engine": engine,
    }


def serve_lm() -> dict:
    """The default ``ServeConfig`` and the paged layout: 8 requests of
    64–1,024 prompt tokens, 64 new tokens each, greedy."""
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.inference import generate
    from distributeddeeplearning_tpu.serving import ServeConfig

    model, params = _serving_model()
    out = {}
    for name, config in (
        ("dense", ServeConfig()),
        ("paged", ServeConfig(kv_layout="paged")),
    ):
        out[name] = _serve(
            model, params, config, SERVE_PROMPT_LENS, SERVE_NEW_TOKENS
        )
    dense, paged = out["dense"], out["paged"]

    # Reference on a small input: the plain forward's logits for the
    # shortest prompt are finite, of the expected shape, and pick the
    # token the server emitted first.
    prompt = dense["_prompts"][0]
    logits = np.asarray(
        jax.jit(lambda p, t: model.apply({"params": p}, t, train=False))(
            params, prompt[None]
        ),
        np.float32,
    )
    assert logits.shape == (1, len(prompt), VOCAB), logits.shape
    assert np.all(np.isfinite(logits))
    # Reported, not asserted: the repo's "bitwise-equal to sequential
    # generate" was shown on the CPU; with random weights the largest
    # logit can change on rounding between two programs.
    reference = np.asarray(generate(
        model, params, prompt[None], max_new_tokens=SERVE_NEW_TOKENS,
        temperature=0.0,
    ))[0, len(prompt):]
    match = {
        "first_token_is_forward_argmax": bool(
            int(np.argmax(logits[0, -1])) == dense["_streams"][0][0]
        ),
        "dense_vs_generate": f"{int(np.sum(reference == dense['_streams'][0]))}/{SERVE_NEW_TOKENS}",
        "paged_vs_generate": f"{int(np.sum(reference == paged['_streams'][0]))}/{SERVE_NEW_TOKENS}",
        "dense_vs_paged_streams_equal": sum(
            a == b for a, b in zip(dense["_streams"], paged["_streams"])
        ),
    }
    return {
        "dense": _public(dense), "paged": _public(paged),
        "token_match": match,
        "compile_sec": round(dense["compile_sec"] + paged["compile_sec"], 2),
        "run_sec": round(dense["run_sec"] + paged["run_sec"], 3),
    }


def _decode_step_logits(model, params, kv_dtype: str):
    """One decode step of the serving decode model, XLA and fused, from
    the same paged cache: [slots, vocab] logits each."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.inference import (
        decode_cache_shapes,
        decode_variant,
    )

    slots, max_len, block = 8, 256, 16
    per_slot = max_len // block
    kw = dict(
        paged_blocks=slots * per_slot + 1, paged_block_size=block,
        kv_dtype=kv_dtype,
    )
    xla = decode_variant(model, **kw)
    fused = decode_variant(model, decode_kernel="fused", **kw)
    # block 0 is the trash block; slot s owns the next per_slot blocks
    table = jnp.asarray(
        1 + np.arange(slots * per_slot).reshape(slots, per_slot), jnp.int32
    )

    def routed(cache, positions):
        """Feed per-row positions and the block table, as the serving
        engine does before every call."""
        def leaf(path, x):
            name = path[-1].key
            if name in ("cache_index", "pos_index"):
                return positions
            return table if name == "block_table" else x
        return jax.tree_util.tree_map_with_path(leaf, cache)

    @jax.jit
    def run(params, tokens, fill_pos, step_tokens, step_pos):
        cache = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype),
            decode_cache_shapes(xla, slots, max_len),
        )
        _, filled = xla.apply(
            {"params": params, "cache": routed(cache, fill_pos)},
            tokens, train=False, mutable=["cache"],
        )
        step = routed(filled["cache"], step_pos)
        return tuple(
            m.apply(
                {"params": params, "cache": step},
                step_tokens[:, None], train=False, mutable=["cache"],
            )[0][:, -1]
            for m in (xla, fused)
        )

    rng = np.random.RandomState(3)
    filled = 100
    tokens = rng.randint(0, VOCAB, size=(slots, filled)).astype(np.int32)
    # every row decodes at its own depth, across block boundaries
    step_pos = (filled - 7 * np.arange(slots)).astype(np.int32)
    args = (
        params, tokens, np.zeros(slots, np.int32),
        rng.randint(0, VOCAB, size=(slots,)).astype(np.int32), step_pos,
    )
    lowered = run.lower(*args)
    assert TPU_CUSTOM_CALL in lowered.as_text()
    return tuple(np.asarray(x, np.float32) for x in lowered.compile()(*args))


def decode_kernel_fused() -> dict:
    """``decode_kernel="fused"`` on the paged engine, KV in bf16 and in
    int8: Mosaic compiles ``ops/pallas/paged_decode.py`` inside the
    engine's decode program, requests complete, and the kernel's logits
    agree with the XLA decode path on the same cache."""
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.serving import ServeConfig

    model, params = _serving_model()
    out = {"compile_sec": 0.0, "run_sec": 0.0}
    for kv_dtype in ("bf16", "int8"):
        # one bucket: the prefill ladder is the XLA path on either kernel
        served = _serve(
            model, params,
            ServeConfig(
                kv_layout="paged", decode_kernel="fused", kv_dtype=kv_dtype,
                buckets=(128,),
            ),
            (24, 48, 64, 77, 96, 100, 120, 128), 32,
        )
        decode = next(
            ps for ps in served["_engine"].program_specs()
            if ps.name == "decode"
        )
        lowered = jax.jit(
            decode.fn, donate_argnums=decode.donate_argnums
        ).lower(*decode.example_args).as_text()
        assert TPU_CUSTOM_CALL in lowered, "decode program has no kernel"

        t0 = time.perf_counter()
        ref, got = _decode_step_logits(model, params, kv_dtype)
        check_sec = time.perf_counter() - t0
        assert np.all(np.isfinite(got))
        err = float(np.abs(got - ref).max() / np.abs(ref).max())
        assert err <= FUSED_LOGIT_TOL, (kv_dtype, err, FUSED_LOGIT_TOL)
        out[kv_dtype] = {
            **_public(served),
            "logit_err_rel_to_max": round(err, 6),
            "argmax_agree": f"{int(np.sum(ref.argmax(-1) == got.argmax(-1)))}/{len(ref)}",
            "check_sec": round(check_sec, 2),
        }
        out["compile_sec"] = round(out["compile_sec"] + served["compile_sec"], 2)
        out["run_sec"] = round(out["run_sec"] + served["run_sec"], 3)
    out["logit_tol"] = FUSED_LOGIT_TOL
    return out


def train_resnet50_4chip() -> dict:
    """The first phase on ``data_parallel_mesh(4)``, global batch 1,024:
    the batch and the memory sit on four chips, the step all-reduces,
    and the losses match one chip accumulating the same global batches."""
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.data import make_dataset
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh

    assert jax.device_count() == 4, jax.devices()
    # one learning rate for both worlds (the default scales it, and its
    # warm-up, by the mesh size), high enough that a wrong gradient
    # reduction would show in the next loss
    lr = dict(base_lr=0.1, scale_lr_by_world_size=False, warmup_epochs=0)
    config = _image_config(RESNET, RESNET_BATCH, STEPS_KERNEL, **lr)
    model = get_model(config.model, **config.model_kwargs())
    data = make_dataset(config, train=True)
    order = [
        (d.id, getattr(d, "coords", None)) for d in jax.devices()
    ]

    four = _train(
        model, config, data, mesh=data_parallel_mesh(4), log_every=1
    )
    shard_devices = {s.device for s in four["_batch"][0].addressable_shards}
    assert len(shard_devices) == 4, shard_devices
    assert "all-reduce" in four["_hlo"]
    # of one order on all four chips, not all on the first
    in_use = four["_bytes_in_use"]
    assert min(in_use) > 0 and max(in_use) <= 2 * min(in_use), in_use
    four = _public(four)

    one = _public(_train(
        model, config.replace(accum_steps=4), data,
        mesh=data_parallel_mesh(1), log_every=1,
    ))
    np.testing.assert_allclose(
        four["losses"], one["losses"], rtol=FOUR_CHIP_LOSS_RTOL
    )
    return {
        "four_chip": four, "one_chip_accum4": one,
        "device_order": order,
        "global_batch": config.global_batch_size,
        "compile_sec": round(four["compile_sec"] + one["compile_sec"], 2),
        "run_sec": round(four["run_sec"] + one["run_sec"], 3),
    }


PHASES = (
    train_resnet50,
    train_lm_flash,
    train_vit_packed,
    serve_lm,
    decode_kernel_fused,
    train_resnet50_4chip,
)


def run(names=None) -> dict:
    """Run the phases (all, or the named ones) and return the summary."""
    device = _device_or_exit()
    import jax

    from distributeddeeplearning_tpu import native
    from distributeddeeplearning_tpu.training.warmup import (
        cache_stats,
        enable_compile_cache,
    )

    print(
        f"chip_smoke: platform={device['platform']} "
        f"device_kind={device['kind']!r} count={device['count']}",
        flush=True,
    )
    cache_dir = enable_compile_cache()
    t_start = time.perf_counter()
    phases = {}
    for phase in PHASES:
        if names is not None and phase.__name__ not in names:
            continue
        if phase is train_resnet50_4chip and jax.device_count() < 4:
            continue
        t0 = time.perf_counter()
        result = phase()
        result["wall_sec"] = round(time.perf_counter() - t0, 1)
        phases[phase.__name__] = result
        print(f"chip_smoke: {phase.__name__} ok {json.dumps(result)}",
              flush=True)
    hits, misses = cache_stats()
    return {
        "ok": True,
        "device": device,
        "phases": phases,
        "compile_cache": {"dir": cache_dir, "hits": hits, "misses": misses},
        # which implementation filled the synthetic pool
        "native_io": "native" if native.native_available() else "python",
        "wall_sec": round(time.perf_counter() - t_start, 1),
        "claim": None,
    }


def main() -> int:
    summary = run()
    print(f"chip_smoke: summary {json.dumps(summary)}", flush=True)
    # the verdict: exactly these keys, and nothing after it
    print(json.dumps({"ok": summary["ok"], "device": summary["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
