"""Continuous-batching serving benchmark — Poisson load vs sequential,
dense vs paged KV pool at a fixed byte budget.

The serving tier's certifiable protocol (BASELINE.md style, one JSON
line on stdout). A seeded Poisson arrival stream of mixed-length
requests is served by up to three configurations:

* **sequential baseline**: one request at a time through
  ``inference.generate`` (each distinct shape warmed first, so the
  comparison is pure steady-state throughput);
* **continuous batching** on the selected KV layout
  (``SERVE_KV_LAYOUT=dense|paged``): the same requests submitted to
  ``serving.Server`` on their arrival schedule, drained to completion;
* **compare** (``SERVE_KV_LAYOUT=compare``): dense AND paged engines at
  the SAME pool-byte budget — the dense pool holds
  ``SERVE_POOL_SLOT_BUDGET`` full ``max_len`` rows; the paged pool gets
  exactly those bytes as blocks (`budget_tokens / block_size` blocks +
  the trash block) but serves ``SERVE_SLOTS`` decode rows. On the
  long-tail length mix (``SERVE_PROFILE=longtail``) most requests need
  a fraction of ``max_len``, so block-granular admission sustains a
  multiple of the dense concurrency from the same HBM. The record
  carries both runs' throughput/concurrency and the script exits
  non-zero unless paged reaches ≥2× dense peak concurrency (or ≥1.5×
  tokens/sec) with bitwise per-request parity and zero mid-measure
  recompiles on BOTH engines.
* **quantization compare** (``SERVE_KV_DTYPE=int8|fp8`` and/or
  ``SERVE_WEIGHT_DTYPE=int8|fp8`` — docs/SERVING.md): the bf16
  (native) engine at ``SERVE_POOL_SLOT_BUDGET`` dense slots vs the
  quantized engine given the SAME KV-pool bytes — the 1-byte store
  tiers + scales pack ~2–3.5× the slots into the budget, so the quantized engine's capacity (and, with
  the per-step cost amortized over more co-resident requests, its
  tokens/sec) certifies the byte win. The load runs GREEDY; exact
  parity is mathematically unavailable under quantization (one flipped
  argmax re-conditions the whole suffix), so quality is gated by a
  **teacher-forced greedy token-match-rate oracle**: every reference
  stream is replayed through the quantized engine with the context
  forced to the bf16 tokens (``SlotEngine.force_token``) and per-step
  agreement must reach ``SERVE_QUANT_MATCH_MIN`` (0.95). The
  free-running positional match and the weight-quantization logit
  error are reported alongside, unGated (documented like the accum ULP
  note). Exits non-zero unless match ≥ threshold AND quantized
  tokens/sec ≥ bf16 with zero mid-measure recompiles and closed
  program sets on BOTH engines.
* **speculative compare** (``SERVE_SPEC_K > 0`` — docs/SERVING.md):
  plain greedy engine vs the speculative engine (``SERVE_SPEC_DRAFT``
  int8 self-draft or n-gram prompt lookup) on the same seeded greedy
  load. Speculation in the greedy regime is **lossless by
  construction**, so parity is gated bitwise; the script also gates
  speculative tokens/sec ≥ ``SERVE_SPEC_MIN_SPEEDUP`` (1.4) × the
  baseline, zero mid-measure recompiles, and both program sets closed
  at their static counts (the speculative set is enlarged — verify +
  draft programs — but still closed). Accept-rate p50/mean and
  draft/verify time are reported.

Env knobs (defaults in parentheses): ``SERVE_SLOTS`` (8),
``SERVE_BUCKETS`` ("8,16"; compare/longtail default covers the long
tail), ``SERVE_REQUESTS`` (32), ``SERVE_MAX_NEW`` (16),
``SERVE_RATE_RPS`` (200 — Poisson arrival rate; 0 = closed backlog,
all at t=0), ``SERVE_SEED`` (0), ``SERVE_PROFILE`` (mixed | longtail | disagg),
``SERVE_KV_LAYOUT`` (dense | paged | compare), ``SERVE_BLOCK_SIZE``
(16), ``SERVE_NUM_BLOCKS`` (0 = dense-equivalent),
``SERVE_POOL_SLOT_BUDGET`` (4 — the fixed byte budget, in dense slots),
``SERVE_KV_DTYPE`` / ``SERVE_WEIGHT_DTYPE`` (bf16 — int8/fp8 selects
the quantization compare; fp8 falls back to int8 off-TPU),
``SERVE_DECODE_KERNEL`` (xla — fused selects the Pallas paged-decode
kernel on every engine the run builds; threaded into the archived
record as ``detail.decode_kernel`` so bench_trend treats a kernel swap
as a protocol change), ``SERVE_QUANT_MATCH_MIN`` (0.95),
``SERVE_SPEC_K`` (0 — >0 selects the speculative compare),
``SERVE_SPEC_DRAFT`` (int8 | ngram), ``SERVE_SPEC_NGRAM_N`` (3),
``SERVE_SPEC_MIN_SPEEDUP`` (1.4),
``BENCH_MODEL`` (lm_tiny), ``BENCH_VOCAB`` (32000), plus the generic
``OBS_DIR``/``--events`` plumbing
bench.py uses. With ``SLO_SPEC`` set (and ``OBS_DIR``) the bench runs
under the live telemetry plane — rollups + SLO burn rates published to
``<OBS_DIR>/rollup.json`` while serving — and
``SERVE_ADMISSION_POLICY=adaptive`` closes the feedback loop: the
scheduler derates admission while a latency SLO burns
(docs/SERVING.md, docs/OBSERVABILITY.md).

Usage::

    python scripts/serve_bench.py [--events]
"""

from __future__ import annotations

import json
import os
import sys
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# Shape mixes + seeded Poisson load + per-shape warmup live in
# serving/loadgen.py (shared with scripts/fleet_bench.py); the names
# are re-exported here because this module IS the serving bench's
# protocol surface.
from distributeddeeplearning_tpu.serving.loadgen import (  # noqa: E402
    MIXED_PROMPT_LENS,
    PROFILES,
    build_requests,
    percentile as _percentile,
    warm_shapes,
)


def _emit_record(record: dict) -> None:
    """bench.py's output contract: the canonical JSON line on stdout
    plus the same record on the event bus."""
    print(json.dumps(record), flush=True)
    from distributeddeeplearning_tpu import obs

    bus = obs.get_bus()
    bus.point("bench_result", **record)
    bus.flush()


def run_sequential(model, params, reqs, temperature, top_k):
    """One-at-a-time baseline through inference.generate; each distinct
    (prompt_len, max_new) shape is warmed first (loadgen.warm_shapes).
    Returns (tokens/sec, per-request outputs, distinct compiled
    shapes)."""
    import jax
    import numpy as np

    from distributeddeeplearning_tpu.inference import generate

    n_shapes = warm_shapes(model, params, reqs, temperature, top_k)
    outs = []
    t0 = time.perf_counter()
    for r in reqs:
        out = generate(
            model, params, r["prompt"][None], max_new_tokens=r["max_new"],
            temperature=temperature, top_k=top_k,
            rng=jax.random.PRNGKey(r["seed"]),
        )
        outs.append(np.asarray(out)[0])
    dt = time.perf_counter() - t0
    tokens = sum(r["max_new"] for r in reqs)
    return tokens / dt, outs, n_shapes


def run_continuous(server, reqs, temperature, top_k):
    """Replay the Poisson schedule against the serving loop: submit
    each request at its arrival offset, pumping the scheduler while
    waiting; drain. Returns (tokens/sec makespan throughput, handles,
    wall seconds)."""
    from distributeddeeplearning_tpu.serving import Request

    handles = []
    t0 = time.perf_counter()
    for r in reqs:
        while time.perf_counter() - t0 < r["arrival_s"]:
            server.step()  # keep decoding while the next arrival is due
        handles.append(server.submit(Request(
            prompt=r["prompt"], max_new_tokens=r["max_new"],
            temperature=temperature, top_k=top_k, rng=r["seed"],
        )))
    server.drain()
    dt = time.perf_counter() - t0
    tokens = sum(len(h.new_tokens) for h in handles)
    return tokens / dt, handles, dt


def serve_one_engine(model, params, reqs, seq_outs, *, engine_kwargs,
                     queue_depth, prefills_per_step, temperature, top_k,
                     admission_policy=None):
    """Build + warm one engine, replay the request schedule through it,
    and report throughput, concurrency, latency percentiles, parity
    against the sequential outputs (None skips the check — the quant
    compare has no bitwise reference) and the compile ledger. Returns
    ``(record, per-request new-token streams, engine)``."""
    import numpy as np

    from distributeddeeplearning_tpu.serving import Server, SlotEngine

    engine = SlotEngine(model, params, **engine_kwargs)
    engine.warmup()
    server = Server(
        engine, queue_depth=max(queue_depth, len(reqs)),
        prefills_per_step=prefills_per_step,
        admission_policy=admission_policy,
    )
    # Warm pass: one request end-to-end so first-dispatch overheads
    # (host transfers, executable load) stay out of the measurement.
    run_continuous(server, reqs[:1], temperature, top_k)
    compile_count_pre = engine.compile_count
    server.stats["peak_active"] = 0

    tps, handles, wall_s = run_continuous(server, reqs, temperature, top_k)

    parity = None if seq_outs is None else all(
        np.array_equal(h.tokens, seq_outs[i][: len(h.tokens)])
        for i, h in enumerate(handles)
    )
    ttft_ms = [h.ttft_s * 1e3 for h in handles if h.ttft_s is not None]
    qwait_ms = [
        h.queue_wait_s * 1e3 for h in handles
        if h.queue_wait_s is not None
    ]
    out = {
        "kv_layout": engine.kv_layout,
        "tokens_per_sec": round(tps, 1),
        "parity": None if parity is None else bool(parity),
        "slots": engine.num_slots,
        "peak_concurrent": server.stats["peak_active"],
        "ttft_p50_ms": round(_percentile(ttft_ms, 0.5), 2),
        "ttft_p99_ms": round(_percentile(ttft_ms, 0.99), 2),
        "queue_wait_p50_ms": round(_percentile(qwait_ms, 0.5), 2),
        "queue_wait_p99_ms": round(_percentile(qwait_ms, 0.99), 2),
        "slot_occupancy_mean": round(server.occupancy_mean, 3),
        "decode_steps": server.stats["decode_steps"],
        "compile_count": engine.compile_count,
        "programs_expected": engine.programs_expected,
        "compiles_during_measure": engine.compile_count - compile_count_pre,
        "wall_s": round(wall_s, 2),
    }
    if engine.allocator is not None:
        snap = engine.allocator.snapshot()
        out["pool"] = {
            "block_size": engine.block_size,
            "capacity_blocks": snap["capacity"],
            "prefix_hit_blocks": snap["prefix_hit_blocks"],
            "evicted": snap["evicted"],
            # utilization at peak demand: how much of the byte budget
            # actually held live KV when the pool was busiest
            "peak_live_blocks": snap["peak_live"],
            "peak_utilization": round(
                snap["peak_live"] / snap["capacity"], 3
            ) if snap["capacity"] else 0.0,
        }
    return out, [list(h.new_tokens) for h in handles], engine


def kv_slot_bytes(model, max_len: int, kv_dtype: str) -> int:
    """Per-slot KV bytes of a dense cache row at ``max_len`` — int8
    payload PLUS f32 scales when quantized (shape-only eval_shape; the
    quant compare sizes the quantized engine's slot count so both
    engines hold the SAME pool bytes)."""
    import math

    import numpy as np
    from flax import traverse_util

    from distributeddeeplearning_tpu.inference import (
        decode_cache_shapes,
        decode_variant,
    )

    shapes = decode_cache_shapes(
        decode_variant(model, kv_dtype=kv_dtype), 1, max_len
    )
    total = 0
    for path, leaf in traverse_util.flatten_dict(dict(shapes)).items():
        if path[-1] in ("cache_index", "pos_index"):
            continue
        total += math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
    return total


def teacher_forced_match(engine, reqs, ref_streams):
    """The quantization quality oracle: per-step greedy agreement with
    the reference context FORCED (``SlotEngine.force_token``). Each
    reference stream replays through the quantized engine; at every
    step the engine answers "given this exact bf16-produced history,
    which token would I emit?" and agreement is counted. Free-running
    comparison would conflate per-step quality with divergence cascades
    (one flip re-conditions the suffix), which is why it is reported
    but not gated."""
    from distributeddeeplearning_tpu.serving import ReqSpec

    total = matched = 0
    i = 0
    active = {}  # slot -> (stream, next position to compare)
    while i < len(reqs) or active:
        for slot in engine.free_slots:
            if i >= len(reqs):
                break
            r, stream = reqs[i], ref_streams[i]
            i += 1
            first, _ = engine.prefill(slot, ReqSpec(
                prompt=r["prompt"], max_new_tokens=len(stream),
                temperature=0.0,
            ))
            total += 1
            matched += int(first == stream[0])
            if len(stream) == 1:
                engine.release(slot)
            else:
                engine.force_token(slot, int(stream[0]))
                active[slot] = (stream, 1)
        if not active:
            continue
        for slot, tok, _eos in engine.decode_step():
            if slot not in active:
                continue
            stream, c = active[slot]
            total += 1
            matched += int(tok == stream[c])
            c += 1
            if c >= len(stream):
                engine.release(slot)
                del active[slot]
            else:
                engine.force_token(slot, int(stream[c - 1]))
                active[slot] = (stream, c)
    return matched / max(total, 1)


def positional_match(ref_streams, q_streams):
    """Free-running positional agreement (reported, not gated)."""
    tot = hit = 0
    for a, b in zip(ref_streams, q_streams):
        tot += max(len(a), len(b))
        hit += sum(x == y for x, y in zip(a, b))
    return hit / max(tot, 1)


def weight_logit_err(model, params, reqs, ref_streams, n_seq: int = 2):
    """Per-step logit error of the weight quantization alone: a
    teacher-forced full forward over reference sequences with exact vs
    dequantized-int8 params (max over positions of max-abs logit
    delta). The KV-cache quantization's contribution is covered by the
    engine-level match oracle; this isolates the weights."""
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu.ops import quant as quantlib

    dq = quantlib.dequantize_params(quantlib.quantize_params(params))
    err = 0.0
    for r, s in list(zip(reqs, ref_streams))[:n_seq]:
        toks = np.concatenate([r["prompt"], np.asarray(s, np.int32)])
        toks = jnp.asarray(toks[None, :])
        lr = model.apply({"params": params}, toks, train=False)
        lq = model.apply({"params": dq}, toks, train=False)
        err = max(err, float(jnp.max(jnp.abs(
            lr.astype(jnp.float32) - lq.astype(jnp.float32)
        ))))
    return err


def run_quant_compare(model, params, reqs, cfg, metric, *, budget_slots,
                      max_len, profile, rate_rps, match_min):
    """The quantized-decode certification: bf16 (native) engine at
    ``budget_slots`` dense slots vs the int8 engine holding the SAME
    KV-pool bytes (more slots — the byte win expressed as capacity),
    same seeded greedy load. Gates: teacher-forced greedy match rate ≥
    ``match_min``, quantized tokens/sec ≥ bf16, zero mid-measure
    recompiles and closed program sets on both engines (the quality
    replay reuses the warmed quantized engine, so it proves the oracle
    itself compiled nothing)."""
    import jax

    common = dict(
        queue_depth=cfg.queue_depth,
        prefills_per_step=cfg.prefills_per_step,
        temperature=0.0, top_k=None,
        admission_policy=cfg.build_admission_policy(),
    )
    ref_run, ref_streams, ref_engine = serve_one_engine(
        model, params, reqs, None,
        engine_kwargs=dict(
            num_slots=budget_slots, max_len=max_len, buckets=cfg.buckets,
            decode_kernel=cfg.decode_kernel,
        ),
        **common,
    )
    native_b = kv_slot_bytes(model, max_len, "bf16")
    quant_b = kv_slot_bytes(model, max_len, cfg.kv_dtype)
    slots_q = max(budget_slots, int(budget_slots * native_b // quant_b))
    q_run, q_streams, q_engine = serve_one_engine(
        model, params, reqs, None,
        engine_kwargs=dict(
            num_slots=slots_q, max_len=max_len, buckets=cfg.buckets,
            kv_dtype=cfg.kv_dtype, weight_dtype=cfg.weight_dtype,
            decode_kernel=cfg.decode_kernel,
        ),
        **common,
    )
    # Quality oracle on the SAME warmed quantized engine: the replay
    # must compile nothing (force_token is pure host data).
    compile_pre = q_engine.compile_count
    match = teacher_forced_match(q_engine, reqs, ref_streams)
    free_match = positional_match(ref_streams, q_streams)
    logit_err = (
        weight_logit_err(model, params, reqs, ref_streams)
        if cfg.weight_dtype != "bf16" else None
    )
    # Label the quantized side by its actual tier (int8 or fp8) so the
    # archived record says what ran; the kv tier names the engine when
    # both tiers are set.
    qlabel = cfg.kv_dtype if cfg.kv_dtype != "bf16" else cfg.weight_dtype
    tps_ratio = (
        q_run["tokens_per_sec"] / ref_run["tokens_per_sec"]
        if ref_run["tokens_per_sec"] else 0.0
    )
    capacity_ratio = (
        q_run["peak_concurrent"] / ref_run["peak_concurrent"]
        if ref_run["peak_concurrent"] else 0.0
    )
    detail = {
        "profile": profile,
        "requests": len(reqs),
        "buckets": list(cfg.buckets),
        "rate_rps": rate_rps,
        "max_len": max_len,
        "platform": jax.devices()[0].platform,
        "kv_dtype": cfg.kv_dtype,
        "weight_dtype": cfg.weight_dtype,
        "decode_kernel": cfg.decode_kernel,
        "pool_budget_slots": budget_slots,
        "kv_slot_bytes": {"bf16": int(native_b), qlabel: int(quant_b)},
        "kv_bytes_per_token": {
            "bf16": ref_engine.byte_accounting()["kv_bytes_per_token"],
            qlabel: q_engine.byte_accounting()["kv_bytes_per_token"],
        },
        "param_bytes": {
            "bf16": ref_engine.byte_accounting()["param_bytes"],
            qlabel: q_engine.byte_accounting()["param_bytes"],
        },
        "bf16": ref_run,
        qlabel: q_run,
        "tps_ratio": round(tps_ratio, 2),
        "capacity_ratio": round(capacity_ratio, 2),
        # Teacher-forced per-step agreement (GATED) vs free-running
        # positional agreement (reported): see docs/SERVING.md — exact
        # parity is mathematically unavailable under quantization.
        "match_rate": round(match, 4),
        "match_rate_min": match_min,
        "match_rate_freerun": round(free_match, 4),
        "weight_logit_err_max": (
            None if logit_err is None else round(logit_err, 5)
        ),
    }
    clean = (
        ref_run["compiles_during_measure"] == 0
        and q_run["compiles_during_measure"] == 0
        and q_engine.compile_count == compile_pre
    )
    closed = all(
        r["compile_count"] == r["programs_expected"]
        for r in (ref_run, q_run)
    )
    ok = (
        clean and closed and match >= match_min and tps_ratio >= 1.0
    )
    record = {
        "metric": metric,
        # headline: quantized throughput at the shared byte budget
        "value": q_run["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": round(tps_ratio, 2),
        "detail": detail,
    }
    _emit_record(record)
    return 0 if ok else 1


def run_spec_compare(model, params, reqs, cfg, metric, *, max_len,
                     profile, rate_rps, min_speedup):
    """The speculative-decode certification (``SERVE_SPEC_K > 0``):
    plain greedy engine vs the speculative engine (same slots, same
    seeded load, same pool geometry). Gates: **bitwise greedy parity**
    (every stream token-for-token equal — speculation must be lossless
    in the greedy regime), speculative tokens/sec >= ``min_speedup`` x
    the baseline, zero mid-measure recompiles and program sets closed
    at their static counts on BOTH engines. Accept-rate p50/mean are
    reported from the engine's per-tick tallies."""
    import jax
    import numpy as np

    common = dict(
        queue_depth=cfg.queue_depth,
        prefills_per_step=cfg.prefills_per_step,
        temperature=0.0, top_k=None,
        admission_policy=cfg.build_admission_policy(),
    )
    base_kwargs = dict(
        num_slots=cfg.num_slots, max_len=max_len, buckets=cfg.buckets,
        decode_kernel=cfg.decode_kernel,
    )
    ref_run, ref_streams, ref_engine = serve_one_engine(
        model, params, reqs, None, engine_kwargs=base_kwargs, **common,
    )
    spec_kwargs = dict(
        base_kwargs, spec_k=cfg.spec_k, spec_draft=cfg.spec_draft,
        spec_ngram_n=cfg.spec_ngram_n,
    )
    spec_run, spec_streams, spec_engine = serve_one_engine(
        model, params, reqs, None, engine_kwargs=spec_kwargs, **common,
    )
    parity = spec_streams == ref_streams  # bitwise, token for token
    st = spec_engine.spec_stats
    rates = st["accept_rates"]
    speedup = (
        spec_run["tokens_per_sec"] / ref_run["tokens_per_sec"]
        if ref_run["tokens_per_sec"] else 0.0
    )
    detail = {
        "profile": profile,
        "requests": len(reqs),
        "buckets": list(cfg.buckets),
        "rate_rps": rate_rps,
        "max_len": max_len,
        "platform": jax.devices()[0].platform,
        "spec_k": cfg.spec_k,
        "spec_draft": cfg.spec_draft,
        "decode_kernel": cfg.decode_kernel,
        "greedy": ref_run,
        "spec": spec_run,
        "speedup": round(speedup, 2),
        "min_speedup": min_speedup,
        "parity": bool(parity),
        "accept_rate_mean": round(float(np.mean(rates)), 4) if rates else None,
        "accept_rate_p50": round(_percentile(sorted(rates), 0.5), 4)
        if rates else None,
        "tokens_per_verify": round(
            st["tokens_committed"] / max(st["verify_ticks"], 1), 2
        ),
        "draft_ms_total": round(st["draft_s"] * 1e3, 1),
        "verify_ms_total": round(st["verify_s"] * 1e3, 1),
        "draft_bytes": {
            k: v for k, v in spec_engine.byte_accounting().items()
            if k.startswith("draft_")
        } or None,
    }
    clean = (
        ref_run["compiles_during_measure"] == 0
        and spec_run["compiles_during_measure"] == 0
    )
    closed = all(
        r["compile_count"] == r["programs_expected"]
        for r in (ref_run, spec_run)
    )
    ok = clean and closed and parity and speedup >= min_speedup
    record = {
        "metric": metric,
        # headline: speculative throughput on the same greedy load
        "value": spec_run["tokens_per_sec"],
        "unit": "tokens/sec",
        "vs_baseline": round(speedup, 2),
        "detail": detail,
    }
    _emit_record(record)
    return 0 if ok else 1


def start_live_plane(obs_dir):
    """Run the live telemetry plane (tail -> rollup -> SLO -> rollup.json)
    in a background thread for the duration of the bench — the thing an
    adaptive admission policy (SERVE_ADMISSION_POLICY=adaptive) reads.
    Returns (stop_event, thread), or (None, None) when SLO_SPEC is
    unset (no objectives = nothing to evaluate or feed back)."""
    import threading

    from distributeddeeplearning_tpu.obs.rollup import LivePlane
    from distributeddeeplearning_tpu.obs.slo import SloEngine

    slo = SloEngine.from_env()
    if slo is None:
        return None, None
    plane = LivePlane(obs_dir, slo_engine=slo)
    stop = threading.Event()

    def pump():
        while not stop.is_set():
            plane.poll(now=time.time())
            stop.wait(0.2)
        plane.poll(now=time.time())

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    return stop, t


def main() -> int:
    if "--events" in sys.argv[1:] or os.environ.get("OBS_DIR"):
        from distributeddeeplearning_tpu import obs

        if not os.environ.get("OBS_DIR"):
            os.environ["OBS_DIR"] = os.path.join(
                "runs", f"serve-bench-{int(time.time())}"
            )
        obs.configure_from_env()
    # Live plane (docs/OBSERVABILITY.md): with SLO_SPEC set the bench
    # runs under its own telemetry — rollup.json is published next to
    # the event files and SERVE_ADMISSION_POLICY=adaptive closes the
    # loop (shed-then-recover under a burning latency SLO).
    plane_stop = plane_thread = None
    if os.environ.get("OBS_DIR") and os.environ.get("SLO_SPEC"):
        plane_stop, plane_thread = start_live_plane(os.environ["OBS_DIR"])
    import jax

    from distributeddeeplearning_tpu.training.warmup import (
        enable_compile_cache,
    )

    enable_compile_cache()

    import flax.linen as nn
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.serving import ServeConfig

    env = os.environ
    model_name = env.get("BENCH_MODEL", "lm_tiny")
    # Realistic LM vocab by default: decode is weight/KV-bandwidth-bound
    # (scripts/decode_audit.py), and the output projection over the full
    # vocab is the term continuous batching amortises across slots —
    # a toy vocab would benchmark dispatch overhead instead.
    vocab = int(env.get("BENCH_VOCAB", "32000"))
    n_requests = int(env.get("SERVE_REQUESTS", "32"))
    max_new = int(env.get("SERVE_MAX_NEW", "16"))
    rate_rps = float(env.get("SERVE_RATE_RPS", "200"))
    seed = int(env.get("SERVE_SEED", "0"))
    profile = env.get("SERVE_PROFILE", "mixed")
    layout = env.get("SERVE_KV_LAYOUT", "dense")
    budget_slots = int(env.get("SERVE_POOL_SLOT_BUDGET", "4"))
    if profile not in PROFILES:
        raise SystemExit(f"unknown SERVE_PROFILE {profile!r}")
    if layout not in ("dense", "paged", "compare"):
        raise SystemExit(f"unknown SERVE_KV_LAYOUT {layout!r}")
    shapes = PROFILES[profile] or [(tp, max_new) for tp in MIXED_PROMPT_LENS]
    cfg = ServeConfig.from_env()
    if cfg.buckets is None:
        cfg.buckets = (8, 16) if profile == "mixed" else (8, 16, 32, 64, 96)
    max_len = max(tp + n_new for tp, n_new in shapes)
    # Quantization compare (SERVE_KV_DTYPE / SERVE_WEIGHT_DTYPE=int8):
    # its own mode — greedy load (the match-rate oracle's regime),
    # engine-vs-engine at a fixed KV-pool byte budget.
    quant = cfg.kv_dtype != "bf16" or cfg.weight_dtype != "bf16"
    if quant and layout != "dense":
        raise SystemExit(
            "the quantization compare runs on the dense layout — unset "
            "SERVE_KV_LAYOUT or the quantized (int8/fp8) dtypes"
        )
    # Speculative compare (SERVE_SPEC_K > 0): greedy-vs-speculative,
    # bitwise greedy parity gated (docs/SERVING.md).
    spec = cfg.spec_k > 0
    if spec and (quant or layout != "dense"):
        raise SystemExit(
            "the speculative compare runs on the dense native-dtype "
            "engines — unset SERVE_KV_LAYOUT / the quantized dtypes or "
            "SERVE_SPEC_K"
        )
    match_min = float(env.get("SERVE_QUANT_MATCH_MIN", "0.95"))
    min_speedup = float(env.get("SERVE_SPEC_MIN_SPEEDUP", "1.4"))
    temperature, top_k = (0.0, None) if quant else (0.8, 40)
    metric = (
        "serve_spec_vs_greedy_tokens_per_sec" if spec
        else "serve_int8_vs_bf16_tokens_per_sec" if quant
        else "serve_paged_vs_dense_capacity" if layout == "compare"
        else "serve_continuous_tokens_per_sec"
    )

    if spec:
        # The verify window writes spec_k lookahead positions past a
        # request's last token; both engines get the same headroom so
        # the compare stays shape-for-shape fair.
        max_len += cfg.spec_k
    try:
        model = get_model(
            model_name, num_classes=vocab, max_seq_len=max_len,
            dtype=jnp.float32,
        )
        variables = jax.jit(model.init, static_argnames=("train",))(
            jax.random.PRNGKey(0), jnp.zeros((2, max_len), jnp.int32),
            train=False,
        )
        params = nn.unbox(variables["params"])
        reqs = build_requests(n_requests, rate_rps, seed, vocab, shapes)

        if spec:
            return run_spec_compare(
                model, params, reqs, cfg, metric, max_len=max_len,
                profile=profile, rate_rps=rate_rps,
                min_speedup=min_speedup,
            )

        if quant:
            return run_quant_compare(
                model, params, reqs, cfg, metric,
                budget_slots=budget_slots, max_len=max_len,
                profile=profile, rate_rps=rate_rps,
                match_min=match_min,
            )

        seq_tps, seq_outs, seq_shapes = run_sequential(
            model, params, reqs, temperature, top_k
        )

        budget_tokens = budget_slots * max_len
        paged_kwargs = dict(
            num_slots=cfg.num_slots, max_len=max_len, buckets=cfg.buckets,
            decode_kernel=cfg.decode_kernel,
            kv_layout="paged", block_size=cfg.block_size,
            num_blocks=(
                cfg.num_blocks or budget_tokens // cfg.block_size + 1
            ),
            prefix_cache=cfg.prefix_cache,
        )
        runs = {}
        if layout in ("dense", "compare"):
            runs["dense"], _, _ = serve_one_engine(
                model, params, reqs, seq_outs,
                engine_kwargs=dict(
                    num_slots=(
                        budget_slots if layout == "compare"
                        else cfg.num_slots
                    ),
                    max_len=max_len, buckets=cfg.buckets,
                    decode_kernel=cfg.decode_kernel,
                ),
                queue_depth=cfg.queue_depth,
                prefills_per_step=cfg.prefills_per_step,
                temperature=temperature, top_k=top_k,
                admission_policy=cfg.build_admission_policy(),
            )
        if layout in ("paged", "compare"):
            runs["paged"], _, _ = serve_one_engine(
                model, params, reqs, seq_outs,
                engine_kwargs=paged_kwargs,
                queue_depth=cfg.queue_depth,
                prefills_per_step=cfg.prefills_per_step,
                temperature=temperature, top_k=top_k,
                admission_policy=cfg.build_admission_policy(),
            )

        detail = {
            "profile": profile,
            "requests": n_requests,
            "buckets": list(cfg.buckets),
            "rate_rps": rate_rps,
            "max_len": max_len,
            "sequential_tokens_per_sec": round(seq_tps, 1),
            "sequential_compiled_shapes": seq_shapes,
            "platform": jax.devices()[0].platform,
            "decode_kernel": cfg.decode_kernel,
        }
        parity = all(r["parity"] for r in runs.values())
        clean = all(r["compiles_during_measure"] == 0 for r in runs.values())
        closed = all(
            r["compile_count"] == r["programs_expected"]
            for r in runs.values()
        )
        if layout == "compare":
            dense, paged = runs["dense"], runs["paged"]
            capacity_ratio = (
                paged["peak_concurrent"] / dense["peak_concurrent"]
                if dense["peak_concurrent"] else 0.0
            )
            tps_ratio = (
                paged["tokens_per_sec"] / dense["tokens_per_sec"]
                if dense["tokens_per_sec"] else 0.0
            )
            detail.update({
                "pool_budget_tokens": budget_tokens,
                "dense": dense,
                "paged": paged,
                "capacity_ratio": round(capacity_ratio, 2),
                "tps_ratio": round(tps_ratio, 2),
                "parity": parity,
            })
            record = {
                "metric": metric,
                # headline: paged throughput at the shared byte budget
                "value": paged["tokens_per_sec"],
                "unit": "tokens/sec",
                "vs_baseline": round(tps_ratio, 2),
            }
            ok = (
                parity and clean and closed
                and (capacity_ratio >= 2.0 or tps_ratio >= 1.5)
            )
        else:
            run = runs[layout]
            detail.update(run)
            detail["speedup_vs_sequential"] = (
                round(run["tokens_per_sec"] / seq_tps, 2) if seq_tps else 0.0
            )
            record = {
                "metric": metric,
                "value": run["tokens_per_sec"],
                "unit": "tokens/sec",
                "vs_baseline": detail["speedup_vs_sequential"],
            }
            ok = parity and clean and closed
        record["detail"] = detail
        _emit_record(record)
        return 0 if ok else 1
    except Exception as e:  # structured failure record, like bench.py
        _emit_record({
            "metric": metric, "value": 0.0,
            "unit": "tokens/sec", "vs_baseline": 0.0, "error": repr(e),
        })
        raise
    finally:
        if plane_stop is not None:
            plane_stop.set()
            plane_thread.join(timeout=10)


if __name__ == "__main__":
    sys.exit(main())
