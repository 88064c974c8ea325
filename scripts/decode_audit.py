"""Decode-path audit (VERDICT r4 #6): roofline position + batch sweep.

The decode tier's 11.7k tok/s (b=8) was the only number in BASELINE.md
with no PROFILE.md account behind it. This script gives it one, using the
same method as the trainer audits: an analytic byte floor, a measured
sweep, and (optionally) a trace.

**Byte floor.** Autoregressive decode is memory-bound: each step must
stream (a) every parameter and (b) the KV cache past. With this repo's
static cache design (``inference.py`` — buffers allocated at the
request length prompt+new, position mask hides the unwritten tail), the
attention reads the FULL buffer every step regardless of how many
tokens are valid yet, so with max_len = prompt_len + new_tokens:

    bytes/step  =  param_bytes + kv_cache_bytes(max_len)
    tok/s floor =  batch * HBM_BW / bytes_per_step

Batch amortizes the parameter (and, less obviously, nothing else: the KV
cache scales WITH batch, so at large b the cache term dominates and
tok/s/seq degrades). The sweep shows exactly where that crossover sits.

**Paged mode** (``--kv-layout paged``, the serving tier's
``SERVE_KV_LAYOUT=paged`` — docs/SERVING.md): decode runs through the
block-pool ``SlotEngine`` instead of ``inference.generate``, and the
floor accounts what that path actually streams per step: the
table-gathered K/V view (``blocks_per_slot * block_size`` rows per
sequence — block-rounded, so ≥ the dense ``max_len``) PLUS the per-slot
int32 block tables the gather indexes through. Leaving the table bytes
out would overstate ``pct_of_floor`` in paged mode; they are itemized as
``block_table_bytes`` in each row.

**Speculative mode** (``--spec-k K`` [``--spec-draft int8|ngram``], the
serving tier's ``SERVE_SPEC_K`` — docs/SERVING.md): every surviving
byte buys MORE than one token. A verify tick streams the target's
params + cache ONCE for K+1 candidate positions and commits
``1..K+1`` tokens, so the audited unit becomes **bytes per accepted
token** (tick bytes ÷ measured commits per verify) and the rows carry a
``floor_multiplier`` against the non-speculative floor. The draft's
costs are itemized honestly, never netted out: the int8 self-draft adds
a second dense KV pool (``draft_cache_mb``) streamed once per draft
step, the resident int8+scale weight tree read once per tick, and K
reads of the dequantized (native-dtype) weight view the draft scan
hoists (``serving/engine._spec_draft_fn``); the n-gram draft adds
nothing. The accept rate is MEASURED through a real speculative
``SlotEngine`` loop, not assumed.

**Quantized mode** (``--kv-dtype int8`` / ``--weight-dtype int8``, the
serving tier's ``SERVE_KV_DTYPE``/``SERVE_WEIGHT_DTYPE``): the floor is
recomputed from the bytes the quantized programs actually stream — int8
K/V + the f32 per-head scale buffers (itemized ``kv_scale_bytes``), and
int8 kernels/embedding + their per-channel scales (itemized
``param_scale_bytes``). Scales are *in* the floor, never hidden:
claiming the bf16 floor with int8 bytes would overstate
``pct_of_floor``. Measurement then runs through a real quantized
``SlotEngine`` decode loop (``inference.generate`` has no quantized
path — the serving engine is the product surface for it).

**Kernel compare** (``--kernel xla|fused|both``, the serving tier's
``SERVE_DECODE_KERNEL`` — docs/SERVING.md): ``fused`` measures through
the Pallas online-softmax decode kernel
(``ops/pallas/paged_decode.py``); ``both`` emits one row per kernel per
batch so the impls are compared against the SAME analytic floor basis.
The per-kernel bytes are itemized honestly: under a quantized cache the
stitched (xla) path materialises full-length compute-dtype K/V buffers
— the gather→dequant round-trip the fused kernel performs in-register —
charged to the xla rows as ``dequant_roundtrip_bytes`` (write + read of
both tensors). The fused rows never pay it, which is exactly the
bytes/step gap serve_bench's compare gate asserts. ``pct_of_floor``
stays ``None`` off-TPU for every kernel (CPU interpret-mode measures
dispatch correctness, not roofline position).

Usage::

    python scripts/decode_audit.py [--model lm_small] [--prompt-len 128]
        [--new-tokens 128] [--batches 1,2,4,8,16,32,64]
        [--kv-layout dense|paged] [--block-size 16]
        [--kv-dtype bf16|int8|fp8] [--weight-dtype bf16|int8|fp8]
        [--kernel xla|fused|both]
        [--spec-k 4] [--spec-draft int8|ngram]
        [--profile-dir /tmp/decode_trace]

Prints a per-batch table and ONE summary JSON line.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import sys
import time

import numpy as np

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

# The chip peaks live in ONE shared table (utils/roofline.py), keyed by
# the device_kind JAX reports.
from distributeddeeplearning_tpu.utils import roofline  # noqa: E402


def tree_bytes(tree) -> int:
    import jax

    return sum(
        leaf.size * leaf.dtype.itemsize for leaf in jax.tree.leaves(tree)
    )


def sweep_row(b: int, tps: float, kv_bytes: int, bytes_per_step: int,
              floor: float, on_tpu: bool, table_bytes: int = 0,
              kv_scale_bytes: int = 0) -> dict:
    """One sweep record. VERDICT r5 item 8: the byte floor is a v5e HBM
    roofline — off-chip (CPU smoke) it is NOT a position, so
    ``pct_of_floor`` is emitted as None there and the analytic floor is
    kept under an explicitly-labelled key instead. ``table_bytes`` (paged
    mode) and ``kv_scale_bytes`` (int8 mode: the f32 per-head scale
    buffers) are already inside ``bytes_per_step``; they are itemized so
    the floor's overheads stay auditable."""
    row = {
        "batch": b,
        "tokens_per_sec": round(tps, 1),
        "tokens_per_sec_per_seq": round(tps / b, 1),
        "bytes_per_step_mb": round(bytes_per_step / 2**20, 1),
        "kv_cache_mb": round(kv_bytes / 2**20, 1),
        "analytic_floor_tokens_per_sec": round(floor, 1),
        "pct_of_floor": round(100.0 * tps / floor, 1) if on_tpu else None,
    }
    if table_bytes:
        row["block_table_bytes"] = int(table_bytes)
    if kv_scale_bytes:
        row["kv_scale_bytes"] = int(kv_scale_bytes)
    return row


def format_row(row: dict) -> str:
    pct = row["pct_of_floor"]
    pct_str = f"{pct:>9.1f}%" if pct is not None else f"{'n/a':>10}"
    return (f"  {row['batch']:>4} {row['tokens_per_sec']:>10.1f} "
            f"{row['tokens_per_sec_per_seq']:>10.1f} "
            f"{row['analytic_floor_tokens_per_sec']:>12.1f} "
            f"{pct_str} {row['kv_cache_mb']:>10.1f}")


def paged_step_bytes(model, b: int, max_len: int, block_size: int,
                     kv_dtype: str = "bf16"):
    """Per-decode-step streamed KV bytes of the PAGED layout for ``b``
    co-resident sequences: the table-gathered K/V view (each sequence
    reads its ``blocks_per_slot`` blocks — block-rounded ``max_len``)
    plus the int32 block tables the gather routes through, plus — under
    ``kv_dtype="int8"`` — the f32 per-head scale pools gathered beside
    the payload (itemized as scale bytes). Shape-only (``eval_shape`` of
    the paged decode clone's init — exactly how the serving engine sizes
    its pool). Returns (view_bytes, table_bytes, scale_bytes); the view
    EXCLUDES scales so callers can itemize."""
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    from distributeddeeplearning_tpu.inference import decode_variant

    mb = -(-max_len // block_size)
    paged_model = decode_variant(
        model, paged_blocks=b * mb + 1, paged_block_size=block_size,
        kv_dtype=kv_dtype,
    )
    shapes = jax.eval_shape(
        lambda r: paged_model.init(
            r, jnp.zeros((b, max_len), jnp.int32), train=False
        ),
        jax.random.PRNGKey(0),
    )["cache"]
    view_bytes = table_bytes = scale_bytes = 0
    for path, leaf in traverse_util.flatten_dict(dict(shapes)).items():
        if path[-1] == "block_table":
            table_bytes += math.prod(leaf.shape) * 4
        elif path[-1] in ("paged_k", "paged_v", "paged_k_scale",
                          "paged_v_scale"):
            _, bs, heads, tail = leaf.shape
            n = b * mb * bs * heads * tail * np.dtype(leaf.dtype).itemsize
            if path[-1].endswith("_scale"):
                scale_bytes += n
            else:
                view_bytes += n
    return view_bytes, table_bytes, scale_bytes


def measure_engine(model, params, b: int, prompt_len: int, new_tokens: int,
                   vocab: int, reps: int = 3, *, kv_layout: str = "dense",
                   block_size: int = 16, kv_dtype: str = "bf16",
                   weight_dtype: str = "bf16",
                   decode_kernel: str = "xla") -> float:
    """Measured engine-decode throughput: ``b`` requests co-resident in
    a SlotEngine (dense or block-pool layout, native or quantized
    dtypes, stitched or fused decode kernel), timing the batched decode
    steps (the path the byte floor describes; prefill is the one-off
    outside it). The quantized/fused configurations only exist on this
    path — ``inference.generate`` stays native-dtype XLA."""
    from distributeddeeplearning_tpu.serving import ReqSpec, SlotEngine

    max_len = prompt_len + new_tokens
    paged_kw = (
        dict(block_size=block_size, prefix_cache=False)
        if kv_layout == "paged" else {}
    )
    engine = SlotEngine(
        model, params, num_slots=b, max_len=max_len,
        buckets=(prompt_len,), kv_layout=kv_layout,
        kv_dtype=kv_dtype, weight_dtype=weight_dtype,
        decode_kernel=decode_kernel, **paged_kw,
    )
    engine.warmup()
    rng = np.random.RandomState(0)
    total = t_meas = 0.0
    for rep in range(reps + 1):  # rep 0 = warmup, untimed
        for slot in list(engine.active_slots):
            engine.release(slot)
        for slot in range(b):
            spec = ReqSpec(
                prompt=rng.randint(0, vocab, size=(prompt_len,)).astype(
                    np.int32
                ),
                max_new_tokens=new_tokens,
                temperature=0.8, top_k=40, rng=rep * b + slot,
            )
            engine.validate_spec(spec)
            engine.prefill(slot, spec)
        engine.decode_step()  # fence: first batched step dispatched
        t0 = time.perf_counter()
        # prefill + the fence step emitted 2 of new_tokens already
        steps = max(new_tokens - 2, 1)
        for _ in range(steps):
            engine.decode_step()
        dt = time.perf_counter() - t0
        if rep:
            total += b * steps
            t_meas += dt
    return total / t_meas


def measure_engine_spec(model, params, b: int, prompt_len: int,
                        new_tokens: int, vocab: int, reps: int = 3, *,
                        spec_k: int = 4, spec_draft: str = "int8",
                        kv_dtype: str = "bf16",
                        decode_kernel: str = "xla"):
    """Measured speculative throughput: ``b`` greedy requests
    co-resident in a spec SlotEngine, timing the draft+verify ticks to
    completion. Returns ``(tokens/sec, accept_rate, commits_per_verify)``
    — the accept rate is what the analytic bytes-per-accepted-token
    figure divides by, so it is measured, never assumed."""
    from distributeddeeplearning_tpu.serving import ReqSpec, SlotEngine

    max_len = prompt_len + new_tokens + spec_k  # verify lookahead headroom
    engine = SlotEngine(
        model, params, num_slots=b, max_len=max_len,
        buckets=(prompt_len,), kv_dtype=kv_dtype,
        decode_kernel=decode_kernel,
        spec_k=spec_k, spec_draft=spec_draft,
    )
    engine.warmup()
    rng = np.random.RandomState(0)
    total = t_meas = 0.0
    for rep in range(reps + 1):  # rep 0 = warmup, untimed
        for slot in list(engine.active_slots):
            engine.release(slot)
        for slot in range(b):
            spec = ReqSpec(
                prompt=rng.randint(0, vocab, size=(prompt_len,)).astype(
                    np.int32
                ),
                max_new_tokens=new_tokens,
            )
            engine.validate_spec(spec)
            engine.prefill(slot, spec)
        t0 = time.perf_counter()
        tokens = 0
        while engine.active_slots:
            for slot, toks, _eos in engine.spec_step():
                tokens += len(toks)
                if engine._cursor[slot] >= engine._max_new[slot]:
                    engine.release(slot)
        dt = time.perf_counter() - t0
        if rep:
            total += tokens
            t_meas += dt
    st = engine.spec_stats
    proposed = st["tokens_accepted"] + st["tokens_rejected"]
    accept_rate = st["tokens_accepted"] / max(proposed, 1)
    commits_per_verify = (
        st["tokens_committed"] * spec_k / max(proposed, 1)
    )
    return total / t_meas, accept_rate, commits_per_verify


def audit(model_name: str, prompt_len: int, new_tokens: int,
          batches, profile_dir=None, vocab: int = 32000,
          kv_layout: str = "dense", block_size: int = 16,
          kv_dtype: str = "bf16", weight_dtype: str = "bf16",
          kernel: str = "xla",
          spec_k: int = 0, spec_draft: str = "int8"):
    import flax.linen as nn
    import jax
    import jax.numpy as jnp
    from flax import traverse_util

    from distributeddeeplearning_tpu.inference import decode_variant, generate
    from distributeddeeplearning_tpu.models import get_model

    max_len = prompt_len + new_tokens
    # Speculative rows write spec_k lookahead positions past the last
    # token — the model (and the spec engine's cache) carries the
    # headroom; non-spec paths keep auditing the max_len view.
    model_len = max_len + spec_k
    model = get_model(model_name, num_classes=vocab, max_seq_len=model_len)
    variables = jax.jit(model.init, static_argnames=("train",))(
        jax.random.PRNGKey(0), jnp.zeros((1, model_len), jnp.int32),
        train=False,
    )
    params = nn.unbox(variables["params"])
    # Param bytes a decode step streams, dtype-aware: with int8 weights
    # the floor charges the quantized kernels/embedding PLUS their f32
    # per-channel scales (itemized — a bf16 floor quoted over int8
    # bytes would overstate pct_of_floor). Shape-only eval_shape of the
    # quantization pass; nothing is materialized here.
    param_scale_bytes = 0
    if weight_dtype == "int8":
        from distributeddeeplearning_tpu.ops import quant as quantlib

        split = quantlib.tree_byte_split(
            jax.eval_shape(quantlib.quantize_params, params)
        )
        param_bytes = split["int8"] + split["scale"] + split["other"]
        param_scale_bytes = split["scale"]
    else:
        param_bytes = tree_bytes(params)

    # KV-cache bytes for batch b: shape-only trace of the decode clone's
    # init (exactly how inference.generate / the engine size buffers);
    # int8 mode's f32 scale buffers come back itemized.
    decode_model = decode_variant(model, kv_dtype=kv_dtype)

    def cache_byte_split(b: int, length: int = max_len):
        shapes = jax.eval_shape(
            lambda r: decode_model.init(
                r, jnp.zeros((b, length), jnp.int32), train=False
            ),
            jax.random.PRNGKey(0),
        )["cache"]
        kv = scale = 0
        for path, leaf in traverse_util.flatten_dict(dict(shapes)).items():
            n = math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
            if path[-1].endswith("_scale"):
                scale += n
            else:
                kv += n
        return kv, scale

    quantized = kv_dtype != "bf16" or weight_dtype != "bf16"
    kernels = ("xla", "fused") if kernel == "both" else (kernel,)

    def native_kv_bytes(b: int) -> int:
        """Full-length K/V bytes in the COMPUTE dtype for batch ``b`` —
        the dequantized buffers the stitched kernel materialises under a
        quantized cache (shape-only; the fused kernel never builds
        them)."""
        if kv_layout == "paged":
            return paged_step_bytes(model, b, max_len, block_size,
                                    "bf16")[0]
        native_model = decode_variant(model)
        shapes = jax.eval_shape(
            lambda r: native_model.init(
                r, jnp.zeros((b, max_len), jnp.int32), train=False
            ),
            jax.random.PRNGKey(0),
        )["cache"]
        return sum(
            math.prod(leaf.shape) * np.dtype(leaf.dtype).itemsize
            for path, leaf in traverse_util.flatten_dict(
                dict(shapes)
            ).items()
            if path[-1] in ("cached_k", "cached_v")
        )

    rows = []
    platform = jax.devices()[0].platform
    on_tpu = platform == "tpu"
    # On the chip the floor is the attached chip's (an unknown kind
    # raises); off it the floor is analytic, for the chip named here.
    chip = jax.devices()[0].device_kind if on_tpu else roofline.V5E
    hbm_gbps = roofline.peaks(chip).hbm_gbps
    floor_basis = roofline.floor_basis(chip)
    print(f"# {model_name} decode audit on {platform}: params "
          f"{param_bytes / 2**20:.1f} MiB "
          f"(weights {weight_dtype}, kv {kv_dtype}), max_len {max_len}",
          flush=True)
    if not on_tpu:
        print(f"# NOTE: floor column is the ANALYTIC byte floor "
              f"({floor_basis}); on {platform} it is not a roofline "
              "position — % of floor suppressed", flush=True)
    print(f"# {'b':>4} {'tok/s':>10} {'tok/s/seq':>10} {'floor tok/s':>12} "
          f"{'% of floor':>10} {'cache MiB':>10}", flush=True)
    import contextlib

    for i, b in enumerate(batches):
        table_bytes = scale_bytes = 0
        if spec_k:
            # Speculative rows: the audited unit is bytes per ACCEPTED
            # token — one verify tick's streamed bytes over the
            # measured commits per verify. The cache view carries the
            # spec_k lookahead positions the verify writes into.
            kv, scale_bytes = cache_byte_split(b, max_len + spec_k)
            verify_bytes = param_bytes + kv + scale_bytes
            draft_cache = draft_resident = 0
            if spec_draft == "int8":
                from distributeddeeplearning_tpu.ops import (
                    quant as quantlib,
                )

                dsplit = quantlib.tree_byte_split(
                    jax.eval_shape(quantlib.quantize_params, params)
                )
                draft_resident = (
                    dsplit["int8"] + dsplit["scale"] + dsplit["other"]
                )
                dkv, dkv_scale = cache_byte_split(b, max_len + spec_k)
                draft_cache = dkv + dkv_scale
            native_bytes = tree_bytes(params)
            draft_tick = (
                draft_resident + spec_k * (native_bytes + draft_cache)
                if spec_draft == "int8" else 0
            )
            bytes_per_tick = verify_bytes + draft_tick
            tps, accept_rate, commits = measure_engine_spec(
                model, params, b, prompt_len, new_tokens, vocab,
                spec_k=spec_k, spec_draft=spec_draft, kv_dtype=kv_dtype,
                decode_kernel=kernels[0],
            )
            commits = max(commits, 1e-9)
            floor = b * commits * hbm_gbps * 1e9 / bytes_per_tick
            base_kv, base_scale = cache_byte_split(b)
            base_bytes = param_bytes + base_kv + base_scale
            row = sweep_row(b, tps, kv, bytes_per_tick, floor, on_tpu,
                            kv_scale_bytes=scale_bytes)
            row.update({
                "kernel": kernels[0],
                "spec_k": spec_k,
                "accept_rate": round(accept_rate, 4),
                "commits_per_verify": round(commits, 2),
                "bytes_per_accepted_token_mb": round(
                    bytes_per_tick / (b * commits) / 2**20, 2
                ),
                "draft_cache_mb": round(draft_cache / 2**20, 1),
                "draft_param_mb": round(draft_resident / 2**20, 1),
                # tokens a surviving byte buys vs the non-spec floor
                "floor_multiplier": round(
                    commits * base_bytes / bytes_per_tick, 2
                ),
            })
            rows.append(row)
            print(format_row(row) + f"  x{row['floor_multiplier']:.2f} "
                  f"floor (accept {accept_rate:.2f})", flush=True)
            continue
        # The engine path serves paged layouts, quantized dtypes AND any
        # non-default kernel (inference.generate has none of the three —
        # the serving engine is the product surface for them).
        use_engine = (
            kv_layout == "paged" or quantized or kernels != ("xla",)
        )
        if use_engine:
            if kv_layout == "paged":
                kv, table_bytes, scale_bytes = paged_step_bytes(
                    model, b, max_len, block_size, kv_dtype
                )
            else:
                kv, scale_bytes = cache_byte_split(b)
            base_bytes = param_bytes + kv + scale_bytes + table_bytes
            dequant_extra = (
                2 * native_kv_bytes(b) if kv_dtype != "bf16" else 0
            )
            for kern in kernels:
                # Stitched kernel under a quantized cache: the gather
                # dequantizes full-length K/V into compute-dtype HBM
                # buffers (write) the score math reads back (read) —
                # traffic the fused kernel does in-register. Charged to
                # the xla rows, itemized; the fused floor is the bare
                # pool stream.
                extra = dequant_extra if kern == "xla" else 0
                bytes_per_step = base_bytes + extra
                floor = b * hbm_gbps * 1e9 / bytes_per_step
                tps = measure_engine(
                    model, params, b, prompt_len, new_tokens, vocab,
                    kv_layout=kv_layout, block_size=block_size,
                    kv_dtype=kv_dtype, weight_dtype=weight_dtype,
                    decode_kernel=kern,
                )
                row = sweep_row(
                    b, tps, kv, bytes_per_step, floor, on_tpu,
                    table_bytes=table_bytes, kv_scale_bytes=scale_bytes,
                )
                row["kernel"] = kern
                if extra:
                    row["dequant_roundtrip_bytes"] = int(extra)
                rows.append(row)
                suffix = f"  [{kern}]" if len(kernels) > 1 else ""
                print(format_row(row) + suffix, flush=True)
            continue
        else:
            kv, _ = cache_byte_split(b)
            bytes_per_step = param_bytes + kv
            floor = b * hbm_gbps * 1e9 / bytes_per_step
            rng = np.random.RandomState(0)
            prompt = rng.randint(0, vocab, size=(b, prompt_len)).astype(
                np.int32
            )
            kw = dict(max_new_tokens=new_tokens, temperature=0.8, top_k=40,
                      rng=jax.random.PRNGKey(1))
            out = generate(model, params, prompt, **kw)  # compile + warmup
            int(np.asarray(out)[0, -1])
            prof = (
                jax.profiler.trace(os.path.join(profile_dir, f"b{b}"))
                if profile_dir else contextlib.nullcontext()
            )
            reps = 3
            with prof:
                t0 = time.perf_counter()
                for r in range(reps):
                    out = generate(model, params, prompt,
                                   **{**kw, "rng": jax.random.PRNGKey(2 + r)})
                int(np.asarray(out)[0, -1])  # host readback fence
                dt = time.perf_counter() - t0
            tps = reps * b * new_tokens / dt
        row = sweep_row(b, tps, kv, bytes_per_step, floor, on_tpu,
                        table_bytes=table_bytes, kv_scale_bytes=scale_bytes)
        rows.append(row)
        print(format_row(row), flush=True)
    out = {
        "audit": f"{model_name}_decode",
        "platform": platform,
        "prompt_len": prompt_len,
        "new_tokens": new_tokens,
        "kv_layout": kv_layout,
        "kv_dtype": kv_dtype,
        "weight_dtype": weight_dtype,
        "decode_kernel": kernel,
        "param_bytes_mb": round(param_bytes / 2**20, 1),
        "hbm_gbps": hbm_gbps,
        "floor_basis": floor_basis,
        # the roofline claim is only a measured position on the chip the
        # floor constant describes
        "floor_applicable": on_tpu,
        "sweep": rows,
    }
    if param_scale_bytes:
        out["param_scale_bytes"] = int(param_scale_bytes)
    if kv_layout == "paged":
        out["block_size"] = block_size
    if spec_k:
        out["spec_k"] = spec_k
        out["spec_draft"] = spec_draft
    return out


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--model", default="lm_small")
    p.add_argument("--prompt-len", type=int, default=128)
    p.add_argument("--new-tokens", type=int, default=128)
    p.add_argument("--batches", default="1,2,4,8,16,32,64")
    p.add_argument("--vocab", type=int, default=32000)
    p.add_argument("--kv-layout", choices=("dense", "paged"),
                   default="dense")
    p.add_argument("--block-size", type=int, default=16)
    p.add_argument("--kv-dtype", choices=("bf16", "int8", "fp8"),
                   default="bf16")
    p.add_argument("--weight-dtype", choices=("bf16", "int8", "fp8"),
                   default="bf16")
    p.add_argument("--kernel", choices=("xla", "fused", "both"),
                   default="xla",
                   help="decode attention lowering to audit "
                        "(SERVE_DECODE_KERNEL); 'both' emits one row "
                        "per kernel per batch for the compare gate")
    p.add_argument("--spec-k", type=int, default=0,
                   help="speculative lookahead (0 = off); rows become "
                        "bytes per ACCEPTED token at the measured "
                        "accept rate")
    p.add_argument("--spec-draft", choices=("int8", "ngram"),
                   default="int8")
    p.add_argument("--profile-dir", default=None)
    args = p.parse_args(argv)
    if args.spec_k and (args.kv_layout == "paged"
                        or args.weight_dtype != "bf16"):
        p.error("--spec-k rows audit the dense native-weight engine "
                "(the serving tier's spec-compare regime)")
    if args.spec_k and args.kernel == "both":
        p.error("--spec-k audits one kernel per run "
                "(--kernel xla or --kernel fused)")
    batches = [int(b) for b in args.batches.split(",") if b.strip()]
    out = audit(args.model, args.prompt_len, args.new_tokens, batches,
                profile_dir=args.profile_dir, vocab=args.vocab,
                kv_layout=args.kv_layout, block_size=args.block_size,
                kv_dtype=args.kv_dtype, weight_dtype=args.weight_dtype,
                kernel=args.kernel,
                spec_k=args.spec_k, spec_draft=args.spec_draft)
    print(json.dumps(out))
    return 0


if __name__ == "__main__":
    sys.exit(main())
