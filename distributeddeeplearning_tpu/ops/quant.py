"""Symmetric int8 / fp8 quantization for the inference/serving tier.

Decode is bandwidth-bound (PROFILE.md; ``scripts/decode_audit.py``):
every step streams the full parameter set plus the whole KV pool, so
throughput scales with *bytes removed*, not FLOPs saved. This module is
the byte-removal primitive: symmetric int8 with f32 scales —

* **weights** per output channel (LLM.int8-style: one scale per column
  of each matmul kernel, one per vocab row of the tied embedding), a
  one-shot tree pass at engine build (:func:`quantize_params`) with
  dequant-on-use inside the compiled decode programs
  (:func:`dequantize_params`);
* **KV cache** per head per position (``models/vit.Attention`` with
  ``kv_dtype="int8"``; per *block* position in the paged layout —
  the same per-head scale, resident in the block pool): writes
  quantize, the decode gather dequantizes to the compute dtype before
  the masked-score math.

Everything here is pure ``jnp``, shape-preserving (scales keep reduced
axes as size-1 so dequant is a plain broadcast multiply), and runs
inside jit/AOT programs — no Python branches on data. Quantize →
dequantize is deterministic (round-half-to-even), so two engines fed
the same stream hold bitwise-identical pools
(``tests/test_serving_quant.py``).

Scales are **itemized, never hidden**: a quantized tensor's true byte
cost is ``quantized bytes + f32 scale bytes``, and ``decode_audit``
accounts both against the floor (claiming the bf16 floor with int8
bytes would overstate ``pct_of_floor``).

The **fp8 tier** reuses the same symmetric-scale shape contract with an
8-bit float payload instead of an integer code: weights store
``float8_e4m3fn`` (the mantissa-priority format — per-channel scales
already normalize the range, so e4m3's extra mantissa bit beats e5m2's
extra exponent bit; e5m2 remains the range-priority alternative and
both dtypes are exported), KV stores ``float8_e4m3fn`` for the same
reason. fp8 is **platform-gated**: :func:`fp8_supported` probes an
actual jitted round-trip on the active backend, and the serving tier
refuses an fp8 request where the probe fails.

Dtype *names* are validated through one registry (``KV_DTYPES`` /
``WEIGHT_DTYPES`` + :func:`validate_store_dtype`) so every boundary —
the ``Attention`` module, ``SlotEngine``, ``ServeConfig`` env parsing —
rejects unknown dtypes with the same supported list named, instead of
each special-casing int8.
"""

from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax.numpy as jnp

# Marker keys a quantized tensor leaf expands into inside a param tree.
# Kept dict-shaped (not a custom pytree node) so the tree still
# flattens/unflattens with stock flax/jax utilities and jit treats the
# quantized payload + scale as two ordinary leaves. fp8 trees use their
# own marker pair so dequantize_params can pick the right decode rule
# per leaf and mixed trees are structurally impossible to mistake.
Q8 = "_q8"
Q8_SCALE = "_q8_scale"
QF8 = "_qf8"
QF8_SCALE = "_qf8_scale"

# int8 symmetric range: ±127 (the -128 code is unused so the range is
# symmetric and q == -q round-trips exactly).
_QMAX = 127.0

# fp8 formats. e4m3fn: finite-only, max 448, 3 mantissa bits — the
# default for both weights and KV (per-channel/per-head scales pin the
# range, so mantissa is the binding constraint). e5m2: max 57344, 2
# mantissa bits — the range-priority alternative, exported for callers
# that quantize without scales.
FP8_E4M3 = jnp.float8_e4m3fn
FP8_E5M2 = jnp.float8_e5m2
FP8_WEIGHT_DTYPE = FP8_E4M3
FP8_KV_DTYPE = FP8_E4M3

# The dtype-name registry every serving boundary validates against.
# "bf16" is the native (unquantized) tier: KV stores the compute dtype,
# weights stay as initialized.
KV_DTYPES = ("bf16", "int8", "fp8")
WEIGHT_DTYPES = ("bf16", "int8", "fp8")


def validate_store_dtype(kind: str, value: str, *, extra: Tuple[str, ...] = ()) -> str:
    """One validation rule for every dtype-name boundary: ``kind`` is
    the knob name (``"kv_dtype"`` / ``"weight_dtype"`` — it leads the
    error so ``SERVE_*`` misconfigurations point at the right env var),
    ``extra`` admits boundary-specific aliases (the ``Attention`` module
    treats ``""`` as native). Returns ``value`` so call sites can
    validate-and-assign in one expression."""
    table = KV_DTYPES if kind == "kv_dtype" else WEIGHT_DTYPES
    allowed = tuple(extra) + tuple(table)
    if value not in allowed:
        raise ValueError(
            f"{kind} must be one of {allowed}, got {value!r}"
        )
    return value


@functools.lru_cache(maxsize=1)
def fp8_supported() -> bool:
    """Whether the active backend executes fp8 storage + casts. Probes a
    real jitted round-trip (compile + numerics) instead of trusting
    dtype existence: a backend can expose the dtype yet refuse to lower
    it. The serving engine raises on ``False`` — an fp8 request is never
    served from another format."""
    import jax
    import numpy as np

    try:
        q = jnp.asarray([0.5, -2.0], jnp.float32).astype(FP8_E4M3)
        out = jax.jit(lambda a: a.astype(jnp.float32) * 2.0)(q)
    except (jax.errors.JaxRuntimeError, NotImplementedError):
        return False
    return bool(np.allclose(np.asarray(out), [1.0, -4.0]))


def kv_store_dtype(kv_dtype: str) -> Optional[Any]:
    """Storage dtype the KV cache holds for a registry name: ``None``
    means native (store the compute dtype; no scales)."""
    validate_store_dtype("kv_dtype", kv_dtype, extra=("",))
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8":
        return FP8_KV_DTYPE
    return None


def quantize_kv(x: jnp.ndarray, kv_dtype: str, axis=-1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Registry-dispatched KV quantization (the ``Attention`` write
    path): int8 → :func:`quantize_int8`, fp8 → :func:`quantize_fp8`."""
    if kv_dtype == "fp8":
        return quantize_fp8(x, axis=axis, dtype=FP8_KV_DTYPE)
    return quantize_int8(x, axis=axis)


def dequantize_store(q: jnp.ndarray, scale: jnp.ndarray,
                     dtype=jnp.float32) -> jnp.ndarray:
    """``q * scale`` in f32, cast to ``dtype`` — the one decode rule
    both payload formats share (int8 codes and fp8 floats multiply out
    identically once upcast)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_int8(x: jnp.ndarray, axis=-1) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric int8 quantization of ``x`` with one f32 scale per slice
    along ``axis`` (int or tuple — the *reduced* axes). Returns
    ``(q, scale)`` with ``scale`` keeping the reduced axes at size 1, so
    ``q * scale`` broadcasts back to ``x``'s shape.

    ``scale = amax / 127`` (all-zero slices get scale 1 so dequant is an
    exact zero, not NaN); values quantize with round-half-to-even and a
    clip that only the amax element can touch.
    """
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _QMAX, 1.0).astype(jnp.float32)
    q = jnp.clip(jnp.round(xf / scale), -_QMAX, _QMAX).astype(jnp.int8)
    return q, scale


def dequantize_int8(q: jnp.ndarray, scale: jnp.ndarray,
                    dtype=jnp.float32) -> jnp.ndarray:
    """``q * scale`` in f32, cast to ``dtype`` (broadcast: ``scale``
    keeps reduced axes at size 1 — :func:`quantize_int8`'s contract)."""
    return (q.astype(jnp.float32) * scale).astype(dtype)


def quantize_fp8(x: jnp.ndarray, axis=-1,
                 dtype=FP8_E4M3) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Symmetric fp8 quantization with the same shape contract as
    :func:`quantize_int8`: one f32 scale per reduced slice, kept at
    size 1 so ``q * scale`` broadcasts back. ``scale = amax / fmax``
    maps the slice's amax onto the format's largest finite value
    (e4m3fn: 448); the cast rounds to nearest-even and the pre-clip
    keeps every value finite (e4m3fn has no inf — an overflow would
    round to NaN, not saturate). All-zero slices get scale 1 so dequant
    is an exact zero. Deterministic, pure jnp, eval_shape-safe."""
    fmax = float(jnp.finfo(dtype).max)
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / fmax, 1.0).astype(jnp.float32)
    q = jnp.clip(xf / scale, -fmax, fmax).astype(dtype)
    return q, scale


def dequantize_fp8(q: jnp.ndarray, scale: jnp.ndarray,
                   dtype=jnp.float32) -> jnp.ndarray:
    """fp8 decode — same rule as int8 (:func:`dequantize_store`)."""
    return dequantize_store(q, scale, dtype)


# ---------------------------------------------------------------------------
# Param-tree pass (inference weights)
# ---------------------------------------------------------------------------

def _is_quantizable(path: Tuple[str, ...], leaf) -> bool:
    """Inference-weight rule: 2-D matmul kernels (attention qkv/proj,
    MLP fc1/fc2, the LM head) per output channel, plus the tied token
    embedding per vocab row — the tensors a decode step actually
    streams in bulk. Biases, norms, positional tables and conv kernels
    stay f32 (byte-negligible; norms are numerically load-bearing)."""
    name = path[-1]
    if name == "kernel" and getattr(leaf, "ndim", 0) == 2:
        return True
    if name == "tok_embed" and getattr(leaf, "ndim", 0) == 2:
        return True
    return False


def _quant_axis(path: Tuple[str, ...]) -> int:
    """Reduced axis for the per-channel scale: kernels ``[in, out]``
    reduce ``in`` (one scale per output channel); the embedding
    ``[vocab, hidden]`` reduces ``hidden`` (one scale per vocab row —
    per-channel for BOTH of its uses: the lookup's row and the tied
    output projection's logit column share the scale)."""
    return 0 if path[-1] == "kernel" else -1


def quantize_params(params: Any, dtype: str = "int8") -> Any:
    """One-shot inference quantization of a param tree: every leaf
    :func:`_is_quantizable` becomes ``{_q8: int8, _q8_scale: f32}``
    (or ``{_qf8: fp8, _qf8_scale: f32}`` under ``dtype="fp8"``) in
    place; everything else passes through untouched. Pure jnp — safe to
    ``jax.jit`` (the engine does) or ``jax.eval_shape`` (the audit
    does, for bytes without materializing anything)."""
    from flax import traverse_util
    from flax.core import unfreeze

    validate_store_dtype("weight_dtype", dtype)
    if dtype == "bf16":
        raise ValueError(
            "quantize_params quantizes — the native 'bf16' tier means "
            "no pass at all; call sites gate on weight_dtype first"
        )
    flat = traverse_util.flatten_dict(unfreeze(params))
    if any(path[-1] in (Q8, Q8_SCALE, QF8, QF8_SCALE) for path in flat):
        # Double-quantizing would treat the quantized payload as weights
        # and re-scale it into garbage. The serving tier guards the one
        # way this used to be reachable (a quantized self-speculative
        # draft of an already-quantized target —
        # serving/spec.validate_spec_config); this keeps the invariant
        # local to the pass itself.
        raise ValueError(
            "param tree is already quantized (quantized-marker leaves "
            "present) — quantize_params is one-shot"
        )
    marker, marker_scale = (QF8, QF8_SCALE) if dtype == "fp8" else (Q8, Q8_SCALE)
    out: Dict[Tuple[str, ...], Any] = {}
    for path, leaf in flat.items():
        if _is_quantizable(path, leaf):
            if dtype == "fp8":
                q, scale = quantize_fp8(
                    leaf, axis=_quant_axis(path), dtype=FP8_WEIGHT_DTYPE
                )
            else:
                q, scale = quantize_int8(leaf, axis=_quant_axis(path))
            out[path + (marker,)] = q
            out[path + (marker_scale,)] = scale
        else:
            out[path] = leaf
    return traverse_util.unflatten_dict(out)


def dequantize_params(params: Any, dtype=jnp.float32) -> Any:
    """Inverse tree pass (dequant-on-use): every ``{_q8, _q8_scale}`` /
    ``{_qf8, _qf8_scale}`` pair collapses back to a dense ``dtype``
    tensor. Called at the TOP of a compiled decode program, so XLA sees
    the quantized payload + scale as the *streamed* operands and the
    dequantized copy as a fused temporary — the per-step HBM traffic is
    the quantized bytes."""
    from flax import traverse_util
    from flax.core import unfreeze

    flat = traverse_util.flatten_dict(unfreeze(params))
    out: Dict[Tuple[str, ...], Any] = {}
    for path, leaf in flat.items():
        if path[-1] in (Q8, QF8):
            scale_key = Q8_SCALE if path[-1] == Q8 else QF8_SCALE
            out[path[:-1]] = dequantize_store(
                leaf, flat[path[:-1] + (scale_key,)], dtype
            )
        elif path[-1] in (Q8_SCALE, QF8_SCALE):
            continue
        else:
            out[path] = leaf
    return traverse_util.unflatten_dict(out)


def is_quantized(params: Any) -> bool:
    """True if the tree went through :func:`quantize_params` (either
    payload dtype)."""
    from flax import traverse_util
    from flax.core import unfreeze

    return any(
        path[-1] in (Q8, QF8)
        for path in traverse_util.flatten_dict(unfreeze(params))
    )


def tree_byte_split(tree: Any) -> Dict[str, int]:
    """Byte accounting with scales itemized (``decode_audit``'s floor
    contract): ``{"int8": ..., "fp8": ..., "scale": ..., "other": ...}``
    summed over leaves — works on real arrays and eval_shape structs
    alike. ``quantized_bytes`` below folds the two payload buckets for
    callers that only need "how many bytes are 8-bit"."""
    import numpy as np
    from flax import traverse_util
    from flax.core import unfreeze

    fp8_dtypes = tuple(
        np.dtype(d) for d in (FP8_E4M3, FP8_E5M2)
    )
    out = {"int8": 0, "fp8": 0, "scale": 0, "other": 0}
    for path, leaf in traverse_util.flatten_dict(unfreeze(tree)).items():
        n = int(np.prod(leaf.shape)) if leaf.shape else 1
        dt = np.dtype(leaf.dtype)
        nbytes = n * dt.itemsize
        if path[-1] == Q8 or dt == np.int8:
            out["int8"] += nbytes
        elif path[-1] == QF8 or dt in fp8_dtypes:
            out["fp8"] += nbytes
        elif path[-1] in (Q8_SCALE, QF8_SCALE) or path[-1].endswith("_scale"):
            out["scale"] += nbytes
        else:
            out["other"] += nbytes
    return out


def quantized_bytes(split: Dict[str, int]) -> int:
    """The 8-bit payload total of a :func:`tree_byte_split` result —
    int8 and fp8 buckets folded (their byte cost is identical; only the
    rounding model differs)."""
    return split["int8"] + split["fp8"]
