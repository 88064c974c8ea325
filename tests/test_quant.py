"""Oracles for ``ops/quant.py`` — the int8/fp8 primitives the
quantized decode tiers stand on.

What must hold (and is pinned here, CPU tier):

* **Round-trip error bounds** per dtype: symmetric int8 with per-slice
  scale ``amax/127`` reconstructs every element within half a
  quantization step (``scale / 2``) — the bound is *per slice*, from
  that slice's own scale, not a global fudge factor.
* **Per-channel vs per-tensor**: channels with wildly different
  magnitudes are exactly why the scales are per-channel — a per-tensor
  scale's error on the small channel is orders worse. The test builds
  that adversarial tensor and checks the ordering quantitatively.
* **Param-tree pass**: quantizes exactly the inference-streamed
  tensors (2-D matmul kernels per output channel, the tied embedding
  per vocab row), leaves norms/biases untouched, byte-splits honestly
  (int8 + scale itemized), and dequantizes back within the bound.
* **Determinism**: quantize → dequantize is bitwise-reproducible
  (round-half-to-even has no data races) — the property the serving
  engine's bitwise pool oracle (tests/test_serving_quant.py) builds on.
* **Full-forward logit error bound**: the weight quantization's
  end-to-end damage on a real LM forward stays small — the per-step
  logit error the serve_bench quality oracle documents (exact parity is
  mathematically unavailable under quantization; the bound is the
  contract instead, like the accum ULP note).
* **fp8 tier** (e4m3fn payload, ``SERVE_*_DTYPE=fp8``): the same scale
  contract at float rounding — per-slice round-trip bounds, extreme
  values kept finite (e4m3fn has no inf; overflow would round to NaN,
  not saturate), registry dispatch, the backend support probe, and the
  ``_qf8``-marker param-tree pass with honest byte splits.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.ops import quant as quantlib


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_roundtrip_error_bound_per_dtype(dtype):
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(16, 64) * 3.0, dtype)
    q, scale = quantlib.quantize_int8(x, axis=-1)
    assert q.dtype == jnp.int8 and scale.dtype == jnp.float32
    assert q.shape == x.shape and scale.shape == (16, 1)
    dq = quantlib.dequantize_int8(q, scale, jnp.float32)
    # |x - dq| <= scale/2 per slice: round() lands within half a step.
    # bf16 inputs are exact f32 values, so the same bound applies.
    err = np.abs(np.asarray(x, np.float32) - np.asarray(dq))
    bound = np.asarray(scale)[..., 0] / 2 + 1e-7
    assert (err.max(axis=-1) <= bound).all()


def test_quantize_handles_zero_slices_and_extremes():
    x = jnp.zeros((4, 8), jnp.float32)
    q, scale = quantlib.quantize_int8(x, axis=-1)
    assert np.asarray(q).max() == 0
    dq = quantlib.dequantize_int8(q, scale)
    assert np.array_equal(np.asarray(dq), np.zeros((4, 8), np.float32))
    # the amax element maps exactly onto ±127 (symmetric range)
    y = jnp.asarray([[1.0, -2.0, 0.5, 2.0]], jnp.float32)
    qy, sy = quantlib.quantize_int8(y, axis=-1)
    assert np.asarray(qy).min() == -127 and np.asarray(qy).max() == 127


def test_per_channel_beats_per_tensor_on_mixed_magnitudes():
    rng = np.random.RandomState(1)
    # channel 0 ~ O(100), channel 1 ~ O(0.01): a shared scale burns
    # the small channel's precision
    x = np.stack([rng.randn(256) * 100.0, rng.randn(256) * 0.01])
    xj = jnp.asarray(x, jnp.float32)
    q_pc, s_pc = quantlib.quantize_int8(xj, axis=-1)      # per channel
    q_pt, s_pt = quantlib.quantize_int8(xj, axis=(0, 1))  # per tensor
    assert s_pc.shape == (2, 1) and s_pt.shape == (1, 1)
    err_pc = np.abs(x[1] - np.asarray(
        quantlib.dequantize_int8(q_pc, s_pc))[1])
    err_pt = np.abs(x[1] - np.asarray(
        quantlib.dequantize_int8(q_pt, s_pt))[1])
    # per-tensor error on the small channel is ~scale_big/scale_small
    # worse; 100x margin keeps the assertion far from flakiness
    assert err_pt.max() > 100 * max(err_pc.max(), 1e-9)


def test_quantize_deterministic_bitwise():
    rng = np.random.RandomState(2)
    x = jnp.asarray(rng.randn(32, 48), jnp.float32)
    q1, s1 = quantlib.quantize_int8(x, axis=-1)
    q2, s2 = quantlib.quantize_int8(x, axis=-1)
    assert np.array_equal(np.asarray(q1), np.asarray(q2))
    assert np.array_equal(np.asarray(s1), np.asarray(s2))


@pytest.fixture(scope="module")
def lm_and_params():
    import flax.linen as nn

    from distributeddeeplearning_tpu.models.transformer_lm import (
        TransformerLM,
    )

    model = TransformerLM(
        variant="tiny", vocab_size=256, max_seq_len=32, dtype=jnp.float32
    )
    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 32), jnp.int32), train=False
    )
    return model, nn.unbox(variables["params"])


def test_param_tree_pass_structure_and_bytes(lm_and_params):
    from flax import traverse_util

    _, params = lm_and_params
    qtree = quantlib.quantize_params(params)
    assert quantlib.is_quantized(qtree)
    assert not quantlib.is_quantized(params)
    flat_in = traverse_util.flatten_dict(params)
    flat_q = traverse_util.flatten_dict(qtree)
    for path, leaf in flat_in.items():
        if quantlib._is_quantizable(path, leaf):
            q = flat_q[path + (quantlib.Q8,)]
            s = flat_q[path + (quantlib.Q8_SCALE,)]
            assert q.dtype == jnp.int8 and q.shape == leaf.shape
            assert s.dtype == jnp.float32
            # per-OUTPUT-channel for kernels, per-vocab-row for embed
            if path[-1] == "kernel":
                assert s.shape == (1, leaf.shape[1])
            else:
                assert s.shape == (leaf.shape[0], 1)
        else:
            # norms / biases / pos tables untouched, bit for bit
            assert np.array_equal(
                np.asarray(flat_q[path]), np.asarray(leaf)
            )
    split = quantlib.tree_byte_split(qtree)
    native = quantlib.tree_byte_split(params)
    assert split["int8"] > 0 and split["scale"] > 0
    # f32 -> int8 on the quantized leaves: payload is a quarter
    assert split["int8"] * 4 + split["other"] <= native["other"]
    # scales are itemized small change, not a hidden second payload
    assert split["scale"] < split["int8"] / 8


def test_param_tree_roundtrip_and_eval_shape(lm_and_params):
    from flax import traverse_util

    _, params = lm_and_params
    dq = quantlib.dequantize_params(quantlib.quantize_params(params))
    flat_in = traverse_util.flatten_dict(params)
    flat_dq = traverse_util.flatten_dict(dq)
    assert set(flat_in) == set(flat_dq)
    for path, leaf in flat_in.items():
        got = flat_dq[path]
        assert got.shape == leaf.shape
        if quantlib._is_quantizable(path, leaf):
            rel = np.abs(np.asarray(got) - np.asarray(leaf)).max()
            amax = np.abs(np.asarray(leaf)).max()
            assert rel <= amax / 127  # half-step bound, loosened to 1 step
    # the audit's shape-only path: eval_shape must run the pass without
    # materializing anything
    shapes = jax.eval_shape(quantlib.quantize_params, params)
    assert quantlib.tree_byte_split(shapes) == quantlib.tree_byte_split(
        quantlib.quantize_params(params)
    )
    # one-shot invariant: re-quantizing an already-quantized tree would
    # re-scale the int8 payload into garbage — rejected loudly (the
    # speculative tier's int8-draft-of-int8-target conflict rule guards
    # the serving-side path; this pins the pass itself)
    with pytest.raises(ValueError, match="already quantized"):
        quantlib.quantize_params(quantlib.quantize_params(params))


def test_full_forward_logit_error_bound(lm_and_params):
    """Weight quantization's end-to-end per-step logit damage on a real
    LM forward stays within a documented bound. The bound (0.05 at this
    size) is what makes the serve_bench match-rate oracle meaningful:
    errors this small flip an argmax only when the top-2 gap is
    comparably tiny."""
    model, params = lm_and_params
    dq = quantlib.dequantize_params(quantlib.quantize_params(params))
    toks = jnp.asarray(
        np.random.RandomState(3).randint(0, 256, size=(2, 24)), jnp.int32
    )
    ref = model.apply({"params": params}, toks, train=False)
    got = model.apply({"params": dq}, toks, train=False)
    err = float(jnp.max(jnp.abs(
        ref.astype(jnp.float32) - got.astype(jnp.float32)
    )))
    assert err < 0.05


# ---------------------------------------------------------------------------
# fp8 tier (e4m3 payload, same scale contract as int8)
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fp8_roundtrip_error_bound_per_dtype(dtype):
    """e4m3fn carries 3 mantissa bits: after the amax/448 scaling every
    normal value reconstructs within 2^-4 relative; near-zero values
    within half a subnormal step of the scaled grid. The bound is per
    element from the slice's own scale — same shape contract as int8."""
    rng = np.random.RandomState(4)
    x = jnp.asarray(rng.randn(16, 64) * 3.0, dtype)
    q, scale = quantlib.quantize_fp8(x, axis=-1)
    assert q.dtype == jnp.float8_e4m3fn and scale.dtype == jnp.float32
    assert q.shape == x.shape and scale.shape == (16, 1)
    dq = quantlib.dequantize_fp8(q, scale, jnp.float32)
    xf = np.asarray(x, np.float32)
    err = np.abs(xf - np.asarray(dq))
    sc = np.asarray(scale)
    bound = np.maximum(np.abs(xf) * 2.0 ** -4, sc * 2.0 ** -10) + 1e-9
    assert (err <= bound).all()


def test_fp8_extreme_values_stay_finite_and_exact():
    # all-zero slices: scale 1, exact zero reconstruction (no NaN)
    z = jnp.zeros((4, 8), jnp.float32)
    qz, sz = quantlib.quantize_fp8(z, axis=-1)
    assert np.array_equal(np.asarray(sz), np.ones((4, 1), np.float32))
    assert np.array_equal(
        np.asarray(quantlib.dequantize_fp8(qz, sz)),
        np.zeros((4, 8), np.float32),
    )
    # the amax element maps exactly onto ±fmax (448 for e4m3fn) and
    # reconstructs exactly; e4m3fn has no inf, so the pre-clip is what
    # keeps an overflow from rounding to NaN
    y = jnp.asarray([[1e30, -1e30, 1e-30, 0.25]], jnp.float32)
    qy, sy = quantlib.quantize_fp8(y, axis=-1)
    qf = np.asarray(qy, np.float32)
    assert np.isfinite(qf).all()
    fmax = float(jnp.finfo(jnp.float8_e4m3fn).max)
    assert qf.max() == fmax and qf.min() == -fmax
    dy = np.asarray(quantlib.dequantize_fp8(qy, sy))
    assert np.isfinite(dy).all()
    np.testing.assert_allclose(dy[0, 0], 1e30, rtol=1e-6)
    # e5m2 (the wider-exponent KV option) honors the same contract
    q5, s5 = quantlib.quantize_fp8(y, axis=-1, dtype=jnp.float8_e5m2)
    assert q5.dtype == jnp.float8_e5m2
    assert np.isfinite(np.asarray(q5, np.float32)).all()


def test_fp8_registry_dispatch_and_support_probe():
    assert quantlib.kv_store_dtype("fp8") == quantlib.FP8_KV_DTYPE
    assert quantlib.kv_store_dtype("int8") == jnp.int8
    assert quantlib.kv_store_dtype("bf16") is None
    q, s = quantlib.quantize_kv(jnp.ones((2, 4)), "fp8")
    assert q.dtype == quantlib.FP8_KV_DTYPE
    with pytest.raises(ValueError, match="kv_dtype"):
        quantlib.validate_store_dtype("kv_dtype", "int4")
    # CPU executes fp8 casts: the probe must say so (the refusal where
    # it does not is exercised by monkeypatching in serving tests)
    assert quantlib.fp8_supported() is True


def test_param_tree_fp8_pass_markers_and_bytes(lm_and_params):
    model, params = lm_and_params
    qtree = quantlib.quantize_params(params, dtype="fp8")
    from flax import traverse_util

    flat = traverse_util.flatten_dict(qtree)
    markers = {p[-1] for p in flat}
    assert quantlib.QF8 in markers and quantlib.QF8_SCALE in markers
    assert quantlib.Q8 not in markers
    assert quantlib.is_quantized(qtree)
    split = quantlib.tree_byte_split(qtree)
    native = quantlib.tree_byte_split(params)
    assert split["fp8"] > 0 and split["int8"] == 0
    assert quantlib.quantized_bytes(split) == split["fp8"]
    # payload + scales + passthrough strictly below the f32 original
    assert sum(split.values()) < sum(native.values())
    # mixing tiers is still one-shot
    with pytest.raises(ValueError, match="already quantized"):
        quantlib.quantize_params(qtree, dtype="fp8")
    # dequant restores every leaf's shape; per-slice error bound holds
    dq = quantlib.dequantize_params(qtree)
    dflat = traverse_util.flatten_dict(dq)
    pflat = traverse_util.flatten_dict(params)
    assert set(dflat) == set(pflat)
    for path, leaf in pflat.items():
        if not quantlib._is_quantizable(path, leaf):
            continue
        axis = quantlib._quant_axis(path)
        ref = np.asarray(leaf, np.float32)
        got = np.asarray(dflat[path], np.float32)
        amax = np.abs(ref).max(axis=axis, keepdims=True)
        assert (np.abs(ref - got) <= amax * 2.0 ** -4 + 1e-9).all(), path
