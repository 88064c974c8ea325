"""Fused Pallas paged-decode kernel parity oracles
(``SERVE_DECODE_KERNEL=fused`` — ops/pallas/paged_decode.py).

The fused kernel replaces the stitched XLA decode lowering (gather →
dequantize → mask → softmax → weighted sum) with ONE Pallas program
that walks the slot's block table, dequantizes K/V blocks in-register
and runs online-softmax masked attention. Its contract, pinned here
(CPU tier — the kernel runs in Pallas interpret mode, same program
text as the TPU lowering):

* **Reference parity** — the kernel output matches the XLA decode math
  (``models/vit.Attention._masked_decode_scores``: f32 scores, additive
  min-mask, f32 softmax) to f32 round-off, across the dense row cache,
  the paged block pool, the int8/fp8 quantized stores, and the
  speculative ``[B, K+1]`` verify window.
* **ULP-bounded outputs** — the fused/XLA divergence is reassociation
  only (online vs two-pass softmax), bounded in units-in-last-place,
  not just in loose absolute tolerance.
* **Masking** — positions beyond a row's ``q_pos`` (and beyond
  ``kv_len``) never contribute: garbage planted there — including the
  paged pool's trash block 0 — cannot perturb the output.
* **Vector-position contract** — scalar-index callers (the lockstep
  ``inference.generate`` path) stay on the XLA lowering; the kernel
  rejects ``q_pos`` that is not ``[B, t]``.
* **Engine bitwise parity** — a fused ``SlotEngine`` emits
  token-for-token what the XLA engine emits under greedy decoding (f32
  model: argmax over ULP-equal logits is bitwise), dense and paged,
  int8 and fp8, plain and speculative — with the program set closed at
  the same count on both kernels.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.models.transformer_lm import TransformerLM
from distributeddeeplearning_tpu.ops import quant
from distributeddeeplearning_tpu.ops.pallas.paged_decode import (
    MAX_QUERY_ROWS,
    fused_decode_attention,
)
from distributeddeeplearning_tpu.serving import ReqSpec, Request, Server, SlotEngine

B, H, D, L = 2, 4, 32, 16
VOCAB, MAX_LEN = 64, 32
# The top bucket is wider than the kernel's query window
# (MAX_QUERY_ROWS), so a fused paged engine's prefill takes the einsum.
BUCKETS = (4, 8, 32)


def _rand(rng, *shape):
    return jnp.asarray(rng.randn(*shape), jnp.float32)


def _ref_attention(q, k_all, v_all, q_pos, kv_len):
    """The XLA decode math (models/vit.Attention._masked_decode_scores),
    f32 end to end — the oracle the fused kernel must reproduce."""
    d = q.shape[-1]
    scores = jnp.einsum(
        "bqhd,bkhd->bhqk", q * d ** -0.5, k_all
    ).astype(jnp.float32)
    k_pos = jnp.arange(k_all.shape[1])
    mask = (
        (k_pos[None, None, :] <= q_pos[:, :, None])
        & (k_pos < kv_len)[None, None, :]
    )
    scores = jnp.where(mask[:, None], scores, jnp.finfo(jnp.float32).min)
    probs = jax.nn.softmax(scores, axis=-1)
    return jnp.einsum("bhqk,bkhd->bqhd", probs, v_all)


def _ulp_distance(a, b):
    """Element-wise f32 ULP distance via the monotone integer mapping
    of IEEE-754 bit patterns (sign-magnitude -> two's-complement)."""

    def mono(x):
        i = np.asarray(x, np.float32).view(np.int32).astype(np.int64)
        return np.where(i < 0, np.int64(-(2 ** 31)) - i, i)

    return np.abs(mono(a) - mono(b))


def _paged_from_dense(dense, block_size, trash=1e4):
    """Scatter a dense [B, L, H, D] cache into a block pool
    [B*mb + 1, block_size, H, D] plus per-row tables; block 0 holds
    garbage (the trash-block convention)."""
    b, length, h, d = dense.shape
    mb = length // block_size
    pool = np.full((b * mb + 1, block_size, h, d), trash, np.float32)
    table = np.zeros((b, mb), np.int32)
    for row in range(b):
        for j in range(mb):
            blk = 1 + row * mb + j
            pool[blk] = np.asarray(
                dense[row, j * block_size:(j + 1) * block_size]
            )
            table[row, j] = blk
    return jnp.asarray(pool), jnp.asarray(table)


def test_dense_row_matches_reference():
    rng = np.random.RandomState(0)
    q = _rand(rng, B, 1, H, D)
    k = _rand(rng, B, L, H, D)
    v = _rand(rng, B, L, H, D)
    pos = jnp.asarray([[5], [L - 1]], jnp.int32)
    out = fused_decode_attention(q, k, v, pos)
    ref = _ref_attention(q, k, v, pos, L)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_dense_outputs_ulp_bounded():
    """Fused vs XLA math differ by softmax reassociation only: every
    output element lands within a small ULP budget of the reference —
    the bound that makes greedy argmax parity a theorem, not luck."""
    rng = np.random.RandomState(1)
    q = _rand(rng, B, 1, H, D)
    k = _rand(rng, B, L, H, D)
    v = _rand(rng, B, L, H, D)
    pos = jnp.full((B, 1), L - 1, jnp.int32)
    out = fused_decode_attention(q, k, v, pos)
    ref = _ref_attention(q, k, v, pos, L)
    assert int(_ulp_distance(out, ref).max()) <= 256


def test_paged_pool_matches_dense():
    rng = np.random.RandomState(2)
    q = _rand(rng, B, 1, H, D)
    k = _rand(rng, B, L, H, D)
    v = _rand(rng, B, L, H, D)
    pos = jnp.asarray([[L - 1], [7]], jnp.int32)
    k_pool, table = _paged_from_dense(k, block_size=4)
    v_pool, _ = _paged_from_dense(v, block_size=4)
    out = fused_decode_attention(
        q, k_pool, v_pool, pos, block_table=table, block_size=4,
    )
    ref = _ref_attention(q, k, v, pos, L)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_trash_block_and_unowned_blocks_never_attended():
    """Table entries past a row's live length point at block 0 (trash);
    masking — not residency — is what keeps them out of the output."""
    rng = np.random.RandomState(3)
    q = _rand(rng, B, 1, H, D)
    k = _rand(rng, B, L, H, D)
    v = _rand(rng, B, L, H, D)
    live = 6  # positions 0..5 live; blocks past ceil(6/4) unassigned
    pos = jnp.full((B, 1), live - 1, jnp.int32)
    k_pool, table = _paged_from_dense(k, block_size=4, trash=1e4)
    v_pool, _ = _paged_from_dense(v, block_size=4, trash=1e4)
    table = np.array(table)
    table[:, 2:] = 0  # unowned tail -> trash block
    out = fused_decode_attention(
        q, k_pool, v_pool, pos, block_table=jnp.asarray(table),
        block_size=4,
    )
    ref = _ref_attention(q, k, v, pos, live)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_kv_len_caps_dense_tail():
    rng = np.random.RandomState(4)
    q = _rand(rng, B, 1, H, D)
    k = _rand(rng, B, L, H, D)
    v = _rand(rng, B, L, H, D)
    kv_len = 10
    poisoned_k = k.at[:, kv_len:].set(1e4)
    poisoned_v = v.at[:, kv_len:].set(1e4)
    pos = jnp.full((B, 1), kv_len - 1, jnp.int32)
    out = fused_decode_attention(q, poisoned_k, poisoned_v, pos,
                                 kv_len=kv_len)
    ref = _ref_attention(q, k, v, pos, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_quantized_store_parity(kv_dtype):
    """Quantized pools: the kernel's in-register dequantize must equal
    attention over the explicitly dequantized store."""
    rng = np.random.RandomState(5)
    q = _rand(rng, B, 1, H, D)
    k = _rand(rng, B, L, H, D)
    v = _rand(rng, B, L, H, D)
    kq, ks = quant.quantize_kv(k, kv_dtype)
    vq, vs = quant.quantize_kv(v, kv_dtype)
    pos = jnp.asarray([[L - 1], [9]], jnp.int32)
    out = fused_decode_attention(q, kq, vq, pos, k_scale=ks, v_scale=vs)
    ref = _ref_attention(
        q,
        quant.dequantize_store(kq, ks, jnp.float32),
        quant.dequantize_store(vq, vs, jnp.float32),
        pos, L,
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_spec_verify_window_matches_reference():
    """The [B, K+1] verify view: per-row ascending positions, causal
    within the window — the spec_verify program's attention shape."""
    rng = np.random.RandomState(6)
    kk = 3
    q = _rand(rng, B, kk + 1, H, D)
    k = _rand(rng, B, L, H, D)
    v = _rand(rng, B, L, H, D)
    start = jnp.asarray([4, 9], jnp.int32)
    pos = start[:, None] + jnp.arange(kk + 1)[None, :]
    out = fused_decode_attention(q, k, v, pos)
    ref = _ref_attention(q, k, v, pos, L)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=1e-5, atol=1e-6)


def test_vector_position_contract_and_scale_pairing():
    rng = np.random.RandomState(7)
    q = _rand(rng, B, 1, H, D)
    k = _rand(rng, B, L, H, D)
    v = _rand(rng, B, L, H, D)
    with pytest.raises(ValueError, match="q_pos"):
        fused_decode_attention(q, k, v, jnp.int32(0))
    with pytest.raises(ValueError, match="q_pos"):
        fused_decode_attention(q, k, v, jnp.zeros((B,), jnp.int32))
    kq, ks = quant.quantize_kv(k, "int8")
    with pytest.raises(ValueError, match="k_scale"):
        fused_decode_attention(q, kq, v, jnp.zeros((B, 1), jnp.int32),
                               k_scale=ks)
    wide = MAX_QUERY_ROWS + 1
    with pytest.raises(ValueError, match="query rows"):
        fused_decode_attention(
            _rand(rng, B, wide, H, D), k, v, jnp.zeros((B, wide), jnp.int32)
        )


# ---------------------------------------------------------------------------
# Engine-level bitwise parity (f32 model: greedy argmax over ULP-equal
# logits is exact)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def model():
    return TransformerLM(
        variant="tiny", vocab_size=VOCAB, max_seq_len=MAX_LEN,
        dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def params(model):
    import flax.linen as nn

    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, MAX_LEN), jnp.int32),
        train=False,
    )
    return nn.unbox(variables["params"])


def _greedy_streams(engine):
    rng = np.random.RandomState(11)
    server = Server(engine, prefills_per_step=2)
    handles = [
        server.submit(Request(
            prompt=rng.randint(0, VOCAB, size=(n,)).astype(np.int32),
            max_new_tokens=m, temperature=0.0, top_k=None,
        ))
        for n, m in [(3, 6), (7, 8), (12, 4), (16, 6), (5, 9)]
    ]
    server.drain()
    assert all(h.status == "done" for h in handles)
    return [list(h.new_tokens) for h in handles]


def _engine_pair(model, params, **kw):
    engines = []
    for kern in ("xla", "fused"):
        eng = SlotEngine(
            model, params, num_slots=4, max_len=MAX_LEN, buckets=BUCKETS,
            decode_kernel=kern, **kw,
        )
        eng.warmup()
        engines.append(eng)
    return engines


@pytest.mark.parametrize(
    "kw",
    [
        pytest.param({}, id="dense-bf16"),
        pytest.param({"kv_dtype": "int8"}, id="dense-int8"),
        pytest.param(
            {"kv_layout": "paged", "block_size": 4, "kv_dtype": "fp8"},
            id="paged-fp8",
        ),
    ],
)
def test_engine_fused_bitwise_matches_xla(model, params, kw):
    xla, fused = _engine_pair(model, params, **kw)
    assert _greedy_streams(xla) == _greedy_streams(fused)
    # same closed program set on both kernels
    for eng in (xla, fused):
        assert eng.compile_count == eng.programs_expected
        assert eng.programs_expected == len(BUCKETS) + 1


def test_engine_spec_verify_fused_bitwise_matches_xla(model, params):
    xla, fused = _engine_pair(
        model, params, kv_layout="paged", block_size=4, kv_dtype="int8",
        spec_k=2, spec_draft="ngram",
    )
    assert _greedy_streams(xla) == _greedy_streams(fused)
    for eng in (xla, fused):
        assert eng.compile_count == eng.programs_expected


def test_engine_decode_logits_ulp_bounded(model, params):
    """Per-step decode logits from the fused and XLA engines differ by
    a few f32 ULPs *of the largest logit* on identical pool state — the
    claim the bitwise token-stream parity rests on. Argmax depends on
    absolute gaps, so the budget is set at the logits' scale: a
    per-element ULP count explodes on logits that happen to sit near
    zero and says nothing about the ordering."""
    xla, fused = _engine_pair(model, params, kv_dtype="int8")
    prompt = np.arange(1, 7, dtype=np.int32)
    spec = ReqSpec(prompt=prompt, max_new_tokens=4)
    for eng in (xla, fused):
        eng.prefill(0, spec)
    logits = []
    for eng in (xla, fused):
        cache = eng._with_positions(
            eng._pool, jnp.asarray(np.full(4, len(prompt), np.int32))
        )
        out, _ = eng.decode_model.apply(
            {"params": eng._live_params(eng.params), "cache": cache},
            jnp.asarray(np.full(4, 3, np.int32))[:, None],
            train=False, mutable=["cache"],
        )
        logits.append(np.asarray(out[0, -1], np.float32))
    err = np.abs(logits[0] - logits[1]).max()
    assert err <= 64 * np.spacing(np.abs(logits[0]).max())
