"""KV-cache generation tests (inference.generate + Attention decode).

Oracle: incremental decoding with the cache must produce exactly the
same tokens as re-running the full forward pass over the growing
sequence — any off-by-one in the cache index, position embedding
counter, or decode mask breaks the equality.
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.inference import generate
from distributeddeeplearning_tpu.models.transformer_lm import TransformerLM

VOCAB, MAX_LEN = 64, 32


def _model(**kw):
    return TransformerLM(
        variant="tiny", vocab_size=VOCAB, max_seq_len=MAX_LEN,
        dtype=jnp.float32, **kw,
    )


def _params(model, seed=0):
    import flax.linen as nn

    variables = jax.jit(model.init, static_argnames="train")(
        jax.random.PRNGKey(seed), jnp.zeros((2, MAX_LEN), jnp.int32),
        train=False,
    )
    return nn.unbox(variables["params"])


def _greedy_reference(model, params, prompt, n_new):
    """Token-by-token greedy via full re-forward (no cache)."""
    seq = jnp.asarray(prompt)
    forward = jax.jit(lambda p, seq: model.apply({"params": p}, seq, train=False))
    for _ in range(n_new):
        logits = forward(params, seq)
        nxt = jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
        seq = jnp.concatenate([seq, nxt[:, None]], axis=1)
    return np.asarray(seq)


def test_greedy_cache_matches_full_forward():
    model = _model()
    params = _params(model)
    rng = np.random.RandomState(0)
    prompt = rng.randint(0, VOCAB, size=(2, 5)).astype(np.int32)
    got = np.asarray(generate(model, params, prompt, max_new_tokens=8))
    ref = _greedy_reference(model, params, prompt, 8)
    np.testing.assert_array_equal(got, ref)
    assert got.shape == (2, 13)
    np.testing.assert_array_equal(got[:, :5], prompt)  # prompt preserved


def test_greedy_cache_matches_full_forward_moe():
    """Decode runs the MoE mixture without capacity dropping (chunk-
    length-dependent drops can't be cache-consistent), so the oracle is
    the no-drop full forward: capacity_factor = num_experts."""
    model = _model(moe_experts=4, moe_capacity_factor=0.5)  # drops in train
    params = _params(model, seed=1)
    rng = np.random.RandomState(1)
    prompt = rng.randint(0, VOCAB, size=(1, 4)).astype(np.int32)
    got = np.asarray(generate(model, params, prompt, max_new_tokens=6))
    no_drop = _model(moe_experts=4, moe_capacity_factor=4.0)
    ref = _greedy_reference(no_drop, params, prompt, 6)
    np.testing.assert_array_equal(got, ref)


def test_sampling_deterministic_per_seed():
    model = _model()
    params = _params(model)
    prompt = np.zeros((2, 3), np.int32)
    a = np.asarray(generate(model, params, prompt, max_new_tokens=10,
                            temperature=1.0, top_k=8,
                            rng=jax.random.PRNGKey(7)))
    b = np.asarray(generate(model, params, prompt, max_new_tokens=10,
                            temperature=1.0, top_k=8,
                            rng=jax.random.PRNGKey(7)))
    c = np.asarray(generate(model, params, prompt, max_new_tokens=10,
                            temperature=1.0, top_k=8,
                            rng=jax.random.PRNGKey(8)))
    np.testing.assert_array_equal(a, b)
    assert not np.array_equal(a, c)
    assert a.max() < VOCAB and a.min() >= 0


def test_length_guard():
    model = _model()
    params = _params(model)
    with pytest.raises(ValueError, match="max_seq_len"):
        generate(model, params, np.zeros((1, 30), np.int32),
                 max_new_tokens=10)
    with pytest.raises(ValueError, match="max_new_tokens"):
        generate(model, params, np.zeros((1, 4), np.int32),
                 max_new_tokens=0)


def test_single_new_token():
    model = _model()
    params = _params(model)
    prompt = np.ones((1, 4), np.int32)
    got = np.asarray(generate(model, params, prompt, max_new_tokens=1))
    ref = _greedy_reference(model, params, prompt, 1)
    np.testing.assert_array_equal(got, ref)


def test_top_p_sampling():
    """Nucleus filter: with a peaked distribution and small p, sampling
    can only return the top token; the filter composes with top_k."""
    from distributeddeeplearning_tpu.inference import _sample

    logits = jnp.asarray(
        [[10.0, 5.0, 1.0, 0.0], [0.0, 10.0, 9.9, 1.0]], jnp.float32
    )
    # p small enough that only the argmax survives in row 0; row 1's top
    # two are near-equal so p=0.9 keeps both
    for _ in range(8):
        tok = _sample(logits, jax.random.PRNGKey(_), 1.0, None, 0.5)
        assert int(tok[0]) == 0
    seen = {
        int(_sample(logits, jax.random.PRNGKey(s), 1.0, None, 0.9)[1])
        for s in range(32)
    }
    assert seen <= {1, 2}
    assert len(seen) == 2  # both nucleus members actually get sampled
    # end-to-end through generate()
    model = _model()
    params = _params(model)
    out = generate(model, params, np.zeros((1, 3), np.int32),
                   max_new_tokens=5, temperature=1.0, top_p=0.8,
                   rng=jax.random.PRNGKey(0))
    assert np.asarray(out).shape == (1, 8)


def test_top_p_validation_and_dp_rules_allowed():
    model = _model()
    params = _params(model)
    with pytest.raises(ValueError, match="top_p"):
        generate(model, params, np.zeros((1, 3), np.int32),
                 max_new_tokens=2, temperature=1.0, top_p=0.0)
    # PARAM_SHARDING=dp under the dp engine is valid (replicated params)
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.training.loop import resolve_engine

    engine, _ = resolve_engine(TrainConfig(engine="dp", param_sharding="dp"))
    assert engine == "dp"


def test_top_k_validated():
    model = _model()
    params = _params(model)
    prompt = np.zeros((1, 3), np.int32)
    with pytest.raises(ValueError, match="top_k"):
        generate(model, params, prompt, max_new_tokens=2,
                 temperature=1.0, top_k=0)
    # top_k > vocab is clamped (keeps everything), not an IndexError
    out = np.asarray(generate(model, params, prompt, max_new_tokens=2,
                              temperature=1.0, top_k=VOCAB + 100,
                              rng=jax.random.PRNGKey(3)))
    assert out.shape == (1, 5)


def test_eos_freezes_finished_rows():
    """After a row emits eos_token, its remaining positions are pad."""
    model = _model()
    params = _params(model)
    prompt = np.asarray([[1, 2, 3]], np.int32)
    ref = np.asarray(generate(model, params, prompt, max_new_tokens=10))
    # pick the token the greedy path actually emits early, use it as eos
    eos = int(ref[0, 4])  # second generated token
    got = np.asarray(
        generate(model, params, prompt, max_new_tokens=10,
                 eos_token=eos, pad_token=0)
    )
    # identical up to and including the FIRST eos occurrence (the chosen
    # token may already appear earlier in the greedy stream — freezing
    # from that earlier point is the correct behaviour), pad afterwards
    gen = ref[0, prompt.shape[1]:]
    first = prompt.shape[1] + int(np.argmax(gen == eos))
    np.testing.assert_array_equal(got[0, : first + 1], ref[0, : first + 1])
    assert got[0, first] == eos
    np.testing.assert_array_equal(got[0, first + 1:], 0)


def test_tp_sharded_state_decodes_token_identically(devices):
    """VERDICT r2 #7: decode straight from a TP-sharded (ENGINE=pjit)
    state on the 8-device mesh — no host gather, no replication — and
    get exactly the replicated path's tokens."""
    import optax

    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.parallel.mesh import create_mesh
    from distributeddeeplearning_tpu.training.pjit_step import build_pjit_state

    model = _model()
    mesh = create_mesh(axes=("data", "model"), shape=(2, 4))
    cfg = TrainConfig(engine="pjit", num_classes=VOCAB,
                      compute_dtype="float32", seed=7)
    state = build_pjit_state(
        model, cfg, optax.sgd(0.1), mesh,
        input_shape=(1, MAX_LEN), input_dtype=jnp.int32,
    )
    qkv = state.params["block0"]["attn"]["qkv"]["kernel"]
    assert "model" in tuple(qkv.sharding.spec)  # genuinely sharded

    rng = np.random.RandomState(0)
    prompt = rng.randint(0, VOCAB, size=(2, 5)).astype(np.int32)
    sharded_out = np.asarray(
        generate(model, state.params, prompt, max_new_tokens=8)
    )
    host_params = jax.device_get(state.params)
    ref_out = np.asarray(
        generate(model, host_params, prompt, max_new_tokens=8)
    )
    np.testing.assert_array_equal(sharded_out, ref_out)


def test_cache_buffers_sized_to_request_not_max_seq_len():
    """Round 5: KV buffers are allocated at prompt+max_new_tokens, not
    model.max_seq_len — decode streams the whole static buffer every
    step, so buffer length IS the KV byte cost (scripts/decode_audit.py).
    Shape-only check via the same eval_shape the sampler uses."""
    model = _model()  # max_seq_len = 32
    decode_model = model.clone(decode=True, attn_impl="xla", seq_axis=None)
    b, total = 2, 12  # 5-token prompt + 7 new << max_seq_len
    shapes = jax.eval_shape(
        lambda r: decode_model.init(
            r, jnp.zeros((b, total), jnp.int32), train=False
        ),
        jax.random.PRNGKey(0),
    )["cache"]
    lengths = {
        leaf.shape[1] for leaf in jax.tree.leaves(shapes) if leaf.ndim >= 3
    }
    assert lengths == {total}, lengths
    # and generation at that size still matches the full re-forward
    params = _params(model)
    prompt = np.random.RandomState(5).randint(
        0, VOCAB, size=(b, 5)
    ).astype(np.int32)
    got = np.asarray(generate(model, params, prompt, max_new_tokens=7))
    np.testing.assert_array_equal(got, _greedy_reference(model, params, prompt, 7))


def test_topk_fast_path_matches_sort_reference():
    """Round 5: the top-k-only sampler uses lax.top_k instead of a full
    vocab sort — the filtered distribution (and hence the draw, same
    key) must be identical to the sort-based construction."""
    from distributeddeeplearning_tpu.inference import _sample

    rng = np.random.RandomState(7)
    logits = jnp.asarray(rng.randn(3, 101).astype(np.float32) * 4)
    key = jax.random.PRNGKey(9)
    for k in (1, 5, 40, 101, 500):
        got = _sample(logits, key, temperature=0.7, top_k=k)
        scaled = logits / 0.7
        srt = jnp.sort(scaled, axis=-1)[:, ::-1]
        kth = srt[:, min(k, scaled.shape[-1]) - 1][:, None]
        ref_logits = jnp.where(
            scaled < kth, jnp.finfo(jnp.float32).min, scaled
        )
        ref = jax.random.categorical(key, ref_logits, axis=-1).astype(jnp.int32)
        np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
