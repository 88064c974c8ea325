"""Decoder-only LM: causality, registry, and real DP train steps.

The long-context tier trained through the same engine as the vision
models — per-token cross-entropy via the generalized loss, causal
attention through ops.dot_product_attention (xla and pallas impls).
"""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.pipeline import shard_batch
from distributeddeeplearning_tpu.data.synthetic import SyntheticTokenDataset
from distributeddeeplearning_tpu.models import get_model
from distributeddeeplearning_tpu.models.transformer_lm import TransformerLM
from distributeddeeplearning_tpu.training import (
    create_train_state,
    make_train_step,
)
from distributeddeeplearning_tpu.training.train_step import (
    cross_entropy_loss,
    replicate_state,
)

VOCAB = 64
T = 16
CFG = TrainConfig(
    model="lm_tiny",
    num_classes=VOCAB,
    batch_size_per_device=2,
    weight_decay=0.0,
    compute_dtype="float32",
)


def _model(impl="xla"):
    return TransformerLM(
        variant="tiny", vocab_size=VOCAB, max_seq_len=T,
        dtype=jnp.float32, attn_impl=impl,
    )


def _batch(n=16, seed=0):
    rng = np.random.RandomState(seed)
    rows = rng.randint(0, VOCAB, size=(n, T + 1)).astype(np.int32)
    return rows[:, :-1], rows[:, 1:]


@pytest.fixture(scope="module")
def state_and_model():
    model = _model()
    tx = optax.sgd(0.5)
    state = create_train_state(
        model, CFG, tx, input_shape=(1, T), input_dtype=jnp.int32
    )
    return model, tx, state


def test_registry_and_vocab_plumbing():
    m = get_model("lm_tiny", num_classes=VOCAB, attn_impl="pallas")
    assert isinstance(m, TransformerLM)
    assert m.vocab_size == VOCAB and m.attn_impl == "pallas"


def test_causality(state_and_model):
    """Logits at position t must not depend on tokens > t."""
    model, _, state = state_and_model
    tokens, _ = _batch(n=2, seed=1)
    out1 = model.apply({"params": state.params}, tokens, train=False)
    perturbed = tokens.copy()
    perturbed[:, -1] = (perturbed[:, -1] + 7) % VOCAB  # change last token
    out2 = model.apply({"params": state.params}, perturbed, train=False)
    np.testing.assert_allclose(
        np.asarray(out1[:, :-1]), np.asarray(out2[:, :-1]), atol=1e-5
    )
    assert np.abs(np.asarray(out1[:, -1]) - np.asarray(out2[:, -1])).max() > 1e-4


def test_token_cross_entropy_shape():
    logits = jnp.zeros((2, 3, VOCAB))
    labels = jnp.zeros((2, 3), jnp.int32)
    loss = cross_entropy_loss(logits, labels)
    np.testing.assert_allclose(float(loss), np.log(VOCAB), rtol=1e-5)


def test_lm_dp_train_step_loss_decreases(state_and_model, mesh8):
    model, tx, state = state_and_model
    state = replicate_state(state, mesh8)
    step = make_train_step(model, tx, mesh8, CFG, donate_state=False)
    batch = shard_batch(_batch(), mesh8)
    losses = []
    for _ in range(5):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(losses))
    assert losses[-1] < losses[0], losses


def test_lm_pallas_matches_xla(state_and_model, mesh8):
    model, tx, state = state_and_model
    tokens, _ = _batch(n=4, seed=2)
    logits_xla = model.apply({"params": state.params}, tokens, train=False)
    logits_fl = _model("pallas").apply(
        {"params": state.params}, tokens, train=False
    )
    np.testing.assert_allclose(
        np.asarray(logits_fl), np.asarray(logits_xla), atol=2e-3
    )


def test_token_dataset_contract():
    ds = SyntheticTokenDataset(
        length=64, global_batch_size=16, seq_len=T, vocab_size=VOCAB,
        num_physical_batches=2,
    )
    assert ds.steps_per_epoch == 4
    n = 0
    for x, y in ds.epoch(0):
        assert x.shape == (16, T) and y.shape == (16, T)
        assert x.dtype == np.int32
        np.testing.assert_array_equal(x[:, 1:], y[:, :-1])  # shifted pair
        n += 1
    assert n == 4
    # per-process disjoint sharding: local batches halve
    d0 = SyntheticTokenDataset(
        length=64, global_batch_size=16, seq_len=T, vocab_size=VOCAB,
        num_physical_batches=2, process_index=0, process_count=2,
    )
    x0, _ = next(iter(d0.epoch(0)))
    assert x0.shape == (8, T)


def test_lm_trains_through_keras_frontend(mesh8):
    """Front-end reachability: Model('lm_tiny').fit(token_data) — the
    engine infers the (1, seq_len) int32 init signature from the dataset."""
    from distributeddeeplearning_tpu.frontends import Model

    cfg = TrainConfig(
        model="lm_tiny",
        num_classes=VOCAB,
        batch_size_per_device=2,
        weight_decay=0.0,
        compute_dtype="float32",
    )
    data = SyntheticTokenDataset(
        length=32, global_batch_size=16, seq_len=T, vocab_size=VOCAB,
        num_physical_batches=2,
    )
    m = Model(_model(), cfg)
    m.compile()
    result = m.fit(data, epochs=1)
    assert np.isfinite(result.history[-1]["loss"])
    assert int(m.state.step) == 2  # 32/(2*8)


# ---- the attention core's lowering, chosen from shape and platform ---------
# (ops/attention.resolve_impl through models/vit.Attention; the rule's own
# table is in tests/test_attention_ops.py; the counter `attn.impl.<path>` says
# what a trace chose)


def _chosen(monkeypatch, *, backend="tpu", devices=1, sharded=False, t=1024,
            heads=12, d=64, decode=False, init=False, asked="auto"):
    """Paths that one Attention call resolved to, with the backend query
    answered as a platform would: nothing runs, the call is only traced."""
    from jax.sharding import PartitionSpec as P

    from distributeddeeplearning_tpu import obs
    from distributeddeeplearning_tpu.models.vit import Attention
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh

    attn = Attention(heads, jnp.bfloat16, asked, causal=True, decode=decode)
    x = jax.ShapeDtypeStruct((8, t, heads * d), jnp.bfloat16)
    init_fn = lambda x: attn.init(jax.random.PRNGKey(0), x, False)  # noqa: E731
    variables = jax.eval_shape(init_fn, x)
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: devices)
    obs.reset()
    if init:  # a new function: the first one's trace is cached
        jax.eval_shape(lambda x: attn.init(jax.random.PRNGKey(0), x, False), x)
    else:
        call = lambda v, x: attn.apply(v, x, False, mutable=["cache"])  # noqa: E731
        if sharded:
            call = jax.shard_map(
                call, mesh=data_parallel_mesh(8),
                in_specs=(P(), P("data")), out_specs=P("data"),
            )
        jax.eval_shape(call, variables, x)
    totals = obs.get_bus().totals()
    obs.reset()
    return sorted(k[len("attn.impl."):] for k in totals if k.startswith("attn.impl."))


@pytest.mark.parametrize(
    "case,path",
    [
        (dict(t=1024), "pallas"),  # GPT-2's shape in the benchmark's cell
        (dict(t=640), "pallas"),  # the rule's lower edge, as measured
        (dict(t=639), "xla"),
        (dict(t=513), "xla"),  # past the packed kernel, short of the flash one
        (dict(t=512), "fused"),  # the packed small-T kernel's last length
        (dict(t=197, heads=12, d=64), "fused"),  # ViT-B/16
        (dict(t=1024, heads=8, d=96), "xla"),  # head blocks do not tile the lanes
        (dict(t=1024, heads=4, d=128), "pallas"),
        (dict(t=1024, backend="cpu"), "xla"),  # off the TPU
        (dict(t=1024, backend="gpu"), "xla"),
        (dict(t=1024, devices=8), "xla"),  # pjit engine: operands not local
        (dict(t=1024, devices=8, sharded=True), "pallas"),  # dp engine: shard_map
        (dict(t=1024, init=True), "xla"),  # the weight draw lowers no kernel
        (dict(t=1024, decode=True), None),  # serving never asks the resolver
        (dict(t=1024, asked="xla"), "xla"),  # explicit values force a path
        (dict(t=1024, asked="pallas", backend="cpu"), "pallas"),
        (dict(t=64, asked="pallas"), "pallas"),
    ],
)
def test_attention_resolver_table(monkeypatch, case, path):
    assert _chosen(monkeypatch, **case) == ([path] if path else [])


def test_lm_auto_equals_xla_off_tpu():
    """Off the TPU the default resolves to the einsum: logits and
    gradients equal the explicit-xla build's bit for bit."""
    tokens, _ = _batch(4)
    m_auto, m_xla = _model("auto"), _model("xla")
    variables = m_xla.init(jax.random.PRNGKey(0), tokens[:1], train=False)

    def loss(model):
        return lambda v: jnp.sum(model.apply(v, tokens, train=False) ** 2)

    np.testing.assert_array_equal(
        np.asarray(m_auto.apply(variables, tokens, train=False)),
        np.asarray(m_xla.apply(variables, tokens, train=False)),
    )
    g_auto, g_xla = jax.grad(loss(m_auto))(variables), jax.grad(loss(m_xla))(variables)
    for a, b in zip(jax.tree.leaves(g_auto), jax.tree.leaves(g_xla)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


@pytest.mark.parametrize("name", ["lm_base", "lm_moe_tiny", "vit_b16"])
def test_default_config_carries_the_resolving_default(name):
    """``TrainConfig()`` untouched -> ``get_model``: the path every
    front-end and the benchmark take carries ``"auto"``; ``ATTN_IMPL``
    still overrides, and the pipeline engine keeps the einsum."""
    from distributeddeeplearning_tpu.models import available_models

    if name not in available_models():
        pytest.skip(f"{name} is not registered")
    config = TrainConfig()
    assert config.attn_impl == "auto"
    assert get_model(name, **config.model_kwargs()).attn_impl == "auto"
    assert get_model(name).attn_impl == "auto"
    forced = TrainConfig.from_env({"ATTN_IMPL": "xla"})
    assert get_model(name, **forced.model_kwargs()).attn_impl == "xla"
