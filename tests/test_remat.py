"""Gradient checkpointing (remat) tests: numerically transparent,
reachable from config, works through the engines and the PP stages."""

import jax
import jax.numpy as jnp
import numpy as np
import optax
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.pipeline import shard_batch
from distributeddeeplearning_tpu.models import get_model
from distributeddeeplearning_tpu.models.transformer_lm import TransformerLM
from distributeddeeplearning_tpu.training import create_train_state, make_train_step
from distributeddeeplearning_tpu.training.train_step import (
    cross_entropy_loss,
    replicate_state,
)

VOCAB, T = 32, 8


def test_remat_env_and_registry_wiring():
    cfg = TrainConfig.from_env({"REMAT": "1", "MODEL": "lm_tiny"})
    assert cfg.remat
    m = get_model(cfg.model, **cfg.model_kwargs())
    assert m.remat
    # conv models ignore the knob instead of erroring
    m2 = get_model("resnet18", **cfg.model_kwargs())
    assert m2.__class__.__name__ == "ResNet"
    v = get_model("vit_ti16", **cfg.model_kwargs())
    assert v.remat


def test_remat_gradients_identical():
    """Remat recomputes the same ops — loss and grads must match the
    stored-activation path to float precision."""
    rng = np.random.RandomState(0)
    rows = rng.randint(0, VOCAB, size=(4, T + 1)).astype(np.int32)
    tokens, labels = jnp.asarray(rows[:, :-1]), jnp.asarray(rows[:, 1:])

    results = {}
    for remat in (False, True):
        model = TransformerLM(
            variant="tiny", vocab_size=VOCAB, max_seq_len=T,
            dtype=jnp.float32, remat=remat,
        )
        import flax.linen as nn

        params = nn.unbox(
            model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
        )

        def loss_fn(p):
            logits = model.apply({"params": p}, tokens, train=False)
            return cross_entropy_loss(logits, labels)

        loss, grads = jax.jit(jax.value_and_grad(loss_fn))(params)
        results[remat] = (float(loss), jax.device_get(grads))

    assert np.isclose(results[False][0], results[True][0], rtol=1e-7)
    for a, b in zip(
        jax.tree.leaves(results[False][1]), jax.tree.leaves(results[True][1])
    ):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-6)


def test_remat_trains_through_dp_engine(mesh8):
    cfg = TrainConfig(num_classes=VOCAB, batch_size_per_device=2,
                      weight_decay=0.0, compute_dtype="float32", remat=True)
    model = get_model("lm_tiny", **cfg.model_kwargs(), max_seq_len=T)
    assert model.remat
    tx = optax.sgd(0.1)
    state = replicate_state(
        create_train_state(model, cfg, tx, input_shape=(1, T),
                           input_dtype=jnp.int32),
        mesh8,
    )
    step = make_train_step(model, tx, mesh8, cfg, donate_state=False)
    rng = np.random.RandomState(1)
    rows = rng.randint(0, VOCAB, size=(16, T + 1)).astype(np.int32)
    batch = shard_batch((rows[:, :-1], rows[:, 1:]), mesh8)
    losses = []
    for _ in range(3):
        state, metrics = step(state, batch)
        losses.append(float(metrics["loss"]))
    assert all(np.isfinite(l) for l in losses)
    assert losses[-1] < losses[0], losses


def test_remat_pp_stage_matches_reference(devices):
    """PP with remat'd stages still equals the sequential oracle."""
    from distributeddeeplearning_tpu.models.pipeline_lm import PipelineLM
    from distributeddeeplearning_tpu.parallel.mesh import create_mesh
    from distributeddeeplearning_tpu.training.pp_step import (
        create_pp_state,
        make_pp_train_step,
    )
    from jax.sharding import NamedSharding, PartitionSpec as P

    mesh = create_mesh(axes=("data", "pipe"), shape=(2, 4))
    pl = PipelineLM(variant="tiny", vocab_size=VOCAB, max_seq_len=T,
                    num_stages=4, n_layers=4, dtype=jnp.float32, remat=True)
    cfg = TrainConfig(num_classes=VOCAB, batch_size_per_device=1,
                      weight_decay=0.0, compute_dtype="float32")
    tx = optax.sgd(0.1)
    state = create_pp_state(pl, cfg, tx, mesh, T)
    host_params = jax.device_get(state.params)
    rng = np.random.RandomState(2)
    rows = rng.randint(0, VOCAB, size=(8, T + 1)).astype(np.int32)
    spec = NamedSharding(mesh, P("data"))
    step = make_pp_train_step(pl, tx, mesh, cfg, num_microbatches=2,
                              donate_state=False)
    _, metrics = step(
        state,
        (jax.device_put(rows[:, :-1], spec), jax.device_put(rows[:, 1:], spec)),
    )

    def ref_loss(params):
        logits = pl.apply_reference(params, jnp.asarray(rows[:, :-1]), train=True)
        return cross_entropy_loss(logits, jnp.asarray(rows[:, 1:]))

    np.testing.assert_allclose(
        float(metrics["loss"]), float(jax.jit(ref_loss)(host_params)), rtol=1e-5
    )


# -- block remat of a spec-built decoder keeps what the flash forward wrote --

SPEC_VOCAB, SPEC_T = 96, 32

# a full layer and a window layer (one pass of the flash kernels each,
# their results kept), and two block-diffusion layers (three passes
# each, all recomputed)
SPEC_KINDS = {
    "causal": ("smallthinker_tiny", {"layers": 2}),
    "block_diffusion": ("sdar_tiny", {}),
}


def _spec_loss(kind, remat):
    """``(loss, params)`` of a tiny spec-built decoder, its attention
    core the flash kernels in interpret mode."""
    name, share = SPEC_KINDS[kind]
    rows = np.random.default_rng(0).integers(0, SPEC_VOCAB, (2, SPEC_T), dtype=np.int32)
    tokens = jnp.asarray(rows)
    if kind == "block_diffusion":
        tokens = jnp.concatenate([tokens, tokens], axis=1)
    kw = dict(num_classes=SPEC_VOCAB, dtype="float32", **share)
    model = get_model(name, attn_impl="pallas", remat=remat, **kw)
    params = get_model(name, attn_impl="xla", **kw).init(
        jax.random.PRNGKey(1), tokens, train=False
    )["params"]

    def loss(p):
        logits = model.apply({"params": p}, tokens, train=True, mutable=["stats"])[0]
        return jnp.sum(logits ** 2)

    return loss, params


def _forward_kernels(jaxpr) -> int:
    """Flash forward ``pallas_call``s in ``jaxpr`` and every jaxpr
    inside it."""
    count = 0
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "pallas_call":
            count += eqn.params["jaxpr"].debug_info.func_name == "_flash_fwd_kernel"
        for sub in jax.core.jaxprs_in_params(eqn.params):
            count += _forward_kernels(sub)
    return count


@pytest.mark.parametrize("kind", list(SPEC_KINDS))
def test_spec_block_remat_gradients_are_those_of_remat_off_to_the_last_bit(kind):
    """The saved output and logsumexp are the very arrays the
    recomputation would have made, and a policy-less remat runs the
    same operations again: no bit of a gradient moves either way."""
    grads = {}
    for remat in (False, True):
        loss, params = _spec_loss(kind, remat)
        grads[remat] = jax.jit(jax.value_and_grad(loss))(params)
    for off, on in zip(jax.tree.leaves(grads[False]), jax.tree.leaves(grads[True])):
        np.testing.assert_array_equal(np.asarray(off), np.asarray(on))


@pytest.mark.parametrize("kind,passes", [("causal", 1), ("block_diffusion", 3)])
def test_spec_block_remat_runs_a_causal_layer_s_forward_kernel_once(kind, passes):
    """The gradient's jaxpr with remat on: one forward kernel a causal
    layer (the recomputed block starts from the two named results),
    two a pass of a block-diffusion layer, which keeps nothing."""
    layers = 2
    counts = {}
    for remat in (False, True):
        loss, params = _spec_loss(kind, remat)
        counts[remat] = _forward_kernels(jax.make_jaxpr(jax.grad(loss))(params).jaxpr)
    assert counts[False] == layers * passes
    assert counts[True] == layers * passes * (1 if kind == "causal" else 2)


@pytest.mark.parametrize(
    "model,kw,tokens",
    [
        ("smallthinker_tiny", {"layers": 2}, (2, SPEC_T)),
        ("sdar_tiny", {}, (2, 2 * SPEC_T)),
        ("lm_base", {"max_seq_len": 1024}, (1, 1024)),
    ],
    ids=["causal", "block_diffusion", "gpt2"],
)
def test_a_name_outside_a_policy_lowers_to_nothing(monkeypatch, model, kw, tokens):
    """A step with no remat (a spec-built decoder of either kind, and
    GPT-2's ``lm_base`` through the packed-QKV rule) lowers to the text
    it lowers to with the residuals left unnamed, but for the numbers
    MLIR's symbol table appends to private functions of one name
    (``@_where_91``): a name is an operation of the jaxpr and of no
    program."""
    import re

    from distributeddeeplearning_tpu.ops.pallas import flash

    net = get_model(model, num_classes=SPEC_VOCAB, dtype="float32", attn_impl="pallas", **kw)
    toks = jnp.zeros(tokens, jnp.int32)
    params = jax.eval_shape(
        lambda: net.init(jax.random.PRNGKey(0), toks, train=False)["params"]
    )

    def loss(p):
        out = net.apply({"params": p}, toks, train=True, mutable=["stats"])[0]
        return jnp.sum(out ** 2)

    def text():
        jax.clear_caches()  # the jitted cores keep their traces
        lowered = jax.jit(jax.value_and_grad(loss)).lower(params).as_text()
        return re.sub(r"@(\w+?)_\d+\b", r"@\1", lowered)

    named = text()
    assert "stablehlo.while" in named  # the kernels, interpreted
    monkeypatch.setattr(flash, "checkpoint_name", lambda x, name: x)
    assert text() == named
