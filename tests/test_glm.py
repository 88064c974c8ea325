"""GLM-4.7-Flash's layers in the spec-built decoder (``models/decoder.py``
``glm_tiny``: latent attention, a dense first layer, sigmoid-routed
experts with a selection bias beside a shared expert, one multi-token-
prediction module; ``ops/moe.route_sigmoid``) against the plain reference
of the ``glm`` family (``benchmarks/references/glm.py``) at a small size:
hidden 64, latents of 24 and 16, q and k heads of 12 + 4 (the rotary
part), v heads of 16, 8 experts chosen 2 a token, L = 32, seeded weights,
float32 on both sides. Float32 against float32 differs by the order of
the sums alone: 1e-5 of the largest value for a layer's output and
1e-4 for logits through five blocks (a bfloat16 product would read
1e-2), 2e-3 of a leaf's largest gradient entry (a leaf's gradient sums
over 96 positions and 8 experts' paths)."""

import dataclasses
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import glm as ref
from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.models import decoder, get_model
from distributeddeeplearning_tpu.ops import moe
from distributeddeeplearning_tpu.ops.attention import dot_product_attention

L, VOCAB = 32, 96
SPEC = decoder.SPECS["glm_tiny"]


def config(held=8, first=0, **over):
    """``glm_tiny`` as the ``glm`` reference reads it."""
    cfg = {
        "hidden_size": 64, "layers": 3, "num_attention_heads": 4,
        "q_lora_rank": 24, "kv_lora_rank": 16, "qk_nope_head_dim": 12,
        "qk_rope_head_dim": 4, "v_head_dim": 16, "first_k_dense_replace": 1,
        "intermediate_size": 96, "moe_intermediate_size": 32, "n_shared_experts": 1,
        "n_routed_experts": held, "first_expert": first, "num_experts_per_tok": 2,
        "routed_scaling_factor": 1.8, "num_nextn_predict_layers": 1,
        "vocab_size": VOCAB, "rms_norm_eps": 1e-5, "rope_theta": 1e6,
        "published": {"n_routed_experts": 8, "layers": 3},
        "assumed": {"mtp_loss_weight": 0.1, "selection_bias_std": 0.3},
    }
    cfg.update(over)
    return cfg


def tree(cfg, seed):
    """The family's seeded tree with the latent projections and the
    routed and shared experts' kernels ten times as loud: at hidden 64 the seed's N(0,
    0.02) give scores of a hundredth, a softmax all but uniform and
    experts that add a thousandth of the residual stream; at the
    published widths they are of order one, as here. The selection bias
    is drawn at 0.3, of the order of the sigmoid scores' spread."""
    flat = ref.flatten(ref.init_params(cfg, seed))
    for k in flat:
        if k.endswith(("q_b/kernel", "kv_b/kernel", "w1/kernel", "w3/kernel", "w2/kernel",
                       "shared/w_in/kernel", "shared/w_out/kernel")):
            flat[k] = 10.0 * flat[k]
    return ref.nest(flat)


def tokens(rows=3, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, (rows, L), dtype=np.int32)


def gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


def _model(attn_impl="xla", **share):
    return get_model(
        "glm_tiny", num_classes=VOCAB, dtype="float32", attn_impl=attn_impl,
        max_seq_len=L, **share,
    )


def _variables(t):
    params, buffers = ref.split_buffers(t)
    return {"params": params, "batch_stats": buffers}


POSITIONS = jnp.arange(L)


def _x(seed=4):
    return jax.random.normal(jax.random.PRNGKey(seed), (2, L, 64))


# -- one layer at a time -------------------------------------------------------

@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_latent_attention_layer_matches_the_reference(attn_impl):
    """``MlaAttention`` against ``references/glm._mla``: the two latents
    and their norms, the rotary part on 4 of a head's 16 dims of q and of
    the one key head all heads share, the causal core (the einsum, or the
    flash kernels in interpret mode), ``o``."""
    cfg = config()
    p = tree(cfg, 3)["block1"]["attn"]
    x = _x()

    def mla(c):
        return jax.jit(lambda p: ref._mla(x, p, POSITIONS, ref.sizes(c), None))(p)

    got = jax.jit(decoder.MlaAttention(SPEC, jnp.float32, attn_impl).apply)(
        {"params": p}, x, POSITIONS
    )
    assert got.shape == (2, L, 64) and gap(got, mla(cfg)) < 1e-5
    # the rotary part alone: over every dim of q and k it is another layer
    assert gap(got, mla(config(without=["partial_rope"]))) > 1e-2


def _expanded(p, x, positions, spec):
    """Latent attention in the expanded form as ``MlaAttention`` wrote it
    until q, k and v came straight from their products: ``q_b``'s output
    split ``[q_nope | q_rope]`` and joined again round ``rotary``, ``kv_b``'s
    split into ``k_nope`` and ``v``, the rotary key broadcast to every head
    and joined to ``k_nope``; the einsum core."""
    b, t, _ = x.shape
    h, hd, r = spec.heads, spec.head_dim, spec.qk_rope_dim
    nope = hd - r

    def rms(v, scale):
        return v * jax.lax.rsqrt(jnp.mean(v * v, axis=-1, keepdims=True) + spec.norm_eps) * scale

    c_q = rms(x @ p["q_a"]["kernel"], p["q_norm"]["scale"])
    q = (c_q @ p["q_b"]["kernel"]).reshape(b, t, h, hd)
    c_kv, k_rope = jnp.split(x @ p["kv_a"]["kernel"], [spec.kv_rank], axis=-1)
    kv = (rms(c_kv, p["kv_norm"]["scale"]) @ p["kv_b"]["kernel"]).reshape(b, t, h, nope + hd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    q = jnp.concatenate(
        [q[..., :nope], decoder.rotary(q[..., nope:], positions, spec.rope_theta)], axis=-1
    )
    k_rope = decoder.rotary(k_rope.reshape(b, t, 1, r), positions, spec.rope_theta)
    k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, t, h, r))], axis=-1)
    out = dot_product_attention(q, k, v, causal=True, impl="xla")
    return out.reshape(b, t, h * hd) @ p["o"]["kernel"]


# glm_tiny; three heads of 32 with a rotary part of 8 (the kernel's block
# is the whole row); two of 256 with the published 64 (its block the last
# 128 lanes of a head)
LATENT_SHAPES = {
    "glm_tiny": {},
    "h3_d32_r8": dict(heads=3, kv_heads=3, head_dim=32, qk_rope_dim=8),
    "h2_d256_r64": dict(heads=2, kv_heads=2, head_dim=256, qk_rope_dim=64),
}


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
@pytest.mark.parametrize("shape", list(LATENT_SHAPES))
def test_the_products_form_equals_the_expanded_form(shape, attn_impl):
    """q, k and v as the products write them (q's rotary lanes turned in
    place: XLA's roll, or the kernels of ``ops/pallas/rope_lanes`` in
    interpret mode; k's product over ``[c_kv | k_rope]`` with an identity
    under its rotary lanes; v's over ``kv_b``'s v columns) against the
    expanded form, float32: the layer's output and its gradients with
    respect to ``x`` and the five kernels. The parameter tree is the one
    the expanded form reads. A partner lane taken the wrong way, or one
    lane off, moves the output by its own order."""
    spec = dataclasses.replace(SPEC, **LATENT_SHAPES[shape])
    layer = decoder.MlaAttention(spec, jnp.float32, attn_impl)
    x = _x()
    p = layer.init(jax.random.PRNGKey(7), x, POSITIONS)["params"]
    p = jax.tree_util.tree_map_with_path(  # latents loud enough that attention is not uniform
        lambda path, w: 10.0 * w if "q_b" in str(path) or "kv_b" in str(path) else w, p
    )
    assert {k: v["kernel"].shape for k, v in p.items() if "kernel" in v} == {
        "q_a": (64, spec.q_rank), "q_b": (spec.q_rank, spec.heads * spec.head_dim),
        "kv_a": (64, spec.kv_rank + spec.qk_rope_dim),
        "kv_b": (spec.kv_rank, spec.heads * (2 * spec.head_dim - spec.qk_rope_dim)),
        "o": (spec.heads * spec.head_dim, 64),
    }
    g = jax.random.normal(jax.random.PRNGKey(8), (2, L, 64))

    def run(fn):  # one program a side: the gradients, and the output as their aux
        def objective(p, x):
            out = fn(p, x)
            return jnp.sum(out * g), out
        return jax.jit(jax.grad(objective, argnums=(0, 1), has_aux=True))(p, x)

    (dp_got, dx_got), got = run(lambda p, x: layer.apply({"params": p}, x, POSITIONS))
    (dp_want, dx_want), want = run(lambda p, x: _expanded(p, x, POSITIONS, spec))
    assert gap(got, want) < 1e-5
    assert gap(dx_got, dx_want) < 1e-5
    for name in ("q_a", "q_b", "kv_a", "kv_b", "o"):
        assert gap(dp_got[name]["kernel"], dp_want[name]["kernel"]) < 1e-5, name
    # the rotary part turned the other way is another layer
    assert gap(got, jax.jit(lambda p, x: _expanded(p, x, -POSITIONS, spec))(p, x)) > 1e-3


def test_a_latent_layer_is_counted_with_the_form_it_runs():
    """``decoder.layer.mla`` names the products form and k's padded width,
    once for each latent layer the trace builds: three blocks, and the
    MTP block's at the held depth."""
    obs.reset()
    jax.eval_shape(
        lambda: _model().init(jax.random.PRNGKey(0), jnp.zeros((1, L), jnp.int32), train=False)
    )
    counted = [e["labels"] for e in obs.get_bus().ring if e.get("name") == "decoder.layer.mla"]
    obs.reset()
    assert sorted(c["layer"] for c in counted) == [0, 1, 2, SPEC.layers]
    assert all(c["qkv"] == "products" for c in counted)
    assert all(c["k_width"] == SPEC.heads * SPEC.head_dim for c in counted)


def test_the_sigmoid_router_chooses_by_score_and_bias_and_gates_by_score():
    """``ops/moe.route_sigmoid`` against a count by hand and the
    reference's ``route``: the experts of the largest ``s + b``, gates
    ``1.8 · s / Σ_chosen s`` from the scores alone."""
    key = jax.random.PRNGKey(0)
    logits = jax.random.normal(key, (64, 8))
    bias = 0.3 * jax.random.normal(jax.random.fold_in(key, 1), (8,))
    routed = moe.route_sigmoid(logits, bias, 2, 1.8)
    s = np.asarray(jax.nn.sigmoid(logits))
    for t in range(64):
        chosen = np.argsort(-(s[t] + np.asarray(bias)), kind="stable")[:2]
        assert set(np.asarray(routed.experts[t])) == set(chosen)
        gates = dict(zip(np.asarray(routed.experts[t]), np.asarray(routed.gates[t])))
        for e in chosen:
            assert gates[e] == pytest.approx(1.8 * s[t, e] / s[t, chosen].sum(), rel=1e-6)
    sizes = ref.sizes(config())
    experts, gates = ref.route(jnp.eye(64), logits, bias, sizes)  # h·W_r = the logits
    assert bool(jnp.all(jnp.sort(experts, -1) == jnp.sort(routed.experts, -1)))
    assert jnp.allclose(jnp.sort(gates, -1), jnp.sort(routed.gates, -1), rtol=1e-6)
    # the bias decides choices and no gate: by the score alone some differ
    alone = moe.route_sigmoid(logits, jnp.zeros(8), 2, 1.8)
    assert bool(jnp.any(jnp.sort(alone.experts, -1) != jnp.sort(routed.experts, -1)))
    # renormalised: a token's gates add up to the routed scale
    assert jnp.allclose(jnp.sum(routed.gates, -1), 1.8, rtol=1e-6)


@pytest.mark.parametrize("without", [(), ("shared_expert",)], ids=["with", "reference-without"])
def test_the_expert_layer_with_its_shared_expert(without):
    """An expert layer (block 1) against ``references/glm._layer``: the
    sigmoid router on ``ln2``'s output with the selection bias the block
    reads from its buffers, 8 experts, and the shared expert beside them;
    a reference without the shared expert is another layer."""
    cfg = config()
    p = tree(cfg, 5)["block1"]
    x = _x()
    variables = _variables(p)
    block = decoder.SpecBlock(SPEC, jnp.float32, "xla", SPEC.kind(1))
    got = jax.jit(block.apply, static_argnums=3)(variables, x, POSITIONS, False)
    want, _ = jax.jit(lambda p: ref._layer(
        x, p, POSITIONS, ref.sizes(config(without=list(without))), None, False
    ))(p)
    if without:
        assert gap(got, want) > 1e-3
    else:
        assert gap(got, want) < 1e-5
    assert "e_score_correction_bias" not in str(jax.tree_util.tree_structure(variables["params"]))


def test_the_dense_first_layer():
    """Layer 0 is the dense SwiGLU of ``dense_ffn_dim`` (96 here), named
    as a dense MLP is (``mlp/w_in``, ``mlp/w_out``); layers after it are
    expert layers."""
    cfg = config()
    p = tree(cfg, 6)["block0"]
    assert SPEC.kind(0).ffn == "glu" and SPEC.kind(1).ffn == ""
    assert p["mlp"]["w_in"]["kernel"].shape == (64, 192)
    x = _x()
    got = jax.jit(decoder.SpecBlock(SPEC, jnp.float32, "xla", SPEC.kind(0)).apply,
                  static_argnums=3)({"params": p}, x, POSITIONS, False)
    want, chosen = jax.jit(lambda p: ref._layer(x, p, POSITIONS, ref.sizes(cfg), None, True))(p)
    assert chosen is None and gap(got, want) < 1e-5


# -- the model whole -------------------------------------------------------------

def _loss_fn(model, buffers, toks, labels):
    def loss(params):
        logits, seen = model.apply(
            {"params": params, "batch_stats": buffers}, toks, train=True,
            mutable=["intermediates", "losses"],
        )
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        main = jnp.mean(logz - picked)
        later = seen["losses"]["mtp_loss"][0]
        return main + later, (logits, seen, main, later)

    return loss


@pytest.fixture(scope="module")
def glm_reference():
    """Seeded weights, three rows, and what ``references/glm.py`` gives on
    them (logits, the experts chosen, the MTP module's logits, loss and
    gradients): computed once, for both implementations."""
    cfg = config()
    t = tree(cfg, 11)
    params, buffers = ref.split_buffers(t)
    rows = tokens(3, seed=5)
    toks, labels = jnp.asarray(rows), jnp.asarray(np.roll(rows, -1, axis=1))
    want = jax.jit(lambda t: ref.forward(t, toks, cfg))(t)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda p: ref.token_loss(p, buffers, toks, labels, cfg)
    ))(params)
    return t, toks, labels, want, lr, gr


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_model_matches_its_reference_on_logits_loss_and_gradients(glm_reference, attn_impl):
    """``glm_tiny`` against ``references/glm.py`` on seeded weights at L =
    32: logits, the experts chosen in both expert layers and the MTP
    block, the loss ``CE_main + 0.1 · CE_mtp`` (the model sows the second
    term, which the train step adds), every parameter's gradient."""
    t, toks, labels, (want, chosen, _), lr, gr = glm_reference
    params, buffers = ref.split_buffers(t)
    model = _model(attn_impl)
    (l, (logits, seen, main, later)), grads = jax.jit(jax.value_and_grad(
        _loss_fn(model, buffers, toks, labels), has_aux=True
    ))(params)
    assert logits.shape == (3, L, VOCAB) and gap(logits, want) < 1e-4
    seen = seen["intermediates"]
    got_chosen = jnp.stack([seen["block1"]["mlp"]["experts"][0],
                            seen["block2"]["mlp"]["experts"][0],
                            seen["mtp"]["block"]["mlp"]["experts"][0]])
    assert chosen.shape == (3, 3 * L, 2)
    assert bool(jnp.all(jnp.sort(got_chosen, -1) == jnp.sort(chosen, -1)))
    assert abs(float(l) - float(lr)) < 1e-5 * abs(float(lr))
    flat_got, flat_want = ref.flatten(grads), ref.flatten(gr)
    assert set(flat_got) == set(flat_want)
    assert not any(k.endswith(ref.BIAS) for k in flat_got)
    for k in flat_want:
        assert gap(flat_got[k], flat_want[k]) < 2e-3, k
    # every leaf of the MTP module learns from its term alone
    assert float(jnp.max(jnp.abs(flat_got["mtp/eh_proj/kernel"]))) > 0


def test_the_mtp_term_and_its_two_masked_positions():
    """The sown term is 0.1 times the mean cross-entropy of the MTP head
    over positions ``t ≤ T − 3`` against token ``t + 2``: what the
    reference's module logits give over those positions, and not their
    sum over all ``T`` positions' count (the two without a target
    counted as nought)."""
    cfg = config()
    t = tree(cfg, 12)
    params, buffers = ref.split_buffers(t)
    toks = jnp.asarray(tokens(2, seed=7))
    model = _model()
    _, seen = jax.jit(lambda p: model.apply(
        {"params": p, "batch_stats": buffers}, toks, train=True, mutable=["losses"]
    ))(params)
    got = float(seen["losses"]["mtp_loss"][0])
    _, _, later = jax.jit(lambda t: ref.forward(t, toks, cfg))(t)
    ce = ref._cross_entropy(later[:, :-2], toks[:, 2:])
    assert got == pytest.approx(0.1 * float(jnp.mean(ce)), rel=1e-5)
    assert abs(got - 0.1 * float(jnp.sum(ce)) / (2 * L)) > 1e-2 * got
    # without a collection to sow into the module still runs (its choices
    # are read) and nothing is added
    _, quiet = jax.jit(lambda p: model.apply(
        {"params": p, "batch_stats": buffers}, toks, train=False, mutable=["intermediates"],
    ))(params)
    assert "mtp" in quiet["intermediates"]


def test_the_reference_sees_each_mechanism(glm_reference):
    """A reference without one of them is another model: the selection
    bias (choices by the score alone), the shared expert, the rotary part
    (rotary over all of q and k), the MTP term (the loss alone moves); on
    the first two rows."""
    cfg = config()
    t, toks = glm_reference[0], glm_reference[1][:2]
    params, buffers = ref.split_buffers(t)
    labels = jnp.roll(toks, -1, axis=1)
    logits = jax.jit(lambda p: _model().apply(
        {"params": p, "batch_stats": buffers}, toks, train=False
    ))(params)

    def forward(c):
        return jax.jit(lambda t: ref.forward(t, toks, c)[:2])(t)

    def loss(c):
        return jax.jit(lambda p: ref.token_loss(p, buffers, toks, labels, c))(params)

    sound, chosen = forward(cfg)
    assert gap(logits, sound) < 1e-4
    for mechanism in ("selection_bias", "shared_expert", "partial_rope"):
        wrong, other = forward(config(without=[mechanism]))
        assert gap(logits, wrong) > 1e-3, mechanism
        if mechanism == "selection_bias":
            assert float(jnp.mean(jnp.sort(other, -1) != jnp.sort(chosen, -1))) > 0.05
    assert float(loss(cfg) - loss(config(without=["mtp"]))) > 0.1 * np.log(VOCAB) * 0.5


@pytest.mark.parametrize("layer", [1, 2])
def test_the_eight_shares_of_an_expert_layer_add_up_to_the_uncut_reference(layer):
    """One expert layer, 8 experts as eight shares of one: the parts the
    shares give (each a whole layer's output: what every chip computes
    alike, the residual stream, attention and the shared expert, counted
    once) add up to what the uncut reference gives for the layer."""
    cfg = config()
    p = tree(cfg, 3)[f"block{layer}"]
    x = _x()
    s = ref.sizes(cfg)

    def share(first):
        mlp = {k: ({"kernel": v["kernel"][first:first + 1]} if k in ("w1", "w2", "w3") else v)
               for k, v in p["mlp"].items()}
        block = decoder.SpecBlock(
            dataclasses.replace(SPEC, experts_held=1, first_expert=first),
            jnp.float32, "xla", SPEC.kind(layer),
        )
        return block.apply(_variables({**p, "mlp": mlp}), x, POSITIONS, False)

    uncut = jax.jit(lambda p: ref._layer(x, p, POSITIONS, s, None, False)[0])
    want = uncut(p)
    alike = uncut({**p, "mlp": {**p["mlp"], "w2": {"kernel": 0.0 * p["mlp"]["w2"]["kernel"]}}})
    parts = [part - alike for part in jax.jit(lambda: [share(first) for first in range(8)])()]
    assert gap(alike + sum(parts), want) < 1e-5
    assert gap(sum(parts), want - alike) < 1e-4  # the routed experts' part alone
    assert float(jnp.max(jnp.abs(want - alike))) > 1e-3


def test_the_selection_bias_is_state_the_step_carries_and_no_parameter():
    """``explicit.setup``'s state keeps each expert layer's bias (and the
    MTP block's) in ``batch_stats``; a train step moves the parameters
    and hands the bias on unchanged, and AdamW never sees it."""
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh
    from distributeddeeplearning_tpu.training.optimizer import create_optimizer
    from distributeddeeplearning_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    cfg = TrainConfig(
        model="glm_tiny", num_classes=VOCAB, compute_dtype="float32",
        batch_size_per_device=2, optimizer="adamw", weight_decay=0.0,
        warmup_epochs=0, lr_schedule="constant", fake=True, epochs=1, base_lr=1e-2,
    )
    model = get_model(cfg.model, **cfg.model_kwargs(), max_seq_len=L)
    tx, _ = create_optimizer(cfg, 10, world_size=1)
    state = create_train_state(model, cfg, tx, input_shape=(1, L), input_dtype=jnp.int32)
    buffers = ref.flatten(state.batch_stats)
    assert sorted(buffers) == [
        "block1/mlp/e_score_correction_bias", "block2/mlp/e_score_correction_bias",
        "mtp/block/mlp/e_score_correction_bias"]
    drawn = {k: 0.3 * np.arange(8, dtype=np.float32) for k in buffers}
    state = state.replace(batch_stats=ref.nest({k: jnp.asarray(v) for k, v in drawn.items()}))
    step = make_train_step(model, tx, data_parallel_mesh(1), cfg)
    x = jnp.asarray(tokens(2, seed=1))
    before = jax.tree.map(jnp.copy, state.params)
    state, metrics = step(state, (x, jnp.roll(x, -1, axis=1)))
    for k, v in ref.flatten(state.batch_stats).items():
        assert np.array_equal(np.asarray(v), drawn[k]), k
    moved = jax.tree.map(lambda a, b: float(jnp.max(jnp.abs(a - b))), state.params, before)
    assert min(jax.tree.leaves(moved)) > 0
    assert float(metrics["loss"]) > float(np.log(VOCAB))  # the MTP term rides on it


@pytest.mark.parametrize(
    "fault", ["kv_heads", "rope_odd", "window", "mtp_tied", "router", "bias"]
)
def test_a_spec_that_cannot_be_built_is_refused(fault):
    change = {
        "bias": {"bias": True},  # k and v are products of kv_b's columns, with no bias
        "kv_heads": {"kv_heads": 2},
        "rope_odd": {"qk_rope_dim": 3},
        "window": {"pattern": (decoder.LayerKind(8, True),)},
        "mtp_tied": {"tied_head": True},
        "router": {"router": "softmax"},  # a routed scale of 1.8 needs the sigmoid
    }[fault]
    model = decoder.SpecDecoder(
        dataclasses.replace(SPEC, **change), vocab_size=VOCAB, max_seq_len=L,
        dtype=jnp.float32, attn_impl="xla",
    )
    with pytest.raises(ValueError):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, L), jnp.int32), train=False)


def test_the_published_spec_against_the_catalog_s_numbers():
    """``glm_4_7_flash`` is the published model, uncut until a run states
    its share: the catalog's row (``architectures.jsonl``, GLM-4.7-Flash,
    ``config``)."""
    spec = decoder.SPECS["glm_4_7_flash"]
    assert (spec.hidden, spec.layers, spec.heads, spec.kv_heads) == (2048, 47, 20, 20)
    assert (spec.q_rank, spec.kv_rank, spec.head_dim, spec.qk_rope_dim) == (768, 512, 256, 64)
    assert spec.head_dim - spec.qk_rope_dim == 192  # qk_nope_head_dim; v_head_dim 256
    assert (spec.ffn, spec.ffn_dim, spec.experts, spec.experts_per_token) == ("moe", 1536, 64, 4)
    assert (spec.router, spec.routed_scale, spec.shared_ffn_dim) == ("sigmoid", 1.8, 1536)
    assert (spec.dense_layers, spec.dense_ffn_dim, spec.mtp_depth) == (1, 10240, 1)
    assert (spec.norm_eps, spec.rope_theta, spec.tied_head, spec.bias) == (1e-5, 1e6, False, False)
    assert [spec.kind(l).ffn for l in range(3)] == ["glu", "", ""]


def test_the_specs_that_were_there_lower_as_they_did():
    """``smallthinker_tiny`` and ``granite_tiny`` after the spec gained
    latent attention, the sigmoid router, the shared expert, the dense
    layers' field and the MTP module: a loss and its gradients lower,
    text for text, to what they lowered to before those fields existed
    (``sdar_tiny`` and ``gpt2_tiny`` are ``tests/test_decoder.py``'s), and
    the parameter trees are theirs."""
    if jax.__version__ != "0.9.0" or jax.device_count() != 8:
        pytest.skip("the text was taken under jax 0.9.0 on the tests' 8 host devices")
    toks = jnp.asarray(tokens(2))
    was = {
        "smallthinker_tiny": ("1811a08bb1ed27bd2c607b6b8f3b6a4710958c073833089f8e495611c5b75bd0",
                              "7b60dbaa641cb1ef"),
        "granite_tiny": ("8fba8f729c81ca735b81ca0978deea85f8dc779f9c9abaefa836432f53bb7478",
                         "17a5593bf52b5b9f"),
    }
    for name in was:
        model = get_model(name, num_classes=VOCAB, dtype="float32", attn_impl="xla", max_seq_len=L)
        params = jax.eval_shape(  # the text and the tree read shapes alone
            lambda: model.init(jax.random.PRNGKey(1), toks, train=False)
        )["params"]

        def loss(p):
            return jnp.sum(
                model.apply({"params": p}, toks, train=True, mutable=["stats"])[0] ** 2
            )

        text = jax.jit(jax.value_and_grad(loss)).lower(params).as_text()
        shapes = sorted(
            (jax.tree_util.keystr(k), v.shape)
            for k, v in jax.tree_util.tree_leaves_with_path(params)
        )
        assert (
            hashlib.sha256(text.encode()).hexdigest(),
            hashlib.sha256(repr(shapes).encode()).hexdigest()[:16],
        ) == was[name], name
