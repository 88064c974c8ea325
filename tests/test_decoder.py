"""The decoder built from a spec (``models/decoder.py``), its expert
layer (``ops/moe.py``), the block-diffusion attention core
(``ops/attention.py``, ``ops/pallas/flash.py``), the noising
(``data/noise.py``) and the weighted loss, each against the plain
reference of the ``sdar`` family (``benchmarks/references/sdar.py``) at a
small size: hidden 64, 2 layers, 4/2 heads of 16, 8 experts top-2,
L = 32, B = 4, seeded weights, float32."""

import dataclasses
import functools
import hashlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks.references import granite
from benchmarks.references import sdar as ref
from benchmarks.references import smallthinker as st
from distributeddeeplearning_tpu.data.noise import block_diffusion_noise
from distributeddeeplearning_tpu.models import decoder, get_model
from distributeddeeplearning_tpu.ops import moe
from distributeddeeplearning_tpu.ops.attention import (
    block_diffusion_attention,
    block_diffusion_mask,
)
from distributeddeeplearning_tpu.ops.pallas import flash
from distributeddeeplearning_tpu.training.train_step import (
    weighted_cross_entropy_loss,
)

L, B, VOCAB = 32, 4, 96


def config(held=8, first=0):
    return {
        "hidden_size": 64, "layers": 2, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "moe_intermediate_size": 32,
        "num_experts": held, "first_expert": first, "num_experts_per_tok": 2,
        "vocab_size": VOCAB, "rms_norm_eps": 1e-6, "rope_theta": 1e6,
        "published": {"num_experts": 8, "layers": 2},
        "assumed": {"block_length": B, "t_min": 0.125, "mask_token_id": VOCAB - 1},
    }


def clean_rows(rows=3, seed=0):
    return np.random.default_rng(seed).integers(0, VOCAB, (rows, L), dtype=np.int32)


def gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


# -- the model against the reference -----------------------------------------

@pytest.fixture(scope="module")
def sdar_reference():
    """Seeded weights, the noised rows, and the reference's logits, loss
    and gradients on them: computed once, for both implementations."""
    cfg = config()
    params = ref.init_params(cfg, 7)
    inputs, targets, weights = (jnp.asarray(x) for x in ref.noise_rows(clean_rows(), cfg))
    want, _ = jax.jit(lambda p: ref.forward(p, inputs, cfg))(params)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda p: ref.diffusion_loss(p, inputs, targets, weights, cfg)
    ))(params)
    return params, (inputs, targets, weights), want, lr, gr


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_model_matches_the_reference_on_logits_loss_and_gradients(sdar_reference, attn_impl):
    params, (inputs, targets, weights), want, lr, gr = sdar_reference
    model = get_model(
        "sdar_tiny", num_classes=VOCAB, dtype="float32", attn_impl=attn_impl
    )

    def loss(params):
        logits = model.apply({"params": params}, inputs, train=True)
        return weighted_cross_entropy_loss(logits, targets, weights), logits

    (l, logits), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    assert logits.shape == (3, L, VOCAB) and gap(logits, want) < 1e-4
    assert abs(float(l) - float(lr)) < 1e-5 * abs(float(lr))
    flat_got, flat_want = ref.flatten(grads), ref.flatten(gr)
    assert set(flat_got) == set(flat_want)
    for k in flat_want:  # every parameter, not the comparison's pooled leaves
        assert gap(flat_got[k], flat_want[k]) < 2e-3, k
    pooled = ref.leaf_norms(gr)
    assert "mlp/routers" in pooled and "mlp/experts" in pooled
    assert len(pooled) == len(flat_want) - 5 - 1  # two layers: six expert kernels as one, two routers as one


def test_a_gpt2_spec_is_the_lm_of_that_size():
    """``gpt2_tiny`` of the spec builder computes what ``lm_tiny``
    computes, the fused qkv kernel cut in its thirds."""
    tokens = jnp.asarray(clean_rows(2))
    lm = get_model("lm_tiny", num_classes=VOCAB, dtype="float32", max_seq_len=L)
    params = jax.tree.map(
        lambda p: p + 0.01, lm.init(jax.random.PRNGKey(1), tokens, train=False)["params"]
    )
    import flax.linen as nn

    params = nn.unbox(params)
    mine = {k: v for k, v in params.items() if not k.startswith("block")}
    for i in range(2):
        blk = dict(params[f"block{i}"])
        attn = blk.pop("attn")
        qkv_k, qkv_b = attn["qkv"]["kernel"], attn["qkv"]["bias"]
        d = qkv_k.shape[0]
        # the fused columns are [3, heads, head_dim]
        ks, bs = qkv_k.reshape(d, 3, d), qkv_b.reshape(3, d)
        blk["attn"] = {
            name: {"kernel": ks[:, j], "bias": bs[j]} for j, name in enumerate("qkv")
        }
        blk["attn"]["o"] = attn["proj"]
        mine[f"block{i}"] = blk
    spec = get_model("gpt2_tiny", num_classes=VOCAB, dtype="float32", max_seq_len=L)
    got = spec.apply({"params": mine}, tokens, train=False)
    assert gap(got, lm.apply({"params": params}, tokens, train=False)) < 1e-5


def _causal_sdar(attn_impl):
    """``sdar_tiny`` on the causal mask: grouped heads (4 over 2)
    through ``ops/attention.dot_product_attention``."""
    return get_model(
        "sdar_tiny", num_classes=VOCAB, dtype="float32", attn_impl=attn_impl,
        block_len=0, max_seq_len=L,
    )


def _logits_and_grads(model, params, tokens):
    def loss(p):
        logits = model.apply({"params": p}, tokens, train=False)
        return jnp.sum(logits ** 2) / logits.size, logits

    (_, logits), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    return logits, grads


@pytest.fixture(scope="module")
def grouped_heads():
    """The einsum's side of both comparisons below, computed once."""
    tokens = jnp.asarray(clean_rows(2))
    model = _causal_sdar("xla")
    params = model.init(jax.random.PRNGKey(2), tokens, train=False)["params"]
    return model, params, tokens, _logits_and_grads(model, params, tokens)


@pytest.mark.parametrize("against", ["pallas", "repeated_key_heads"])
def test_the_causal_spec_model_with_grouped_heads(grouped_heads, against):
    """The einsum over grouped heads against the flash kernels (interpret
    mode), and against the einsum over the same weights with every key
    and value head's projection written out once a query head."""
    model, params, tokens, (logits, grads) = grouped_heads
    assert logits.shape == (2, L, VOCAB)
    if against == "pallas":
        got, got_grads = _logits_and_grads(_causal_sdar("pallas"), params, tokens)
        assert gap(got, logits) < 1e-5
        for a, b in zip(jax.tree.leaves(got_grads), jax.tree.leaves(grads)):
            assert gap(a, b) < 1e-4
        return
    spec = model.spec
    group = spec.heads // spec.kv_heads

    def widen(kernel):  # [hidden, KV·d] -> [hidden, H·d], key head g for queries g·r..
        w = kernel.reshape(spec.hidden, spec.kv_heads, spec.head_dim)
        return jnp.repeat(w, group, axis=1).reshape(spec.hidden, -1)

    wide = dict(params)
    for i in range(spec.layers):
        attn = dict(wide[f"block{i}"]["attn"])
        attn["k"] = {"kernel": widen(attn["k"]["kernel"])}
        attn["v"] = {"kernel": widen(attn["v"]["kernel"])}
        wide[f"block{i}"] = {**wide[f"block{i}"], "attn": attn}
    equal = get_model(
        "sdar_tiny", num_classes=VOCAB, dtype="float32", attn_impl="xla",
        block_len=0, max_seq_len=L, kv_heads=spec.heads,
    )
    got, got_grads = _logits_and_grads(equal, wide, tokens)
    assert gap(got, logits) < 1e-5
    # what does not feed a key head has the gradient it had
    assert gap(got_grads["block0"]["attn"]["q"]["kernel"],
               grads["block0"]["attn"]["q"]["kernel"]) < 1e-4
    assert gap(got_grads["tok_embed"], grads["tok_embed"]) < 1e-4


# -- layers that differ: window and full attention, a router before attention --

def _st_config(held=8, first=0, layers=8):
    return {
        "hidden_size": 64, "layers": layers, "num_attention_heads": 4,
        "num_key_value_heads": 2, "head_dim": 16, "moe_ffn_hidden_size": 32,
        "moe_num_primary_experts": held, "first_expert": first,
        "moe_num_active_primary_experts": 2, "vocab_size": VOCAB,
        "rms_norm_eps": 1e-6, "rope_theta": 1e6, "sliding_window_size": 8,
        "sliding_window_layout": [0, 1, 1, 1] * 2, "rope_layout": [0, 1, 1, 1] * 2,
        "published": {"moe_num_primary_experts": 8, "layers": 8},
        "assumed": {"router_input": "ln1"},
    }


def _st_params(cfg, seed):
    """The family's seeded weights with q, k and the experts' kernels
    ten times as loud: at hidden 64 the seed's N(0, 0.02) kernels give
    scores of a hundredth, so that every mask's softmax is all but
    uniform, and experts that add a thousandth of the residual stream;
    at the published width the scores are of order one, as here."""
    params = st.init_params(cfg, seed)
    for i in range(cfg["layers"]):
        for name in ("q", "k"):
            kernel = params[f"block{i}"]["attn"][name]["kernel"]
            params[f"block{i}"]["attn"][name]["kernel"] = 10.0 * kernel
        for name in ("w1", "w3", "w2"):
            kernel = params[f"block{i}"]["mlp"][name]["kernel"]
            params[f"block{i}"]["mlp"][name]["kernel"] = 10.0 * kernel
    return params


@pytest.fixture(scope="module")
def st_reference():
    """Seeded weights, three rows, and what ``references/smallthinker.py``
    gives on them (logits, the experts chosen, loss and gradients):
    computed once, for both implementations."""
    cfg = _st_config()
    params = _st_params(cfg, 11)
    rows = clean_rows(3, seed=5)
    tokens, labels = jnp.asarray(rows), jnp.asarray(np.roll(rows, -1, axis=1))
    want = jax.jit(lambda p: st.forward(p, tokens, cfg))(params)
    lr, gr = jax.jit(jax.value_and_grad(lambda p: st.token_loss(p, tokens, labels, cfg)))(params)
    return params, tokens, labels, want, lr, gr


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_mixed_layer_model_matches_its_reference(st_reference, attn_impl):
    """``smallthinker_tiny`` (two periods of a full layer without
    positions and three window-8 layers with rotary ones, router on
    ``ln1``'s output, ReLU gate) against ``references/smallthinker.py``
    on seeded weights at L = 32: logits, loss, every leaf's gradient,
    the experts chosen. float32 on both sides: 1e-4 of the largest
    logit, 2e-3 of a leaf's largest gradient entry (a bfloat16 product
    would read 1e-2)."""
    params, tokens, labels, (want, chosen), lr, gr = st_reference
    model = get_model(
        "smallthinker_tiny", num_classes=VOCAB, dtype="float32",
        attn_impl=attn_impl, max_seq_len=L,
    )

    def loss(params):
        logits, seen = model.apply(
            {"params": params}, tokens, train=True, mutable=["intermediates"]
        )
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - picked), (logits, seen)

    (l, (logits, seen)), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    assert logits.shape == (3, L, VOCAB) and gap(logits, want) < 1e-4
    got_chosen = jnp.stack([
        seen["intermediates"][f"block{i}"]["mlp"]["experts"][0] for i in range(8)
    ])
    assert bool(jnp.all(jnp.sort(got_chosen, -1) == jnp.sort(chosen, -1)))
    assert abs(float(l) - float(lr)) < 1e-5 * abs(float(lr))
    flat_got, flat_want = st.flatten(grads), st.flatten(gr)
    assert set(flat_got) == set(flat_want)
    for k in flat_want:
        assert gap(flat_got[k], flat_want[k]) < 2e-3, k


@pytest.fixture(scope="module")
def st_two_rows(st_reference):
    """The program's logits on the first two rows and the sound
    reference's: what each variant below is held against."""
    params, tokens = st_reference[0], st_reference[1][:2]
    model = get_model(
        "smallthinker_tiny", num_classes=VOCAB, dtype="float32",
        attn_impl="xla", max_seq_len=L,
    )
    logits = jax.jit(lambda p: model.apply({"params": p}, tokens, train=False))(params)
    return params, tokens, logits, jax.jit(lambda p: st.forward(p, tokens, _st_config())[0])(params)


@pytest.mark.parametrize(
    "variant", ["every_layer_full", "rope_everywhere", "router_after_attention", "silu"]
)
def test_the_reference_sees_each_thing_the_model_adds(st_two_rows, monkeypatch, variant):
    """A reference without one of the four (the window, the layers
    without positions, the router's input, the ReLU gate) is another
    model: its logits leave the program's by far more than the 1e-4 the
    sound one keeps."""
    params, tokens, logits, sound = st_two_rows
    other = _st_config()
    if variant == "every_layer_full":
        other["sliding_window_layout"] = [0] * 8
    elif variant == "rope_everywhere":
        other["rope_layout"] = [1] * 8
    elif variant == "router_after_attention":
        other["assumed"] = {"router_input": "ln2"}
    if variant == "silu":  # read as the reference is traced
        monkeypatch.setattr(st.jax.nn, "relu", jax.nn.silu)
    wrong = jax.jit(lambda p: st.forward(p, tokens, other)[0])(params)
    assert gap(logits, sound) < 1e-4
    assert gap(logits, wrong) > 1e-3


@pytest.mark.parametrize("kind", ["full-nope", "window-rope"])
def test_the_eight_shares_of_a_mixed_layer_add_up_to_the_uncut_reference(kind):
    """One layer of the new model, 8 experts as eight shares of one: the
    parts the shares give (each a whole layer's output: what every chip
    computes alike, the residual stream and attention, counted once)
    add up to what the uncut reference gives for the layer, with the
    router on ``ln1``'s output and the ReLU gate."""
    cfg = _st_config()
    s = st.sizes(cfg)
    layer = 0 if kind == "full-nope" else 1
    p = _st_params(cfg, 3)[f"block{layer}"]
    x = jax.random.normal(jax.random.PRNGKey(4), (2, L, 64))
    positions = jnp.arange(L)
    spec = decoder.SPECS["smallthinker_tiny"]
    assert spec.kind(layer) == decoder.LayerKind(*((0, False) if layer == 0 else (8, True)))

    def share(first):
        mlp = {k: {"kernel": v["kernel"][first:first + 1]} for k, v in p["mlp"].items()
               if k != "router"}
        mlp["router"] = p["mlp"]["router"]
        block = decoder.SpecBlock(
            dataclasses.replace(spec, experts_held=1, first_expert=first),
            jnp.float32, "xla", spec.kind(layer),
        )
        return block.apply({"params": {**p, "mlp": mlp}}, x, positions, False)

    uncut = jax.jit(lambda p: st._layer(x, p, positions, s, None, s["windowed"][layer],
                                        s["rope"][layer])[0])
    want = uncut(p)
    alike = uncut({**p, "mlp": {**p["mlp"], "w2": {"kernel": 0.0 * p["mlp"]["w2"]["kernel"]}}})
    parts = [part - alike for part in jax.jit(lambda: [share(first) for first in range(8)])()]
    assert gap(alike + sum(parts), want) < 1e-5
    assert gap(sum(parts), want - alike) < 1e-4  # the experts' part alone
    assert float(jnp.max(jnp.abs(want - alike))) > 1e-3


def test_the_specs_that_were_there_build_and_compute_what_they_did():
    """``sdar_tiny`` and ``gpt2_tiny`` after the spec gained its pattern,
    the router's input and the activation: the parameter trees they had,
    and a loss and its gradients that lower, text for text, to what they
    lowered to before (taken from the parent commit's tree, PR 31;
    ``sdar_tiny``'s anew in PR 32, whose expert layer sows its share of
    live rows and counts a stretch's rows as the sum of its groups: the
    gather, the scatter-add and the products are line for line PR 31's)."""
    if jax.__version__ != "0.9.0" or jax.device_count() != 8:
        pytest.skip("the text was taken under jax 0.9.0 on the tests' 8 host devices")
    tokens = jnp.asarray(clean_rows(2))
    was = {
        "sdar_tiny": ("7b44f98dcf0da324e30b3bfcc2969a4850ab866aabfbd78580b3b026ffbe59ba",
                      "797057832e8bff9b"),
        "gpt2_tiny": ("280764fbc3855e0c2df55564e1aeaad9db5ab255357f1110b43e8c224de4b947",
                      "a1539845ca3f9ad3"),
    }
    for name, kw, toks in (
        ("sdar_tiny", {}, jnp.concatenate([tokens, tokens], 1)),
        ("gpt2_tiny", {"max_seq_len": L}, tokens),
    ):
        model = get_model(name, num_classes=VOCAB, dtype="float32", attn_impl="xla", **kw)
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


# -- state-space layers beside an attention layer -----------------------------

def _granite_config(**over):
    """``granite_tiny`` as the ``granite`` reference reads it."""
    cfg = {
        "hidden_size": 64, "layers": 5, "num_attention_heads": 4,
        "num_key_value_heads": 2, "shared_intermediate_size": 96,
        "vocab_size": VOCAB, "rms_norm_eps": 1e-5,
        "layer_types": ["mamba", "mamba", "attention", "mamba", "mamba"],
        "mamba_n_heads": 4, "mamba_d_head": 16, "mamba_d_state": 16,
        "mamba_n_groups": 1, "mamba_d_conv": 4, "mamba_chunk_size": 8,
        "embedding_multiplier": 12, "attention_multiplier": 0.0625,
        "residual_multiplier": 0.22, "logits_scaling": 8,
        "published": {"layers": 5},
    }
    cfg.update(over)
    return cfg


def _granite_params(cfg, seed):
    """The family's seeded weights, with ``D`` drawn too (its published
    initialisation is 1: a side that dropped it would not show)."""
    params = granite.init_params(cfg, seed)
    for i, kind in enumerate(cfg["layer_types"][:cfg["layers"]]):
        if kind == "mamba":
            key = jax.random.fold_in(jax.random.PRNGKey(seed), i)
            params[f"block{i}"]["ssm"]["D"] = 1.0 + 0.3 * jax.random.normal(key, (4,))
    return params


GRANITE_L = 27  # chunk 8: three chunks and a ragged fourth


@pytest.fixture(scope="module")
def granite_reference():
    """Seeded weights, three rows, and what the recurrence reference gives
    on them (logits, loss, gradients): computed once, for both
    implementations and the variants below."""
    cfg = _granite_config()
    params = _granite_params(cfg, 11)
    rows = np.random.default_rng(5).integers(0, VOCAB, (3, GRANITE_L), dtype=np.int32)
    tokens, labels = jnp.asarray(rows), jnp.asarray(np.roll(rows, -1, axis=1))
    want = jax.jit(lambda p: granite.forward(p, tokens, cfg))(params)
    lr, gr = jax.jit(jax.value_and_grad(
        lambda p: granite.token_loss(p, tokens, labels, cfg)
    ))(params)
    return cfg, params, tokens, labels, want, lr, gr


@pytest.mark.parametrize("attn_impl", ["xla", "pallas"])
def test_the_state_space_model_matches_its_reference(granite_reference, attn_impl):
    """``granite_tiny`` (two Mamba-2 layers, an attention layer without
    positions whose scores take the spec's scale, two more Mamba-2
    layers; a dense gated MLP; the four multipliers; tied head) against
    ``references/granite.py``, whose state-space layer is the token-by-
    token recurrence, on seeded weights at L = 27: logits, loss, every
    leaf's gradient. float32 on both sides: 1e-5 of the largest logit,
    1e-4 of a leaf's largest gradient entry."""
    cfg, params, tokens, labels, want, lr, gr = granite_reference
    model = get_model(
        "granite_tiny", num_classes=VOCAB, dtype="float32", attn_impl=attn_impl,
        max_seq_len=32,
    )

    def loss(params):
        logits = model.apply({"params": params}, tokens, train=True)
        logz = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
        return jnp.mean(logz - picked), logits

    (l, logits), grads = jax.jit(jax.value_and_grad(loss, has_aux=True))(params)
    assert logits.shape == (3, GRANITE_L, VOCAB) and gap(logits, want) < 1e-5
    assert abs(float(l) - float(lr)) < 1e-6 * abs(float(lr))
    flat_got, flat_want = granite.flatten(grads), granite.flatten(gr)
    assert set(flat_got) == set(flat_want)
    for k in flat_want:
        assert gap(flat_got[k], flat_want[k]) < 1e-4, k
    # the tree is the one the family's shapes state, and block remat's is the same
    assert {k: v.shape for k, v in flat_got.items()} == granite.param_shapes(cfg)


@pytest.fixture(scope="module")
def granite_two_rows(granite_reference):
    """The program's logits on the first two rows and the sound
    reference's: what each variant below is held against."""
    cfg, params, tokens = granite_reference[:3]
    tokens = tokens[:2]
    model = get_model(
        "granite_tiny", num_classes=VOCAB, dtype="float32", attn_impl="xla",
        max_seq_len=32,
    )
    logits = jax.jit(lambda p: model.apply({"params": p}, tokens, train=False))(params)
    return params, tokens, logits, jax.jit(lambda p: granite.forward(p, tokens, cfg))(params)


@pytest.mark.parametrize(
    "variant", ["no_decay", "no_convolution", "gate_after_the_norm", "residual_one"]
)
def test_the_reference_sees_each_mechanism_of_the_state_space_layer(granite_two_rows, variant):
    """A reference without one of the four (the state's decay, the
    convolution, the gate before the norm, the residual multiplier) is
    another model: its logits leave the program's by more than ten times
    the 1e-5 the sound one keeps."""
    params, tokens, logits, sound = granite_two_rows
    other = _granite_config(**{
        "no_decay": {"without": ["decay"]},
        "no_convolution": {"without": ["conv"]},
        "gate_after_the_norm": {"without": ["gate_first"]},
        "residual_one": {"residual_multiplier": 1.0},
    }[variant])
    assert gap(logits, sound) < 1e-5
    assert gap(logits, jax.jit(lambda p: granite.forward(p, tokens, other))(params)) > 1e-4


def test_the_published_state_space_spec_against_the_catalog_s_numbers():
    """``granite_4_0_h_micro`` is the catalog row's ``config``, number
    for number, and at the cell's cut it builds the parameters ISSUE 33
    counts: 76,182,976 a Mamba-2 layer, 60,821,504 the attention layer,
    772,160,448 in ten layers and an eighth of the vocabulary."""
    spec = decoder.SPECS["granite_4_0_h_micro"]
    assert (spec.hidden, spec.layers, spec.heads, spec.kv_heads, spec.head_dim) == (
        2048, 40, 32, 8, 2048 // 32)
    assert spec.ssm_heads * spec.ssm_head_dim == 2 * spec.hidden  # mamba_expand
    assert (spec.ssm_state, spec.ssm_groups, spec.ssm_conv, spec.ssm_chunk) == (128, 1, 4, 256)
    assert [i for i in range(40) if spec.kind(i).mixer == "attention"] == [5, 15, 25, 35]
    assert (spec.embed_scale, spec.attn_scale, spec.residual_scale, spec.logits_scale) == (
        12.0, 0.015625, 0.22, 8.0)
    assert spec.norm_eps == 1e-5 and spec.tied_head and not spec.bias and spec.ffn == "glu"
    model = get_model("granite_4_0_h_micro", layers=10, num_classes=12544)
    shapes = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((1, 8), jnp.int32), train=False)
    )["params"]
    count = lambda tree: sum(int(np.prod(v.shape)) for v in jax.tree.leaves(tree))  # noqa: E731
    assert count(shapes["block0"]) == 76_182_976
    assert count(shapes["block5"]) == 60_821_504
    assert count(shapes) == 772_160_448
    assert set(shapes["block0"]["ssm"]) == {
        "in_proj", "conv", "dt_bias", "A_log", "D", "norm", "out_proj"}
    assert shapes["block0"]["ssm"]["in_proj"]["kernel"].shape == (2048, 8512)
    assert shapes["block0"]["ssm"]["conv"]["kernel"].shape == (4352, 4)
    assert "attn" in shapes["block5"] and "ssm" not in shapes["block5"]


@pytest.mark.parametrize("fault", ["block_len", "window", "rope", "heads", "mixer"])
def test_a_state_space_spec_that_cannot_be_built_is_refused(fault):
    spec = decoder.SPECS["granite_tiny"]
    bad = {
        "block_len": dict(block_len=4),
        "window": dict(pattern=(decoder.LayerKind(8, False, "mamba2"),)),
        "rope": dict(pattern=(decoder.LayerKind(0, True, "mamba2"),)),
        "heads": dict(ssm_groups=3),
        "mixer": dict(pattern=(decoder.LayerKind(0, False, "retention"),)),
    }[fault]
    model = decoder.SpecDecoder(
        dataclasses.replace(spec, **bad), vocab_size=VOCAB, dtype=jnp.float32,
        attn_impl="xla",
    )
    with pytest.raises(ValueError):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 16), jnp.int32), train=False)


def test_the_model_s_own_draw_is_the_mixers_published_initialisation():
    model = get_model("granite_tiny", num_classes=VOCAB, dtype="float32", attn_impl="xla")
    p = jax.jit(lambda t: model.init(jax.random.PRNGKey(2), t, train=False))(
        jnp.zeros((1, 16), jnp.int32))["params"]
    ssm = p["block0"]["ssm"]
    assert bool(jnp.all(ssm["D"] == 1.0))
    a = jnp.exp(ssm["A_log"])
    assert bool(jnp.all((a >= 1.0) & (a <= 16.0)))
    step = jax.nn.softplus(ssm["dt_bias"])
    assert bool(jnp.all((step >= 1e-3 * 0.999) & (step <= 1e-1 * 1.001)))
    assert float(jnp.max(jnp.abs(ssm["conv"]["kernel"]))) <= 0.5


def test_the_block_diffusion_mask_takes_no_window():
    model = get_model(
        "smallthinker_tiny", num_classes=VOCAB, dtype="float32", block_len=4
    )
    with pytest.raises(ValueError, match="window"):
        model.init(jax.random.PRNGKey(0), jnp.zeros((1, 2 * L), jnp.int32), train=False)


# -- the attention core -------------------------------------------------------

def test_the_mask_is_the_reference_s():
    i = jnp.arange(2 * L)
    want = ref.allowed(i[:, None], i[None, :], L, B)
    assert bool(jnp.all(block_diffusion_mask(L, B) == want))
    # a row of the noised half sees its own block and the clean blocks before
    assert int(want[5].sum()) == B + B and int(want[L + 5].sum()) == 2 * B


def _qkv(length, heads, kv, d, seed=0):
    key = jax.random.PRNGKey(seed)
    return (
        jax.random.normal(key, (2, 2 * length, heads, d)),
        jax.random.normal(jax.random.fold_in(key, 1), (2, 2 * length, kv, d)),
        jax.random.normal(jax.random.fold_in(key, 2), (2, 2 * length, kv, d)),
        jax.random.normal(jax.random.fold_in(key, 3), (2, 2 * length, heads, d)),
    )


def _dense(q, k, v, m):
    """Softmax attention over the keys ``m [q, k]`` lets a query see (a
    key head for its group of query heads), and the rows' logsumexp."""
    rep = q.shape[2] // k.shape[2]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, rep, 2)) * q.shape[-1] ** -0.5
    s = jnp.where(m, s, -1e30)
    lse = jax.nn.logsumexp(s, -1)
    p = jnp.where(m, jnp.exp(s - lse[..., None]), 0.0)
    return jnp.einsum("bhqk,bkhd->bqhd", p, jnp.repeat(v, rep, 2)), lse.transpose(0, 2, 1)


def test_the_einsum_core_is_the_reference_s_dense_attention():
    q, k, v, _ = _qkv(L, 4, 2, 16)
    got = block_diffusion_attention(q, k, v, block_len=B, impl="xla")
    want = ref._attention(q, k, v, L, B).reshape(got.shape)
    assert gap(got, want) < 1e-5


@pytest.mark.parametrize(
    "length,heads,kv,d",
    [(64, 4, 2, 16), (128, 4, 2, 128)],
    ids=["narrow-heads", "grouped-in-place"],
)
def test_the_kernels_match_the_einsum_forward_and_backward(length, heads, kv, d):
    q, k, v, w = _qkv(length, heads, kv, d)

    def run(impl):  # one program a side: the gradients, and the output as their aux
        def g(q, k, v):
            out = block_diffusion_attention(q, k, v, block_len=B, impl=impl)
            return jnp.sum(out * w), out
        return jax.jit(jax.grad(g, (0, 1, 2), has_aux=True))(q, k, v)

    (got, out), (want, want_out) = run("pallas"), run("xla")
    assert gap(out, want_out) < 1e-5
    for a, b in zip(got, want):
        assert gap(a, b) < 1e-5


@pytest.mark.parametrize("strict", [False, True])
def test_a_block_causal_pass_gives_the_rows_logsumexp_and_its_gradient(strict):
    q, k, v, w = _qkv(64, 4, 2, 128)
    t = q.shape[1]
    live = jnp.arange(t) >= (B if strict else 0)  # the first block sees nothing

    r, c = jnp.arange(t)[:, None] // B, jnp.arange(t)[None, :] // B
    dense = functools.partial(_dense, m=c < r if strict else c <= r)

    def kernel(q, k, v):
        return flash.flash_attention_stats(
            q, k, v, mask=flash.Mask(True, B, strict), block=64, interpret=True
        )

    def run(f):  # one program a side: the gradients, and the logsumexp as their aux
        def g(q, k, v):
            out, lse = f(q, k, v)
            return jnp.sum(jnp.where(live[None, :, None, None], out * w, 0.0)) + jnp.sum(
                jnp.where(live[None, :, None], lse * w[..., 0], 0.0)
            ), lse
        return jax.jit(jax.grad(g, (0, 1, 2), has_aux=True))(q, k, v)

    (got, lse), (want, _) = run(kernel), run(dense)
    for a, b in zip(got, want):
        assert bool(jnp.all(jnp.isfinite(a))) and gap(a, b) < 1e-5
    if strict:
        assert float(lse[0, 0, 0]) < -1e29


@pytest.mark.parametrize("heads", [2, 8], ids=["rep2", "rep8"])
@pytest.mark.parametrize(
    "mask", [flash.Mask(True), flash.Mask(True, B), flash.Mask(True, B, strict=True),
             flash.Mask(False, B, own=True)],
    ids=["causal", "blocks<=", "blocks<", "own-block"],
)
def test_a_pass_over_several_resident_blocks(monkeypatch, mask, heads):
    """Sixteen blocks in four resident ones: a program's index maps have
    to name the resident block and the walked head they mean (a wrong
    one reads a neighbour's rows, which short sequences cannot show).
    The one backward kernel sums ``dq`` over the sixteen k blocks'
    programs, a slot a query head and resident block, and writes each
    block in the last one's pass: every row of ``dq``, ``dk``, ``dv`` is
    compared, with the rows' logsumexp in the loss (``dlse``)."""
    monkeypatch.setattr(flash, "_TILE_ELEMS", 4 * 32 * 32)
    t, kv, d = 512, 1, 128
    assert flash._plan(t, 32) == (32, 4, 4)
    key = jax.random.PRNGKey(3)
    q = jax.random.normal(key, (1, t, heads, d))
    k, v = (jax.random.normal(jax.random.fold_in(key, i), (1, t, kv, d)) for i in (1, 2))
    live = jnp.arange(t) >= (B if mask.strict else 0)
    r, c = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    if mask.own:
        m = r // B == c // B
    else:
        m = (c // mask.gran < r // mask.gran) if mask.strict else (c // mask.gran <= r // mask.gran)

    dense = functools.partial(_dense, m=m)

    def kernel(q, k, v):
        return flash.flash_attention_stats(q, k, v, mask=mask, block=32, interpret=True)

    def run(f):  # one program a side: the gradients, and the output as their aux
        def g(q, k, v):
            out, lse = f(q, k, v)
            return jnp.sum(
                jnp.where(live[None, :, None, None], jnp.sin(out), 0.0)
            ) + jnp.sum(jnp.where(live[None, :, None], jnp.cos(lse), 0.0)), out
        return jax.jit(jax.grad(g, (0, 1, 2), has_aux=True))(q, k, v)

    (got, out), (want, want_out) = run(kernel), run(dense)
    assert gap(out[:, B:], want_out[:, B:]) < 1e-5
    for a, b in zip(got, want):
        assert gap(a, b) < 1e-5


# -- the expert layer ---------------------------------------------------------

def _layer(seed=0, tokens=64, d=64, f=32, experts=8):
    key = jax.random.PRNGKey(seed)
    x = jax.random.normal(key, (tokens, d))
    p = {
        "router": {"kernel": jax.random.normal(jax.random.fold_in(key, 1), (d, experts))},
        "w1": {"kernel": 0.2 * jax.random.normal(jax.random.fold_in(key, 2), (experts, d, f))},
        "w3": {"kernel": 0.2 * jax.random.normal(jax.random.fold_in(key, 3), (experts, d, f))},
        "w2": {"kernel": 0.2 * jax.random.normal(jax.random.fold_in(key, 4), (experts, f, d))},
    }
    return x, p


def _share(x, p, first, held, top_k=2):
    routed = moe.route_top_k(
        jnp.matmul(x, p["router"]["kernel"], precision="highest"), top_k
    )
    cut = slice(first, first + held)
    return moe.held_experts_ffn(
        x, routed, p["w1"]["kernel"][cut], p["w3"]["kernel"][cut],
        p["w2"]["kernel"][cut], first=first,
        num_experts=p["router"]["kernel"].shape[1],
    )


def _reference_layer(x, p, cfg):
    return ref._experts(x, p, ref.sizes(cfg), None)[0]


def test_the_shares_of_the_experts_add_up_to_the_uncut_layer():
    """8 experts as 4 shares of 2: what the four give, added, is what
    the reference gives for the whole layer; and one share is what the
    reference gives for that share."""
    x, p = _layer()
    parts = [_share(x, p, first, 2)[0] for first in (0, 2, 4, 6)]
    assert gap(sum(parts), _reference_layer(x, p, config(held=8))) < 1e-5
    cut = {k: {"kernel": v["kernel"][2:4]} for k, v in p.items() if k != "router"}
    cut["router"] = p["router"]
    assert gap(parts[1], _reference_layer(x, cut, config(held=2, first=2))) < 1e-5
    drawn = sum(int(_share(x, p, first, 2)[1].sum()) for first in (0, 2, 4, 6))
    assert drawn == x.shape[0] * 2  # every pair falls to one share


@pytest.mark.parametrize("skew", [0.0, 50.0], ids=["even", "onto-one-expert"])
def test_no_token_is_dropped(monkeypatch, skew):
    """A router skewed onto one held expert sends it every token, four
    token, more than a stretch holds: the further stretches take the rest, and output
    and gradients are the dense sum's."""
    monkeypatch.setattr(moe, "_ROW_TILE", 8)
    x, p = _layer(tokens=256)
    x = x.at[:, 0].set(1.0)  # a feature every token has, for the skew to pull on
    p["router"]["kernel"] = p["router"]["kernel"].at[0, 2].add(skew)
    assert moe.usual_cap(512, 2, 8) == 256

    def mine(x, p):
        return _share(x, p, 2, 2)[0]

    def dense(x, p):
        cut = {k: {"kernel": v["kernel"][2:4]} for k, v in p.items() if k != "router"}
        cut["router"] = p["router"]
        return _reference_layer(x, cut, config(held=2, first=2))

    y, drawn = jax.jit(lambda x, p: _share(x, p, 2, 2))(x, p)
    if skew:
        assert int(drawn[0]) == 256 and int(drawn.sum()) > 256
    assert gap(y, jax.jit(dense)(x, p)) < 1e-5
    got = jax.jit(jax.grad(lambda x, p: jnp.sum(jnp.sin(mine(x, p))), (0, 1)))(x, p)
    want = jax.jit(jax.grad(lambda x, p: jnp.sum(jnp.sin(dense(x, p))), (0, 1)))(x, p)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        assert gap(a, b) < 1e-4


def test_the_model_reports_its_experts_load():
    model = get_model(
        "sdar_tiny", num_classes=VOCAB, dtype="float32", experts_held=4,
        first_expert=2,
    )
    tokens = jnp.asarray(ref.noise_rows(clean_rows(), config())[0])
    variables = jax.jit(lambda t: model.init(jax.random.PRNGKey(0), t, train=False))(tokens)
    assert variables["params"]["block0"]["mlp"]["w1"]["kernel"].shape == (4, 64, 32)
    assert variables["params"]["block0"]["mlp"]["router"]["kernel"].shape == (64, 8)
    _, seen = jax.jit(lambda p: model.apply(
        {"params": p}, tokens, train=True, mutable=["stats", "intermediates"],
    ))(variables["params"])
    stats = seen["stats"]["block1"]["mlp"]
    chosen = seen["intermediates"]["block1"]["mlp"]["experts"][0]
    held = int(((chosen >= 2) & (chosen < 6)).sum())
    assert float(stats["moe.pairs_local"][0]) == held
    assert float(stats["moe.expert_load_max_over_mean"][0]) >= 1.0


# -- noising and loss ---------------------------------------------------------

def test_the_noising_is_the_reference_s_and_a_function_of_the_rows():
    rows = clean_rows(5, seed=3)
    got = block_diffusion_noise(rows, block_len=B, t_min=0.125, mask_id=VOCAB - 1)
    want = ref.noise_rows(rows, config())
    for a, b in zip(got, want):
        assert a.dtype == b.dtype and np.array_equal(a, b)
    inputs, targets, weights = got
    again = block_diffusion_noise(rows[::-1], block_len=B, t_min=0.125, mask_id=VOCAB - 1)
    assert np.array_equal(again[0][::-1], inputs)  # a row's draw is its own
    masked = targets >= 0
    assert np.array_equal(inputs[:, L:], rows)
    assert np.all(inputs[:, :L][masked] == VOCAB - 1)
    assert np.array_equal(inputs[:, :L][~masked], rows[~masked])
    assert np.all(weights[masked] >= 1.0) and np.all(weights[masked] <= 8.0)
    assert np.all(weights[~masked] == 0.0)
    # one level a block
    per_block = weights.reshape(5, L // B, B)
    level = per_block.max(-1, keepdims=True)
    assert np.all((per_block == 0.0) | (per_block == level))


def test_the_weighted_loss_against_a_hand_count():
    logits = jnp.log(jnp.asarray([
        [[0.5, 0.25, 0.25], [0.1, 0.8, 0.1]],
        [[0.2, 0.2, 0.6], [1 / 3, 1 / 3, 1 / 3]],
    ]))
    targets = jnp.asarray([[0, -1], [2, 1]])
    weights = jnp.asarray([[2.0, 5.0], [1.0, 4.0]])  # the ignored one's is not read
    want = (2.0 * -np.log(0.5) + 1.0 * -np.log(0.6) + 4.0 * -np.log(1 / 3)) / 4
    got = weighted_cross_entropy_loss(logits, targets, weights)
    assert abs(float(got) - want) < 1e-6
    grad = jax.grad(lambda z: weighted_cross_entropy_loss(z, targets, weights))(logits)
    assert float(jnp.abs(grad[0, 1]).max()) == 0.0  # an ignored position has no say
    assert abs(float(grad[1, 0, 2]) - (0.6 - 1.0) / 4) < 1e-6


# -- every other model's step -------------------------------------------------

def test_the_two_element_batch_compiles_to_the_step_it_compiled_to():
    """``lm_tiny``'s train step over ``(tokens, labels)``, lowered, is
    text for text what it was (the weighted objective, the sown
    statistics and the batch's prefix spec add nothing to a step that
    does not use them). Taken anew in PR 28, whose loss reads the logits
    in place (PR 27's text held the float32 copy and the gather), and in
    PR 29, whose one einsum core (``ops/attention._xla_attention``)
    carries the group axis at width 1 for equal heads: the attention
    core's operations alone differ from PR 28's text, each by a unit
    axis."""
    if jax.__version__ != "0.9.0" or jax.device_count() != 8:
        pytest.skip("the text was taken under jax 0.9.0 on the tests' 8 host devices")
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh
    from distributeddeeplearning_tpu.training.optimizer import create_optimizer
    from distributeddeeplearning_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    cfg = TrainConfig(
        model="lm_tiny", num_classes=256, compute_dtype="float32",
        batch_size_per_device=2, optimizer="adamw", weight_decay=0.0,
        warmup_epochs=0, lr_schedule="constant", fake=True, epochs=1,
    )
    model = get_model("lm_tiny", num_classes=256, dtype="float32", max_seq_len=32)
    tx, _ = create_optimizer(cfg, 10, world_size=1)
    state = create_train_state(model, cfg, tx, input_shape=(1, 32), input_dtype=jnp.int32)
    step = make_train_step(model, tx, data_parallel_mesh(1), cfg)
    x = jnp.zeros((2, 32), jnp.int32)
    text = step._resolve(state, False).lower(state, (x, x)).as_text()
    assert hashlib.sha256(text.encode()).hexdigest() == (
        "99cf36b5bedc2b98b3bfdacd00ff4a04beddf5939bc5fad27076a4ed98b3cae3"
    )
