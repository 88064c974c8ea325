"""The flash kernels through the TPU's own compiler, with no chip: the
compiler is installed with libtpu and compiles for a v5e that is
described and not attached. What interpret mode cannot show is shown
here: that Mosaic takes the kernels at the widths the benchmark runs,
and that a model left at its defaults reaches them, under the scope
the device metrics read. Nothing runs, so nothing here is a speed.

One file, and the topology inside a fixture: only the worker that is
given this file loads the TPU's library.
"""

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.models import get_model
from distributeddeeplearning_tpu.models.transformer_lm import TRAIN_STEP_GROUPS
from distributeddeeplearning_tpu.obs import programs
from distributeddeeplearning_tpu.ops.pallas.flash import flash_attention


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or it is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize(
    "shape,causal",
    [
        ((4, 1024, 12, 64), True),  # GPT-2 124M: two heads a program, b = 512
        ((4, 768, 12, 64), True),  # b = 256, three blocks
        ((2, 1000, 12, 64), False),  # the keys' padding masked
        ((2, 1024, 4, 128), True),  # one head a program
        ((1, 8192, 8, 64), True),  # resident blocks stream along the grid
        ((2, 1024, 8, 96), True),  # head blocks off the lanes: transposed
    ],
)
def test_mosaic_takes_the_kernels(one_chip, shape, causal):
    arg = jax.ShapeDtypeStruct(shape, jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out = flash_attention(q, k, v, causal=causal, interpret=False)
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(arg, arg, arg).compile()
    # forward, backward
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def test_a_default_lm_reaches_the_kernel_under_attn_core(one_chip, monkeypatch):
    """``get_model`` with no ``attn_impl``, a sequence the rule takes, the
    backend query answered as on the chip: every layer's attention core
    is two Pallas kernels, they stand under ``attn_core`` in the
    compiled program's scope table (forward and backward: the custom
    VJP keeps the scope), and the trace counted what it chose."""
    seq = 640
    model = get_model("lm_tiny", num_classes=256, max_seq_len=seq, dtype="bfloat16")
    tokens = jnp.zeros((2, seq), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    )
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip), params
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    jax.clear_caches()  # attn.bwd.* counts traces, and a jitted core traces once
    obs.reset()

    def loss(params, tokens):
        logits = model.apply({"params": params}, tokens, train=True)
        return jnp.sum(logits.astype(jnp.float32))

    tok = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype, sharding=one_chip)
    compiled = jax.jit(jax.grad(loss)).lower(params, tok).compile()
    totals = obs.get_bus().totals()
    obs.reset()
    layers = 2  # lm_tiny
    assert totals["attn.impl.pallas"]["count"] == layers
    assert not any(k.startswith("attn.impl.") and k != "attn.impl.pallas" for k in totals)
    # the jitted core is traced once for the layers' one signature
    assert totals["attn.bwd.fused"]["count"] == 1
    scopes = programs.parse_hlo_scopes(compiled.as_text())
    assert programs.kernel_calls_by_group(scopes, TRAIN_STEP_GROUPS) == {
        "attn_core": 2 * layers
    }
    kernels = [p for p in scopes.values() if p.endswith("/" + programs.KERNEL_CALL)]
    assert sum(programs.BACKWARD in p for p in kernels) == layers


@pytest.mark.parametrize(
    "mask", [(True, 4, False), (True, 4, True), (False, 4, False, True)],
    ids=["blocks<=", "blocks<", "own-block"],
)
def test_mosaic_takes_the_block_diffusion_passes(one_chip, mask):
    """The three passes of ``ops/attention.block_diffusion_attention`` at
    the SDAR cell's shapes: 32 query heads reading 4 key heads of 128 in
    place, 4,096 positions, the diagonal tile masked in units of 4 (or
    nothing but it computed); the forward, and one backward kernel whose
    last grid axis walks the eight query heads of a key head, a slot of
    ``dq``'s sums in VMEM for each."""
    from distributeddeeplearning_tpu.ops.pallas.flash import Mask, flash_attention_stats

    q = jax.ShapeDtypeStruct((1, 4096, 32, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 4096, 4, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out, lse = flash_attention_stats(
            q, k, v, mask=Mask(*mask), interpret=False
        )
        return jnp.sum(out.astype(jnp.float32)) + jnp.sum(lse)

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


@pytest.mark.parametrize("remat", [False, True], ids=["plain", "remat"])
def test_an_sdar_layer_reaches_its_kernels_under_their_scopes(one_chip, monkeypatch, remat):
    """One layer of ``sdar_30b_a3b`` at its published widths (16 of the
    128 experts held), left at its defaults and asked as on the chip:
    six flash kernels under ``attn_core`` (three passes, forward and
    backward; nine under block remat, which keeps nothing of a
    block-diffusion layer and runs each pass's forward again), the
    grouped products under ``moe_experts``, and every scope group of
    both tables present in the compiled program."""
    from distributeddeeplearning_tpu.models.decoder import MOE_GROUPS

    model = get_model(
        "sdar_30b_a3b", num_classes=18992, dtype="bfloat16", layers=1,
        experts_held=16, remat=remat,
    )
    tokens = jnp.zeros((1, 8192), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    )
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip), params
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    jax.clear_caches()  # attn.bwd.* counts traces, and a jitted core traces once
    obs.reset()

    def objective(params, tokens):  # not `loss`: jit(loss) would read as the loss scope
        logits = model.apply({"params": params}, tokens, train=True)
        return jnp.sum(logits.astype(jnp.float32))

    tok = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype, sharding=one_chip)
    compiled = jax.jit(jax.grad(objective)).lower(params, tok).compile()
    totals = obs.get_bus().totals()
    obs.reset()
    assert totals["attn.impl.pallas"]["count"] == 1
    assert totals["attn.mask.block_diffusion"]["count"] == 1
    assert totals["attn.bwd.fused"]["count"] == 3  # a backward kernel a pass
    assert totals["moe.impl.ragged_dot"]["count"] == 1
    assert totals["attn.fwd.named"]["count"] == 3  # a pass
    scopes = programs.parse_hlo_scopes(compiled.as_text())
    assert programs.kernel_calls_by_group(scopes, TRAIN_STEP_GROUPS)["attn_core"] == (
        9 if remat else 6
    )
    assert programs.kernel_calls_by_group(scopes, MOE_GROUPS).get("moe_experts", 0) >= 3
    assert programs.groups_in(scopes, MOE_GROUPS) == {g for g, _ in MOE_GROUPS}
    assert programs.groups_in(scopes, TRAIN_STEP_GROUPS) >= {
        "attn_core", "attn_proj", "mlp", "head_loss", "norm_residual"
    }


@pytest.mark.parametrize(
    "t,heads,window", [(16384, 28, 4096), (16384, 28, 0), (4352, 4, 1000)],
    ids=["cell-window", "cell-full", "b256-sub8"],
)
def test_mosaic_takes_the_window_rule_at_the_long_cell_s_shapes(one_chip, t, heads, window):
    """``Mask(causal, window)`` at the 16k cell's shapes: 28 query heads
    reading 4 key heads of 128 in place over 16,384 rows (blocks of 512,
    two a resident block; the backward's ``dq`` sums 7 × 16,384 × 128 × 4
    B = 56 MiB of VMEM), and a length that takes blocks of 256, eight a
    resident block (a static branch a live span)."""
    from distributeddeeplearning_tpu.ops.pallas.flash import Mask, flash_attention_stats

    q = jax.ShapeDtypeStruct((1, t, heads, 128), jnp.bfloat16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, t, 4, 128), jnp.bfloat16, sharding=one_chip)

    def loss(q, k, v):
        out, _ = flash_attention_stats(
            q, k, v, mask=Mask(True, window=window), interpret=False
        )
        return jnp.sum(out.astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2))).lower(q, kv, kv).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def test_a_period_of_mixed_layers_reaches_its_kernels_under_their_kinds(one_chip, monkeypatch):
    """One period of ``smallthinker_21b_a3b`` at its published widths (8
    of the 64 experts held, 16,384 positions), left at its defaults and
    asked as on the chip: a full layer's kernels under ``attn_full``,
    three window layers' under ``attn_window``, the router's product
    under ``moe_route``, every group of the three tables present."""
    from distributeddeeplearning_tpu.models.decoder import ATTN_KIND_GROUPS, MOE_GROUPS

    model = get_model(
        "smallthinker_21b_a3b", num_classes=18992, dtype="bfloat16", layers=4,
        experts_held=8, remat=True,
    )
    tokens = jnp.zeros((1, 16384), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    )
    params = jax.tree.map(
        lambda p: jax.ShapeDtypeStruct(p.shape, p.dtype, sharding=one_chip), params
    )
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    jax.clear_caches()
    obs.reset()

    def objective(params, tokens):
        logits = model.apply({"params": params}, tokens, train=True)
        # not the plain sum: a cotangent of all ones makes the head's
        # gradient a broadcast of column sums, and with the row kernel in
        # the step XLA:TPU's simplifier writes that broadcast with the
        # wrong dimension and fails its own verifier (PR 32); no loss
        # hands the head a constant
        return jnp.sum(jnp.square(logits.astype(jnp.float32)))

    tok = jax.ShapeDtypeStruct(tokens.shape, tokens.dtype, sharding=one_chip)
    compiled = jax.jit(jax.grad(objective)).lower(params, tok).compile()
    totals = obs.get_bus().totals()
    obs.reset()
    assert totals["attn.mask.window"]["count"] == 3 * totals["attn.mask.causal"]["count"]
    assert totals["decoder.layer.window"]["count"] == 3 * totals["decoder.layer.full"]["count"]
    assert totals["moe.route.before_attention"]["count"] == totals["moe.impl.ragged_dot"]["count"]
    assert totals["moe.rows.impl.kernel"]["count"] == totals["moe.impl.ragged_dot"]["count"]
    assert totals["attn.window.blocks"]["count"] >= 2  # forward and backward, traced once
    scopes = programs.parse_hlo_scopes(compiled.as_text())
    by_kind = programs.kernel_calls_by_group(scopes, ATTN_KIND_GROUPS)
    # a layer's forward and its backward: block remat keeps what the
    # forward wrote (the two names of `flash._named`), so the recomputed
    # block holds no forward kernel (3 and 9, 12 in all, until PR 34)
    assert totals["attn.fwd.named"]["count"] == 2  # a kind's jitted core, traced once
    assert (by_kind["attn_full"], by_kind["attn_window"]) == (2, 6)
    assert programs.kernel_calls_by_group(scopes, TRAIN_STEP_GROUPS)["attn_core"] == 8
    assert programs.groups_in(scopes, MOE_GROUPS) == {g for g, _ in MOE_GROUPS}
    assert programs.groups_in(scopes, ATTN_KIND_GROUPS) == {g for g, _ in ATTN_KIND_GROUPS}


@pytest.mark.parametrize(
    "tokens,width,k,experts,held",
    [(16384, 2560, 6, 64, 8), (16384, 2048, 8, 128, 16)],
    ids=["smallthinker-t16k", "sdar-bd4k"],
)
def test_mosaic_takes_the_row_kernels_and_the_layer_sorts_once(
    one_chip, monkeypatch, tokens, width, k, experts, held
):
    """``ops/moe.held_experts_ffn`` at both expert cells' shapes, the rule
    answered as on the chip: Mosaic takes ``to_tiles`` and ``rows_by_place``
    (``ops/pallas/moe_rows.py``) forward and backward (a row of 2,560 as 24
    pieces of 128 lanes, one of 2,048 as 16); the compiled layer sorts the
    pairs once (``order``) and nothing else under the dispatch and combine
    scopes, and scatters nothing there: not rows, not gate cotangents; and
    the scope table puts the kernels (the products' rows tiled and summed
    by place, forward; ``d rows`` tiled and summed by place, backward: four
    for the usual stretch and four in the branch that computes further
    ones) under ``moe_dispatch``, where the device metric reads them."""
    import re

    from distributeddeeplearning_tpu.models.decoder import MOE_GROUPS
    from distributeddeeplearning_tpu.ops import moe

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    jax.clear_caches()
    obs.reset()

    def layer(x, logits, w1, w3, w2):
        with jax.named_scope(moe.ROUTE):
            routed = moe.route_top_k(logits, k)
        y, _ = moe.held_experts_ffn(x, routed, w1, w3, w2, first=0, num_experts=experts)
        return jnp.sum(y.astype(jnp.float32))

    def arg(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_chip)

    ffn = 768
    compiled = jax.jit(jax.value_and_grad(layer, (0, 1, 2, 3, 4))).lower(
        arg((tokens, width), jnp.bfloat16), arg((tokens, experts), jnp.float32),
        arg((held, width, ffn), jnp.float32), arg((held, width, ffn), jnp.float32),
        arg((held, ffn, width), jnp.float32),
    ).compile()
    totals = obs.get_bus().totals()
    obs.reset()
    assert totals["moe.rows.impl.kernel"]["count"] == 1
    text = compiled.as_text()
    assert text.count("moe_rows_to_tiles") and text.count("moe_rows_by_place")
    moved = [
        line for line in text.splitlines()
        if re.search(r"op_name=\"[^\"]*(moe_dispatch|moe_combine)", line)
    ]
    sorts = [line for line in moved if re.search(r" sort\(", line)]
    assert len(sorts) == 1 and f"s32[{tokens * k}]" in sorts[0]  # the pairs, once
    assert not [line for line in moved if re.search(r" scatter\(", line)]
    scopes = programs.parse_hlo_scopes(text)
    assert programs.kernel_calls_by_group(scopes, MOE_GROUPS)["moe_dispatch"] == 8
    kernels = [
        p for p in scopes.values()
        if p.endswith("/" + programs.KERNEL_CALL) and "moe_rows" in p
    ]
    assert len(kernels) == 8 and sum(programs.BACKWARD in p for p in kernels) == 4


@pytest.mark.parametrize(
    "shape,groups,state,chunk,dtype",
    [
        ((2, 4096, 64, 64), 1, 128, 256, jnp.bfloat16),  # the Granite cell's: two heads a lane block
        ((1, 1024, 16, 128), 1, 128, 256, jnp.bfloat16),  # a head fills a lane block
        ((1, 1024, 16, 32), 2, 256, 128, jnp.bfloat16),  # four heads a block, two groups, one tile a chunk
        ((1, 1024, 8, 64), 1, 128, 256, jnp.float32),
    ],
)
def test_mosaic_takes_the_scan_kernels(one_chip, shape, groups, state, chunk, dtype):
    """``ops/pallas/ssd.py`` at every kind of shape ``supports`` admits
    (a chunk of 512 and heads of 16 stop the compiler on a check of its
    own, and the rule keeps them on the XLA form): forward and backward,
    one Mosaic call each."""
    from distributeddeeplearning_tpu.ops.pallas import ssd

    b, t, h, _ = shape
    assert ssd.supports(chunk, h, groups, shape[3], state)
    assert not ssd.supports(512, h, groups, shape[3], state)
    assert not ssd.supports(chunk, h, groups, 16, state)
    like = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa: E731
    args = (
        like(shape, dtype), like((b, t, h), jnp.float32), like((b, t, h), jnp.float32),
        like((b, t, groups, state), dtype), like((b, t, groups, state), dtype),
    )

    def loss(*v):
        return jnp.sum(ssd.ssd_chunks(*v, chunk=chunk, interpret=False).astype(jnp.float32))

    compiled = jax.jit(jax.grad(loss, (0, 1, 2, 3, 4))).lower(*args).compile()
    assert compiled.as_text().count('custom_call_target="tpu_custom_call"') == 2


def _cell_step(one_chip, monkeypatch, traffic, configuration, block_diffusion=False, **share):
    """A cell's whole train step (its traffic file's rows, length,
    optimizer and remat; its configuration's model with the run's
    ``share`` of it; under ``block_diffusion`` the objective its
    configuration assumes, a row as ``[noised ‖ clean]`` with targets and
    weights; ``make_train_step`` as ``explicit.setup`` builds it)
    compiled for the described v5e. Returns ``(compiled, state, totals,
    named)``: the state as shapes, the trace's counters, and the MiB
    that each traced flash forward names for block remat to keep."""
    import json
    import os

    import numpy as np
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.training.optimizer import create_optimizer
    from distributeddeeplearning_tpu.training.train_step import (
        create_train_state,
        make_train_step,
    )

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(root, "benchmarks", "traffic", traffic)) as fh:
        job = json.load(fh)
    with open(os.path.join(root, "benchmarks", "configs", configuration)) as fh:
        config = json.load(fh)
    rows, seq = job["batch_per_chip"], job["seq_len"]
    mesh = Mesh(np.array(list(one_chip.device_set)), ("data",))
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    jax.clear_caches()
    obs.reset()
    opt = job["optimizer"]
    noising, width = {}, seq
    share = {k: config[v] for k, v in share.items()}
    if "layers" in config:  # a spec-built decoder's depth as run
        share["layers"] = config["layers"]
    if block_diffusion:
        assumed = config["assumed"]
        noising = dict(
            objective="block_diffusion", diffusion_block=assumed["block_length"],
            diffusion_t_min=assumed["t_min"], mask_token_id=assumed["mask_token_id"],
        )
        share["block_len"], width = assumed["block_length"], 2 * seq
    cfg = TrainConfig(
        model=config["program"]["model"], num_classes=config["vocab_size"],
        compute_dtype="bfloat16", batch_size_per_device=rows, remat=job.get("remat", False),
        optimizer=opt["name"], base_lr=opt["learning_rate"],
        adam_beta1=opt["adam_beta1"], adam_beta2=opt["adam_beta2"],
        adam_eps=opt["adam_eps"], decoupled_weight_decay=opt["decoupled_weight_decay"],
        weight_decay=0.0, label_smoothing=0.0, warmup_epochs=0,
        lr_schedule="constant", scale_lr_by_world_size=False, fake=True, epochs=1,
        **noising,
    )
    model = get_model(cfg.model, **cfg.model_kwargs(), **share)
    tx, _ = create_optimizer(cfg, 1000, world_size=1)
    state = jax.eval_shape(lambda: create_train_state(
        model, cfg, tx, input_shape=(1, width), input_dtype=jnp.int32
    ))
    held = NamedSharding(mesh, P())
    state = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=held), state
    )
    data = NamedSharding(mesh, P("data"))
    batch = (jax.ShapeDtypeStruct((rows, seq), jnp.int32, sharding=data),) * 2
    if block_diffusion:  # the noised and clean halves, the targets, their weights
        batch = (
            jax.ShapeDtypeStruct((rows, width), jnp.int32, sharding=data), batch[1],
            jax.ShapeDtypeStruct((rows, seq), jnp.float32, sharding=data),
        )
    step = make_train_step(model, tx, mesh, cfg)
    compiled = step._resolve(state, False).lower(state, batch).compile()
    totals = obs.get_bus().totals()
    named = [
        e["labels"]["mib"] for e in obs.get_bus().ring if e.get("name") == "attn.fwd.named"
    ]
    obs.reset()
    return compiled, state, totals, named


def _fits_the_chip(compiled, state, weights, temporaries_under):
    """The state (float32 weights and two Adam moments, donated) and the
    step's temporaries stay under the chip's 15.75 GiB beside the copy of
    the first parameters that ``benchmarks/runners/train.py`` holds
    through its checked steps. Prints the sizes (``pytest -s``)."""
    import numpy as np

    memory = compiled.memory_analysis()
    gib = 2.0 ** 30
    assert sum(int(np.prod(p.shape)) for p in jax.tree.leaves(state.params)) == weights
    resident = memory.argument_size_in_bytes / gib
    assert resident == pytest.approx(12 * weights / gib, rel=0.001)
    assert memory.alias_size_in_bytes == pytest.approx(memory.argument_size_in_bytes, rel=0.001)
    temporaries = memory.temp_size_in_bytes / gib
    copy = 4 * weights / gib  # runners/train.py's theta0
    print(
        f"state {resident:.2f} GiB + temporaries {temporaries:.2f} + the runner's copy "
        f"{copy:.2f} = {resident + temporaries + copy:.2f} of 15.75"
    )
    assert temporaries < temporaries_under, temporaries
    assert resident + temporaries + copy < 15.75


def test_the_gpt2_cell_s_step_holds_a_forward_and_a_backward_kernel_a_layer(
    one_chip, monkeypatch
):
    """``gpt2-train-t1024``'s whole train step (``lm_base``, 16 rows of
    1,024 tokens, AdamW, no remat) for the described v5e: by pass, 12
    flash kernels forward, 12 backward and none that runs again, which is
    what ``step_recompute_device_ms.train`` reads as 0.0 in that cell."""
    compiled, _, totals, named = _cell_step(
        one_chip, monkeypatch, "t1024.json", "gpt2-124m.json", max_seq_len="n_positions"
    )
    assert totals["attn.impl.pallas"]["count"] == 12
    scopes = programs.parse_hlo_scopes(compiled.as_text())
    assert programs.groups_in(scopes, TRAIN_STEP_GROUPS) == {g for g, _ in TRAIN_STEP_GROUPS}
    assert programs.kernel_calls_by_pass(scopes, TRAIN_STEP_GROUPS) == {
        "attn_core": {"forward": 12, "recompute": 0, "backward": 12, "other": 0}}
    assert not [p for p in scopes.values() if programs.pass_of(p) == "recompute"]


def test_the_state_space_cell_s_step_compiles_and_fits_the_chip(one_chip, monkeypatch):
    """``granite-4.0-h-micro-train-t4k``'s whole train step (ten layers
    at the published widths, 2 rows of 4,096 tokens, AdamW, block remat)
    for the described v5e, under the step's one-device ``shard_map``:
    ISSUE 33's ladder, by the compiler. The state (8.63 GiB) and the
    step's temporaries stay under the chip's 15.75 GiB with the runner's
    2.88 GiB copy: rows 2 are taken. Nine scans, each the Pallas kernels
    of ``ops/pallas/ssd.py`` (forward, block remat's replay of it, and
    the backward: 9 / 9 / 9 under ``ssm``; the weight draw's is XLA's),
    and nine convolutions under their scopes, the attention layer's flash
    kernels under ``attn_full`` with the spec's scale, every group of
    both tables."""
    from distributeddeeplearning_tpu.models.decoder import HYBRID_STEP_GROUPS, SSM_GROUPS

    compiled, state, totals, named = _cell_step(
        one_chip, monkeypatch, "t4k.json", "granite-4.0-h-micro.json"
    )
    assert named == [40.0]  # 2 rows x 4,096 x 2,048 bfloat16 and the logsumexp
    # the weight draw (`create_train_state` under `eval_shape`) and the
    # step each trace a layer once: the draw takes XLA's scan, the step
    # the kernels
    assert totals["decoder.layer.mamba2"]["count"] == 9 * totals["decoder.layer.full"]["count"]
    assert totals["ssm.impl.pallas"]["count"] == 9 and totals["ssm.impl.xla"]["count"] == 9
    assert totals["ssm.bwd.pallas"]["count"] == 9
    assert totals["attn.impl.pallas"]["count"] >= 1 and "attn.impl.xla" in totals  # init: einsum
    # 1.70 when PR 33 wrote this, 1.74 with the attention layer's 40 MiB kept,
    # 1.70 with the scan's kernels: a layer's entering states (64 MiB) stand
    # where the `while` kept its own residuals
    _fits_the_chip(compiled, state, 772_160_448, temporaries_under=2.5)
    scopes = programs.parse_hlo_scopes(compiled.as_text())
    assert programs.groups_in(scopes, HYBRID_STEP_GROUPS) == {g for g, _ in HYBRID_STEP_GROUPS}
    assert programs.groups_in(scopes, SSM_GROUPS) == {g for g, _ in SSM_GROUPS}
    # the attention layer: forward and one fused backward; block remat
    # starts from what the forward wrote (3 until PR 34)
    assert programs.kernel_calls_by_group(scopes, HYBRID_STEP_GROUPS)["attn_core"] == 2
    assert programs.kernel_calls_by_pass(scopes, HYBRID_STEP_GROUPS) == {
        "ssm": {"forward": 9, "recompute": 9, "backward": 9, "other": 0},
        "attn_core": {"forward": 1, "recompute": 0, "backward": 1, "other": 0}}
    assert programs.kernel_calls_by_group(scopes, SSM_GROUPS)["ssm_scan"] == 27


def test_the_long_cell_s_step_keeps_its_forwards_and_fits_the_chip(one_chip, monkeypatch):
    """``smallthinker-21b-a3b-train-t16k``'s whole train step (eight
    layers at the published widths, 8 experts held, 1 row of 16,384
    tokens, AdamW, block remat) for the described v5e. Block remat keeps
    each layer's attention output and logsumexp (112 + 14 MiB a layer):
    the temporaries rise from 3.71 GiB to 4.49, and state, temporaries
    and the runner's 2.40 GiB copy stay under the chip's 15.75 GiB; the
    step holds a forward and a backward kernel a layer, 16 under
    ``attn_core`` where the recomputed forwards made 24."""
    from distributeddeeplearning_tpu.models.decoder import ATTN_KIND_GROUPS

    compiled, state, totals, named = _cell_step(
        one_chip, monkeypatch, "t16k.json", "smallthinker-21b-a3b-instruct.json",
        experts_held="moe_num_primary_experts", max_seq_len="max_position_embeddings",
    )
    # a full and a window layer's jitted core, each traced once: 112 MiB
    # of output and 14 of logsumexp a layer
    assert named == [126.0, 126.0]
    _fits_the_chip(compiled, state, 643_852_800, temporaries_under=4.8)
    scopes = programs.parse_hlo_scopes(compiled.as_text())
    by_kind = programs.kernel_calls_by_group(scopes, ATTN_KIND_GROUPS)
    assert (by_kind["attn_full"], by_kind["attn_window"]) == (4, 12)
    assert programs.kernel_calls_by_group(scopes, TRAIN_STEP_GROUPS)["attn_core"] == 16
    # by pass: none of the 16 is block remat's (8 / 8 / 8 until PR 34)
    assert programs.kernel_calls_by_pass(scopes, TRAIN_STEP_GROUPS)["attn_core"] == {
        "forward": 8, "recompute": 0, "backward": 8, "other": 0}


def test_the_block_diffusion_cell_s_step_lowers_as_it_did_and_fits_the_chip(
    one_chip, monkeypatch
):
    """``sdar-30b-a3b-train-bd4k``'s whole train step (six layers at the
    published widths, 16 experts held, 2 rows of 4,096 clean tokens as
    8,192 positions, AdamW, block remat) for the described v5e. Block
    remat keeps nothing of a block-diffusion layer: the flash forwards
    name 1.27 GiB over the six layers of three passes, the temporaries
    stay at PR 32's 4.88 GiB (14.50 of the chip's 15.75 with the state
    and the runner's 2.41 GiB copy; 5.55 and 15.17 with the names kept,
    which fits), and the step holds a forward, the recomputed forward
    and a backward kernel a pass, 54 under ``attn_core``."""
    compiled, state, totals, named = _cell_step(
        one_chip, monkeypatch, "bd4k.json", "sdar-30b-a3b-chat.json", block_diffusion=True,
        experts_held="num_experts", max_seq_len="max_position_embeddings",
    )
    assert totals["attn.mask.block_diffusion"]["count"] >= 1
    assert len(named) == 3 and 6 * sum(named) / 1024 == pytest.approx(1.27, abs=0.01)
    _fits_the_chip(compiled, state, 645_623_296, temporaries_under=5.0)
    scopes = programs.parse_hlo_scopes(compiled.as_text())
    assert programs.kernel_calls_by_group(scopes, TRAIN_STEP_GROUPS)["attn_core"] == 54
    # six layers of three passes: forward, block remat's forward, backward
    assert programs.kernel_calls_by_pass(scopes, TRAIN_STEP_GROUPS)["attn_core"] == {
        "forward": 18, "recompute": 18, "backward": 18, "other": 0}
