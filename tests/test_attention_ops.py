"""Correctness tests for the three attention implementations, and the
rule that chooses among them (``ops/attention.resolve_impl``).

VERDICT round-1 flagged ``impl='pallas'`` and ``impl='ring'`` as phantom
dispatches; these tests pin the now-real implementations to the XLA
reference path (fwd + grads), on the same 8-device CPU mesh the rest of
the suite uses (the Pallas kernel runs in interpreter mode off-TPU, so
the kernel body itself is exercised).
"""

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.ops.attention import (
    dot_product_attention,
    kernel_interpreted,
    resolve_impl,
)
from distributeddeeplearning_tpu.ops.pallas import flash
from distributeddeeplearning_tpu.ops.pallas.flash import Mask, flash_attention, flash_attention_stats
from distributeddeeplearning_tpu.parallel.mesh import create_mesh
from distributeddeeplearning_tpu.parallel.ring_attention import ring_attention


def _qkv(b=2, t=64, h=4, d=16, seed=0):
    rng = np.random.RandomState(seed)
    return tuple(
        jnp.asarray(rng.randn(b, t, h, d).astype(np.float32)) for _ in range(3)
    )


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_xla_forward(causal):
    q, k, v = _qkv()
    ref = dot_product_attention(q, k, v, causal=causal, impl="xla")
    out = dot_product_attention(q, k, v, causal=causal, impl="pallas")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_matches_xla_grads(causal):
    q, k, v = _qkv(t=32, d=8)

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v, causal=causal) ** 2)

    g_flash = jax.grad(loss(flash_attention), argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(
        loss(lambda q, k, v, causal: dot_product_attention(q, k, v, causal=causal)),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_flash, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_flash_ragged_length():
    """Sequence not divisible by the block size: padding must be masked."""
    q, k, v = _qkv(t=100, d=8)
    ref = dot_product_attention(q, k, v, impl="xla")
    out = flash_attention(q, k, v)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


def test_flash_causal_requires_equal_lengths():
    q, k, v = _qkv(t=32, d=8)
    with pytest.raises(ValueError):
        flash_attention(q, k[:, :16], v[:, :16], causal=True)


def _ring_fn(mesh, causal):
    def ring(q, k, v):
        return ring_attention(q, k, v, axis_name="seq", causal=causal)

    spec = P(None, "seq")
    return jax.jit(
        jax.shard_map(
            ring, mesh=mesh, in_specs=(spec, spec, spec), out_specs=spec
        )
    )


@pytest.mark.parametrize("causal", [False, True])
def test_ring_matches_xla(devices, causal):
    mesh = create_mesh(axes=("seq",))
    q, k, v = _qkv()
    out = _ring_fn(mesh, causal)(q, k, v)
    ref = dot_product_attention(q, k, v, causal=causal, impl="xla")
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_ring_grads_match_xla(devices, causal):
    mesh = create_mesh(axes=("seq",))
    q, k, v = _qkv(d=8)
    f = _ring_fn(mesh, causal)
    g_ring = jax.grad(lambda q, k, v: jnp.sum(f(q, k, v) ** 2), argnums=(0, 1, 2))(
        q, k, v
    )
    g_ref = jax.grad(
        lambda q, k, v: jnp.sum(
            dot_product_attention(q, k, v, causal=causal) ** 2
        ),
        argnums=(0, 1, 2),
    )(q, k, v)
    for a, b in zip(g_ring, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


def test_ring_dispatch_requires_shard_map():
    # impl='ring' defaults to the mesh convention's "seq" axis, which is
    # only bound inside shard_map — outside, jax rejects the axis name.
    q, k, v = _qkv(t=8, d=8)
    with pytest.raises(NameError, match="seq"):
        dot_product_attention(q, k, v, impl="ring")


def test_unknown_impl_raises():
    q, k, v = _qkv(t=8, d=8)
    with pytest.raises(ValueError):
        dot_product_attention(q, k, v, impl="nope")


# ---- grouped query heads through the one entry -----------------------------


@pytest.mark.parametrize("impl", ["xla", "pallas"])
@pytest.mark.parametrize("causal", [False, True])
def test_grouped_heads_equal_repeated_key_heads(impl, causal):
    """``H // KV`` query heads to a key head give what the same call gives
    with every key head written out once a query head, forward and
    gradient (the gradient of a shared key head is its group's sum)."""
    q, k, v = _qkv(t=32, h=4, d=8)
    k, v = k[:, :, :2], v[:, :, :2]
    rep = lambda x: jnp.repeat(x, 2, axis=2)  # noqa: E731

    def loss(q, k, v):
        return jnp.sum(dot_product_attention(q, k, v, causal=causal, impl=impl) ** 2)

    np.testing.assert_allclose(
        np.asarray(dot_product_attention(q, k, v, causal=causal, impl=impl)),
        np.asarray(dot_product_attention(q, rep(k), rep(v), causal=causal, impl=impl)),
        atol=1e-5,
    )
    grouped = jax.grad(loss, argnums=(0, 1, 2))(q, k, v)
    repeated = jax.grad(lambda q, k, v: loss(q, rep(k), rep(v)), argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(grouped, repeated):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=1e-4)


# ---- the rule: which lowering a call takes ---------------------------------
# (the model-level cases are in tests/test_transformer_lm.py; these ask
# the rule itself, as the spec-built attention of models/decoder.py does)


def _resolved(monkeypatch, *, want, backend="tpu", local=True, init=False, t=4096,
              heads=32, kv=4, d=128, mask="causal", packed=False, asked="auto"):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    monkeypatch.setattr(jax, "device_count", lambda: 1 if local else 8)
    x = jax.ShapeDtypeStruct((2, t, heads * d), jnp.bfloat16)
    obs.reset()
    impl = resolve_impl(
        asked, x, heads=heads, head_dim=d, initializing=init, packed_qkv=packed,
        kv_heads=kv, mask=mask,
    )
    events = {e["name"]: e["labels"] for e in obs.get_bus().ring if e["kind"] == "counter"}
    obs.reset()
    assert events == {
        f"attn.impl.{want}": {
            "asked": asked, "shape": [2, t, heads * d], "heads": heads,
            "kv_heads": kv, "mask": mask,
        },
        f"attn.mask.{mask}": {"impl": want},
    }
    return impl


@pytest.mark.parametrize(
    "case,path",
    [
        (dict(), "pallas"),  # SDAR's heads, causal at L = 4,096
        (dict(t=8192, mask="block_diffusion"), "pallas"),  # the cell: 2·L judged by L
        (dict(t=1278, mask="block_diffusion"), "xla"),  # L = 639: a pass walks a half
        (dict(t=1280, mask="block_diffusion"), "pallas"),  # L = 640
        (dict(t=639), "xla"),  # the same length causal: judged whole
        (dict(t=512), "xla"),  # grouped heads, separate projections: never "fused"
        (dict(t=512, kv=32, packed=True), "fused"),  # only a fused QKV feeds the packed kernel
        (dict(t=4096, packed=True), "pallas"),  # past the packed kernel's lengths
        (dict(heads=8, kv=8, d=96), "xla"),  # head blocks do not tile the lanes
        (dict(local=False), "xla"),  # multi-device GSPMD: operands not local
        (dict(init=True), "xla"),  # the weight draw lowers no kernel
        (dict(backend="cpu"), "xla"),  # off the TPU
        (dict(backend="cpu", asked="pallas"), "pallas"),  # explicit: taken as given
        (dict(asked="xla"), "xla"),
        (dict(t=64, asked="fused"), "fused"),
    ],
)
def test_resolve_impl_table(monkeypatch, case, path):
    assert _resolved(monkeypatch, want=path, **case) == path


def test_resolve_impl_judges_and_counts_a_window_as_a_named_mask(monkeypatch):
    """``mask="window"`` is judged as the causal mask is (the whole
    length) and counted as any named mask: ``attn.mask.window`` with the
    path taken, the shape and the name on ``attn.impl.<path>``."""
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    x = jax.ShapeDtypeStruct((1, 16384, 28 * 128), jnp.bfloat16)
    obs.reset()
    assert resolve_impl(
        "auto", x, heads=28, head_dim=128, initializing=False, kv_heads=4,
        mask="window",
    ) == "pallas"
    events = {e["name"]: e["labels"] for e in obs.get_bus().ring if e["kind"] == "counter"}
    obs.reset()
    assert events["attn.mask.window"] == {"impl": "pallas"}
    assert events["attn.impl.pallas"]["mask"] == "window"
    assert events["attn.impl.pallas"]["shape"] == [1, 16384, 3584]


def test_a_caller_that_names_no_mask_gets_the_labels_it_had(monkeypatch):
    """``models/vit.Attention`` states neither key heads nor a mask: its
    counter carries ``asked``, ``shape``, ``heads`` and there is no
    ``attn.mask.*``."""
    x = jax.ShapeDtypeStruct((2, 16, 32), jnp.float32)
    obs.reset()
    assert resolve_impl(
        "auto", x, heads=4, head_dim=8, initializing=False, packed_qkv=True
    ) == "xla"
    (event,) = [e for e in obs.get_bus().ring if e["kind"] == "counter"]
    obs.reset()
    assert event["name"] == "attn.impl.xla"
    assert event["labels"] == {"asked": "auto", "shape": [2, 16, 32], "heads": 4}


def test_the_fused_backward_is_counted_once_a_traced_backward():
    """``attn.bwd.fused`` at trace time, as ``attn.impl.<path>`` is: one
    a traced backward of the flash kernels, with the operands' shape as
    the kernels take them, the query heads a key head and the mask; a
    forward alone counts none."""
    q = jnp.zeros((1, 64, 4, 128))
    kv = jnp.zeros((1, 64, 2, 128))

    def core(q, k, v):
        out, _ = flash_attention_stats(
            q, k, v, mask=Mask(True, 4), block=32, interpret=True
        )
        return jnp.sum(out)

    def counted():
        return [
            e for e in obs.get_bus().ring
            if e["kind"] == "counter" and e["name"].startswith("attn.bwd.")
        ]

    jax.clear_caches()  # the jitted core traces once a signature
    obs.reset()
    jax.jit(core).lower(q, kv, kv)
    assert counted() == []
    jax.jit(jax.grad(core, (0, 1, 2))).lower(q, kv, kv)
    (event,) = counted()
    obs.reset()
    assert event["name"] == "attn.bwd.fused" and event["value"] == 1
    assert event["labels"] == {
        "shape": [1, 64, 512], "rep": 2,
        "mask": {"causal": True, "gran": 4, "strict": False, "own": False,
                 "window": 0},
    }


# -- the window rule ----------------------------------------------------------

def _window_operands(t, h, kv, d, seed=0):
    key = jax.random.PRNGKey(seed)
    return (
        jax.random.normal(key, (1, t, h, d)),
        jax.random.normal(jax.random.fold_in(key, 1), (1, t, kv, d)),
        jax.random.normal(jax.random.fold_in(key, 2), (1, t, kv, d)),
    )


def _rel_gap(a, b):
    return float(jnp.max(jnp.abs(a - b)) / (jnp.max(jnp.abs(b)) + 1e-30))


@pytest.mark.parametrize(
    "t,h,kv,d,window,block",
    [
        (512, 2, 1, 128, 20, 32),    # a window inside one block
        (512, 2, 1, 128, 32, 32),    # = a block
        (512, 2, 1, 128, 33, 32),    # a block and one key
        (512, 2, 1, 128, 50, 32),    # between blocks
        (512, 2, 1, 128, 128, 32),   # = a resident block of four
        (512, 2, 1, 128, 2, 32),     # a query and the key before it
        (512, 2, 1, 128, 5000, 32),  # covers the sequence: the whole triangle
        (512, 7, 1, 128, 100, 64),   # seven query heads a key head, in place
        (500, 2, 2, 128, 100, 64),   # a sequence that pads
        (350, 4, 2, 64, 65, 64),     # narrow heads, two a program
    ],
    ids=["lt-block", "eq-block", "block+1", "between", "eq-resident", "two",
         "ge-seq", "rep7", "pads", "narrow"],
)
def test_the_window_kernels_match_the_einsum_over_the_band(monkeypatch, t, h, kv, d, window,
                                                           block):
    """``Mask(causal, window=w)`` in the flash kernels (interpret mode,
    four blocks a resident block: sixteen blocks in four resident ones at
    the first shapes, a static branch a live span of the four) against
    the einsum over the same band: the output and all three gradients."""
    monkeypatch.setattr(flash, "_TILE_ELEMS", 4 * block * block)
    assert flash._plan(t, block).major > 1
    q, k, v = _window_operands(t, h, kv, d)

    def kernel(q, k, v):
        return flash_attention_stats(
            q, k, v, mask=Mask(True, window=window), block=block, interpret=True
        )[0]

    def einsum(q, k, v):
        return dot_product_attention(q, k, v, causal=True, window=window, impl="xla")

    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    band = (cols <= rows) & (cols > rows - window)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, jnp.repeat(k, h // kv, 2)) * d**-0.5
    want = jnp.einsum(
        "bhqk,bkhd->bqhd", jax.nn.softmax(jnp.where(band, s, -jnp.inf), -1),
        jnp.repeat(v, h // kv, 2),
    )
    def run(f):  # one program a side: the gradients, and the output as their aux
        def g(*a):
            out = f(*a)
            return jnp.sum(jnp.sin(out)), out
        return jax.jit(jax.grad(g, (0, 1, 2), has_aux=True))(q, k, v)

    (got, out), (ref, ref_out) = run(kernel), run(einsum)
    assert _rel_gap(ref_out, want) < 1e-5  # the einsum path masks the band
    assert _rel_gap(out, want) < 1e-5
    for a, b in zip(got, ref):
        assert _rel_gap(a, b) < 1e-5


def test_the_pallas_entry_takes_the_window_and_the_other_paths_refuse_it():
    q, k, v = _window_operands(1024, 2, 2, 128)
    got = dot_product_attention(q, k, v, causal=True, window=300, impl="pallas")
    want = dot_product_attention(q, k, v, causal=True, window=300, impl="xla")
    assert _rel_gap(got, want) < 1e-5
    assert _rel_gap(want, dot_product_attention(q, k, v, causal=True)) > 1e-2
    with pytest.raises(ValueError, match="window"):
        dot_product_attention(q, k, v, causal=False, window=300)
    with pytest.raises(ValueError, match="window"):
        dot_product_attention(q, k, v, causal=True, window=300, impl="ring")
    with pytest.raises(ValueError, match="window"):
        flash_attention_stats(q, k, v, mask=Mask(True, 4, window=300), interpret=True)


@pytest.mark.parametrize("owner_first", [True, False], ids=["forward", "backward"])
@pytest.mark.parametrize(
    "t,b,window", [(16384, 512, 4096), (2048, 128, 300), (1024, 64, 64), (1024, 64, 1)]
)
def test_a_block_behind_the_window_is_neither_computed_nor_fetched(t, b, window, owner_first):
    """The index maps name, for every step of a head's grid, a resident
    block that holds part of the band (so one wholly behind it, or above
    the diagonal, is never fetched), ``_walk_band`` computes in exactly
    the steps whose own resident block does, and ``_band_steps`` counts
    those: at the cell's shapes (16,384 rows, blocks of 512, two a
    resident block) 112 of a head's 512 steps forward."""
    plan = flash._plan(t, b)
    _, walk, _, _, _ = flash._specs(
        plan, plan, 128, 1, 1, True, owner_first=owner_first, window=window
    )
    live = lambda i, j: j <= i and (i - j - 1) * b + 1 < window  # q block i meets k block j

    def holds_band(i, jm):  # resident block jm against owner block i
        subs = range(jm * plan.sub, (jm + 1) * plan.sub)
        return any(live(i, j) if owner_first else live(j, i) for j in subs)

    visited = 0
    for i in range(plan.blocks):
        for jm in range(plan.major):
            named = int(walk().index_map(0, 0, i, jm)[1])
            assert holds_band(i, named), (i, jm, named)
            if holds_band(i, jm):
                assert named == jm
                visited += 1
    assert flash._band_steps(plan, plan, window, owner_first) == (
        visited, plan.blocks * plan.major - visited)
    if (t, owner_first) == (16384, True):
        assert (visited, plan.blocks * plan.major) == (
            sum(i // 2 - max(i - 8, 0) // 2 + 1 for i in range(32)), 512)


def test_the_window_walk_is_counted_forward_and_backward():
    """``attn.window.blocks`` at trace time, each time a pass is traced
    (a differentiated call traces the forward as the primal and as the
    rule's): the steps visited and skipped of a head's grid, forward and
    backward; a window that covers the sequence ``dot_product_
    attention`` hands on as the causal rule, whose kernels count
    nothing."""
    q, k, v = (jnp.zeros((1, 1024, 1, 128)),) * 3

    def core(window):
        return lambda q, k, v: jnp.sum(flash.flash_attention_stats(
            q, k, v, mask=Mask(True, window=window), block=64, interpret=True
        )[0])

    def counted():
        return [
            e["labels"] for e in obs.get_bus().ring
            if e["kind"] == "counter" and e["name"] == "attn.window.blocks"
        ]

    jax.clear_caches()
    obs.reset()
    jax.jit(jax.grad(core(100), (0, 1, 2))).lower(q, k, v)
    *forwards, backward = counted()
    forward = forwards[0]
    assert all(f == forward for f in forwards)
    plan = flash._plan(1024, 64)
    assert forward == {
        "visited": flash._band_steps(plan, plan, 100, True)[0],
        "skipped": flash._band_steps(plan, plan, 100, True)[1],
        "window": 100, "block": 64, "pass": "forward"}
    assert backward["pass"] == "backward" and backward["skipped"] > 0
    assert forward["visited"] + forward["skipped"] == 16 * 2
    obs.reset()
    covered = lambda q, k, v: jnp.sum(dot_product_attention(
        q, k, v, causal=True, window=1024, impl="pallas"))
    jax.jit(jax.grad(covered, (0, 1, 2))).lower(q, k, v)
    assert counted() == []
    obs.reset()


@pytest.mark.parametrize(
    "impl,backend,interpreted",
    [
        ("pallas", "cpu", True), ("fused", "cpu", True), ("pallas", "tpu", False),
        ("auto", "cpu", False), ("xla", "cpu", False), ("ring", "cpu", False),
        (None, "cpu", False),  # a model with no attention
    ],
)
def test_kernel_interpreted(monkeypatch, impl, backend, interpreted):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert kernel_interpreted(impl) is interpreted


def _packed(seed, n, t, heads, d):
    """The kernels' own operand layout: ``[n, t, heads·d]``."""
    rng = np.random.RandomState(seed)
    return jnp.asarray(rng.randn(n, t, heads * d).astype(np.float32))


def _bwd_case(t, heads, d, block, causal=False, kv=None, tile_elems=None, dlse=False):
    return dict(t=t, heads=heads, kv=kv or heads, d=d, block=block, mask=causal,
                tile_elems=tile_elems, dlse=dlse)


@pytest.mark.parametrize(
    "case",
    [
        # three ragged 32-blocks: q and k padding
        pytest.param(_bwd_case(70, 2, 8, 32), id="ragged-full"),
        pytest.param(_bwd_case(70, 2, 8, 32, True), id="ragged-causal"),
        # GPT-2's head width, the rule's own block: two heads a program,
        # three 128-blocks on both axes
        pytest.param(_bwd_case(300, 2, 64, None), id="d64-full"),
        pytest.param(_bwd_case(300, 2, 64, None, True), id="d64-causal"),
        # grouped query heads read a key head in place: dq has a slot a
        # query head, dk and dv sum over the group
        pytest.param(_bwd_case(200, 4, 128, 64, True, kv=2), id="rep2-causal"),
        pytest.param(_bwd_case(200, 8, 128, 64, kv=1), id="rep8-full"),
        # the block-diffusion passes: the diagonal tile's rule in units of
        # 4, the rows' logsumexp used by the caller
        pytest.param(
            _bwd_case(200, 4, 128, 64, Mask(True, 4), kv=2, dlse=True), id="blocks<="
        ),
        pytest.param(
            _bwd_case(200, 4, 128, 64, Mask(True, 4, True), kv=2, dlse=True), id="blocks<"
        ),
        pytest.param(
            _bwd_case(200, 4, 128, 64, Mask(False, 4, own=True), kv=2, dlse=True),
            id="own-block",
        ),
        # ten 32-blocks in five resident ones of two: dq's sums cross the
        # programs of ten k blocks, each over several resident blocks,
        # and are written in the last one's pass
        pytest.param(
            _bwd_case(300, 2, 64, 32, True, tile_elems=2 * 32 * 32), id="streamed-d64"
        ),
        pytest.param(
            _bwd_case(300, 8, 128, 32, Mask(True, 4), kv=1, tile_elems=2 * 32 * 32,
                      dlse=True),
            id="streamed-rep8-blocks<=",
        ),
        pytest.param(
            _bwd_case(300, 4, 128, 32, kv=2, tile_elems=2 * 32 * 32), id="streamed-full"
        ),
    ],
)
def test_flash_bwd_kernels_match_scan_reference(monkeypatch, case):
    """The Mosaic backward kernel (dq, dk, dv from one transposed tile,
    the statistics as rows) against the kept pure-JAX scan backward, and
    against the derivative of the dense masked softmax, on ragged
    lengths that span several blocks on both axes, so the causal skip
    and the padding masks are exercised; every row of dq, dk and dv is
    compared, so every block's."""
    t, heads, kv, d, block = (case[x] for x in ("t", "heads", "kv", "d", "block"))
    mask = flash._as_mask(case["mask"])
    rep = heads // kv
    if case["tile_elems"]:
        monkeypatch.setattr(flash, "_TILE_ELEMS", case["tile_elems"])
        assert flash._plan(t, block).major > 1
    q, do = (_packed(seed, 2, t, heads, d) for seed in (3, 6))
    k, v = (_packed(seed, 2, t, kv, d) for seed in (4, 5))
    dlse = _packed(7, 2, t, heads, 1) if case["dlse"] else None
    if mask.strict:  # the first rows see no key: their cotangents are nought
        do, dlse = do.at[:, : mask.gran].set(0.0), dlse.at[:, : mask.gran].set(0.0)
    scale = d**-0.5
    out, lse = flash._flash(q, k, v, heads, mask, scale, block, True, rep=rep)
    got = flash._flash_bwd_rule(
        heads, mask, scale, block, True, (q, k, v, out, lse), do, rep=rep, dlse=dlse
    )

    def wide(x):  # key heads written out once a query head
        return jnp.repeat(x.reshape(2, t, kv, d), rep, axis=2).reshape(2, t, -1)

    def narrow(dx):  # and their gradients summed back
        return dx.reshape(2, t, kv, rep, d).sum(3).reshape(2, t, -1)

    dq, dk, dv = jax.jit(lambda *res: flash._flash_bwd_scan(
        heads, mask, scale, block, True, res, do, dlse=dlse
    ))(q, wide(k), wide(v), out, lse)
    for name, a, b in zip(("dq", "dk", "dv"), got, (dq, narrow(dk), narrow(dv))):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=name
        )

    rows, cols = jnp.arange(t)[:, None], jnp.arange(t)[None, :]
    sees = flash._sees(rows, cols, mask) if mask.causal or mask.own else cols >= 0

    def dense(q, k, v):
        split = lambda x: x.reshape(2, t, heads, d)
        s = jnp.einsum("bqhd,bkhd->bhqk", split(q), split(wide(k))) * scale
        s = jnp.where(sees, s, -1e30)
        stat = jax.nn.logsumexp(s, -1)
        p = jnp.where(sees, jnp.exp(s - stat[..., None]), 0.0)
        out = jnp.einsum("bhqk,bkhd->bqhd", p, split(wide(v)))
        return out.reshape(2, t, -1), stat.transpose(0, 2, 1)

    cts = (do, dlse if case["dlse"] else jnp.zeros((2, t, heads)))
    want = jax.jit(lambda q, k, v: jax.vjp(dense, q, k, v)[1](cts))(q, k, v)
    for name, a, b in zip(("dq", "dk", "dv"), got, want):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, err_msg=name
        )


def test_flash_bwd_refuses_more_of_dq_than_vmem_holds(monkeypatch):
    """dq's sums for the whole query side stay in VMEM; a call whose
    padded length x heads a program x width is over the budget is told
    so, not handed to the compiler."""
    monkeypatch.setattr(flash, "_DQ_VMEM", 2 * 256 * 128 * 4 - 1)
    q, k, v = _qkv(b=1, t=256, h=2, d=64)
    flash_attention(q, k, v, causal=True)  # the forward holds no such sums
    with pytest.raises(ValueError, match="dq's f32 sums"):
        jax.grad(lambda q: jnp.sum(flash_attention(q, k, v, causal=True)))(q)


@pytest.mark.parametrize("tile_elems", [None, 2 * 128 * 128])
def test_flash_matches_xla_at_gpt2_head_width(monkeypatch, tile_elems):
    """Forward and gradients against the XLA path, causal, d = 64 (two
    heads share a program's 128 lanes), T = 300: three 128-row blocks on
    both axes, the last one padded. With a small tile budget the walked
    operand lies in two resident blocks, so the accumulators cross grid
    steps and whole resident blocks above the diagonal are skipped."""
    if tile_elems:
        monkeypatch.setattr(flash, "_TILE_ELEMS", tile_elems)
        assert flash._plan(300, 128) == (128, 2, 2)
    q, k, v = _qkv(b=2, t=300, h=2, d=64, seed=1)
    w = _qkv(b=2, t=300, h=2, d=64, seed=2)[0]

    def run(fn):  # one program a side: the gradients, and the output as their aux
        def loss(q, k, v):
            out = fn(q, k, v)
            return jnp.sum(out * w), out
        return jax.jit(jax.grad(loss, argnums=(0, 1, 2), has_aux=True))(q, k, v)

    (g_kernel, out), (g_xla, want) = (
        run(lambda q, k, v: flash_attention(q, k, v, causal=True)),
        run(lambda q, k, v: dot_product_attention(q, k, v, causal=True)),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    for name, a, b in zip(("dq", "dk", "dv"), g_kernel, g_xla):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=1e-4, err_msg=name
        )


@pytest.mark.parametrize(
    "b,t,h,d,causal",
    [
        (1, 200, 4, 32, False),  # four heads a program; keys' padding masked
        (1, 100, 3, 24, True),  # heads do not tile the lanes: transposed
        (1, 96, 2, 128, True),  # one head a program
    ],
)
def test_flash_head_layouts_match_xla(b, t, h, d, causal):
    q, k, v = _qkv(b=b, t=t, h=h, d=d, seed=7)
    ref = dot_product_attention(q, k, v, causal=causal, impl="xla")
    out = flash_attention(q, k, v, causal=causal, block=32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=1e-5)


@pytest.mark.parametrize(
    "b,t,h,d,causal,block",
    [
        (2, 300, 4, 64, True, None),  # GPT-2's head width, three 128-blocks
        (1, 100, 4, 32, False, 32),  # four heads a program, padded keys
        (1, 96, 2, 128, True, 32),  # one head a program
    ],
)
def test_flash_qkv_reads_the_packed_projection_in_place(b, t, h, d, causal, block):
    """``flash_qkv_attention`` over ``[B, T, 3·H·d]`` (q, k and v as
    thirds of one array, by block index alone) against the XLA path on
    the slices, forward and gradient."""
    from distributeddeeplearning_tpu.ops.pallas.flash import flash_qkv_attention

    rng = np.random.RandomState(11)
    qkv = jnp.asarray(rng.randn(b, t, 3 * h * d).astype(np.float32))
    w = jnp.asarray(rng.randn(b, t, h * d).astype(np.float32))
    def run(fn):  # one program a side: the gradient, and the output as its aux
        def loss(x):
            out = fn(x)
            return jnp.sum(out * w), out
        return jax.jit(jax.grad(loss, has_aux=True))(qkv)

    (g_kernel, out), (g_ref, want) = (
        run(lambda x: flash_qkv_attention(x, h, causal=causal, block=block)),
        run(lambda x: _packed_ref(x, h, causal)),
    )
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=1e-5)
    np.testing.assert_allclose(np.asarray(g_kernel), np.asarray(g_ref), atol=1e-4)
    with pytest.raises(ValueError):
        flash_qkv_attention(jnp.zeros((1, 64, 3 * 2 * 96)), 2)  # d = 96


@pytest.mark.parametrize(
    "t,block", [(1024, 512), (768, 256), (640, 128), (2048, 512), (8192, 512), (100, 128)]
)
def test_flash_block_rule(t, block):
    from distributeddeeplearning_tpu.ops.pallas.flash import _pick_block

    assert _pick_block(t) == block


@pytest.mark.parametrize(
    "t,h,d,ok",
    [
        (1024, 12, 64, True), (640, 12, 64, True), (639, 12, 64, False),
        (513, 12, 64, False), (512, 12, 64, False), (1024, 8, 96, False),
        (1024, 4, 128, True), (1024, 3, 64, False), (8192, 8, 64, True),
    ],
)
def test_flash_supports_gating(t, h, d, ok):
    assert flash.supports(t, h, d) is ok


# ---- packed small-T kernel (ops/pallas/flash_packed.py) ----

from distributeddeeplearning_tpu.ops.pallas.flash_packed import (  # noqa: E402
    fused_qkv_attention,
    supports,
)


def _packed_ref(qkv, heads, causal):
    """Independent einsum reference for the packed layout."""
    b, t, thd = qkv.shape
    d = thd // 3 // heads
    q, k, v = [x.reshape(b, t, heads, d) for x in jnp.split(qkv, 3, -1)]
    out = dot_product_attention(q, k, v, causal=causal, impl="xla")
    return out.reshape(b, t, heads * d)


@pytest.mark.parametrize(
    "b,t,h,d,causal",
    [
        (4, 29, 2, 64, False),  # ragged T, two heads per 128-lane block
        (2, 29, 2, 64, True),
        (2, 16, 1, 128, True),  # one head per block
        (3, 48, 4, 32, False),  # four heads per block
    ],
)
def test_packed_matches_xla(b, t, h, d, causal):
    rng = np.random.RandomState(0)
    qkv = jnp.asarray(rng.randn(b, t, 3 * h * d).astype(np.float32))
    out = fused_qkv_attention(qkv, h, causal=causal, interpret=True)
    ref = _packed_ref(qkv, h, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_packed_grads_match_xla(causal):
    rng = np.random.RandomState(1)
    qkv = jnp.asarray(rng.randn(2, 29, 3 * 2 * 64).astype(np.float32))

    def loss(fn):
        return lambda x: jnp.sum(jnp.sin(fn(x)))

    g = jax.grad(
        loss(lambda x: fused_qkv_attention(x, 2, causal=causal, interpret=True))
    )(qkv)
    g_ref = jax.grad(loss(lambda x: _packed_ref(x, 2, causal)))(qkv)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref), atol=1e-4)


def test_packed_ragged_tail_is_finite():
    """The unpadded ragged tail must be sanitised in-kernel: gradients
    through every contraction over T stay finite (a poisoned tail row
    would NaN dq/dk/dv)."""
    rng = np.random.RandomState(2)
    qkv = jnp.asarray(rng.randn(2, 17, 3 * 2 * 64).astype(np.float32))
    g = jax.grad(
        lambda x: jnp.sum(fused_qkv_attention(x, 2, interpret=True))
    )(qkv)
    assert bool(jnp.all(jnp.isfinite(g)))


def test_packed_supports_gating():
    assert supports(197, 12, 64)
    assert supports(512, 16, 128)
    # long T is the streaming kernel's regime — and at 1024 the ~6 live
    # [T, T] f32 intermediates alone exceed the scoped-VMEM budget
    assert not supports(1024, 16, 128)
    assert not supports(2048, 12, 64)
    assert not supports(197, 3, 64)  # 3 heads don't fill 128-lane blocks
    with pytest.raises(ValueError):
        fused_qkv_attention(jnp.zeros((1, 8, 3 * 3 * 64)), 3, interpret=True)
