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
from distributeddeeplearning_tpu.ops.pallas.flash import flash_attention
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


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize(
    "t,heads,d,block",
    [
        (70, 2, 8, 32),  # three ragged 32-blocks: q and k padding
        (300, 2, 64, None),  # GPT-2's head width, the rule's own block:
        # two heads a program, three 128-blocks on both axes
    ],
)
def test_flash_bwd_kernels_match_scan_reference(causal, t, heads, d, block):
    """The Mosaic backward kernels (dq; dk/dv on transposed tiles, the
    statistics as rows) against the kept pure-JAX scan backward they
    replaced, on ragged lengths that span several blocks on both axes,
    so the causal skip and the padding masks are exercised."""
    from distributeddeeplearning_tpu.ops.pallas.flash import (
        _flash,
        _flash_bwd_rule,
        _flash_bwd_scan,
    )

    q, k, v, do = (_packed(seed, 2, t, heads, d) for seed in (3, 4, 5, 6))
    scale = d**-0.5
    out, lse = _flash(q, k, v, heads, causal, scale, block, True)
    res = (q, k, v, out, lse)
    got = _flash_bwd_rule(heads, causal, scale, block, True, res, do)
    ref = _flash_bwd_scan(heads, causal, scale, block, True, res, do)
    for name, a, b in zip(("dq", "dk", "dv"), got, ref):
        np.testing.assert_allclose(
            np.asarray(a), np.asarray(b), atol=2e-4, err_msg=name
        )


@pytest.mark.parametrize("tile_elems", [None, 2 * 128 * 128])
def test_flash_matches_xla_at_gpt2_head_width(monkeypatch, tile_elems):
    """Forward and gradients against the XLA path, causal, d = 64 (two
    heads share a program's 128 lanes), T = 300: three 128-row blocks on
    both axes, the last one padded. With a small tile budget the walked
    operand lies in two resident blocks, so the accumulators cross grid
    steps and whole resident blocks above the diagonal are skipped."""
    from distributeddeeplearning_tpu.ops.pallas import flash

    if tile_elems:
        monkeypatch.setattr(flash, "_TILE_ELEMS", tile_elems)
        assert flash._plan(300, 128) == (128, 2, 2)
    q, k, v = _qkv(b=2, t=300, h=2, d=64, seed=1)
    w = _qkv(b=2, t=300, h=2, d=64, seed=2)[0]

    def loss(fn):
        return lambda q, k, v: jnp.sum(fn(q, k, v) * w)

    kernel = lambda q, k, v: flash_attention(q, k, v, causal=True)
    xla = lambda q, k, v: dot_product_attention(q, k, v, causal=True)
    np.testing.assert_allclose(
        np.asarray(kernel(q, k, v)), np.asarray(xla(q, k, v)), atol=1e-5
    )
    g_kernel = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    g_xla = jax.grad(loss(xla), argnums=(0, 1, 2))(q, k, v)
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
    kernel = lambda x: flash_qkv_attention(x, h, causal=causal, block=block)
    ref = lambda x: _packed_ref(x, h, causal)
    np.testing.assert_allclose(
        np.asarray(kernel(qkv)), np.asarray(ref(qkv)), atol=1e-5
    )
    g_kernel = jax.grad(lambda x: jnp.sum(kernel(x) * w))(qkv)
    g_ref = jax.grad(lambda x: jnp.sum(ref(x) * w))(qkv)
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
    from distributeddeeplearning_tpu.ops.pallas import flash

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
