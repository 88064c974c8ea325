"""Quantized decode tier oracles (kv_dtype / weight_dtype = "int8" /
"fp8" — plus the fused-kernel env surface they share).

The quantized tier's contract, pinned here (CPU tier):

* **Cache shape contract** — ``kv_dtype="int8"`` turns the decode
  caches (dense rows AND the paged block pool) into int8 payload + f32
  per-head scale leaves; everything the engine templates from
  ``decode_cache_shapes`` follows.
* **Bitwise determinism** — two identical request loads produce
  bitwise-identical token streams AND bitwise-identical quantized pool
  bytes (quantize is round-half-to-even; no data-dependent branches).
* **Paged twin** — the quantized PAGED engine emits token-for-token
  what the quantized DENSE engine emits under greedy and seeded
  sampling: quantization and the block-pool layout compose without
  interacting.
* **Closed program set** — the int8 engine compiles exactly
  ``len(buckets) + 1`` programs and an admission/eviction churn
  triggers ZERO backend compiles (the existing churn oracle, extended
  to the quantized configuration).
* **Byte accounting** — ``byte_accounting()`` / the warmup gauges
  report int8 + scale bytes (never payload-only), and the quantized
  engine's per-token KV bytes land strictly below the native engine's.
* **force_token** — the teacher-forcing hook the serve_bench quality
  oracle uses: forcing the token the engine would have fed anyway is a
  no-op (self-replay == free run, bitwise), and forcing an empty slot
  is an error.
"""

import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.inference import (
    decode_cache_shapes,
    decode_variant,
)
from distributeddeeplearning_tpu.models.transformer_lm import TransformerLM
from distributeddeeplearning_tpu.serving import (
    ReqSpec,
    Request,
    ServeConfig,
    Server,
    SlotEngine,
)

VOCAB, MAX_LEN = 64, 32
BUCKETS = (4, 8, 16)


@pytest.fixture(scope="module")
def model():
    return TransformerLM(
        variant="tiny", vocab_size=VOCAB, max_seq_len=MAX_LEN,
        dtype=jnp.float32,
    )


@pytest.fixture(scope="module")
def params(model):
    import flax.linen as nn
    import jax

    variables = model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, MAX_LEN), jnp.int32),
        train=False,
    )
    return nn.unbox(variables["params"])


@pytest.fixture(scope="module")
def _q_engine(model, params):
    eng = SlotEngine(
        model, params, num_slots=4, max_len=MAX_LEN, buckets=BUCKETS,
        kv_dtype="int8", weight_dtype="int8",
    )
    eng.warmup()
    return eng


@pytest.fixture
def q_engine(_q_engine):
    for s in _q_engine.active_slots:
        _q_engine.release(s)
    yield _q_engine
    for s in _q_engine.active_slots:
        _q_engine.release(s)


def _prompt(rng, n):
    return rng.randint(0, VOCAB, size=(n,)).astype(np.int32)


def _flat_pool(engine):
    from flax import traverse_util
    from flax.core import unfreeze

    return {
        "/".join(p): np.asarray(leaf)
        for p, leaf in traverse_util.flatten_dict(
            unfreeze(engine._pool)
        ).items()
    }


def test_cache_shapes_carry_int8_and_scales(model):
    dense = decode_cache_shapes(
        decode_variant(model, kv_dtype="int8"), 2, MAX_LEN
    )
    from flax import traverse_util

    flat = {
        p[-1]: leaf
        for p, leaf in traverse_util.flatten_dict(dict(dense)).items()
    }
    assert flat["cached_k"].dtype == jnp.int8
    assert flat["cached_v"].dtype == jnp.int8
    assert flat["cached_k_scale"].dtype == jnp.float32
    # per head per position: K shape minus the head_dim axis, kept as 1
    assert flat["cached_k_scale"].shape == flat["cached_k"].shape[:-1] + (1,)
    paged = decode_cache_shapes(
        decode_variant(model, paged_blocks=9, paged_block_size=4,
                       kv_dtype="int8"),
        2, MAX_LEN,
    )
    pflat = {
        p[-1]: leaf
        for p, leaf in traverse_util.flatten_dict(dict(paged)).items()
    }
    assert pflat["paged_k"].dtype == jnp.int8
    assert pflat["paged_k_scale"].dtype == jnp.float32
    assert pflat["paged_k_scale"].shape == pflat["paged_k"].shape[:-1] + (1,)
    # invalid dtype rejected at the module boundary
    with pytest.raises(ValueError, match="kv_dtype"):
        decode_cache_shapes(
            decode_variant(model, kv_dtype="int4"), 1, MAX_LEN
        )


def _run_load(engine, seeds):
    rng = np.random.RandomState(7)
    server = Server(engine, prefills_per_step=2)
    handles = [
        server.submit(Request(
            prompt=_prompt(rng, n), max_new_tokens=m, temperature=t,
            top_k=k, rng=seed,
        ))
        for (n, m, t, k), seed in zip(
            [(3, 6, 0.0, None), (7, 9, 0.9, 8), (12, 4, 0.0, None),
             (16, 8, 0.7, 5), (5, 10, 1.1, 12), (9, 5, 0.0, None)],
            seeds,
        )
    ]
    server.drain()
    assert all(h.status == "done" for h in handles)
    return [list(h.new_tokens) for h in handles]


def test_quantized_write_gather_bitwise_deterministic(q_engine):
    """Same load twice through the quantized pool: token streams AND
    the int8/scale pool bytes bitwise-identical (run 2 starts from run
    1's residue — released rows are masked and fully overwritten, so
    state convergence is part of the claim)."""
    first = _run_load(q_engine, seeds=range(6))
    snap1 = _flat_pool(q_engine)
    second = _run_load(q_engine, seeds=range(6))
    snap2 = _flat_pool(q_engine)
    assert first == second
    for name in snap1:
        assert np.array_equal(snap1[name], snap2[name]), name


def test_paged_twin_matches_dense_quantized(model, params, q_engine):
    """Quantized paged engine == quantized dense engine token-for-token
    (greedy + seeded sampling mix) — layout and quantization compose."""
    dense_streams = _run_load(q_engine, seeds=range(10, 16))
    paged = SlotEngine(
        model, params, num_slots=4, max_len=MAX_LEN, buckets=BUCKETS,
        kv_layout="paged", block_size=4,
        kv_dtype="int8", weight_dtype="int8",
    )
    paged.warmup()
    paged_streams = _run_load(paged, seeds=range(10, 16))
    assert dense_streams == paged_streams


def test_int8_churn_zero_compiles_and_closed_programs(q_engine):
    """The existing churn oracle extended to the int8 config: programs
    == buckets + 1, admission/eviction/cancel churn compiles nothing."""
    from jax._src import monitoring

    assert q_engine.compile_count == len(q_engine.buckets) + 1
    q_engine.warmup()  # idempotent
    assert q_engine.compile_count == len(q_engine.buckets) + 1

    compiles = []
    monitoring.register_event_duration_secs_listener(
        lambda event, duration, **kw: compiles.append(event)
        if "backend_compile" in event else None
    )
    baseline = len(compiles)
    rng = np.random.RandomState(3)
    server = Server(q_engine, prefills_per_step=2)
    mk = lambda n, m, **kw: server.submit(Request(  # noqa: E731
        prompt=_prompt(rng, n), max_new_tokens=m, **kw
    ))
    wave = [
        mk(3, 8, temperature=0.9, top_k=8, rng=1),
        mk(8, 10, rng=2),
        mk(13, 10, temperature=0.7, top_k=5, rng=3),
        mk(16, 6, temperature=1.1, top_k=12, top_p=0.9, rng=4),
    ]
    for _ in range(4):
        server.step()
    wave[1].cancel()
    mk(5, 7, temperature=0.8, top_k=6, rng=5)  # reuses the freed slot
    server.drain()
    assert len(compiles) == baseline, compiles[baseline:]
    assert q_engine.compile_count == len(q_engine.buckets) + 1


def test_byte_accounting_int8_below_native(model, params, q_engine):
    native = SlotEngine(
        model, params, num_slots=4, max_len=MAX_LEN, buckets=BUCKETS
    )  # accounting needs no warmup
    a_nat = native.byte_accounting()
    a_q = q_engine.byte_accounting()
    assert a_q["kv_bytes_per_token"] < a_nat["kv_bytes_per_token"]
    assert a_q["param_bytes"] < a_nat["param_bytes"]
    # scales are IN the numbers: per-token bytes exceed the bare int8
    # payload (heads * head_dim * 2 tensors * layers)
    heads, head_dim, layers = 4, 32, 2
    payload_only = heads * head_dim * 2 * layers
    assert a_q["kv_bytes_per_token"] > payload_only
    # and the f32 engine's KV shrinks by ~the dtype ratio (scale
    # overhead keeps it above exactly 4x-less)
    assert a_q["kv_bytes_per_token"] < a_nat["kv_bytes_per_token"] / 3


def test_warmup_emits_byte_gauges(model, params, tmp_path):
    from distributeddeeplearning_tpu import obs

    bus = obs.configure(str(tmp_path), run_id="quant-test", proc=0,
                        install_handlers=False)
    try:
        eng = SlotEngine(
            model, params, num_slots=2, max_len=MAX_LEN, buckets=(8,),
            kv_dtype="int8", weight_dtype="int8",
        )
        eng.warmup()
        bus.flush()
    finally:
        obs.reset()
    from distributeddeeplearning_tpu.obs.report import (
        load, render, summarize,
    )

    summary = summarize(load([str(tmp_path)]))
    srv = summary["serving"]
    acct = eng.byte_accounting()
    assert srv["kv_bytes_per_token"] == pytest.approx(
        acct["kv_bytes_per_token"]
    )
    assert srv["param_bytes"] == pytest.approx(acct["param_bytes"])
    text = render(summary)
    assert "KV/token" in text


def test_force_token_self_replay_is_noop(q_engine):
    """Forcing the engine's own greedy stream back in reproduces it
    bitwise — the teacher-forcing hook changes context, not math."""
    rng = np.random.RandomState(9)
    prompt = _prompt(rng, 6)
    first, _ = q_engine.prefill(0, ReqSpec(prompt=prompt,
                                           max_new_tokens=8))
    free = [first]
    for _ in range(7):
        [(slot, tok, _e)] = q_engine.decode_step()
        free.append(tok)
    q_engine.release(0)
    first2, _ = q_engine.prefill(0, ReqSpec(prompt=prompt,
                                            max_new_tokens=8))
    forced = [first2]
    for i in range(7):
        q_engine.force_token(0, free[i])  # what it fed itself anyway
        [(slot, tok, _e)] = q_engine.decode_step()
        forced.append(tok)
    q_engine.release(0)
    assert forced == free
    with pytest.raises(ValueError, match="not occupied"):
        q_engine.force_token(1, 0)


def test_serve_config_quant_env_and_kwargs():
    cfg = ServeConfig.from_env({
        "SERVE_KV_DTYPE": "int8", "SERVE_WEIGHT_DTYPE": "int8",
    })
    assert cfg.kv_dtype == "int8" and cfg.weight_dtype == "int8"
    kw = cfg.engine_kwargs()
    assert kw["kv_dtype"] == "int8" and kw["weight_dtype"] == "int8"
    dflt = ServeConfig.from_env({})
    assert dflt.kv_dtype == "bf16" and dflt.weight_dtype == "bf16"
    with pytest.raises(ValueError, match="kv_dtype"):
        SlotEngine(
            TransformerLM(variant="tiny", vocab_size=8, max_seq_len=8),
            {}, kv_dtype="fp4",
        )
    with pytest.raises(ValueError, match="weight_dtype"):
        SlotEngine(
            TransformerLM(variant="tiny", vocab_size=8, max_seq_len=8),
            {}, weight_dtype="fp4",
        )


def test_serve_config_kernel_and_fp8_env_surface():
    """The round-10 knobs ride the same registry: fp8 parses as a real
    tier, SERVE_DECODE_KERNEL threads into engine_kwargs, and unknown
    values fail naming the supported list (not an int8 special case)."""
    cfg = ServeConfig.from_env({
        "SERVE_KV_DTYPE": "fp8", "SERVE_WEIGHT_DTYPE": "fp8",
        "SERVE_DECODE_KERNEL": "fused",
    })
    assert cfg.kv_dtype == "fp8" and cfg.weight_dtype == "fp8"
    assert cfg.decode_kernel == "fused"
    kw = cfg.engine_kwargs()
    assert kw["kv_dtype"] == "fp8" and kw["decode_kernel"] == "fused"
    assert ServeConfig.from_env({}).decode_kernel == "xla"
    with pytest.raises(ValueError, match=r"kv_dtype.*bf16.*int8.*fp8"):
        ServeConfig(kv_dtype="int4").engine_kwargs()
    with pytest.raises(ValueError, match="SERVE_DECODE_KERNEL"):
        ServeConfig(decode_kernel="pallas2").engine_kwargs()
    with pytest.raises(ValueError, match="decode_kernel"):
        SlotEngine(
            TransformerLM(variant="tiny", vocab_size=8, max_seq_len=8),
            {}, decode_kernel="turbo",
        )


def test_fp8_engine_raises_when_unsupported(model, params, monkeypatch):
    """The platform gate: where the fp8 probe fails the engine refuses
    to build — an fp8 request is never quietly served from int8."""
    from distributeddeeplearning_tpu.ops import quant as quantlib

    monkeypatch.setattr(quantlib, "fp8_supported", lambda: False)
    for kw in ({"kv_dtype": "fp8"}, {"weight_dtype": "fp8"}):
        with pytest.raises(ValueError, match="fp8 storage is unsupported"):
            SlotEngine(
                model, params, num_slots=2, max_len=MAX_LEN, buckets=(8,),
                **kw,
            )
