"""Oracles for the sync-free hot loop (ISSUE 1).

Three invariants, all CPU-tier provable:

1. **True epoch means, bit-for-bit.** The loop's epoch logs equal a
   synchronous reference loop's host-side f32 running mean of per-step
   metrics — exactly, in f32 — because the on-device accumulator does
   the identical f32 adds in the identical order.
2. **≤ 1 host materialisation per epoch.** Counted by the hostsync
   accountant while additionally patching ``jax.device_get`` itself
   (``hostsync.track``), so a stray sync anywhere inside ``fit`` —
   callbacks, staging, checkpointing — would be caught.
3. **Warm-cache warmup skips recompilation.** With the persistent
   compilation cache enabled, a second AOT warmup of a fresh engine
   observes cache HITS (and writes no new entries for the same program).

Plus: the accumulating step variant leaves training math untouched
(state bit-identical to the vanilla step) under every engine.
"""

import os

import jax
import numpy as np
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.data.pipeline import prefetch_to_device
from distributeddeeplearning_tpu.data.synthetic import (
    SyntheticImageDataset,
    SyntheticTokenDataset,
)
from distributeddeeplearning_tpu.models import get_model
from distributeddeeplearning_tpu.training import loop
from distributeddeeplearning_tpu.training.engines import build_engine
from distributeddeeplearning_tpu.training.metrics import (
    METRIC_KEYS,
    finalize_accumulator,
    init_accumulator,
)
from distributeddeeplearning_tpu.training.optimizer import create_optimizer
from distributeddeeplearning_tpu.utils import hostsync

VOCAB, T = 64, 16


def _cfg(**kw):
    base = dict(
        model="resnet18",
        num_classes=8,
        image_size=16,
        batch_size_per_device=2,
        fake_data_length=48,
        epochs=2,
        compute_dtype="float32",
        log_every_steps=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def _image_data(cfg, seed=0):
    return SyntheticImageDataset(
        length=cfg.fake_data_length,
        global_batch_size=cfg.global_batch_size,
        image_size=cfg.image_size,
        num_classes=cfg.num_classes,
        seed=seed,
    )


def _token_cfg(engine, **kw):
    base = dict(
        engine=engine,
        model="lm_tiny",
        num_classes=VOCAB,
        batch_size_per_device=2,
        fake_data_length=32,
        epochs=1,
        compute_dtype="float32",
        weight_decay=0.0,
        log_every_steps=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def _token_data(cfg, seed=0):
    return SyntheticTokenDataset(
        length=cfg.fake_data_length,
        global_batch_size=cfg.global_batch_size,
        seq_len=T,
        vocab_size=VOCAB,
        seed=seed,
    )


def _build(model_name, cfg, data, mesh):
    from distributeddeeplearning_tpu.parallel.mesh import dp_size

    tx, _ = create_optimizer(
        cfg, data.steps_per_epoch, world_size=dp_size(mesh)
    )
    model = get_model(
        model_name,
        num_classes=cfg.num_classes,
        dtype=cfg.compute_dtype,
        **({"max_seq_len": T} if model_name.startswith("lm_") else {}),
    )
    from distributeddeeplearning_tpu.training.loop import _init_spec

    shape, dtype = _init_spec(data)
    return build_engine(
        model, cfg, tx, mesh, input_shape=shape, input_dtype=dtype
    )


def test_epoch_means_match_synchronous_reference_bitwise(mesh8):
    """(1): fit's epoch logs == host-side f32 running means of the
    per-step metrics a synchronous (device_get-every-step) loop sees."""
    cfg = _cfg()
    model = get_model("resnet18", num_classes=8, dtype="float32")
    res = loop.fit(
        model, cfg, _image_data(cfg), mesh=mesh8, add_default_logger=False
    )

    # Reference: identical engine from the identical seed, stepped with
    # the plain (non-accumulating) step, materialising EVERY step.
    eng = _build("resnet18", cfg, _image_data(cfg), mesh8)
    state = eng.state
    for epoch in range(cfg.epochs):
        sums = {k: np.float32(0.0) for k in METRIC_KEYS}
        steps = 0
        for batch in prefetch_to_device(
            _image_data(cfg).epoch(epoch), mesh8, size=0
        ):
            state, metrics = eng.train_step(state, batch)
            host = jax.device_get(metrics)  # the sync fit no longer does
            for k in sums:
                sums[k] = np.float32(sums[k] + np.float32(host[k]))
            steps += 1
        for k in sums:
            want = np.float32(sums[k] / np.float32(steps))
            got = np.float32(res.history[epoch][k])
            assert got == want, (epoch, k, got.tobytes(), want.tobytes())


def test_loop_performs_at_most_one_sync_per_epoch(mesh8):
    """(2): the whole fit — staging, callbacks, epoch summary — crosses
    device→host exactly once per epoch."""
    cfg = _cfg(epochs=3)
    model = get_model("resnet18", num_classes=8, dtype="float32")
    hostsync.accountant().reset()
    with hostsync.track():
        res = loop.fit(
            model, cfg, _image_data(cfg), mesh=mesh8,
            add_default_logger=False,
        )
    acct = hostsync.accountant()
    assert acct.count == cfg.epochs, acct.by_label
    assert acct.by_label.get("epoch_metrics") == cfg.epochs
    assert res.perf["host_sync_count"] == cfg.epochs
    # ...and the loop really used the accumulator: true means, not the
    # last step's values, reached history (epoch_images sanity too).
    assert res.history[0]["epoch_images"] == cfg.fake_data_length // 16 * 16


@pytest.mark.parametrize("engine", ["dp", "pjit", "sp", "pp"])
def test_accumulating_step_is_math_neutral(engine, mesh8):
    """The acc-threading variant must not perturb training: same seed +
    same batches => bit-identical params, and the accumulator's means
    equal the f32 mean of the per-step metrics it saw."""
    from distributeddeeplearning_tpu.training.loop import resolve_engine

    kw = {}
    if engine == "pp":
        kw = dict(
            mesh_axes=("data", "pipe"), mesh_shape=(2, 4), pp_microbatches=2
        )
    elif engine == "sp":
        kw = dict(mesh_axes=("data", "seq"), mesh_shape=(2, 4))
    cfg = _token_cfg(engine, **kw)
    _, mesh = resolve_engine(cfg)
    data = _token_data(cfg)

    eng_a = _build("lm_tiny", cfg, data, mesh)
    eng_b = _build("lm_tiny", cfg, data, mesh)
    state_a, state_b = eng_a.state, eng_b.state
    acc = init_accumulator(mesh)
    per_step = []
    for batch in prefetch_to_device(
        data.epoch(0), mesh, size=0, sharding=eng_a.batch_sharding
    ):
        state_a, m_a = eng_a.train_step(state_a, batch)
        state_b, m_b, acc = eng_b.train_step(state_b, batch, acc)
        per_step.append(jax.device_get(m_b))
        np.testing.assert_array_equal(
            jax.device_get(m_a["loss"]), jax.device_get(m_b["loss"])
        )
    for la, lb in zip(
        jax.tree.leaves(jax.device_get(state_a.params)),
        jax.tree.leaves(jax.device_get(state_b.params)),
    ):
        np.testing.assert_array_equal(la, lb)
    means = jax.device_get(finalize_accumulator(acc))
    for k in METRIC_KEYS:
        run = np.float32(0.0)
        for m in per_step:
            run = np.float32(run + np.float32(m[k]))
        want = np.float32(run / np.float32(len(per_step)))
        assert np.float32(means[k]) == want, (k, means[k], want)


def test_sync_invariant_holds_with_event_bus_enabled(mesh8, tmp_path):
    """ISSUE 2 hard constraint: with the event bus WRITING (OBS_DIR
    live), instrumentation adds zero host syncs — the ≤1-per-epoch
    invariant holds under hostsync.track(), and the captured events
    prove the bus saw the whole run from host-side floats only."""
    import json

    from distributeddeeplearning_tpu import obs

    cfg = _token_cfg("dp", epochs=2)
    bus = obs.configure(str(tmp_path / "run"))
    try:
        hostsync.accountant().reset()
        with hostsync.track():
            res = loop.fit(
                get_model("lm_tiny", num_classes=VOCAB, dtype="float32",
                          max_seq_len=T),
                cfg,
                _token_data(cfg),
                mesh=mesh8,
                add_default_logger=False,
            )
        acct = hostsync.accountant()
        assert acct.count == cfg.epochs, acct.by_label
        assert acct.by_label.get("epoch_metrics") == cfg.epochs
        assert res.perf["host_sync_count"] == cfg.epochs
        # The bus captured the run: per-step spans, per-epoch spans, and
        # exactly the epoch-boundary materialisations as sync counters.
        lines = [json.loads(ln) for ln in open(bus.path)]
        steps = [r for r in lines
                 if r.get("kind") == "span" and r["name"] == "step"]
        epochs = [r for r in lines
                  if r.get("kind") == "span" and r["name"] == "epoch"]
        syncs = [r for r in lines
                 if r.get("kind") == "counter" and r["name"] == "host_sync"]
        assert len(epochs) == cfg.epochs
        assert len(steps) == cfg.epochs * _token_data(cfg).steps_per_epoch
        assert sum(r["value"] for r in syncs) == cfg.epochs
        assert {r["labels"]["label"] for r in syncs} == {"epoch_metrics"}
    finally:
        obs.reset()


@pytest.fixture
def placed_cache(tmp_path, monkeypatch):
    """A persistent compile cache placed from outside, the way JAX sees
    it when ``JAX_COMPILATION_CACHE_DIR`` is exported before start-up
    (JAX reads the variable into its config at import) — and switched
    on, which the suite otherwise keeps off (conftest)."""
    from jax.experimental.compilation_cache import compilation_cache

    cache_dir = str(tmp_path / "xla-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", cache_dir)
    jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_enable_compilation_cache", True)
    compilation_cache.reset_cache()
    yield cache_dir
    jax.config.update("jax_enable_compilation_cache", False)
    jax.config.update("jax_compilation_cache_dir", None)
    compilation_cache.reset_cache()


def test_compile_cache_is_placed_from_outside_or_in_the_checkout(
    placed_cache, monkeypatch
):
    """The one cache rule (training/warmup.enable_compile_cache):
    JAX_COMPILATION_CACHE_DIR set -> no directory is set in code;
    unset -> ``<checkout>/.jax_cache``, a fixed path."""
    from distributeddeeplearning_tpu.training import warmup as wu

    updates = []
    real_update = jax.config.update

    def spy(name, value):
        updates.append(name)
        real_update(name, value)

    monkeypatch.setattr(jax.config, "update", spy)
    assert wu.enable_compile_cache() == placed_cache
    assert "jax_compilation_cache_dir" not in updates
    assert jax.config.jax_compilation_cache_dir == placed_cache

    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert wu.enable_compile_cache() == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == wu.DEFAULT_CACHE_DIR
    del updates[:]
    wu.enable_compile_cache()  # same answer: nothing to move
    assert "jax_compilation_cache_dir" not in updates


def test_warm_persistent_cache_skips_recompilation(mesh8, placed_cache):
    """(3): second AOT warmup against a warm on-disk cache observes
    cache hits; the executables really landed on disk the first time."""
    cfg = _token_cfg("dp", aot_warmup=True)
    data = _token_data(cfg)
    eng = _build("lm_tiny", cfg, data, mesh8)
    batch = next(iter(prefetch_to_device(data.epoch(0), mesh8, size=0)))
    acc = init_accumulator(mesh8)

    info1 = eng.warmup(batch, acc=acc)
    assert info1["train_compile_sec"] > 0
    assert info1["compile_sec"] > 0
    n_entries = len(os.listdir(placed_cache))
    assert n_entries > 0  # the compile was persisted

    # Fresh engine (fresh jit wrappers) + cleared in-memory caches:
    # the only way the second compile can be cheap is the disk cache.
    jax.clear_caches()
    eng2 = _build("lm_tiny", cfg, data, mesh8)
    info2 = eng2.warmup(batch, acc=acc)
    assert info2["persistent_cache_hits"] > 0, info2
    assert info2["persistent_cache_misses"] == 0, info2
    # the warm pass may lazily persist small helper programs that
    # were only in-memory before, but never re-writes the step
    assert len(os.listdir(placed_cache)) >= n_entries


def test_fit_aot_warmup_reports_compile_sec(mesh8):
    """AOT_WARMUP=1 end-to-end: fit compiles up front and surfaces
    compile_sec (+ FLOPs when the backend reports them) in perf."""
    cfg = _token_cfg("dp", aot_warmup=True)
    res = loop.fit(
        get_model("lm_tiny", num_classes=VOCAB, dtype="float32",
                  max_seq_len=T),
        cfg,
        _token_data(cfg),
        mesh=mesh8,
        add_default_logger=False,
    )
    assert res.perf["train_compile_sec"] > 0
    assert res.perf["compile_sec"] > 0
    assert res.perf["host_sync_count"] == cfg.epochs
    assert np.isfinite(res.history[-1]["loss"])


def test_config_env_contract():
    cfg = TrainConfig.from_env({"AOT_WARMUP": "1"})
    assert cfg.aot_warmup is True
    # the compile cache is placed by JAX_COMPILATION_CACHE_DIR alone:
    # the repo's old second name for it is not a config field
    assert not hasattr(cfg, "compilation_cache_dir")
