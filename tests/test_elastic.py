"""Elastic-worlds oracles (ISSUE 11): topology-independent checkpoints,
shrink-to-survivors restart, grow-back.

Tiers:

* fast — the FAULT_PLAN elasticity grammar (shrink/restore_capacity),
  the capacity-probe file protocol, divisor-compatible world selection,
  the process-count-independent "global" data topology, the checkpoint
  **portability oracle** (save on an 8-device mesh; restore + reshard
  onto 1, 4 and 8 devices — params bitwise-identical as global arrays,
  optimizer state round-trips, manifest intact), ``reshard_state``,
  the faultgen elastic-drill CLI, bench_trend's ``world_change`` skip,
  and a jax-light supervisor e2e driving the whole
  shrink→resume→grow cycle in seconds (``tests/_fault_child.py``).
* heavy (``tests/heavy_tests.txt``) — the in-process trajectory oracle:
  an lm_tiny world preempted mid-epoch resumes on HALF the devices with
  ``BATCHSIZE``/``ACCUM_STEPS`` doubled (effective batch constant, LR
  world pinned) and the post-resume trajectory matches the uninterrupted
  fixed-world run at f32-ULP; a grow-back resumes on the full mesh and
  the final params still match. The real 2-OS-process supervised drill
  lives in ``tests/test_fault_tolerance.py``.
"""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from distributeddeeplearning_tpu import faults
from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.training.checkpoint import (
    CheckpointManager,
    build_manifest,
    reshard_state,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, T = 64, 16


# ---------------------------------------------------------------------------
# Fast: elasticity grammar + capacity probe
# ---------------------------------------------------------------------------

def test_parse_elastic_plan_grammar():
    plan = faults.parse_fault_plan(
        "shrink:step=3,ranks=2;restore_capacity:secs=30"
    )
    assert plan[0] == faults.Fault(kind="shrink", step=3, ranks=2)
    assert plan[1].kind == "restore_capacity"
    assert plan[1].step == 0 and plan[1].secs == 30.0
    # step-indexed restore (the deterministic drill form)
    plan = faults.parse_fault_plan("shrink:step=2;restore_capacity:step=6")
    assert plan[0].ranks == 1
    assert plan[1].step == 6


@pytest.mark.parametrize(
    "bad",
    [
        "kill:step=1,ranks=2",      # ranks is shrink-only
        "restore_capacity:",        # needs secs= or step=
        "shrink:ranks=1",           # missing step
        "shrink:step=1,ranks=0",    # must lose >= 1 process
    ],
)
def test_parse_elastic_plan_rejects(bad):
    with pytest.raises(ValueError):
        faults.parse_fault_plan(bad)


def test_capacity_probe_protocol(tmp_path):
    cap = str(tmp_path / "capacity.json")
    # no file / unreadable file -> full capacity (never block a relaunch)
    assert faults.probe_capacity(cap, 8) == 8
    assert faults.probe_capacity(None, 8) == 8
    (tmp_path / "capacity.json").write_text("{torn")
    assert faults.probe_capacity(cap, 8) == 8
    faults.write_capacity(cap, 3)
    assert faults.probe_capacity(cap, 8) == 3
    # a recorded restore_at in the past means capacity came back
    faults.write_capacity(cap, 3, restore_at=time.time() - 1)
    assert faults.probe_capacity(cap, 8) == 8
    faults.write_capacity(cap, 3, restore_at=time.time() + 3600)
    assert faults.probe_capacity(cap, 8) == 3
    # clamped to [0, full]
    faults.write_capacity(cap, 99)
    assert faults.probe_capacity(cap, 8) == 8


def test_elastic_world_selection():
    from distributeddeeplearning_tpu.launch import _elastic_world

    # largest divisor of the full world that fits available capacity
    assert _elastic_world(8, 8, 1) == 8
    assert _elastic_world(8, 7, 1) == 4
    assert _elastic_world(8, 3, 1) == 2
    assert _elastic_world(2, 1, 1) == 1
    # the operator's floor wins over availability
    assert _elastic_world(8, 1, 2) == 2
    assert _elastic_world(2, 0, 1) == 1
    # floor above every divisor -> full world
    assert _elastic_world(4, 0, 5) == 4


def test_injector_shrink_writes_capacity_and_spares_survivors(
    tmp_path, monkeypatch
):
    """The shrink verb's split personality: every rank records the lost
    capacity, only the top ``ranks`` casualties die. Rank 0 of a
    2-process world survives a ranks=1 shrink — so we can assert the
    capacity file (a SIGKILLed process asserts nothing)."""
    cap = str(tmp_path / "capacity.json")
    plan = faults.parse_fault_plan(
        "shrink:step=2,ranks=1;restore_capacity:secs=45"
    )
    inj = faults.FaultInjector(
        plan, rank=0, world=2, capacity_file=cap
    )
    assert inj.restore_secs == 45.0
    assert inj.due_after(2)
    t0 = time.time()
    inj.fire_after(2)  # rank 0 < survivors(1): returns alive
    d = json.loads((tmp_path / "capacity.json").read_text())
    assert d["available"] == 1
    assert t0 + 40 <= d["restore_at"] <= time.time() + 50
    # one-shot: fired directives are gone
    assert not inj.due_after(2)


def test_injector_restore_capacity_step_announces_full_world(
    tmp_path,
):
    cap = str(tmp_path / "capacity.json")
    inj = faults.FaultInjector(
        faults.parse_fault_plan("restore_capacity:step=5"),
        rank=0, world=1, full_world=2, capacity_file=cap,
    )
    assert inj.due_after(5)
    inj.fire_after(5)  # announces capacity and RETURNS (run continues)
    assert faults.probe_capacity(cap, 2) == 2
    assert json.loads((tmp_path / "capacity.json").read_text())[
        "available"
    ] == 2


def test_faultgen_elastic_drill_cli():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, "scripts/faultgen.py", "elastic-drill",
         "--step", "3", "--ranks", "1", "--restore-step", "6"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120, env=env,
    )
    assert res.returncode == 0, res.stderr
    assert res.stdout.strip() == (
        "shrink:step=3,ranks=1;restore_capacity:step=6"
    )
    # the emitted plan validates
    val = subprocess.run(
        [sys.executable, "scripts/faultgen.py", "validate",
         res.stdout.strip()],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120, env=env,
    )
    assert val.returncode == 0, val.stderr
    assert "shrink" in val.stdout and "restore_capacity" in val.stdout
    # wall-clock form + exit-code table carries the resize code
    secs = subprocess.run(
        [sys.executable, "scripts/faultgen.py", "elastic-drill",
         "--restore-secs", "12"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120, env=env,
    )
    assert secs.stdout.strip().endswith("restore_capacity:secs=12")
    codes = subprocess.run(
        [sys.executable, "scripts/faultgen.py", "exit-codes"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120, env=env,
    )
    assert "world_resize" in codes.stdout


def test_config_elastic_env_contract():
    cfg = TrainConfig.from_env({
        "ELASTIC": "1",
        "LR_WORLD_SIZE": "8",
        "DATA_TOPOLOGY": "global",
        "COMPUTE_DTYPE": "float32",
    })
    assert cfg.elastic is True
    assert cfg.lr_world_size == 8
    assert cfg.data_topology == "global"
    assert cfg.compute_dtype == "float32"
    d = TrainConfig.from_env({})
    assert d.elastic is False and d.lr_world_size is None
    assert d.data_topology == "process"
    from distributeddeeplearning_tpu.training.loop import resolve_engine

    with pytest.raises(ValueError, match="DATA_TOPOLOGY"):
        resolve_engine(d.replace(data_topology="sideways"))
    with pytest.raises(ValueError, match="LR_WORLD_SIZE"):
        resolve_engine(d.replace(lr_world_size=0))


# ---------------------------------------------------------------------------
# Fast: process-count-independent ("global") data topology
# ---------------------------------------------------------------------------

def test_global_topology_token_stream_is_world_size_invariant():
    from distributeddeeplearning_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )

    kw = dict(length=32, global_batch_size=8, seq_len=4, vocab_size=17,
              topology="global")
    one = SyntheticTokenDataset(**kw)
    two = [
        SyntheticTokenDataset(
            **kw, process_index=i, process_count=2
        )
        for i in range(2)
    ]
    for e in (0, 1):
        s1 = list(one.epoch(e))
        s2a, s2b = list(two[0].epoch(e)), list(two[1].epoch(e))
        for k in range(len(s1)):
            for part in (0, 1):  # inputs and targets
                np.testing.assert_array_equal(
                    s1[k][part],
                    np.concatenate([s2a[k][part], s2b[k][part]], axis=0),
                )
    # single-process global topology is BITWISE the legacy stream, so
    # turning it on does not invalidate any recorded single-host run
    legacy = SyntheticTokenDataset(
        length=32, global_batch_size=8, seq_len=4, vocab_size=17
    )
    for (a1, b1), (a2, b2) in zip(one.epoch(0), legacy.epoch(0)):
        np.testing.assert_array_equal(a1, a2)
        np.testing.assert_array_equal(b1, b2)


def test_global_topology_image_stream_is_world_size_invariant():
    from distributeddeeplearning_tpu.data.synthetic import (
        SyntheticImageDataset,
    )

    kw = dict(length=32, global_batch_size=8, image_size=4, num_classes=3,
              topology="global")
    one = SyntheticImageDataset(**kw)
    parts = [
        SyntheticImageDataset(**kw, process_index=i, process_count=4)
        for i in range(4)
    ]
    s1 = list(one.epoch(1))
    sp = [list(d.epoch(1)) for d in parts]
    for k in range(len(s1)):
        np.testing.assert_array_equal(
            s1[k][0], np.concatenate([s[k][0] for s in sp], axis=0)
        )
        np.testing.assert_array_equal(
            s1[k][1], np.concatenate([s[k][1] for s in sp], axis=0)
        )
    # exact mode: padded tail weights are against the GLOBAL length
    ex = SyntheticImageDataset(
        length=10, global_batch_size=8, image_size=4, num_classes=3,
        topology="global", exact=True,
    )
    w = np.concatenate([b[2] for b in ex.epoch(0)])
    assert w.sum() == 10
    with pytest.raises(ValueError, match="topology"):
        SyntheticImageDataset(
            length=8, global_batch_size=8, image_size=4, num_classes=3,
            topology="diagonal",
        )


# ---------------------------------------------------------------------------
# Fast: checkpoint portability oracle (save on 8, restore on 1 / 4 / 8)
# ---------------------------------------------------------------------------

def _submeshes(devices):
    from distributeddeeplearning_tpu.parallel.mesh import create_mesh

    return {
        1: create_mesh(devices=devices[:1]),
        4: create_mesh(devices=devices[:4]),
        8: create_mesh(devices=devices),
    }


def _toy_state(mesh, fill=None):
    """A TrainState with real optax momentum state, data-sharded and
    replicated leaves — the sharding shapes a real run produces."""
    import optax

    from distributeddeeplearning_tpu.training.state import TrainState

    params = {
        "kernel": jnp.arange(16, dtype=jnp.float32).reshape(4, 4),
        "bias": jnp.arange(4, dtype=jnp.float32),
    }
    if fill is not None:
        params = jax.tree.map(lambda x: x * 0 + fill, params)
    tx = optax.sgd(1e-2, momentum=0.9)
    state = TrainState.create(
        params=params, batch_stats={}, tx=tx
    )
    return jax.device_put(state, NamedSharding(mesh, P())), tx


def test_checkpoint_portability_across_device_counts(tmp_path, devices):
    """The portability oracle: save a real TrainState (params + sgd
    momentum + step) from the 8-device mesh; restore onto 1-, 4- and
    8-device meshes — every leaf bitwise-identical as a global array,
    the optimizer state round-tripping, the manifest decoding the same
    data cursor everywhere."""
    meshes = _submeshes(devices)
    state8, _ = _toy_state(meshes[8])
    # make momentum non-trivial so opt_state round-trip means something
    import optax

    grads = jax.tree.map(jnp.ones_like, state8.params)
    tx = optax.sgd(1e-2, momentum=0.9)
    updates, new_opt = tx.update(grads, state8.opt_state, state8.params)
    state8 = state8.replace(
        params=optax.apply_updates(state8.params, updates),
        opt_state=new_opt,
        step=state8.step + 1,
    )

    d = str(tmp_path / "ckpt")
    mgr = CheckpointManager(d, save_every_steps=1, async_save=False)
    assert mgr.save_step(
        6, state8,
        manifest=build_manifest(
            global_step=6, steps_per_epoch=4, effective_batch=16,
            accum_steps=1,
        ),
    )
    mgr.close()

    want = jax.device_get(state8)
    for n, mesh in meshes.items():
        template, _ = _toy_state(mesh, fill=0.0)
        mgr2 = CheckpointManager(d, save_every_steps=1, async_save=False)
        got, epoch, skip = mgr2.maybe_restore_at(
            template, steps_per_epoch=4
        )
        # manifest decodes the cursor identically on every topology
        assert (epoch, skip) == (1, 2)
        assert mgr2.last_manifest["effective_batch"] == 16
        assert mgr2.last_manifest["world_size"] == 8
        for (pa, a), (pb, b) in zip(
            jax.tree_util.tree_leaves_with_path(want),
            jax.tree_util.tree_leaves_with_path(jax.device_get(got)),
        ):
            assert str(pa) == str(pb)
            np.testing.assert_array_equal(a, b, err_msg=f"{n}-dev {pa}")
        # the restored arrays actually live on the target mesh
        leaf = jax.tree.leaves(got)[0]
        assert set(leaf.sharding.device_set) <= set(mesh.devices.flat)
        mgr2.close()


def test_reshard_state_roundtrip_bitwise(devices):
    meshes = _submeshes(devices)
    x8 = jax.device_put(
        jnp.arange(16, dtype=jnp.float32),
        NamedSharding(meshes[8], P("data")),
    )
    r8 = jax.device_put(
        jnp.arange(4, dtype=jnp.float32) * 3, NamedSharding(meshes[8], P())
    )
    state = {"w": x8, "b": r8}
    tmpl4 = {
        "w": jax.ShapeDtypeStruct(
            (16,), jnp.float32,
            sharding=NamedSharding(meshes[4], P("data")),
        ),
        "b": jax.ShapeDtypeStruct(
            (4,), jnp.float32, sharding=NamedSharding(meshes[4], P())
        ),
    }
    down = reshard_state(state, tmpl4)
    assert set(down["w"].sharding.device_set) == set(
        meshes[4].devices.flat
    )
    tmpl8 = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=x.sharding
        ),
        state,
    )
    back = reshard_state(down, tmpl8)
    np.testing.assert_array_equal(np.asarray(back["w"]), np.asarray(x8))
    np.testing.assert_array_equal(np.asarray(back["b"]), np.asarray(r8))
    # global shapes are the contract: a mismatch is refused loudly
    with pytest.raises(ValueError, match="shape"):
        reshard_state(
            {"w": jnp.arange(8, dtype=jnp.float32)},
            {"w": tmpl4["w"]},
        )


# ---------------------------------------------------------------------------
# Fast: bench_trend world_change skip
# ---------------------------------------------------------------------------

def test_bench_trend_world_change_is_skip_not_regression(tmp_path):
    from scripts.bench_trend import analyze

    def rec(n, value, world=None):
        detail = {"platform": "cpu"}
        if world is not None:
            detail["world_size"] = world
        path = tmp_path / f"BENCH_r{n:02d}.json"
        path.write_text(json.dumps({
            "n": n, "rc": 0,
            "parsed": {"metric": "resnet50_imgs_per_sec", "value": value,
                       "unit": "img/s", "detail": detail},
        }))
        return str(path)

    paths = [
        rec(1, 1000.0, world=8),
        rec(2, 400.0, world=4),   # elastic resize: new baseline, NOT a drop
        rec(3, 395.0, world=4),   # like-for-like: fine (-1.2%)
        rec(4, 100.0, world=4),   # like-for-like: REAL regression
    ]
    out = analyze(paths, threshold=0.10)
    rows = {r["round"]: r for r in out["rows"]}
    assert rows[2]["skip"] == "world_change:8->4"
    assert rows[3]["skip"] is None and rows[3]["delta_pct"] is not None
    assert len(out["regressions"]) == 1
    assert out["regressions"][0]["to_round"] == 4
    # legacy records (no world field) normalize together and stay comparable
    legacy = [rec(5, 500.0), rec(6, 490.0)]
    out2 = analyze(legacy, threshold=0.10)
    assert out2["ok"]
    assert all(r["skip"] in (None, "world_change:4->unspecified")
               for r in out2["rows"])


# ---------------------------------------------------------------------------
# Fast: jax-light supervisor e2e — the whole shrink→resume→grow cycle
# ---------------------------------------------------------------------------

def _run_launcher(args, timeout=600):
    return subprocess.run(
        [sys.executable, "launch.py", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=timeout,
    )


def test_supervisor_elastic_shrink_and_grow_jaxlight(tmp_path):
    """launch.py --elastic over the jax-light child: a shrink preemption
    kills the top rank of a 2-process world and records lost capacity;
    the supervisor relaunches at world 1 with BATCHSIZE/ACCUM_STEPS
    doubled and LR_WORLD_SIZE pinned; the shrunken world announces
    restored capacity at a later step; the grow poller stops it with the
    resize code (no restart budget burned) and relaunches at full size,
    which resumes and completes."""
    obs_dir = tmp_path / "run"
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--max-restarts", "1",
            "--restart-backoff", "0.1",
            "--elastic",
            "--min-world-size", "1",
            "--grow-check-every-s", "0.2",
            "--timeout", "120",
            "--obs-dir", str(obs_dir),
            "--env", "JAX_PLATFORMS=cpu",
            "--env", "FAKE_STEPS=100",
            "--env", "BATCHSIZE=2",
            "--env", "ACCUM_STEPS=1",
            # rank=1 pins the directive to the casualty process, so the
            # world-1 relaunch (rank 0) can never re-fire it whatever
            # step its state file persisted before the teardown.
            # Capacity returns at step 40, not right after the shrink:
            # rank 0 of the full world keeps stepping (50 ms each) until
            # the 10 Hz supervisor tears it down, and a loaded machine
            # let it pass step 6 — the shrunken world then resumed beyond
            # the restore step and never grew back.
            "--env",
            "FAULT_PLAN=shrink:step=3,rank=1,ranks=1;"
            "restore_capacity:step=40",
            "--env", f"STATE_FILE={tmp_path}/state",
            "tests/_fault_child.py",
        ],
        timeout=300,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    # attempt 0: full world, base geometry
    assert "FAULT_CHILD_WORLD rank=0 world=2 batch=2 accum=1 lr_world=2" in out
    # shrink classified as a retryable signal death; relaunch at world 1
    # with the integer rescale (effective batch held constant)
    assert "rc=-9, signal_SIGKILL" in out
    assert (
        "supervisor: elastic world 1/2 processes — BATCHSIZE 2->4, "
        "ACCUM_STEPS 1->2" in out
    ), out[-4000:]
    assert "FAULT_CHILD_WORLD rank=0 world=1 batch=4 accum=2 lr_world=2" in out
    # the shrunken world resumed from persisted progress, not step 0
    # (rank 0 survived to at least the shrink step before teardown)
    # grow-back: resize stop (rc 95) burns no budget, full world resumes
    assert "launch: world resize requested (capacity restored" in out
    assert "supervisor: world resize 1 -> 2" in out
    assert "no restart budget consumed" in out
    assert "FAULT_CHILD_WORLD rank=1 world=2 batch=2 accum=1 lr_world=2" in out
    assert "FAULT_CHILD_DONE 0" in out and "FAULT_CHILD_DONE 1" in out
    # capacity file went through the full protocol
    cap = json.loads((obs_dir / "capacity.json").read_text())
    assert cap["available"] == 2  # restore_capacity announced full world
    # supervisor record: per-attempt world sizes + the resize event
    recs = [
        json.loads(ln) for ln in open(obs_dir / "events-supervisor.jsonl")
    ]
    starts = [
        r["labels"]["world_size"] for r in recs
        if r.get("name") == "attempt_start"
    ]
    assert starts == [2, 1, 2], starts
    resized = [r for r in recs if r.get("name") == "elastic.world_resized"]
    assert any(
        r["labels"]["phase"] == "grow"
        and r["labels"]["from_world"] == 1
        and r["labels"]["to_world"] == 2
        for r in resized
    ), resized
    # shrink flight dump: the casualty left its black box
    dumps = list(obs_dir.glob("flight-p1*.jsonl"))
    assert dumps, sorted(os.listdir(obs_dir))
    head = json.loads(open(dumps[0]).readline())
    assert head["reason"] == "fault_shrink"


def test_supervisor_elastic_respects_min_world_size(tmp_path):
    """MIN_WORLD_SIZE=2 on a 2-process world: the shrink's capacity loss
    cannot go below the floor, so the supervisor relaunches at FULL size
    (the only divisor >= the floor) — and the run, resumed past the
    one-shot shrink step, completes."""
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--max-restarts", "2",
            "--restart-backoff", "0.1",
            "--elastic",
            "--min-world-size", "2",
            "--timeout", "120",
            "--env", "JAX_PLATFORMS=cpu",
            "--env", "FAKE_STEPS=6",
            "--env", "FAULT_PLAN=shrink:step=3,ranks=1",
            "--env", f"STATE_FILE={tmp_path}/state",
            "tests/_fault_child.py",
        ],
        timeout=300,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "FAULT_CHILD_WORLD rank=1 world=2" in out
    # no rescale announcement: the floor kept the world at full size
    assert "supervisor: elastic world" not in out
    assert "world=1" not in out
    assert "FAULT_CHILD_DONE 1 start=3" in out, out[-4000:]


# ---------------------------------------------------------------------------
# Heavy: in-process elastic trajectory oracle (registered in
# tests/heavy_tests.txt)
# ---------------------------------------------------------------------------

def _lm_cfg(**kw):
    base = dict(
        model="lm_tiny",
        num_classes=VOCAB,
        batch_size_per_device=2,
        fake_data_length=64,
        epochs=3,
        compute_dtype="float32",
        weight_decay=0.0,
        log_every_steps=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def _lm_fit(cfg, mesh):
    from distributeddeeplearning_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training import loop

    data = SyntheticTokenDataset(
        length=cfg.fake_data_length,
        global_batch_size=16,  # constant at every world size
        seq_len=T,
        vocab_size=VOCAB,
    )
    model = get_model(
        "lm_tiny", num_classes=VOCAB, dtype="float32", max_seq_len=T
    )
    return loop.fit(model, cfg, data, mesh=mesh, add_default_logger=False)


def _ulp_equal(tree_a, tree_b):
    for (pa, a), (pb, b) in zip(
        jax.tree_util.tree_leaves_with_path(jax.device_get(tree_a)),
        jax.tree_util.tree_leaves_with_path(jax.device_get(tree_b)),
    ):
        np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-7,
                                   err_msg=str(pa))


def test_inprocess_elastic_shrink_grow_is_ulp_equivalent(
    tmp_path, devices, monkeypatch
):
    """The elastic math contract, in one process: preempt a mesh8 run
    mid-epoch; resume on mesh4 with BATCHSIZE x2 + ACCUM_STEPS x2 and
    the LR world pinned (effective batch 16 everywhere); the resumed
    trajectory matches the uninterrupted mesh8 run at f32-ULP; grow
    back onto mesh8 for the final epoch and the final params still
    match. Also asserts the elastic telemetry (world_resized /
    reshard_ms / data.resume_skip) and the steady-state sync invariant.
    """
    import shutil

    from distributeddeeplearning_tpu import obs
    from distributeddeeplearning_tpu.parallel.mesh import create_mesh
    from distributeddeeplearning_tpu.utils import hostsync

    mesh8 = create_mesh(devices=devices)
    mesh4 = create_mesh(devices=devices[:4])

    # References: uninterrupted fixed world at 3 epochs (the final
    # comparison) and at 2 (the shrunken leg's endpoint) — the first
    # under the sync accountant, proving elasticity added ZERO host
    # syncs to the steady-state loop (no step checkpoints here; the one
    # sync per epoch stands).
    hostsync.accountant().reset()
    with hostsync.track():
        ref = _lm_fit(_lm_cfg(elastic=True, lr_world_size=8), mesh8)
    assert hostsync.accountant().count == 3, hostsync.accountant().by_label
    ref2 = _lm_fit(_lm_cfg(epochs=2), mesh8)

    ckpt_dir = str(tmp_path / "ckpt")
    cfg8 = _lm_cfg(
        model_dir=ckpt_dir, checkpoint_every_steps=1, checkpoint_async=False,
        lr_world_size=8, checkpoint_keep=20,
    )
    full = _lm_fit(cfg8, mesh8)
    _ulp_equal(ref.state.params, full.state.params)  # ckpt is neutral

    # Preempt at step 6 (4 steps/epoch -> mid-epoch-1, 2 batches done).
    for s in faults.checkpoint_steps(ckpt_dir):
        if s > 6:
            shutil.rmtree(os.path.join(ckpt_dir, str(s)))
    assert faults.checkpoint_steps(ckpt_dir)[-1] == 6

    obs_dir = tmp_path / "obs"
    monkeypatch.setenv("OBS_DIR", str(obs_dir))
    shrunk = _lm_fit(
        _lm_cfg(
            model_dir=ckpt_dir, checkpoint_every_steps=1,
            checkpoint_async=False, batch_size_per_device=4, accum_steps=2,
            lr_world_size=8, elastic=True, epochs=2, checkpoint_keep=20,
        ),
        mesh4,
    )
    monkeypatch.delenv("OBS_DIR")
    obs.reset()
    # The resume REALLY re-entered mid-epoch: only the 2 remaining
    # batches of epoch 1 ran (2 x global batch 16 = 32 images), and the
    # post-resume params land ULP-equal to the fixed-world 2-epoch run.
    assert len(shrunk.history) == 1
    assert shrunk.history[-1]["global_step"] == 8
    assert shrunk.history[-1]["epoch_images"] == 32
    _ulp_equal(ref2.state.params, shrunk.state.params)
    # elastic telemetry: cross-topology restore reported the reshard +
    # the O(step) resume replay reported its cost
    events = []
    for p in sorted(obs_dir.glob("events-*.jsonl")):
        events += [json.loads(ln) for ln in open(p)]
    names = [e.get("name") for e in events]
    assert "elastic.world_resized" in names
    resized = next(
        e for e in events if e.get("name") == "elastic.world_resized"
    )
    assert resized["labels"]["from_world"] == 8
    assert resized["labels"]["to_world"] == 4
    assert "elastic.reshard_ms" in names
    skip_ev = next(e for e in events if e.get("name") == "data.resume_skip")
    assert skip_ev["labels"]["skipped"] == 2
    assert "data.resume_skip_ms" in names

    # Grow back: full mesh for the last epoch, resuming the mesh4 world's
    # checkpoint — the post-resume loss trajectory and the final params
    # (and optimizer state) match the uninterrupted run at f32-ULP.
    grown = _lm_fit(
        _lm_cfg(
            model_dir=ckpt_dir, checkpoint_every_steps=1,
            checkpoint_async=False, lr_world_size=8, elastic=True,
            checkpoint_keep=20,
        ),
        mesh8,
    )
    assert grown.history[-1]["global_step"] == 12
    np.testing.assert_allclose(
        grown.history[-1]["loss"], ref.history[-1]["loss"],
        rtol=1e-4, atol=1e-6,
    )
    _ulp_equal(ref.state.params, grown.state.params)
    _ulp_equal(ref.state.opt_state, grown.state.opt_state)


def test_elastic_resume_refuses_wrong_effective_batch(
    tmp_path, devices
):
    """The accum-rescale validation: resuming an elastic world at a
    DIFFERENT effective batch (shrunken devices without the BATCHSIZE
    rescale) is refused with the contract named; with ELASTIC off the
    same mismatch only warns."""
    from distributeddeeplearning_tpu.parallel.mesh import create_mesh

    mesh8 = create_mesh(devices=devices)
    mesh4 = create_mesh(devices=devices[:4])
    ckpt_dir = str(tmp_path / "ckpt")
    cfg = _lm_cfg(
        model_dir=ckpt_dir, checkpoint_every_steps=1,
        checkpoint_async=False, epochs=1,
    )
    _lm_fit(cfg, mesh8)

    from distributeddeeplearning_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training import loop

    bad = _lm_cfg(
        model_dir=ckpt_dir, checkpoint_every_steps=1,
        checkpoint_async=False, elastic=True, epochs=2,
    )  # still 2/device, but only 4 shards -> effective 8 != 16
    data = SyntheticTokenDataset(
        length=bad.fake_data_length, global_batch_size=8, seq_len=T,
        vocab_size=VOCAB,
    )
    model = get_model(
        "lm_tiny", num_classes=VOCAB, dtype="float32", max_seq_len=T
    )
    with pytest.raises(ValueError, match="effective batch"):
        loop.fit(model, bad, data, mesh=mesh4, add_default_logger=False)
