"""Fault-tolerance oracles: step-granular checkpointing, corrupt-latest
fallback, and the resume-equivalence criterion — an interrupted-and-
resumed run must end BITWISE-equal to an uninterrupted one, because
restore is exact (orbax), the data stream is deterministic per
(seed, epoch), and the engines are bitwise run-to-run deterministic
(``tests/test_determinism.py``).

Tiers:

* fast — manager keying/fallback units on plain pytrees, plus an
  in-process mid-epoch resume equivalence (simulated preemption:
  newer checkpoints deleted, fit resumed from a mid-epoch key);
* heavy (``tests/heavy_tests.txt``) — the ISSUE 4 acceptance runs:
  2-OS-process worlds under ``launch.py --max-restarts`` where a
  FAULT_PLAN SIGKILLs rank 1 mid-epoch and the supervisor resumes from
  the step checkpoint, across the dp and pjit engines; and the NaN
  guard halting a supervised world with the non-retryable code.
"""

import json
import os
import re
import shutil
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu.config import TrainConfig
from distributeddeeplearning_tpu.training.checkpoint import CheckpointManager

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VOCAB, T = 64, 16


# ---------------------------------------------------------------------------
# Fast: step-granular keying
# ---------------------------------------------------------------------------

def _tree(v: float):
    return {"w": jnp.full((4,), float(v), jnp.float32),
            "b": jnp.full((2,), float(v) * 10, jnp.float32)}


def test_step_granular_save_and_resume_keying(tmp_path):
    mgr = CheckpointManager(
        str(tmp_path / "ckpt"), save_every_steps=2, async_save=False,
        max_to_keep=10,
    )
    assert mgr.step_granular
    assert not mgr.save_step(1, _tree(1))   # not due
    assert mgr.save_step(2, _tree(2))       # due every 2
    assert not mgr.save_step(3, _tree(3))
    # epoch boundary (epoch 0 of a 4-step epoch) forces the save under
    # its global-step key
    assert mgr.save_epoch_end(0, _tree(4), global_step=4)
    # boundary coinciding with an already-saved due step is idempotent
    assert mgr.save_step(4, _tree(4)) is False
    assert mgr.save_step(6, _tree(6))
    mgr.close()

    mgr2 = CheckpointManager(
        str(tmp_path / "ckpt"), save_every_steps=2, async_save=False
    )
    state, epoch, skip = mgr2.maybe_restore_at(_tree(0), steps_per_epoch=4)
    assert (epoch, skip) == (1, 2)  # key 6 on a 4-step epoch
    np.testing.assert_array_equal(np.asarray(state["w"]), np.full(4, 6.0))
    mgr2.close()


def test_epoch_mode_unchanged_and_skipless(tmp_path):
    """save_epoch_end without step granularity keeps the legacy epoch
    keying and maybe_restore_at always reports skip_steps == 0."""
    mgr = CheckpointManager(str(tmp_path / "ckpt"), async_save=False)
    assert not mgr.step_granular
    assert mgr.save_step(5, _tree(5)) is False  # step saves are inert
    assert mgr.save_epoch_end(0, _tree(1), global_step=4)
    state, epoch, skip = mgr.maybe_restore_at(_tree(0), steps_per_epoch=4)
    assert (epoch, skip) == (1, 0)
    np.testing.assert_array_equal(np.asarray(state["w"]), np.full(4, 1.0))
    mgr.close()


# ---------------------------------------------------------------------------
# Fast: corrupt-latest fallback (the partial-write fault)
# ---------------------------------------------------------------------------

def test_corrupt_latest_checkpoint_falls_back(tmp_path):
    """A truncated newest checkpoint (preemption mid-write, rehearsed by
    scripts/faultgen.py corrupt-latest) must not kill the resume: the
    manager falls back to the previous valid step."""
    ckpt_dir = str(tmp_path / "ckpt")
    mgr = CheckpointManager(
        ckpt_dir, save_every_steps=2, async_save=False, max_to_keep=10
    )
    assert mgr.save_step(2, _tree(2))
    assert mgr.save_step(4, _tree(4))
    mgr.close()

    # corrupt through the CLI so the tool itself is exercised
    res = subprocess.run(
        [sys.executable, "scripts/faultgen.py", "corrupt-latest", ckpt_dir],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert res.returncode == 0, res.stderr
    assert "truncated checkpoint step 4" in res.stdout

    mgr2 = CheckpointManager(
        ckpt_dir, save_every_steps=2, async_save=False
    )
    state, epoch, skip = mgr2.maybe_restore_at(_tree(0), steps_per_epoch=4)
    assert (epoch, skip) == (0, 2)  # fell back from 4 to 2
    np.testing.assert_array_equal(np.asarray(state["w"]), np.full(4, 2.0))
    mgr2.close()

    # every checkpoint corrupt -> clean cold start, not a crash
    from distributeddeeplearning_tpu import faults

    shutil.rmtree(os.path.join(ckpt_dir, "4"))  # only step 2 remains...
    faults.corrupt_latest_checkpoint(ckpt_dir)  # ...and now it's corrupt
    mgr3 = CheckpointManager(
        ckpt_dir, save_every_steps=2, async_save=False
    )
    state, epoch, skip = mgr3.maybe_restore_at(_tree(0), steps_per_epoch=4)
    assert (epoch, skip) == (0, 0)
    np.testing.assert_array_equal(np.asarray(state["w"]), np.zeros(4))
    mgr3.close()


# ---------------------------------------------------------------------------
# Fast-ish: in-process mid-epoch resume equivalence
# ---------------------------------------------------------------------------

def _lm_cfg(**kw):
    base = dict(
        model="lm_tiny",
        num_classes=VOCAB,
        batch_size_per_device=2,
        fake_data_length=64,
        epochs=2,
        compute_dtype="float32",
        weight_decay=0.0,
        log_every_steps=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def _lm_fit(cfg, mesh8):
    from distributeddeeplearning_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training import loop

    data = SyntheticTokenDataset(
        length=cfg.fake_data_length,
        global_batch_size=cfg.global_batch_size,
        seq_len=T,
        vocab_size=VOCAB,
    )
    model = get_model(
        "lm_tiny", num_classes=VOCAB, dtype="float32", max_seq_len=T
    )
    return loop.fit(model, cfg, data, mesh=mesh8, add_default_logger=False)


def test_midepoch_resume_is_bitwise_equivalent(tmp_path, mesh8):
    """Simulated preemption: a fully-trained run's checkpoints are rolled
    back to a MID-epoch step key, and a fresh fit resumes there — epoch
    stream re-entered, completed batches skipped — landing on final
    params bitwise-equal to the uninterrupted run."""
    # Reference: uninterrupted, no checkpointing.
    ref = _lm_fit(_lm_cfg(), mesh8)

    # Checkpointed run: steps keyed globally, every save durable.
    ckpt_dir = str(tmp_path / "ckpt")
    cfg = _lm_cfg(
        model_dir=ckpt_dir,
        checkpoint_every_steps=3,
        checkpoint_async=False,
    )
    full = _lm_fit(cfg, mesh8)
    # Checkpointing must not perturb the math to begin with.
    for a, b in zip(
        jax.tree.leaves(jax.device_get(ref.state.params)),
        jax.tree.leaves(jax.device_get(full.state.params)),
    ):
        np.testing.assert_array_equal(a, b)

    # "Preempt at step 6": drop every newer checkpoint (4 steps/epoch,
    # so key 6 is MID-epoch-1: skip 2 of its 4 batches) and resume.
    from distributeddeeplearning_tpu import faults

    steps = faults.checkpoint_steps(ckpt_dir)
    assert 6 in steps, steps
    for s in steps:
        if s > 6:
            shutil.rmtree(os.path.join(ckpt_dir, str(s)))
    resumed = _lm_fit(cfg, mesh8)
    assert resumed.history[0]["epoch_images"] == 32  # 2 of 4 batches left
    for a, b in zip(
        jax.tree.leaves(jax.device_get(ref.state.params)),
        jax.tree.leaves(jax.device_get(resumed.state.params)),
    ):
        np.testing.assert_array_equal(a, b)


# ---------------------------------------------------------------------------
# Heavy: the ISSUE 4 acceptance runs (2-OS-process worlds)
# ---------------------------------------------------------------------------

def _run_launcher(args, timeout=900):
    return subprocess.run(
        [sys.executable, "launch.py", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=timeout,
    )


def _ft_env_args(tmp_path, engine, **extra):
    env = dict(
        FAKE="True",
        MODEL="resnet18",
        IMAGE_SIZE="8",
        NUM_CLASSES="8",
        BATCHSIZE="2",
        FAKE_DATA_LENGTH="64",
        EPOCHS="2",
        ENGINE=engine,
        CHECKPOINT_ASYNC="0",
    )
    env.update(extra)
    out = []
    for k, v in env.items():
        out += ["--env", f"{k}={v}"]
    return out


def _shas(out):
    return dict(re.findall(r"FT_PARAMS_SHA (\d+) ([0-9a-f]{64})", out))


@pytest.mark.parametrize("engine", ["dp", "pjit"])
def test_resume_equivalence_across_supervised_restart(engine, tmp_path):
    """The acceptance criterion: FAULT_PLAN SIGKILLs process 1 after
    step 3 of a 2-process world; the supervisor restarts it, the world
    resumes from the step-granular checkpoint mid-epoch, and the final
    params are BITWISE-equal to an uninterrupted run — under both the
    shard_map dp engine and the GSPMD pjit engine."""
    base = [
        "--num-processes", "2",
        "--devices-per-process", "4",
        "--platform", "cpu",
        "--timeout", "540",
    ]
    # Run A: uninterrupted reference (no checkpointing at all).
    res_a = _run_launcher(
        [*base, *_ft_env_args(tmp_path, engine), "tests/_ft_child.py"]
    )
    out_a = res_a.stdout + res_a.stderr
    assert res_a.returncode == 0, out_a[-4000:]
    shas_a = _shas(out_a)
    assert set(shas_a) == {"0", "1"}, out_a[-2000:]
    assert shas_a["0"] == shas_a["1"]  # replicated params agree

    # Run B: step checkpoints + SIGKILL of rank 1 after step 3, under
    # the restart supervisor.
    res_b = _run_launcher(
        [
            *base,
            "--max-restarts", "1",
            "--restart-backoff", "0.1",
            *_ft_env_args(
                tmp_path, engine,
                MODEL_DIR=str(tmp_path / "b_ckpt"),
                CHECKPOINT_EVERY_STEPS="1",
                FAULT_PLAN="kill:step=3,rank=1",
            ),
            "tests/_ft_child.py",
        ]
    )
    out_b = res_b.stdout + res_b.stderr
    assert res_b.returncode == 0, out_b[-4000:]
    assert "supervisor: attempt 0 failed (rc=-9, signal_SIGKILL" in out_b
    # the relaunched world resumed MID-epoch from the step checkpoint
    assert "resuming from epoch 0 step 3" in out_b, out_b[-4000:]
    shas_b = _shas(out_b)
    assert set(shas_b) == {"0", "1"}, out_b[-2000:]
    assert shas_b == shas_a, (shas_a, shas_b)  # bitwise-equal final params


def test_corrupt_latest_falls_back_across_topologies(tmp_path, devices):
    """Corrupt-checkpoint fallback under RESHARDING: step checkpoints
    written from the 8-device mesh, the newest truncated (preemption
    mid-write), then restored at a DIFFERENT device count — the manager
    must fall back past the corrupt step onto the new topology, manifest
    intact."""
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from distributeddeeplearning_tpu import faults
    from distributeddeeplearning_tpu.parallel.mesh import create_mesh
    from distributeddeeplearning_tpu.training.checkpoint import (
        build_manifest,
    )

    mesh8 = create_mesh(devices=devices)
    ckpt_dir = str(tmp_path / "ckpt")

    def tree(mesh, v):
        return {
            "w": jax.device_put(
                jnp.full((16,), float(v), jnp.float32),
                NamedSharding(mesh, P("data")),
            ),
            "b": jax.device_put(
                jnp.full((4,), float(v) * 10, jnp.float32),
                NamedSharding(mesh, P()),
            ),
        }

    mgr = CheckpointManager(
        ckpt_dir, save_every_steps=2, async_save=False, max_to_keep=10
    )
    for s in (2, 4):
        assert mgr.save_step(
            s, tree(mesh8, s),
            manifest=build_manifest(
                global_step=s, steps_per_epoch=4, effective_batch=16,
                world_size=8,
            ),
        )
    mgr.close()
    assert faults.corrupt_latest_checkpoint(ckpt_dir)

    for n_dev in (1, 4):
        sub = create_mesh(devices=devices[:n_dev])
        mgr2 = CheckpointManager(
            ckpt_dir, save_every_steps=2, async_save=False
        )
        state, epoch, skip = mgr2.maybe_restore_at(
            tree(sub, 0), steps_per_epoch=4
        )
        assert (epoch, skip) == (0, 2)  # fell back from 4 to 2
        np.testing.assert_array_equal(
            np.asarray(state["w"]), np.full(16, 2.0)
        )
        assert mgr2.last_manifest["global_step"] == 2
        assert mgr2.last_manifest["world_size"] == 8
        assert set(jax.tree.leaves(state)[0].sharding.device_set) <= set(
            sub.devices.flat
        )
        mgr2.close()


# ---------------------------------------------------------------------------
# Heavy: the ISSUE 11 elastic drill (2-OS-process world, shrink -> grow)
# ---------------------------------------------------------------------------

def _losses(out):
    """rank-0 FT_EPOCH_LOSS lines -> {global_step: loss} (hex-exact)."""
    return {
        int(s): float.fromhex(v)
        for r, s, v in re.findall(
            r"FT_EPOCH_LOSS (\d+) (\d+) (\S+)", out
        )
        if r == "0"
    }


def test_elastic_supervised_shrink_grow_e2e(tmp_path):
    """The ISSUE 11 acceptance drill: a supervised 2-process lm_tiny
    world loses rank 1 mid-epoch (shrink preemption). The elastic
    supervisor relaunches at world 1 with BATCHSIZE/ACCUM_STEPS doubled
    (effective batch constant, LR world pinned), re-sharding from the
    topology-independent step checkpoint. The shrunken world announces
    restored capacity at a later step; the grow poller stops it and the
    full-size world resumes, re-sharding again. The post-resume loss
    trajectory and final params match an uninterrupted fixed-world run
    at f32-ULP (the accum rescale re-associates reductions — the
    documented ISSUE-3 semantics; bitwise is mathematically
    unavailable)."""
    base = [
        "--num-processes", "2",
        "--devices-per-process", "2",
        "--platform", "cpu",
        "--timeout", "540",
    ]
    env = dict(
        MODEL="lm_tiny",
        NUM_CLASSES="64",
        SEQ_LEN="16",
        COMPUTE_DTYPE="float32",
        WEIGHT_DECAY="0",
        BATCHSIZE="2",
        FAKE_DATA_LENGTH="64",   # global batch 8 -> 8 steps/epoch
        EPOCHS="2",
        ENGINE="dp",
        CHECKPOINT_ASYNC="0",
        DATA_TOPOLOGY="global",  # world-size-independent stream
    )

    def env_args(extra):
        out = []
        for k, v in {**env, **extra}.items():
            out += ["--env", f"{k}={v}"]
        return out

    # Run A: uninterrupted fixed world.
    res_a = _run_launcher(
        [*base, *env_args({"FT_PARAMS_OUT": str(tmp_path / "ref.npz")}),
         "tests/_ft_child.py"]
    )
    out_a = res_a.stdout + res_a.stderr
    assert res_a.returncode == 0, out_a[-4000:]
    losses_a = _losses(out_a)
    assert set(losses_a) == {8, 16}, out_a[-2000:]

    # Run B: the elastic drill. shrink after step 3 (mid-epoch-0),
    # capacity restored once the shrunken world completes step 6.
    res_b = _run_launcher(
        [
            *base,
            "--max-restarts", "2",
            "--restart-backoff", "0.1",
            "--elastic",
            "--min-world-size", "1",
            "--grow-check-every-s", "0.2",
            "--obs-dir", str(tmp_path / "run"),
            *env_args({
                "MODEL_DIR": str(tmp_path / "b_ckpt"),
                "CHECKPOINT_EVERY_STEPS": "1",
                "CHECKPOINT_KEEP": "30",
                "FAULT_PLAN": (
                    "shrink:step=3,rank=1,ranks=1;restore_capacity:step=6"
                ),
                "FT_PARAMS_OUT": str(tmp_path / "elastic.npz"),
            }),
            "tests/_ft_child.py",
        ]
    )
    out_b = res_b.stdout + res_b.stderr
    assert res_b.returncode == 0, out_b[-4000:]
    # the shrink was classified and the world relaunched HALVED with the
    # integer rescale announced
    assert "rc=-9, signal_SIGKILL" in out_b
    assert (
        "supervisor: elastic world 1/2 processes — BATCHSIZE 2->4, "
        "ACCUM_STEPS 1->2" in out_b
    ), out_b[-4000:]
    # the shrunken world resumed MID-epoch from the step checkpoint
    assert re.search(r"resuming from epoch 0 step [3-9]", out_b), out_b[-4000:]
    # grow-back: coordinated resize stop, full world resumed
    assert "supervisor: world resize 1 -> 2" in out_b, out_b[-4000:]
    assert "no restart budget consumed" in out_b

    # Oracle: the post-resume trajectory matches the uninterrupted run
    # at f32-ULP (final full epoch is entirely post-resume)...
    losses_b = _losses(out_b)
    assert 16 in losses_b, (losses_b, out_b[-2000:])
    np.testing.assert_allclose(
        losses_b[16], losses_a[16], rtol=1e-4, atol=1e-6
    )
    # ...and so do the final params (both ranks bitwise-agree on them
    # inside run B — the grow-back restore is bitwise-faithful).
    shas_b = _shas(out_b)
    assert set(shas_b) == {"0", "1"} and shas_b["0"] == shas_b["1"]
    ref_np = np.load(str(tmp_path / "ref.npz"))
    ela_np = np.load(str(tmp_path / "elastic.npz"))
    assert set(ref_np.files) == set(ela_np.files)
    for k in ref_np.files:
        np.testing.assert_allclose(
            ela_np[k], ref_np[k], rtol=2e-4, atol=2e-7, err_msg=k
        )
    # supervisor record carries the per-attempt world sizes
    recs = [
        json.loads(ln)
        for ln in open(tmp_path / "run" / "events-supervisor.jsonl")
    ]
    starts = [
        r["labels"]["world_size"] for r in recs
        if r.get("name") == "attempt_start"
    ]
    assert starts[:2] == [2, 1] and starts[-1] == 2, starts


def test_nan_guard_halts_supervised_world(tmp_path):
    """NaN-injected loss halts the supervised world with the distinct
    non-retryable exit code: no restart is attempted, rc is 121."""
    res = _run_launcher(
        [
            "--num-processes", "1",
            "--devices-per-process", "4",
            "--platform", "cpu",
            "--timeout", "540",
            "--max-restarts", "2",
            "--restart-backoff", "0.1",
            *_ft_env_args(
                tmp_path, "dp",
                EPOCHS="1",
                FAULT_PLAN="nan:step=2",
            ),
            "tests/_ft_child.py",
        ]
    )
    out = res.stdout + res.stderr
    assert res.returncode == 121, out[-4000:]
    assert "non-finite loss" in out
    assert "non-retryable" in out
    assert "restarting in" not in out  # the guard's code burns no restarts
