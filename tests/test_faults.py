"""Fault-tolerance fast battery: plan grammar, exit taxonomy, the
restart supervisor (jax-light e2e in the ``test_launch.py`` style), the
compile heartbeat, and the on-device non-finite guard.

The heavy resume-equivalence oracles (real training, 2-OS-process
worlds, bitwise param equality across a SIGKILL + supervisor resume)
live in ``tests/test_fault_tolerance.py``; this file is the
seconds-not-minutes tier that runs on every ``make fault-suite``.
"""

import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from distributeddeeplearning_tpu import faults
from distributeddeeplearning_tpu.config import TrainConfig

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Unit: fault-plan grammar
# ---------------------------------------------------------------------------

def test_parse_fault_plan_grammar():
    plan = faults.parse_fault_plan(
        "kill:step=3,rank=1; term:step=5 ;hang:step=4,secs=9.5;"
        "nan:step=2;exit:step=6,code=121"
    )
    kinds = [f.kind for f in plan]
    assert kinds == ["kill", "term", "hang", "nan", "exit"]
    assert plan[0] == faults.Fault(kind="kill", step=3, rank=1)
    assert plan[1].rank is None  # no rank = every process
    assert plan[2].secs == 9.5
    assert plan[4].code == 121
    assert faults.parse_fault_plan("") == []


@pytest.mark.parametrize(
    "bad",
    [
        "explode:step=1",        # unknown kind
        "kill:rank=1",           # missing step
        "kill:step=0",           # steps are 1-based completed counts
        "kill:step=1,when=now",  # unknown key
        "kill:step",             # not key=value
    ],
)
def test_parse_fault_plan_rejects(bad):
    with pytest.raises(ValueError):
        faults.parse_fault_plan(bad)


def test_injector_rank_filtering_and_one_shot():
    plan = faults.parse_fault_plan("exit:step=2,rank=1;nan:step=3")
    inj0 = faults.FaultInjector(plan, rank=0)
    # rank-1 exit filtered out; the rankless nan stays
    assert not inj0.due_after(2)
    assert [f.kind for f in inj0.pending] == ["nan"]
    # nan faults never terminate — due_after ignores them
    assert not inj0.due_after(3)
    # poison fires once, then disarms
    batch = (np.ones((2, 2), np.float32), np.zeros((2,), np.int32))
    poisoned = inj0.poison(3, batch)
    assert np.isnan(np.asarray(poisoned[0])).all()
    assert np.asarray(poisoned[1]).dtype == np.int32  # ints untouched
    again = inj0.poison(3, batch)
    assert not np.isnan(np.asarray(again[0])).any()


def test_injector_from_env(monkeypatch):
    monkeypatch.delenv("FAULT_PLAN", raising=False)
    assert faults.FaultInjector.from_env() is None
    monkeypatch.setenv("FAULT_PLAN", "kill:step=3,rank=1")
    monkeypatch.setenv("DDL_PROCESS_ID", "0")
    assert faults.FaultInjector.from_env() is None  # targets rank 1 only
    monkeypatch.setenv("DDL_PROCESS_ID", "1")
    inj = faults.FaultInjector.from_env()
    assert inj is not None and inj.due_after(3)


# ---------------------------------------------------------------------------
# Unit: exit-code taxonomy
# ---------------------------------------------------------------------------

def test_exit_code_taxonomy():
    assert not faults.classify_exit(0).retryable
    assert not faults.classify_exit(faults.EXIT_NONFINITE).retryable
    assert not faults.classify_exit(faults.EXIT_TIMEOUT).retryable
    assert not faults.classify_exit(faults.EXIT_INTERRUPTED).retryable
    assert faults.classify_exit(faults.EXIT_HUNG).retryable
    assert faults.classify_exit(1).retryable
    kill = faults.classify_exit(-9)
    assert kill.retryable and kill.reason == "signal_SIGKILL"
    assert faults.normalize_rc(-9) == 137
    assert faults.normalize_rc(faults.EXIT_NONFINITE) == 121


def test_faultgen_cli_validate_and_exit_codes():
    env = {**os.environ, "JAX_PLATFORMS": "cpu"}
    res = subprocess.run(
        [sys.executable, "scripts/faultgen.py", "validate",
         "kill:step=3,rank=1;hang:step=2,secs=5"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120, env=env,
    )
    assert res.returncode == 0, res.stderr
    assert "kill" in res.stdout and "process 1" in res.stdout
    assert "for 5s" in res.stdout
    bad = subprocess.run(
        [sys.executable, "scripts/faultgen.py", "validate", "boom:step=1"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120, env=env,
    )
    assert bad.returncode == 2 and "invalid FAULT_PLAN" in bad.stderr
    codes = subprocess.run(
        [sys.executable, "scripts/faultgen.py", "exit-codes"],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120, env=env,
    )
    assert codes.returncode == 0
    assert "nonfinite_loss" in codes.stdout
    assert "signal_SIGKILL" in codes.stdout


def test_config_robustness_env_contract():
    cfg = TrainConfig.from_env({
        "CHECKPOINT_EVERY_STEPS": "25",
        "CHECKPOINT_ASYNC": "0",
        "RESUME": "false",
        "NONFINITE_ACTION": "warn",
    })
    assert cfg.checkpoint_every_steps == 25
    assert cfg.checkpoint_async is False
    assert cfg.resume is False
    assert cfg.nonfinite_action == "warn"
    # defaults: epoch-granular, async, resume on, guard aborting
    d = TrainConfig.from_env({})
    assert d.checkpoint_every_steps == 0
    assert d.checkpoint_async is True and d.resume is True
    assert d.nonfinite_action == "abort"
    from distributeddeeplearning_tpu.training.loop import resolve_engine

    with pytest.raises(ValueError, match="NONFINITE_ACTION"):
        resolve_engine(d.replace(nonfinite_action="panic"))
    with pytest.raises(ValueError, match="CHECKPOINT_EVERY_STEPS"):
        resolve_engine(d.replace(checkpoint_every_steps=-1))


# ---------------------------------------------------------------------------
# E2e: restart supervisor over jax-light worlds (test_launch.py style)
# ---------------------------------------------------------------------------

def _run_launcher(args, timeout=600):
    return subprocess.run(
        [sys.executable, "launch.py", *args],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=timeout,
    )


def test_supervisor_restarts_after_sigkill_and_resumes(tmp_path):
    """The crash → classify → backoff → relaunch → resume cycle: SIGKILL
    of process 1 after step 3 kills the world; the supervisor restarts
    it with resume enabled and the relaunched rank continues from its
    persisted progress instead of step 0."""
    obs_dir = tmp_path / "run"
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--max-restarts", "2",
            "--restart-backoff", "0.1",
            "--timeout", "120",
            "--obs-dir", str(obs_dir),
            "--env", "JAX_PLATFORMS=cpu",
            "--env", "FAULT_PLAN=kill:step=3,rank=1",
            "--env", f"STATE_FILE={tmp_path}/state",
            "tests/_fault_child.py",
        ],
        timeout=300,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "supervisor: attempt 0 failed (rc=-9, signal_SIGKILL" in out
    assert "restarting in 0.1s" in out
    # the relaunched rank resumed from its persisted step, not from 0
    assert "FAULT_CHILD_DONE 1 start=3" in out, out[-4000:]
    assert "FAULT_CHILD_DONE 0" in out
    # black box: SIGKILL cannot be handled, so the injector dumped the
    # ring itself before dying
    dump = obs_dir / "flight-p1.jsonl"
    assert dump.exists(), out[-2000:]
    head = json.loads(open(dump).readline())
    assert head["reason"] == "fault_kill"
    # per-attempt file identity: the restart did not truncate attempt 0
    assert (obs_dir / "events-p1.jsonl").exists()
    assert (obs_dir / "events-p1-r1.jsonl").exists()
    assert (obs_dir / "events-supervisor.jsonl").exists()
    # one merged timeline across both attempts + the supervisor
    recs = [json.loads(ln) for ln in open(obs_dir / "events.jsonl")]
    names = {r.get("name") for r in recs}
    assert {"attempt_start", "attempt_exit", "restart_scheduled",
            "fault_fired", "world_exit"} <= names
    assert len({r["run"] for r in recs if r.get("kind") == "meta"}) == 1
    # ...and the report renders the failure timeline
    rep = subprocess.run(
        [sys.executable, "scripts/obs_report.py", str(obs_dir)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "restart_scheduled" in rep.stdout
    assert "supervisor" in rep.stdout


def test_supervisor_treats_nonfinite_exit_as_terminal(tmp_path):
    """Exit 121 (the NaN guard's code) must NOT burn restarts: the run
    is deterministic, so a resume replays the same NaN."""
    res = _run_launcher(
        [
            "--num-processes", "1",
            "--max-restarts", "3",
            "--restart-backoff", "0.1",
            "--timeout", "120",
            "--env", "JAX_PLATFORMS=cpu",
            "--env", "FAULT_PLAN=exit:step=2,code=121",
            "tests/_fault_child.py",
        ],
        timeout=300,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 121, out[-2000:]
    assert "non-retryable" in out
    assert "restarting in" not in out  # zero restart attempts


def test_supervisor_recovers_watchdog_killed_hang(tmp_path):
    """Hang → watchdog kill (125) → classified retryable → relaunch →
    resume past the hang step → clean exit. The watchdog counts from the
    launch, and ``--hang-timeout`` holds for the restarted child too: it
    must fit a process start (importing the package takes 3 s on an
    idle machine, more beside five other test workers), or the watchdog
    kills the recovery it is testing. The injected hang is 300 s."""
    res = _run_launcher(
        [
            "--num-processes", "1",
            "--max-restarts", "1",
            "--restart-backoff", "0.1",
            "--hang-timeout", "15",
            "--timeout", "120",
            "--env", "JAX_PLATFORMS=cpu",
            "--env", "FAULT_PLAN=hang:step=2,secs=300",
            "--env", f"STATE_FILE={tmp_path}/state",
            "tests/_fault_child.py",
        ],
        timeout=300,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "declaring the world hung" in out
    assert "rc=125, world_hung" in out
    assert "FAULT_CHILD_DONE 0 start=2" in out  # resumed past the hang


def test_supervisor_restart_budget_exhausts(tmp_path):
    """A fault that recurs on every attempt (no state file -> no resume,
    the kill step is re-hit) drains max-restarts and surfaces the
    normalized (128+sig) final code."""
    res = _run_launcher(
        [
            "--num-processes", "1",
            "--max-restarts", "1",
            "--restart-backoff", "0.1",
            "--timeout", "120",
            "--env", "JAX_PLATFORMS=cpu",
            "--env", "FAULT_PLAN=kill:step=2",
            "tests/_fault_child.py",
        ],
        timeout=300,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 137, out[-2000:]  # 128 + SIGKILL
    assert "restart budget exhausted (1)" in out


# ---------------------------------------------------------------------------
# E2e: compile heartbeat vs the hang watchdog
# ---------------------------------------------------------------------------

_HB_CHILD = textwrap.dedent(
    """
    import time
    from distributeddeeplearning_tpu.utils import heartbeat
    print("alive", flush=True)
    with heartbeat.during("aot_compile"):
        time.sleep(20)  # silent-but-compiling: used to be watchdog bait
    print("HB_CHILD_OK", flush=True)
    """
)


def test_heartbeat_keeps_compiling_world_alive(tmp_path):
    """A 20s-silent 'compile' under a 12s hang watchdog survives because
    the launcher exports DDL_HEARTBEAT_EVERY_S and counts the magic
    lines as liveness — while keeping them out of the streamed log. (12
    s, not 3: the watchdog counts from the launch, and the child's
    imports alone take 3 s on an idle machine.)"""
    script = tmp_path / "hb.py"
    script.write_text(_HB_CHILD)
    res = _run_launcher(
        [
            "--num-processes", "1",
            "--hang-timeout", "12",
            "--timeout", "120",
            "--env", "JAX_PLATFORMS=cpu",
            str(script),
        ],
        timeout=300,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "HB_CHILD_OK" in out
    from distributeddeeplearning_tpu.utils.heartbeat import MAGIC

    assert MAGIC not in out  # liveness lines never reach the log


def test_heartbeat_unit(monkeypatch):
    """during() is a no-op when disarmed and pumps MAGIC lines into its
    sink when armed."""
    import io
    import time

    from distributeddeeplearning_tpu.utils import heartbeat

    monkeypatch.delenv(heartbeat.ENV_VAR, raising=False)
    sink = io.StringIO()
    with heartbeat.during("x", sink=sink):
        time.sleep(0.1)
    assert sink.getvalue() == ""  # disarmed

    sink = io.StringIO()
    with heartbeat.during("compile", interval_s=0.02, sink=sink):
        time.sleep(0.15)
    lines = sink.getvalue().splitlines()
    assert len(lines) >= 3
    assert all(ln.startswith(heartbeat.MAGIC) for ln in lines)
    assert "compile" in lines[0]
    n = len(lines)
    time.sleep(0.1)  # thread must stop at context exit
    assert len(sink.getvalue().splitlines()) == n


# ---------------------------------------------------------------------------
# In-process: the on-device non-finite guard
# ---------------------------------------------------------------------------

def _guard_cfg(**kw):
    base = dict(
        model="resnet18",
        num_classes=8,
        image_size=8,
        batch_size_per_device=2,
        fake_data_length=32,
        epochs=1,
        compute_dtype="float32",
        log_every_steps=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def _guard_fit(cfg, mesh8):
    from distributeddeeplearning_tpu.data.synthetic import (
        SyntheticImageDataset,
    )
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training import loop

    data = SyntheticImageDataset(
        length=cfg.fake_data_length,
        global_batch_size=cfg.global_batch_size,
        image_size=cfg.image_size,
        num_classes=cfg.num_classes,
    )
    model = get_model("resnet18", num_classes=8, dtype="float32")
    return loop.fit(model, cfg, data, mesh=mesh8, add_default_logger=False)


def test_nonfinite_guard_aborts_with_distinct_exit_code(
    mesh8, monkeypatch
):
    """FAULT_PLAN NaN injection -> the accumulator's on-device counter
    trips at the epoch boundary -> NonFiniteLossError carrying exit 121
    (SystemExit subclass: an uncaught escape exits the process with the
    supervisor's non-retryable code)."""
    monkeypatch.setenv("FAULT_PLAN", "nan:step=1")
    monkeypatch.delenv("DDL_PROCESS_ID", raising=False)
    with pytest.raises(faults.NonFiniteLossError) as ei:
        _guard_fit(_guard_cfg(), mesh8)
    assert ei.value.code == faults.EXIT_NONFINITE
    assert isinstance(ei.value, SystemExit)
    assert ei.value.nonfinite_steps >= 1


def test_nonfinite_guard_warn_mode_continues(mesh8, monkeypatch):
    monkeypatch.setenv("FAULT_PLAN", "nan:step=1")
    monkeypatch.delenv("DDL_PROCESS_ID", raising=False)
    res = _guard_fit(_guard_cfg(nonfinite_action="warn"), mesh8)
    assert math.isnan(res.history[0]["loss"])
    # the guard's count never leaks into user-facing history
    assert "nonfinite_steps" not in res.history[0]


def test_guard_costs_zero_extra_syncs(mesh8):
    """The acceptance invariant: with the guard armed (default abort
    mode), the loop still performs exactly one host materialisation per
    epoch — detection rides the existing epoch sync."""
    from distributeddeeplearning_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training import loop
    from distributeddeeplearning_tpu.utils import hostsync

    cfg = TrainConfig(
        model="lm_tiny", num_classes=64, batch_size_per_device=2,
        fake_data_length=32, epochs=2, compute_dtype="float32",
        weight_decay=0.0, log_every_steps=0, nonfinite_action="abort",
    )
    data = SyntheticTokenDataset(
        length=cfg.fake_data_length,
        global_batch_size=cfg.global_batch_size,
        seq_len=16, vocab_size=64,
    )
    model = get_model(
        "lm_tiny", num_classes=64, dtype="float32", max_seq_len=16
    )
    hostsync.accountant().reset()
    with hostsync.track():
        res = loop.fit(
            model, cfg, data, mesh=mesh8, add_default_logger=False
        )
    acct = hostsync.accountant()
    assert acct.count == cfg.epochs, acct.by_label
    assert res.perf["host_sync_count"] == cfg.epochs
    assert math.isfinite(res.history[-1]["loss"])  # guard stayed quiet
