"""Launcher tests — including TRUE multi-process (2 OS processes) runs.

The reference's distributed logic is smoke-tested by ``mpirun -np 2 -H
localhost:2`` inside the framework container (``Horovod*/00_CreateImage
AndTest.ipynb`` cells 6-10, SURVEY.md §4.2). These tests do the same for
the TPU build: ``launch.py --num-processes 2`` forks two real python
processes that rendezvous via ``jax.distributed.initialize`` on a forced
CPU backend and execute the genuinely multi-host code paths
(``make_array_from_process_local_data``, ``broadcast_one_to_all``,
per-process TFRecord sharding) that the in-process 8-device suite cannot.
"""

import io
import json
import os
import subprocess
import sys
import textwrap
import time

import numpy as np
import pytest

from distributeddeeplearning_tpu.launch import (
    _child_env,
    _parse_env_args,
    _require_host_device_world,
    build_pod_command,
    find_free_port,
    launch_local,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ---------------------------------------------------------------------------
# Unit: command construction
# ---------------------------------------------------------------------------

def test_find_free_port():
    p = find_free_port()
    assert isinstance(p, int) and 0 < p < 65536


def test_parse_env_args():
    assert _parse_env_args(["A=1", "B=x=y"]) == {"A": "1", "B": "x=y"}
    with pytest.raises(SystemExit):
        _parse_env_args(["NOEQUALS"])


def test_child_env_contract():
    env = _child_env(
        {"XLA_FLAGS": "--xla_force_host_platform_device_count=8 --foo"},
        coordinator="127.0.0.1:1234",
        num_processes=2,
        process_id=1,
        platform="cpu",
        devices_per_process=4,
        extra_env={"FAKE": "True"},
    )
    assert env["DDL_COORDINATOR"] == "127.0.0.1:1234"
    assert env["DDL_NUM_PROCESSES"] == "2"
    assert env["DDL_PROCESS_ID"] == "1"
    assert env["JAX_PLATFORMS"] == "cpu"
    assert env["FAKE"] == "True"
    # stale forced-device-count flag replaced, other flags kept
    assert env["XLA_FLAGS"].count("--xla_force_host_platform_device_count") == 1
    assert "--xla_force_host_platform_device_count=4" in env["XLA_FLAGS"]
    assert "--foo" in env["XLA_FLAGS"]


def test_local_multiprocess_world_requires_cpu_platform(monkeypatch):
    """A chip belongs to one process: several local children that may
    each claim the host's TPUs are refused with a message, before any
    process starts — never a world that hangs."""
    monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    with pytest.raises(SystemExit, match="refusing to start 2 local"):
        launch_local("never_started.py", num_processes=2)
    with pytest.raises(SystemExit, match="refusing"):
        launch_local("never_started.py", num_processes=2, platform="tpu")
    # asked for by name, by any of the three routes: allowed
    _require_host_device_world(2, "cpu", {})
    _require_host_device_world(2, None, {"JAX_PLATFORMS": "cpu"})
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    _require_host_device_world(2, None, {})
    # one process drives every chip of the host: nothing to refuse
    monkeypatch.delenv("JAX_PLATFORMS")
    _require_host_device_world(1, None, {})


def test_build_pod_command():
    cmd = build_pod_command(
        "examples/imagenet_keras_tpu.py",
        ["--flag"],
        tpu="v5e-64-pod",
        zone="us-west4-a",
        project="proj",
        env={"FAKE": "True"},
    )
    joined = " ".join(cmd)
    assert cmd[:5] == ["gcloud", "compute", "tpus", "tpu-vm", "ssh"]
    assert "v5e-64-pod" in cmd
    assert "--worker=all" in cmd
    assert "--project=proj" in joined
    # remote command exports DISTRIBUTED=True (autodetect path) + user env
    remote = [c for c in cmd if c.startswith("--command=")][0]
    assert "DISTRIBUTED=True" in remote
    assert "FAKE=True" in remote
    assert "python3 -u examples/imagenet_keras_tpu.py" in remote


# ---------------------------------------------------------------------------
# Integration: real 2-process worlds
# ---------------------------------------------------------------------------

def _write_tfrecords(out_dir: str, n_files: int = 4, per_file: int = 8) -> str:
    """Write tiny JPEG TFRecord shards with globally-unique labels 0..N-1."""
    import tensorflow as tf
    from PIL import Image

    label = 0
    for f in range(n_files):
        path = os.path.join(out_dir, f"train-{f:05d}.tfrecord")
        with tf.io.TFRecordWriter(path) as w:
            for _ in range(per_file):
                arr = np.random.RandomState(label).randint(
                    0, 255, (8, 8, 3), np.uint8
                )
                buf = io.BytesIO()
                Image.fromarray(arr).save(buf, format="JPEG")
                ex = tf.train.Example(
                    features=tf.train.Features(
                        feature={
                            "image/encoded": tf.train.Feature(
                                bytes_list=tf.train.BytesList(value=[buf.getvalue()])
                            ),
                            "image/class/label": tf.train.Feature(
                                int64_list=tf.train.Int64List(value=[label])
                            ),
                        }
                    )
                )
                w.write(ex.SerializeToString())
                label += 1
    return os.path.join(out_dir, "train-*.tfrecord")


def _run_launcher(args, timeout=600):
    return subprocess.run(
        [sys.executable, "launch.py", *args],
        cwd=REPO_ROOT,
        capture_output=True,
        text=True,
        timeout=timeout,
    )


def test_two_process_world(tmp_path):
    """2 OS processes: rendezvous, collectives, global-array DP step,
    per-process TFRecord sharding — the mpirun -np 2 smoke equivalent."""
    pattern = _write_tfrecords(str(tmp_path))
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--devices-per-process", "4",
            "--platform", "cpu",
            "--timeout", "540",
            "tests/_mp_child.py", pattern,
        ]
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "MP_CHILD_OK 0" in out, out[-4000:]
    assert "MP_CHILD_OK 1" in out, out[-4000:]
    assert "[0] " in out and "[1] " in out  # rank-tagged streaming


def test_two_process_keras_frontend_end_to_end():
    """The VERDICT done-criterion: launch.py -n 2 trains the Keras-style
    front-end example on one host (synthetic data, tiny shapes)."""
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--devices-per-process", "4",
            "--platform", "cpu",
            "--timeout", "540",
            "--env", "FAKE=True",
            "--env", "FAKE_DATA_LENGTH=128",
            "--env", "EPOCHS=1",
            "--env", "BATCHSIZE=4",
            "--env", "IMAGE_SIZE=32",
            "--env", "NUM_CLASSES=8",
            "--env", "MODEL=resnet18",
            "examples/imagenet_keras_tpu.py",
        ]
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "images/sec" in out, out[-4000:]


def test_child_failure_terminates_world(tmp_path):
    """All-or-nothing exit semantics: one failing rank kills the job
    promptly (no hang waiting on the healthy rank's sleep)."""
    script = tmp_path / "failer.py"
    script.write_text(
        textwrap.dedent(
            """
            import os, sys, time
            if os.environ["DDL_PROCESS_ID"] == "1":
                sys.exit(3)
            time.sleep(120)
            """
        )
    )
    res = _run_launcher(
        ["--num-processes", "2", "--timeout", "90", str(script)], timeout=110
    )
    assert res.returncode == 3, (res.returncode, res.stdout[-2000:])


def test_dry_run_modes():
    res = _run_launcher(["--dry-run", "-n", "4", "script.py"])
    assert res.returncode == 0 and "4 local processes" in res.stdout
    res = _run_launcher(
        ["--tpu", "pod", "--zone", "us-west4-a", "--dry-run", "script.py"]
    )
    assert res.returncode == 0
    assert "gcloud compute tpus tpu-vm ssh" in res.stdout
    assert "--worker=all" in res.stdout


def test_hang_watchdog_kills_silent_world(tmp_path):
    """Failure detection the reference lacks: a world whose processes are
    alive but silent (deadlocked collective) is declared hung and killed
    with exit 125."""
    script = tmp_path / "hang.py"
    script.write_text(
        "import time\nprint('alive', flush=True)\ntime.sleep(300)\n"
    )
    t0 = time.time()
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--hang-timeout", "4",
            "--timeout", "120",
            str(script),
        ],
        timeout=90,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 125, out[-2000:]
    assert "declaring the world hung" in out, out[-2000:]
    assert time.time() - t0 < 60  # watchdog fired, not the 120s timeout


# ---------------------------------------------------------------------------
# Observability: --obs-dir events, host-0 merge, flight recorder (ISSUE 2)
# ---------------------------------------------------------------------------

_OBS_CHILD = textwrap.dedent(
    """
    import json, os, sys, time
    from distributeddeeplearning_tpu import obs

    bus = obs.configure_from_env()
    rank = os.environ["DDL_PROCESS_ID"]
    with bus.span("work", rank=rank):
        time.sleep(0.05)
    bus.counter("things", 3)
    bus.flush()
    bus.point("unflushed_tail")  # ring-only: the flight dump's proof
    print("OBS_CHILD_OK", rank, flush=True)
    if rank == "1" and os.environ.get("HANG"):
        time.sleep(300)  # silent: the watchdog must kill us
    """
)


def test_obs_run_produces_merged_events_and_report(tmp_path):
    """The ISSUE 2 done-criterion: a 2-OS-process launch.py run writes
    per-process events.jsonl, the launcher (host 0) merges them, and
    scripts/obs_report.py renders a report from the run dir."""
    script = tmp_path / "obs_child.py"
    script.write_text(_OBS_CHILD)
    obs_dir = tmp_path / "run1"
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--obs-dir", str(obs_dir),
            "--timeout", "120",
            "--env", "JAX_PLATFORMS=cpu",
            str(script),
        ],
        timeout=180,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "OBS_CHILD_OK 0" in out and "OBS_CHILD_OK 1" in out
    # per-process event files + the launcher's own lifecycle file
    assert (obs_dir / "events-p0.jsonl").exists()
    assert (obs_dir / "events-p1.jsonl").exists()
    assert (obs_dir / "events-launcher.jsonl").exists()
    # host-0 merge ran at world exit
    merged = obs_dir / "events.jsonl"
    assert merged.exists(), out[-2000:]
    recs = [json.loads(ln) for ln in open(merged)]
    metas = [r for r in recs if r["kind"] == "meta"]
    assert {str(m["p"]) for m in metas} == {"0", "1", "launcher"}
    # one shared run id across the whole world (launcher-minted)
    assert len({m["run"] for m in metas}) == 1
    names = {r["name"] for r in recs if r["kind"] != "meta"}
    assert {"rendezvous", "child_start", "child_exit", "world_exit",
            "work", "things"} <= names
    walls = [r["wall"] for r in recs if "wall" in r]
    assert walls == sorted(walls)  # one consistent timeline

    # ...and the report CLI renders it
    rep = subprocess.run(
        [sys.executable, "scripts/obs_report.py", str(obs_dir)],
        cwd=REPO_ROOT, capture_output=True, text=True, timeout=120,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert rep.returncode == 0, rep.stdout + rep.stderr
    assert "work" in rep.stdout and "timeline" in rep.stdout


def test_watchdog_accepts_telemetry_as_liveness(tmp_path):
    """Live-plane liveness (ISSUE 7): a process that prints NOTHING but
    keeps emitting bus events (flushed by OBS_FLUSH_EVERY_S) must not be
    declared hung — the watchdog consumes event-file growth as a
    heartbeat. The control case (same silence, no events) is
    test_hang_watchdog_kills_silent_world."""
    script = tmp_path / "silent_worker.py"
    script.write_text(textwrap.dedent(
        """
        import time
        from distributeddeeplearning_tpu import obs

        bus = obs.configure_from_env()
        for i in range(45):          # ~18s of stdout silence
            bus.point("tick", i=i)
            time.sleep(0.4)
        bus.flush()
        """
    ))
    obs_dir = tmp_path / "run-liveness"
    res = _run_launcher(
        [
            "--num-processes", "1",
            "--obs-dir", str(obs_dir),
            # > the child's import time (3 s idle, several times that
            # beside five other test workers), < its runtime
            "--hang-timeout", "15",
            "--timeout", "120",
            "--env", "JAX_PLATFORMS=cpu",
            "--env", "OBS_FLUSH_EVERY_S=0.5",
            str(script),
        ],
        timeout=180,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "declaring the world hung" not in out


def test_obs_killed_child_leaves_flight_dump(tmp_path):
    """Watchdog kill (SIGTERM) = preemption rehearsal: the hung child's
    flight-recorder ring reaches disk with its last events — including
    ones never flushed to the normal file — and the launcher records
    the watchdog fire; merge still happens on the failure path."""
    script = tmp_path / "obs_child.py"
    script.write_text(_OBS_CHILD)
    obs_dir = tmp_path / "run2"
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--obs-dir", str(obs_dir),
            # long enough for both children to import the package and
            # arm the flight recorder before the watchdog's SIGTERM
            "--hang-timeout", "15",
            "--timeout", "120",
            "--env", "JAX_PLATFORMS=cpu",
            "--env", "HANG=1",
            str(script),
        ],
        timeout=180,
    )
    out = res.stdout + res.stderr
    assert res.returncode == 125, out[-4000:]
    dump = obs_dir / "flight-p1.jsonl"
    assert dump.exists(), out[-2000:]
    recs = [json.loads(ln) for ln in open(dump)]
    assert recs[0]["kind"] == "flight_meta"
    assert recs[0]["reason"] == "sigterm"
    names = [r["name"] for r in recs[1:]]
    assert "work" in names
    assert "unflushed_tail" in names  # the ring caught the unflushed tail
    # launcher-side record of WHY the world died, merged and all
    launcher_events = [
        json.loads(ln) for ln in open(obs_dir / "events-launcher.jsonl")
    ]
    assert any(r.get("name") == "watchdog_fired" for r in launcher_events)
    assert (obs_dir / "events.jsonl").exists()


@pytest.mark.parametrize(
    "engine_env",
    [
        ("sp", [("MESH_AXES", "data,seq"), ("MESH_SHAPE", "2,4")]),
        ("pp", [("MESH_AXES", "data,pipe"), ("MESH_SHAPE", "2,4"),
                ("PP_MICROBATCHES", "2"), ("PP_SCHEDULE", "1f1b")]),
    ],
    ids=["sp", "pp-1f1b"],
)
def test_two_process_engine_contract(engine_env):
    """ENGINE=sp / ENGINE=pp across 2 REAL OS processes: the ring/pipe
    ppermute hops cross the process boundary over the distributed
    backend — the multi-host story for the round-3 engine contract."""
    engine, extra = engine_env
    env_args = []
    for k, v in [("FAKE_DATA_LENGTH", "64"), ("EPOCHS", "1"),
                 ("BATCHSIZE", "2"), ("SEQ_LEN", "16"), ("VOCAB", "64"),
                 ("MODEL", "lm_tiny"), ("ENGINE", engine), *extra]:
        env_args += ["--env", f"{k}={v}"]
    res = _run_launcher(
        [
            "--num-processes", "2",
            "--devices-per-process", "4",
            "--platform", "cpu",
            "--timeout", "540",
            *env_args,
            "examples/lm_synthetic_tpu.py",
        ]
    )
    out = res.stdout + res.stderr
    assert res.returncode == 0, out[-4000:]
    assert "images/sec" in out, out[-4000:]
