"""bench.py smoke: the driver's benchmark harness must stay runnable."""

import numpy as np
import pytest


@pytest.fixture(autouse=True)
def _cpu_by_name(monkeypatch):
    """bench.main() measures only the TPU; the one other run it accepts
    is a CPU run asked for by name, which is what this tier is."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")


def test_run_bench_smoke(mesh8):
    # knobs are explicit parameters now (main() owns the env parsing)
    import bench

    ips, n_dev, perf = bench.run_bench(2, devices=2, depth=18, image_size=16)
    assert n_dev == 2
    assert np.isfinite(ips) and ips > 0
    # sync-free accounting: compile time measured apart from the loop,
    # and the measured region syncs exactly once (the closing fence).
    assert perf["compile_sec"] > 0
    assert perf["host_sync_count"] == 1


def test_run_bench_named_model_smoke(mesh8):
    import bench

    ips, n_dev, perf = bench.run_bench(
        2, devices=2, model_name="vit_ti16", image_size=16
    )
    assert n_dev == 2
    assert np.isfinite(ips) and ips > 0
    assert perf["host_sync_count"] == 1


def test_bench_scaling_emits_efficiency(mesh8, capsys, monkeypatch):
    """BENCH_SCALING=1 must produce the scaling_efficiency field on the
    multi-device mesh — the 8→64 measurement path cannot rot before
    multi-chip hardware arrives (BASELINE >90% target)."""
    import json

    import bench

    monkeypatch.setenv("BENCH_SCALING", "1")
    monkeypatch.setenv("BENCH_BATCH", "2")
    monkeypatch.setenv("BENCH_DEPTH", "18")
    monkeypatch.setenv("BENCH_IMAGE_SIZE", "16")
    assert bench.main() == 0
    line = capsys.readouterr().out.strip().splitlines()[-1]
    out = json.loads(line)
    detail = out["detail"]
    assert "scaling_efficiency" in detail, detail
    assert 0.0 < detail["scaling_efficiency"] <= 1.5
    assert detail["images_per_sec_1_device"] > 0
    # perf-trajectory fields ride every bench line (ISSUE 1)
    assert out["compile_sec"] > 0
    assert out["host_sync_count"] >= 1


def test_bench_decode_mode(mesh8, capsys, monkeypatch):
    """BENCH_DECODE=1 emits the decode-throughput JSON line."""
    import json

    import bench

    monkeypatch.setenv("BENCH_DECODE", "1")
    monkeypatch.setenv("BENCH_MODEL", "lm_tiny")
    monkeypatch.setenv("BENCH_VOCAB", "64")
    monkeypatch.setenv("BENCH_BATCH", "2")
    monkeypatch.setenv("BENCH_PROMPT_LEN", "4")
    monkeypatch.setenv("BENCH_NEW_TOKENS", "4")
    assert bench.main() == 0
    out = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    # a CPU record names its device and is renamed: it can never be
    # read as the device metric
    assert out["metric"] == "cpu_smoke.lm_tiny_decode_tokens_per_sec"
    assert out["device"]["platform"] == "cpu" and out["device"]["count"] == 8
    assert out["value"] > 0


def test_bench_refuses_without_a_tpu(mesh8, capsys, monkeypatch):
    """JAX falls back to the CPU with a warning when it finds no
    accelerator; the benchmark must not. Unless the CPU was asked for by
    name the run exits non-zero before compiling and prints no record."""
    import bench

    monkeypatch.delenv("JAX_PLATFORMS")
    with pytest.raises(SystemExit) as exc:
        bench.main()
    assert exc.value.code not in (0, None)
    assert "no TPU" in str(exc.value.code)
    assert capsys.readouterr().out == ""


def test_bench_error_is_an_error(mesh8, capsys, monkeypatch):
    """The batch is the batch that was asked for: a failing protocol
    raises and emits nothing — no retry at a smaller size."""
    import bench

    calls = []

    def boom(per_device_batch, **kw):
        calls.append(per_device_batch)
        raise RuntimeError("RESOURCE_EXHAUSTED")

    monkeypatch.setattr(bench, "run_bench", boom)
    with pytest.raises(RuntimeError, match="RESOURCE_EXHAUSTED"):
        bench.main()
    assert calls == [256]
    assert capsys.readouterr().out == ""


def test_recertify_run_protocol_tolerates_partial_json(monkeypatch):
    """ADVICE r5: a killed child can leave a partial '{'-prefixed stdout
    line; the battery must record a failed row, not abort on
    JSONDecodeError — and a failed row is run once, not retried."""
    import subprocess
    import types

    from scripts import recertify

    runs = []

    def fake_run(cmd, env=None, timeout=None, capture_output=None, text=None):
        runs.append(cmd)
        return types.SimpleNamespace(
            stdout='garbage\n{"metric": "x", "value": 3.0, truncated',
            stderr="", returncode=1,
        )

    monkeypatch.setattr(subprocess, "run", fake_run)
    rec = recertify.run_protocol("resnet50", {"BENCH_BATCH": "1"}, 5.0)
    assert "unparseable JSON" in rec["error"]
    assert len(runs) == 1


def test_recertify_serve_row_dispatches_to_serve_bench(monkeypatch):
    """The serve_lm protocol runs scripts/serve_bench.py (its own
    entrypoint, not a bench.py mode) and ambient SERVE_* protocol vars
    are scrubbed before the row's own env applies."""
    import subprocess
    import types

    from scripts import recertify

    seen = {}

    def fake_run(cmd, env=None, timeout=None, capture_output=None, text=None):
        seen["cmd"] = cmd
        seen["env"] = dict(env or {})
        return types.SimpleNamespace(
            stdout='{"metric": "serve_continuous_tokens_per_sec", '
                   '"value": 5.0}',
            stderr="", returncode=0,
        )

    monkeypatch.setattr(subprocess, "run", fake_run)
    monkeypatch.setenv("SERVE_SLOTS", "99")  # ambient leak attempt
    rec = recertify.run_protocol(
        "serve_lm", recertify.PROTOCOLS["serve_lm"], 5.0
    )
    assert rec["value"] == 5.0
    assert seen["cmd"][-1].endswith("scripts/serve_bench.py")
    assert seen["env"]["SERVE_SLOTS"] == "8"  # the row's value, not 99
    assert "_script" not in seen["env"]
    assert recertify.PROTOCOLS["serve_lm"]["_script"]  # source not mutated

    # every other row still runs bench.py
    recertify.run_protocol("resnet50", {"BENCH_BATCH": "1"}, 5.0)
    assert seen["cmd"][-1].endswith("bench.py")


def test_recertify_survives_a_tree_without_git(tmp_path, monkeypatch):
    """The chip tool's copy of the repo has no .git: the battery still
    runs there, its rows stamped with an empty commit."""
    from scripts import recertify

    monkeypatch.setattr(recertify, "REPO", str(tmp_path))
    assert recertify.head_commit() == ""
    assert recertify.lint_verdict("") == {"missing": True}


def test_chip_smoke_refuses_without_a_tpu():
    """chip_smoke.py is the on-chip proof: off the chip it exits
    non-zero in seconds and prints nothing that looks like a result."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, os.path.join(repo, "chip_smoke.py")],
        capture_output=True, text=True, timeout=120, cwd=repo,
        env={**os.environ, "JAX_PLATFORMS": "cpu"},
    )
    assert r.returncode != 0, (r.stdout, r.stderr)
    assert "no TPU" in r.stderr
    assert not any(ln.startswith("{") for ln in r.stdout.splitlines())
    assert '"ok"' not in r.stdout


def test_chip_smoke_last_line_is_the_verdict(monkeypatch, capsys):
    """The driver's chip check reads the last stdout line: one JSON object
    with exactly ``ok`` and ``device`` (platform, kind, count). The
    per-phase summary is the line before it, never part of it."""
    import json

    import chip_smoke

    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "run", lambda: {
        "ok": True, "device": device, "phases": {"p": {"compile_sec": 1.0}},
        "claim": None,
    })
    assert chip_smoke.main() == 0
    lines = capsys.readouterr().out.splitlines()
    assert json.loads(lines[-1]) == {"ok": True, "device": device}
    assert lines[-2].startswith("chip_smoke: summary {")
    assert lines[-2].endswith('"claim": null}')
