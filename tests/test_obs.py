"""Event bus / flight recorder / trace capture / report oracles (ISSUE 2).

CPU-tier provable invariants:

* the bus writes schema-correct JSONL (meta first, monotonic t, run id,
  process identity) and the ring stays bounded;
* a SIGTERM'd / crashing process leaves a flight-recorder dump with its
  last N events — even events never flushed to the normal file;
* merge aligns multi-process files onto one wall clock; the report
  computes span percentiles, sync counts by label, and skew;
* the training loop emits through the bus with ZERO extra host syncs
  (asserted in test_sync_free_loop.py with the bus enabled);
* the trace controller starts/stops captures on the epoch boundary only
  (periodic + on-demand).
"""

import json
import os
import signal
import subprocess
import sys
import textwrap
import time
import types

import pytest

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.obs import report as obs_report
from distributeddeeplearning_tpu.obs import trace as obs_trace
from distributeddeeplearning_tpu.obs.bus import EventBus

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_bus():
    """Never leak a configured global bus (or crash handlers) across
    tests."""
    obs.reset()
    yield
    obs.reset()


# ---------------------------------------------------------------------------
# Bus unit
# ---------------------------------------------------------------------------

def test_bus_writes_schema_jsonl(tmp_path):
    bus = EventBus(directory=str(tmp_path), proc=3, run_id="r-test")
    with bus.span("epoch", epoch=0):
        bus.span_event("step", 0.004, epoch=0)
        bus.counter("host_sync", 1, label="epoch_metrics")
        bus.gauge("epoch.loss", 1.25, epoch=0)
        bus.point("run_end")
    bus.flush()
    lines = [json.loads(ln) for ln in open(bus.path)]
    meta, events = lines[0], lines[1:]
    assert meta["kind"] == "meta" and meta["run"] == "r-test"
    assert meta["p"] == 3 and meta["pid"] == os.getpid()
    assert "mono0" in meta and "wall0" in meta
    assert [e["kind"] for e in events] == [
        "span", "counter", "gauge", "point", "span",
    ]  # the enclosing span lands at exit, after its contents
    by_name = {e["name"]: e for e in events}
    assert by_name["step"]["dur"] == pytest.approx(0.004)
    assert by_name["host_sync"]["labels"] == {"label": "epoch_metrics"}
    assert by_name["epoch"]["dur"] >= 0
    # monotonic timestamps, per-process sequence numbers
    assert all(e["p"] == 3 for e in events)
    assert [e["seq"] for e in events] == sorted(e["seq"] for e in events)


def test_ring_is_bounded_and_keeps_latest():
    bus = EventBus(ring_size=16)  # ring-only: no directory
    for i in range(100):
        bus.point("tick", i=i)
    assert len(bus.ring) == 16
    assert [r["labels"]["i"] for r in bus.ring] == list(range(84, 100))
    assert bus.path is None  # nothing on disk


def test_flight_dump_contains_last_n_events(tmp_path):
    bus = EventBus(directory=str(tmp_path), proc=0, ring_size=8)
    for i in range(50):
        bus.point("tick", i=i)
    path = bus.dump_flight("unit-test")
    lines = [json.loads(ln) for ln in open(path)]
    assert lines[0]["kind"] == "flight_meta"
    assert lines[0]["reason"] == "unit-test"
    assert [r["labels"]["i"] for r in lines[1:]] == list(range(42, 50))


def test_configure_from_env_idempotent(tmp_path, monkeypatch):
    monkeypatch.setenv("OBS_DIR", str(tmp_path))
    monkeypatch.setenv("OBS_RUN_ID", "r-env")
    b1 = obs.configure_from_env()
    b2 = obs.configure_from_env()
    assert b1 is b2 and b1.run_id == "r-env"
    assert b1.directory == str(tmp_path)
    monkeypatch.delenv("OBS_DIR")
    assert obs.configure_from_env() is b1  # no OBS_DIR: keep current bus


def test_module_level_helpers_route_to_global_bus(tmp_path):
    bus = obs.configure(str(tmp_path), run_id="r-mod")
    obs.counter("c", 2, label="x")
    obs.gauge("g", 1.0)
    with obs.span("s"):
        pass
    obs.flush()
    kinds = [json.loads(ln)["kind"] for ln in open(bus.path)]
    assert kinds == ["meta", "counter", "gauge", "span"]


# ---------------------------------------------------------------------------
# Crash handlers (real processes)
# ---------------------------------------------------------------------------

_CHILD_SRC = textwrap.dedent(
    """
    import os, sys, time
    sys.path.insert(0, {repo!r})
    from distributeddeeplearning_tpu import obs
    bus = obs.configure_from_env()
    for i in range(40):
        bus.point("tick", i=i)
    with bus.span("work"):
        pass
    bus.flush()
    bus.point("unflushed")  # in the ring only, never written normally
    print("READY", flush=True)
    {tail}
    """
)


def _spawn(tmp_path, tail, extra_env=None):
    env = dict(
        os.environ,
        JAX_PLATFORMS="cpu",
        OBS_DIR=str(tmp_path),
        OBS_RING_SIZE="16",
        DDL_PROCESS_ID="0",
        **(extra_env or {}),
    )
    return subprocess.Popen(
        [sys.executable, "-c",
         _CHILD_SRC.format(repo=REPO_ROOT, tail=tail)],
        env=env,
        stdout=subprocess.PIPE,
        stderr=subprocess.STDOUT,
        text=True,
    )


def test_sigterm_leaves_flight_dump(tmp_path):
    """The preemption/watchdog black box: a killed process dumps its
    last N events even though they were never flushed."""
    proc = _spawn(tmp_path, "time.sleep(120)")
    # wait for READY so the bus exists and handlers are installed
    line = proc.stdout.readline()
    assert "READY" in line, line
    proc.send_signal(signal.SIGTERM)
    rc = proc.wait(timeout=30)
    assert rc != 0  # died by signal, semantics preserved
    dump = tmp_path / "flight-p0.jsonl"
    assert dump.exists()
    lines = [json.loads(ln) for ln in open(dump)]
    assert lines[0]["kind"] == "flight_meta"
    assert lines[0]["reason"] == "sigterm"
    names = [r["name"] for r in lines[1:]]
    assert "unflushed" in names  # ring caught what the file never saw
    assert len(lines) - 1 <= 16  # bounded by OBS_RING_SIZE


def test_unhandled_exception_leaves_flight_dump(tmp_path):
    proc = _spawn(tmp_path, "raise RuntimeError('boom')")
    out, _ = proc.communicate(timeout=60)
    assert proc.returncode == 1
    assert "boom" in out  # original traceback still printed
    lines = [json.loads(ln) for ln in open(tmp_path / "flight-p0.jsonl")]
    assert lines[0]["reason"] == "exception:RuntimeError"
    crash = [r for r in lines[1:] if r["name"] == "crash"]
    assert crash and "boom" in crash[0]["labels"]["error"]


# ---------------------------------------------------------------------------
# Merge + report
# ---------------------------------------------------------------------------

def _two_proc_run(tmp_path):
    for p in (0, 1):
        bus = EventBus(directory=str(tmp_path), proc=p, run_id="r-merge")
        t0 = time.monotonic()
        bus.span_event("step", 0.004, t=t0, epoch=0)
        bus.span_event("step", 0.004, t=t0 + 0.004, epoch=0)
        bus.span_event("step", 0.010, t=t0 + 0.008, epoch=0)
        bus.span_event("epoch", 0.050, t=t0, epoch=0, steps=3)
        bus.counter("host_sync", 1, label="epoch_metrics")
        bus.gauge("perf.compile_sec", 1.5 + p)
        bus.point("run_end")
        bus.close()
    return tmp_path


def test_merge_and_summarize(tmp_path):
    _two_proc_run(tmp_path)
    merged = obs_report.merge_run_dir(str(tmp_path))
    assert os.path.basename(merged) == "events.jsonl"
    # merged file: metas first, then events sorted by wall time
    lines = [json.loads(ln) for ln in open(merged)]
    metas = [r for r in lines if r["kind"] == "meta"]
    events = [r for r in lines if r["kind"] != "meta"]
    assert {m["p"] for m in metas} == {0, 1}
    walls = [e["wall"] for e in events]
    assert walls == sorted(walls)

    # a dir with a merged file loads identically to its parts
    summary = obs_report.summarize(obs_report.load([str(tmp_path)]))
    assert summary["run_ids"] == ["r-merge"]
    assert summary["spans"]["step"]["count"] == 6
    assert summary["spans"]["step"]["p50_ms"] == pytest.approx(4.0)
    assert summary["spans"]["step"]["p99_ms"] == pytest.approx(10.0)
    assert summary["host_sync_by_label"] == {"epoch_metrics": 2}
    assert summary["points"]["run_end"] == 2
    assert summary["epochs_seen"] == 1
    assert summary["max_epoch_skew_ms"] >= 0.0
    assert summary["step_s"] == pytest.approx(0.036)

    text = obs_report.render(summary)
    for needle in ("step", "epoch_metrics", "compile vs step", "timeline"):
        assert needle in text, text


def test_report_cli(tmp_path, capsys):
    """scripts/obs_report.py renders from a run dir (and --json mode)."""
    from scripts.obs_report import main as report_main

    _two_proc_run(tmp_path)
    assert report_main([str(tmp_path)]) == 0
    out = capsys.readouterr().out
    assert "host syncs" in out and "step" in out
    assert report_main([str(tmp_path), "--json"]) == 0
    summary = json.loads(capsys.readouterr().out)
    assert summary["counters"]["host_sync"] == 2
    assert report_main([str(tmp_path / "missing")]) == 2


def test_report_tolerates_truncated_tail(tmp_path):
    """A process killed mid-write leaves a torn last line; loading must
    not explode (that is exactly the crash-forensics use case)."""
    bus = EventBus(directory=str(tmp_path), proc=0, run_id="r-torn")
    bus.point("ok")
    bus.close()
    with open(bus.path, "a") as fh:
        fh.write('{"t": 1.0, "kind": "point", "na')  # torn
    loaded = obs_report.load([str(tmp_path)])
    assert [e["name"] for e in loaded["events"]] == ["ok"]


# ---------------------------------------------------------------------------
# Trace controller
# ---------------------------------------------------------------------------

def _fake_profiler(monkeypatch):
    import jax

    calls = []
    fake = types.SimpleNamespace(
        ProfileOptions=jax.profiler.ProfileOptions,
        start_trace=lambda d, profiler_options=None: calls.append(
            ("start", d, profiler_options)
        ),
        stop_trace=lambda: calls.append(("stop",)),
    )
    monkeypatch.setattr(jax, "profiler", fake)
    return calls


def test_trace_controller_periodic_and_on_demand(tmp_path, monkeypatch):
    calls = _fake_profiler(monkeypatch)
    ctrl = obs_trace.TraceController(str(tmp_path), every_n=2)
    assert ctrl.maybe_start(0) and ctrl.active
    assert not ctrl.maybe_start(0)  # never nested
    assert ctrl.maybe_stop(0) and not ctrl.active
    assert not ctrl.maybe_start(1)  # 1 % 2 != 0
    ctrl.request()  # on-demand (the SIGUSR1 path)
    assert ctrl.maybe_start(1)
    assert ctrl.maybe_stop(1)
    assert not ctrl.maybe_stop(1)  # stop is idempotent
    assert [c[0] for c in calls] == ["start", "stop", "start", "stop"]
    assert "trace-epoch0000" in calls[0][1]
    assert "trace-epoch0001" in calls[2][1]
    # captures start with the Python tracer off, as the benchmark's do
    assert calls[0][2].python_tracer_level == 0


def test_trace_from_env(tmp_path, monkeypatch):
    assert obs_trace.from_env(env={}) is None
    obs.configure(str(tmp_path))
    ctrl = obs_trace.from_env(env={"TRACE_EVERY_N_EPOCHS": "3"})
    assert ctrl is not None and ctrl.every_n == 3
    assert ctrl.directory == os.path.join(str(tmp_path), "traces")
    ctrl2 = obs_trace.from_env(
        env={"TRACE_ON_SIGNAL": "1", "TRACE_DIR": "/tmp/elsewhere"}
    )
    assert ctrl2 is not None and ctrl2.every_n == 0
    assert ctrl2.directory == "/tmp/elsewhere"


# ---------------------------------------------------------------------------
# Loop integration: fit() emits through the bus (incl. trace trigger)
# ---------------------------------------------------------------------------

def test_fit_emits_epoch_step_perf_and_trace_events(
    tmp_path, mesh8, monkeypatch
):
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.data.synthetic import SyntheticTokenDataset
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training import loop

    calls = _fake_profiler(monkeypatch)
    monkeypatch.setenv("OBS_DIR", str(tmp_path))
    monkeypatch.setenv("TRACE_EVERY_N_EPOCHS", "1")
    cfg = TrainConfig(
        model="lm_tiny", num_classes=64, batch_size_per_device=2,
        fake_data_length=32, epochs=1, compute_dtype="float32",
        weight_decay=0.0, log_every_steps=0,
    )
    data = SyntheticTokenDataset(
        length=32, global_batch_size=cfg.global_batch_size,
        seq_len=16, vocab_size=64,
    )
    res = loop.fit(
        get_model("lm_tiny", num_classes=64, dtype="float32", max_seq_len=16),
        cfg, data, mesh=mesh8, add_default_logger=False,
    )
    bus = obs.get_bus()
    lines = [json.loads(ln) for ln in open(bus.path)]
    names = {(r["kind"], r["name"]) for r in lines[1:]}
    assert ("point", "run_begin") in names
    assert ("span", "step") in names
    assert ("span", "epoch") in names
    assert ("span", "epoch_materialize") in names
    assert ("gauge", "perf.host_sync_count") in names
    assert ("point", "run_end") in names
    # epoch gauges carry the materialised metrics (loss among them)
    gauges = {r["name"]: r["value"] for r in lines if r["kind"] == "gauge"}
    assert gauges["epoch.loss"] == res.history[0]["loss"]
    assert gauges["perf.host_sync_count"] == res.perf["host_sync_count"]
    # step spans: one per step, durations match the dispatch clock count
    steps = [r for r in lines if r["kind"] == "span" and r["name"] == "step"]
    assert len(steps) == data.steps_per_epoch
    # the per-epoch profiler capture really started and stopped
    assert ("point", "trace_start") in names
    assert ("point", "trace_stop") in names
    assert [c[0] for c in calls] == ["start", "stop"]


# ---------------------------------------------------------------------------
# Satellite units that ride along this file
# ---------------------------------------------------------------------------

def test_bench_records_route_through_bus(tmp_path, capsys):
    """bench.py --events contract: the canonical stdout JSON line names
    its device AND the same record lands on the bus as bench_result. A
    record made off the TPU is renamed so it cannot pass for the device
    metric."""
    import bench

    bus = obs.configure(str(tmp_path))
    record = {"metric": "resnet50_synthetic_train_images_per_sec",
              "value": 123.4, "unit": "images/sec", "vs_baseline": 0.1}
    tpu = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    bench._emit_record(record, tpu)
    line = capsys.readouterr().out.strip()
    assert json.loads(line) == {**record, "device": tpu}
    events = [json.loads(ln) for ln in open(bus.path)][1:]
    assert events[-1]["name"] == "bench_result"
    assert events[-1]["labels"]["metric"] == record["metric"]
    assert events[-1]["labels"]["value"] == 123.4

    bench._emit_record(record, {"platform": "cpu", "kind": "cpu", "count": 8})
    off_chip = json.loads(capsys.readouterr().out.strip())
    assert off_chip["metric"] == "cpu_smoke." + record["metric"]


def test_heavy_refresh_duration_parsing():
    from scripts.heavy_refresh import parse_durations_log

    log = [
        "96.21s call     tests/test_vit.py::test_packed",
        "24.99s call     tests/test_fast.py::test_under",
        "30.00s setup    tests/test_x.py::test_setup_not_call",
        "110.5s call     tests/test_eff.py::test_loss",
        "garbage line",
    ]
    assert parse_durations_log(log, 25.0) == [
        "tests/test_vit.py::test_packed",
        "tests/test_eff.py::test_loss",
    ]


def test_decode_audit_cpu_honest_rows():
    from scripts.decode_audit import format_row, sweep_row

    on_chip = sweep_row(8, 11700.0, 2**26, 2**27, 20000.0, True)
    off_chip = sweep_row(8, 117.0, 2**26, 2**27, 20000.0, False)
    assert on_chip["pct_of_floor"] == pytest.approx(58.5)
    assert off_chip["pct_of_floor"] is None  # CPU: no roofline position
    assert off_chip["analytic_floor_tokens_per_sec"] == 20000.0
    assert "%" in format_row(on_chip)
    assert "n/a" in format_row(off_chip)


def test_roofline_peaks_are_keyed_by_device_kind():
    """A floor is quoted against the attached chip's published peaks; a
    device_kind the table does not hold is an error, never a default."""
    from distributeddeeplearning_tpu.utils import roofline

    v5e = roofline.peaks(roofline.V5E)
    assert (v5e.hbm_gbps, v5e.bf16_tflops) == (819.0, 197.0)
    assert v5e.source
    assert roofline.floor_basis(roofline.V5E) == "TPU v5 lite-hbm-819GBps"
    for kind in ("cpu", "TPU v4", ""):
        with pytest.raises(KeyError, match="no roofline peaks"):
            roofline.peaks(kind)


def test_decode_audit_paged_floor_accounts_table_bytes():
    """Paged-mode byte floor (ISSUE 6 satellite): the analytic
    bytes/step must stream the table-gathered K/V view (block-rounded)
    PLUS the int32 block tables — leaving the tables out would overstate
    pct_of_floor in paged mode. Shape-only, no compile."""
    import math

    import jax
    import jax.numpy as jnp
    import numpy as np
    from flax import traverse_util

    from distributeddeeplearning_tpu.inference import decode_variant
    from distributeddeeplearning_tpu.models.transformer_lm import (
        TransformerLM,
    )
    from scripts.decode_audit import paged_step_bytes, sweep_row

    model = TransformerLM(
        variant="tiny", vocab_size=64, max_seq_len=16, dtype=jnp.float32
    )
    shapes = jax.eval_shape(
        lambda r: decode_variant(model).init(
            r, jnp.zeros((2, 16), jnp.int32), train=False
        ),
        jax.random.PRNGKey(0),
    )["cache"]
    dense_kv = sum(
        math.prod(s.shape) * np.dtype(s.dtype).itemsize
        for p, s in traverse_util.flatten_dict(dict(shapes)).items()
        if p[-1] in ("cached_k", "cached_v")
    )
    view, table, scale = paged_step_bytes(model, 2, 16, block_size=4)
    # block-aligned max_len: the gathered view streams exactly the dense
    # KV bytes — the floor differs ONLY by the table overhead (and no
    # scale bytes exist on the native dtype)
    assert view == dense_kv
    assert table > 0
    assert scale == 0
    # non-dividing block size: rounding makes the view strictly larger
    view5, _, _ = paged_step_bytes(model, 2, 16, block_size=5)
    assert view5 > dense_kv
    # int8 mode: payload shrinks, f32 per-head scales appear itemized
    view8, _, scale8 = paged_step_bytes(model, 2, 16, block_size=4,
                                        kv_dtype="int8")
    assert view8 < view and scale8 > 0
    # the row itemizes the table bytes already inside bytes_per_step
    row = sweep_row(2, 100.0, view, view + table, 1000.0, False,
                    table_bytes=table)
    assert row["block_table_bytes"] == table
    assert "block_table_bytes" not in sweep_row(
        2, 100.0, dense_kv, dense_kv, 1000.0, False
    )
