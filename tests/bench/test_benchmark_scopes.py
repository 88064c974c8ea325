"""The readers of the program's own spans, counters and scope tables
(``benchmarks/programs/obs.py`` and the readers over it): on a rehearsed
CPU run of the train cell, on a hand-made run with a ``Trace``, and
against a program that has none of it to read."""

import importlib
import json
import os
import sys
import time

import pytest

from benchmarks import harness, tracing
from benchmarks.programs import obs as program_obs

DATA = os.path.join(os.path.dirname(__file__), "data")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_benchmark_manifest import REAL  # noqa: E402

CELL = "gpt2-train-t1024"
DEVICE = [
    "attn_core_device_ms.train", "attn_proj_device_ms.train", "mlp_device_ms.train",
    "norm_residual_device_ms.train", "head_loss_device_ms.train",
    "optimizer_device_ms.train", "unscoped_device_pct.train",
]
HOST = [
    "step_dispatch_host_ms", "stage_wait_ms_per_step", "h2d_mib_per_step",
    "setup_engine_s", "setup_compile_s",
]


def _spec(name):
    with open(os.path.join(harness.HERE, "metrics", name + ".json")) as fh:
        return json.load(fh)


def _read(name, run):
    spec = _spec(name)
    return importlib.import_module(f"benchmarks.readers.{spec['reader']}").read(run, spec)


def test_the_manifest_lists_the_twelve_for_the_train_cell():
    mine = {m["name"]: m for m in REAL["per_layer"] if m["name"] in DEVICE + HOST}
    assert sorted(mine) == sorted(DEVICE + HOST)
    assert all(m["workloads"] == [CELL] for m in mine.values())
    assert {mine[n]["layer"] for n in DEVICE} == {"model step"}
    assert {mine[n]["source"] for n in DEVICE} == {"device_trace"}
    assert [mine[n]["layer"] for n in HOST] == [
        "step", "input", "input", "set-up", "compile cache"]
    assert [mine[n]["moves"] for n in HOST[-2:]] == ["setup_s"] * 2


# -- a rehearsed run of the train cell on the CPU ----------------------------

@pytest.fixture(scope="module")
def rehearsed(tmp_path_factory):
    m = json.loads(json.dumps(REAL))
    m["configs"][0]["file"] = os.path.join(DATA, "configs", "gpt2-tiny.json")
    path = os.path.join(str(tmp_path_factory.mktemp("m")), "tiny.json")
    with open(path, "w") as fh:
        json.dump(m, fh)
    # untraced: the readers take no notice of `cell.trace`, and a traced
    # rehearsal writes under one fixed directory, which the traced
    # rehearsal of test_benchmark_runs.py may hold in another worker
    cell = harness.load_cell(
        CELL, 2**31 + 5, 2.0, False, time.monotonic(),
        manifest_path=path, require_chip=False,
    )
    run = importlib.import_module("benchmarks.runners.train").run(cell)
    return cell, run


def _unread(run):
    """The dispatch metric reads the dispatches after a logging sync (one
    every hundred steps): a slow rehearsal's ring may hold none."""
    synced = program_obs.ring_events("step.log_sync", "span", run["window"])
    return set() if synced else {"step_dispatch_host_ms"}


@pytest.mark.parametrize("name", HOST)
def test_host_readers_on_a_rehearsed_run(rehearsed, name):
    cell, run = rehearsed
    value = _read(name, run)
    if name in _unread(run):
        assert value is None
        return
    assert value is not None and value >= 0.0
    if name == "h2d_mib_per_step":  # tokens and labels, int32
        rows = cell.traffic["batch_per_chip"] * cell.chips
        assert value == pytest.approx(2 * rows * cell.traffic["seq_len"] * 4 / 2**20)
    if name.startswith("setup_"):
        assert 0.0 < value < run["end_to_end"]["setup_s"]
    if name == "step_dispatch_host_ms":
        # the first dispatches after a logging sync, and nothing slower
        spans = program_obs.ring_events("step", "span", run["window"])
        assert min(e["dur"] for e in spans) <= value / 1e3 <= max(e["dur"] for e in spans)


@pytest.mark.parametrize("name", DEVICE)
def test_device_readers_find_nothing_without_a_device_plane(rehearsed, name):
    _, run = rehearsed
    assert run["trace"] is None and _read(name, run) is None


def test_the_result_line_carries_the_host_metrics_and_no_device_one(rehearsed):
    cell, run = rehearsed
    metrics = harness.read_per_layer(cell, run)
    host = set(HOST) - _unread(run)
    assert host <= set(metrics) and not set(DEVICE) & set(metrics)
    units = {m["name"]: m["unit"] for m in REAL["per_layer"]}
    assert all(metrics[n]["unit"] == units[n] for n in host)


def test_the_steps_names_outlive_the_trainer_the_runner_frees(rehearsed):
    """The runner deletes the trainer before any reader runs: the step's
    scope table has read its names by then, and holds every group."""
    import gc

    from distributeddeeplearning_tpu.models.transformer_lm import TRAIN_STEP_GROUPS
    from distributeddeeplearning_tpu.obs import programs

    gc.collect()
    table = programs.tables("jit_local_step")[-1]
    assert not table.holds_executable and len(table) > 100
    assert programs.groups_in(table.scopes(), TRAIN_STEP_GROUPS) == {
        name for name, _ in TRAIN_STEP_GROUPS}


# -- a hand-made run with a Trace ----------------------------------------------

HLO = """HloModule jit_local_step

ENTRY %main () -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/transpose(jvp(TransformerLM))/block0/attn/attn_core/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/attn/qkv/dot_general"}
  %fusion.3 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/mlp/fc1/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/ln2/mul"}
  %fusion.5 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(loss)/reduce_max"}
  %fusion.6 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/optimizer/mul"}
  ROOT %copy.7 = f32[4]{0} copy(%a)
}
"""
MS = 1_000_000


class _Compiled:
    def as_text(self):
        return HLO


OWNER = _Compiled  # who runs the program: anything that lives on


def _step(t0):
    """One run of the step: 1 ms in each group in turn, 1 ms unscoped."""
    names = ["%%fusion.%d = f32[4]{0} fusion(%%a)" % i for i in range(1, 7)] + ["copy.7"]
    return [(n, t0 + i * MS, t0 + (i + 1) * MS) for i, n in enumerate(names)]


@pytest.fixture
def handmade():
    programs = pytest.importorskip("distributeddeeplearning_tpu.obs.programs")
    programs.clear()
    programs.register("jit_local_step", _Compiled(), OWNER)
    ops = _step(0) + _step(10 * MS) + _step(20 * MS) + [("fusion.1", 40 * MS, 45 * MS)]
    modules = [("jit_local_step(123)", 0, 7 * MS), ("jit_local_step(123)", 10 * MS, 17 * MS),
               ("jit_local_step(123)", 20 * MS, 27 * MS),  # cut by the window's end
               ("jit_local_step_acc(9)", 40 * MS, 45 * MS)]  # another program
    trace = tracing.Trace(
        ops={0: ops}, modules={0: modules}, host=[("traced_window", 0, 25 * MS)],
    )
    yield {"trace": trace, "window": (0.0, 1.0)}
    programs.clear()


@pytest.mark.parametrize("name", DEVICE)
def test_device_readers_on_a_handmade_trace(handmade, name):
    # two whole runs in the window: a millisecond a group a run, and one
    # of seven in no group; the other program's operations are not counted
    expect = 100.0 / 7 if name.startswith("unscoped") else 1.0
    assert _read(name, handmade) == pytest.approx(expect)
    by = program_obs.step_by_scope(handmade, _spec(name))
    assert by["runs"] == 2 and by["total_s"] == pytest.approx(0.014)
    assert by["groups"]["attn_core"]["backward_s"] == pytest.approx(0.002)
    assert handmade["_by_scope"]["jit_local_step"] is by  # one reduction for all seven


def test_device_readers_against_a_program_without_tables(handmade, monkeypatch):
    from distributeddeeplearning_tpu.obs import programs

    programs.clear()  # this program was never compiled ahead
    assert [_read(n, handmade) for n in DEVICE] == [None] * 7
    handmade.pop("_by_scope")
    programs.register("jit_local_step", _Compiled(), OWNER)
    assert _read(DEVICE[0], handmade) == pytest.approx(1.0)
    handmade.pop("_by_scope")
    # an executable out of a cache that another tree filled: its names
    # lack this tree's `attn_core`, and nothing is read under wrong names
    stale = _Compiled()
    stale.as_text = lambda: HLO.replace("/attn_core", "")
    programs.register("jit_local_step", stale, OWNER)
    assert [_read(n, handmade) for n in DEVICE] == [None] * 7
    handmade.pop("_by_scope")
    # a parent commit from before obs/programs.py existed
    monkeypatch.setitem(sys.modules, "distributeddeeplearning_tpu.obs.programs", None)
    assert [_read(n, handmade) for n in DEVICE] == [None] * 7


def test_dispatch_is_read_where_the_queue_was_empty(monkeypatch):
    """`step` spans behind a full device queue read the device's step
    time: the metric takes the first dispatches after each logging sync."""
    def span(name, t, dur):
        return {"t": t, "kind": "span", "name": name, "dur": dur}

    class Bus:
        ring = (
            [span("step", 0.01 * i, 0.169) for i in range(5)]  # blocked
            + [span("step.log_sync", 0.10, 5.0)]
            + [span("step", 0.20 + 0.01 * i, 0.001) for i in range(8)]
            + [span("data.stage_wait", 0.29, 0.5)]  # another name: not counted
            + [span("step", 0.30 + 0.01 * i, 0.169) for i in range(5)]
            + [span("step.log_sync", 0.40, 5.0), span("step", 0.41, 0.003)]
            + [span("step.log_sync", 2.0, 5.0), span("step", 2.1, 9.0)]  # past the window
        )

    monkeypatch.setattr(program_obs, "_bus", lambda: Bus())
    run = {"window": (0.0, 1.0)}
    assert _read("step_dispatch_host_ms", run) == pytest.approx((8 * 1.0 + 3.0) / 9)
    assert _spec("step_dispatch_host_ms")["first"] == 8
    Bus.ring = Bus.ring[:5]  # no sync in what the ring holds: nothing to read
    assert _read("step_dispatch_host_ms", run) is None


def test_host_readers_against_a_program_without_spans_or_totals(monkeypatch):
    class OldBus:  # no totals(), and a ring that never saw the names
        ring = [{"t": 0.5, "kind": "span", "name": "epoch", "dur": 1.0}]

    monkeypatch.setattr(program_obs, "_bus", lambda: OldBus())
    run = {"window": (0.0, 1.0)}
    assert [_read(n, run) for n in HOST] == [None] * 5
