"""Names on the device's time and spans on the profiler's clock
(``obs/programs.py``, ``EventBus.span``'s ``ddl:`` annotations,
``EventBus.totals``) and the spans of the explicit train path."""

import gc
import json
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.models.transformer_lm import TRAIN_STEP_GROUPS
from distributeddeeplearning_tpu.obs import programs

GROUPS = [name for name, _ in TRAIN_STEP_GROUPS]


@pytest.fixture(autouse=True)
def _fresh():
    obs.reset()
    programs.clear()
    yield
    obs.reset()
    programs.clear()


# -- a tiny trainer, as the benchmark builds one ----------------------------

SEQ, ROWS, VOCAB = 32, 2, 256


def _trainer():
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.frontends import explicit
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh

    cfg = TrainConfig(
        model="lm_tiny", num_classes=VOCAB, batch_size_per_device=ROWS,
        optimizer="adamw", weight_decay=0.0, fake=True, epochs=1,
        log_every_steps=2,
    )
    model = get_model(
        "lm_tiny", num_classes=VOCAB, max_seq_len=SEQ, dtype="bfloat16",
        attn_impl=cfg.attn_impl,
    )
    return explicit.setup(
        model, cfg, mesh=data_parallel_mesh(1), steps_per_epoch=10,
        input_shape=(1, SEQ), input_dtype=jnp.int32,
    )


class _Tokens:
    """A dataset of ``steps`` token batches an epoch."""

    seq_len = SEQ  # loop.fit sizes the model's init from this

    def __init__(self, steps):
        self.steps_per_epoch = steps
        self.batch = (np.zeros((ROWS, SEQ), np.int32),) * 2

    def epoch(self, i):  # noqa: ARG002
        return iter([self.batch] * self.steps_per_epoch)


@pytest.fixture(scope="module")
def compiled_step():
    """One lm_tiny train step compiled ahead on the CPU, twice."""
    from distributeddeeplearning_tpu.data.pipeline import shard_batch

    obs.reset()
    programs.clear()
    pieces, state = _trainer()
    batch = shard_batch(_Tokens(1).batch, pieces.mesh)
    pieces.train_step.aot_compile(state, batch)
    first = programs.tables("jit_local_step")
    first[0].scopes()  # read now: the second compile lets this executable go
    pieces.train_step.aot_compile(state, batch)
    again = programs.tables("jit_local_step")
    spans = [e for e in obs.get_bus().ring if e["kind"] == "span"]
    assert again[0].holds_executable  # unread while its step lives
    del pieces, state  # the trainer torn down, as the benchmark does
    gc.collect()
    return first, again, spans


@pytest.mark.parametrize("group", GROUPS)
def test_scope_table_of_a_train_step_names_every_group(compiled_step, group):
    _, (table,), _ = compiled_step
    assert table.program == "jit_local_step"
    assert group in programs.groups_in(table.scopes(), TRAIN_STEP_GROUPS)
    # every group runs in the backward pass too, but the optimizer
    backward = {
        k: path for k, path in table.scopes().items() if programs.BACKWARD in path
    }
    assert (group in programs.groups_in(backward, TRAIN_STEP_GROUPS)) == (
        group != "optimizer")


def test_the_model_names_its_embeddings_and_residual_adds(compiled_step):
    """No pattern stands for "the rest of the model": what the modules do
    not name has a scope of its own, and a path under the model with no
    such name reads as unscoped."""
    from distributeddeeplearning_tpu.training.overlap import OVERLAP_SCOPE

    _, (table,), _ = compiled_step
    paths = set(table.scopes().values())
    for scope in ("embed", "residual", "head", "loss", "optimizer", "metrics"):
        assert any(f"/{scope}/" in p or f"({scope})" in p for p in paths), scope
    group = lambda path: programs.group_of(path, TRAIN_STEP_GROUPS)  # noqa: E731
    assert group("jit(local_step)/jvp(TransformerLM)/embed/gather") == "norm_residual"
    assert group("jit(local_step)/jvp(TransformerLM)/block1/residual/add") == "norm_residual"
    assert group(f"jit(local_step)/{OVERLAP_SCOPE}/psum") == "optimizer"
    assert group("jit(local_step)/jvp(TransformerLM)/block1/convert_element_type") == "unscoped"
    assert group("jit(local_step)/transpose(jvp(TransformerLM))/scatter-add") == "unscoped"


def test_scope_table_survives_a_second_aot_compile_and_its_step(compiled_step):
    (first,), (again,), spans = compiled_step
    # the step is gone: the table read the names then, and let the executable go
    assert not again.holds_executable and not first.holds_executable
    # compiling the same signature again replaces the table, and says the same
    assert again is not first and len(again) == len(first) > 100
    assert again.scopes() == first.scopes()
    names = [e["name"] for e in spans if e["name"].startswith("compile")]
    assert names == ["compile.lower", "compile.backend", "compile"] * 2
    whole = [e for e in spans if e["name"] == "compile"]
    assert all(e["labels"]["program"] == "jit_local_step" for e in whole)
    assert all(e["labels"]["cache_hit"] is False for e in whole)  # cache is off here


class _Owner:
    """Who runs a program (a StepFn, a serving engine)."""


def test_a_table_holds_its_executable_no_longer_than_its_owner(monkeypatch):
    owner, other = _Owner(), _Owner()
    compiled = [_Text(HLO) for _ in range(3)]
    for i, c in enumerate(compiled):
        programs.register("jit_f", c, owner, key=i)
    programs.register("jit_f_acc", compiled[0], other)
    assert [t.program for t in programs.tables()] == ["jit_f"] * 3 + ["jit_f_acc"]
    assert len(programs.tables("jit_f")) == 3  # not jit_f_acc: no prefix match
    # the same program compiled again: the old table lets go unread
    old = programs.tables("jit_f")[0]
    programs.register("jit_f", _Text(HLO), owner, key=0)
    assert not old.holds_executable and compiled[0].asked == 0 and len(old) == 0
    assert all(t.holds_executable for t in programs.tables())
    # the owner goes: its tables read the names then, and keep them alone
    del owner
    gc.collect()
    mine = programs.tables("jit_f")
    assert [t.holds_executable for t in mine] == [False] * 3
    assert [c.asked for c in compiled] == [0, 1, 1]
    assert all(t.scopes()["fusion.4"].endswith("optimizer/mul") for t in mine)
    assert programs.tables("jit_f_acc")[0].holds_executable  # its owner lives
    # of the tables that hold names alone, the newest few stay
    monkeypatch.setattr(programs, "MAX_READ_TABLES", 2)
    programs.register("jit_g", _Text(""), other)
    assert [t.program for t in programs.tables()] == ["jit_f", "jit_f_acc", "jit_f", "jit_g"]


# -- the reduction, on hand-made events ---------------------------------------

HLO = """HloModule jit_local_step, entry_computation_layout={()->()}

%fused_computation.1 (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %multiply.9 = f32[4]{0} multiply(%p, %p), metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/ln1/mul"}
}

ENTRY %main () -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%a), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(local_step)/transpose(jvp(TransformerLM))/block0/attn/attn_core/dot_general" source_file="x.py" source_line=3}
  %fusion.2 = f32[4]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/attn/qkv/dot_general"}
  %convolution.3 = f32[4]{0} convolution(%a, %b), metadata={op_name="jit(local_step)/jvp(TransformerLM)/head/btd,vd->btv/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%a), kind=kLoop, calls=%f, metadata={op_name="jit(local_step)/optimizer/mul"}
  fusion.5 = f32[4]{0} fusion(a), kind=kLoop, calls=f, metadata={op_name="jit(local_step)/transpose(jvp(loss))/sub"}
  %while.6 = (s32[]) while(%t), condition=%c, body=%b, metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/mlp/while"}
  %copy.7 = f32[4]{0} copy(%a)
  %convert.10 = bf16[4]{0} convert(%a), metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/convert_element_type"}
  ROOT %add.8 = f32[4]{0} add(%a, %a), metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/residual/add"}
}
"""


class _Text:
    def __init__(self, text):
        self.text, self.asked = text, 0

    def as_text(self):
        self.asked += 1
        return self.text


def test_table_parses_lazily_and_once():
    compiled, owner = _Text(HLO), _Owner()
    table = programs.register("jit_local_step", compiled, owner)
    assert compiled.asked == 0  # registering costs a reference
    scopes = table.scopes()
    assert scopes["fusion.5"] == "jit(local_step)/transpose(jvp(loss))/sub"
    assert "copy.7" not in scopes and table.program == "jit_local_step"
    assert "multiply.9" in scopes and len(table) == 9
    assert compiled.asked == 1


MS = 1_000_000  # ns


def _by_scope(events, **kw):
    scopes = programs.parse_hlo_scopes(HLO)
    return programs.device_seconds_by_scope(events, scopes, TRAIN_STEP_GROUPS, **kw)


@pytest.mark.parametrize("events,window,expect", [
    # a fusion under transpose(jvp(...))/attn_core: attn_core, backward;
    # the TPU's event names are whole HLO lines
    ([("%fusion.1 = f32[4]{0} fusion(%a), kind=kLoop", 0, 4 * MS),
      ("%fusion.2 = f32[4]{0} fusion(%a)", 4 * MS, 6 * MS)],
     (None, None),
     {"attn_core": (0.004, 0.004), "attn_proj": (0.002, 0.0), "unscoped": 0.0}),
    # an instruction the table does not know, one with no metadata, and one
    # of the model's that no module or scope names
    ([("fusion.99", 0, MS), ("copy.7", MS, 3 * MS), ("convolution.3", 3 * MS, 4 * MS),
      ("convert.10", 4 * MS, 5 * MS)],
     (None, None),
     {"head_loss": (0.001, 0.0), "unscoped": 0.004}),
    # clipped to a window: half of the first, all of the second, none of the third
    ([("fusion.4", 0, 2 * MS), ("fusion.5", 2 * MS, 3 * MS), ("add.8", 5 * MS, 6 * MS)],
     (MS, 4 * MS),
     {"optimizer": (0.001, 0.0), "head_loss": (0.001, 0.001),
      "norm_residual": (0.0, 0.0), "unscoped": 0.0}),
    # a while over the operations of its body: each instant once, for the
    # innermost event; the loop keeps what its body leaves
    ([("while.6", 0, 10 * MS), ("fusion.2", MS, 3 * MS), ("copy.7", 3 * MS, 4 * MS),
      ("fusion.1", 6 * MS, 9 * MS)],
     (None, None),
     {"mlp": (0.004, 0.0), "attn_proj": (0.002, 0.0), "attn_core": (0.003, 0.003),
      "unscoped": 0.001}),
], ids=["backward-fusion", "unknown-unscoped", "window", "nested-while"])
def test_device_seconds_by_scope(events, window, expect):
    out = _by_scope(events, window=window)
    unscoped = expect.pop("unscoped")
    assert out["unscoped_s"] == pytest.approx(unscoped)
    for group in GROUPS:
        seconds, backward = expect.get(group, (0.0, 0.0))
        assert out["groups"][group]["seconds"] == pytest.approx(seconds), group
        assert out["groups"][group]["backward_s"] == pytest.approx(backward), group
    total = sum(g["seconds"] for g in out["groups"].values()) + out["unscoped_s"]
    assert out["total_s"] == pytest.approx(total)
    assert sum(s for _, s in out["unscoped_top"]) == pytest.approx(unscoped)


def test_within_keeps_the_events_of_a_programs_runs():
    ops = [("a", 0, 10), ("b", 10, 20), ("c", 25, 35), ("d", 40, 50)]
    assert programs.within(ops, [(0, 20), (38, 60)]) == [ops[0], ops[1], ops[3]]


def test_program_by_scope_joins_a_programs_own_runs_over_devices():
    scopes = programs.parse_hlo_scopes(HLO)
    one = [("fusion.1", 0, 2 * MS), ("fusion.4", 2 * MS, 3 * MS),
           ("fusion.1", 10 * MS, 14 * MS),  # under the other program's run
           ("fusion.2", 20 * MS, 21 * MS), ("copy.7", 21 * MS, 22 * MS)]
    ops = {0: one, 1: one[:2]}
    modules = {
        0: [("jit_local_step(7)", 0, 3 * MS), ("jit_local_step_acc(8)", 10 * MS, 14 * MS),
            ("jit_local_step(7)", 20 * MS, 22 * MS)],
        1: [("jit_local_step(7)", 0, 3 * MS)],
    }
    by = programs.program_by_scope(ops, modules, "jit_local_step", scopes, TRAIN_STEP_GROUPS)
    assert (by["runs"], by["devices"]) == (3, 2) and by["run_s"] == pytest.approx(0.008)
    assert by["groups"]["attn_core"]["seconds"] == pytest.approx(0.004)  # not the 4 ms
    assert by["groups"]["optimizer"]["seconds"] == pytest.approx(0.002)
    assert by["unscoped_s"] == pytest.approx(0.001) and by["total_s"] == pytest.approx(0.008)
    # whole runs inside the window alone; a program that never ran: nothing
    cut = programs.program_by_scope(
        ops, modules, "jit_local_step", scopes, TRAIN_STEP_GROUPS, (0, 21 * MS))
    assert cut["runs"] == 2 and cut["total_s"] == pytest.approx(0.006)
    assert programs.program_by_scope(ops, modules, "jit_local", scopes, TRAIN_STEP_GROUPS) is None


def test_groups_in_tells_a_table_with_another_trees_names():
    scopes = programs.parse_hlo_scopes(HLO)
    assert programs.groups_in(scopes, TRAIN_STEP_GROUPS) == set(GROUPS)
    stale = {k: p.replace("/attn_core", "") for k, p in scopes.items()}
    assert programs.groups_in(stale, TRAIN_STEP_GROUPS) == set(GROUPS) - {"attn_core"}


def test_idle_gaps_fall_to_the_innermost_span():
    ops = [("a", 10, 20), ("b", 30, 40), ("c", 70, 80)]
    host = [("step", 0, 50), ("data.stage_wait", 22, 28)]
    gaps = programs.idle_gaps_by_span(ops, host, window=(0, 100))
    assert gaps == pytest.approx({
        "step": 10e-9, "data.stage_wait": 10e-9, "unannotated": 50e-9,
    })


# -- spans on the profiler's clock, totals ------------------------------------

def test_span_opens_a_ddl_annotation(monkeypatch):
    seen = []

    class Note:
        def __init__(self, name):
            seen.append(("new", name))

        def __enter__(self):
            seen.append("enter")

        def __exit__(self, *exc):
            seen.append("exit")

    monkeypatch.setattr(sys.modules["jax.profiler"], "TraceAnnotation", Note)
    bus = obs.EventBus()
    with bus.span("data.stage", k=1) as labels:
        labels["late"] = True  # what the block learns inside
        seen.append("body")
    assert seen == [("new", "ddl:data.stage"), "enter", "body", "exit"]
    (rec,) = bus.ring
    assert rec["name"] == "data.stage" and rec["labels"] == {"k": 1, "late": True}
    bus.span_event("step", 0.5)  # after the fact: recorded, not mirrored
    assert len(seen) == 4 and len(bus.ring) == 2


def test_span_records_with_no_profiler_and_with_no_jax(monkeypatch):
    bus = obs.EventBus()
    with bus.span("step"):  # no profiler session: the annotation is a no-op
        pass
    monkeypatch.setitem(sys.modules, "jax.profiler", None)  # a jax-free process
    with bus.span("step"):
        pass
    with pytest.raises(ValueError), bus.span("step"):
        raise ValueError("the block's own error passes through")
    assert [e["name"] for e in bus.ring] == ["step"] * 3


def test_ddl_spans_lie_in_a_profiler_capture(tmp_path):
    import threading

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=options)
    try:
        with obs.span("step"):
            jnp.ones((8, 8)).sum().block_until_ready()

        def staged():
            with obs.span("data.stage"):
                pass

        t = threading.Thread(target=staged)
        t.start()
        t.join(timeout=30)
    finally:
        jax.profiler.stop_trace()
    profile = programs.load_profile(str(tmp_path))
    names = {name for name, _, _ in profile.host}
    assert {"step", "data.stage"} <= names
    assert all(b >= a for _, a, b in profile.host)
    assert profile.ops == {}  # the CPU has no device plane


def test_totals_outlive_the_ring(tmp_path):
    bus = obs.EventBus(ring_size=4)
    for _ in range(20):
        bus.span_event("setup.engine", 0.25)
        bus.counter("data.h2d_bytes", 1024)
        bus.gauge("epoch.loss", 1.0)  # gauges have no totals
    with bus.span("compile"):
        pass
    assert len(bus.ring) == 4
    totals = bus.totals()
    assert totals["setup.engine"] == {"kind": "span", "count": 20, "sum": 5.0}
    assert totals["data.h2d_bytes"] == {"kind": "counter", "count": 20, "sum": 20480.0}
    assert totals["compile"]["count"] == 1 and "epoch.loss" not in totals
    path = bus.dump_flight("test", path=str(tmp_path / "flight.jsonl"))
    with open(path) as fh:
        header = json.loads(fh.readline())
    assert header["totals"]["setup.engine"]["count"] == 20


# -- the explicit train path, and loop.fit beside it ---------------------------

def _names(kind=None):
    return [
        e["name"] for e in obs.get_bus().ring
        if kind is None or e["kind"] == kind
    ]


def test_explicit_path_emits_setup_step_and_staging_events():
    from distributeddeeplearning_tpu.frontends import explicit

    pieces, state = _trainer()
    setup = [name for name in _names("span") if name.startswith("setup")]
    assert setup == ["setup.engine"]
    explicit.train_epoch(pieces, state, _Tokens(3), 0)
    ring = list(obs.get_bus().ring)
    count = lambda name: sum(1 for e in ring if e["name"] == name)  # noqa: E731
    assert count("step") == 3 and count("data.stage") == 3
    assert count("data.stage_wait") == 4  # three batches and the end
    assert count("step.log_sync") == 1  # log_every_steps=2
    staged = [e["value"] for e in ring if e["name"] == "data.h2d_bytes"]
    assert staged == [2 * ROWS * SEQ * 4] * 3  # tokens and labels, int32
    assert obs.get_bus().totals()["data.h2d_bytes"]["sum"] == 3 * 2 * ROWS * SEQ * 4


def test_fit_and_the_explicit_loop_emit_the_same_step_events():
    from distributeddeeplearning_tpu.config import TrainConfig
    from distributeddeeplearning_tpu.frontends import explicit
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel.mesh import data_parallel_mesh
    from distributeddeeplearning_tpu.training import loop

    per_step = {"step", "step.log_sync", "data.stage", "data.stage_wait",
                "data.h2d_bytes"}
    pieces, state = _trainer()
    explicit.train_epoch(pieces, state, _Tokens(4), 0)
    explicit_names = set(_names()) & per_step

    obs.reset()
    cfg = TrainConfig(
        model="lm_tiny", num_classes=VOCAB, batch_size_per_device=ROWS,
        optimizer="adamw", weight_decay=0.0, fake=True, epochs=1,
        log_every_steps=2,
    )
    model = get_model("lm_tiny", num_classes=VOCAB, max_seq_len=SEQ,
                      dtype="bfloat16", attn_impl=cfg.attn_impl)
    loop.fit(model, cfg, _Tokens(4), mesh=data_parallel_mesh(1), epochs=1)
    fit_names = set(_names()) & per_step
    assert explicit_names == fit_names == per_step


# -- the operator's view: scripts/trace_report.py over a capture ---------------

XPLANE = """
planes { id: 1 name: "/device:TPU:0"
  lines { id: 1 name: "XLA Modules" timestamp_ns: 1000
    events { metadata_id: 10 offset_ps: 0 duration_ps: 10000000000 }
    events { metadata_id: 10 offset_ps: 20000000000 duration_ps: 10000000000 } }
  lines { id: 2 name: "XLA Ops" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 4000000000 duration_ps: 5000000000 }
    events { metadata_id: 3 offset_ps: 9000000000 duration_ps: 1000000000 }
    events { metadata_id: 1 offset_ps: 20000000000 duration_ps: 4000000000 }
    events { metadata_id: 2 offset_ps: 24000000000 duration_ps: 5000000000 }
    events { metadata_id: 3 offset_ps: 29000000000 duration_ps: 1000000000 } }
  event_metadata { key: 1 value { id: 1 name: "%fusion.1 = f32[4]{0} fusion(%a), kind=kLoop" } }
  event_metadata { key: 2 value { id: 2 name: "%fusion.4 = f32[4]{0} fusion(%a), kind=kLoop" } }
  event_metadata { key: 3 value { id: 3 name: "%copy.7 = f32[4]{0} copy(%a)" } }
  event_metadata { key: 10 value { id: 10 name: "jit_local_step(123)" } }
}
planes { id: 3 name: "/host:CPU"
  lines { id: 7 name: "python3" timestamp_ns: 1000
    events { metadata_id: 1 offset_ps: 0 duration_ps: 30000000000 }
    events { metadata_id: 2 offset_ps: 12000000000 duration_ps: 6000000000 }
    events { metadata_id: 3 offset_ps: 0 duration_ps: 1000000 } }
  event_metadata { key: 1 value { id: 1 name: "ddl:step" } }
  event_metadata { key: 2 value { id: 2 name: "ddl:data.stage_wait" } }
  event_metadata { key: 3 value { id: 3 name: "PjitFunction(step)" } }
}
"""


def _trace_report():
    import importlib.util
    import os

    path = os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "scripts", "trace_report.py",
    )
    spec = importlib.util.spec_from_file_location("trace_report", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_trace_report_prints_device_time_by_scope_and_idle_gaps_by_span():
    from jax.profiler import ProfileData

    profile = programs.from_profile_data(ProfileData.from_text_proto(XPLANE))
    assert sorted(n for n, _, _ in profile.host) == ["data.stage_wait", "step"]
    scopes = programs.parse_hlo_scopes(HLO)
    report = _trace_report()
    groups = TRAIN_STEP_GROUPS
    rep = report.device_report("capture", groups, profile, {"jit_local_step": scopes})
    (by,) = rep["programs"]
    assert by["runs"] == 2 and by["run_s"] == pytest.approx(0.020)
    assert by["groups"]["attn_core"]["seconds"] == pytest.approx(0.008)
    assert by["groups"]["optimizer"]["seconds"] == pytest.approx(0.010)
    assert by["unscoped_s"] == pytest.approx(0.002)
    # the one gap (10-20 ms) falls to the innermost span over its middle
    assert rep["idle"][0]["gaps"] == pytest.approx({"data.stage_wait": 0.010})
    text = report.render_device(rep)
    assert "program jit_local_step on 1 device(s): 2 run(s), 10.00 ms a run" in text
    assert "attn_core" in text and "unscoped" in text and "copy.7" in text
    assert "data.stage_wait 0.0100" in text
    assert "Mosaic kernels in the program, by group: none" in text
    # with no table beside the capture the gaps are still named
    bare = report.render_device(report.device_report("capture", groups, profile, {}))
    assert "no scope table" in bare and "data.stage_wait" in bare


def test_trace_controller_leaves_the_scope_tables_beside_a_capture(tmp_path, capsys):
    from distributeddeeplearning_tpu.obs import trace as obs_trace

    owner = _Owner()
    programs.register("jit_local_step", _Text(HLO), owner)
    ctrl = obs_trace.TraceController(str(tmp_path), every_n=1)
    assert ctrl.maybe_start(0)
    with obs.span("step"):
        pass
    assert ctrl.maybe_stop(0)
    capture = str(tmp_path / "trace-epoch0000")
    tables = programs.load_tables(capture)
    assert tables["jit_local_step"]["fusion.4"] == "jit(local_step)/optimizer/mul"
    report = _trace_report()
    assert report.find_captures([str(tmp_path)]) == [capture]
    assert report.main([str(tmp_path)]) == 0  # a bare capture directory
    out = capsys.readouterr().out
    assert "no device plane" in out and "step" in out


def test_the_resolved_attention_path_is_counted_at_trace_time_and_reported():
    """``ops/attention.resolve_impl`` counts what it chose, once a trace:
    ``bus.totals()`` holds it, the event carries the shape, and ``make
    trace-report`` prints it from a run's event files."""
    from distributeddeeplearning_tpu.models.vit import Attention

    attn = Attention(4, jnp.float32, "auto", causal=True)
    x = jnp.zeros((2, 16, 32), jnp.float32)
    variables = attn.init(jax.random.PRNGKey(0), x, False)
    obs.reset()
    jax.jit(lambda v, x: attn.apply(v, x, False))(variables, x)
    totals = obs.get_bus().totals()
    assert totals["attn.impl.xla"] == {"kind": "counter", "count": 1, "sum": 1.0}
    (event,) = [e for e in obs.get_bus().ring if e["name"] == "attn.impl.xla"]
    assert event["labels"] == {"asked": "auto", "shape": [2, 16, 32], "heads": 4}
    line = _trace_report().chosen_paths([event, dict(event)])
    assert line == "xla x2 at [2, 16, 32]"


def test_the_loss_path_is_reported_beside_the_attentions():
    """``loss.impl.<path>`` rides the same report line by its prefix."""
    from distributeddeeplearning_tpu.training.train_step import cross_entropy_loss

    obs.reset()
    logits = jnp.zeros((2, 16, 300), jnp.bfloat16)
    jax.jit(lambda l: cross_entropy_loss(l, jnp.zeros((2, 16), jnp.int32))).lower(logits)
    events = [e for e in obs.get_bus().ring if e["kind"] == "counter"]
    report = _trace_report()
    assert report.chosen_paths(events, "loss.impl.") == "xla x1 at [2, 16, 300]"
    assert report.chosen_paths(events) == ""  # no attention was traced
    obs.reset()


def test_the_flash_backward_is_reported_beside_the_attentions(tmp_path, capsys):
    """``attn.bwd.<path>`` (``ops/pallas/flash._flash_bwd_rule``, once a
    traced backward) rides the same report line by its prefix, and
    ``make trace-report`` prints it from a run's event files."""
    from distributeddeeplearning_tpu.ops.pallas.flash import flash_attention

    run = tmp_path / "run"
    obs.configure(str(run), install_handlers=False)
    try:
        x = jnp.zeros((1, 64, 2, 64))
        jax.clear_caches()  # the jitted core traces once a signature
        jax.jit(jax.grad(
            lambda q: jnp.sum(flash_attention(q, x, x, causal=True, interpret=True))
        )).lower(x)
        events = [e for e in obs.get_bus().ring if e["kind"] == "counter"]
        obs.flush()
    finally:
        obs.reset()
    report = _trace_report()
    assert report.chosen_paths(events, "attn.bwd.") == "fused x1 at [1, 64, 128]"
    assert report.main([str(run)]) == 0
    out = capsys.readouterr().out
    assert "attention backward, as chosen at trace time: fused x1 at [1, 64, 128]" in out


def test_a_mixed_layer_decoder_s_counters_ride_the_report(tmp_path, capsys):
    """What PR 31 counts at trace time — the mask a layer named, the
    window's walk, the layers built by kind, the router's input, the
    experts' gate — printed by ``make trace-report`` beside the rest."""
    from distributeddeeplearning_tpu.models import get_model

    run = tmp_path / "run"
    obs.configure(str(run), install_handlers=False)
    try:
        model = get_model(
            "smallthinker_tiny", num_classes=64, dtype="float32",
            attn_impl="pallas", layers=4,
        )
        tokens = jnp.zeros((1, 32), jnp.int32)
        params = model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
        jax.clear_caches()
        obs.get_bus().ring.clear()
        jax.jit(jax.grad(
            lambda p: jnp.sum(model.apply({"params": p}, tokens, train=True))
        )).lower(params)
        events = [e for e in obs.get_bus().ring if e["kind"] == "counter"]
        obs.flush()
    finally:
        obs.reset()
    report = _trace_report()
    assert report.chosen_paths(events, "attn.mask.") == "causal x1 at [], window x3 at []"
    assert report.chosen_paths(events, "decoder.layer.") == "full x1 at [0], window x3 at [8]"
    walk = report.chosen_paths(events, "attn.window.")
    assert "blocks x" in walk and "'forward'" in walk and "'backward'" in walk
    routed = [e for e in events if e["name"] == "moe.route.before_attention"]
    gated = [e for e in events if e["name"] == "moe.impl.ragged_dot"]
    assert len(routed) == len(gated) == 4
    assert {e["labels"]["activation"] for e in gated} == {"relu"}
    assert report.main([str(run)]) == 0
    out = capsys.readouterr().out
    for line in ("attention mask, as chosen", "window walk (pass, steps visited, skipped, window), as chosen",
                 "decoder layers, as chosen", "expert layer, as chosen"):
        assert line in out, line


@pytest.mark.parametrize(
    "name,rows,impl,line",
    [
        ("smallthinker_tiny", (1, 32), "pallas", "named x2 at [4, 32, 16, 0.0234375]"),
        ("granite_tiny", (1, 32), "pallas", "named x1 at [4, 32, 16, 0.0234375]"),
        ("sdar_tiny", (1, 64), "pallas", "named x3 at [4, 32, 16, 0.0234375]"),
        ("smallthinker_tiny", (1, 32), "xla", ""),
    ],
)
def test_what_the_flash_forward_names_rides_the_report(tmp_path, capsys, name, rows, impl, line):
    """``attn.fwd.named`` (``ops/pallas/flash._named``, where a forward
    rule is traced: the output's shape as the kernel writes it and the
    MiB of output and logsumexp that a remat policy keeping the names
    holds, as ``models/decoder.SpecDecoder``'s does; once a mask for the
    full and the window layers, whose jitted core is traced once a
    shape, and once a pass of block diffusion) printed by ``make
    trace-report``; the einsum path names nothing and prints nothing."""
    from distributeddeeplearning_tpu.models import get_model

    run = tmp_path / "run"
    jax.clear_caches()  # the jitted cores keep their traces
    obs.configure(str(run), install_handlers=False)
    try:
        model = get_model(name, num_classes=64, dtype="float32", attn_impl=impl, remat=True)
        tokens = jnp.zeros(rows, jnp.int32)
        params = jax.eval_shape(
            lambda: model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
        )
        jax.eval_shape(jax.grad(lambda p: jnp.sum(
            model.apply({"params": p}, tokens, train=True, mutable=["stats"])[0]
        )), params)
        events = [e for e in obs.get_bus().ring if e["kind"] == "counter"]
        obs.flush()
    finally:
        obs.reset()
    report = _trace_report()
    assert report.chosen_paths(events, "attn.fwd.") == line
    assert report.main([str(run)]) == 0
    printed = (
        "attention forward's results, named for block remat to keep (output's shape, MiB), "
        "as chosen at trace time: " + line
    )
    assert (printed in capsys.readouterr().out) == bool(line)


def test_the_expert_layer_s_live_share_rides_the_report(tmp_path, capsys):
    """What a step's expert layers sow and a log sync reads back
    (``frontends/explicit`` counts each under its name with the epoch):
    ``make trace-report`` prints the mean of the readings, since a share
    does not add up; the counters of trace time keep their own line, and
    there ``moe.rows.impl.<path>`` says what moved the rows."""
    from distributeddeeplearning_tpu.ops import moe

    run = tmp_path / "run"
    obs.configure(str(run), install_handlers=False)
    try:
        routed = moe.route_top_k(jax.random.normal(jax.random.PRNGKey(0), (64, 8)), 2)
        w = jnp.zeros((2, 128, 16))
        jax.jit(lambda x: moe.held_experts_ffn(
            x, routed, w, w, w.transpose(0, 2, 1), first=0, num_experts=8)[0]
        ).lower(jnp.zeros((64, 128)))
        for share in (0.5, 0.25):
            obs.counter("moe.rows_live_share", share, epoch=0)
        obs.counter("moe.pairs_local", 12288.0, epoch=0)
        events = [e for e in obs.get_bus().ring if e["kind"] == "counter"]
        obs.flush()
    finally:
        obs.reset()
    report = _trace_report()
    assert report.step_statistics(events) == (
        "moe.pairs_local 1.229e+04 (n=1), moe.rows_live_share 0.375 (n=2)"
    )
    assert "rows.impl.xla x1" in report.chosen_paths(events, "moe.")
    assert report.main([str(run)]) == 0
    out = capsys.readouterr().out
    assert "moe.rows_live_share 0.375 (n=2)" in out
    assert "expert layer, as chosen at trace time" in out and "rows.impl.xla" in out


@pytest.mark.parametrize("path", [
    "jit(local_step)/jvp(TransformerLM)/head/btd,vd->btv/dot_general",
    "jit(local_step)/jvp(loss)/reduce_max",
    "jit(local_step)/jvp(loss)/reduce_sum",
    "jit(local_step)/jvp(loss)/select_n",
    "jit(local_step)/transpose(jvp(loss))/mul",
    "jit(local_step)/metrics/reduce_sum",
])
def test_what_the_loss_runs_counts_as_head_loss(path):
    assert programs.group_of(path, TRAIN_STEP_GROUPS) == "head_loss"


def test_the_compiled_loss_stands_whole_under_head_loss(compiled_step):
    """Every instruction of the compiled lm_tiny step that the ``loss``
    scope names, forward and backward, falls in ``head_loss``, the
    reductions the loss now makes itself among them (the rows' maximum,
    the sums); it gathers nothing, and ``metrics`` holds no argmax over
    the logits: ``head_loss_device_ms.train`` reads the whole of it."""
    _, (table,), _ = compiled_step
    paths = set(table.scopes().values())
    of_loss = {p for p in paths if "(loss)" in p or "/loss/" in p}
    assert {programs.group_of(p, TRAIN_STEP_GROUPS) for p in of_loss} == {"head_loss"}
    assert any(programs.BACKWARD in p for p in of_loss)
    assert any(p.endswith("/reduce_max") for p in of_loss)
    assert any(p.endswith("/reduce_sum") for p in of_loss)
    assert not [p for p in of_loss if "gather" in p or "scatter" in p]
    assert not [p for p in paths if "/metrics/" in p and "argmax" in p]


KERNEL_HLO = """ENTRY %main () -> f32[4] {
  %_attention_core.6 = f32[4]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(local_step)/transpose(jvp(TransformerLM))/block0/attn/attn_core/jit(_attention_core)/pallas_call"}
  %copy.8 = f32[4]{0} copy(%_attention_core.6), metadata={op_name="jit(local_step)/transpose(jvp(TransformerLM))/block0/attn/attn_core/jit(_attention_core)/pallas_call"}
  %custom-call.2 = f32[4]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(local_step)/fused_grads/pallas_call"}
  %custom-call.3 = f32[4]{0} custom-call(%a), custom_call_target="Sharding", metadata={op_name="jit(local_step)/jvp(TransformerLM)/block0/mlp/sharding_constraint"}
}"""


def test_kernel_calls_by_group_counts_the_mosaic_custom_calls_alone():
    """A kernel's copies and tuple elements share its ``op_name``; the
    parser marks the custom call itself, and the mark rides through the
    dump that ``scripts/trace_report.py`` reads."""
    scopes = programs.parse_hlo_scopes(KERNEL_HLO)
    assert scopes["_attention_core.6"].endswith("/pallas_call/" + programs.KERNEL_CALL)
    assert scopes["copy.8"].endswith("/pallas_call")
    assert programs.kernel_calls_by_group(scopes, TRAIN_STEP_GROUPS) == {
        "attn_core": 1, programs.UNSCOPED: 1,
    }
    assert programs.group_of(scopes["_attention_core.6"], TRAIN_STEP_GROUPS) == "attn_core"
    assert programs.BACKWARD in scopes["_attention_core.6"]


# -- a state-space mixer's parts (models/decoder.py, ops/ssm.py) ----------------

SSM_HLO = """HloModule jit_local_step

ENTRY %main () -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/ssm/in_proj/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/ssm/ssm_conv/add"}
  %fusion.3 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/transpose(jvp(SpecDecoder))/block0/ssm/ssm_scan/while/body/checkpoint/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/mlp/w_in/dot_general"}
  %fusion.5 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block5/attn/attn_core/attn_full/dot_general"}
  ROOT %fusion.6 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/optimizer/mul"}
}
"""


def test_the_state_space_mixer_is_a_group_of_its_own_table_and_splits_in_three():
    """``HYBRID_STEP_GROUPS`` puts module ``ssm`` before the groups every
    decoder has (which then read what they read); ``SSM_GROUPS`` splits
    it by its two scopes and what is left of the module. Under
    ``TRAIN_STEP_GROUPS`` alone the mixer would be unscoped: a new part
    of the model gets a name, not a wider pattern."""
    from distributeddeeplearning_tpu.models.decoder import (
        HYBRID_STEP_GROUPS,
        SSM_GROUPS,
    )

    assert HYBRID_STEP_GROUPS[0][0] == "ssm" and HYBRID_STEP_GROUPS[1:] == tuple(TRAIN_STEP_GROUPS)
    scopes = programs.parse_hlo_scopes(SSM_HLO)
    step = lambda path: programs.group_of(path, HYBRID_STEP_GROUPS)  # noqa: E731
    assert [step(scopes[f"fusion.{i}"]) for i in range(1, 7)] == [
        "ssm", "ssm", "ssm", "mlp", "attn_core", "optimizer"]
    part = lambda path: programs.group_of(path, SSM_GROUPS)  # noqa: E731
    assert [part(scopes[f"fusion.{i}"]) for i in range(1, 7)] == [
        "ssm_proj", "ssm_conv", "ssm_scan", "unscoped", "unscoped", "unscoped"]
    assert programs.group_of(scopes["fusion.1"], TRAIN_STEP_GROUPS) == "unscoped"
    assert programs.groups_in(scopes, SSM_GROUPS) == {g for g, _ in SSM_GROUPS}
    ops = [(f"fusion.{i}", (i - 1) * MS, i * MS) for i in range(1, 7)]
    by = programs.device_seconds_by_scope(ops, scopes, SSM_GROUPS)
    assert by["groups"]["ssm_scan"]["seconds"] == pytest.approx(1e-3)
    assert by["groups"]["ssm_scan"]["backward_s"] == pytest.approx(1e-3)
    assert by["unscoped_s"] == pytest.approx(3e-3)


def test_a_program_without_the_mixer_reads_nothing_under_its_tables():
    """The steps that were there have no ``ssm`` scope: their tables
    lack the group, so a reader that asks for a table's every group
    (``benchmarks/programs/obs.py``) reports nothing, and the operator's
    report falls back to the groups they have."""
    from distributeddeeplearning_tpu.models.decoder import (
        HYBRID_STEP_GROUPS,
        SSM_GROUPS,
    )

    scopes = programs.parse_hlo_scopes(HLO)
    assert programs.groups_in(scopes, SSM_GROUPS) == set()
    assert "ssm" not in programs.groups_in(scopes, HYBRID_STEP_GROUPS)
    report = _trace_report()
    assert report.step_groups("capture", {"jit_local_step": scopes}) is TRAIN_STEP_GROUPS
    hybrid = {"jit_local_step": programs.parse_hlo_scopes(SSM_HLO)}
    assert report.step_groups("capture", hybrid) is HYBRID_STEP_GROUPS


def test_the_scan_s_choice_and_the_layers_built_are_reported():
    """``ops/ssm.resolve_impl`` counts what it chose once a traced layer
    (``ssm.impl.xla`` with the call's shape, chunk and padding) and
    ``SpecDecoder`` each state-space layer it builds (``decoder.layer.
    mamba2``): ``bus.totals()`` holds both and ``make trace-report``
    prints them beside the attention's and the expert layer's."""
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models import get_model

    obs.reset()
    model = get_model("granite_tiny", num_classes=64, dtype="float32", attn_impl="xla")
    jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), jnp.zeros((2, 27), jnp.int32), train=False)
    )
    totals = obs.get_bus().totals()
    events = list(obs.get_bus().ring)
    obs.reset()
    assert totals["decoder.layer.mamba2"]["count"] == 4
    assert totals["decoder.layer.full"]["count"] == 1
    assert totals["ssm.impl.xla"]["count"] == 4
    scan = next(e for e in events if e["name"] == "ssm.impl.xla")
    assert scan["labels"] == {
        "shape": [2, 27, 4, 16], "heads": 4, "head_dim": 16, "state": 16,
        "chunk": 8, "chunks": 4, "padded": 5, "head_block": 0}
    layer = next(e for e in events if e["name"] == "decoder.layer.mamba2")
    assert layer["labels"] == {"layer": 0, "heads": 4, "state": 16, "chunk": 8}
    report = _trace_report()
    assert report.chosen_paths(events, "ssm.impl.") == "xla x4 at [2, 27, 4, 16]"
    assert "mamba2 x4" in report.chosen_paths(events, "decoder.layer.")


def test_a_capture_that_holds_both_of_the_scan_s_paths_reports_both(monkeypatch):
    """What the Granite cell's run leaves on the bus: the weight draw's
    scans on the XLA form (``initializing``), the step's on the kernels
    (the rule answered as on the chip; ``eval_shape`` traces and runs
    nothing), and a traced backward counted ``ssm.bwd.pallas``. The
    report's line for the state-space scan names both paths with their
    shapes."""
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.ops import ssm

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(jax, "device_count", lambda: 1)
    obs.reset()
    sizes = dict(xs=(2, 512, 16, 64), dt=(2, 512, 16), a=(16,), b=(2, 512, 1, 128), d=(16,))
    like = lambda name: jax.ShapeDtypeStruct(sizes[name], jnp.float32)  # noqa: E731
    args = tuple(like(n) for n in ("xs", "dt", "a", "b", "b", "d"))
    for initializing in (True, True, False):
        jax.eval_shape(
            lambda *v, i=initializing: jax.grad(
                lambda *w: jnp.sum(ssm.ssd_scan(*w, chunk=256, initializing=i))
            )(*v), *args,
        )
    events = list(obs.get_bus().ring)
    totals = obs.get_bus().totals()
    obs.reset()
    assert totals["ssm.impl.xla"]["count"] == 2 and totals["ssm.impl.pallas"]["count"] == 1
    report = _trace_report()
    assert report.chosen_paths(events, "ssm.impl.") == (
        "pallas x1 at [2, 512, 16, 64], xla x2 at [2, 512, 16, 64]"
    )
    assert report.chosen_paths(events, "ssm.bwd.") == "pallas x1 at [2, 512, 16, 64]"


# -- the step by pass: forward, remat's recomputed forward, backward ----------

_FWD = "jit(local_step)/jvp(SpecDecoder)"
_BWD = "jit(local_step)/transpose(jvp(SpecDecoder))/jvp(SpecDecoder)/checkpoint"
_REPLAY = _BWD + "/rematted_computation"
PASS_PATHS = {
    # the forward, a loop of it, and the loss
    "fusion.1": _FWD + "/block0/attn/attn_core/attn_full/jit(_core)/pallas_call",
    "while.2": _FWD + "/block0/ssm/ssm_scan/while",
    "fusion.3": _FWD + "/block0/ssm/ssm_scan/while/body/closed_call/checkpoint/dot_general",
    "fusion.4": "jit(local_step)/jvp(loss)/reduce_max",
    # block remat's replay: a kernel, a product, the scan's loop
    "kernel.5": _REPLAY + "/block0/attn/attn_core/attn_full/jit(_core)/pallas_call",
    "fusion.6": _REPLAY + "/block0/mlp/w_in/dot_general",
    "fusion.7": _REPLAY + "/block0/ssm/ssm_scan/while/body/closed_call/dot_general",
    # the backward's own loop replays its checkpointed body: a nested mark
    "while.8": _BWD + "/block0/ssm/ssm_scan/while",
    "fusion.9": _BWD + "/block0/ssm/ssm_scan/while/body/closed_call/checkpoint/"
                "rematted_computation/dot_general",
    "fusion.10": _BWD + "/block0/ssm/ssm_scan/while/body/closed_call/checkpoint/transpose",
    "kernel.11": _BWD + "/block0/attn/attn_core/attn_full/jit(_core)/pallas_call",
    "fusion.12": "jit(local_step)/transpose(jvp(loss))/sub",
    # neither: the optimizer, the metrics, and a copy the compiler put in
    "fusion.13": "jit(local_step)/optimizer/mul",
    "fusion.14": "jit(local_step)/metrics/reduce_sum",
}
PASS_HLO = "ENTRY %main () -> f32[4] {\n" + "\n".join(
    f"  %{name} = f32[4]{{0}} " + (
        'custom-call(%a), custom_call_target="tpu_custom_call"' if name.startswith("kernel")
        else "fusion(%a)"
    ) + f', metadata={{op_name="{path}"}}'
    for name, path in PASS_PATHS.items()
) + "\n  %copy.15 = f32[4]{0} copy(%a)\n}"
PASS_OF = {
    "forward": ["fusion.1", "while.2", "fusion.3", "fusion.4"],
    "recompute": ["kernel.5", "fusion.6", "fusion.7", "fusion.9"],
    "backward": ["while.8", "fusion.10", "kernel.11", "fusion.12"],
    "other": ["fusion.13", "fusion.14", "copy.15"],
}


@pytest.mark.parametrize("path,expect", [
    ("jit(f)/jvp(block0)/attn/dot_general", "forward"),
    ("jit(f)/transpose(jvp(block0))/jvp(block0)/checkpoint/mlp/mul", "backward"),
    ("jit(f)/transpose(jvp(block0))/jvp(block0)/checkpoint/rematted_computation/mlp/sub",
     "recompute"),
    # flax's nn.remat: the module path after the mark
    ("jit(local_step)/transpose(jvp(SpecDecoder))/jvp(SpecDecoder)/checkpoint/"
     "rematted_computation/block0/mlp/w_in/dot_general", "recompute"),
    # a checkpointed scan body inside the backward's loop
    ("jit(f)/transpose(jvp(M))/ssm_scan/while/body/closed_call/checkpoint/"
     "rematted_computation/mul", "recompute"),
    ("jit(f)/rematted_computation", "recompute"),
    # a whole component, not a scope someone named alike
    ("jit(f)/jvp(M)/my_rematted_computation_notes/add", "forward"),
    ("jit(local_step)/optimizer/mul", "other"),
    ("jit(local_step)/metrics/reduce_sum", "other"),
    ("jit(decode)/block0/attn/attn_core/pallas_call", "other"),
    ("", "other"), (None, "other"),
])
def test_pass_of_reads_the_marks_the_transforms_leave(path, expect):
    assert programs.pass_of(path) == expect
    assert expect in programs.PASSES


def test_the_reduction_tells_the_four_passes_apart_and_they_add_up():
    """Hand-made events over hand-made paths of all four passes: each
    instruction its number of milliseconds, the loops over their bodies
    (a loop keeps what its body leaves, in the loop's own pass)."""
    from distributeddeeplearning_tpu.models.decoder import HYBRID_STEP_GROUPS

    scopes = programs.parse_hlo_scopes(PASS_HLO)
    assert {n: programs.pass_of(scopes.get(n)) for ns in PASS_OF.values() for n in ns} == {
        n: p for p, ns in PASS_OF.items() for n in ns}
    events, t = [], 0
    for name in list(PASS_PATHS) + ["copy.15"]:
        ms = int(name.split(".")[1])
        events.append((name, t, t + ms * MS))
        t += ms * MS
    # the loops cover their bodies: while.2 over fusion.3, while.8 over 9 and 10
    events[1] = ("while.2", events[1][1], events[2][2])
    events[7] = ("while.8", events[7][1], events[9][2])
    by = programs.device_seconds_by_scope(events, scopes, HYBRID_STEP_GROUPS)
    expect = {p: sum(int(n.split(".")[1]) for n in ns) / 1e3 for p, ns in PASS_OF.items()}
    assert by["by_pass"] == pytest.approx(expect)
    assert list(by["by_pass"]) == list(programs.PASSES)
    assert sum(by["by_pass"].values()) == pytest.approx(by["total_s"]) == pytest.approx(0.120)
    groups = by["groups"]
    # today's backward_s: everything under transpose(jvp(, the replay with it
    assert groups["ssm"]["backward_s"] == pytest.approx(0.034)  # 7 + 8 + 9 + 10
    assert groups["ssm"]["recompute_s"] == pytest.approx(0.016)  # outer 7, inner 9
    assert groups["ssm"]["forward_s"] == pytest.approx(0.005)
    assert groups["attn_core"] == pytest.approx(
        {"seconds": 0.017, "forward_s": 0.001, "recompute_s": 0.005, "backward_s": 0.016})
    assert groups["mlp"] == pytest.approx(
        {"seconds": 0.006, "forward_s": 0.0, "recompute_s": 0.006, "backward_s": 0.006})
    assert groups["head_loss"] == pytest.approx(  # the metrics' 14 ms in no pass
        {"seconds": 0.030, "forward_s": 0.004, "recompute_s": 0.0, "backward_s": 0.012})
    assert groups["optimizer"] == pytest.approx(
        {"seconds": 0.013, "forward_s": 0.0, "recompute_s": 0.0, "backward_s": 0.0})
    for g in groups.values():
        assert g["recompute_s"] <= g["backward_s"] <= g["seconds"]
    in_groups = {
        "forward": sum(g["forward_s"] for g in groups.values()),
        "recompute": sum(g["recompute_s"] for g in groups.values()),
        "backward": sum(g["backward_s"] - g["recompute_s"] for g in groups.values()),
    }
    assert in_groups == pytest.approx({p: expect[p] for p in in_groups})  # nothing unscoped in them
    assert by["unscoped_s"] == pytest.approx(0.015)  # the copy, in `other`
    # the kernels by pass: the replayed forward is the one under `recompute`
    assert programs.kernel_calls_by_pass(scopes, HYBRID_STEP_GROUPS) == {
        "attn_core": {"forward": 0, "recompute": 1, "backward": 1, "other": 0}}
    assert programs.kernel_calls_by_group(scopes, HYBRID_STEP_GROUPS) == {"attn_core": 2}


def test_program_by_scope_adds_the_passes_up_over_runs_and_devices():
    scopes = programs.parse_hlo_scopes(PASS_HLO)
    one = [("fusion.1", 0, MS), ("kernel.5", MS, 3 * MS), ("kernel.11", 3 * MS, 6 * MS),
           ("fusion.13", 6 * MS, 7 * MS), ("copy.15", 7 * MS, 8 * MS)]
    ops = {0: one + [(n, a + 10 * MS, b + 10 * MS) for n, a, b in one], 1: one}
    modules = {0: [("jit_local_step(7)", 0, 8 * MS), ("jit_local_step(7)", 10 * MS, 18 * MS)],
               1: [("jit_local_step(7)", 0, 8 * MS)]}
    by = programs.program_by_scope(ops, modules, "jit_local_step", scopes, TRAIN_STEP_GROUPS)
    assert by["runs"] == 3 and by["total_s"] == pytest.approx(0.024)
    assert by["by_pass"] == pytest.approx(
        {"forward": 0.003, "recompute": 0.006, "backward": 0.009, "other": 0.006})
    assert by["groups"]["attn_core"] == pytest.approx(
        {"seconds": 0.018, "forward_s": 0.003, "recompute_s": 0.006, "backward_s": 0.015})


def test_trace_report_prints_groups_by_pass_the_program_by_pass_and_the_kernels():
    """What ``make trace-report`` shows a trainer who turned ``remat``
    on: each group's forward, recomputed forward and backward, the whole
    program by pass, and whether a kernel is among what runs again."""
    from distributeddeeplearning_tpu.models.decoder import HYBRID_STEP_GROUPS

    scopes = programs.parse_hlo_scopes(PASS_HLO)
    ops = [(n, i * MS, (i + 1) * MS) for i, n in enumerate(list(PASS_PATHS) + ["copy.15"])]
    ops = [e for e in ops if not e[0].startswith("while")]
    profile = programs.Profile(
        ops={0: ops}, modules={0: [("jit_local_step(1)", 0, 15 * MS)]}, host=[])
    report = _trace_report()
    tables = {"jit_local_step": scopes}
    assert report.step_groups("capture", tables) is HYBRID_STEP_GROUPS
    rep = report.device_report("capture", HYBRID_STEP_GROUPS, profile, tables)
    (by,) = rep["programs"]
    assert by["kernel_calls"]["attn_core"]["recompute"] == 1
    text = report.render_device(rep)
    lines = {line.split()[0]: line.split() for line in text.splitlines() if line.strip()}
    assert lines["group"] == ["group", "ms/run", "share", "forward", "recompute", "backward"]
    assert lines["ssm"][1:] == ["4.000", "30.8%", "1.000", "2.000", "1.000"]
    assert lines["attn_core"][1:] == ["3.000", "23.1%", "1.000", "1.000", "1.000"]
    assert lines["optimizer"][1:] == ["1.000", "7.7%", "0.000", "0.000", "0.000"]
    assert lines["program"][1:9] == [
        "13.000", "100.0%", "3.000", "4.000", "3.000", "and", "3.000", "in"]
    assert ("Mosaic kernels in the program, by group: "
            "attn_core 0 forward / 1 recompute / 1 backward") in text


def _spec_step_scopes(name, impl, remat):
    """The scope table of a small spec-built decoder's gradient,
    compiled here (the flash kernels in interpret mode, where a kernel's
    grid is a ``while`` under its jitted core)."""
    from distributeddeeplearning_tpu.models import get_model

    jax.clear_caches()  # the jitted cores keep their traces
    model = get_model(name, num_classes=64, dtype="float32", attn_impl=impl, remat=remat)
    tokens = jnp.zeros((1, 64 if name == "sdar_tiny" else 32), jnp.int32)
    params = jax.eval_shape(
        lambda: model.init(jax.random.PRNGKey(0), tokens, train=False)["params"]
    )

    def objective(p, tokens):
        return jnp.sum(model.apply({"params": p}, tokens, train=True, mutable=["stats"])[0])

    compiled = jax.jit(jax.grad(objective)).lower(params, tokens).compile()
    return programs.parse_hlo_scopes(compiled.as_text())


@pytest.mark.parametrize("name,impl,remat,recomputed,replays_the_kernel", [
    ("gpt2_tiny", "xla", False, set(), False),
    ("gpt2_tiny", "xla", True, {"attn_core", "attn_proj", "mlp", "norm_residual"}, False),
    # a causal layer under block remat keeps what the flash forward wrote
    ("smallthinker_tiny", "pallas", True,
     {"attn_core", "attn_proj", "mlp", "norm_residual"}, False),
    # a block-diffusion layer keeps nothing: its three passes run again
    ("sdar_tiny", "pallas", True, {"attn_core", "attn_proj", "mlp", "norm_residual"}, True),
])
def test_a_spec_decoder_recomputes_its_blocks_under_remat_alone(
    name, impl, remat, recomputed, replays_the_kernel
):
    """``SpecDecoder`` compiled with ``remat`` on and off: the table
    holds ``recompute`` instructions only when on, under the groups a
    block has (never the head's, the loss's or the optimizer's); the
    forward and the backward are there either way; and with the policy
    of a causal spec the flash forward is not among what runs again."""
    compiled = [(g, re.compile(p)) for g, p in TRAIN_STEP_GROUPS]
    by: dict = {}
    kernel_grids = {p: 0 for p in programs.PASSES}
    for path in set(_spec_step_scopes(name, impl, remat).values()):
        group, in_pass = programs.group_of(path, compiled), programs.pass_of(path)
        by.setdefault(in_pass, set()).add(group)
        if group == "attn_core" and "/while/body/" in path:
            kernel_grids[in_pass] += 1
    assert by.get("recompute", set()) - {programs.UNSCOPED} == recomputed
    assert {"attn_core", "attn_proj", "mlp", "norm_residual"} <= by["forward"] & by["backward"]
    assert "head_loss" in by["forward"] | by["backward"]
    if impl == "pallas":
        assert kernel_grids["forward"] and kernel_grids["backward"]
        assert bool(kernel_grids["recompute"]) == replays_the_kernel
