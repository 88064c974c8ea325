"""The step's device time by pass (reader ``scope_pass_device_ms`` over
``obs/programs.pass_of``): the nine metrics on a hand-made run whose
step holds a forward, block remat's recomputed forward, a backward and
what belongs to none; on a step without remat, which recomputes
nothing and says 0.0; and against a program, a trace or a table that
gives the reader nothing to tell the passes by."""

import importlib
import json
import os
import sys

import pytest

from benchmarks import harness, tracing
from benchmarks.programs import obs as program_obs

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_benchmark_manifest import REAL  # noqa: E402

CELLS = ["gpt2-train-t1024", "sdar-30b-a3b-train-bd4k"]
TABLE = "distributeddeeplearning_tpu.models.transformer_lm:TRAIN_STEP_GROUPS"
# metric -> (pass, group or None, ms a step of the hand-made remat step below)
PASSES = {
    "step_forward_device_ms.train": ("forward", None, 4.0),
    "step_recompute_device_ms.train": ("recompute", None, 5.0),
    "step_backward_device_ms.train": ("backward", None, 3.0),
    "step_pass_other_device_ms.train": ("other", None, 2.0),
    "attn_core_forward_device_ms.train": ("forward", "attn_core", 1.0),
    "attn_core_recompute_device_ms.train": ("recompute", "attn_core", 2.0),
    "attn_core_backward_device_ms.train": ("backward", "attn_core", 3.0),
    "attn_proj_recompute_device_ms.train": ("recompute", "attn_proj", 1.0),
    "mlp_recompute_device_ms.train": ("recompute", "mlp", 2.0),
}
RECOMPUTE = [name for name, (which, _, _) in PASSES.items() if which == "recompute"]

FWD = "jit(local_step)/jvp(SpecDecoder)"
BWD = "jit(local_step)/transpose(jvp(SpecDecoder))/checkpoint"
# instruction -> (path, ms a step); a pathless copy rides behind them
REMAT_STEP = {
    "fusion.1": (f"{FWD}/block0/attn/attn_core/pallas_call", 1),
    "fusion.2": (f"{BWD}/rematted_computation/block0/attn/attn_core/pallas_call", 2),
    "fusion.3": (f"{BWD}/block0/attn/attn_core/pallas_call", 3),
    "fusion.4": (f"{BWD}/rematted_computation/block0/attn/qkv/dot_general", 1),
    "fusion.5": (f"{BWD}/rematted_computation/block0/mlp/w_in/dot_general", 2),
    "fusion.6": (f"{FWD}/block0/mlp/w_in/dot_general", 1),
    "fusion.7": (f"{FWD}/block0/ln2/mul", 1),
    "fusion.8": ("jit(local_step)/jvp(loss)/reduce_max", 1),
    "fusion.9": ("jit(local_step)/optimizer/mul", 1),
}
PLAIN_STEP = {
    k: (path.replace("/rematted_computation", "").replace("/checkpoint", ""), ms)
    for k, (path, ms) in REMAT_STEP.items()
}
MS = 1_000_000


class _Compiled:
    def __init__(self, step):
        self.step = step

    def as_text(self):
        lines = [
            f'  %{name} = f32[4]{{0}} fusion(%a), metadata={{op_name="{path}"}}'
            for name, (path, _) in self.step.items()
        ]
        return "\n".join(
            ["HloModule jit_local_step", "", "ENTRY %main () -> f32[4] {"] + lines
            + ["  ROOT %copy.10 = f32[4]{0} copy(%a)", "}"]
        )


def _events(step, t0):
    out, t = [], t0
    for name, ms in [(k, ms) for k, (_, ms) in step.items()] + [("copy.10", 1)]:
        out.append((name, t, t + ms * MS))
        t += ms * MS
    return out


def _run(step):
    """Two whole runs of ``step`` in the traced window, a third cut by
    its end, and another program's run after it."""
    programs = pytest.importorskip("distributeddeeplearning_tpu.obs.programs")
    programs.clear()
    programs.register("jit_local_step", _Compiled(step), _Compiled)  # an owner that lives on
    starts = (0, 20 * MS, 40 * MS)
    ops = [e for t in starts for e in _events(step, t)]
    ops.append(("fusion.2", 80 * MS, 85 * MS))
    modules = [("jit_local_step(7)", t, t + 14 * MS) for t in starts]
    modules.append(("jit_local_step_acc(9)", 80 * MS, 85 * MS))
    trace = tracing.Trace(
        ops={0: ops}, modules={0: modules}, host=[("traced_window", 0, 50 * MS)],
    )
    return {"trace": trace, "window": (0.0, 1.0)}


def _fixture(step):
    @pytest.fixture
    def run():
        yield _run(step)
        importlib.import_module("distributeddeeplearning_tpu.obs.programs").clear()

    return run


remat, plain = _fixture(REMAT_STEP), _fixture(PLAIN_STEP)


def _spec(name):
    with open(os.path.join(harness.HERE, "metrics", name + ".json")) as fh:
        return json.load(fh)


def _read(name, run):
    spec = _spec(name)
    return importlib.import_module(f"benchmarks.readers.{spec['reader']}").read(run, spec)


# -- the manifest's entries and the metric files --------------------------------

@pytest.mark.parametrize("name", PASSES)
def test_the_entry_names_a_file_a_reader_and_a_table_that_exist(name):
    entry = next(m for m in REAL["per_layer"] if m["name"] == name)
    assert entry == {
        "name": name, "unit": "ms", "better": "lower", "source": "device_trace",
        "layer": "model step", "moves": "train_items_per_s_per_chip", "workloads": CELLS,
    }
    spec = _spec(name)
    which, group, _ = PASSES[name]
    assert (spec["reader"], spec["match"], spec["groups"]) == (
        "scope_pass_device_ms", "jit_local_step", TABLE)
    assert (spec["pass"], spec.get("group")) == (which, group)
    importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    module, _, attribute = spec["groups"].partition(":")
    groups = getattr(importlib.import_module(module), attribute)
    assert group is None or group in {g for g, _ in groups}


def test_the_nine_are_the_manifest_s_last_entries_and_nothing_else_reads_the_passes():
    assert [m["name"] for m in REAL["per_layer"][-9:]] == list(PASSES)
    readers = set()
    for m in REAL["per_layer"]:
        if _spec(m["name"])["reader"] == "scope_pass_device_ms":
            readers.add(m["name"])
    assert readers == set(PASSES)


# -- the reader on a hand-made run -----------------------------------------------

@pytest.mark.parametrize("name", PASSES)
def test_a_step_under_block_remat_reads_each_pass(remat, name):
    assert _read(name, remat) == pytest.approx(PASSES[name][2])
    by = remat["_by_scope"]["jit_local_step"]
    # the reduction the other scope metrics of the program share
    assert by is program_obs.step_by_scope(remat, _spec("attn_core_device_ms.train"))
    assert by["runs"] == 2 and by["total_s"] == pytest.approx(0.028)


def test_the_four_passes_add_up_to_the_step_and_the_three_to_the_group(remat):
    step = [n for n, (_, group, _) in PASSES.items() if group is None]
    assert sum(_read(n, remat) for n in step) == pytest.approx(14.0)
    core = [n for n, (_, group, _) in PASSES.items() if group == "attn_core"]
    assert sum(_read(n, remat) for n in core) == pytest.approx(
        _read("attn_core_device_ms.train", remat))
    by = remat["_by_scope"]["jit_local_step"]
    for g in by["groups"].values():  # backward_s means what it meant
        assert g["recompute_s"] <= g["backward_s"] <= g["seconds"]
    assert by["groups"]["attn_core"]["backward_s"] == pytest.approx(0.010)


@pytest.mark.parametrize("name", PASSES)
def test_a_step_without_remat_recomputes_nothing_and_says_so(plain, name):
    which, group, _ = PASSES[name]
    expect = {
        ("forward", None): 4.0, ("recompute", None): 0.0, ("backward", None): 8.0,
        ("other", None): 2.0, ("forward", "attn_core"): 1.0,
        ("recompute", "attn_core"): 0.0, ("backward", "attn_core"): 5.0,
        ("recompute", "attn_proj"): 0.0, ("recompute", "mlp"): 0.0,
    }[which, group]
    value = _read(name, plain)
    assert value == pytest.approx(expect) and value is not None
    if name in RECOMPUTE:
        assert value == 0.0


@pytest.mark.parametrize("name", PASSES)
def test_a_reduction_from_before_the_passes_reads_nothing(remat, monkeypatch, name):
    """The driver lays these files over the parent's checkout too: its
    ``program_by_scope`` gives ``seconds`` and ``backward_s`` a group and
    no ``by_pass``, and the line leaves the metric out."""
    from distributeddeeplearning_tpu.obs import programs

    new = programs.program_by_scope

    def old(*args, **kw):
        by = new(*args, **kw)
        by.pop("by_pass")
        for g in by["groups"].values():
            del g["forward_s"], g["recompute_s"]
        return by

    monkeypatch.setattr(programs, "program_by_scope", old)
    assert _read(name, remat) is None
    assert _read("attn_core_device_ms.train", remat) == pytest.approx(6.0)


def test_nothing_is_read_without_a_trace_a_table_or_a_sound_table(remat):
    from distributeddeeplearning_tpu.obs import programs

    assert [_read(n, {"trace": None}) for n in PASSES] == [None] * 9
    programs.clear()  # the program was never compiled ahead
    remat.pop("_by_scope", None)
    assert [_read(n, remat) for n in PASSES] == [None] * 9
    # another tree's names (an executable out of a cache that tree filled)
    remat.pop("_by_scope")
    stale = {k: (p.replace("/attn_core", ""), ms) for k, (p, ms) in REMAT_STEP.items()}
    programs.register("jit_local_step", _Compiled(stale), _Compiled)
    assert [_read(n, remat) for n in PASSES] == [None] * 9


def test_the_two_cells_report_the_nine_and_the_other_two_what_they_reported():
    def names(cell):
        return harness.load_cell(cell, 0, 1.0, True, 0.0).metric_names("per_layer")

    for cell in CELLS:
        assert set(PASSES) <= set(names(cell))
    # pinned by tests/bench/test_benchmark_{smallthinker,granite}.py: the
    # ledger's PR 34 lines hold 23 and 19 per-layer names
    assert len(names("smallthinker-21b-a3b-train-t16k")) == 23
    assert len(names("granite-4.0-h-micro-train-t4k")) == 19
