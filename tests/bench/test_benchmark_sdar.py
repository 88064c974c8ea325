"""The ``sdar`` family through the harness on the CPU: a tiny
configuration rehearses ``sdar-30b-a3b-train-bd4k`` (the trainer's
normal path with the block-diffusion objective, the plain reference, the
routing readings of ``runners/train_routed``), the cell's two controls
come out as not correct, and the readers this family brought are run on
hand-made data."""

import json
import os
import time

import pytest

from benchmarks import harness
from benchmarks.readers import kernel_roofline, obs_counter_mean, scope_group_device_ms

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "sdar-30b-a3b-train-bd4k"


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    for c in m["configs"]:
        if c["name"] == "sdar-30b-a3b-chat":
            c["file"] = os.path.join(DATA, "configs", "sdar-tiny.json")
    path = os.path.join(str(tmp_path_factory.mktemp("sdar")), "manifest.json")
    with open(path, "w") as fh:
        json.dump(m, fh)
    return path


def _run(manifest, **kw):
    cell = harness.load_cell(
        CELL, 2**31 + 5, 1.0, False, time.monotonic(),
        manifest_path=manifest, require_chip=False, **kw,
    )
    return harness.run_cell(cell)


@pytest.fixture(scope="module")
def result(manifest):
    return _run(manifest)


def test_the_cell_rehearses_on_the_cpu(result):
    r = json.loads(json.dumps(result))
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "train_items_per_s_per_chip"}
    assert set(r["checks"]) >= {
        "loss_gap", "grad_norm_gap", "delta_norm_gap",
        "routing_differ_share", "pairs_held_gap",
    }
    assert all(c["limit"] is not None for k, c in r["checks"].items()
               if k != "leaves_left_out")


def test_float32_program_and_reference_choose_the_same_experts(result):
    assert result["checks"]["routing_differ_share"]["value"] == 0.0
    assert result["checks"]["pairs_held_gap"]["value"] == 0.0


@pytest.mark.parametrize("control", ["reference", "half_batch"])
def test_a_control_is_not_correct(manifest, control):
    r = _run(manifest, control=control)
    assert r["correct"] is False
    # the program's own readings ride beside the control's, and pass
    assert r["checks"]["program_loss_gap"]["value"] < 1e-4


def test_the_new_metric_files_name_readers_and_tables_that_exist():
    import importlib

    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    mine = [p["name"] for p in m["per_layer"] if p["workloads"] == [CELL]]
    assert len(mine) == 14
    for name in mine:
        with open(os.path.join(ROOT, "benchmarks", "metrics", name + ".json")) as fh:
            spec = json.load(fh)
        importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        if "groups" in spec:
            module, _, attribute = spec["groups"].partition(":")
            groups = getattr(importlib.import_module(module), attribute)
            assert spec.get("group", groups[0][0]) in {g for g, _ in groups}


# -- the family's readers on a hand-made run ----------------------------------

HLO = """HloModule jit_local_step

ENTRY %main () -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/attn/attn_core/jit(_stats_core)/pallas_call"}
  %fusion.2 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/attn/q/dot_general"}
  %fusion.3 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/mlp/moe_route/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/mlp/checkpoint/moe_dispatch/gather"}
  %fusion.5 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/transpose(jvp(SpecDecoder))/block0/mlp/checkpoint/moe_combine/mul"}
  %ragged-dot-none.6 = f32[4]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.7 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/mlp/checkpoint/moe_experts/mul"}
  %fusion.8 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/ln_final/mul"}
  %fusion.9 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/head/dot_general"}
  ROOT %fusion.10 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/optimizer/mul"}
}
"""
MS = 1_000_000


class _Compiled:
    def as_text(self):
        return HLO


def _spec(name):
    with open(os.path.join(ROOT, "benchmarks", "metrics", name + ".json")) as fh:
        return json.load(fh)


def _read(name, run):
    import importlib

    spec = _spec(name)
    return importlib.import_module(f"benchmarks.readers.{spec['reader']}").read(run, spec)


@pytest.fixture
def handmade(manifest):
    from benchmarks import tracing
    from distributeddeeplearning_tpu import obs
    from distributeddeeplearning_tpu.obs import programs

    programs.clear()
    obs.reset()
    programs.register("jit_local_step", _Compiled(), _Compiled)
    names = ["fusion.%d" % i for i in range(1, 6)] + ["ragged-dot-none.6"] + [
        "fusion.%d" % i for i in range(7, 11)]
    step = lambda t0: [(n, t0 + i * MS, t0 + (i + 1) * MS) for i, n in enumerate(names)]
    trace = tracing.Trace(
        ops={0: step(0) + step(20 * MS)},
        modules={0: [("jit_local_step(1)", 0, 10 * MS), ("jit_local_step(1)", 20 * MS, 30 * MS)]},
        host=[("traced_window", 0, 40 * MS)],
    )
    cell = harness.load_cell(CELL, 1, 1.0, True, time.monotonic(),
                             manifest_path=manifest, require_chip=False)
    for pairs in (30.0, 50.0):  # two log syncs
        obs.counter("moe.pairs_local", pairs)
        obs.counter("moe.expert_load_max_over_mean", pairs / 20.0)
    yield {"trace": trace, "window": (0.0, 1.0), "cell": cell,
           "device": {"kind": "TPU v5 lite"}}
    programs.clear()
    obs.reset()


def test_the_expert_layer_s_groups_on_a_handmade_trace(handmade):
    # a millisecond an operation a run; XLA's ragged-dot call counts as the experts'
    assert _read("moe_route_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("moe_dispatch_device_ms.train", handmade) == pytest.approx(2.0)
    assert _read("moe_experts_device_ms.train", handmade) == pytest.approx(2.0)
    # and beside the step's own table, which is reduced apart from it
    assert _read("spec_mlp_device_ms.train", handmade) == pytest.approx(5.0)
    assert _read("spec_attn_core_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("spec_unscoped_device_pct.train", handmade) == pytest.approx(0.0)
    by = scope_group_device_ms.by_scope(handmade, _spec("moe_experts_device_ms.train"))
    assert by["runs"] == 2 and by["unscoped_s"] == pytest.approx(0.010)


def test_the_roofline_shares_on_a_handmade_trace(handmade):
    from benchmarks import peaks
    from benchmarks.references import sdar as ref

    cfg, job = handmade["cell"].config, handmade["cell"].traffic
    work = ref.attn_core_cost(cfg, job["seq_len"], job["batch_per_chip"])
    least = peaks.roofline_seconds(work["flops"], work["bytes"], "TPU v5 lite")["seconds"]
    assert _read("attn_core_roofline_pct.train", handmade) == pytest.approx(100 * least / 1e-3)
    work = ref.expert_cost(cfg, 40.0)  # the counter's mean
    least = peaks.roofline_seconds(work["flops"], work["bytes"], "TPU v5 lite")["seconds"]
    assert _read("moe_experts_roofline_pct.train", handmade) == pytest.approx(100 * least / 2e-3)
    assert _read("expert_load_max_over_mean", handmade) == pytest.approx(2.0)
    assert obs_counter_mean.mean("moe.pairs_local") == pytest.approx(40.0)


def test_the_readers_find_nothing_in_a_program_without_the_layer(handmade, monkeypatch):
    """A parent commit has no expert layer, no table beside a spec-built
    model and no such counters: each reader returns None."""
    import sys

    from distributeddeeplearning_tpu import obs

    obs.reset()
    assert _read("expert_load_max_over_mean", handmade) is None
    assert _read("moe_experts_roofline_pct.train", handmade) is None
    monkeypatch.setitem(sys.modules, "distributeddeeplearning_tpu.models.decoder", None)
    handmade.pop("_by_scope_of", None)
    for name in ("moe_route_device_ms.train", "moe_dispatch_device_ms.train",
                 "moe_experts_device_ms.train", "moe_experts_roofline_pct.train"):
        assert _read(name, handmade) is None
    handmade["trace"] = None
    assert _read("attn_core_roofline_pct.train", handmade) is None
    assert kernel_roofline.read(handmade, _spec("attn_core_roofline_pct.train")) is None


def test_the_cost_functions_count_what_the_issue_counts():
    from benchmarks.references import sdar as ref

    with open(os.path.join(ROOT, "benchmarks", "configs", "sdar-30b-a3b-chat.json")) as fh:
        cfg = json.load(fh)
    assert ref.param_count(cfg) == 645_623_296
    assert ref.live_pairs(4096, 4) == 4096**2 + 4 * 4096
    step = 2 * ref.train_flops_per_sequence(cfg, 4096)
    assert step == pytest.approx(25.9e12, rel=0.01)  # ISSUE 27's 25.9 TFLOP at rows 2
    assert ref.expert_cost(cfg, 16384.0)["flops"] == pytest.approx(
        3 * 16384 * 3 * 2 * 2048 * 768 * 6)
