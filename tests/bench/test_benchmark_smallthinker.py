"""The ``smallthinker`` family through the harness on the CPU: a tiny
configuration rehearses ``smallthinker-21b-a3b-train-t16k`` (the
trainer's normal path with the next-token objective over layers that
differ, the plain reference through three AdamW steps, the routing
readings of ``runners/train_routed``), the cell's controls come out as
not correct, the configuration file is held against the catalog's
numbers and the cut's table, the cost functions against hand counts, and
the by-kind attention metrics are read from a hand-made trace."""

import importlib
import json
import math
import os
import time

import pytest

from benchmarks import harness, peaks
from benchmarks.references import smallthinker as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "smallthinker-21b-a3b-train-t16k"
CONFIG = "smallthinker-21b-a3b-instruct"


def _real(kind, name):
    with open(os.path.join(ROOT, "benchmarks", kind, name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    for c in m["configs"]:
        if c["name"] == CONFIG:
            c["file"] = os.path.join(DATA, "configs", "smallthinker-tiny.json")
    path = os.path.join(str(tmp_path_factory.mktemp("smallthinker")), "manifest.json")
    with open(path, "w") as fh:
        json.dump(m, fh)
    return path


def _run(manifest, **kw):
    cell = harness.load_cell(
        CELL, 2**31 + 7, 1.0, False, time.monotonic(),
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


# -- the configuration file ----------------------------------------------------

CATALOG = {  # architectures.jsonl, row SmallThinker-21BA3B-Instruct, `config`
    "head_dim": 128, "hidden_size": 2560, "max_position_embeddings": 16384,
    "model_name": "smallthinker_21b_instruct", "moe_ffn_hidden_size": 768,
    "moe_num_active_primary_experts": 6, "moe_num_primary_experts": 64,
    "moe_primary_router_apply_softmax": True, "norm_topk_prob": True,
    "num_attention_heads": 28, "num_hidden_layers": 52, "num_key_value_heads": 4,
    "rms_norm_eps": 1e-06, "rope_layout": [0, 1, 1, 1] * 13, "rope_scaling": None,
    "rope_theta": 1500000, "sliding_window_layout": [0, 1, 1, 1] * 13,
    "sliding_window_size": 4096, "tie_word_embeddings": False, "vocab_size": 151936,
}


def test_the_configuration_holds_the_published_numbers_and_states_its_cut():
    cfg = _real("configs", CONFIG)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"] if c["name"] == CONFIG)
    reduced = entry["reduced"]
    assert reduced == ["layers", "moe_num_primary_experts", "vocab_size"]
    assert entry["source"] == cfg["source"] and "SmallThinker-21BA3B-Instruct" in cfg["source"]
    for key, value in CATALOG.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert (cfg["layers"], cfg["moe_num_primary_experts"], cfg["vocab_size"]) == (8, 8, 18992)
    assert cfg["published"] == {
        "layers": 52, "moe_num_primary_experts": 64, "vocab_size": 151936,
        "parameters": "21.5B"}
    assert cfg["vocab_size"] * 8 == 151936 and cfg["layers"] % 4 == 0
    for key in ("hidden_act", "router_input", "window", "rope", "optimizer",
                "compute_dtype", "weights", "secondary_experts", "attention_bias"):
        assert cfg["assumed"][key], key
    assert cfg["assumed"]["hidden_act"] == "relu" and cfg["assumed"]["router_input"] == "ln1"
    assert "eight chips share each layer" in cfg["deployment"]


def test_the_cut_s_table_is_the_parameter_count():
    cfg = _real("configs", CONFIG)
    shapes = ref.param_shapes(cfg)
    size = lambda keep: sum(math.prod(s) for k, s in shapes.items() if keep(k))
    assert size(lambda k: k.startswith("block0/") and "/mlp/w" not in k) == 21_140_480
    assert size(lambda k: k.startswith("block0/mlp/w")) == 8 * 5_898_240 == 47_185_920
    assert size(lambda k: not k.startswith("block")) == 97_241_600
    assert ref.param_count(cfg) == 643_852_800
    assert ref.layer_kinds(cfg) == {"window": 6, "full": 2}
    # the program's spec is the published layer, uncut until a run states its share
    from distributeddeeplearning_tpu.models import decoder

    spec = decoder.SPECS["smallthinker_21b_a3b"]
    assert (spec.hidden, spec.layers, spec.heads, spec.kv_heads, spec.head_dim) == (
        2560, 52, 28, 4, 128)
    assert (spec.experts, spec.experts_held, spec.experts_per_token, spec.ffn_dim) == (
        64, 64, 6, 768)
    assert [spec.kind(l).window for l in range(8)] == [0, 4096, 4096, 4096] * 2
    assert [spec.kind(l).rope for l in range(8)] == [False, True, True, True] * 2
    assert spec.rope_theta == 1.5e6 and spec.route_before_attention
    assert spec.activation == "relu" and not spec.qk_norm and not spec.tied_head


def test_the_cost_functions_count_what_a_hand_counts():
    """At 6 tokens under a window of 3 every pair can be written down:
    the triangle has 21, the band 3 + 2 + 1 ... = 1 + 2 + 3 + 3 + 3 + 3 =
    15."""
    assert ref.live_pairs(6) == 21 and ref.live_pairs(6, 3) == 15
    assert ref.live_pairs(6, 6) == ref.live_pairs(6, 9) == 21
    assert ref.live_pairs(16384, 4096) == 58_722_304  # 44% of the triangle
    cfg = dict(_real("configs", CONFIG))
    cfg.update(layers=4, sliding_window_size=3)
    heads, hd, d = 28, 128, 2560
    window, full = ref.attn_window_cost(cfg, 6, 2), ref.attn_full_cost(cfg, 6, 2)
    assert window["flops"] == 3 * 4 * hd * heads * 15 * 3 * 2  # three window layers, two rows
    assert full["flops"] == 3 * 4 * hd * heads * 21 * 1 * 2
    both = ref.attn_core_cost(cfg, 6, 2)
    assert both["flops"] == window["flops"] + full["flops"]
    assert both["bytes"] == window["bytes"] + full["bytes"]
    wide, narrow = heads * hd, 4 * hd  # bf16: q, k, v, o forward; those and do read, dq, dk, dv written
    assert full["bytes"] == 2 * ((2 * wide + 2 * narrow) + (3 * wide + 2 * narrow)
                                 + (wide + 2 * narrow)) * 6 * 2
    per_position = d * hd * (2 * heads + 2 * 4) + d * 64 + 6 * 8 / 64 * 3 * d * 768
    assert ref.forward_flops(cfg, 6) == pytest.approx(
        4 * 2 * per_position * 6 + 4 * hd * heads * (3 * 15 + 21) + 2 * d * 18992 * 6)
    real = _real("configs", CONFIG)
    assert ref.train_flops_per_sequence(real, 16384) == pytest.approx(51.6e12, rel=0.005)
    assert ref.attn_core_cost(real, 16384, 1)["flops"] == pytest.approx(26.7e12, rel=0.005)
    assert ref.expert_cost(real, 12288.0)["flops"] == pytest.approx(
        3 * 12288 * 3 * 2 * 2560 * 768 * 8)


# -- the manifest's entries and the metric files --------------------------------

def test_the_new_metric_files_name_readers_tables_and_costs_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    mine = [p["name"] for p in m["per_layer"] if p["workloads"] == [CELL]]
    assert len(mine) == 17 and {
        "attn_window_device_ms.train", "attn_full_device_ms.train",
        "attn_window_roofline_pct.train", "attn_full_roofline_pct.train",
    } <= set(mine)
    shared = [p["name"] for p in m["end_to_end"] + m["per_layer"]
              if CELL in p.get("workloads", []) and p["workloads"] != [CELL]]
    assert sorted(shared) == sorted([
        "train_items_per_s_per_chip", "cache_misses", "input_host_ms_per_step",
        "step_device_ms.train", "step_mfu_pct.train", "device_idle_pct.train",
        "hbm_peak_gib.train"])
    for name in mine:
        spec = _real("metrics", name)
        importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        if "groups" in spec:
            module, _, attribute = spec["groups"].partition(":")
            groups = getattr(importlib.import_module(module), attribute)
            assert spec.get("group", groups[0][0]) in {g for g, _ in groups}
        if "cost" in spec:
            assert callable(getattr(ref, spec["cost"]))
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "t16k"
    job = _real("traffic", "t16k")
    assert (job["kind"], job["seq_len"], job["batch_per_chip"]) == ("train_routed", 16384, 1)
    # the rate ISSUE 31 gave, SDAR's; loss_gap and pairs_held_gap are read
    # and carried with no limit: no control moves either to three times
    # the sound runs' largest (PERF.md section 6, PR 31)
    assert job["optimizer"]["learning_rate"] == 1e-4
    assert set(job["correct"]["limits"]) == {
        "grad_norm_gap", "delta_norm_gap", "routing_differ_share"}


# -- the by-kind readers on a hand-made run --------------------------------------

HLO = """HloModule jit_local_step

ENTRY %main () -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/attn/attn_core/attn_full/jit(_stats_core)/pallas_call"}
  %fusion.2 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block1/attn/attn_core/attn_window/jit(_stats_core)/pallas_call"}
  %fusion.3 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/transpose(jvp(SpecDecoder))/block1/attn/attn_core/attn_window/jit(_stats_core)/pallas_call"}
  %fusion.4 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block1/attn/q/dot_general"}
  %fusion.5 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block1/mlp/moe_route/dot_general"}
  %ragged-dot-none.6 = f32[4]{0} custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="ragged-dot-none"}
  %fusion.7 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/ln_final/mul"}
  %fusion.8 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/head/dot_general"}
  %fusion.9 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block1/mlp/checkpoint/moe_dispatch/gather"}
  ROOT %fusion.10 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/optimizer/mul"}
}
"""
MS = 1_000_000


class _Compiled:
    def as_text(self):
        return HLO


def _read(name, run):
    spec = _real("metrics", name)
    return importlib.import_module(f"benchmarks.readers.{spec['reader']}").read(run, spec)


@pytest.fixture
def handmade():
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
    cell = harness.load_cell(CELL, 1, 1.0, True, time.monotonic(), require_chip=False)
    obs.counter("moe.pairs_local", 12288.0)
    obs.counter("moe.expert_load_max_over_mean", 1.5)
    yield {"trace": trace, "window": (0.0, 1.0), "cell": cell,
           "device": {"kind": "TPU v5 lite"}}
    programs.clear()
    obs.reset()


def test_the_attention_kinds_on_a_handmade_trace(handmade):
    # a millisecond an operation a run: one full-layer call, two window-layer calls
    assert _read("attn_full_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("attn_window_device_ms.train", handmade) == pytest.approx(2.0)
    assert _read("smallthinker_attn_core_device_ms.train", handmade) == pytest.approx(3.0)
    assert _read("smallthinker_attn_proj_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("smallthinker_mlp_device_ms.train", handmade) == pytest.approx(3.0)
    assert _read("smallthinker_moe_route_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("smallthinker_moe_dispatch_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("smallthinker_moe_experts_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("smallthinker_optimizer_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("smallthinker_head_loss_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("smallthinker_norm_residual_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("smallthinker_unscoped_device_pct.train", handmade) == pytest.approx(0.0)
    assert _read("smallthinker_expert_load_max_over_mean", handmade) == pytest.approx(1.5)
    cfg, job = handmade["cell"].config, handmade["cell"].traffic
    for name, cost, ms in (
        ("attn_window_roofline_pct.train", ref.attn_window_cost, 2.0),
        ("attn_full_roofline_pct.train", ref.attn_full_cost, 1.0),
        ("smallthinker_attn_core_roofline_pct.train", ref.attn_core_cost, 3.0),
    ):
        work = cost(cfg, job["seq_len"], job["batch_per_chip"])
        least = peaks.roofline_seconds(work["flops"], work["bytes"], "TPU v5 lite")
        assert least["bound_by"] == "flops"
        assert _read(name, handmade) == pytest.approx(100 * least["seconds"] / (1e-3 * ms))
    work = ref.expert_cost(cfg, 12288.0)
    least = peaks.roofline_seconds(work["flops"], work["bytes"], "TPU v5 lite")["seconds"]
    assert _read("smallthinker_moe_experts_roofline_pct.train", handmade) == pytest.approx(
        100 * least / 1e-3)


def test_the_readers_find_nothing_in_a_program_without_the_kinds(handmade, monkeypatch):
    """A parent commit's decoder has no ``ATTN_KIND_GROUPS`` and its step
    no ``attn_window`` scope: each by-kind reader returns None."""
    from distributeddeeplearning_tpu.models import decoder

    monkeypatch.delattr(decoder, "ATTN_KIND_GROUPS")
    for name in ("attn_window_device_ms.train", "attn_full_device_ms.train",
                 "attn_window_roofline_pct.train", "attn_full_roofline_pct.train"):
        assert _read(name, handmade) is None
    monkeypatch.undo()
    handmade.pop("_by_scope_of", None)

    class _Causal:  # a step whose every layer is full: the table lacks a group
        def as_text(self):
            return HLO.replace("attn_window", "attn_full")

    from distributeddeeplearning_tpu.obs import programs

    programs.clear()
    programs.register("jit_local_step", _Causal(), _Causal)
    assert _read("attn_full_device_ms.train", handmade) is None
    handmade["trace"] = None
    assert _read("attn_window_roofline_pct.train", handmade) is None
