"""The ``granite`` family through the harness on the CPU: a tiny
configuration rehearses ``granite-4.0-h-micro-train-t4k`` (the trainer's
normal path over state-space layers beside an attention layer, the
plain token-by-token reference through three AdamW steps), the cell's
controls come out as not correct, the configuration file is held
against the catalog's numbers and the cut's table, the cost functions
against hand counts, and the new metrics are read from a hand-made
trace."""

import importlib
import json
import math
import os
import time

import pytest

from benchmarks import harness, peaks
from benchmarks.references import granite as ref

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
CELL = "granite-4.0-h-micro-train-t4k"
CONFIG = "granite-4.0-h-micro"


def _real(kind, name):
    with open(os.path.join(ROOT, "benchmarks", kind, name + ".json")) as fh:
        return json.load(fh)


@pytest.fixture(scope="module")
def manifest(tmp_path_factory):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    for c in m["configs"]:
        if c["name"] == CONFIG:
            c["file"] = os.path.join(DATA, "configs", "granite-tiny.json")
    path = os.path.join(str(tmp_path_factory.mktemp("granite")), "manifest.json")
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
    """Rows of 27 tokens under a chunk of 8: three chunks and a ragged
    fourth, through ``explicit.setup`` and the runner as they stand."""
    r = json.loads(json.dumps(result))
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", "train_items_per_s_per_chip"}
    assert set(r["checks"]) >= {"loss_gap", "grad_norm_gap", "delta_norm_gap"}
    # float32 on both sides: the chunked scan is the recurrence
    assert r["checks"]["grad_norm_gap"]["value"] < 1e-4
    assert r["checks"]["delta_norm_gap"]["value"] < 1e-4


@pytest.mark.parametrize("control", ["reference", "half_batch"])
def test_a_control_is_not_correct(manifest, control):
    r = _run(manifest, control=control)
    assert r["correct"] is False
    assert r["checks"]["grad_norm_gap"]["value"] > r["checks"]["grad_norm_gap"]["limit"]
    # the program's own readings ride beside the control's, and pass
    assert r["checks"]["program_grad_norm_gap"]["value"] < 1e-4


MECHANISMS = ["no_decay", "no_conv", "residual_one", "gate_after_norm"]


@pytest.fixture(scope="module")
def hand_lines(manifest):
    """``benchmarks/hand_controls.py`` over the tiny cell: the reference
    with one mechanism taken out in the program's place."""
    from benchmarks import hand_controls

    cell = harness.load_cell(
        CELL, 2**31 + 7, 0.0, False, time.monotonic(),
        manifest_path=manifest, require_chip=False,
    )
    return {line["control"]: line for line in hand_controls.run(cell, MECHANISMS)}


@pytest.mark.parametrize("name", MECHANISMS)
def test_a_hand_control_is_not_correct_by_the_cell_s_own_limits(hand_lines, name):
    line = json.loads(json.dumps(hand_lines[name]))
    assert line["correct"] is False
    assert {"grad_norm_gap", "delta_norm_gap"} <= set(line["fails"])
    for k in line["fails"]:
        assert line["checks"][k]["value"] > line["checks"][k]["limit"]


def test_the_cell_names_the_hand_controls_the_tiny_one_rehearses():
    real = _real("traffic", "t4k")["correct"]
    with open(os.path.join(DATA, "traffic", "t4k.json")) as fh:
        tiny = json.load(fh)["correct"]
    assert real["hand_controls"] == tiny["hand_controls"]
    assert list(real["hand_controls"]) == MECHANISMS
    # every number compared has a limit of the cell's own
    assert set(real["limits"]) == {"loss_gap", "grad_norm_gap", "delta_norm_gap"}


# -- the configuration file ----------------------------------------------------

PERIOD = ["mamba"] * 5 + ["attention"] + ["mamba"] * 4
CATALOG = {  # architectures.jsonl, row granite-4.0-h-micro, `config`
    "attention_bias": False, "attention_multiplier": 0.015625,
    "embedding_multiplier": 12, "hidden_act": "silu", "hidden_size": 2048,
    "intermediate_size": 8192, "layer_types": PERIOD * 4, "logits_scaling": 8,
    "mamba_chunk_size": 256, "mamba_conv_bias": True, "mamba_d_conv": 4,
    "mamba_d_head": 64, "mamba_d_state": 128, "mamba_expand": 2,
    "mamba_n_groups": 1, "mamba_n_heads": 64, "mamba_proj_bias": False,
    "max_position_embeddings": 131072, "model_type": "granitemoehybrid",
    "normalization_function": "rmsnorm", "num_attention_heads": 32,
    "num_experts_per_tok": 0, "num_hidden_layers": 40, "num_key_value_heads": 8,
    "num_local_experts": 0, "position_embedding_type": "nope",
    "residual_multiplier": 0.22, "rms_norm_eps": 1e-05, "rope_scaling": None,
    "rope_theta": 10000, "shared_intermediate_size": 8192,
    "tie_word_embeddings": True, "vocab_size": 100352,
}


def test_the_configuration_holds_the_published_numbers_and_states_its_cut():
    cfg = _real("configs", CONFIG)
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        entry = next(c for c in json.load(fh)["configs"] if c["name"] == CONFIG)
    reduced = entry["reduced"]
    assert reduced == ["layers", "vocab_size"]
    assert entry["source"] == cfg["source"] and "granite-4.0-h-micro" in cfg["source"]
    for key, value in CATALOG.items():
        if key not in reduced:
            assert cfg[key] == value, key
    assert (cfg["layers"], cfg["vocab_size"]) == (10, 12544)
    assert cfg["vocab_size"] * 8 == 100352 and cfg["layers"] == len(PERIOD)
    assert cfg["published"] == {
        "layers": 40, "vocab_size": 100352, "parameters": "3.19B",
        "parameters_counted": 36 * 76_182_976 + 4 * 60_821_504 + 100352 * 2048 + 2048}
    for key in ("head_dim", "attention", "mamba_in_proj", "mamba_conv", "mamba_dt",
                "mamba_scan", "mamba_gate", "mlp", "multipliers", "optimizer",
                "compute_dtype", "weights"):
        assert cfg["assumed"][key], key
    assert "four chips as pipeline stages" in cfg["deployment"]
    assert "772,160,448" in cfg["deployment"]


def test_the_cut_s_table_is_the_parameter_count():
    cfg = _real("configs", CONFIG)
    shapes = ref.param_shapes(cfg)
    size = lambda keep: sum(math.prod(s) for k, s in shapes.items() if keep(k))  # noqa: E731
    assert size(lambda k: k.startswith("block0/ssm/")) == 25_847_232
    assert size(lambda k: k.startswith("block0/ssm/conv/")) == 21_760
    assert size(lambda k: k.startswith("block0/mlp/")) == 50_331_648
    assert size(lambda k: k.startswith("block0/")) == 76_182_976
    assert size(lambda k: k.startswith("block5/attn/")) == 10_485_760
    assert size(lambda k: k.startswith("block5/")) == 60_821_504
    assert size(lambda k: not k.startswith("block")) == 25_690_112 + 2048
    assert ref.param_count(cfg) == 772_160_448
    assert ref.layer_kinds(cfg) == {"mamba": 9, "attention": 1}
    # the program's spec is the published model, uncut until a run states its depth
    from distributeddeeplearning_tpu.models import decoder

    spec = decoder.SPECS["granite_4_0_h_micro"]
    assert (spec.hidden, spec.layers, spec.heads, spec.kv_heads, spec.head_dim) == (
        2048, 40, 32, 8, 64)
    assert (spec.ssm_heads, spec.ssm_head_dim, spec.ssm_state, spec.ssm_groups,
            spec.ssm_conv, spec.ssm_chunk) == (64, 64, 128, 1, 4, 256)
    assert [spec.kind(l).mixer == "mamba2" for l in range(40)] == [
        t == "mamba" for t in cfg["layer_types"]]
    assert not any(spec.kind(l).rope or spec.kind(l).window for l in range(40))
    assert (spec.ffn, spec.ffn_dim, spec.tied_head, spec.norm_eps) == (
        "glu", 8192, True, 1e-5)
    assert (spec.embed_scale, spec.attn_scale, spec.residual_scale,
            spec.logits_scale) == (12.0, 0.015625, 0.22, 8.0)


def test_the_cost_functions_count_what_a_hand_counts():
    """At 6 tokens under a chunk of 4 every pair can be written down: a
    whole chunk has 4 + 3 + 2 + 1 = 10 pairs with s <= t, the ragged one
    of 2 tokens 3."""
    assert ref.live_pairs(4) == 10 and ref.live_pairs(2) == 3
    cfg = dict(_real("configs", CONFIG))
    cfg.update(layers=3, mamba_chunk_size=4)  # mamba, mamba, mamba
    heads, p, n, d, f = 64, 64, 128, 2048, 8192
    scan = 13 * (2 * n + 2 * p * heads) + 6 * 2 * 2 * p * n * heads
    assert ref.scan_flops(cfg, 6) == scan
    cost = ref.ssm_scan_cost(cfg, 6, 2)
    assert cost["flops"] == 3 * scan * 2 * 3  # fwd + bwd, two rows, three layers
    operands, out = heads * p + 2 * n + heads, heads * p  # xs, B, C, the step | y
    assert cost["bytes"] == 2 * ((operands + out) + (2 * operands + out)) * 6 * 2 * 3
    mamba = d * 8512 + 4096 * d + 3 * d * f
    assert ref.forward_flops(cfg, 6) == pytest.approx(
        2 * 6 * 3 * mamba + 3 * scan + 2 * d * 12544 * 6)
    cfg.update(layers=6)  # five state-space layers and the attention layer
    attention = d * 64 * (2 * 32 + 2 * 8) + 3 * d * f
    assert ref.forward_flops(cfg, 6) == pytest.approx(
        2 * 6 * (5 * mamba + attention) + 4 * 64 * 32 * 21 + 5 * scan + 2 * d * 12544 * 6)
    core = ref.attn_core_cost(cfg, 6, 2)
    assert core["flops"] == 3 * 4 * 64 * 32 * 21 * 2
    wide, narrow = 32 * 64, 8 * 64
    assert core["bytes"] == 2 * ((2 * wide + 2 * narrow) + (3 * wide + 2 * narrow)
                                 + (wide + 2 * narrow)) * 6 * 2
    real = _real("configs", CONFIG)
    assert ref.train_flops_per_sequence(real, 4096) / 4096 == pytest.approx(4.79e9, rel=0.005)
    scans = ref.ssm_scan_cost(real, 4096, 1)["flops"]
    assert scans / ref.train_flops_per_sequence(real, 4096) == pytest.approx(0.0180, rel=0.02)
    cores = ref.attn_core_cost(real, 4096, 1)["flops"]
    assert cores / ref.train_flops_per_sequence(real, 4096) == pytest.approx(0.0105, rel=0.02)


# -- the manifest's entries and the metric files --------------------------------

MINE = {
    "ssm_scan_device_ms.train", "ssm_conv_device_ms.train", "ssm_proj_device_ms.train",
    "ssm_scan_roofline_pct.train", "granite_attn_core_roofline_pct.train",
    "granite_ssm_device_ms.train", "granite_attn_core_device_ms.train",
    "granite_attn_proj_device_ms.train", "granite_mlp_device_ms.train",
    "granite_norm_residual_device_ms.train", "granite_head_loss_device_ms.train",
    "granite_optimizer_device_ms.train", "granite_unscoped_device_pct.train",
}


def test_the_new_metric_files_name_readers_tables_and_costs_that_exist():
    with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
        m = json.load(fh)
    mine = [p["name"] for p in m["per_layer"] if p["workloads"] == [CELL]]
    assert set(mine) == MINE and len(mine) == 13
    shared = [p["name"] for p in m["end_to_end"] + m["per_layer"]
              if CELL in p.get("workloads", []) and p["workloads"] != [CELL]]
    assert sorted(shared) == sorted([
        "train_items_per_s_per_chip", "cache_misses", "input_host_ms_per_step",
        "step_device_ms.train", "step_mfu_pct.train", "device_idle_pct.train",
        "hbm_peak_gib.train"])
    tables = set()
    for name in mine:
        spec = _real("metrics", name)
        importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        module, _, attribute = spec["groups"].partition(":")
        groups = getattr(importlib.import_module(module), attribute)
        tables.add(attribute)
        assert spec.get("group", groups[0][0]) in {g for g, _ in groups}
        if "cost" in spec:
            assert callable(getattr(ref, spec["cost"]))
    # one table a reader's reduction: the step's parts by a table that has
    # the mixer's group, the mixer's own parts by a second
    assert tables == {"HYBRID_STEP_GROUPS", "SSM_GROUPS"}
    cell = next(w for w in m["workloads"] if w["name"] == CELL)
    assert cell["chips"] == 1 and cell["traffic"] == "t4k" and cell["config"] == CONFIG
    job = _real("traffic", "t4k")
    assert (job["kind"], job["seq_len"], job["remat"]) == ("train", 4096, True)
    assert job["batch_per_chip"] in (1, 2)  # ISSUE 33's ladder, by the compiler
    assert job["optimizer"]["learning_rate"] == 1e-4
    assert set(job["correct"]["limits"]) >= {"grad_norm_gap", "delta_norm_gap"}


# -- the readers on a hand-made run ------------------------------------------------

HLO = """HloModule jit_local_step

ENTRY %main () -> f32[4] {
  %fusion.1 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/ssm/in_proj/dot_general"}
  %fusion.2 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/ssm/ssm_conv/mul"}
  %fusion.3 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/ssm/ssm_scan/while/body/checkpoint/dot_general"}
  %fusion.4 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/transpose(jvp(SpecDecoder))/block0/ssm/ssm_scan/while/body/checkpoint/dot_general"}
  %fusion.5 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block0/ssm/norm/mul"}
  %fusion.6 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block5/attn/attn_core/attn_full/jit(_stats_core)/pallas_call"}
  %fusion.7 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block5/attn/q/dot_general"}
  %fusion.8 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/block5/mlp/w_in/dot_general"}
  %fusion.9 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/ln_final/mul"}
  %fusion.10 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/jvp(SpecDecoder)/head/dot_general"}
  %copy.11 = f32[4]{0} copy(%a)
  ROOT %fusion.12 = f32[4]{0} fusion(%a), metadata={op_name="jit(local_step)/optimizer/mul"}
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
    names = ["fusion.%d" % i for i in range(1, 11)] + ["copy.11", "fusion.12"]
    step = lambda t0: [(n, t0 + i * MS, t0 + (i + 1) * MS) for i, n in enumerate(names)]  # noqa: E731
    trace = tracing.Trace(
        ops={0: step(0) + step(20 * MS)},
        modules={0: [("jit_local_step(1)", 0, 12 * MS), ("jit_local_step(1)", 20 * MS, 32 * MS)]},
        host=[("traced_window", 0, 40 * MS)],
    )
    cell = harness.load_cell(CELL, 1, 1.0, True, time.monotonic(), require_chip=False)
    yield {"trace": trace, "window": (0.0, 1.0), "cell": cell,
           "device": {"kind": "TPU v5 lite"}}
    programs.clear()
    obs.reset()


def test_the_new_metrics_on_a_handmade_trace(handmade):
    # a millisecond an operation a run
    assert _read("ssm_scan_device_ms.train", handmade) == pytest.approx(2.0)
    assert _read("ssm_conv_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("ssm_proj_device_ms.train", handmade) == pytest.approx(2.0)
    assert _read("granite_ssm_device_ms.train", handmade) == pytest.approx(5.0)
    assert _read("granite_attn_core_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("granite_attn_proj_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("granite_mlp_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("granite_norm_residual_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("granite_head_loss_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("granite_optimizer_device_ms.train", handmade) == pytest.approx(1.0)
    assert _read("granite_unscoped_device_pct.train", handmade) == pytest.approx(100 / 12)
    cfg, job = handmade["cell"].config, handmade["cell"].traffic
    for name, cost, ms, bound in (
        ("ssm_scan_roofline_pct.train", ref.ssm_scan_cost, 2.0, "bytes"),
        ("granite_attn_core_roofline_pct.train", ref.attn_core_cost, 1.0, "flops"),
    ):
        work = cost(cfg, job["seq_len"], job["batch_per_chip"])
        least = peaks.roofline_seconds(work["flops"], work["bytes"], "TPU v5 lite")
        assert least["bound_by"] == bound
        assert _read(name, handmade) == pytest.approx(100 * least["seconds"] / (1e-3 * ms))


def test_the_readers_find_nothing_in_a_program_without_the_mixer(handmade, monkeypatch):
    """A parent commit's decoder has neither table, and a step with no
    state-space layer no ``ssm`` scope: each new reader returns None and
    the result line leaves the metric out."""
    from distributeddeeplearning_tpu.models import decoder
    from distributeddeeplearning_tpu.obs import programs

    monkeypatch.delattr(decoder, "SSM_GROUPS")
    monkeypatch.delattr(decoder, "HYBRID_STEP_GROUPS")
    for name in MINE:
        assert _read(name, handmade) is None, name
    monkeypatch.undo()
    for kept in ("_by_scope_of", "_by_scope"):
        handmade.pop(kept, None)

    class _NoMixer:  # every layer an attention layer: the tables lack a group
        def as_text(self):
            return "\n".join(l for l in HLO.splitlines() if "/ssm/" not in l)

    programs.clear()
    programs.register("jit_local_step", _NoMixer(), _NoMixer)
    for name in MINE:
        assert _read(name, handmade) is None, name
    handmade["trace"] = None
    assert _read("ssm_scan_roofline_pct.train", handmade) is None
