"""``BENCHMARK.json`` against the contract's rules that can be checked
without a chip, and every file the harness finds by a name in it."""

import importlib
import json
import os
import re

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
with open(os.path.join(ROOT, "BENCHMARK.json")) as fh:
    REAL = json.load(fh)
with open(os.path.join(os.path.dirname(__file__), "data", "serve_entries.json")) as fh:
    SERVE = json.load(fh)


def with_serve_entries(manifest):
    """``manifest`` with the serve cells' entries added as a later PR
    would add them: new entries, and a cell's name appended to the
    ``workloads`` of a metric that is there."""
    m = json.loads(json.dumps(manifest))
    for key in ("workloads", "end_to_end", "per_layer"):
        m[key] += json.loads(json.dumps(SERVE[key]))
    for entry in m["per_layer"]:
        entry["workloads"] += [
            c for c in SERVE["per_layer_also"].get(entry["name"], [])
            if c not in entry["workloads"]
        ]
    return m


# the rules hold for the manifest as it is and as it will be with the
# serve cells that wait in data/serve_entries.json (PERF.md section 7)
M = with_serve_entries(REAL)

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
METRICS = M["end_to_end"] + M["per_layer"]
CELLS = {w["name"]: w for w in M["workloads"]}


def _cells_of(metric):
    return metric.get("workloads", list(CELLS))


def test_top_level_keys():
    assert set(REAL) == set(M)
    assert set(M) == {"command", "paths", "run_seconds", "configs", "workloads",
                      "end_to_end", "per_layer"}
    assert 1 <= M["run_seconds"] <= 51 and isinstance(M["run_seconds"], int)
    assert len(json.dumps(M)) < 64 * 1024


def test_command_and_paths_stay_inside_the_benchmark():
    assert 1 <= len(M["paths"]) <= 16
    for word in M["command"]:
        assert not word.startswith("/") and ".." not in word
    files = [w for w in M["command"] if os.path.exists(os.path.join(ROOT, w))]
    assert files and all(any(f.startswith(p + "/") for p in M["paths"]) for f in files)


@pytest.mark.parametrize("metric", METRICS, ids=lambda m: m["name"])
def test_metric_entry(metric):
    assert NAME.match(metric["name"]) and UNIT.match(metric["unit"])
    assert metric["better"] in ("lower", "higher")
    assert metric["source"] in SOURCES
    assert set(_cells_of(metric)) <= set(CELLS)


@pytest.mark.parametrize("metric", M["end_to_end"], ids=lambda m: m["name"])
def test_end_to_end_entry(metric):
    assert set(metric) <= {"name", "unit", "better", "bound", "source", "workloads"}
    assert 0.01 <= metric["bound"] <= 0.1
    assert metric["source"] in ("host_clock", "device_trace")


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_entry(metric):
    assert set(metric) == {"name", "unit", "better", "source", "layer", "moves", "workloads"}
    moved = next(e for e in M["end_to_end"] if e["name"] == metric["moves"])
    # every cell that reads the metric reports the end-to-end metric it moves
    assert metric["workloads"] and set(metric["workloads"]) <= set(_cells_of(moved))
    assert "\n" not in metric["layer"] and len(metric["layer"]) <= 200
    if "roofline" in metric["name"] or "mfu" in re.split(r"[_.]", metric["name"]):
        assert metric["unit"] == "%"


@pytest.mark.parametrize("metric", M["per_layer"], ids=lambda m: m["name"])
def test_per_layer_metric_has_its_file_and_reader(metric):
    path = os.path.join(ROOT, "benchmarks", "metrics", metric["name"] + ".json")
    with open(path) as fh:
        spec = json.load(fh)
    reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
    assert callable(reader.read) and spec["reads"]


def test_names_are_unique_and_setup_is_there():
    names = [m["name"] for m in METRICS]
    assert len(names) == len(set(names)) and "setup_s" in names
    assert len(CELLS) == len(M["workloads"])
    assert len({(w["config"], w["traffic"]) for w in M["workloads"]}) == len(CELLS)


@pytest.mark.parametrize("cell", M["workloads"], ids=lambda w: w["name"])
def test_cell_entry(cell):
    assert set(cell) == {"name", "config", "traffic", "chips", "why"}
    assert NAME.match(cell["name"]) and NAME.match(cell["traffic"])
    assert cell["chips"] in (1, 4) and 1 <= len(cell["why"]) <= 200
    assert cell["config"] in {c["name"] for c in M["configs"]}
    traffic = os.path.join(ROOT, "benchmarks", "traffic", cell["traffic"] + ".json")
    tests_own = not os.path.exists(traffic)
    if tests_own:  # a mix of the tests' own, rehearsed without a trace
        assert cell["name"] not in {w["name"] for w in REAL["workloads"]}
        traffic = os.path.join(os.path.dirname(__file__), "data", "traffic",
                               cell["traffic"] + ".json")
    with open(traffic) as fh:
        kind = json.load(fh)["kind"]
    assert callable(importlib.import_module(f"benchmarks.runners.{kind}").run)
    # setup_s, another end-to-end metric and a per-layer metric
    e2e = [m["name"] for m in M["end_to_end"] if cell["name"] in _cells_of(m)]
    assert "setup_s" in e2e and len(e2e) >= 2
    layer = [m["name"] for m in M["per_layer"] if cell["name"] in m["workloads"]]
    assert tests_own or (
        any("mfu" in n for n in layer) and any(n.startswith("device_idle") for n in layer)
    )


def test_at_most_a_quarter_of_the_cells_take_four_chips():
    four = sum(1 for w in M["workloads"] if w["chips"] == 4)
    assert four <= max(1, len(M["workloads"]) // 4)


@pytest.mark.parametrize("config", M["configs"], ids=lambda c: c["name"])
def test_config_entry(config):
    assert set(config) == {"name", "source", "file", "reduced", "why"}
    assert any(config["file"].startswith(p + "/") for p in M["paths"])
    with open(os.path.join(ROOT, config["file"])) as fh:
        cfg = json.load(fh)
    for side in ("references", "programs"):
        importlib.import_module(f"benchmarks.{side}.{cfg['family']}")
    widths = re.compile(r"(hidden|intermediate|latent|state|proj|_dim$|_rank$|head_dim|n_embd|n_inner)")
    assert not [k for k in config["reduced"] if widths.search(k)]
    assert any(w["config"] == config["name"] for w in M["workloads"])


def test_the_real_manifest_is_whole_by_itself():
    cells = {w["name"] for w in REAL["workloads"]}
    assert cells and all(c["name"] in {w["config"] for w in REAL["workloads"]}
                         for c in REAL["configs"])
    for metric in REAL["end_to_end"] + REAL["per_layer"]:
        assert set(metric.get("workloads", cells)) <= cells
    for metric in REAL["per_layer"]:
        assert metric["moves"] in {e["name"] for e in REAL["end_to_end"]}
    for cell in cells:
        layer = [m["name"] for m in REAL["per_layer"] if cell in m["workloads"]]
        assert any("mfu" in n for n in layer) and any(n.startswith("device_idle") for n in layer)


def test_the_chat_cell_reports_a_decode_roofline_and_no_gap_percentile_end_to_end():
    assert any(m["name"].startswith("decode_roofline") and "gpt2-serve-chat" in m["workloads"]
               for m in M["per_layer"])
    assert "serve_itl_p95_ms" not in [m["name"] for m in M["end_to_end"]]
