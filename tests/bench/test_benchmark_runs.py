"""One tiny-size rehearsal of each kind of run on the CPU (the harness's
look for a chip skipped), and the timed path broken underneath: each
fault a cell can have has to come out as ``correct: false``."""

import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import pytest

from benchmarks import harness

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
DATA = os.path.join(os.path.dirname(__file__), "data")
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
from test_benchmark_manifest import M  # noqa: E402 - with the serve cells' entries


def _manifest(tmp, config, dense=False):
    """The manifest over a tiny configuration: traffic files of the same
    names sit beside it under ``data/``. ``dense`` adds one more cell of
    the tests' own, as a later PR would, by entries alone."""
    m = json.loads(json.dumps(M))
    m["configs"][0]["file"] = os.path.join(DATA, "configs", config + ".json")
    if dense:
        m["workloads"].append({
            "name": "gpt2-serve-chatdense", "config": m["configs"][0]["name"],
            "traffic": "chatdense", "chips": 1, "why": "test only",
        })
        for e in m["end_to_end"]:
            if e["name"] == "serve_tpot_p95_ms":
                e["workloads"].append("gpt2-serve-chatdense")
    path = os.path.join(str(tmp), config + ".json")
    with open(path, "w") as fh:
        json.dump(m, fh)
    return path


@pytest.fixture(scope="module")
def manifests(tmp_path_factory):
    tmp = tmp_path_factory.mktemp("manifests")
    return {
        "tiny": _manifest(tmp, "gpt2-tiny"),
        "8k": _manifest(tmp, "gpt2-tiny-v8k", dense=True),
    }


# the serve cells are no cells of the benchmark today (PERF.md section 7);
# their rehearsals stay, so that a later PR finds the paths working
CELLS = {
    "gpt2-serve-chat": "serve_tpot_p95_ms",
    "gpt2-train-t1024": "train_items_per_s_per_chip",
    "gpt2-serve-docbatch": "serve_tokens_per_s",
}


def _run(manifest, workload, trace=False, **kw):
    cell = harness.load_cell(
        workload, 2**31 + 77, 2.0, trace, time.monotonic(),
        manifest_path=manifest, require_chip=False, **kw,
    )
    return harness.run_cell(cell)


@pytest.fixture(scope="module")
def results(manifests):
    return {name: _run(manifests["tiny"], name) for name in CELLS}


@pytest.mark.parametrize("workload", list(CELLS))
def test_rehearsal_prints_the_result_lines_shape(results, workload):
    r = json.loads(json.dumps(results[workload]))
    assert list(r)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(r)[-1] == "checks"
    assert r["correct"] is True and r["attempted"] > 0 and r["failed"] == 0
    assert set(r["metrics"]) == {"setup_s", CELLS[workload]}
    units = {m["name"]: m["unit"] for m in M["end_to_end"]}
    for name, m in r["metrics"].items():
        assert m["unit"] == units[name] and m["value"] > 0
    assert list(r)[-3:] == ["beside", "reference_s", "checks"]
    assert set(r["device"]) == {"platform", "kind", "count", "memory_peak_bytes"}
    judged = [c for c in r["checks"].values() if c["limit"] is not None]
    assert judged and all(c["value"] <= c["limit"] for c in judged)


@pytest.mark.parametrize("workload", ["gpt2-serve-chat", "gpt2-train-t1024"])
def test_traced_rehearsal_reports_only_the_cells_per_layer_metrics(manifests, workload):
    r = _run(manifests["tiny"], workload, trace=True)
    mine = {m["name"] for m in M["per_layer"] if workload in m["workloads"]}
    assert r["metrics"] and set(r["metrics"]) <= mine
    # no device plane on the CPU: nothing read from a trace is reported,
    # and no share of a roofline or of a peak is ever a made-up 0
    assert not [n for n in r["metrics"] if "roofline" in n or "mfu" in n]
    assert "cache_misses" in r["metrics"]


def test_no_chip_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmarks", "run.py"),
         "--workload", "gpt2-train-t1024", "--seed", "1", "--seconds", "1",
         "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=300,
    )
    assert p.returncode == harness.NO_CHIP
    assert not [ln for ln in p.stdout.splitlines() if ln.startswith("{")]
    assert "nothing was run" in p.stderr


# -- the timed path broken underneath ---------------------------------------

def _alter_a_token(server):
    """A token altered where it is produced: every fifth decode tick
    hands out a wrong token for its first slot."""
    engine, calls = server.engine, {"n": 0}
    decode = engine.decode_step

    def broken():
        out = decode()
        calls["n"] += 1
        if out and calls["n"] % 5 == 0:
            slot, tok, eos = out[0]
            out[0] = (slot, (tok + 1) % engine.model.vocab_size, eos)
        return out

    engine.decode_step = broken


class _Step:
    """Stands where ``pieces.train_step`` stood."""

    def __init__(self, inner, mode):
        self.inner, self.mode = inner, mode

    def __call__(self, state, batch):
        if self.mode == "frozen":  # returns its state unchanged
            kept = jax.tree.map(jnp.copy, state)
            _, metrics = self.inner(state, batch)
            return kept, metrics
        x, y = batch  # half of the batch left out, the mean over the rest
        n = x.shape[0] // 2
        return self.inner(state, (x[:n], y[:n]))


def _break_step(mode):
    def sabotage(pieces):
        pieces.train_step = _Step(pieces.train_step, mode)
    return sabotage


@pytest.mark.parametrize("workload", ["gpt2-serve-chat", "gpt2-serve-docbatch"])
def test_an_altered_token_is_not_correct(manifests, workload):
    r = _run(manifests["tiny"], workload, sabotage=_alter_a_token)
    assert r["correct"] is False
    judged = r["checks"]["miss_gap_meansq"]
    assert judged["value"] > judged["limit"]


@pytest.mark.parametrize("mode", ["frozen", "half_batch"])
def test_a_broken_train_step_is_not_correct(manifests, mode):
    r = _run(manifests["tiny"], "gpt2-train-t1024", sabotage=_break_step(mode))
    assert r["correct"] is False
    over = [k for k, c in r["checks"].items()
            if c["limit"] is not None and c["value"] > c["limit"]]
    assert over
    if mode == "frozen":  # unmoved leaves read 1 by the measure
        assert r["checks"]["delta_norm_gap"]["value"] == pytest.approx(1.0)


# -- the controls, at a size a test run can hold ------------------------------

def test_serving_in_int8_is_not_correct(manifests):
    """The control of the serve cells: the program's own int8 weights
    and int8 cache switched on. A thousand served tokens of a tiny model
    with a vocabulary of 8,192 tell it from the bf16 path."""
    sound = _run(manifests["8k"], "gpt2-serve-chatdense")
    control = _run(manifests["8k"], "gpt2-serve-chatdense", control="program")
    assert sound["correct"] is True and control["correct"] is False
    a, b = (r["checks"]["miss_gap_meansq"] for r in (sound, control))
    assert 3 * a["value"] < a["limit"] < b["value"] / 3
    assert sound["checks"]["tokens_compared"]["value"] >= 900


@pytest.mark.parametrize("stand_in", ["reference", "half_batch"])
def test_training_in_int8_or_on_half_the_batch_is_not_correct(manifests, stand_in):
    """The control of the train cell (the reference in int8, forward and
    backward) and the half-batch fault planted in the reference: each
    stands in the program's place, is judged by the cell's own limits and
    comes out as not correct; the program's own numbers stand beside it."""
    r = _run(manifests["tiny"], "gpt2-train-t1024", control=stand_in)
    assert r["correct"] is False
    checks = r["checks"]
    names = ("loss_gap", "grad_norm_gap", "delta_norm_gap")
    assert [k for k in names if checks[k]["value"] > checks[k]["limit"]]
    for k in names:  # the program itself was sound
        assert checks["program_" + k]["limit"] is None
        assert checks["program_" + k]["value"] <= checks[k]["limit"]
    if stand_in == "reference":
        assert checks["grad_norm_gap"]["value"] > 10 * checks["program_grad_norm_gap"]["value"]


def test_the_reference_in_int8_is_not_correct_for_serving(manifests):
    """The same for a serve cell: the reference in int8 in the program's
    place, at each position of the served prompts and tokens."""
    r = _run(manifests["8k"], "gpt2-serve-chatdense", control="reference")
    assert r["correct"] is False
    a, b = r["checks"]["program_miss_gap_meansq"], r["checks"]["miss_gap_meansq"]
    assert a["limit"] is None and a["value"] < b["limit"] < b["value"]
