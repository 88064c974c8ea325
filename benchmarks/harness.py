"""The harness: finds a cell's files by the names ``BENCHMARK.json``
gives, looks for the chip, runs the cell's kind of run and prints the
result line. Everything that belongs to one configuration, one traffic
mix or one per-layer metric is a file of its own:

* ``benchmarks/configs/<config>.json`` — the sizes; ``family`` names the
  plain reference (``benchmarks/references/<family>.py``) and the
  program's side (``benchmarks/programs/<family>.py``).
* ``benchmarks/traffic/<traffic>.json`` — the mix; ``kind`` names the
  runner (``benchmarks/runners/<kind>.py``).
* ``benchmarks/metrics/<metric>.json`` — a per-layer metric: ``reader``
  names ``benchmarks/readers/<reader>.py`` and the rest are its
  arguments (which span, counter or trace event it reads).

A later PR adds a cell or a metric by adding such files and entries in
``BENCHMARK.json``; no file that is here has to change.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import sys
from typing import Any, Callable, Dict, List, Optional

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HERE = os.path.join(ROOT, "benchmarks")
TRACE_DIR = os.path.join(ROOT, ".bench_trace")  # a traced run's files, by cell
NO_CHIP = 3  # exit code: no accelerator, or fewer chips than the cell asks


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    """One entry of ``workloads`` with everything its run needs."""
    name: str
    chips: int
    config: Dict[str, Any]
    traffic: Dict[str, Any]
    seed: int
    seconds: float
    trace: bool
    manifest: Dict[str, Any]
    t_start: float  # time.monotonic() at process start
    bench_dir: str = HERE
    require_chip: bool = True
    # a test's hook: called with the program-side object (server or
    # trainer pieces) once it is built, to break the timed path underneath
    sabotage: Optional[Callable[[Any], None]] = None
    # "program": the program's own lower-precision path switched on;
    # "reference": the reference in the control's precision stands in the
    # program's place; "half_batch" (training): so does the reference with
    # half of the batch left out. Each has to come out as not correct.
    control: Optional[str] = None
    keep_trace: bool = False  # leave .bench_trace/<cell> for a look by hand

    def family(self, side: str):
        return importlib.import_module(
            f"benchmarks.{side}.{self.config['family']}"
        )

    def metric_names(self, group: str) -> List[str]:
        """Metrics of ``group`` that this cell reports."""
        return [
            m["name"] for m in self.manifest[group]
            if "workloads" not in m or self.name in m["workloads"]
        ]


def _read_json(path: str) -> Dict[str, Any]:
    with open(path) as fh:
        return json.load(fh)


def load_cell(workload: str, seed: int, seconds: float, trace: bool,
              t_start: float, manifest_path: Optional[str] = None,
              **kw) -> Cell:
    path = manifest_path or os.path.join(ROOT, "BENCHMARK.json")
    manifest = _read_json(path)
    base = os.path.dirname(os.path.abspath(path))
    entry = next((w for w in manifest["workloads"] if w["name"] == workload), None)
    if entry is None:
        raise SystemExit(
            f"no workload {workload!r} in {path} "
            f"(have {[w['name'] for w in manifest['workloads']]})"
        )
    cfg_entry = next(c for c in manifest["configs"] if c["name"] == entry["config"])
    # traffic/ and metrics/ sit beside the directory of the config's file
    bench_dir = os.path.dirname(os.path.dirname(os.path.join(base, cfg_entry["file"])))
    return Cell(
        name=workload, chips=int(entry["chips"]),
        config=_read_json(os.path.join(base, cfg_entry["file"])),
        traffic=_read_json(os.path.join(bench_dir, "traffic", entry["traffic"] + ".json")),
        bench_dir=bench_dir,
        seed=int(seed), seconds=float(seconds), trace=bool(trace),
        manifest=manifest, t_start=t_start, **kw,
    )


def device_info(cell: Cell) -> Dict[str, Any]:
    """The device as JAX reports it; raises :class:`NoChip` where the
    run has no accelerator or too few chips."""
    import jax

    devs = jax.devices()
    d = devs[0]
    if cell.require_chip and (d.platform != "tpu" or len(devs) < cell.chips):
        raise NoChip(
            f"{cell.name} needs {cell.chips} TPU chip(s); JAX found "
            f"{len(devs)} x {d.platform} ({d.device_kind})"
        )
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest chip, as the runtime counts them
    (program temporaries are not in this counter on the TPU; the runners
    add the largest compiled temporary they ran)."""
    import jax

    peak = 0
    for d in jax.devices()[:chips]:
        stats = d.memory_stats() or {}
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
    return peak


def enable_cache() -> str:
    """The program's own rule places the persistent compile cache:
    ``JAX_COMPILATION_CACHE_DIR`` if whoever runs us set it, else
    ``<checkout>/.jax_cache`` — a fixed path inside the checkout."""
    from distributeddeeplearning_tpu.training.warmup import enable_compile_cache

    return enable_compile_cache()


def read_per_layer(cell: Cell, run: Dict[str, Any]) -> Dict[str, Dict[str, Any]]:
    """Each per-layer metric of the cell through its reader. A reader
    that finds nothing to read returns None and the metric is left out."""
    units = {m["name"]: m["unit"] for m in cell.manifest["per_layer"]}
    out = {}
    for name in cell.metric_names("per_layer"):
        path = os.path.join(cell.bench_dir, "metrics", name + ".json")
        if not os.path.exists(path):
            path = os.path.join(HERE, "metrics", name + ".json")
        spec = _read_json(path)
        reader = importlib.import_module(f"benchmarks.readers.{spec['reader']}")
        value = reader.read(run, spec)
        if value is not None:
            out[name] = {"value": float(value), "unit": units[name]}
    return out


def finish(cell: Cell, run: Dict[str, Any]) -> Dict[str, Any]:
    """The result line's object from what a runner measured."""
    from benchmarks import stats

    units = {m["name"]: m["unit"] for m in cell.manifest["end_to_end"]}
    if cell.trace:
        metrics = read_per_layer(cell, run)
    else:
        metrics = {
            name: {"value": float(run["end_to_end"][name]), "unit": units[name]}
            for name in cell.metric_names("end_to_end")
        }
    device = dict(run["device"])
    device["memory_peak_bytes"] = int(run["memory_peak_bytes"])
    result: Dict[str, Any] = {
        "correct": bool(run["correct"]),
        "attempted": int(run["attempted"]),
        "failed": int(run["failed"]),
        "metrics": metrics,
        "device": device,
    }
    if cell.trace and run.get("trace_summary"):
        device["busy_s"] = float(run["trace_summary"]["busy_s"])
        device["window_s"] = float(run["trace_summary"]["window_s"])
        result["breakdown"] = run["breakdown"]
    # beside the metrics, for whoever sets a bound: the host-clock samples'
    # medians and tails in every run, traced or not (the driver ignores the key)
    result["beside"] = {
        f"{name}.p{q}": stats.percentile(xs, q)
        for name, xs in run.get("samples", {}).items() if xs for q in (50, 95)
    }
    result["beside"].update(run.get("host", {}))
    result["reference_s"] = float(run["reference_s"])  # not part of setup_s
    result["checks"] = run["checks"]
    return result


def attach_trace(cell: Cell, run: Dict[str, Any], trace) -> bool:
    """Put the traced window's summary and breakdown into ``run``. On a
    chip a trace in which no operation ran is an error; a rehearsal on
    the CPU has no device plane and reports the host-clock metrics only."""
    from benchmarks import tracing

    run["trace"] = trace
    if trace is None or (not trace.ops and not cell.require_chip):
        run["trace"] = None
        return False
    run["trace_summary"] = tracing.summary(trace)
    run["breakdown"] = tracing.breakdown(trace)
    return True


def judge(readings: Dict[str, float], limits: Dict[str, float]) -> Dict[str, Dict[str, float]]:
    """Each number compared beside its limit. A reading with no limit in
    the traffic file (a count, the program's own reading beside a
    control's) is carried with ``limit: null`` and not judged."""
    return {
        k: {"value": float(v), "limit": limits.get(k)}
        for k, v in readings.items()
    }


def all_within(checks: Dict[str, Dict[str, float]]) -> bool:
    judged = [c for c in checks.values() if c["limit"] is not None]
    return bool(judged) and all(
        c["value"] == c["value"] and c["value"] <= c["limit"] for c in judged
    )


def print_checks(checks: Dict[str, Dict[str, float]]) -> None:
    for k, c in checks.items():
        print(f"check {k} value={c['value']!r} limit={c['limit']!r}",
              file=sys.stderr)
    sys.stderr.flush()


def run_cell(cell: Cell) -> Dict[str, Any]:
    runner = importlib.import_module(f"benchmarks.runners.{cell.traffic['kind']}")
    run = runner.run(cell)
    result = finish(cell, run)
    print_checks(result["checks"])
    return result
