"""Per-request critical-path digest from trace-stamped event files.

The trace plane's CLI (``distributeddeeplearning_tpu/obs/traces.py``):
point it at a run directory (``OBS_DIR``) or any set of
``events*.jsonl`` files and it reconstructs every request's critical
path — queue wait → prefill → decode ticks → delivery, with chaos
re-routes attributed by cause — then renders the top-K-slowest digest:
each slow request decomposed per phase against the fleet p50, naming
the dominant culprit. Training runs get the same treatment per step
(data wait vs dispatch vs collective).

A path that holds a profiler capture (an ``.xplane.pb``, as
``TRACE_EVERY_N_EPOCHS`` / SIGUSR1 leave under ``<OBS_DIR>/traces``)
also gets the device's view: device seconds by scope group and by pass
(forward, the forward that remat runs again, backward, other) for each
program compiled ahead (``obs/programs.py``; the scope tables lie
beside the capture, the groups beside the model:
``models/transformer_lm.TRAIN_STEP_GROUPS``, or ``models/decoder.
HYBRID_STEP_GROUPS`` for a step with state-space layers), and
the device's idle gaps by the innermost ``ddl:`` span — the bus's own
spans on the profiler's clock — over each gap's middle.

Usage::

    python scripts/trace_report.py RUN_DIR_OR_FILES... [--json] [--top K]
    make trace-report                 # newest runs/<dir>

Gap accounting is first-class: each request's phases must sum to its
measured end-to-end latency within ``max(GAP_TOL_S, GAP_TOL_FRAC *
e2e)`` (docs/OBSERVABILITY.md); the unattributed remainder is printed,
never hidden. Orphan traces (admission point without a terminal
outcome) are listed — a healthy run has zero.
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import sys
from typing import List

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def _ms(v) -> str:
    return f"{(v or 0.0) * 1e3:.1f}ms"


def render(recon: dict, training, top_k: int) -> str:
    from distributeddeeplearning_tpu.obs import traces

    out: List[str] = []
    add = out.append
    add(
        f"trace digest: {recon['count']} request(s), "
        f"{recon['within_tolerance']} within gap tolerance "
        f"(max({traces.GAP_TOL_S:g}s, {traces.GAP_TOL_FRAC:.0%} of e2e)), "
        f"{recon['sheds']} shed, {recon['orphan_count']} orphan(s)"
    )
    if recon["causes"]:
        add("interventions: " + ", ".join(
            f"{c} x{n}" for c, n in sorted(recon["causes"].items())
        ))
    reqs = recon["requests"]
    if reqs:
        p50s = traces.phase_p50s(reqs)
        add("")
        add("fleet p50 per phase: " + "  ".join(
            f"{p} {_ms(p50s[p])}" for p in traces.PHASES
        ) + f"  gap {_ms(p50s['gap'])}  e2e {_ms(p50s['e2e'])}")
        add("")
        add(f"top {top_k} slowest (phase / +excess vs fleet p50):")
        for r in traces.top_slow(reqs, k=top_k, p50s=p50s):
            add(
                f"  req={r.get('req', '?')} tenant={r.get('tenant', '?')} "
                f"e2e {_ms(r['e2e_s'])} outcome={r['outcome']} "
                f"attempts={r['attempts']}"
                f"  <- culprit: {r['culprit']} "
                f"(+{_ms(r['culprit_excess_s'])})"
            )
            cells = []
            for p in traces.PHASES:
                v = r["phases"].get(p, 0.0)
                if v or r["excess"].get(p):
                    cells.append(f"{p} {_ms(v)} (+{_ms(r['excess'][p])})")
            cells.append(
                f"gap {_ms(max(r['gap_s'], 0.0))}"
                + ("" if r["within_tolerance"] else " OVER TOLERANCE")
            )
            add("      " + "  ".join(cells))
            for iv in r["interventions"]:
                add(
                    f"      intervention: {iv['what']} "
                    f"cause={iv.get('cause', '?')}"
                    + (f" from-replica={iv['src']}"
                       if iv.get("src") is not None else "")
                    + (f" replica={iv['replica']}"
                       if iv.get("replica") is not None else "")
                    + (f" dur {_ms(iv['dur_s'])}"
                       if iv.get("dur_s") else "")
                )
    for o in recon["orphans"]:
        add(
            f"ORPHAN trace {o['trace']}: admission seen, no terminal "
            f"outcome ({o['events']} event(s), last wall {o['end_wall']})"
        )
    if training:
        add("")
        add(
            f"training attribution ({training['steps']} step(s), "
            f"{training['procs']} proc(s)): "
            f"wall {training['wall_s']:.3f}s = "
            f"dispatch {training['dispatch_s']:.3f}s + "
            f"data wait {training['data_wait_s']:.3f}s + "
            f"collective {training['collective_s']:.3f}s + "
            f"other {training['other_s']:.3f}s"
        )
        for s in training["slowest"]:
            add(
                f"  slow step p={s['p']} epoch={s.get('epoch', '?')}: "
                f"wall {s['wall_s']:.3f}s (dispatch {s['dispatch_s']:.3f}s, "
                f"data wait {s['data_wait_s']:.3f}s, "
                f"other {s['other_s']:.3f}s)"
            )
    return "\n".join(out)


def device_report(capture_dir: str, groups, profile=None, tables=None) -> dict:
    """One capture reduced: for each program with a scope table beside
    the capture, device seconds by scope group (``groups``) and by pass
    over its runs on every device, and its Mosaic kernels by group and
    pass; for each device, the idle gaps by ``ddl:`` span."""
    from distributeddeeplearning_tpu.obs import programs

    if profile is None:
        profile = programs.load_profile(capture_dir)
    if tables is None:
        tables = programs.load_tables(capture_dir)
    out = {"capture": capture_dir, "programs": [], "idle": [],
           "host_spans": sorted({name for name, _, _ in profile.host})}
    for program, scopes in tables.items():
        by = programs.program_by_scope(
            profile.ops, profile.modules, program, scopes, groups
        )
        if by is not None:
            out["programs"].append(dict(
                by, program=program,
                kernel_calls=programs.kernel_calls_by_pass(scopes, groups),
            ))
    for dev, ops in sorted(profile.ops.items()):
        gaps = programs.idle_gaps_by_span(ops, profile.host)
        window = (max(e[2] for e in ops) - min(e[1] for e in ops)) / 1e9
        out["idle"].append({"device": dev, "window_s": window, "gaps": gaps})
    return out


def step_groups(capture_dir: str, tables=None):
    """The groups that stand beside the model whose step a capture
    holds: ``models/decoder.HYBRID_STEP_GROUPS`` where a program's table
    has a state-space mixer's scope, else ``models/transformer_lm.
    TRAIN_STEP_GROUPS`` (a table is read by the groups it has all of)."""
    from distributeddeeplearning_tpu.models.decoder import HYBRID_STEP_GROUPS
    from distributeddeeplearning_tpu.models.transformer_lm import TRAIN_STEP_GROUPS
    from distributeddeeplearning_tpu.obs import programs

    if tables is None:
        tables = programs.load_tables(capture_dir)
    mixer = HYBRID_STEP_GROUPS[0][0]
    if any(mixer in programs.groups_in(scopes, HYBRID_STEP_GROUPS[:1])
           for scopes in tables.values()):
        return HYBRID_STEP_GROUPS
    return TRAIN_STEP_GROUPS


def kernels_by_pass(calls: dict) -> str:
    """``kernel_calls_by_pass``'s counts on one line: ``attn_core 18
    forward / 18 recompute / 18 backward`` (a kernel under ``recompute``
    is one that remat runs again; ``other`` where a program that takes
    no gradient holds kernels)."""
    return ", ".join(
        f"{group} " + " / ".join(
            f"{n} {p}" for p, n in by_pass.items() if n or p != "other"
        )
        for group, by_pass in sorted(calls.items())
    ) or "none"


def render_device(rep: dict) -> str:
    out: List[str] = [f"device trace: {rep['capture']}"]
    add = out.append
    if not rep["idle"]:
        add("  no device plane in this capture (a CPU run); host spans: "
            + (", ".join(rep["host_spans"]) or "none"))
    if rep["idle"] and not rep["programs"]:
        add("  no scope table beside the capture (scope_tables.json): "
            "device time by model part needs the programs compiled ahead")
    for by in rep["programs"]:
        n = by["runs"]
        add(
            f"  program {by['program']} on {by['devices']} device(s): {n} run(s), "
            f"{1e3 * by['run_s'] / n:.2f} ms a run, "
            f"{1e3 * by['total_s'] / n:.2f} ms in operations"
        )
        add(
            f"    {'group':<16}{'ms/run':>10}{'share':>8}"
            f"{'forward':>10}{'recompute':>10}{'backward':>10}"
        )
        total = by["total_s"] or 1.0
        rows = sorted(by["groups"].items(), key=lambda kv: -kv[1]["seconds"])
        for group, g in rows:
            add(
                f"    {group:<16}{1e3 * g['seconds'] / n:>10.3f}"
                f"{100 * g['seconds'] / total:>7.1f}%"
                f"{1e3 * g['forward_s'] / n:>10.3f}{1e3 * g['recompute_s'] / n:>10.3f}"
                f"{1e3 * (g['backward_s'] - g['recompute_s']) / n:>10.3f}"
            )
        add(
            f"    {'unscoped':<16}{1e3 * by['unscoped_s'] / n:>10.3f}"
            f"{100 * by['unscoped_s'] / total:>7.1f}%   "
            + ", ".join(f"{k} {1e3 * v / n:.3f}" for k, v in by["unscoped_top"][:5])
        )
        in_pass = {p: 1e3 * s / n for p, s in by["by_pass"].items()}
        add(
            f"    {'program':<16}{1e3 * by['total_s'] / n:>10.3f}{100.0:>7.1f}%"
            f"{in_pass['forward']:>10.3f}{in_pass['recompute']:>10.3f}"
            f"{in_pass['backward']:>10.3f}   and {in_pass['other']:.3f} in no pass "
            "(the optimizer, the metrics, the compiler's pathless copies, and its "
            "ragged-dot-* calls, whichever pass asked for them)"
        )
        add("    Mosaic kernels in the program, by group: " + kernels_by_pass(
            by.get("kernel_calls", {})
        ))
    for idle in rep["idle"]:
        gaps = idle["gaps"]
        add(
            f"  device {idle['device']} idle {sum(gaps.values()):.4f} s of "
            f"{idle['window_s']:.4f} s, by innermost ddl: span: "
            + (", ".join(
                f"{k} {v:.4f}" for k, v in sorted(gaps.items(), key=lambda kv: -kv[1])
            ) or "no gap")
        )
    return "\n".join(out)


def chosen_paths(events, prefix: str = "attn.impl.") -> str:
    """What the program chose each time it was traced, one line, from
    the counters under ``prefix``: ``attn.impl.<path>`` (the attention
    core, ``models/vit.Attention``), ``attn.mask.<mask>`` (the mask a
    spec-built layer named), ``attn.fwd.named``, ``attn.bwd.<path>`` and
    ``attn.window.blocks`` (the flash kernels: the forward's results as
    named for a remat policy, the backward, and the steps a window's
    walk visits and skips, ``ops/pallas/flash.py``), ``loss.impl.
    <path>`` (the loss, ``training/train_step.loss_and_hits``),
    ``decoder.layer.<kind>`` and ``moe.*`` (the layers a spec-built
    decoder built, ``models/decoder.py``), ``ssm.impl.<path>`` and
    ``ssm.bwd.pallas`` (a state-space layer's scan, ``ops/ssm.py``, and
    its kernels' traced backward, ``ops/pallas/ssd.py``). A counter with
    no ``shape`` label is keyed by ``visited``/``skipped`` (the window's walk) or
    ``window`` where it has them; ``mib`` (the named results) follows
    the shape."""
    chosen: dict = {}
    for e in events:
        name = str(e.get("name", ""))
        if e.get("kind") == "counter" and name.startswith(prefix):
            labels = e.get("labels") or {}
            shape = list(labels.get("shape") or [
                labels[k] for k in ("pass", "visited", "skipped", "window")
                if k in labels
            ])
            if "mib" in labels:
                shape.append(labels["mib"])
            key = (name[len(prefix):], tuple(shape))
            chosen[key] = chosen.get(key, 0) + int(e.get("value", 1))
    return ", ".join(
        f"{path} x{n} at {list(shape)}" for (path, shape), n in sorted(chosen.items())
    )


def step_statistics(events, prefix: str = "moe.") -> str:
    """The step's own statistics under ``prefix``, one line: what the
    model's layers sowed and a log sync read back (``frontends/explicit``
    counts each with the ``epoch`` it was read in and no other label:
    ``moe.rows_live_share``, ``moe.pairs_local``, ``moe.
    expert_load_max_over_mean``), each as the mean of its readings, since
    a share or a ratio does not add up."""
    read: dict = {}
    for e in events:
        name = str(e.get("name", ""))
        if (
            e.get("kind") == "counter" and name.startswith(prefix)
            and set(e.get("labels") or {}) == {"epoch"}
        ):
            read.setdefault(name, []).append(float(e.get("value", 0.0)))
    return ", ".join(
        f"{name} {sum(xs) / len(xs):.4g} (n={len(xs)})" for name, xs in sorted(read.items())
    )


def find_captures(paths: List[str]) -> List[str]:
    """Directories under ``paths`` that hold an ``.xplane.pb`` at their
    top or below ``plugins/profile``: one per capture."""
    found = []
    for path in paths:
        if not os.path.isdir(path):
            continue
        for pb in sorted(glob.glob(
            os.path.join(path, "**", "*.xplane.pb"), recursive=True
        )):
            head = pb.split(os.sep + "plugins" + os.sep + "profile" + os.sep)[0]
            capture = head if head != pb else os.path.dirname(pb)
            if capture not in found:
                found.append(capture)
    return found


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("paths", nargs="+", help="run dir(s) and/or events*.jsonl")
    p.add_argument("--json", action="store_true", help="emit digest JSON")
    p.add_argument("--top", type=int, default=5, help="slowest requests shown")
    args = p.parse_args(argv)

    from distributeddeeplearning_tpu.obs import report, traces

    devices = []
    captures = find_captures(args.paths)
    if captures:
        devices = [device_report(c, step_groups(c)) for c in captures]
    try:
        loaded = report.load(args.paths)
    except FileNotFoundError as e:
        if devices:  # a bare capture directory: the device's view alone
            if args.json:
                print(json.dumps({"device_traces": devices}, default=str))
            else:
                print("\n".join(render_device(d) for d in devices))
            return 0
        print(f"ERROR: no event files under {e}", file=sys.stderr)
        return 2
    recon = traces.reconstruct(loaded)
    training = traces.training_attribution(loaded)
    if args.json:
        out = dict(recon)
        out["top_slow"] = traces.top_slow(recon["requests"], k=args.top)
        out["training"] = training
        out["device_traces"] = devices
        print(json.dumps(out, default=str))
        return 0
    if not recon["count"] and not recon["orphan_count"] and not training:
        print(
            "no trace-stamped request events found (run predates the "
            "trace plane, or nothing was served)"
        )
    else:
        print(render(recon, training, args.top))
    for what, prefix in (
        ("attention core", "attn.impl."), ("attention mask", "attn.mask."),
        ("attention forward's results, named for block remat to keep (output's shape, MiB)",
         "attn.fwd."),
        ("attention backward", "attn.bwd."),
        ("window walk (pass, steps visited, skipped, window)", "attn.window."),
        ("loss", "loss.impl."), ("decoder layers", "decoder.layer."),
        ("state-space scan", "ssm.impl."), ("state-space scan's backward", "ssm.bwd."),
        ("expert layer", "moe."),
    ):
        paths_chosen = chosen_paths(loaded["events"], prefix)
        if paths_chosen:
            print(f"{what}, as chosen at trace time: " + paths_chosen)
    statistics = step_statistics(loaded["events"])
    if statistics:
        print("expert layer, the step's own statistics (mean of the readings): " + statistics)
    for d in devices:
        print()
        print(render_device(d))
    return 0


if __name__ == "__main__":
    sys.exit(main())
