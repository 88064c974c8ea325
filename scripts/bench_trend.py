"""Regression sentinel over a trajectory of archived bench records.

Each file matched by ``--glob`` is one round: ``{"n": <round>, "rc":
<exit code>, "parsed": <the bench.py record>}``, named
``BENCH_r<k>.json``. This script reads that trajectory and answers the
one question a perf-focused repo must keep answering: **did a
like-for-like headline regress?** A round that did not measure — an
``error`` field, a value ≤ 0, an unparseable record — is **listed but
skipped**, never read as a 100% drop. Also skipped, as new baselines
rather than regressions: cross-platform pairs (``device.platform``),
pairs whose
``kv_dtype``/``weight_dtype`` changed (a re-quantized protocol is a new
baseline, not a regression; records predating the quantized tier count
as the native "bf16" config), pairs whose ``spec_k`` changed (a
re-speculated protocol likewise — records predating the speculative
tier count as ``spec_k=0``), pairs whose ``data_format`` changed
(synthetic pool vs streamed shards is a different input pipeline —
``data_change`` skip; records predating the streamed tier count as the
native synthetic reader), pairs whose ``chaos_plan`` differs (a
fault storm is part of the protocol — ``chaos_change`` skip;
chaos-free records normalize to no plan), pairs whose ``coloc``
knob string differs (a re-arbitrated pool — different geometry,
shrink step, or surge window — is a new colocation protocol —
``coloc_change`` skip; non-colocated records normalize to none),
pairs whose disaggregation ``pool_split`` differs (re-drawing the
prefill/decode pool boundary is a new serving protocol —
``disagg_change`` skip; colocated records normalize to none),
and pairs whose
``decode_kernel`` changed (the fused Pallas decode path vs the stitched
XLA lowering is a different machine program per token —
``kernel_change`` skip; records predating the kernel tier count as the
native ``xla`` lowering).

A drop > ``--threshold`` (default 10%) between *consecutive comparable*
records of the same metric+platform exits nonzero — the CI tripwire
``make bench-trend`` wires up.

Usage::

    python scripts/bench_trend.py --glob 'records/BENCH_r*.json'
        [--threshold 0.10] [--json]
"""

from __future__ import annotations

import argparse
import glob as globlib
import json
import os
import re
import sys
from typing import Any, Dict, List, Optional

_ROUND_RE = re.compile(r"BENCH_r(\d+)\.json$")


def load_round(path: str) -> Dict[str, Any]:
    """One trajectory entry: round number + the parsed bench record
    (may be absent when the round's output was unparseable)."""
    with open(path) as fh:
        d = json.load(fh)
    m = _ROUND_RE.search(os.path.basename(path))
    n = d.get("n") if isinstance(d, dict) else None
    if n is None and m:
        n = int(m.group(1))
    record = d.get("parsed") if isinstance(d, dict) else None
    return {
        "path": path,
        "round": n,
        "rc": d.get("rc") if isinstance(d, dict) else None,
        "record": record if isinstance(record, dict) else None,
    }


def classify(entry: Dict[str, Any]) -> Optional[str]:
    """Why this round is NOT comparable (None = comparable).

    A round that did not measure shows up as an unparsed record, an
    error field or a non-positive value. Reporting any of them as a
    regression would be the 100%-drop misread this sentinel exists to
    kill."""
    rec = entry["record"]
    if rec is None:
        return "unparsed"
    if rec.get("error"):
        return "error"
    try:
        if float(rec.get("value", 0.0)) <= 0.0:
            return "zero_value"
    except (TypeError, ValueError):
        return "bad_value"
    return None


def analyze(
    paths: List[str], threshold: float = 0.10
) -> Dict[str, Any]:
    """The trajectory verdict: per-round rows + like-for-like drops."""
    entries = sorted(
        (load_round(p) for p in paths),
        key=lambda e: (e["round"] is None, e["round"] or 0),
    )
    rows: List[Dict[str, Any]] = []
    regressions: List[Dict[str, Any]] = []
    # metric -> last comparable (round, value, platform, dtypes)
    last: Dict[str, Dict[str, Any]] = {}
    for e in entries:
        rec = e["record"] or {}
        skip = classify(e)
        detail = rec.get("detail") or {}
        row = {
            "round": e["round"],
            "metric": rec.get("metric"),
            "value": rec.get("value"),
            "unit": rec.get("unit"),
            "platform": (rec.get("device") or {}).get("platform"),
            # A kv_dtype/weight_dtype change is a protocol change, not a
            # regression — same treatment as a platform change. Records
            # predating the quantized tier carry no dtype fields; they
            # ran the native ("bf16") engines, so absent normalizes to
            # that and stays comparable.
            "dtypes": (
                detail.get("kv_dtype") or "bf16",
                detail.get("weight_dtype") or "bf16",
            ),
            # A spec_k change re-shapes the whole protocol (draft +
            # verify programs, commits per tick) — a new baseline, not
            # a regression; records predating the speculative tier ran
            # spec_k=0 and stay comparable. Same treatment as dtypes.
            "spec_k": int(detail.get("spec_k") or 0),
            # A data-format change (synthetic pool -> streamed shards,
            # or any reader swap) re-shapes the input side of a train
            # protocol — different bytes, different host pipeline — so
            # it is a protocol skip, not a regression. Records predating
            # the streamed tier carry no field and normalize to the
            # native synthetic reader.
            "data_format": detail.get("data_format") or "native",
            # A replica-count change re-shapes the fleet protocol the
            # same way (aggregate throughput over N pools is a new
            # baseline); non-fleet records normalize to 1 replica.
            "replicas": int(detail.get("replicas") or 1),
            # A decode-kernel swap (stitched XLA lowering <-> fused
            # Pallas paged-decode) replaces the per-token machine
            # program outright — a new baseline, not a regression.
            # Records predating the kernel tier carry no field and ran
            # the native "xla" lowering.
            "kernel": detail.get("decode_kernel") or "xla",
            # A chaos plan's presence (or a different storm) re-shapes
            # the whole run — faults, rebuilds and brownout windows are
            # part of the protocol, not noise around it — so any
            # chaos-plan difference is a protocol skip, never a
            # regression. Chaos-free records normalize to "".
            "chaos": str(detail.get("chaos_plan") or ""),
            # The colocation knob string (pool geometry, shrink step,
            # brownout stages, surge window — coloc_bench's `coloc`
            # detail) re-shapes the arbitrated storm the same way: a
            # different arbitration protocol is a new baseline
            # (``coloc_change`` skip), never a regression.
            # Non-colocated records normalize to "".
            "coloc": str(detail.get("coloc") or ""),
            # The disaggregation pool split (disagg_bench's
            # `pool_split` detail, e.g. "prefill:2,decode:2"): moving
            # replicas between the prefill and decode pools re-shapes
            # which phase each engine serves — a new serving protocol
            # (``disagg_change`` skip), never a regression. Colocated
            # records normalize to "".
            "pools": str(detail.get("pool_split") or ""),
            # An elastic world resize is the training-side analog: the
            # same metric over a different device count is a new
            # baseline (``world_change`` skip). Pre-elastic records
            # carry no world_size but always recorded ``devices`` — the
            # same number — so they normalize to it and stay comparable
            # across the field's introduction; records with neither
            # normalize to 0 ("unspecified").
            "world": int(
                detail.get("world_size") or detail.get("devices") or 0
            ),
            "skip": skip,
            "delta_pct": None,
        }
        if skip is None:
            metric = rec["metric"]
            value = float(rec["value"])
            prev = last.get(metric)
            if (
                prev is not None
                and prev["platform"] == row["platform"]
                and prev["dtypes"] == row["dtypes"]
                and prev["spec_k"] == row["spec_k"]
                and prev["kernel"] == row["kernel"]
                and prev["replicas"] == row["replicas"]
                and prev["world"] == row["world"]
                and prev["data_format"] == row["data_format"]
                and prev["chaos"] == row["chaos"]
                and prev["coloc"] == row["coloc"]
                and prev["pools"] == row["pools"]
            ):
                delta = (value - prev["value"]) / prev["value"]
                row["delta_pct"] = round(delta * 100.0, 2)
                if delta < -threshold:
                    regressions.append({
                        "metric": metric,
                        "from_round": prev["round"],
                        "to_round": e["round"],
                        "from_value": prev["value"],
                        "to_value": value,
                        "drop_pct": round(-delta * 100.0, 2),
                    })
            elif prev is not None and prev["platform"] != row["platform"]:
                row["skip"] = (
                    f"platform_change:{prev['platform']}->{row['platform']}"
                )
            elif prev is not None and prev["dtypes"] != row["dtypes"]:
                row["skip"] = (
                    f"dtype_change:{'/'.join(prev['dtypes'])}"
                    f"->{'/'.join(row['dtypes'])}"
                )
            elif prev is not None and prev["spec_k"] != row["spec_k"]:
                row["skip"] = (
                    f"spec_change:k={prev['spec_k']}->k={row['spec_k']}"
                )
            elif prev is not None and prev["kernel"] != row["kernel"]:
                row["skip"] = (
                    f"kernel_change:{prev['kernel']}->{row['kernel']}"
                )
            elif prev is not None and prev["replicas"] != row["replicas"]:
                row["skip"] = (
                    f"replica_change:{prev['replicas']}"
                    f"->{row['replicas']}"
                )
            elif prev is not None and prev["data_format"] != row["data_format"]:
                row["skip"] = (
                    f"data_change:{prev['data_format']}"
                    f"->{row['data_format']}"
                )
            elif prev is not None and prev["chaos"] != row["chaos"]:
                row["skip"] = (
                    f"chaos_change:"
                    f"{prev['chaos'] or 'none'}->{row['chaos'] or 'none'}"
                )
            elif prev is not None and prev["coloc"] != row["coloc"]:
                row["skip"] = (
                    f"coloc_change:"
                    f"{prev['coloc'] or 'none'}->{row['coloc'] or 'none'}"
                )
            elif prev is not None and prev["pools"] != row["pools"]:
                row["skip"] = (
                    f"disagg_change:"
                    f"{prev['pools'] or 'none'}->{row['pools'] or 'none'}"
                )
            elif prev is not None:
                row["skip"] = (
                    f"world_change:{prev['world'] or 'unspecified'}"
                    f"->{row['world'] or 'unspecified'}"
                )
            if row["skip"] is None or "_change" in str(row["skip"]):
                # A protocol/platform transition row is not COMPARED,
                # but it IS the new baseline — otherwise one permanent
                # dtype/spec change would skip every later round forever
                # and the sentinel would go blind for that metric.
                last[metric] = {
                    "round": e["round"], "value": value,
                    "platform": row["platform"], "dtypes": row["dtypes"],
                    "spec_k": row["spec_k"], "kernel": row["kernel"],
                    "replicas": row["replicas"],
                    "world": row["world"],
                    "data_format": row["data_format"],
                    "chaos": row["chaos"],
                    "coloc": row["coloc"],
                    "pools": row["pools"],
                }
        rows.append(row)
    return {
        "rows": rows,
        "regressions": regressions,
        "threshold_pct": threshold * 100.0,
        "ok": not regressions,
    }


def render(result: Dict[str, Any]) -> str:
    out = []
    add = out.append
    add(f"{'round':>5s} {'metric':42s} {'value':>12s} {'Δ%':>8s}  note")
    for r in result["rows"]:
        val = "-" if r["value"] is None else f"{r['value']:.1f}"
        delta = "-" if r["delta_pct"] is None else f"{r['delta_pct']:+.1f}"
        note = r["skip"] or (r["platform"] or "")
        add(
            f"{r['round'] if r['round'] is not None else '?':>5} "
            f"{(r['metric'] or '<unparsed>'):42s} {val:>12s} {delta:>8s}"
            f"  {note}"
        )
    if result["regressions"]:
        add("")
        for g in result["regressions"]:
            add(
                f"REGRESSION: {g['metric']} dropped {g['drop_pct']:.1f}% "
                f"(round {g['from_round']}: {g['from_value']:.1f} -> "
                f"round {g['to_round']}: {g['to_value']:.1f}; "
                f"threshold {result['threshold_pct']:.0f}%)"
            )
    else:
        add("")
        add(
            f"OK: no like-for-like drop > {result['threshold_pct']:.0f}% "
            f"(rounds that did not measure are skipped, not misread)"
        )
    return "\n".join(out)


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument(
        "--glob", required=True,
        help="trajectory files, e.g. 'records/BENCH_r*.json'",
    )
    p.add_argument("--threshold", type=float, default=0.10,
                   help="like-for-like drop that fails (fraction)")
    p.add_argument("--json", action="store_true")
    args = p.parse_args(argv)
    paths = sorted(globlib.glob(args.glob))
    if not paths:
        print(f"ERROR: no trajectory files match {args.glob}",
              file=sys.stderr)
        return 2
    result = analyze(paths, threshold=args.threshold)
    if args.json:
        print(json.dumps(result))
    else:
        print(render(result))
    return 0 if result["ok"] else 1


if __name__ == "__main__":
    sys.exit(main())
