"""ddlint — run the repo's static-analysis suite (docs/ANALYSIS.md).

Three analyzer families over one finding/suppression substrate
(``distributeddeeplearning_tpu/analysis/``):

    ast       host-sync, tracer-bool       (AST over the hot paths)
    hlo       hlo-donation, hlo-collectives, hlo-cache-key
              (lowers every engine step + the SlotEngine program set
              on the forced-8-CPU-device mesh)
    contract  env-docs, env-free-tiers, obs-registry, protocol-vars

Usage::

    python scripts/ddlint.py                  # everything; writes lint.json
    python scripts/ddlint.py --rule env-docs  # one rule, fast iteration
    python scripts/ddlint.py --family ast     # one family
    python scripts/ddlint.py --list           # rule catalogue
    python scripts/ddlint.py --check          # CI drift guard: no write,
                                              # fail if lint.json is stale
    python scripts/ddlint.py --changed-ok     # gate mode (make check):
                                              # run everything, refresh
                                              # lint.json, fail only on
                                              # unsuppressed findings

Exit code 1 on any unsuppressed finding (or, under ``--check``, a stale
``lint.json``). The summary line counts suppressions — they are visible
budget, not silence.
"""

from __future__ import annotations

# The HLO family lowers real programs: force the CPU backend and the
# 8-device test mesh BEFORE anything imports jax.
import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
# The audit reads what it compiles and has no restart to make cheap: the
# persistent compile cache the engine builders would switch on stays off.
os.environ.setdefault("JAX_ENABLE_COMPILATION_CACHE", "false")
_FLAG = "--xla_force_host_platform_device_count=8"
if _FLAG not in os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "") + " " + _FLAG
    ).strip()

import argparse  # noqa: E402
import json  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402

sys.path.insert(
    0, os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)

from distributeddeeplearning_tpu.analysis import (  # noqa: E402
    FAMILIES,
    REPO_ROOT,
    apply_suppressions,
    package_sources,
    rules,
)

LINT_JSON = os.path.join(REPO_ROOT, "lint.json")


def _summary(findings, names) -> dict:
    per_rule = {
        n: {"findings": 0, "suppressed": 0} for n in names
    }
    for f in findings:
        row = per_rule.setdefault(
            f.rule, {"findings": 0, "suppressed": 0}
        )
        row["suppressed" if f.suppressed else "findings"] += 1
    commit = subprocess.run(
        ["git", "-C", REPO_ROOT, "rev-parse", "--short", "HEAD"],
        capture_output=True, text=True,
    ).stdout.strip()
    open_n = sum(r["findings"] for r in per_rule.values())
    supp_n = sum(r["suppressed"] for r in per_rule.values())
    return {
        "commit": commit,
        "date": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
        "ok": open_n == 0,
        "rules": per_rule,
        "findings_total": open_n,
        "suppressions_total": supp_n,
        "findings": [
            {
                "rule": f.rule, "path": f.path, "line": f.line,
                "message": f.message, "suppressed": f.suppressed,
                **({"reason": f.reason} if f.reason else {}),
            }
            for f in sorted(
                findings, key=lambda f: (f.rule, f.path, f.line)
            )
        ],
    }


def _comparable(summary: dict) -> dict:
    """lint.json minus the volatile stamp fields (drift = same commit
    basis, different verdict/findings)."""
    return {
        k: v for k, v in summary.items() if k not in ("commit", "date")
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        description=__doc__,
        formatter_class=argparse.RawDescriptionHelpFormatter,
    )
    p.add_argument("--rule", action="append", default=None,
                   help="run one rule (repeatable) — local iteration")
    p.add_argument("--family", choices=FAMILIES, default=None,
                   help="run one analyzer family")
    p.add_argument("--list", action="store_true",
                   help="print the rule catalogue and exit")
    p.add_argument("--check", action="store_true",
                   help="drift guard: run, don't write, exit 1 if "
                        "lint.json on disk is stale")
    p.add_argument("--changed-ok", action="store_true",
                   help="gate mode: refresh lint.json whatever it held; "
                        "only unsuppressed findings fail")
    p.add_argument("--json", default=None, metavar="PATH",
                   help="write the summary here (default: lint.json at "
                        "the repo root for full runs; off for --rule/"
                        "--family runs)")
    p.add_argument("-q", "--quiet", action="store_true")
    args = p.parse_args(argv)

    catalogue = rules(args.family)
    if args.list:
        for name, (family, desc, _) in sorted(
            catalogue.items(), key=lambda kv: (kv[1][0], kv[0])
        ):
            print(f"{name:16s} [{family:8s}] {desc}")
        return 0

    if args.rule:
        unknown = [r for r in args.rule if r not in catalogue]
        if unknown:
            print(f"unknown rule(s) {unknown}; have {sorted(catalogue)}")
            return 2
        catalogue = {r: catalogue[r] for r in args.rule}

    partial = bool(args.rule or args.family)
    findings = []
    for name, (family, _, runner) in sorted(
        catalogue.items(), key=lambda kv: (kv[1][0], kv[0])
    ):
        t0 = time.perf_counter()
        found = runner()
        if not args.quiet:
            print(
                f"ddlint: {name}: {len(found)} raw finding(s) "
                f"in {time.perf_counter() - t0:.1f}s",
                flush=True,
            )
        findings.extend(found)
    findings = apply_suppressions(findings, package_sources())
    # bad-suppression findings ride along on every run; in partial runs
    # keep only the selected rules' results plus those markers.
    if partial:
        keep = set(catalogue) | {"bad-suppression"}
        findings = [f for f in findings if f.rule in keep]

    summary = _summary(findings, list(catalogue))
    for f in summary["findings"]:
        if not f["suppressed"] or not args.quiet:
            tag = " [suppressed]" if f["suppressed"] else ""
            print(f"{f['path']}:{f['line']}: {f['rule']}: "
                  f"{f['message']}{tag}")

    stale = False
    if args.check and not partial:
        try:
            with open(LINT_JSON) as fh:
                on_disk = json.load(fh)
        except (OSError, json.JSONDecodeError):
            on_disk = None
        stale = on_disk is None or _comparable(on_disk) != _comparable(
            summary
        )
        if stale:
            print("STALE: lint.json does not match this run "
                  "(python scripts/ddlint.py to refresh)")
    elif not partial or args.json:
        path = args.json or LINT_JSON
        with open(path, "w") as fh:
            json.dump(summary, fh, indent=1)
            fh.write("\n")

    n_rules = len(catalogue)
    print(
        f"ddlint: {n_rules} rule(s), "
        f"{summary['findings_total']} finding(s), "
        f"{summary['suppressions_total']} suppression(s)"
        + (" [STALE lint.json]" if stale else "")
    )
    return 0 if summary["ok"] and not stale else 1


if __name__ == "__main__":
    sys.exit(main())
