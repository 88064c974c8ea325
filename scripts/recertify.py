"""Re-certify every headline number at ONE commit (VERDICT r4 #2).

Runs the full BASELINE.md measurement battery back-to-back in fresh
subprocesses (one per protocol — separate processes keep compile caches
and allocator state from bleeding between rows) and writes
``RECERT.json`` with (commit, date, row) for each. BASELINE.md rows are
then refreshed from that file in one edit.

Protocols (all via bench.py's existing modes — no new measurement code):

    resnet50      BENCH_BATCH=256                      images/sec
    vit_b16       BENCH_MODEL=vit_b16 BENCH_BATCH=256  images/sec
    efficientnet  BENCH_MODEL=efficientnet_b4 ...      images/sec
    lm_small @1k  BENCH_MODEL=lm_small SEQ=1024        tokens/sec
    lm_small @8k  ... SEQ=8192 (flash kernel regime)   tokens/sec
    lm_small @32k ... SEQ=32768 BATCH=1                tokens/sec
    lm_moe_small  BENCH_MODEL=lm_moe_small             tokens/sec
    decode        BENCH_DECODE=1 (b=8, 128+128)        tokens/sec
    serve_lm      scripts/serve_bench.py (32k vocab)   tokens/sec
    serve_lm_paged  serve_bench dense-vs-paged A/B at  tokens/sec
                    a fixed pool-byte budget (longtail)
    serve_lm_int8   serve_bench bf16-vs-int8 (KV +     tokens/sec
                    weights) at a fixed byte budget,
                    teacher-forced match-rate oracle
    serve_lm_spec   serve_bench greedy-vs-speculative  tokens/sec
                    (int8 self-draft, K=4), bitwise
                    greedy parity + accept-rate stats
    serve_lm_fleet  fleet_bench 1-vs-2 router-fronted  tokens/sec
                    replicas, multi-tenant closed
                    backlog: scaling + flat TTFT +
                    weighted fairness + bitwise parity
    serve_lm_disagg disagg_bench split prefill/decode  tokens/sec
                    pools vs colocated at equal
                    replica count: TTFT win, parity,
                    prefill-once directory, live
                    migration, closed sets per pool
    serve_lm_chaos  chaos_bench seeded mixed-verb      tokens/sec
                    fault storm (crash/hang/slow/
                    corrupt/flap) + brownout ladder:
                    splice parity, corrupt healed,
                    breaker budget, bounded TTFT
    lm_coloc        coloc_bench train/serve pool       tokens/sec
                    arbitration under a combined
                    fault+chaos storm: ULP re-join,
                    zero-drop, lease/capacity cycle
    lm_stream       stream_bench pretrain-on-shards    tokens/sec
                    (streamed reader, cursor manifest)
                    -> restore -> SlotEngine greedy
                    serve, gated vs inference.generate

Usage::

    python scripts/recertify.py [--only resnet50,vit_b16] [--timeout 900]
"""

from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

PROTOCOLS = {
    "resnet50": {"BENCH_BATCH": "256"},
    # In-step gradient accumulation A/B at the bench batch: one dispatch
    # scans 4 microbatches of 64 — certifies the on-chip cost of the
    # ACCUM_STEPS scan the moment hardware returns (PROFILE.md carries
    # the host-side memory proof meanwhile). Shares the battery's
    # compilation cache with the plain resnet50 row SAFELY: ACCUM_STEPS
    # changes the lowered HLO (the scan + accumulator), so the XLA
    # persistent-cache key — a hash of the HLO module — cannot collide
    # between rows that differ only in this env var (guarded by
    # tests/test_grad_accum.py::test_accum_changes_compiled_program).
    "resnet50_accum4": {"BENCH_BATCH": "256", "ACCUM_STEPS": "4"},
    "vit_b16": {"BENCH_MODEL": "vit_b16", "BENCH_BATCH": "256"},
    "efficientnet_b4": {"BENCH_MODEL": "efficientnet_b4", "BENCH_BATCH": "64"},
    "lm_small_1k": {
        "BENCH_MODEL": "lm_small", "BENCH_SEQ_LEN": "1024", "BENCH_BATCH": "8",
    },
    "lm_small_8k": {
        "BENCH_MODEL": "lm_small", "BENCH_SEQ_LEN": "8192", "BENCH_BATCH": "1",
    },
    "lm_small_32k": {
        "BENCH_MODEL": "lm_small", "BENCH_SEQ_LEN": "32768", "BENCH_BATCH": "1",
    },
    "lm_moe_small": {
        "BENCH_MODEL": "lm_moe_small", "BENCH_SEQ_LEN": "1024",
        "BENCH_BATCH": "8",
    },
    "decode": {"BENCH_DECODE": "1", "BENCH_MODEL": "lm_small"},
    # Serving tier: continuous batching vs sequential generate at 32k
    # vocab under Poisson load (scripts/serve_bench.py — its own
    # entrypoint, not a bench.py mode; the row's JSON line carries
    # speedup, TTFT p50/p99, occupancy and the compile count, and the
    # script exits non-zero on parity loss or a mid-measure recompile).
    "serve_lm": {
        "_script": "scripts/serve_bench.py",
        "BENCH_MODEL": "lm_tiny", "BENCH_VOCAB": "32000",
        "SERVE_REQUESTS": "32", "SERVE_MAX_NEW": "16",
        "SERVE_RATE_RPS": "200", "SERVE_SLOTS": "8", "SERVE_BUCKETS": "8,16",
    },
    # Paged KV pool headline (docs/SERVING.md): dense vs paged at the
    # SAME pool-byte budget on the long-tail length mix — the row's JSON
    # line carries both runs, capacity_ratio and tps_ratio, and the
    # script exits non-zero unless paged reaches >=2x concurrency (or
    # >=1.5x tokens/sec) with bitwise parity and zero recompiles.
    "serve_lm_paged": {
        "_script": "scripts/serve_bench.py",
        "BENCH_MODEL": "lm_tiny", "BENCH_VOCAB": "32000",
        "SERVE_KV_LAYOUT": "compare", "SERVE_PROFILE": "longtail",
        "SERVE_REQUESTS": "32", "SERVE_RATE_RPS": "0",
        "SERVE_SLOTS": "16", "SERVE_POOL_SLOT_BUDGET": "4",
        "SERVE_BLOCK_SIZE": "16",
    },
    # Quantized decode tier (docs/SERVING.md): bf16 vs int8 KV+weights
    # engines at the SAME KV-pool byte budget on a decode-heavy greedy
    # load — the row's JSON line carries both runs, tps/capacity ratios
    # and the teacher-forced greedy match rate, and the script exits
    # non-zero unless match >= 0.95 AND int8 tokens/sec >= bf16 with
    # zero recompiles and closed program sets on both engines.
    "serve_lm_int8": {
        "_script": "scripts/serve_bench.py",
        "BENCH_MODEL": "lm_tiny", "BENCH_VOCAB": "32000",
        "SERVE_KV_DTYPE": "int8", "SERVE_WEIGHT_DTYPE": "int8",
        "SERVE_PROFILE": "mixed", "SERVE_MAX_NEW": "32",
        "SERVE_REQUESTS": "48", "SERVE_RATE_RPS": "0",
        "SERVE_POOL_SLOT_BUDGET": "4", "SERVE_PREFILLS_PER_STEP": "4",
    },
    # Speculative decode tier (docs/SERVING.md): plain greedy vs the
    # int8 self-draft speculative engine on a decode-heavy closed
    # backlog — the row's JSON line carries both runs, the accept-rate
    # p50/mean and draft/verify time split, and the script exits
    # non-zero unless spec tokens/sec >= 1.4x the greedy baseline with
    # BITWISE greedy parity, zero mid-measure recompiles, and both
    # program sets closed at their static counts.
    "serve_lm_spec": {
        "_script": "scripts/serve_bench.py",
        "BENCH_MODEL": "lm_tiny", "BENCH_VOCAB": "32000",
        "SERVE_SPEC_K": "4", "SERVE_SPEC_DRAFT": "int8",
        "SERVE_PROFILE": "mixed", "SERVE_MAX_NEW": "64",
        "SERVE_REQUESTS": "24", "SERVE_RATE_RPS": "0",
        "SERVE_SLOTS": "8", "SERVE_PREFILLS_PER_STEP": "8",
    },
    # Fleet tier (docs/SERVING.md): one seeded multi-tenant closed
    # backlog served by 1 vs 2 router-fronted replicas — the row's JSON
    # line carries both runs, the scaling ratio and its basis
    # (single-core hosts CANNOT scale linearly and say so instead of
    # faking it), p99-TTFT ratio, per-tenant fairness at contention and
    # the per-replica compile ledgers; the script exits non-zero unless
    # scaling >= the basis floor AND p99 TTFT holds AND every tenant's
    # token share is within 15% of its weight share AND streams are
    # bitwise identical across runs with closed program sets.
    "serve_lm_fleet": {
        "_script": "scripts/fleet_bench.py",
        "BENCH_MODEL": "lm_tiny", "BENCH_VOCAB": "32000",
        "SERVE_REPLICAS": "2", "SERVE_SLOTS": "4",
        "SERVE_TENANT_WEIGHTS": "gold:3,silver:2,bronze:1",
        "SERVE_PLACEMENT": "affinity",
        "SERVE_REQUESTS": "48", "SERVE_MAX_NEW": "16",
        "SERVE_RATE_RPS": "0", "SERVE_BUCKETS": "8,16",
    },
    # Serving chaos plane (docs/ROBUSTNESS.md serving failure model):
    # one seeded mixed-verb fault storm (crash+hang+slow+corrupt+flap,
    # chaos.storm_plan) over a closed 3-tenant backlog on 2 replicas,
    # with the brownout ladder driven through a deterministic burn
    # window — the row's JSON line carries the undisturbed and storm
    # runs, the fired-fault ledger and every gate verdict, and the
    # script exits non-zero unless every non-shed request completes
    # with BITWISE splice parity, the corrupt injection is detected
    # and healed (never delivered), the flap opens the breaker inside
    # its declared budget, program sets stay closed (rebuilds
    # itemized), p99 TTFT holds within the declared multiple, and the
    # brownout ladder steps down AND back up.
    "serve_lm_chaos": {
        "_script": "scripts/chaos_bench.py",
        "BENCH_MODEL": "lm_tiny", "BENCH_VOCAB": "32000",
        "SERVE_REPLICAS": "2", "SERVE_SLOTS": "4",
        "SERVE_TENANT_WEIGHTS": "gold:3,silver:2,bronze:1",
        "SERVE_REQUESTS": "36", "SERVE_MAX_NEW": "16",
        "SERVE_RATE_RPS": "0", "SERVE_BUCKETS": "8,16",
        "SERVE_CHAOS_SEED": "0",
    },
    # Disaggregation tier (docs/SERVING.md disaggregation): the same
    # bimodal hot-prefix backlog served by a colocated fleet and by the
    # SAME replica count split into prefill/decode pools with the
    # fleet-wide prefix directory on — the row's JSON line carries both
    # runs, the p99-TTFT speedup, the handoff/migration/directory
    # ledgers and every gate verdict, and the script exits non-zero
    # unless disagg p99 TTFT strictly beats coloc, inter-token p99
    # stays inside its factor, every stream is bitwise equal to
    # sequential generate, the directory probe re-serves a shared
    # prompt with ZERO fleet-wide prefill executions, one scheduled
    # live migration lands with zero drops, and program sets stay
    # closed per pool.
    "serve_lm_disagg": {
        "_script": "scripts/disagg_bench.py",
        "BENCH_MODEL": "lm_tiny", "BENCH_VOCAB": "32000",
        "SERVE_REPLICAS": "4", "SERVE_SLOTS": "4",
        "SERVE_TENANT_WEIGHTS": "alpha:1,beta:1",
        "SERVE_REQUESTS": "24", "SERVE_RATE_RPS": "0",
        "SERVE_PROFILE": "disagg", "SERVE_SEED": "0",
    },
    # Colocation tier (docs/ROBUSTNESS.md colocation): ONE device pool
    # shared by training and serving under a combined fault+chaos storm
    # — a serving surge drives the brownout ladder to exhaustion, the
    # PoolArbiter shrinks training through the capacity file
    # (owner="arbiter"), the FleetController's scale-up is lease-gated
    # (denied -> backoff, granted -> second replica), then reclaim
    # drains the leased replica zero-drop and training grows back; the
    # script exits non-zero unless the training trajectory re-joins the
    # uninterrupted reference at f32 ULP, serving p99 TTFT holds the
    # COLOC_TTFT_SLO_MS bound, every request completes with bitwise
    # stream parity (zero dropped, zero mixed-version), program sets
    # stay closed, and the full shrink -> lease -> reclaim -> grow
    # cycle is observed with the capacity file round-tripping.
    "lm_coloc": {
        "_script": "scripts/coloc_bench.py",
        "BENCH_MODEL": "lm_tiny", "BENCH_VOCAB": "64",
        "SERVE_REQUESTS": "24", "SERVE_MAX_NEW": "12",
        "SERVE_TENANT_WEIGHTS": "gold:3,silver:2,bronze:1",
        "SERVE_CHAOS_SEED": "0",
        "COLOC_POOL_DEVICES": "8", "COLOC_SHRINK_STEP": "6",
    },
    # Streamed data plane + the first pretrain->serve artifact
    # (docs/DATA.md): pretrain lm_tiny on seeded token shards through
    # the stream reader (checkpointable shuffle cursor + host prefetch),
    # restore the final checkpoint FROM DISK, serve it greedily through
    # a SlotEngine — the row's JSON line carries training tokens/sec on
    # the streamed reader plus the three gates (restored params bitwise
    # == trained, manifest carries the data_cursor, served streams
    # token-equal to inference.generate), and the script exits non-zero
    # if any gate fails.
    "lm_stream": {
        "_script": "scripts/stream_bench.py",
        "BENCH_MODEL": "lm_tiny",
        "STREAM_RECORDS": "512", "STREAM_SEQ_LEN": "64",
        "STREAM_VOCAB": "256", "STREAM_SHARD_RECORDS": "128",
        "STREAM_SHUFFLE_BLOCK": "64", "STREAM_BATCH": "8",
        "STREAM_EPOCHS": "2", "PREFETCH_HOST_BATCHES": "2",
        "SERVE_MAX_NEW": "16", "SERVE_SLOTS": "4",
    },
}


# Every var a protocol row may define: ambient values are dropped before
# a row's own env applies, so an exported BENCH_MODEL/ACCUM_STEPS can
# never leak into rows that deliberately leave it unset (the rows are
# the protocol — the environment only supplies infra knobs like
# JAX_COMPILATION_CACHE_DIR/JAX_PLATFORMS).
_PROTOCOL_VARS = (
    "BENCH_MODEL", "BENCH_BATCH", "BENCH_SEQ_LEN", "BENCH_DECODE",
    "BENCH_DEPTH", "BENCH_IMAGE_SIZE", "BENCH_SCALING", "ACCUM_STEPS",
    # Overlap toggle (training/overlap.py): an ambient
    # ASYNC_COLLECTIVES=0 would silently re-lower every train row's
    # gradient all-reduces without the overlap tag.
    "ASYNC_COLLECTIVES",
    # Decode-row geometry + the profile-capture dir (a leaked
    # BENCH_PROFILE would trace-capture every row's measured region).
    "BENCH_PROMPT_LEN", "BENCH_NEW_TOKENS", "BENCH_PROFILE",
    "BENCH_VOCAB", "SERVE_REQUESTS", "SERVE_MAX_NEW", "SERVE_RATE_RPS",
    "SERVE_SLOTS", "SERVE_BUCKETS", "SERVE_QUEUE_DEPTH", "SERVE_SEED",
    "SERVE_DEADLINE_MS", "SERVE_PREFILLS_PER_STEP", "SERVE_TOP_K_CAP",
    "SERVE_KV_LAYOUT", "SERVE_PROFILE", "SERVE_BLOCK_SIZE",
    "SERVE_NUM_BLOCKS", "SERVE_PREFIX_CACHE", "SERVE_POOL_SLOT_BUDGET",
    "SERVE_KV_DTYPE", "SERVE_WEIGHT_DTYPE", "SERVE_DECODE_KERNEL",
    "SERVE_QUANT_MATCH_MIN",
    "SERVE_SPEC_K", "SERVE_SPEC_DRAFT", "SERVE_SPEC_NGRAM_N",
    "SERVE_SPEC_MIN_SPEEDUP",
    # Telemetry-feedback knobs (docs/SERVING.md adaptive admission): an
    # ambient adaptive policy (or a stale rollup path) must never derate
    # a protocol row's admission mid-measurement.
    "SERVE_ADMISSION_POLICY", "SERVE_ROLLUP_PATH",
    "SERVE_REPLICAS", "SERVE_TENANT_WEIGHTS", "SERVE_PLACEMENT",
    "SERVE_FLEET_QUEUE_DEPTH", "SERVE_FLEET_QUANTUM",
    "SERVE_FLEET_MIN_SCALING", "SERVE_FLEET_SINGLE_CORE_MIN",
    "SERVE_FLEET_TTFT_MAX_RATIO", "SERVE_FLEET_FAIRNESS_TOL",
    # Chaos plane + self-healing knobs (serve_lm_chaos row,
    # docs/ROBUSTNESS.md): a leaked SERVE_CHAOS_PLAN must never storm
    # the other serving rows.
    "SERVE_CHAOS_PLAN", "SERVE_CHAOS_SEED", "SERVE_CHAOS_TTFT_MAX_RATIO",
    "SERVE_STRAGGLER_FACTOR", "SERVE_STRAGGLER_TICKS",
    "SERVE_QUARANTINE_TICKS", "SERVE_PUMP_HEARTBEAT_S",
    "SERVE_REPLICA_MAX_RESTARTS", "SERVE_REPLICA_RESTART_BACKOFF",
    "SERVE_FAULT_JOIN_S", "SERVE_BROWNOUT_STAGES",
    # Disaggregation plane (serve_lm_disagg row, docs/SERVING.md): a
    # leaked SERVE_DISAGG (or pool split / bench tuning) must never
    # split the other serving rows' fleets or reshape the disagg gates.
    "SERVE_DISAGG", "SERVE_POOL_PREFILL", "SERVE_POOL_DECODE",
    "SERVE_DISAGG_DIRECTORY", "SERVE_DISAGG_PREFETCH",
    "BENCH_DISAGG_PREFIX_LEN", "BENCH_DISAGG_ITL_FACTOR",
    "BENCH_DISAGG_MIGRATE_TICK",
    # Streamed data plane (lm_stream row + the DATA_* data-factory
    # knobs, docs/DATA.md): joined here so an exported DATA_FORMAT or
    # stream geometry can never leak into rows that leave it unset.
    "STREAM_RECORDS", "STREAM_SEQ_LEN", "STREAM_VOCAB",
    "STREAM_SHARD_RECORDS", "STREAM_SHUFFLE_BLOCK", "STREAM_BATCH",
    "STREAM_EPOCHS", "SERVE_PROMPT_LEN",
    "PREFETCH_HOST_BATCHES", "DATA_FORMAT", "DATA_TOPOLOGY",
    # Colocation arbiter plane (lm_coloc row, serving/arbiter.py +
    # docs/ROBUSTNESS.md colocation): a leaked pool geometry or stale
    # capacity TTL must never arbitrate the other rows' devices.
    "COLOC_POOL_DEVICES", "COLOC_SHRINK_STEP", "COLOC_TTFT_SLO_MS",
    "COLOC_BROWNOUT_STAGES", "COLOC_SURGE_WINDOW",
    "ARBITER_POOL_DEVICES", "ARBITER_MIN_TRAIN_WORLD",
    "ARBITER_DEVICES_PER_REPLICA", "ARBITER_SHRINK_TICKS",
    "ARBITER_GROW_TICKS", "ARBITER_HIGH_PRESSURE",
    "ARBITER_LOW_PRESSURE", "ARBITER_LEASE_TTL_S",
    "ARBITER_WATCH_PREFIX", "CAPACITY_STALE_S",
)


def run_protocol(name: str, env_over: dict, timeout_s: float) -> dict:
    env = dict(os.environ)
    for var in _PROTOCOL_VARS:
        env.pop(var, None)
    env_over = dict(env_over)
    script = env_over.pop("_script", "bench.py")
    env.update(env_over)
    # Each row is one child process, one at a time, and this parent
    # never touches JAX: the chip belongs to the child. The children
    # place their own compile cache (training/warmup.py), so the whole
    # battery — and a re-run at the same commit — shares one.
    t0 = time.perf_counter()
    try:
        r = subprocess.run(
            [sys.executable, os.path.join(REPO, script)],
            env=env, timeout=timeout_s, capture_output=True, text=True,
        )
    except subprocess.TimeoutExpired:
        return {"error": f"timeout after {timeout_s:.0f}s"}
    lines = [
        ln for ln in r.stdout.strip().splitlines() if ln.startswith("{")
    ]
    if not lines:
        return {"error": f"no JSON line; rc={r.returncode}",
                "stderr_tail": r.stderr[-500:]}
    try:
        rec = json.loads(lines[-1])
    except json.JSONDecodeError as e:
        # A killed child can leave a partial line that starts with '{'
        # — record a failed row, don't abort the battery.
        return {"error": f"unparseable JSON line ({e}); rc={r.returncode}",
                "stdout_tail": r.stdout[-300:]}
    rec["wall_s"] = round(time.perf_counter() - t0, 1)
    return rec


def head_commit() -> str:
    """HEAD's short hash — empty where the tree is not a git checkout
    (the chip tool's copy of the repo has no ``.git``) or git is absent."""
    try:
        r = subprocess.run(
            ["git", "-C", REPO, "rev-parse", "--short", "HEAD"],
            capture_output=True, text=True,
        )
    except FileNotFoundError:
        return ""
    return r.stdout.strip() if r.returncode == 0 else ""


def lint_verdict(commit: str) -> dict:
    """The ddlint verdict recorded beside the bench rows (docs/
    ANALYSIS.md): read ``lint.json`` (``make lint`` writes it) and note
    staleness against this battery's commit — so a static-invariant
    regression shows up in the recert trajectory, not only in CI."""
    try:
        with open(os.path.join(REPO, "lint.json")) as f:
            lint = json.load(f)
    except (OSError, json.JSONDecodeError):
        return {"missing": True}
    return {
        "ok": bool(lint.get("ok")),
        "commit": lint.get("commit"),
        "stale": lint.get("commit") != commit,
        "findings": lint.get("findings_total", 0),
        "suppressions": lint.get("suppressions_total", 0),
    }


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--only", default=None,
                   help="comma-separated protocol subset")
    p.add_argument("--timeout", type=float, default=900.0)
    args = p.parse_args(argv)
    names = (
        [n.strip() for n in args.only.split(",")] if args.only
        else list(PROTOCOLS)
    )
    commit = head_commit()
    out = {
        "commit": commit,
        "date": time.strftime("%Y-%m-%d %H:%M UTC", time.gmtime()),
        "lint": lint_verdict(commit),
        "rows": {},
    }
    for name in names:
        print(f"=== {name} ===", flush=True)
        rec = run_protocol(name, PROTOCOLS[name], args.timeout)
        out["rows"][name] = rec
        print(json.dumps(rec), flush=True)
        # Incremental write: a crash mid-battery keeps completed rows.
        with open(os.path.join(REPO, "RECERT.json"), "w") as f:
            json.dump(out, f, indent=1)
    ok = all(r.get("value", 0) > 0 for r in out["rows"].values())
    print(json.dumps({"recertified": ok, "commit": commit,
                      "rows": len(out["rows"]),
                      "lint_ok": out["lint"].get("ok", False)}))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
