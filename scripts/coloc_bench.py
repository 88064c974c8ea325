"""Train/serve colocation bench — one pool, combined storm, gated.

The colocation tier's robustness protocol (BASELINE.md style, one JSON
line on stdout; recertify row ``lm_coloc``; docs/ROBUSTNESS.md
colocation section). One seeded drill exercises the whole
``PoolArbiter`` cycle (serving/arbiter.py) end to end:

1. **uninterrupted training reference** — an elastic mesh-``POOL`` LM
   run with per-step checkpoints: the trajectory every storm leg must
   re-join at f32 ULP.
2. **serving surge + arbitration storm** — a multi-tenant backlog hits
   a 1-replica fleet while a seeded ``SERVE_CHAOS_PLAN`` storms it and
   a deterministic surge window drives ``serve.fleet_pressure`` + an
   SLO burn. The brownout ladder escalates first (shed tiers); only
   once it is *exhausted* does the arbiter shrink training through the
   capacity file (``owner="arbiter"``); the ``FleetController``'s
   scale-up is lease-gated (denied → ``fleet.scaleup_denied`` +
   backoff; granted → second replica). When the surge passes the
   arbiter reclaims: the leased replica drains (zero-drop), the lease
   releases, full capacity is restored.
3. **training storm legs** — the shrink/grow the arbiter decided is
   replayed against the reference checkpoints exactly as the elastic
   supervisor would: resume at the shrink boundary on the
   half-size mesh with the BATCHSIZE x ``ACCUM_STEPS`` rescale, then
   grow back to the full mesh for the remainder.

Gates (exit non-zero unless ALL hold): training losses + final params
(and the shrunken midpoint) f32-ULP-equal to the uninterrupted
reference; serving p99 TTFT within ``COLOC_TTFT_SLO_MS`` through the
whole cycle; zero dropped and zero mixed-version requests (every
stream completes AND is bitwise-identical to an undisturbed serving
baseline; splices verified); closed program sets per replica; the
arbiter's shrink → lease-deny → lease-grant → reclaim → drain → grow
sequence observed with the capacity file round-tripping
8 → 4 → 8 under ``owner="arbiter"``.

Env knobs (defaults): ``COLOC_POOL_DEVICES`` (8),
``COLOC_SHRINK_STEP`` (6), ``COLOC_TTFT_SLO_MS`` (30000),
``COLOC_BROWNOUT_STAGES`` ("spec_off,max_new:8" — no shed stage: the
zero-drop gate is absolute), ``COLOC_SURGE_WINDOW`` ("8:60" router
ticks), ``SERVE_CHAOS_PLAN`` (early-tick crash/hang/slow/corrupt
recipe on replica 0), ``SERVE_CHAOS_SEED`` (0), ``SERVE_REQUESTS``
(24), ``SERVE_MAX_NEW`` (12), ``SERVE_TENANT_WEIGHTS``
("gold:3,silver:2,bronze:1"), ``BENCH_MODEL`` (lm_tiny),
``BENCH_VOCAB`` (64), plus ``OBS_DIR`` for the event streams the
pool-ownership timeline renders.

Usage::

    python scripts/coloc_bench.py [--events]
    make coloc-bench
"""

from __future__ import annotations

import dataclasses
import json
import os
import shutil
import sys
import tempfile
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from distributeddeeplearning_tpu.serving.loadgen import (  # noqa: E402
    build_tenant_requests,
    percentile,
    profile_shapes,
)

#: Sequence length of the training legs (mirrors tests/test_elastic.py's
#: in-process oracle — tiny shapes, exact math).
TRAIN_SEQ_LEN = 16
#: Constant effective batch at every world size.
GLOBAL_BATCH = 16

#: Default serving-side storm: early-tick verbs on replica 0 only (the
#: scale-up replica must survive to drain zero-drop; a flap would burn
#: the breaker and remove the fleet's only pre-surge replica).
DEFAULT_CHAOS_PLAN = (
    "crash:tick=12,replica=0;hang:tick=24,replica=0,secs=0.5;"
    "slow:tick=36,replica=0,factor=6,secs=0.5;corrupt:tick=48,replica=0"
)


def _emit_record(record: dict) -> None:
    print(json.dumps(record), flush=True)
    from distributeddeeplearning_tpu import obs

    bus = obs.get_bus()
    bus.point("bench_result", **record)
    bus.flush()


def _ulp_close(tree_a, tree_b) -> bool:
    """tests/test_elastic.py's f32-ULP criterion as a predicate."""
    import jax
    import numpy as np

    for a, b in zip(
        jax.tree_util.tree_leaves(jax.device_get(tree_a)),
        jax.tree_util.tree_leaves(jax.device_get(tree_b)),
    ):
        try:
            np.testing.assert_allclose(a, b, rtol=2e-4, atol=2e-7)
        except AssertionError:
            return False
    return True


def _train_cfg(vocab: int, **kw):
    from distributeddeeplearning_tpu.config import TrainConfig

    base = dict(
        model="lm_tiny",
        num_classes=vocab,
        batch_size_per_device=2,
        fake_data_length=64,
        epochs=3,
        compute_dtype="float32",
        weight_decay=0.0,
        log_every_steps=0,
    )
    base.update(kw)
    return TrainConfig(**base)


def _train_fit(cfg, mesh, vocab: int):
    from distributeddeeplearning_tpu.data.synthetic import (
        SyntheticTokenDataset,
    )
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.training import loop

    data = SyntheticTokenDataset(
        length=cfg.fake_data_length,
        global_batch_size=GLOBAL_BATCH,
        seq_len=TRAIN_SEQ_LEN,
        vocab_size=vocab,
    )
    model = get_model(
        "lm_tiny", num_classes=vocab, dtype="float32",
        max_seq_len=TRAIN_SEQ_LEN,
    )
    return loop.fit(model, cfg, data, mesh=mesh, add_default_logger=False)


def run_serving(model, params, reqs, scfg, fcfg, max_len, *,
                chaos_plan, brownout_stages, surge_window, arbiter_kw,
                cap_file):
    """Serve the backlog once. With ``arbiter_kw`` the full colocation
    control plane is armed: chaos injector, brownout ladder,
    PoolArbiter, and a lease-gated FleetController, all driven by a
    deterministic surge window over router ticks (pressure high + SLO
    burning inside ``[a, b)``, calm outside)."""
    from distributeddeeplearning_tpu.serving import (
        BrownoutLadder,
        ChaosInjector,
        ControllerConfig,
        FleetController,
        Replica,
        Request,
        Router,
        parse_brownout_stages,
        parse_chaos_plan,
    )
    from distributeddeeplearning_tpu.serving.arbiter import (
        ArbiterConfig,
        PoolArbiter,
    )

    fcfg = dataclasses.replace(fcfg, chaos_plan="", brownout_stages="")
    router = Router(config=fcfg)
    obs_dir = os.environ.get("OBS_DIR") or None

    def make_replica(rid: int) -> Replica:
        return Replica(
            rid, model, params, scfg, max_len=max_len, obs_dir=obs_dir,
        )

    router.add_replica(make_replica(0), start=True, threaded=True)
    t0 = time.perf_counter()
    while not all(r.state == "ready" for r in router.replicas):
        if time.perf_counter() - t0 > 600:
            raise TimeoutError("fleet warmup timed out")
        time.sleep(0.01)
    # Warm pass so first-dispatch overheads stay out of the measurement.
    warm_placement = router.config.placement
    router.config.placement = "rr"
    router.submit(Request(
        prompt=reqs[0]["prompt"], max_new_tokens=2, temperature=0.0,
    ))
    router.drain(timeout=300)
    router.config.placement = warm_placement

    # Arm the drill AFTER the warm pass: chaos clock and surge window
    # both start at storm tick 0.
    router._ticks = 0
    chaos = None
    if chaos_plan:
        chaos = ChaosInjector(
            parse_chaos_plan(chaos_plan), seed=fcfg.chaos_seed
        )
        router.chaos = chaos
        for r in router.replicas:
            r.chaos = chaos

    arbiter = controller = ladder = None
    if arbiter_kw is not None:
        a, b = surge_window

        def surging() -> bool:
            return a <= router._ticks < b

        def slo_reader():
            return {
                "gauges": {
                    "serve.fleet_pressure": {
                        "value": 2.0 if surging() else 0.0
                    },
                },
                "slo": [
                    {"objective": "coloc_drill_ttft", "stat": "p99",
                     "metric": "serve.ttft", "burning": surging()}
                ] if surging() else [],
            }

        ladder = BrownoutLadder(
            parse_brownout_stages(brownout_stages),
            reader=slo_reader, refresh_s=0.0, escalate_ticks=2,
            recover_ticks=4,
        )
        router.brownout = ladder
        arbiter = PoolArbiter(
            ArbiterConfig(**arbiter_kw), cap_file, reader=slo_reader,
            ladder=ladder,
        )
        controller = FleetController(
            router, make_replica,
            ControllerConfig(
                min_replicas=1, max_replicas=2, up_ticks=2, down_ticks=4,
                denied_backoff_ticks=6,
            ),
            reader=lambda: 2.0 if surging() else 0.0,
            threaded_replicas=True,
            arbiter=arbiter,
        )

    engines_pre = {
        r.rid: (id(r.engine), r.engine.compile_count)
        for r in router.replicas
    }
    handles = []
    t0 = time.perf_counter()
    for r in reqs:
        handles.append((r, router.submit(Request(
            prompt=r["prompt"], max_new_tokens=r["max_new"],
            temperature=0.0,
        ), tenant=r["tenant"])))
    while router.step():
        if controller is not None:
            controller.tick()
            arbiter.tick()
        time.sleep(0.005)
    # Quiescence: the storm must settle AND — in the arbitrated run —
    # training must have reclaimed the whole pool (replica drained,
    # lease released, capacity restored). Hard cap so an undeliverable
    # directive cannot wedge the bench.
    t_q = time.perf_counter()
    while time.perf_counter() - t_q < 60.0:
        router.step()
        if controller is not None:
            controller.tick()
            arbiter.tick()
        settled = not any(
            r.state in ("faulted", "starting") for r in router.replicas
        )
        reclaimed = arbiter is None or (
            arbiter.train_world == arbiter.config.pool_devices
            and not arbiter.leases
        )
        if settled and reclaimed and (chaos is None or chaos.quiescent()):
            break
        time.sleep(0.01)
    dt = time.perf_counter() - t0

    tokens = sum(len(fh.new_tokens) for _, fh in handles)
    ttft_ms = [
        fh.ttft_s * 1e3 for _, fh in handles if fh.ttft_s is not None
    ]
    ledger = []
    for r in router.replicas:
        pre = engines_pre.get(r.rid)
        rebuilt = pre is None or pre[0] != id(r.engine)
        ledger.append({
            "replica": r.rid,
            "state": r.state,
            "rebuilt": rebuilt,
            "compile_count": r.engine.compile_count if r.engine else 0,
            "programs_expected":
                r.engine.programs_expected if r.engine else 0,
            "compiles_during_measure": (
                0 if rebuilt or pre is None
                else r.engine.compile_count - pre[1]
            ),
        })
    run = {
        "tokens_per_sec": round(tokens / dt, 1) if dt else 0.0,
        "wall_s": round(dt, 2),
        "tokens": tokens,
        "ttft_p50_ms": round(percentile(ttft_ms, 0.5), 2),
        "ttft_p99_ms": round(percentile(ttft_ms, 0.99), 2),
        "stats": dict(router.stats),
        "per_replica": ledger,
        "chaos_fired": list(chaos.fired) if chaos else [],
        "brownout_transitions":
            list(ladder.transitions) if ladder else [],
        "arbiter_decisions":
            list(arbiter.decisions) if arbiter else [],
        "controller_actions":
            list(controller.actions) if controller else [],
        "final_replica_count": len(router.replicas),
    }
    streams = [list(fh.new_tokens) for _, fh in handles]
    outcomes = [fh.finish_reason for _, fh in handles]
    splice_ok = all(fh.restart_consistent for _, fh in handles)
    mismatches = sum(fh.splice_mismatches for _, fh in handles)
    router.close()
    return run, streams, outcomes, splice_ok, mismatches, arbiter


def main() -> int:
    # The training legs need the full virtual pool BEFORE jax
    # initialises a backend (tests/conftest.py does the same).
    pool = int(os.environ.get("COLOC_POOL_DEVICES", "8"))
    flag = f"--xla_force_host_platform_device_count={pool}"
    if flag not in os.environ.get("XLA_FLAGS", ""):
        os.environ["XLA_FLAGS"] = (
            os.environ.get("XLA_FLAGS", "") + " " + flag
        ).strip()
    if "--events" in sys.argv[1:] or os.environ.get("OBS_DIR"):
        from distributeddeeplearning_tpu import obs

        if not os.environ.get("OBS_DIR"):
            os.environ["OBS_DIR"] = os.path.join(
                "runs", f"coloc-bench-{int(time.time())}"
            )
        obs.configure_from_env()
    import jax

    from distributeddeeplearning_tpu.training.warmup import (
        enable_compile_cache,
    )

    enable_compile_cache()

    import flax.linen as nn
    import jax.numpy as jnp
    import numpy as np

    from distributeddeeplearning_tpu import faults
    from distributeddeeplearning_tpu.launch import _elastic_world
    from distributeddeeplearning_tpu.models import get_model
    from distributeddeeplearning_tpu.parallel.mesh import create_mesh
    from distributeddeeplearning_tpu.serving import FleetConfig, ServeConfig
    from distributeddeeplearning_tpu.serving.fleet.router import (
        parse_tenant_weights,
    )

    env = os.environ
    model_name = env.get("BENCH_MODEL", "lm_tiny")
    vocab = int(env.get("BENCH_VOCAB", "64"))
    n_requests = int(env.get("SERVE_REQUESTS", "24"))
    max_new = int(env.get("SERVE_MAX_NEW", "12"))
    seed = int(env.get("SERVE_SEED", "0"))
    profile = env.get("SERVE_PROFILE", "mixed")
    weights = parse_tenant_weights(
        env.get("SERVE_TENANT_WEIGHTS", "gold:3,silver:2,bronze:1")
    )
    shrink_step = int(env.get("COLOC_SHRINK_STEP", "6"))
    ttft_slo_ms = float(env.get("COLOC_TTFT_SLO_MS", "30000"))
    brownout_stages = env.get(
        "COLOC_BROWNOUT_STAGES", "spec_off,max_new:8"
    )
    surge_raw = env.get("COLOC_SURGE_WINDOW", "8:60")
    surge_window = tuple(int(x) for x in surge_raw.split(":"))
    chaos_plan = env.get("SERVE_CHAOS_PLAN") or DEFAULT_CHAOS_PLAN

    scfg = ServeConfig.from_env()
    if env.get("SERVE_SLOTS") is None:
        scfg.num_slots = 4
    if scfg.buckets is None:
        scfg.buckets = (8, 16)
    fcfg = FleetConfig.from_env()
    fcfg.replicas = 1  # training holds the pool; serving starts minimal
    fcfg.tenant_weights = weights
    if env.get("SERVE_REPLICA_MAX_RESTARTS") is None:
        fcfg.max_restarts = 2
    if env.get("SERVE_REPLICA_RESTART_BACKOFF") is None:
        fcfg.restart_backoff_s = 0.05
    if env.get("SERVE_STRAGGLER_FACTOR") is None:
        fcfg.straggler_factor = 4.0
    if env.get("SERVE_STRAGGLER_TICKS") is None:
        fcfg.straggler_ticks = 5
    if env.get("SERVE_QUARANTINE_TICKS") is None:
        fcfg.quarantine_ticks = 60
    if env.get("SERVE_PUMP_HEARTBEAT_S") is None:
        fcfg.heartbeat_timeout_s = 0.75

    workdir = env.get("OBS_DIR") or tempfile.mkdtemp(prefix="coloc-bench-")
    cap_file = os.path.join(workdir, "capacity.json")
    ckpt_dir = os.path.join(workdir, "ckpt")
    coloc_knobs = (
        f"pool={pool};shrink_step={shrink_step};stages={brownout_stages};"
        f"surge={surge_raw}"
    )

    shapes = profile_shapes(profile, max_new)
    serve_max_len = max(tp + n_new for tp, n_new in shapes)
    tenants = sorted(weights)
    metric = "lm_coloc_tokens_per_sec"
    try:
        devices = jax.devices()
        if len(devices) < pool:
            raise RuntimeError(
                f"pool needs {pool} devices, host has {len(devices)}"
            )
        mesh_full = create_mesh(devices=devices[:pool])

        # -- 1. uninterrupted training reference (the ULP oracle) ------
        steps_per_epoch = 64 // GLOBAL_BATCH
        epochs = 3
        ref = _train_fit(
            _train_cfg(
                vocab, model_dir=ckpt_dir, checkpoint_every_steps=1,
                checkpoint_async=False, lr_world_size=pool, elastic=True,
                checkpoint_keep=20, epochs=epochs,
            ),
            mesh_full, vocab,
        )
        ref_mid = _train_fit(
            _train_cfg(
                vocab, lr_world_size=pool,
                epochs=shrink_step // steps_per_epoch + 1,
            ),
            mesh_full, vocab,
        )

        # -- 2. serving surge + arbitration storm ----------------------
        model = get_model(
            model_name, num_classes=vocab, max_seq_len=serve_max_len,
            dtype=jnp.float32,
        )
        variables = jax.jit(model.init, static_argnames=("train",))(
            jax.random.PRNGKey(0),
            jnp.zeros((2, serve_max_len), jnp.int32),
            train=False,
        )
        params = nn.unbox(variables["params"])
        reqs = build_tenant_requests(
            tenants, n_requests, 0.0, seed, vocab, shapes
        )

        base, base_streams, base_outcomes, _, _, _ = run_serving(
            model, params, reqs, scfg, fcfg, serve_max_len,
            chaos_plan="", brownout_stages="", surge_window=surge_window,
            arbiter_kw=None, cap_file=cap_file,
        )
        min_train = _elastic_world(pool, pool // 2, 1)
        arbiter_kw = dict(
            pool_devices=pool,
            min_train_world=min_train,
            devices_per_replica=pool - min_train,
            shrink_ticks=2,
            grow_ticks=4,
            lease_ttl_s=600.0,
        )
        cap_probes = {}
        (storm, storm_streams, storm_outcomes, splice_ok, mismatches,
         arbiter) = run_serving(
            model, params, reqs, scfg, fcfg, serve_max_len,
            chaos_plan=chaos_plan, brownout_stages=brownout_stages,
            surge_window=surge_window, arbiter_kw=arbiter_kw,
            cap_file=cap_file,
        )
        decisions = storm["arbiter_decisions"]
        shrinks = [d for d in decisions if d["action"] == "shrink"]
        grows = [d for d in decisions if d["action"] == "grow"]
        cap_probes["final"] = faults.probe_capacity(cap_file, pool)
        with open(cap_file) as fh:
            cap_owner = json.load(fh).get("owner")

        # -- 3. training storm legs (replay the arbiter's decisions) ---
        shrunk_world = (
            shrinks[0]["to_world"] if shrinks else min_train
        )
        scale = pool // shrunk_world
        for s in faults.checkpoint_steps(ckpt_dir):
            if s > shrink_step:
                shutil.rmtree(os.path.join(ckpt_dir, str(s)))
        mesh_small = create_mesh(devices=devices[:shrunk_world])
        shrunk = _train_fit(
            _train_cfg(
                vocab, model_dir=ckpt_dir, checkpoint_every_steps=1,
                checkpoint_async=False,
                batch_size_per_device=2 * scale, accum_steps=scale,
                lr_world_size=pool, elastic=True,
                epochs=shrink_step // steps_per_epoch + 1,
                checkpoint_keep=20,
            ),
            mesh_small, vocab,
        )
        grown = _train_fit(
            _train_cfg(
                vocab, model_dir=ckpt_dir, checkpoint_every_steps=1,
                checkpoint_async=False, lr_world_size=pool, elastic=True,
                checkpoint_keep=20, epochs=epochs,
            ),
            mesh_full, vocab,
        )

        # -- gates ------------------------------------------------------
        completed = all(o in ("eos", "length") for o in storm_outcomes)
        parity = storm_streams == base_streams
        corrupt_armed = any(
            f["kind"] == "corrupt" for f in storm["chaos_fired"]
        )
        corrupt_detected = (not corrupt_armed) or (
            storm["stats"]["splice_mismatch"] >= 1
        )
        closed = all(
            row["compile_count"] == row["programs_expected"]
            for run in (base, storm) for row in run["per_replica"]
            if row["compile_count"]
        )
        clean = all(
            row["compiles_during_measure"] == 0
            for run in (base, storm) for row in run["per_replica"]
        )
        ttft_ok = storm["ttft_p99_ms"] <= ttft_slo_ms
        brownout_down = any(
            t["direction"] == "down"
            for t in storm["brownout_transitions"]
        )
        brownout_up = any(
            t["direction"] == "up"
            for t in storm["brownout_transitions"]
        )
        denies = [
            d for d in decisions if d["action"] == "lease_deny"
        ]
        grants = [
            d for d in decisions if d["action"] == "lease_grant"
        ]
        releases = [
            d for d in decisions if d["action"] == "lease_release"
        ]
        ctl_denied = [
            a for a in storm["controller_actions"]
            if a["action"] == "scaleup_denied"
        ]
        ctl_scaled = [
            a for a in storm["controller_actions"]
            if a["action"] == "scale_up"
        ]
        arbitration_ok = (
            len(shrinks) >= 1 and len(grows) >= 1
            and shrinks[0]["from_world"] == pool
            and shrinks[0]["to_world"] == shrunk_world
            and bool(grants) and bool(releases)
            and bool(ctl_scaled)
            and arbiter.train_world == pool
            and not arbiter.leases
        )
        capacity_ok = (
            cap_probes["final"] == pool and cap_owner == "arbiter"
        )
        mid_epoch_steps = (
            shrink_step // steps_per_epoch + 1
        ) * steps_per_epoch
        ulp_mid = (
            _ulp_close(ref_mid.state.params, shrunk.state.params)
            and shrunk.history[-1]["global_step"] == mid_epoch_steps
        )
        ulp_final = (
            _ulp_close(ref.state.params, grown.state.params)
            and _ulp_close(ref.state.opt_state, grown.state.opt_state)
            and grown.history[-1]["global_step"]
            == epochs * steps_per_epoch
        )
        loss_ok = bool(np.isclose(
            grown.history[-1]["loss"], ref.history[-1]["loss"],
            rtol=1e-4, atol=1e-6,
        ))
        ok = (
            completed and parity and splice_ok and corrupt_detected
            and closed and clean and ttft_ok
            and brownout_down and brownout_up
            and arbitration_ok and capacity_ok
            and ulp_mid and ulp_final and loss_ok
        )
        detail = {
            "profile": profile,
            "requests": n_requests,
            "pool_devices": pool,
            "shrunk_world": shrunk_world,
            "slots_per_replica": scfg.num_slots,
            "tenant_weights": weights,
            "platform": jax.devices()[0].platform,
            "coloc": coloc_knobs,
            "chaos_plan": chaos_plan,
            "chaos_seed": fcfg.chaos_seed,
            "brownout_stages": brownout_stages,
            "surge_window_ticks": list(surge_window),
            "undisturbed": base,
            "storm": storm,
            "ttft_slo_ms": ttft_slo_ms,
            "gates": {
                "zero_dropped": completed,
                "stream_parity": parity,
                "splice_verified": splice_ok,
                "splice_mismatches": mismatches,
                "corrupt_detected": corrupt_detected,
                "programs_closed": closed,
                "zero_untouched_recompiles": clean,
                "ttft_within_slo": ttft_ok,
                "brownout_step_down": brownout_down,
                "brownout_step_up": brownout_up,
                "arbitration_cycle": arbitration_ok,
                "lease_denied_then_granted": (
                    bool(denies or ctl_denied) and bool(grants)
                ),
                "capacity_roundtrip": capacity_ok,
                "ulp_midpoint": ulp_mid,
                "ulp_final": ulp_final,
                "loss_match": loss_ok,
            },
        }
        record = {
            "metric": metric,
            "value": storm["tokens_per_sec"],
            "unit": "tokens/sec",
            "vs_baseline": round(
                storm["tokens_per_sec"] / base["tokens_per_sec"], 2
            ) if base["tokens_per_sec"] else 0.0,
            "detail": detail,
        }
        _emit_record(record)
        if not ok:
            failed = [k for k, v in detail["gates"].items()
                      if v is False]
            print(f"COLOC GATES FAILED: {failed}", file=sys.stderr)
        return 0 if ok else 1
    except Exception as e:  # structured failure record, like bench.py
        _emit_record({
            "metric": metric, "value": 0.0,
            "unit": "tokens/sec", "vs_baseline": 0.0, "error": repr(e),
        })
        raise


if __name__ == "__main__":
    sys.exit(main())
