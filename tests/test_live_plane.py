"""Live telemetry plane oracles (ISSUE 7).

The plane's claims, each pinned here:

* **Tailer** (`obs/tail.py`) — incremental, exactly-once delivery
  across polls; a partial final line is never emitted torn and never
  twice; files appearing mid-run (restart suffixes
  ``events-p0-r1.jsonl``) join seamlessly; events from two fake hosts
  with unrelated monotonic clocks land on ONE wall timeline via their
  meta clock pairs; truncation resets the cursor.
* **Rollup** (`obs/rollup.py`) — windowed rates/gauges/quantiles from
  bounded state; the log-histogram quantiles stay within the documented
  error bound of *exact* percentiles; ``rollup.json`` is published
  atomically and a torn read degrades to None.
* **SLO engine** (`obs/slo.py`) — the ``SLO_SPEC`` grammar
  (round-tripping the docstring examples, rejecting junk), multi-window
  burn-rate semantics (short AND long to breach, short alone to
  recover), ``finite`` objectives, breach/recover points on the bus.
* **Feedback** (`serving/scheduler.py`) — AdaptiveAdmissionPolicy
  derates ``prefills_per_step`` + the QueueFull threshold from a
  burning-latency snapshot and restores on recovery, deterministically;
  the END-TO-END oracle runs a real SlotEngine server under an
  injected-breach SLO with the plane live and asserts the
  shed-then-recover sequence from the MERGED event stream:
  ``slo_breach`` → ``serve.admission_derate`` (lowered gauge) →
  ``slo_recover`` → ``serve.admission_restore``.
* **Satellites** — the bus's ``OBS_FLUSH_EVERY_S`` bounded-staleness
  flush, the launcher watchdog's telemetry liveness signature,
  ``scripts/obs_watch.py --once``, ``scripts/bench_trend.py`` tier
  skipping, and the post-hoc report's SLO section.
"""

import json
import math
import os
import time
import types

import numpy as np
import pytest

from distributeddeeplearning_tpu import obs
from distributeddeeplearning_tpu.obs import report as obs_report
from distributeddeeplearning_tpu.obs.bus import EventBus
from distributeddeeplearning_tpu.obs.rollup import (
    HIST_GROWTH,
    LivePlane,
    WindowedAggregator,
    read_snapshot,
    write_snapshot,
)
from distributeddeeplearning_tpu.obs.slo import (
    BURN_MAX,
    SloEngine,
    parse_objective,
    parse_slo_spec,
)
from distributeddeeplearning_tpu.obs.tail import Tailer, activity_signature

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _fresh_bus():
    obs.reset()
    yield
    obs.reset()


def _write(path, *records, mode="a"):
    with open(path, mode) as fh:
        for r in records:
            fh.write(json.dumps(r) + "\n")


def _meta(p, mono0, wall0):
    return {"kind": "meta", "schema": 1, "run": "r-t", "p": p,
            "mono0": mono0, "wall0": wall0}


# ---------------------------------------------------------------------------
# Tailer
# ---------------------------------------------------------------------------

def test_tailer_incremental_exactly_once_with_partial_line(tmp_path):
    p0 = tmp_path / "events-p0.jsonl"
    _write(p0, _meta(0, 100.0, 1000.0),
           {"t": 101.0, "kind": "point", "name": "a", "p": 0}, mode="w")
    t = Tailer(str(tmp_path))
    assert [e["name"] for e in t.poll()] == ["a"]
    assert t.poll() == []  # nothing new, nothing re-delivered
    # A torn tail (writer flushed mid-record) must be held back whole...
    with open(p0, "a") as fh:
        fh.write('{"t": 102.0, "kind": "point", "name": "b"')
    assert t.poll() == []
    # ...and delivered exactly once when completed.
    with open(p0, "a") as fh:
        fh.write(', "p": 0}\n')
    ev = t.poll()
    assert [e["name"] for e in ev] == ["b"]
    assert t.errors == 0
    assert t.events_seen == 2


def test_tailer_discovers_restart_suffix_files_mid_run(tmp_path):
    p0 = tmp_path / "events-p0.jsonl"
    _write(p0, _meta(0, 100.0, 1000.0),
           {"t": 101.0, "kind": "point", "name": "a", "p": 0}, mode="w")
    t = Tailer(str(tmp_path))
    assert len(t.poll()) == 1
    # A restart attempt's file appears later (OBS_PROC_SUFFIX identity).
    _write(tmp_path / "events-p0-r1.jsonl", _meta("p0-r1", 5.0, 2000.0),
           {"t": 6.0, "kind": "point", "name": "after-restart",
            "p": "p0-r1"}, mode="w")
    ev = t.poll()
    assert [e["name"] for e in ev] == ["after-restart"]
    assert ev[0]["wall"] == pytest.approx(2001.0)
    assert len(t.files) == 2


def test_tailer_aligns_two_fake_hosts_on_one_wall_timeline(tmp_path):
    # Host A's monotonic clock started ~eons before host B's; wall order
    # is the OPPOSITE of file order. Only the meta clock pairs can sort
    # this correctly.
    _write(tmp_path / "events-pA.jsonl", _meta("A", 50000.0, 1000.0),
           {"t": 50003.0, "kind": "point", "name": "late-on-A", "p": "A"},
           mode="w")
    _write(tmp_path / "events-pB.jsonl", _meta("B", 7.0, 1000.0),
           {"t": 8.0, "kind": "point", "name": "early-on-B", "p": "B"},
           mode="w")
    ev = Tailer(str(tmp_path)).poll()
    assert [e["name"] for e in ev] == ["early-on-B", "late-on-A"]
    assert ev[0]["wall"] == pytest.approx(1001.0)
    assert ev[1]["wall"] == pytest.approx(1003.0)


def test_tailer_resets_on_truncation_and_skips_merged_file(tmp_path):
    p0 = tmp_path / "events-p0.jsonl"
    _write(p0, _meta(0, 100.0, 1000.0),
           {"t": 101.0, "kind": "point", "name": "old", "p": 0}, mode="w")
    # the launcher's merged file must never be tailed (it duplicates
    # every part file)
    _write(tmp_path / "events.jsonl", _meta(0, 100.0, 1000.0),
           {"t": 101.0, "kind": "point", "name": "dup", "p": 0}, mode="w")
    t = Tailer(str(tmp_path))
    assert [e["name"] for e in t.poll()] == ["old"]
    # rewrite smaller (a restart WITHOUT the suffix identity)
    _write(p0, _meta(0, 1.0, 3000.0),
           {"t": 2.0, "kind": "point", "name": "new", "p": 0}, mode="w")
    ev = t.poll()
    assert [e["name"] for e in ev] == ["new"]
    assert ev[0]["wall"] == pytest.approx(3001.0)  # NEW clock pair applies


def test_activity_signature_reflects_file_growth(tmp_path):
    p0 = tmp_path / "events-p0.jsonl"
    _write(p0, _meta(0, 1.0, 1.0), mode="w")
    s1 = activity_signature(str(tmp_path))
    s2 = activity_signature(str(tmp_path))
    assert s1 == s2
    _write(p0, {"t": 2.0, "kind": "point", "name": "x", "p": 0})
    assert activity_signature(str(tmp_path)) != s1


# ---------------------------------------------------------------------------
# Bus flush (OBS_FLUSH_EVERY_S satellite)
# ---------------------------------------------------------------------------

def _disk_names(path):
    return [json.loads(ln)["name"] for ln in open(path) if
            json.loads(ln).get("kind") != "meta"]


def test_bus_time_based_flush_bounds_staleness(tmp_path):
    bus = EventBus(directory=str(tmp_path), proc=0, flush_every_s=0.05)
    bus.point("first")
    assert _disk_names(bus.path) == []  # inside the staleness budget
    time.sleep(0.06)
    bus.point("second")  # first emit past the budget flushes the buffer
    assert _disk_names(bus.path) == ["first", "second"]


def test_bus_flush_every_zero_restores_epoch_boundary_behavior(tmp_path):
    bus = EventBus(directory=str(tmp_path), proc=0, flush_every_s=0.0)
    bus.point("a")
    time.sleep(0.02)
    bus.point("b")
    assert _disk_names(bus.path) == []  # only explicit flush (or size)
    bus.flush()
    assert _disk_names(bus.path) == ["a", "b"]


def test_bus_flush_knob_from_env(tmp_path, monkeypatch):
    monkeypatch.setenv("OBS_FLUSH_EVERY_S", "0.01")
    bus = EventBus(directory=str(tmp_path), proc=0)
    assert bus._flush_every_s == pytest.approx(0.01)
    monkeypatch.setenv("OBS_FLUSH_EVERY_S", "junk")
    assert EventBus(proc=1)._flush_every_s == 5.0  # default survives junk


# ---------------------------------------------------------------------------
# Rollup: windows, rates, quantile accuracy, atomic snapshot
# ---------------------------------------------------------------------------

def test_rollup_quantiles_within_bound_of_exact_percentiles():
    rng = np.random.RandomState(7)
    durs = rng.lognormal(mean=-5.0, sigma=1.2, size=4000)
    agg = WindowedAggregator(60.0, slice_s=1.0)
    for i, d in enumerate(durs):
        agg.add({"kind": "span", "name": "s", "dur": float(d),
                 "wall": 1000.0 + (i % 50)})
    # One histogram bucket is a HIST_GROWTH ratio; the geometric-midpoint
    # readback is off by at most sqrt(growth) either way (+ float slop).
    bound = HIST_GROWTH ** 0.5 * 1.01
    for q in (0.50, 0.95, 0.99):
        exact = float(np.percentile(durs, q * 100))
        est = agg.span_quantile("s", q)
        assert 1.0 / bound <= est / exact <= bound, (q, est, exact)


def test_rollup_windows_expire_and_memory_stays_bounded():
    agg = WindowedAggregator(10.0, slice_s=1.0)
    for sec in range(10_000):
        agg.add({"kind": "counter", "name": "c", "value": 2,
                 "wall": float(sec)})
        agg.add({"kind": "span", "name": "s", "dur": 0.01,
                 "wall": float(sec)})
    # bounded state: only the retained window's slices survive 10k s
    assert len(agg._slices) <= int(agg.retain_s / agg.slice_s) + 2
    assert agg.counter_sum("c") == pytest.approx(20.0)  # 10 slices x 2
    assert agg.counter_rate("c") == pytest.approx(2.0)
    # an explicitly narrower window
    assert agg.counter_sum("c", window_s=3.0) == pytest.approx(6.0)
    # events older than the window are gone from the quantile view
    assert sum(agg.span_hist("s").values()) == 10


def test_rollup_gauges_last_value_wins_with_age():
    agg = WindowedAggregator(60.0)
    agg.add({"kind": "gauge", "name": "g", "value": 1.0, "wall": 100.0})
    agg.add({"kind": "gauge", "name": "g", "value": 2.5, "wall": 120.0})
    assert agg.gauge_last("g") == 2.5
    snap = agg.snapshot(now=130.0)
    assert snap["gauges"]["g"] == {"value": 2.5, "age_s": 10.0}


def test_snapshot_atomic_write_and_torn_read(tmp_path):
    path = str(tmp_path / "rollup.json")
    snap = {"schema": 1, "counters": {"c": {"sum": 1.0}}}
    write_snapshot(path, snap)
    assert read_snapshot(path)["counters"]["c"]["sum"] == 1.0
    # no temp litter left behind by the atomic replace
    assert os.listdir(tmp_path) == ["rollup.json"]
    with open(path, "w") as fh:
        fh.write('{"torn": ')
    assert read_snapshot(path) is None  # degrade, never raise
    assert read_snapshot(str(tmp_path / "absent.json")) is None


# ---------------------------------------------------------------------------
# SLO grammar + burn-rate engine
# ---------------------------------------------------------------------------

def test_slo_grammar_docstring_examples():
    objs = parse_slo_spec(
        "serve.ttft:p99 < 250ms over 60s; epoch.loss finite\n"
        "serve.rejected:rate < 1% over 30s  # comment\n"
        "queue.depth:last <= 32"
    )
    o0, o1, o2, o3 = objs
    assert (o0.metric, o0.stat, o0.op) == ("serve.ttft", "p99", "<")
    assert o0.threshold == pytest.approx(0.25)  # ms normalized to s
    assert o0.window_s == 60.0
    assert (o1.metric, o1.stat) == ("epoch.loss", "finite")
    assert (o2.stat, o2.threshold, o2.window_s) == ("rate", 0.01, 30.0)
    assert (o3.stat, o3.op, o3.threshold) == ("last", "<=", 32.0)


@pytest.mark.parametrize("bad", [
    "serve.ttft:p42 < 1ms",          # unknown stat
    "serve.ttft < ",                 # missing value
    "serve.ttft:p99 < -3ms",         # nonpositive threshold
    "serve.ttft:p99 < 1ms over 0s",  # zero window
    "epoch.loss:p50 finite",         # finite takes no stat
    "what even is this",
])
def test_slo_grammar_rejects_junk(bad):
    with pytest.raises(ValueError):
        parse_slo_spec(bad)


def test_slo_from_env_inline_and_file(tmp_path, monkeypatch):
    assert SloEngine.from_env(env={}) is None
    eng = SloEngine.from_env(env={"SLO_SPEC": "a.b:rate < 5 over 10s"})
    assert eng.objectives[0].metric == "a.b"
    assert eng.retain_s() == pytest.approx(50.0)  # long window factor
    spec = tmp_path / "slo.spec"
    spec.write_text("# fleet SLOs\nserve.ttft:p99 < 250ms over 20s\n")
    eng = SloEngine.from_env(env={"SLO_SPEC": str(spec)})
    assert eng.objectives[0].window_s == 20.0


def _span_burst(agg, name, dur, t0, n=20, spacing=0.1):
    for i in range(n):
        agg.add({"kind": "span", "name": name, "dur": dur,
                 "wall": t0 + i * spacing})


def test_slo_multiwindow_burn_breach_and_fast_recovery():
    emitted = []
    eng = SloEngine(
        parse_slo_spec("s:p99 < 10ms over 10s"), long_factor=5.0,
        emit=lambda name, **kw: emitted.append((name, kw)),
    )
    agg = WindowedAggregator(10.0, slice_s=1.0, retain_s=eng.retain_s())
    # Slow history is CLEAN; a short spike alone must not breach (the
    # long window vetoes one-sample pages)...
    _span_burst(agg, "s", 0.002, t0=1000.0, n=300, spacing=0.1)
    _span_burst(agg, "s", 0.100, t0=1031.0, n=3, spacing=0.1)
    st = eng.evaluate(agg, now=1032.0)[0]
    assert st["burn"] > 1.0  # short window IS hot...
    assert not st["burning"]  # ...but long window still holds the p99
    assert emitted == []
    # ...until the breach sustains long enough to own the long window.
    _span_burst(agg, "s", 0.100, t0=1032.0, n=100, spacing=0.1)
    st = eng.evaluate(agg, now=1042.0)[0]
    assert st["burning"] and st["burn_long"] > 1.0
    assert [e[0] for e in emitted] == ["slo_breach"]
    assert emitted[0][1]["burn"] == pytest.approx(st["burn"], rel=0.01)
    # Recovery needs only the SHORT window clean — fast all-clear.
    st = eng.evaluate(agg, now=1060.0)[0]
    assert not st["burning"]
    assert [e[0] for e in emitted] == ["slo_breach", "slo_recover"]
    assert st["worst_burn"] > 1.0  # the engine remembers the worst
    assert st["breaches"] == 1


def test_slo_finite_objective_and_rate():
    emitted = []
    eng = SloEngine(
        parse_slo_spec("epoch.loss finite; err:rate < 1% over 10s"),
        emit=lambda name, **kw: emitted.append((name, kw)),
    )
    agg = WindowedAggregator(10.0, slice_s=1.0, retain_s=eng.retain_s())
    agg.add({"kind": "gauge", "name": "epoch.loss", "value": 1.25,
             "wall": 1000.0})
    sts = eng.evaluate(agg, now=1000.0)
    assert not sts[0]["burning"] and sts[0]["burn"] == 0.0
    agg.add({"kind": "gauge", "name": "epoch.loss", "value": float("nan"),
             "wall": 1001.0})
    sts = eng.evaluate(agg, now=1001.0)
    assert sts[0]["burning"] and sts[0]["burn"] == BURN_MAX
    assert emitted[0][0] == "slo_breach"
    # rate: 2 events over the 10s window = 0.2/s vs 0.01/s threshold
    agg.add({"kind": "counter", "name": "err", "value": 2, "wall": 1002.0})
    sts = eng.evaluate(agg, now=1002.0)
    assert sts[1]["burn"] == pytest.approx(20.0)


def test_slo_points_land_on_the_global_bus(tmp_path):
    bus = obs.configure(str(tmp_path), run_id="r-slo")
    eng = SloEngine(parse_slo_spec("s:p99 < 1ms over 5s"))
    agg = WindowedAggregator(5.0, slice_s=0.5, retain_s=eng.retain_s())
    _span_burst(agg, "s", 0.5, t0=100.0, n=30, spacing=0.1)
    eng.evaluate(agg, now=103.0)
    bus.flush()
    events = [json.loads(ln) for ln in open(bus.path)][1:]
    breach = [e for e in events if e["name"] == "slo_breach"]
    assert breach and breach[0]["labels"]["objective"] == "s:p99 < 1ms over 5s"


# ---------------------------------------------------------------------------
# LivePlane: tail -> rollup -> SLO -> rollup.json
# ---------------------------------------------------------------------------

def test_live_plane_end_to_end_over_bus_files(tmp_path):
    bus = obs.configure(str(tmp_path), run_id="r-plane")
    eng = SloEngine(parse_slo_spec("serve.ttft:p99 < 1ms over 5s"))
    plane = LivePlane(str(tmp_path), window_s=5.0, slice_s=0.5,
                      slo_engine=eng)
    t0 = time.monotonic()
    for i in range(10):
        bus.span_event("serve.ttft", 0.05, t=t0 + i * 0.01)
        bus.counter("serve.tokens", 3)
    bus.gauge("serve.slot_occupancy", 0.75)
    bus.flush()
    snap = plane.poll()
    assert snap["spans"]["serve.ttft"]["count"] == 10
    assert snap["counters"]["serve.tokens"]["sum"] == 30.0
    assert snap["gauges"]["serve.slot_occupancy"]["value"] == 0.75
    assert snap["slo"][0]["burning"]
    # the published file is the same consistent view
    disk = read_snapshot(os.path.join(str(tmp_path), "rollup.json"))
    assert disk["slo"][0]["burning"] is True
    assert disk["spans"]["serve.ttft"]["count"] == 10


# ---------------------------------------------------------------------------
# Admission feedback (serving/scheduler.py) — deterministic unit
# ---------------------------------------------------------------------------

def _fake_server(prefills=4, depth=64):
    return types.SimpleNamespace(
        prefills_per_step=prefills, queue_depth=depth, queue_limit=depth,
    )


def _slo_status(burning, stat="p99", metric="serve.ttft"):
    return {"objective": f"{metric}:{stat} < 250ms over 60s",
            "metric": metric, "stat": stat, "burning": burning,
            "burn": 2.0 if burning else 0.5}


def test_adaptive_policy_derates_and_restores_deterministically(tmp_path):
    from distributeddeeplearning_tpu.serving.scheduler import (
        AdaptiveAdmissionPolicy,
    )

    bus = obs.configure(str(tmp_path), run_id="r-pol")
    snaps = [
        None,                                  # plane not up yet: static
        {"slo": [_slo_status(True)]},          # latency SLO burning
        {"slo": [_slo_status(True)]},          # still burning: no re-derate
        {"slo": [_slo_status(False)]},         # recovered
    ]
    it = iter(snaps)
    pol = AdaptiveAdmissionPolicy(
        reader=lambda: next(it), refresh_s=0.0, derate_prefills=1,
        derate_queue_frac=0.5,
    )
    srv = _fake_server(prefills=4, depth=64)
    pol.tick(srv, now=1.0)
    assert (srv.prefills_per_step, srv.queue_limit) == (4, 64)
    pol.tick(srv, now=2.0)
    assert (srv.prefills_per_step, srv.queue_limit) == (1, 32)
    assert pol.derated
    pol.tick(srv, now=3.0)  # idempotent while burning
    assert (srv.prefills_per_step, srv.queue_limit) == (1, 32)
    pol.tick(srv, now=4.0)
    assert (srv.prefills_per_step, srv.queue_limit) == (4, 64)
    assert not pol.derated
    bus.flush()
    events = [json.loads(ln) for ln in open(bus.path)][1:]
    names = [e["name"] for e in events]
    assert names.index("serve.admission_derate") < names.index(
        "serve.admission_restore"
    )
    prefill_gauges = [
        e["value"] for e in events
        if e["name"] == "serve.admission_prefills"
    ]
    assert prefill_gauges == [1.0, 4.0]  # lowered, then restored


def test_adaptive_policy_ignores_non_latency_objectives():
    from distributeddeeplearning_tpu.serving.scheduler import (
        AdaptiveAdmissionPolicy,
    )

    pol = AdaptiveAdmissionPolicy(
        reader=lambda: {"slo": [_slo_status(True, stat="rate")]},
        refresh_s=0.0,
    )
    srv = _fake_server()
    pol.tick(srv, now=1.0)
    assert not pol.derated  # a burning THROUGHPUT slo must not shed load
    # and the latency filter can be narrowed by metric prefix
    pol2 = AdaptiveAdmissionPolicy(
        reader=lambda: {"slo": [_slo_status(True, metric="train.step")]},
        refresh_s=0.0, watch_prefix="serve.",
    )
    pol2.tick(srv, now=1.0)
    assert not pol2.derated


def test_serve_config_admission_policy_env(tmp_path, monkeypatch):
    from distributeddeeplearning_tpu.serving import ServeConfig
    from distributeddeeplearning_tpu.serving.scheduler import (
        AdaptiveAdmissionPolicy,
    )

    assert ServeConfig.from_env(env={}).build_admission_policy() is None
    cfg = ServeConfig.from_env(env={
        "SERVE_ADMISSION_POLICY": "adaptive",
        "SERVE_ROLLUP_PATH": str(tmp_path / "ro.json"),
    })
    pol = cfg.build_admission_policy()
    assert isinstance(pol, AdaptiveAdmissionPolicy)
    assert pol.snapshot_path == str(tmp_path / "ro.json")
    # default path: $OBS_DIR/rollup.json
    monkeypatch.setenv("OBS_DIR", str(tmp_path))
    cfg = ServeConfig.from_env(env={"SERVE_ADMISSION_POLICY": "adaptive"})
    assert cfg.build_admission_policy().snapshot_path == os.path.join(
        str(tmp_path), "rollup.json"
    )
    with pytest.raises(ValueError):
        ServeConfig.from_env(
            env={"SERVE_ADMISSION_POLICY": "wat"}
        ).build_admission_policy()


# ---------------------------------------------------------------------------
# END-TO-END oracle: shed-then-recover, asserted from the merged stream
# ---------------------------------------------------------------------------

def test_server_sheds_then_recovers_under_injected_slo_breach(tmp_path):
    """The acceptance oracle (ISSUE 7): a real SlotEngine server under a
    live plane + an SLO guaranteed to breach (ttft p99 < 0.01ms — any
    real prefill violates it). The plane's rollup feeds the adaptive
    admission policy; the merged event stream must show
    slo_breach -> serve.admission_derate (gauge lowered) ->
    slo_recover -> serve.admission_restore (gauge restored)."""
    import jax
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models.transformer_lm import (
        TransformerLM,
    )
    from distributeddeeplearning_tpu.serving import Request, Server, SlotEngine
    from distributeddeeplearning_tpu.serving.scheduler import (
        AdaptiveAdmissionPolicy,
    )

    vocab, max_len = 64, 16
    model = TransformerLM(variant="tiny", vocab_size=vocab,
                          max_seq_len=max_len, dtype=jnp.float32)
    import flax.linen as nn

    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, max_len), jnp.int32),
        train=False,
    )["params"])

    bus = obs.configure(str(tmp_path), run_id="r-e2e")
    slo = SloEngine(parse_slo_spec("serve.ttft:p99 < 0.01ms over 1s"))
    plane = LivePlane(str(tmp_path), window_s=1.0, slice_s=0.25,
                      slo_engine=slo)
    policy = AdaptiveAdmissionPolicy(
        snapshot_path=plane.snapshot_path, refresh_s=0.0,
        derate_prefills=1, derate_queue_frac=0.5,
    )
    engine = SlotEngine(model, params, num_slots=2, max_len=max_len,
                        buckets=(4,))
    engine.warmup()
    server = Server(engine, queue_depth=8, prefills_per_step=2,
                    admission_policy=policy)
    rng = np.random.RandomState(0)
    for _ in range(6):
        server.submit(Request(
            prompt=rng.randint(0, vocab, size=(3,)).astype(np.int32),
            max_new_tokens=6,
        ))
    # Pump scheduler and plane in lockstep: every tick flushes the bus,
    # the plane tails + evaluates, the NEXT tick's policy read sees it.
    while server.step():
        bus.flush()
        plane.poll(now=time.time())
    assert policy.derated  # breach arrived while work was in flight
    assert server.prefills_per_step == 1 and server.queue_limit == 4
    # Traffic stopped: let the short SLO window drain, then one more
    # tick so the policy reads the recovered snapshot.
    deadline = time.time() + 10.0
    while slo.any_burning and time.time() < deadline:
        time.sleep(0.15)
        bus.flush()
        plane.poll(now=time.time())
    assert not slo.any_burning
    server.step()  # policy tick on the recovered rollup
    assert not policy.derated
    assert server.prefills_per_step == 2 and server.queue_limit == 8
    bus.flush()

    # The whole story must be reconstructible from the merged stream.
    merged = obs_report.merge_run_dir(str(tmp_path))
    events = [json.loads(ln) for ln in open(merged)]
    names = [e["name"] for e in events if e.get("kind") != "meta"]
    seq = [n for n in names if n in (
        "slo_breach", "serve.admission_derate", "slo_recover",
        "serve.admission_restore",
    )]
    assert seq == ["slo_breach", "serve.admission_derate",
                   "slo_recover", "serve.admission_restore"]
    gauges = [
        (e["name"], e["value"]) for e in events
        if e.get("kind") == "gauge"
        and e["name"] == "serve.admission_prefills"
    ]
    assert gauges == [("serve.admission_prefills", 1.0),
                      ("serve.admission_prefills", 2.0)]
    # every submitted request still finished (shed slows admission;
    # it never corrupts or drops admitted work)
    assert server.stats["completed"] == 6
    # and the post-hoc report renders the same story as an SLO section
    summary = obs_report.summarize(obs_report.load([str(tmp_path)]))
    slo_sec = summary["slo"]["serve.ttft:p99 < 0.01ms over 1s"]
    assert slo_sec["breaches"] == 1 and slo_sec["recovers"] == 1
    assert slo_sec["worst_burn"] > 1.0
    assert "SLO (breach/recover timeline" in obs_report.render(summary)


# ---------------------------------------------------------------------------
# obs_watch CLI (--once / --json)
# ---------------------------------------------------------------------------

def _synthetic_serving_run(tmp_path):
    bus = obs.configure(str(tmp_path), run_id="r-watch")
    t0 = time.monotonic()
    for i in range(20):
        bus.span_event("serve.ttft", 0.040, t=t0 + i * 0.01)
        bus.counter("serve.tokens", 4)
    bus.gauge("serve.slot_occupancy", 0.5)
    bus.flush()
    obs.reset()


def test_obs_watch_once_renders_rollups_and_slo(tmp_path, capsys):
    from scripts.obs_watch import main as watch_main

    _synthetic_serving_run(tmp_path)
    rc = watch_main([
        str(tmp_path), "--once",
        "--slo", "serve.ttft:p99 < 1ms over 60s; serve.ttft:p50 < 1s",
    ])
    assert rc == 0
    out = capsys.readouterr().out
    assert "SLO objectives" in out
    assert "BURNING" in out and "[ok" in out
    assert "serve.ttft" in out and "serve.tokens" in out
    # --once published the snapshot other components read
    snap = read_snapshot(os.path.join(str(tmp_path), "rollup.json"))
    assert snap["spans"]["serve.ttft"]["count"] == 20
    # --json mode is machine-readable
    rc = watch_main([str(tmp_path), "--json", "--no-write"])
    assert rc == 0
    parsed = json.loads(capsys.readouterr().out)
    assert parsed["counters"]["serve.tokens"]["sum"] == 80.0


def test_obs_watch_rejects_missing_dir(tmp_path, capsys):
    from scripts.obs_watch import main as watch_main

    assert watch_main([str(tmp_path / "nope"), "--once"]) == 2


# ---------------------------------------------------------------------------
# bench_trend CLI (regression sentinel satellite)
# ---------------------------------------------------------------------------

def _trend_file(tmp_path, n, value, *, error=None, platform="tpu"):
    rec = {"metric": "m", "value": value, "unit": "u", "vs_baseline": 1.0,
           "device": {"platform": platform}, "detail": {}}
    if error:
        rec["error"] = error
    with open(tmp_path / f"BENCH_r{n:02d}.json", "w") as fh:
        json.dump({"n": n, "rc": 1 if error else 0, "parsed": rec}, fh)


def test_bench_trend_skips_unmeasured_rounds_and_flags_real_drops(tmp_path):
    from scripts.bench_trend import analyze, main as trend_main

    _trend_file(tmp_path, 1, 100.0)
    _trend_file(tmp_path, 2, 0.0, error="compile failed")  # NOT a -100%
    _trend_file(tmp_path, 3, 40.0, platform="cpu")  # another platform
    _trend_file(tmp_path, 4, 95.0)           # -5% vs r1: fine
    result = analyze(sorted(map(str, tmp_path.glob("BENCH_r*.json"))))
    assert result["ok"]
    skips = {r["round"]: r["skip"] for r in result["rows"]}
    assert skips[2] == "error"
    assert skips[3] == "platform_change:tpu->cpu"
    # now a real like-for-like drop
    _trend_file(tmp_path, 5, 80.0)           # -15.8% vs r4
    rc = trend_main(["--glob", str(tmp_path / "BENCH_r*.json")])
    assert rc == 1
    result = analyze(sorted(map(str, tmp_path.glob("BENCH_r*.json"))))
    assert result["regressions"][0]["drop_pct"] == pytest.approx(
        15.79, abs=0.01
    )


def test_bench_trend_spec_k_change_is_skip_not_regression(tmp_path):
    """A spec_k protocol change (speculative tier on/off or re-tuned)
    is a new baseline — same treatment as a dtype change; absent spec_k
    (pre-speculation records) normalizes to 0 and stays comparable."""
    from scripts.bench_trend import analyze

    _trend_file(tmp_path, 1, 100.0)          # pre-spec record: spec_k=0
    _trend_file(tmp_path, 2, 98.0)           # still comparable
    with open(tmp_path / "BENCH_r03.json", "w") as fh:
        json.dump({"n": 3, "rc": 0, "parsed": {
            "metric": "m", "value": 60.0, "unit": "u",
            "device": {"platform": "tpu"}, "detail": {"spec_k": 4},
        }}, fh)
    result = analyze(sorted(map(str, tmp_path.glob("BENCH_r*.json"))))
    assert result["ok"]  # the -39% "drop" is a protocol change
    assert result["rows"][2]["skip"] == "spec_change:k=0->k=4"
    # and the new spec protocol becomes its own comparable baseline
    with open(tmp_path / "BENCH_r04.json", "w") as fh:
        json.dump({"n": 4, "rc": 0, "parsed": {
            "metric": "m", "value": 30.0, "unit": "u",
            "device": {"platform": "tpu"}, "detail": {"spec_k": 4},
        }}, fh)
    result = analyze(sorted(map(str, tmp_path.glob("BENCH_r*.json"))))
    assert not result["ok"]  # -50% like-for-like at spec_k=4 IS real


# ---------------------------------------------------------------------------
# Report SLO section (post-hoc satellite)
# ---------------------------------------------------------------------------

def test_report_summarize_builds_slo_timeline(tmp_path):
    bus = EventBus(directory=str(tmp_path), proc=0, run_id="r-rep")
    bus.point("slo_breach", objective="o1", burn=3.2, value=0.8)
    bus.point("slo_recover", objective="o1", burn=0.4, value=0.1)
    bus.point("slo_breach", objective="o2", burn=1.5, value=9)
    bus.close()
    summary = obs_report.summarize(obs_report.load([str(tmp_path)]))
    o1 = summary["slo"]["o1"]
    assert o1["breaches"] == 1 and o1["recovers"] == 1
    assert o1["worst_burn"] == pytest.approx(3.2)
    assert [e["event"] for e in o1["timeline"]] == ["breach", "recover"]
    assert summary["slo"]["o2"]["breaches"] == 1
    text = obs_report.render(summary)
    assert "STILL BREACHED" in text  # o2 never recovered
    assert "worst burn 3.20x" in text
    # runs without SLO events render no section
    assert obs_report.summarize(
        obs_report.load([str(tmp_path)])
    )["slo"] is not None
