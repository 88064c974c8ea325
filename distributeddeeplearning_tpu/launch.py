"""Multi-process job launcher — the mpirun / Batch-AI-submit equivalent.

The reference starts every distributed run from outside the trainer:

* locally, ``mpirun -np 2 -H localhost:2 python -u <script>`` inside the
  framework container (``Horovod*/00_CreateImageAndTest.ipynb`` cells
  6-7, SURVEY.md §3.4) — the pre-cluster smoke test;
* on the cluster, a Batch AI job whose ``commandLine`` is
  ``mpirun --hostfile $AZ_BATCHAI_MPI_HOST_FILE -x NCCL_* -x
  DISTRIBUTED=True … python -u <script>`` (``01_Train*.ipynb`` cell 15),
  with stdout/stderr streamed back (cells 25-26).

TPU-native redesign — no MPI, no SSH rendezvous:

* **local mode** forks N python processes on this host and wires the
  gRPC-rendezvous contract ``parallel/distributed.maybe_initialize``
  consumes: ``DDL_COORDINATOR`` (process 0's host:port),
  ``DDL_NUM_PROCESSES``, ``DDL_PROCESS_ID``. Env propagation (mpirun's
  ``-x``) is ``--env KEY=VALUE``; rank-tagged log streaming (mpirun
  ``--tag-output`` / ``az batchai job file stream``) is built in. With
  ``--platform cpu --devices-per-process K`` the same code path runs on
  forced host devices — the reference's 2-process smoke test, no
  hardware needed. A local world of several processes is always such a
  CPU world: the TPU chips of one host are driven by ONE process (a chip
  belongs to one process at a time), so ``-n 2`` without the CPU
  platform is refused instead of started and left to hang.
* **pod mode** (``--tpu NAME``) wraps
  ``gcloud compute tpus tpu-vm ssh NAME --worker=all --command=…`` —
  every TPU-VM worker runs the same script and
  ``jax.distributed.initialize()`` autodetects the pod topology from
  TPU metadata, so no DDL_* vars are needed; we export
  ``DISTRIBUTED=True`` (the reference's own flag) to request it.

Usage::

    # reference: mpirun -np 2 -H localhost:2 python -u script.py
    python launch.py --num-processes 2 [--devices-per-process 4]
        [--platform cpu] [--env FAKE=True] script.py [args…]

    # reference: az batchai job create (01_Train*.ipynb cell 19)
    python launch.py --tpu v5e-pod --zone us-west4-a
        [--env FAKE=True] script.py [args…]
"""

from __future__ import annotations

import argparse
import glob
import json
import os
import re
import shlex
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Dict, List, Optional, Sequence

_DEVCOUNT_RE = re.compile(r"--xla_force_host_platform_device_count=\d+\s*")

# Child liveness lines (utils/heartbeat.py): tick the hang watchdog but
# never reach the streamed log.
_HEARTBEAT_MAGIC = b"__ddl_heartbeat__"


def find_free_port() -> int:
    """Pick a free TCP port for the process-0 coordination service."""
    with socket.socket(socket.AF_INET, socket.SOCK_STREAM) as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _parse_env_args(pairs: Sequence[str]) -> Dict[str, str]:
    out: Dict[str, str] = {}
    for p in pairs:
        if "=" not in p:
            raise SystemExit(f"--env expects KEY=VALUE, got {p!r}")
        k, v = p.split("=", 1)
        out[k] = v
    return out


def _child_env(
    base: Dict[str, str],
    *,
    coordinator: str,
    num_processes: int,
    process_id: int,
    platform: Optional[str],
    devices_per_process: Optional[int],
    extra_env: Optional[Dict[str, str]],
) -> Dict[str, str]:
    env = dict(base)
    env.update(extra_env or {})
    # python sets sys.path[0] to the *script's* dir, so a child started as
    # `python tests/foo.py` can't import the framework package; put the
    # package's own root and the launch cwd first (the reference's
    # PYTHONPATH=/workspace/common move, 00_CreateImageAndTest.ipynb cell
    # 7). The package root keeps imports working when launching from any
    # directory of an uninstalled source checkout.
    pkg_root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    paths = [pkg_root, os.getcwd(), env.get("PYTHONPATH")]
    env["PYTHONPATH"] = os.pathsep.join(
        dict.fromkeys(p for p in paths if p)  # de-dup, order-preserving
    )
    env["DDL_COORDINATOR"] = coordinator
    env["DDL_NUM_PROCESSES"] = str(num_processes)
    env["DDL_PROCESS_ID"] = str(process_id)
    if platform:
        env["JAX_PLATFORMS"] = platform
    if devices_per_process is not None:
        flags = _DEVCOUNT_RE.sub("", env.get("XLA_FLAGS", "")).strip()
        env["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={devices_per_process}"
        ).strip()
    return env


def _stream(
    proc: subprocess.Popen, rank: int, tag: bool, sink, heartbeat=None
) -> threading.Thread:
    """Pump one child's merged stdout/stderr to ``sink``, rank-tagged.

    The log-streaming role of ``az batchai job file stream … stdout.txt``
    (``01_Train*.ipynb`` cells 25-26) and mpirun ``--tag-output``.
    ``heartbeat``: single-element list updated with the time of the last
    line from ANY child — the hang watchdog's signal.
    """

    def pump():
        prefix = f"[{rank}] " if tag else ""
        raw = proc.stdout  # binary pipe (see launch_local's Popen)
        pending = b""
        while True:
            # Chunked binary reads, not line iteration: the heartbeat must
            # tick on ANY bytes (e.g. `\r`-style progress bars that never
            # emit a newline), or the watchdog would kill a healthy world.
            chunk = raw.read1(65536)
            if not chunk:
                break
            if heartbeat is not None:
                heartbeat[0] = time.monotonic()
            pending += chunk
            lines = pending.splitlines(keepends=True)
            if lines and not lines[-1].endswith((b"\n", b"\r")):
                pending = lines.pop()
            else:
                pending = b""
            wrote = False
            for ln in lines:
                # Heartbeat lines (emitted during long silent compiles,
                # utils/heartbeat.py) already ticked the watchdog via
                # the chunk read above; suppress them from the log.
                if ln.startswith(_HEARTBEAT_MAGIC):
                    continue
                sink.write(prefix + ln.decode(errors="replace"))
                wrote = True
            if wrote:
                sink.flush()
        if pending and not pending.startswith(_HEARTBEAT_MAGIC):
            sink.write(prefix + pending.decode(errors="replace") + "\n")
            sink.flush()

    t = threading.Thread(target=pump, daemon=True)
    t.start()
    return t


def _require_host_device_world(
    num_processes: int, platform: Optional[str], extra_env: Dict[str, str]
) -> None:
    """Refuse a local world of several processes unless it was asked to
    run on the CPU.

    A TPU chip belongs to one process at a time and every child of a
    local world sees every chip of the host, so the first child to
    initialise JAX takes them all and the rest fail or hang at start-up.
    On one host, several chips are driven by ONE process (a mesh over
    ``jax.devices()``); a local N-process world exists to exercise the
    multi-process code path on forced host devices. This launcher stays
    off JAX, so it cannot look for chips — it asks for the platform by
    name instead."""
    if num_processes <= 1:
        return
    resolved = (
        platform
        or extra_env.get("JAX_PLATFORMS")
        or os.environ.get("JAX_PLATFORMS", "")
    )
    if resolved.strip().lower() != "cpu":
        raise SystemExit(
            f"launch: refusing to start {num_processes} local processes "
            f"on platform {resolved or '<unset: JAX picks the TPU>'!r}: "
            "each child would claim every TPU chip of this host and the "
            "world would hang. Pass --platform cpu (with "
            "--devices-per-process K) for a host-device world, or drive "
            "this host's chips from one process (-n 1)."
        )


def launch_local(
    script: str,
    script_args: Sequence[str] = (),
    *,
    num_processes: int = 2,
    devices_per_process: Optional[int] = None,
    platform: Optional[str] = None,
    env: Optional[Dict[str, str]] = None,
    tag_output: bool = True,
    timeout: Optional[float] = None,
    hang_timeout: Optional[float] = None,
    obs_dir: Optional[str] = None,
    launcher_proc: str = "launcher",
    stop_check=None,
    sink=None,
) -> int:
    """Run ``script`` in ``num_processes`` local python processes.

    Returns the first nonzero child exit code, or 0. On any child
    failure (or timeout) the remaining children are terminated — the
    all-or-nothing semantics of an mpirun world.

    ``hang_timeout``: failure-detection watchdog the reference lacks
    (SURVEY.md §5 "Failure detection: absent"). A distributed world can
    die without any process *exiting* — one rank stuck in a collective
    the others already left never returns and never prints. If NO child
    produces a line of output for ``hang_timeout`` seconds, the world is
    declared hung and terminated (exit 125). With ``obs_dir`` set the
    watchdog also consumes liveness from the telemetry plane: growth of
    any ``events-*``/``flight-*`` file (the bus flushes at least every
    ``OBS_FLUSH_EVERY_S`` while a process emits — obs/bus.py) ticks the
    heartbeat, so a world that works silently — no stdout, telemetry
    flowing — is alive, and a *stale* event file is part of what "hung"
    means.

    ``stop_check``: optional zero-arg callable polled by the supervision
    loop; returning a truthy reason string tears the world down with
    ``faults.EXIT_RESIZE`` (SIGTERM first, so checkpoints/flight rings
    drain) — how the elastic supervisor stops a shrunken world when
    capacity returns (``launch_supervised(elastic=True)``).

    ``obs_dir``: the world's observability run directory. The launcher
    writes its own lifecycle events (rendezvous, child start/exit,
    watchdog/timeout fires) to ``events-launcher.jsonl`` there, exports
    ``OBS_DIR``/``OBS_RUN_ID`` so every child's event bus lands next to
    it, and — playing "host 0" — merges all part files into one
    wall-clock-ordered ``events.jsonl`` when the world exits, whatever
    the exit code. A watchdog/timeout kill is delivered as SIGTERM, so
    children dump their flight-recorder rings before dying.
    """
    sink = sink or sys.stdout
    extra_env = dict(env or {})
    _require_host_device_world(num_processes, platform, extra_env)
    coordinator = f"127.0.0.1:{find_free_port()}"
    lbus = None
    if hang_timeout:
        # Arm the children's compile-phase heartbeat (utils/heartbeat.py)
        # so a long silent AOT compile is not mistaken for a hang; the
        # magic lines tick the watchdog and are filtered from the log.
        extra_env.setdefault(
            "DDL_HEARTBEAT_EVERY_S", f"{max(hang_timeout / 3.0, 0.5):g}"
        )
    if obs_dir:
        from distributeddeeplearning_tpu.obs import EventBus

        obs_dir = os.path.abspath(obs_dir)
        run_id = (
            extra_env.get("OBS_RUN_ID")
            or os.environ.get("OBS_RUN_ID")
            or f"run-{int(time.time())}"
        )
        # A PRIVATE bus (not the process-global one): launching is an
        # action inside some caller's process, not that process's run.
        # The supervisor names each attempt's launcher distinctly
        # ("launcher", "launcher-r1", ...) so restarts never truncate an
        # earlier attempt's lifecycle record.
        lbus = EventBus(directory=obs_dir, run_id=run_id, proc=launcher_proc)
        extra_env["OBS_DIR"] = obs_dir
        extra_env["OBS_RUN_ID"] = run_id
    procs: List[subprocess.Popen] = []
    pumps: List[threading.Thread] = []
    heartbeat = [time.monotonic()]  # updated by every pump thread
    for pid in range(num_processes):
        cenv = _child_env(
            dict(os.environ),
            coordinator=coordinator,
            num_processes=num_processes,
            process_id=pid,
            platform=platform,
            devices_per_process=devices_per_process,
            extra_env=extra_env,
        )
        procs.append(
            subprocess.Popen(
                [sys.executable, "-u", script, *script_args],
                env=cenv,
                stdout=subprocess.PIPE,
                stderr=subprocess.STDOUT,
                # binary pipe: _stream reads raw chunks so the hang
                # watchdog sees un-newlined output too
            )
        )
        if lbus is not None:
            lbus.point("child_start", rank=pid, pid=procs[-1].pid)
        pumps.append(_stream(procs[-1], pid, tag_output, sink, heartbeat))
    if lbus is not None:
        lbus.point(
            "rendezvous",
            coordinator=coordinator,
            num_processes=num_processes,
            script=script,
        )
        lbus.flush()

    deadline = time.monotonic() + timeout if timeout else None
    exit_code = 0
    live = set(range(num_processes))
    # Telemetry liveness (obs/tail.py): a changed (name, size) signature
    # over the run dir's event files means some process appended
    # telemetry — tick the heartbeat like stdout would. stat()-only and
    # throttled to ~1 Hz so the 10 Hz supervision loop stays cheap.
    obs_sig = None
    obs_sig_next = 0.0
    if obs_dir and hang_timeout:
        from distributeddeeplearning_tpu.obs.tail import activity_signature

        obs_sig = activity_signature(obs_dir)
    try:
        while live:
            for pid in sorted(live):
                rc = procs[pid].poll()
                if rc is not None:
                    live.discard(pid)
                    if lbus is not None:
                        lbus.point("child_exit", rank=pid, rc=rc)
                    if rc != 0 and exit_code == 0:
                        exit_code = rc
                        sink.write(
                            f"launch: process {pid} exited {rc}; "
                            "terminating the job\n"
                        )
                        raise _ChildFailed()
            if deadline and time.monotonic() > deadline:
                sink.write(f"launch: timeout after {timeout}s; terminating\n")
                exit_code = 124
                if lbus is not None:
                    lbus.point("timeout_fired", timeout_s=timeout)
                raise _ChildFailed()
            if obs_sig is not None and time.monotonic() >= obs_sig_next:
                obs_sig_next = time.monotonic() + 1.0
                sig = activity_signature(obs_dir)
                if sig != obs_sig:
                    obs_sig = sig
                    heartbeat[0] = time.monotonic()
            if stop_check is not None:
                reason = stop_check()
                if reason:
                    from distributeddeeplearning_tpu import faults

                    sink.write(
                        f"launch: world resize requested ({reason}); "
                        "stopping the world for relaunch\n"
                    )
                    exit_code = faults.EXIT_RESIZE
                    if lbus is not None:
                        lbus.point("resize_stop", reason=reason)
                    raise _ChildFailed()
            if (
                hang_timeout
                and time.monotonic() - heartbeat[0] > hang_timeout
            ):
                sink.write(
                    f"launch: no output from any process for "
                    f"{hang_timeout}s — declaring the world hung; "
                    "terminating\n"
                )
                exit_code = 125
                if lbus is not None:
                    lbus.point("watchdog_fired", silence_s=hang_timeout)
                raise _ChildFailed()
            time.sleep(0.1)
    except (_ChildFailed, KeyboardInterrupt):
        for p in procs:
            if p.poll() is None:
                p.send_signal(signal.SIGTERM)
        t_end = time.monotonic() + 10
        for p in procs:
            if p.poll() is None:
                try:
                    p.wait(timeout=max(0.1, t_end - time.monotonic()))
                except subprocess.TimeoutExpired:
                    p.kill()
        if exit_code == 0:
            exit_code = 130
    finally:
        for t in pumps:
            t.join(timeout=5)
        if lbus is not None:
            lbus.point("world_exit", rc=exit_code)
            lbus.close()
            try:
                from distributeddeeplearning_tpu.obs.report import (
                    merge_run_dir,
                )

                merged = merge_run_dir(obs_dir)
                if merged:
                    sink.write(f"launch: merged events -> {merged}\n")
            except Exception as e:  # merging must never mask the run's rc
                sink.write(f"launch: event merge failed: {e!r}\n")
    return exit_code


class _ChildFailed(Exception):
    pass


# ---------------------------------------------------------------------------
# Restart supervisor (fault tolerance — docs/ROBUSTNESS.md)
# ---------------------------------------------------------------------------

def _flight_reasons(obs_dir: str, attempt: int) -> List[str]:
    """Black-box verdicts for one attempt: the ``reason`` field of every
    flight dump that attempt's processes left behind (``flight-p0.jsonl``
    for attempt 0, ``flight-p0-r<k>.jsonl`` for restart k)."""
    tag = f"-r{attempt}" if attempt else ""
    out: List[str] = []
    for path in sorted(glob.glob(os.path.join(obs_dir, "flight-*.jsonl"))):
        stem = os.path.basename(path)[len("flight-"):-len(".jsonl")]
        if attempt:
            if not stem.endswith(tag):
                continue
            stem = stem[: -len(tag)]
        elif "-r" in stem:
            continue
        try:
            with open(path) as fh:
                head = json.loads(fh.readline())
        except (OSError, json.JSONDecodeError):
            continue
        out.append(f"{stem}:{head.get('reason', '?')}")
    return out


def _elastic_world(full: int, available: int, min_world: int) -> int:
    """The world size an elastic relaunch should use: the largest
    divisor of the FULL world (so the BATCHSIZE/ACCUM_STEPS rescale is
    an integer factor and the effective batch is exactly preserved) that
    fits the available capacity, never below the operator's
    ``min_world`` floor. When capacity sits below the floor, the floor's
    smallest divisor-compatible world is returned anyway — the attempt
    fails fast and the restart budget bounds the retries."""
    divisors = [w for w in range(1, full + 1) if full % w == 0]
    fits = [w for w in divisors if min_world <= w <= max(available, 0)]
    if fits:
        return max(fits)
    floor = [w for w in divisors if w >= min_world]
    return min(floor) if floor else full


def _grow_checker(
    cap_file: str, full: int, cur: int, min_world: int, every_s: float
):
    """stop_check for a shrunken world: polls the capacity probe every
    ``every_s`` seconds (stat-cheap, throttled — the 10 Hz supervision
    loop stays light) and asks for a resize stop as soon as a LARGER
    divisor-compatible world fits the restored capacity."""
    from distributeddeeplearning_tpu import faults

    state = {"next": 0.0}

    def check() -> Optional[str]:
        now = time.monotonic()
        if now < state["next"]:
            return None
        state["next"] = now + max(every_s, 0.1)
        available = faults.probe_capacity(cap_file, full, current=cur)
        target = _elastic_world(full, available, min_world)
        if target > cur:
            return (
                f"capacity restored ({available} available): "
                f"world {cur} -> {target}"
            )
        return None

    return check


def launch_supervised(
    script: str,
    script_args: Sequence[str] = (),
    *,
    max_restarts: int = 0,
    restart_backoff: float = 1.0,
    backoff_cap: float = 60.0,
    elastic: bool = False,
    min_world_size: int = 1,
    grow_check_every_s: float = 30.0,
    env: Optional[Dict[str, str]] = None,
    obs_dir: Optional[str] = None,
    sink=None,
    **launch_kw,
) -> int:
    """Run ``launch_local`` under a restart supervisor.

    On a retryable world death (child crash/signal, watchdog kill,
    simulated preemption) the world is torn down, the failure classified
    from the exit code (``faults.classify_exit``) plus any flight-recorder
    dumps, and the whole world relaunched with exponential backoff —
    ``restart_backoff * 2**attempt`` seconds, capped — up to
    ``max_restarts`` times. Every restart attempt:

    * exports ``RESUME=True`` so the children auto-resume from the
      newest valid checkpoint (step-granular when
      ``CHECKPOINT_EVERY_STEPS`` is set — see ``training/checkpoint.py``);
    * exports ``OBS_PROC_SUFFIX=-r<k>`` + a distinct launcher identity so
      each attempt's event/flight files survive into one merged failure
      timeline (rendered by ``scripts/obs_report.py``);
    * exports ``DDL_RESTART=<k>`` for anything that wants to know.

    Every attempt compiles against the same persistent cache directory
    (``training/warmup.enable_compile_cache``), so a restarted world
    deserializes what the previous attempt compiled.

    Non-retryable exits (success, the non-finite-loss guard's 121,
    timeout 124, operator interrupt 130) return immediately. The return
    value is shell-normalized (signal deaths become 128+N). ``--timeout``
    and ``--hang-timeout`` apply per attempt.

    **Elastic worlds** (``elastic=True`` / env ``ELASTIC``,
    docs/ROBUSTNESS.md): instead of always relaunching at the full
    size, a retryable death triggers a capacity probe
    (``faults.probe_capacity`` over ``$ELASTIC_CAPACITY_FILE`` /
    ``<obs_dir>/capacity.json``) and the world relaunches at the largest
    divisor-compatible surviving size ≥ ``min_world_size`` — with the
    MATH preserved: ``BATCHSIZE`` and ``ACCUM_STEPS`` are rescaled by
    the same integer factor (effective batch held constant; per-device
    microbatch, and so memory, unchanged) and ``LR_WORLD_SIZE`` is
    pinned to the full world so the LR schedule never moves. The
    children re-shard from the topology-independent step checkpoint
    (``training/checkpoint.py``) and resume mid-epoch. While shrunken,
    the supervisor polls the probe every ``grow_check_every_s`` seconds
    and, when capacity returns, stops the world at a step boundary
    (``faults.EXIT_RESIZE`` — a coordinated handover that burns NO
    restart budget) and relaunches at full size, re-sharding again.
    Attempt records (``attempt_start``) carry the world size, and
    resizes emit ``elastic.world_resized`` points.
    """
    from distributeddeeplearning_tpu import faults

    sink = sink or sys.stdout
    base_env = dict(env or {})
    full_world = int(launch_kw.pop("num_processes", 2) or 2)
    devices_pp = int(launch_kw.get("devices_per_process") or 1)
    cur_world = full_world
    cap_file = None
    base_batch = base_accum = 0
    if elastic:
        cap_file = base_env.get(faults.CAPACITY_FILE_ENV) or os.environ.get(
            faults.CAPACITY_FILE_ENV
        )
        if not cap_file and obs_dir:
            cap_file = os.path.join(os.path.abspath(obs_dir), "capacity.json")
        base_batch = int(
            base_env.get("BATCHSIZE") or os.environ.get("BATCHSIZE") or 64
        )
        base_accum = int(
            base_env.get("ACCUM_STEPS")
            or os.environ.get("ACCUM_STEPS")
            or 1
        )
        min_world_size = max(int(min_world_size), 1)
    sbus = None
    if obs_dir:
        from distributeddeeplearning_tpu.obs import EventBus

        obs_dir = os.path.abspath(obs_dir)
        run_id = (
            base_env.get("OBS_RUN_ID")
            or os.environ.get("OBS_RUN_ID")
            or f"run-{int(time.time())}"
        )
        # One run id for every attempt: the supervisor owns the run.
        base_env["OBS_RUN_ID"] = run_id
        sbus = EventBus(directory=obs_dir, run_id=run_id, proc="supervisor")
    attempt = 0
    restarts_used = 0  # resizes are free; only FAILURES burn the budget
    try:
        while True:
            extra = dict(base_env)
            if attempt:
                extra["OBS_PROC_SUFFIX"] = f"-r{attempt}"
                extra["DDL_RESTART"] = str(attempt)
                extra["RESUME"] = "True"  # resume from the newest checkpoint
            stop_check = None
            if elastic:
                # The elasticity contract the children see: capacity
                # file for the shrink/restore drills, the FULL world for
                # restore announcements, a pinned LR world so the
                # schedule never moves, and — on a shrunken world — the
                # integer BATCHSIZE/ACCUM_STEPS rescale that holds the
                # effective batch (and per-device microbatch memory)
                # exactly constant.
                extra["ELASTIC"] = "1"
                extra["DDL_WORLD_FULL"] = str(full_world)
                extra["LR_WORLD_SIZE"] = str(full_world * devices_pp)
                if cap_file:
                    extra[faults.CAPACITY_FILE_ENV] = cap_file
                scale = full_world // cur_world
                if scale > 1:
                    extra["BATCHSIZE"] = str(base_batch * scale)
                    extra["ACCUM_STEPS"] = str(base_accum * scale)
                    sink.write(
                        f"supervisor: elastic world {cur_world}/"
                        f"{full_world} processes — BATCHSIZE "
                        f"{base_batch}->{base_batch * scale}, ACCUM_STEPS "
                        f"{base_accum}->{base_accum * scale} (effective "
                        "batch held constant)\n"
                    )
                if cur_world < full_world and cap_file:
                    stop_check = _grow_checker(
                        cap_file, full_world, cur_world, min_world_size,
                        grow_check_every_s,
                    )
            if sbus is not None:
                sbus.point(
                    "attempt_start", attempt=attempt, world_size=cur_world,
                    full_world=full_world if elastic else None,
                )
                if elastic:
                    # Pool-ownership gauge (colocation, serving/
                    # arbiter.py): how many pool devices training holds.
                    sbus.gauge("pool.train_world", float(cur_world))
                sbus.flush()
            rc = launch_local(
                script,
                script_args,
                num_processes=cur_world,
                env=extra,
                obs_dir=obs_dir,
                launcher_proc=(
                    "launcher" if attempt == 0 else f"launcher-r{attempt}"
                ),
                stop_check=stop_check,
                sink=sink,
                **launch_kw,
            )
            verdict = faults.classify_exit(rc)
            flight = _flight_reasons(obs_dir, attempt) if obs_dir else []
            if sbus is not None:
                sbus.point(
                    "attempt_exit",
                    attempt=attempt,
                    rc=rc,
                    world_size=cur_world,
                    retryable=verdict.retryable,
                    reason=verdict.reason,
                    flight=", ".join(flight) or None,
                )
                sbus.flush()
            if rc == 0:
                return 0
            if elastic and rc == faults.EXIT_RESIZE:
                # Coordinated grow-back handover: capacity returned, the
                # world was stopped at a step boundary — relaunch at the
                # restored size with resume; no backoff, no budget.
                available = faults.probe_capacity(
                    cap_file, full_world, current=cur_world
                )
                new_world = _elastic_world(
                    full_world, available, min_world_size
                )
                sink.write(
                    f"supervisor: world resize {cur_world} -> {new_world} "
                    f"({available} available); relaunching with resume "
                    "(no restart budget consumed)\n"
                )
                if sbus is not None:
                    sbus.point(
                        "elastic.world_resized",
                        from_world=cur_world,
                        to_world=new_world,
                        phase="grow",
                        attempt=attempt + 1,
                    )
                    sbus.flush()
                cur_world = new_world
                attempt += 1
                continue
            if not verdict.retryable:
                sink.write(
                    f"supervisor: rc={rc} ({verdict.reason}) is "
                    "non-retryable; giving up\n"
                )
                return faults.normalize_rc(rc)
            if restarts_used >= max_restarts:
                sink.write(
                    f"supervisor: restart budget exhausted "
                    f"({max_restarts}); last failure rc={rc} "
                    f"({verdict.reason})\n"
                )
                return faults.normalize_rc(rc)
            next_world = cur_world
            if elastic:
                available = faults.probe_capacity(
                    cap_file, full_world, current=cur_world
                )
                next_world = _elastic_world(
                    full_world, available, min_world_size
                )
                if next_world != cur_world:
                    sink.write(
                        f"supervisor: capacity probe says {available} of "
                        f"{full_world} processes available — shrinking "
                        f"world {cur_world} -> {next_world} for the "
                        "relaunch (math preserved via the ACCUM_STEPS "
                        "rescale)\n"
                    )
                    if sbus is not None:
                        sbus.point(
                            "elastic.world_resized",
                            from_world=cur_world,
                            to_world=next_world,
                            phase=(
                                "shrink" if next_world < cur_world
                                else "grow"
                            ),
                            attempt=attempt + 1,
                        )
            delay = min(restart_backoff * (2 ** restarts_used), backoff_cap)
            sink.write(
                f"supervisor: attempt {attempt} failed (rc={rc}, "
                f"{verdict.reason}"
                + (f"; flight: {', '.join(flight)}" if flight else "")
                + f"); restarting in {delay:g}s with resume enabled "
                f"(restart {restarts_used + 1}/{max_restarts})\n"
            )
            if sbus is not None:
                sbus.counter("restarts")
                sbus.point(
                    "restart_scheduled",
                    attempt=attempt + 1,
                    backoff_s=delay,
                    rc=rc,
                    reason=verdict.reason,
                    world_size=next_world,
                )
                sbus.flush()
            time.sleep(delay)
            cur_world = next_world
            attempt += 1
            restarts_used += 1
    finally:
        if sbus is not None:
            sbus.point("supervisor_exit")
            sbus.close()
            try:
                # Fold the supervisor's own record into the merged
                # timeline (launch_local merged before our final events).
                from distributeddeeplearning_tpu.obs.report import (
                    merge_run_dir,
                )

                merge_run_dir(obs_dir)
            except Exception as e:  # merging must never mask the rc
                sink.write(f"supervisor: event merge failed: {e!r}\n")


# ---------------------------------------------------------------------------
# TPU pod mode (job submission — 01_Train*.ipynb cell 15/19 equivalent)
# ---------------------------------------------------------------------------

def build_remote_command(
    script: str,
    script_args: Sequence[str] = (),
    *,
    env: Optional[Dict[str, str]] = None,
    workdir: str = "~/ddl",
    python: str = "python3",
    detach_job: Optional[str] = None,
    image: Optional[str] = None,
) -> str:
    """The shell line every TPU-VM worker executes.

    One construction point for both launch modes (foreground and the
    submitter's detached mode) so quoting/env/workdir semantics cannot
    drift. Mirrors the reference's job ``commandLine`` (``01_Train*.
    ipynb`` cell 15): env exports (mpirun ``-x``), then ``python -u
    <script>``. ``DISTRIBUTED=True`` switches ``maybe_initialize`` onto
    the TPU-metadata autodetect path.

    ``image``: run inside the prebuilt training container instead of the
    host python (pairs with ``provision setup --image``); ``--privileged
    --net=host`` exposes the TPU devices and the pod network, and
    ``workdir`` is mounted at ``/workspace`` (code + data + logs).
    """
    exports = {"DISTRIBUTED": "True", **(env or {})}
    export_str = " ".join(
        f"{k}={shlex.quote(v)}" for k, v in sorted(exports.items())
    )
    args_str = " ".join(shlex.quote(a) for a in script_args)
    if image:
        docker_env = " ".join(
            f"-e {shlex.quote(k)}={shlex.quote(v)}"
            for k, v in sorted(exports.items())
        )
        inner = (
            f"sudo docker run --rm --privileged --net=host {docker_env} "
            f"-v $(cd {workdir} && pwd):/workspace -w /workspace "
            f"{shlex.quote(image)} "
            f"{python} -u {shlex.quote(script)} {args_str}"
        ).strip()
    else:
        # `env` prefix: plain K=V assignments are shell syntax that nohup
        # (detached mode) cannot exec — `nohup env K=V cmd` works in both.
        inner = (
            f"env {export_str} {python} -u {shlex.quote(script)} {args_str}"
        ).strip()
    if detach_job:
        job = shlex.quote(detach_job)
        if image:
            # Name the container so status/stop can address it via
            # docker (the nohup pid is the root-owned `sudo docker run`,
            # unsignalable by the ssh user).
            inner = inner.replace(
                "docker run --rm", f"docker run --rm --name ddl-job-{job}", 1
            )
        return (
            f"cd {workdir} && mkdir -p logs && "
            f"nohup {inner} > logs/{job}.log 2>&1 & "
            f"echo $! > logs/{job}.pid; "
            f"echo submitted {job} pid $(cat logs/{job}.pid)"
        )
    return f"cd {workdir} && {inner}"


def ssh_command(
    tpu: str,
    zone: str,
    command: str,
    *,
    worker: str = "all",
    project: Optional[str] = None,
) -> List[str]:
    """The one place the ``gcloud … tpu-vm ssh`` argv is assembled
    (launcher, submitter, and provisioner all route through here)."""
    cmd = [
        "gcloud",
        "compute",
        "tpus",
        "tpu-vm",
        "ssh",
        tpu,
        f"--zone={zone}",
        f"--worker={worker}",
        f"--command={command}",
    ]
    if project:
        cmd.insert(5, f"--project={project}")
    return cmd


def build_pod_command(
    script: str,
    script_args: Sequence[str] = (),
    *,
    tpu: str,
    zone: str,
    project: Optional[str] = None,
    worker: str = "all",
    env: Optional[Dict[str, str]] = None,
    workdir: str = "~/ddl",
    python: str = "python3",
    detach_job: Optional[str] = None,
    image: Optional[str] = None,
) -> List[str]:
    """Build the ``gcloud … ssh --worker=all`` argv for a pod-wide run."""
    remote = build_remote_command(
        script,
        script_args,
        env=env,
        workdir=workdir,
        python=python,
        detach_job=detach_job,
        image=image,
    )
    return ssh_command(tpu, zone, remote, worker=worker, project=project)


def launch_pod(
    script: str,
    script_args: Sequence[str] = (),
    *,
    tpu: str,
    zone: str,
    project: Optional[str] = None,
    env: Optional[Dict[str, str]] = None,
    dry_run: bool = False,
    sink=None,
) -> int:
    """Submit a pod-wide run (streams combined worker output via ssh)."""
    sink = sink or sys.stdout
    cmd = build_pod_command(
        script, script_args, tpu=tpu, zone=zone, project=project, env=env
    )
    sink.write("launch: " + " ".join(shlex.quote(c) for c in cmd) + "\n")
    if dry_run:
        return 0
    return subprocess.call(cmd)


def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(
        prog="launch.py",
        description="Launch a training script across processes (local) or "
        "TPU-VM workers (pod).",
    )
    ap.add_argument("--num-processes", "-n", type=int, default=None)
    ap.add_argument(
        "--devices-per-process",
        type=int,
        default=None,
        help="force this many host devices per process (CPU smoke mode)",
    )
    ap.add_argument(
        "--platform",
        choices=("cpu", "tpu"),
        default=None,
        help="override the JAX platform in children (cpu = smoke test)",
    )
    ap.add_argument(
        "--env",
        "-x",
        action="append",
        default=[],
        metavar="KEY=VALUE",
        help="set env var in every process (mpirun -x equivalent)",
    )
    ap.add_argument("--tpu", default=None, help="TPU pod name (pod mode)")
    ap.add_argument("--zone", default=None)
    ap.add_argument("--project", default=None)
    ap.add_argument("--dry-run", action="store_true")
    ap.add_argument("--timeout", type=float, default=None)
    ap.add_argument(
        "--hang-timeout",
        type=float,
        default=None,
        help="kill the world if no process prints for this many seconds "
        "(deadlocked-collective watchdog)",
    )
    ap.add_argument(
        "--obs-dir",
        default=os.environ.get("OBS_DIR") or None,
        help="event-bus run directory: per-process events.jsonl, "
        "launcher lifecycle events, merged report input "
        "(default: $OBS_DIR; see docs/OBSERVABILITY.md)",
    )
    ap.add_argument(
        "--max-restarts",
        type=int,
        default=int(os.environ.get("MAX_RESTARTS", "0")),
        help="restart supervisor: relaunch the world up to N times after "
        "a retryable failure (crash/signal/watchdog), resuming from the "
        "newest checkpoint (default: $MAX_RESTARTS or 0 = off; "
        "docs/ROBUSTNESS.md)",
    )
    ap.add_argument(
        "--restart-backoff",
        type=float,
        default=float(os.environ.get("RESTART_BACKOFF", "1.0")),
        help="base seconds between restarts (exponential: base * 2^attempt,"
        " capped at 60s; default: $RESTART_BACKOFF or 1.0)",
    )
    ap.add_argument(
        "--elastic",
        action="store_true",
        default=os.environ.get("ELASTIC", "").strip().lower()
        in ("1", "true", "t", "yes", "y", "on"),
        help="elastic worlds: on a retryable death, probe capacity and "
        "relaunch at the surviving world size with BATCHSIZE/ACCUM_STEPS "
        "rescaled (effective batch held constant), then grow back to "
        "full size when capacity returns (default: $ELASTIC; requires "
        "--max-restarts; docs/ROBUSTNESS.md)",
    )
    ap.add_argument(
        "--min-world-size",
        type=int,
        default=int(os.environ.get("MIN_WORLD_SIZE", "1")),
        help="elastic floor: never relaunch below this many processes "
        "(default: $MIN_WORLD_SIZE or 1)",
    )
    ap.add_argument(
        "--grow-check-every-s",
        type=float,
        default=float(os.environ.get("GROW_CHECK_EVERY_S", "30")),
        help="how often a shrunken elastic world polls the capacity "
        "probe for grow-back (default: $GROW_CHECK_EVERY_S or 30)",
    )
    ap.add_argument("--no-tag-output", action="store_true")
    ap.add_argument("script")
    ap.add_argument("script_args", nargs=argparse.REMAINDER)
    args = ap.parse_args(argv)

    extra_env = _parse_env_args(args.env)
    if args.tpu:
        if not args.zone:
            ap.error("--tpu requires --zone")
        for flag, val in (
            ("--num-processes", args.num_processes),
            ("--devices-per-process", args.devices_per_process),
            ("--platform", args.platform),
            ("--timeout", args.timeout),
            ("--hang-timeout", args.hang_timeout),
        ):
            if val is not None:
                ap.error(f"{flag} applies to local mode only, not --tpu")
        if args.max_restarts:
            ap.error(
                "--max-restarts applies to local mode only, not --tpu "
                "(pod jobs are resubmitted through orchestration/submit)"
            )
        if args.elastic:
            ap.error(
                "--elastic applies to local mode only, not --tpu "
                "(pod resizes go through orchestration/provision)"
            )
        if args.obs_dir:
            # Pod mode: no shared filesystem to merge on — each worker
            # writes its own event files under OBS_DIR on its VM (fetch
            # or stream them later; merging is the local-mode luxury).
            extra_env.setdefault("OBS_DIR", args.obs_dir)
        return launch_pod(
            args.script,
            args.script_args,
            tpu=args.tpu,
            zone=args.zone,
            project=args.project,
            env=extra_env,
            dry_run=args.dry_run,
        )
    n = args.num_processes or 2
    if args.dry_run:
        print(
            f"launch: would fork {n} local processes of "
            f"{args.script} {' '.join(args.script_args)}"
        )
        return 0
    local_kw = dict(
        num_processes=n,
        devices_per_process=args.devices_per_process,
        platform=args.platform,
        tag_output=not args.no_tag_output,
        timeout=args.timeout,
        hang_timeout=args.hang_timeout,
    )
    if args.elastic and args.max_restarts <= 0:
        ap.error("--elastic requires --max-restarts >= 1 (the supervisor)")
    if args.max_restarts > 0:
        return launch_supervised(
            args.script,
            args.script_args,
            max_restarts=args.max_restarts,
            restart_backoff=args.restart_backoff,
            elastic=args.elastic,
            min_world_size=args.min_world_size,
            grow_check_every_s=args.grow_check_every_s,
            env=extra_env,
            obs_dir=args.obs_dir,
            **local_kw,
        )
    return launch_local(
        args.script,
        args.script_args,
        env=extra_env,
        obs_dir=args.obs_dir,
        **local_kw,
    )


if __name__ == "__main__":
    raise SystemExit(main())
