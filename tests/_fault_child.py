"""Jax-light child for the supervisor / fault-injection e2e tests.

A stand-in "training loop" that exercises the whole restart machinery
without compiling anything: it counts steps, persists its progress to a
per-rank state file (the checkpoint analogue), consults the
``FAULT_PLAN`` injector after every step exactly like ``loop.fit`` does,
and emits through the obs bus. Run under ``launch.py --max-restarts``
this reproduces, in seconds, the crash → classify → backoff → relaunch →
resume cycle the real training oracles take minutes to drive.

Env contract: ``FAKE_STEPS`` (total steps, default 6), ``STATE_FILE``
(progress-file prefix; ``.{rank}`` appended), plus the launcher's
``DDL_PROCESS_ID``/``FAULT_PLAN``/``OBS_*``.
"""

import os
import time

from distributeddeeplearning_tpu import faults, obs


def main() -> None:
    bus = obs.configure_from_env()
    rank = int(os.environ.get("DDL_PROCESS_ID", "0"))
    steps = int(os.environ.get("FAKE_STEPS", "6"))
    injector = faults.FaultInjector.from_env()
    state_file = os.environ.get("STATE_FILE")
    path = f"{state_file}.{rank}" if state_file else None

    if os.environ.get("ELASTIC"):  # elastic drills assert the rescale
        print(
            f"FAULT_CHILD_WORLD rank={rank} "
            f"world={os.environ.get('DDL_NUM_PROCESSES', '1')} "
            f"batch={os.environ.get('BATCHSIZE', '-')} "
            f"accum={os.environ.get('ACCUM_STEPS', '-')} "
            f"lr_world={os.environ.get('LR_WORLD_SIZE', '-')}",
            flush=True,
        )

    start = 0
    if path and os.path.exists(path):
        start = int(open(path).read().strip() or 0)

    for step in range(start + 1, steps + 1):
        print(f"step {step} rank {rank}", flush=True)
        with bus.span("fake_step", step=step, rank=rank):
            time.sleep(0.05)
        if path:  # "checkpoint": durable before any fault can fire
            with open(path, "w") as fh:
                fh.write(str(step))
        if injector is not None and injector.due_after(step):
            bus.flush()
            injector.fire_after(step)
    bus.flush()
    print(f"FAULT_CHILD_DONE {rank} start={start}", flush=True)


if __name__ == "__main__":
    main()
