"""Event-bus-triggered ``jax.profiler`` capture.

PROFILE.md's traces were always manual (``BENCH_PROFILE=dir``) and
whole-run; this wires capture into the training loop as a *triggered*
action instead:

* ``TRACE_EVERY_N_EPOCHS=k`` — capture every k-th epoch (epoch 0, k,
  2k, …) into ``<OBS_DIR>/traces/trace-epochNNNN``;
* on-demand — ``kill -USR1 <pid>`` (or :meth:`TraceController.request`)
  marks the *next* epoch for capture, so a live production job can be
  profiled exactly when it misbehaves without restarting it.

Captures start with the Python tracer off (the device planes and the
bus's ``ddl:`` span annotations are what they are read for), and each
stop leaves the compiled programs' scope tables beside the capture
(``obs/programs.dump_tables``), so ``scripts/trace_report.py`` can
print the device's time by model part and its idle gaps by span.

Start/stop are epoch-boundary actions (the loop calls
``maybe_start``/``maybe_stop`` outside the dispatch clock), so capture
never adds work inside the hot loop itself; each transition emits a
``point`` event on the bus, which is how a report correlates "epoch 7
was slow" with "epoch 7 was being traced".
"""

from __future__ import annotations

import os
import signal
import threading
from typing import Optional

from distributeddeeplearning_tpu.obs import bus as _bus


class TraceController:
    """Decides, per epoch, whether a profiler capture starts/stops."""

    def __init__(self, directory: str, every_n: int = 0) -> None:
        self.directory = directory
        self.every_n = max(int(every_n), 0)
        self._requested = False
        self._active_dir: Optional[str] = None

    @property
    def active(self) -> bool:
        return self._active_dir is not None

    def request(self) -> None:
        """Capture the next epoch (signal handler / user code)."""
        self._requested = True

    def install_signal(self, signum: Optional[int] = None) -> bool:
        """SIGUSR1 → :meth:`request`. Main thread only; returns False
        when signals are unavailable (e.g. called from a worker)."""
        signum = signum or getattr(signal, "SIGUSR1", None)
        if signum is None:
            return False
        if threading.current_thread() is not threading.main_thread():
            return False
        try:
            signal.signal(signum, lambda *_: self.request())
        except (ValueError, OSError):
            return False
        return True

    def maybe_start(self, epoch: int) -> bool:
        """Start a capture for ``epoch`` if due (periodic or requested)."""
        if self._active_dir is not None:
            return False
        due = self._requested or (
            self.every_n > 0 and epoch % self.every_n == 0
        )
        if not due:
            return False
        self._requested = False
        out = os.path.join(self.directory, f"trace-epoch{epoch:04d}")
        import jax

        # Python tracer off: the device planes and the bus's `ddl:`
        # annotations are what a capture is read for
        # (scripts/trace_report.py), and the Python tracer's events
        # swell the file and slow the host.
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        jax.profiler.start_trace(out, profiler_options=options)
        self._active_dir = out
        _bus.point("trace_start", epoch=epoch, dir=out)
        return True

    def maybe_stop(self, epoch: int) -> bool:
        """Stop the active capture (epoch boundary)."""
        if self._active_dir is None:
            return False
        import jax

        jax.profiler.stop_trace()
        # the scope tables of the programs compiled ahead, beside the
        # capture: `make trace-report` names the device's time by them
        from distributeddeeplearning_tpu.obs import programs

        programs.dump_tables(self._active_dir)
        _bus.point("trace_stop", epoch=epoch, dir=self._active_dir)
        self._active_dir = None
        return True


def from_env(env=None, directory: Optional[str] = None) -> Optional[TraceController]:
    """Build the controller the env asks for, or None when tracing is
    entirely off (``TRACE_EVERY_N_EPOCHS`` unset/0 and no
    ``TRACE_ON_SIGNAL``). The trace directory defaults to
    ``<OBS_DIR>/traces`` next to the event files."""
    e = os.environ if env is None else env
    every_n = int(e.get("TRACE_EVERY_N_EPOCHS", "0") or 0)
    on_signal = e.get("TRACE_ON_SIGNAL", "").strip().lower() in {
        "1", "true", "t", "yes", "y", "on"
    }
    if every_n <= 0 and not on_signal:
        return None
    if directory is None:
        base = e.get("TRACE_DIR")
        if not base:
            bus_dir = _bus.get_bus().directory
            base = os.path.join(bus_dir or os.getcwd(), "traces")
        directory = base
    ctrl = TraceController(directory, every_n=every_n)
    ctrl.install_signal()
    return ctrl
