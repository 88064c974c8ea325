"""Process-local structured event bus + flight recorder.

The repo's observability used to be stdout lines: the reference's
``Timer`` print, PR 1's warmup/hostsync log lines, and ``bench.py``'s
one-JSON-line protocol each spoke their own dialect, and a crashed or
preempted process left nothing behind at all. This module is the one
substrate under all of them:

* :class:`EventBus` — spans, counters, gauges and point events, written
  as JSONL with monotonic timestamps and run/host/process identity. One
  file per process (``events-p<proc>.jsonl``); the first line is a
  ``meta`` record carrying the (monotonic, wall) clock pair so a merger
  can align files from different hosts.
* **Flight recorder** — every event also lands in a bounded in-memory
  ring; :func:`install_crash_handlers` dumps the ring to
  ``flight-p<proc>.jsonl`` on unhandled exception or SIGTERM
  (preemption / launcher watchdog kill), so a dead process leaves a
  black box with its last N events even when nothing was ever flushed.
* **Sync-free by construction** — emitting buffers a plain dict
  host-side; nothing here may ever touch a jax array or the device.
  The hot loop's instrumentation cost is a dict append; file writes
  happen on the time threshold below, at epoch boundaries (``flush()``)
  or on the internal batch-size threshold, never per event.
* **Bounded staleness** — the live telemetry plane (``obs/tail.py``)
  and the launcher's watchdog read these files *while the run is
  alive*; a bus that only flushed at epoch boundaries would show them
  a file minutes stale. ``OBS_FLUSH_EVERY_S`` (default 5s) flushes the
  buffer whenever an emit lands at least that long after the previous
  flush — still batched writes (never per-event I/O in a tight loop),
  still zero host syncs, but a reader's view lags live events by at
  most the knob. ``OBS_FLUSH_EVERY_S=0`` restores the old
  epoch-boundary-only behavior.

Schema (one JSON object per line)::

    {"kind": "meta", "schema": 1, "run": ..., "p": 0, "host": ...,
     "pid": ..., "slice": ..., "mono0": ..., "wall0": ..., "argv": [...]}
    {"t": <monotonic s>, "kind": "span",    "name": ..., "dur": <s>,
     "labels": {...}, "p": 0, "seq": n}
    {"t": ...,           "kind": "counter", "name": ..., "value": n, ...}
    {"t": ...,           "kind": "gauge",   "name": ..., "value": x, ...}
    {"t": ...,           "kind": "point",   "name": ..., ...}

**Trace context (docs/OBSERVABILITY.md trace plane):** any emit made
while the calling thread holds a bound :class:`TraceContext`
(``with bus.trace_ctx(trace_id):`` / ``obs.trace_ctx``) additionally
carries ``trace``/``span`` (and ``parent``/``cause`` when set) — the
request-scoped causal identity that survives router → replica → engine
handoffs. Stamping is a host-side dict assignment; it adds zero host
syncs and no device work. ``obs/traces.py`` reconstructs per-request
critical paths from the stamped files.

**On the profiler's clock:** the context-manager form
(:meth:`EventBus.span`) also opens a ``jax.profiler.TraceAnnotation``
named ``ddl:<span name>``, so that while a profiler session runs every
such span lies in the ``.xplane.pb`` on the device trace's own clock, on
the thread that did the work. With no session the annotation is one
flag test in the runtime. :meth:`EventBus.span_event` reports a
duration after the fact and cannot be mirrored.

**Totals:** the ring forgets (512 events); :meth:`EventBus.totals`
does not. Every span and counter name keeps a cumulative count and sum
for the life of the bus, one dict update an emit.

Knobs (env): ``OBS_DIR`` (run directory; unset = ring-only, no files),
``OBS_RUN_ID`` (shared by the launcher so all processes of one world
agree), ``OBS_RING_SIZE`` (flight-recorder depth, default 512),
``OBS_FLUSH_EVERY_S`` (max buffered-event staleness, default 5s; 0 =
flush only on the size threshold / explicit ``flush()``).
"""

from __future__ import annotations

import atexit
import collections
import contextlib
import json
import os
import signal
import socket
import sys
import threading
import time
from typing import Any, Dict, Iterator, Optional, Union

SCHEMA_VERSION = 1
# Prefix of the profiler annotations the context-manager spans open.
ANNOTATION_PREFIX = "ddl:"
DEFAULT_RING_SIZE = 512
_AUTOFLUSH_EVERY = 256
DEFAULT_FLUSH_EVERY_S = 5.0


def new_trace_id() -> str:
    """A fresh trace id (12 hex chars, host-side entropy only)."""
    return os.urandom(6).hex()


def new_span_id() -> str:
    """A fresh span id within a trace (8 hex chars)."""
    return os.urandom(4).hex()


class TraceContext:
    """One thread's trace coordinates: every emit made while a context
    is bound is stamped with ``trace``/``span`` (+ ``parent``/``cause``
    when set). Immutable; nesting derives child contexts whose
    ``parent`` is the enclosing span of the *same* trace — a re-route
    child span links back to the parent trace causally via ``cause``
    (``hedge`` | ``splice`` | ``brownout`` | ``migration``)."""

    __slots__ = ("trace", "span", "parent", "cause")

    def __init__(
        self,
        trace: str,
        span: Optional[str] = None,
        parent: Optional[str] = None,
        cause: Optional[str] = None,
    ) -> None:
        self.trace = str(trace)
        self.span = str(span) if span else new_span_id()
        self.parent = parent
        self.cause = cause

    def __repr__(self) -> str:  # pragma: no cover - debugging aid
        extra = f", cause={self.cause!r}" if self.cause else ""
        return f"TraceContext({self.trace}/{self.span}{extra})"


def _flush_every_s_from_env() -> float:
    try:
        return max(
            float(os.environ.get(
                "OBS_FLUSH_EVERY_S", str(DEFAULT_FLUSH_EVERY_S)
            )),
            0.0,
        )
    except ValueError:
        return DEFAULT_FLUSH_EVERY_S


def _annotation(name: str):
    """A ``jax.profiler.TraceAnnotation`` for span ``name``, or None in
    a process that never imported jax (the launcher, the report tools):
    no profiler session can run there, and this module stays jax-free."""
    profiler = sys.modules.get("jax.profiler")
    if profiler is None:
        return None
    return profiler.TraceAnnotation(ANNOTATION_PREFIX + name)


def _proc_tag(proc: Union[int, str]) -> str:
    return f"p{proc}" if isinstance(proc, int) else str(proc)


class EventBus:
    """A process-local structured event sink (JSONL + ring buffer).

    ``directory=None`` keeps the bus ring-only: events are recorded in
    memory (so a later :meth:`dump_flight` still works) but nothing is
    written. All methods are thread-safe and never raise into the
    instrumented code path.
    """

    def __init__(
        self,
        *,
        directory: Optional[str] = None,
        run_id: Optional[str] = None,
        proc: Optional[Union[int, str]] = None,
        ring_size: int = DEFAULT_RING_SIZE,
        identity: Optional[Dict[str, Any]] = None,
        flush_every_s: Optional[float] = None,
    ) -> None:
        self._lock = threading.Lock()
        self._flush_every_s = (
            _flush_every_s_from_env() if flush_every_s is None
            else max(float(flush_every_s), 0.0)
        )
        self._last_flush = time.monotonic()
        if proc is None:
            proc = int(os.environ.get("DDL_PROCESS_ID", "0"))
            # Restart supervisor (launch.launch_supervised): attempt k>0
            # exports OBS_PROC_SUFFIX="-rk" so a relaunched process does
            # NOT truncate attempt k-1's event/flight files — every
            # attempt keeps its own identity in the merged failure
            # timeline (events-p0.jsonl, events-p0-r1.jsonl, ...).
            suffix = os.environ.get("OBS_PROC_SUFFIX", "")
            if suffix:
                proc = f"p{proc}{suffix}"
        self.proc = proc
        self.run_id = run_id or f"run-{int(time.time())}-{os.getpid()}"
        self.directory = os.path.abspath(directory) if directory else None
        self.ring: collections.deque = collections.deque(maxlen=max(ring_size, 1))
        self._buffer: list = []
        self._seq = 0
        # name -> [kind, count, sum] of every span (sum of dur) and
        # counter (sum of value) ever emitted: what the ring has aged
        # out is still here (totals()).
        self._totals: Dict[str, list] = {}
        # In-flight trace registry (trace_open/trace_close): what this
        # bus's process/replica is holding RIGHT NOW — dumped into the
        # flight-recorder header so a crash black box names the
        # requests a dead replica was serving.
        self._active_traces: Dict[str, Dict[str, Any]] = {}
        self._fh = None
        self.path: Optional[str] = None
        self.meta: Dict[str, Any] = {
            "kind": "meta",
            "schema": SCHEMA_VERSION,
            "run": self.run_id,
            "p": self.proc,
            "host": socket.gethostname(),
            "pid": os.getpid(),
            "slice": os.environ.get("DDL_SLICE"),
            # The clock pair every consumer needs to align this file with
            # others: wall = wall0 + (t - mono0).
            "mono0": time.monotonic(),
            "wall0": time.time(),
            "argv": list(sys.argv),
        }
        if identity:
            self.meta.update(identity)
        if self.directory:
            os.makedirs(self.directory, exist_ok=True)
            self.path = os.path.join(
                self.directory, f"events-{_proc_tag(self.proc)}.jsonl"
            )
            self._fh = open(self.path, "w")
            self._fh.write(json.dumps(self.meta, default=str) + "\n")
            self._fh.flush()

    # -- emission ----------------------------------------------------------

    def emit(
        self,
        kind: str,
        name: str,
        *,
        value: Any = None,
        dur: Optional[float] = None,
        t: Optional[float] = None,
        labels: Optional[Dict[str, Any]] = None,
    ) -> None:
        """Record one event (host-side dict append; no device work)."""
        rec: Dict[str, Any] = {
            "t": time.monotonic() if t is None else t,
            "kind": kind,
            "name": name,
            "p": self.proc,
        }
        if value is not None:
            rec["value"] = value
        if dur is not None:
            rec["dur"] = dur
        if labels:
            rec["labels"] = labels
        ctx = getattr(_TLS, "trace", None)
        if ctx is not None:
            # Host-side dict stamping only — zero new host syncs.
            rec["trace"] = ctx.trace
            rec["span"] = ctx.span
            if ctx.parent:
                rec["parent"] = ctx.parent
            if ctx.cause:
                rec["cause"] = ctx.cause
        amount = dur if kind == "span" else value if kind == "counter" else None
        with self._lock:
            self._seq += 1
            rec["seq"] = self._seq
            self.ring.append(rec)
            if isinstance(amount, (int, float)):
                tot = self._totals.get(name)
                if tot is None:
                    tot = self._totals[name] = [kind, 0, 0.0]
                tot[1] += 1
                tot[2] += amount
            if self._fh is not None:
                self._buffer.append(rec)
                # Size threshold, OR the bounded-staleness clock: the
                # first emit landing >= OBS_FLUSH_EVERY_S after the last
                # flush carries the whole buffer out, so live readers
                # (tailer, watchdog liveness) never see a file more than
                # one knob-interval behind an *emitting* process.
                if len(self._buffer) >= _AUTOFLUSH_EVERY or (
                    self._flush_every_s > 0
                    and time.monotonic() - self._last_flush
                    >= self._flush_every_s
                ):
                    self._flush_locked()

    def counter(self, name: str, n: int = 1, **labels: Any) -> None:
        self.emit("counter", name, value=n, labels=labels or None)

    def gauge(self, name: str, value: float, **labels: Any) -> None:
        self.emit("gauge", name, value=value, labels=labels or None)

    def point(self, name: str, **labels: Any) -> None:
        self.emit("point", name, labels=labels or None)

    @contextlib.contextmanager
    def span(self, name: str, **labels: Any) -> Iterator[Dict[str, Any]]:
        """Time a block; emits one ``span`` event at exit (t = start).
        The block also runs under a profiler annotation ``ddl:<name>``
        (module docstring). Yields the labels, so that the block can add
        what it only learns inside (``compile``'s ``cache_hit``)."""
        with _annotation(name) or contextlib.nullcontext():
            t0 = time.monotonic()
            try:
                yield labels
            finally:
                self.emit(
                    "span", name, t=t0, dur=time.monotonic() - t0,
                    labels=labels or None,
                )

    def span_event(
        self, name: str, dur: float, t: Optional[float] = None, **labels: Any
    ) -> None:
        """A span whose duration was measured elsewhere (e.g. the step
        dispatch clock) — ``t`` defaults to "it just ended"."""
        if t is None:
            t = time.monotonic() - dur
        self.emit("span", name, t=t, dur=dur, labels=labels or None)

    # -- trace context -----------------------------------------------------

    def trace_ctx(
        self,
        trace: Union["TraceContext", str, None],
        span: Optional[str] = None,
        *,
        parent: Optional[str] = None,
        cause: Optional[str] = None,
    ):
        """Bind a trace context for the calling thread (see the
        module-level :func:`trace_ctx` — the binding is thread-local,
        not per-bus, so it rides every bus the thread emits to)."""
        return trace_ctx(trace, span, parent=parent, cause=cause)

    def trace_open(self, trace_id: str, **info: Any) -> None:
        """Register ``trace_id`` as in flight on this bus (flight
        recorder: a crash dump's header names the active traces)."""
        rec = dict(info)
        rec["opened_t"] = time.monotonic()
        with self._lock:
            self._active_traces[str(trace_id)] = rec

    def trace_close(self, trace_id: str) -> None:
        """Mark ``trace_id`` no longer held by this bus's process."""
        with self._lock:
            self._active_traces.pop(str(trace_id), None)

    def active_traces(self) -> Dict[str, Dict[str, Any]]:
        """Snapshot of the in-flight trace registry."""
        with self._lock:
            return {k: dict(v) for k, v in self._active_traces.items()}

    def totals(self) -> Dict[str, Dict[str, Any]]:
        """``{name: {"kind", "count", "sum"}}`` over the bus's whole
        life, for every span (sum of durations, seconds) and counter
        (sum of increments)."""
        with self._lock:
            return self._totals_locked()

    def _totals_locked(self) -> Dict[str, Dict[str, Any]]:
        return {
            name: {"kind": kind, "count": count, "sum": total}
            for name, (kind, count, total) in self._totals.items()
        }

    # -- persistence -------------------------------------------------------

    def _flush_locked(self) -> None:
        self._last_flush = time.monotonic()
        if self._fh is None or not self._buffer:
            return
        self._fh.write(
            "".join(json.dumps(r, default=str) + "\n" for r in self._buffer)
        )
        self._fh.flush()
        self._buffer.clear()

    def flush(self) -> None:
        with self._lock:
            self._flush_locked()

    def dump_flight(
        self, reason: str, path: Optional[str] = None
    ) -> Optional[str]:
        """Write the ring (last N events) to disk — the black box.

        Called by the crash handlers on unhandled exception / SIGTERM;
        callable directly too. Ring-only buses with no directory dump
        next to the cwd so a crash still leaves evidence."""
        with self._lock:
            recs = list(self.ring)
            totals = self._totals_locked()
            active = {k: dict(v) for k, v in self._active_traces.items()}
        if path is None:
            base = self.directory or os.getcwd()
            path = os.path.join(base, f"flight-{_proc_tag(self.proc)}.jsonl")
        header = dict(self.meta)
        header["kind"] = "flight_meta"
        header["reason"] = reason
        header["dump_wall"] = time.time()
        header["dump_t"] = time.monotonic()
        # What the ring no longer holds: cumulative count and sum of
        # every span and counter name since the bus was made.
        header["totals"] = totals
        if active:
            # The requests this process was holding at crash time — a
            # post-mortem joins these trace ids against the fleet's
            # event files to name what died here.
            header["active_traces"] = active
        try:
            with open(path, "w") as fh:
                fh.write(json.dumps(header, default=str) + "\n")
                for r in recs:
                    fh.write(json.dumps(r, default=str) + "\n")
        except OSError:
            return None
        return path

    def close(self) -> None:
        with self._lock:
            self._flush_locked()
            if self._fh is not None:
                self._fh.close()
                self._fh = None


# ---------------------------------------------------------------------------
# Process-global bus + crash handlers + per-thread binding
# ---------------------------------------------------------------------------

_GLOBAL_LOCK = threading.Lock()
_GLOBAL: Optional[EventBus] = None
# Thread-local bus override (serving fleet, docs/SERVING.md): a replica
# worker thread binds its OWN EventBus (proc "p0-s<k>") so every
# instrumentation site it runs — scheduler ticks, engine warmup spans,
# pool gauges — lands in that replica's event stream without any call
# site holding a bus reference. Unbound threads keep the global bus.
_TLS = threading.local()
_handlers_installed = False
_prev_excepthook = None
_prev_sigterm = None


def get_bus() -> EventBus:
    """The process-global bus (ring-only until :func:`configure` runs),
    so instrumentation sites never need to check whether observability
    is on."""
    global _GLOBAL
    with _GLOBAL_LOCK:
        if _GLOBAL is None:
            _GLOBAL = EventBus()
        return _GLOBAL


def current_bus() -> EventBus:
    """The bus the *calling thread* emits to: its bound bus when one is
    installed (:func:`bind_bus` / :func:`bound_bus`), the global bus
    otherwise. Every module-level convenience routes through this, so
    code instrumented with ``obs.counter(...)`` transparently writes to
    a replica's private stream inside that replica's thread."""
    bus = getattr(_TLS, "bus", None)
    return bus if bus is not None else get_bus()


def bind_bus(bus: Optional[EventBus]) -> Optional[EventBus]:
    """Bind ``bus`` as this thread's emission target (None unbinds).
    Returns the previously bound bus (None when the thread was on the
    global bus) so callers can restore it."""
    prev = getattr(_TLS, "bus", None)
    _TLS.bus = bus
    return prev


@contextlib.contextmanager
def bound_bus(bus: Optional[EventBus]) -> Iterator[Optional[EventBus]]:
    """Scope a thread-local bus binding: emissions inside the block go
    to ``bus``; the previous binding is restored on exit. ``None`` is a
    no-op passthrough (keeps call sites branch-free when a component
    may or may not own a private stream)."""
    if bus is None:
        yield None
        return
    prev = bind_bus(bus)
    try:
        yield bus
    finally:
        bind_bus(prev)


def current_trace() -> Optional[TraceContext]:
    """The calling thread's bound trace context (None when untraced)."""
    return getattr(_TLS, "trace", None)


@contextlib.contextmanager
def trace_ctx(
    trace: Union[TraceContext, str, None],
    span: Optional[str] = None,
    *,
    parent: Optional[str] = None,
    cause: Optional[str] = None,
) -> Iterator[Optional[TraceContext]]:
    """Scope a thread-local trace context: every emit inside the block
    (any bus) is stamped with its coordinates; the previous context is
    restored on exit.

    ``trace`` may be a trace id (a child span id is minted; nesting
    under the same trace links ``parent`` to the enclosing span), a
    ready-made :class:`TraceContext` (bound as-is — how a component
    re-binds a context that crossed a thread boundary on a request
    object), or ``None`` (passthrough: keeps call sites branch-free
    for requests that carry no trace). ``cause`` marks causal child
    spans — a hedge/splice/brownout/migration re-route."""
    if trace is None:
        yield getattr(_TLS, "trace", None)
        return
    prev = getattr(_TLS, "trace", None)
    if isinstance(trace, TraceContext):
        ctx = trace
    else:
        if parent is None and prev is not None and prev.trace == str(trace):
            parent = prev.span
        ctx = TraceContext(trace, span, parent, cause)
    _TLS.trace = ctx
    try:
        yield ctx
    finally:
        _TLS.trace = prev


def configure(
    directory: Optional[str],
    *,
    run_id: Optional[str] = None,
    ring_size: Optional[int] = None,
    proc: Optional[Union[int, str]] = None,
    install_handlers: bool = True,
) -> EventBus:
    """(Re)point the global bus at ``directory`` (None = back to
    ring-only) and install the crash handlers. Returns the new bus."""
    global _GLOBAL
    if ring_size is None:
        ring_size = int(os.environ.get("OBS_RING_SIZE", str(DEFAULT_RING_SIZE)))
    with _GLOBAL_LOCK:
        if _GLOBAL is not None:
            _GLOBAL.close()
        _GLOBAL = EventBus(
            directory=directory, run_id=run_id, proc=proc, ring_size=ring_size
        )
        bus = _GLOBAL
    if directory and install_handlers:
        install_crash_handlers()
    return bus


def configure_from_env(env=None) -> EventBus:
    """Honour ``OBS_DIR``/``OBS_RUN_ID``/``OBS_RING_SIZE`` (idempotent:
    a bus already writing to OBS_DIR is kept). With no ``OBS_DIR`` the
    existing (possibly ring-only) bus is returned unchanged."""
    e = os.environ if env is None else env
    directory = e.get("OBS_DIR")
    if not directory:
        return get_bus()
    bus = get_bus()
    if bus.directory == os.path.abspath(directory):
        return bus
    return configure(directory, run_id=e.get("OBS_RUN_ID"))


def install_crash_handlers() -> None:
    """Chain an excepthook + SIGTERM handler that dump the flight ring.

    SIGTERM matters twice here: it is what the launcher's watchdog sends
    a hung world, and what a preempted TPU VM receives — both are
    exactly the moments a black box is worth the most. Handlers chain to
    whatever was installed before and re-deliver the signal so exit
    semantics are unchanged."""
    global _handlers_installed, _prev_excepthook, _prev_sigterm
    if _handlers_installed:
        return
    _prev_excepthook = sys.excepthook

    def _hook(tp, val, tb):
        try:
            bus = get_bus()
            bus.point("crash", error=repr(val), type=tp.__name__)
            bus.dump_flight(f"exception:{tp.__name__}")
            bus.flush()
        except Exception:
            pass
        _prev_excepthook(tp, val, tb)

    sys.excepthook = _hook
    if threading.current_thread() is threading.main_thread():
        try:
            _prev_sigterm = signal.getsignal(signal.SIGTERM)

            def _on_term(signum, frame):
                try:
                    bus = get_bus()
                    bus.point("sigterm")
                    bus.dump_flight("sigterm")
                    bus.flush()
                except Exception:
                    pass
                prev = _prev_sigterm
                if callable(prev):
                    prev(signum, frame)
                else:
                    signal.signal(signal.SIGTERM, signal.SIG_DFL)
                    os.kill(os.getpid(), signal.SIGTERM)

            signal.signal(signal.SIGTERM, _on_term)
        except (ValueError, OSError):  # non-main thread / exotic platform
            _prev_sigterm = None
    _handlers_installed = True


def reset() -> None:
    """Tests only: restore handlers and drop back to a fresh ring-only
    bus."""
    global _GLOBAL, _handlers_installed, _prev_excepthook, _prev_sigterm
    _TLS.bus = None  # unbind the calling thread (other threads own theirs)
    _TLS.trace = None  # drop any bound trace context with it
    with _GLOBAL_LOCK:
        if _GLOBAL is not None:
            _GLOBAL.close()
        _GLOBAL = None
    if _handlers_installed:
        if _prev_excepthook is not None:
            sys.excepthook = _prev_excepthook
        if _prev_sigterm is not None:
            try:
                signal.signal(signal.SIGTERM, _prev_sigterm)
            except (ValueError, OSError):
                pass
        _handlers_installed = False
        _prev_excepthook = None
        _prev_sigterm = None


@atexit.register
def _close_at_exit() -> None:  # pragma: no cover - interpreter teardown
    with _GLOBAL_LOCK:
        if _GLOBAL is not None:
            _GLOBAL.close()


# Module-level conveniences: route to the calling thread's bus (bound
# replica stream or the global bus) so call sites read `obs.counter(...)`
# without holding a bus reference.

def counter(name: str, n: int = 1, **labels: Any) -> None:
    current_bus().counter(name, n, **labels)


def gauge(name: str, value: float, **labels: Any) -> None:
    current_bus().gauge(name, value, **labels)


def point(name: str, **labels: Any) -> None:
    current_bus().point(name, **labels)


def span(name: str, **labels: Any):
    return current_bus().span(name, **labels)


def span_event(
    name: str, dur: float, t: Optional[float] = None, **labels: Any
) -> None:
    current_bus().span_event(name, dur, t=t, **labels)


def trace_open(trace_id: str, **info: Any) -> None:
    current_bus().trace_open(trace_id, **info)


def trace_close(trace_id: str) -> None:
    current_bus().trace_close(trace_id)


def flush() -> None:
    current_bus().flush()
