"""Structured observability: event bus, flight recorder, live plane.

The repo-wide rule: layers emit *through* the bus, not around it. The
training loop, warmup, checkpointing, host-sync accounting, launcher
and job submitter all record spans/counters/gauges here; ``OBS_DIR``
turns on per-process JSONL capture, the flight-recorder ring is always
armed, and ``scripts/obs_report.py`` renders a merged run report.

The **live plane** reads the same files while the run is alive:
``obs/tail.py`` (incremental multi-file tailer), ``obs/rollup.py``
(windowed rollups + atomic ``rollup.json`` snapshots), ``obs/slo.py``
(``SLO_SPEC`` objectives with multi-window burn rates, emitting
``slo_breach``/``slo_recover`` back into the bus). See
``docs/OBSERVABILITY.md`` for the schema and knobs.

Beside the host's events, ``obs/programs.py`` puts names on the
device's time: a scope table for each ahead-of-time compiled program
and the reduction of a profiler trace by model part.
"""

from distributeddeeplearning_tpu.obs.bus import (
    ANNOTATION_PREFIX,
    DEFAULT_RING_SIZE,
    EventBus,
    TraceContext,
    bind_bus,
    bound_bus,
    configure,
    configure_from_env,
    counter,
    current_bus,
    current_trace,
    flush,
    gauge,
    get_bus,
    install_crash_handlers,
    new_span_id,
    new_trace_id,
    point,
    reset,
    span,
    span_event,
    trace_close,
    trace_ctx,
    trace_open,
)
from distributeddeeplearning_tpu.obs.rollup import (  # noqa: F401
    LivePlane,
    WindowedAggregator,
    read_snapshot,
    write_snapshot,
)
from distributeddeeplearning_tpu.obs.slo import (  # noqa: F401
    SloEngine,
    parse_slo_spec,
)
from distributeddeeplearning_tpu.obs.tail import Tailer  # noqa: F401

__all__ = [
    "ANNOTATION_PREFIX",
    "DEFAULT_RING_SIZE",
    "EventBus",
    "LivePlane",
    "SloEngine",
    "Tailer",
    "TraceContext",
    "WindowedAggregator",
    "bind_bus",
    "bound_bus",
    "current_bus",
    "current_trace",
    "configure",
    "configure_from_env",
    "counter",
    "flush",
    "gauge",
    "get_bus",
    "install_crash_handlers",
    "new_span_id",
    "new_trace_id",
    "parse_slo_spec",
    "point",
    "read_snapshot",
    "reset",
    "span",
    "span_event",
    "trace_close",
    "trace_ctx",
    "trace_open",
    "write_snapshot",
]
