"""From a profiler trace (``.xplane.pb``) to numbers.

The reduction works on plain tuples ``(name, start_ns, end_ns)`` so that
it can be checked on hand-made events; :func:`load` turns the profiler's
file into them. Device planes are the planes named ``/device:TPU:<n>``;
on each, the line ``XLA Ops`` holds one event per operation that ran and
the line ``XLA Modules`` one per program. The benchmark's own host spans
are ``jax.profiler.TraceAnnotation`` events whose names start with
``bench:``; they sit on the host's thread lines, on the same clock.
"""

from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, int, int]  # name, start_ns, end_ns

DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PREFIX = "bench:"
WINDOW_SPAN = HOST_PREFIX + "traced_window"
COLLECTIVE = re.compile(
    r"^(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)"
)
_SUFFIX = re.compile(r"[.\d]+$")


@dataclasses.dataclass
class Trace:
    """Events of one traced window, by device."""
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: List[Event]  # the benchmark's own spans, prefix stripped

    @property
    def window_ns(self) -> Tuple[int, int]:
        """The traced window: the ``traced_window`` span where the run
        wrote one, else first to last device event."""
        for name, a, b in self.host:
            if name == WINDOW_SPAN[len(HOST_PREFIX):]:
                return a, b
        every = [e for evs in self.ops.values() for e in evs]
        if not every:
            raise ValueError("the trace holds no device operation")
        return min(e[1] for e in every), max(e[2] for e in every)


def start(trace_dir: str) -> None:
    """Start the profiler with the Python tracer off: the device planes
    and the ``bench:`` annotations are all the reduction reads, and the
    Python tracer's events swell the file and slow the host."""
    import jax

    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(trace_dir, profiler_options=options)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def from_profile(profile) -> Trace:
    """A ``jax.profiler.ProfileData`` as a :class:`Trace`."""
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in profile.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            dev = int(m.group(1))
            for line in plane.lines:
                if line.name not in (OPS_LINE, MODULES_LINE):
                    continue
                evs = [
                    (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                    for e in line.events
                ]
                (ops if line.name == OPS_LINE else modules)[dev] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(HOST_PREFIX):
                        host.append((
                            e.name[len(HOST_PREFIX):], int(e.start_ns),
                            int(e.start_ns + e.duration_ns),
                        ))
    return Trace(ops=ops, modules=modules, host=host)


def describe(path: str, names: int = 6) -> List[str]:
    """Planes, lines and the first event names of a trace file: what to
    look at by hand before trusting the reduction on a new platform."""
    from jax.profiler import ProfileData

    out = []
    for plane in ProfileData.from_file(path).planes:
        out.append(f"plane {plane.name!r}")
        for line in plane.lines:
            evs = list(line.events)
            seen = list(dict.fromkeys(e.name for e in evs[:400]))[:names]
            span = (
                f"{evs[0].start_ns:.0f}..{evs[-1].start_ns + evs[-1].duration_ns:.0f} ns"
                if evs else "empty"
            )
            out.append(f"  line {line.name!r}: {len(evs)} events, {span}, {seen}")
    return out


def load(path: str) -> Trace:
    from jax.profiler import ProfileData

    return from_profile(ProfileData.from_file(path))


# -- intervals ---------------------------------------------------------------

def _clip(events: Iterable[Event], lo: int, hi: int) -> List[Tuple[int, int]]:
    out = []
    for _, a, b in events:
        a, b = max(a, lo), min(b, hi)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals: Iterable[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[Tuple[int, int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def total(intervals: Iterable[Tuple[int, int]]) -> int:
    return sum(b - a for a, b in intervals)


def subtract(a: Sequence[Tuple[int, int]], b: Sequence[Tuple[int, int]]
             ) -> List[Tuple[int, int]]:
    """Parts of merged ``a`` that no interval of merged ``b`` covers."""
    out, j = [], 0
    for lo, hi in a:
        cur = lo
        while j < len(b) and b[j][1] <= cur:
            j += 1
        k = j
        while k < len(b) and b[k][0] < hi:
            if b[k][0] > cur:
                out.append((cur, b[k][0]))
            cur = max(cur, b[k][1])
            k += 1
        if cur < hi:
            out.append((cur, hi))
    return out


# -- the numbers -------------------------------------------------------------

def busy_seconds(trace: Trace) -> Dict[int, float]:
    """Per device: seconds of the window in which an operation ran (the
    union of the operations' intervals)."""
    lo, hi = trace.window_ns
    return {
        dev: total(merge(_clip(evs, lo, hi))) / 1e9
        for dev, evs in trace.ops.items()
    }


def window_seconds(trace: Trace) -> float:
    lo, hi = trace.window_ns
    return (hi - lo) / 1e9


def idle_share(trace: Trace) -> float:
    """1 − busy over the window, the mean over the devices traced (as the
    driver works it out from ``busy_s`` and ``window_s``)."""
    busy = busy_seconds(trace)
    if not busy:
        raise ValueError("the trace holds no device plane")
    return 1.0 - (sum(busy.values()) / len(busy)) / window_seconds(trace)


def program_seconds(trace: Trace, match: str) -> List[float]:
    """Durations of every run, inside the window, of the programs whose
    name starts with ``match`` (all devices)."""
    lo, hi = trace.window_ns
    return [
        (b - a) / 1e9
        for evs in trace.modules.values() for name, a, b in evs
        if name.startswith(match) and a >= lo and b <= hi
    ]


def exposed_collective_seconds(trace: Trace) -> Dict[int, float]:
    """Per device: collective time during which no other operation ran
    on that device."""
    lo, hi = trace.window_ns
    out = {}
    for dev, evs in trace.ops.items():
        is_coll = lambda e: bool(COLLECTIVE.match(e[0].lstrip("%")))  # noqa: E731
        coll = merge(_clip((e for e in evs if is_coll(e)), lo, hi))
        rest = merge(_clip((e for e in evs if not is_coll(e)), lo, hi))
        out[dev] = total(subtract(coll, rest)) / 1e9
    return out


def op_group(name: str) -> str:
    """``fusion.123`` -> ``fusion``; ``%copy.4`` -> ``copy``. The TPU's
    ``XLA Ops`` events carry the whole HLO line (``%while.46 = (s32[],
    ...) while(...)``): the name is what stands before `` = ``."""
    name = name.split(" = ", 1)[0].strip().lstrip("%")
    return _SUFFIX.sub("", name) or name


def breakdown(trace: Trace, top: int = 10) -> Dict[str, List[List[object]]]:
    """Device operations by total time (all devices, grouped by name
    without its number), and the idle gaps of the first device summed by
    the benchmark's host span that covered each gap's middle."""
    lo, hi = trace.window_ns
    by_op: Dict[str, float] = collections.defaultdict(float)
    for evs in trace.ops.values():
        for name, a, b in evs:
            a, b = max(a, lo), min(b, hi)
            if b > a:
                by_op[op_group(name)] += (b - a) / 1e9
    gaps: Dict[str, float] = collections.defaultdict(float)
    if trace.ops:
        first = min(trace.ops)
        busy = merge(_clip(trace.ops[first], lo, hi))
        spans = sorted(
            (e for e in trace.host if e[0] != WINDOW_SPAN[len(HOST_PREFIX):]),
            key=lambda e: e[2] - e[1],
        )  # shortest first: the innermost span names the gap
        for a, b in subtract([(lo, hi)], busy):
            mid = (a + b) // 2
            owner = next(
                (n for n, s, e in spans if s <= mid < e), "unannotated"
            )
            gaps[owner] += (b - a) / 1e9
    rank = lambda d: [  # noqa: E731
        [k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]
    ]
    return {"device_ops": rank(by_op), "idle_gaps": rank(gaps)}


def summary(trace: Trace) -> Dict[str, object]:
    busy = busy_seconds(trace)
    if not busy or max(busy.values()) <= 0.0:
        raise ValueError("no operation ran on a device inside the traced window")
    return {
        "busy_s": sum(busy.values()) / len(busy),
        "window_s": window_seconds(trace),
        "busy_by_device": busy,
    }
