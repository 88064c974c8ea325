"""The program's own observability, for the readers: the one module
through which ``benchmarks/readers/`` reach ``distributeddeeplearning_tpu
.obs`` (its event bus, and the scope tables of its compiled programs).

Every function returns None where the program has nothing of the kind
to read (a parent commit from before the spans, the totals or the scope
tables existed): the reader then reports nothing and the result line
leaves the metric out.
"""

from __future__ import annotations

import importlib
from typing import Any, Dict, List, Optional, Tuple


def _bus():
    from distributeddeeplearning_tpu import obs

    return obs.get_bus()


def _in_window(e: Dict[str, Any], name: str, kind: str, window) -> bool:
    return e.get("name") == name and e.get("kind") == kind and window[0] <= e["t"] < window[1]


def ring_events(name: str, kind: str, window: Tuple[float, float]) -> List[Dict[str, Any]]:
    """Events ``name`` of ``kind`` that the flight-recorder ring still
    holds and that began inside ``window`` (``time.monotonic()`` seconds,
    the clock of ``run["window"]``)."""
    return [e for e in list(_bus().ring) if _in_window(e, name, kind, window)]


def ring_spans_after(
    name: str, after: str, first: int, window: Tuple[float, float]
) -> List[Dict[str, Any]]:
    """Of the spans ``name`` that :func:`ring_events` would give, the
    ``first`` that were emitted next after each span ``after`` (the ring
    is in the order of emission, a span's at its end)."""
    out, left = [], 0
    for e in list(_bus().ring):
        if _in_window(e, after, "span", window):
            left = first
        elif left and _in_window(e, name, "span", window):
            out.append(e)
            left -= 1
    return out


def total(name: str) -> Optional[Dict[str, Any]]:
    """``{"kind", "count", "sum"}`` of span or counter ``name`` over the
    whole process (``EventBus.totals``), None where the bus keeps no
    totals or never saw the name."""
    totals = getattr(_bus(), "totals", None)
    return totals().get(name) if totals is not None else None


def step_by_scope(run: Dict[str, Any], spec: Dict[str, Any]) -> Optional[Dict[str, Any]]:
    """Device seconds by scope group of the runs of the program
    ``spec["match"]`` (its exact name on the ``XLA Modules`` line) that
    lie whole inside the traced window: the program's newest scope table
    joined with the ``XLA Ops`` events inside those runs, through the
    program's own reduction (``obs/programs.program_by_scope``) and the
    groups the model keeps beside itself (``spec["groups"]``, a
    ``module:ATTRIBUTE``). Seconds are sums over the runs and devices;
    ``runs`` counts them. None, too, where the table lacks one of the
    groups: its names are then another tree's (an executable out of a
    compile cache that the other tree filled), and the seconds would
    land under the wrong names. Kept in ``run`` so that the metrics of
    one program share one reduction."""
    trace = run.get("trace")
    if trace is None or not trace.ops:
        return None
    kept = run.setdefault("_by_scope", {})
    program = spec["match"]
    if program not in kept:
        kept[program] = _step_by_scope(trace, program, spec["groups"])
    return kept[program]


def _step_by_scope(trace, program: str, groups_at: str) -> Optional[Dict[str, Any]]:
    module, _, attribute = groups_at.partition(":")
    try:
        programs = importlib.import_module("distributeddeeplearning_tpu.obs.programs")
        groups = getattr(importlib.import_module(module), attribute)
    except (ImportError, AttributeError):
        return None
    tables = programs.tables(program)
    if not tables:
        return None
    scopes = tables[-1].scopes()
    if programs.groups_in(scopes, groups) != {name for name, _ in groups}:
        return None
    return programs.program_by_scope(
        trace.ops, trace.modules, program, scopes, groups, trace.window_ns
    )
