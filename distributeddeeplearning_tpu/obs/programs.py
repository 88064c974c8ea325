"""Names on the device's time: which part of the model each operation is.

A device trace names operations as the compiler does (``fusion.123``,
``convolution_bitcast_fusion.4``): that says nothing about which part
of the model ran. The ``jax.named_scope`` path that every instruction
carries in its metadata (``op_name="jit(local_step)/transpose(jvp(
TransformerLM))/block3/attn/attn_core/dot_general"``) does. This module
joins the two:

* :class:`ScopeTable` — for one compiled program, HLO instruction name
  -> ``op_name`` path, parsed from ``compiled.as_text()`` on the first
  question. :func:`register` is called where the compile happens
  (``training/metrics.StepFn.aot_compile``, ``serving/engine.SlotEngine
  .warmup``); until somebody asks, or the program's owner goes, a
  table is a reference to the executable its owner holds anyway. An
  executable loaded from the persistent compile cache keeps its
  metadata, so a warm start has the same table as a cold one.
* :func:`device_seconds_by_scope` — the reduction, over plain ``(name,
  start_ns, end_ns)`` tuples so that it can be checked on hand-made
  events: device seconds by scope group (a group is a regular
  expression over the path) and by pass (:func:`pass_of`: the forward,
  the forward that ``jax.checkpoint`` runs again inside the backward,
  the backward, and what differentiation never touched), and the
  seconds that fell to no group. :func:`program_by_scope` applies it to
  the runs of one program in a capture. The groups are the caller's: they
  name a model's modules and a step's scopes, so they stand beside the
  model (``models/transformer_lm.TRAIN_STEP_GROUPS``), and this module
  knows no model.
* :func:`load_profile` — a profiler capture (``.xplane.pb``) as such
  tuples, with the bus's ``ddl:`` host spans beside the device lines;
  :func:`dump_tables` leaves the tables beside a capture
  (``obs/trace.TraceController`` does at every stop) so that
  ``scripts/trace_report.py`` can read it in another process.

**A fusion has one ``op_name``: its root's.** XLA fuses across scope
boundaries (a LayerNorm's last multiply into the matrix product that
reads it), and the fused instruction carries the metadata of its root
alone, so a group's seconds are those of the fusions *rooted* in it,
and a pass's likewise (:func:`pass_of`).
The check that this is good enough is the reduction's own: the
``unscoped`` share, and the groups' sum against the program's time.
"""

from __future__ import annotations

import bisect
import dataclasses
import glob
import json
import os
import re
import threading
import weakref
from typing import Any, Dict, Iterable, List, Optional, Sequence, Set, Tuple

from distributeddeeplearning_tpu.obs.bus import ANNOTATION_PREFIX

Event = Tuple[str, int, int]  # name, start_ns, end_ns

# `%fusion.12 = ... metadata={op_name="..." ...}`; newer XLA prints no `%`.
_INSTRUCTION = re.compile(
    r'^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?\bop_name="((?:[^"\\]|\\.)*)"'
)
BACKWARD = "transpose(jvp("
FORWARD = "jvp("
# the path component under which `jax.checkpoint` (flax's `nn.remat`)
# replays a block's forward inside the transposed computation
RECOMPUTE = "rematted_computation"
_RECOMPUTED = re.compile(rf"(?:^|/){RECOMPUTE}(?:/|$)")
PASSES = ("forward", "recompute", "backward", "other")
UNSCOPED = "unscoped"
# A Pallas kernel is one custom call of this target on the TPU; the
# parser hangs the target on the instruction's path as its last part.
KERNEL_CALL = "tpu_custom_call"
_KERNEL_TARGET = f'custom_call_target="{KERNEL_CALL}"'

Groups = Sequence[Tuple[str, str]]  # (group name, pattern over the path)


def part(*names: str) -> str:
    """A pattern for ``names`` as whole components of a scope path,
    bare (``/attn/``) or wrapped by a transform (``jvp(loss)``): what a
    group is made of. In a sequence of groups the first pattern found in
    an instruction's path names its group."""
    return r"(?:^|[/(])(?:" + "|".join(names) + r")(?:[/)]|$)"


class ScopeTable:
    """Instruction name -> ``op_name`` path of one compiled program.

    Holds the executable only until the names are read, and no longer
    than ``owner`` lives (who runs the program: a ``StepFn``, a serving
    engine). When the owner goes the table reads the names and keeps
    them alone: a capture is often read after the trainer was torn down
    (the benchmark's readers run once it has freed the engine), and no
    executable, with its generated code on the device, outlives its
    owner for a table's sake."""

    def __init__(self, program: str, compiled: Any, owner: Any) -> None:
        self.program = program
        self._compiled = compiled
        # re-entrant: the collector may run the owner's finalizer on the
        # thread that is inside scopes()
        self._lock = threading.RLock()
        self._scopes: Optional[Dict[str, str]] = None
        self._at_owners_end = weakref.finalize(owner, self._read)
        self._at_owners_end.atexit = False  # nobody reads after the process

    @property
    def holds_executable(self) -> bool:
        return self._compiled is not None

    def scopes(self) -> Dict[str, str]:
        with self._lock:
            if self._scopes is None:
                compiled, self._compiled = self._compiled, None
                self._scopes = (
                    parse_hlo_scopes(compiled.as_text()) if compiled is not None else {}
                )
            return self._scopes

    def _read(self) -> None:
        try:
            self.scopes()
        except Exception:  # noqa: BLE001 - a backend already torn down
            self._scopes = {}

    def release(self) -> None:
        """Let the executable go unread (a newer compile took its place)."""
        self._at_owners_end.detach()
        with self._lock:
            self._compiled = None

    def __len__(self) -> int:
        return len(self.scopes())


def parse_hlo_scopes(text: str) -> Dict[str, str]:
    """``{instruction: op_name}`` from HLO text. Every computation's
    instructions are taken (a ``while`` body's operations run as events
    of their own); instructions without metadata are not in the table.
    A Mosaic kernel's custom call gets ``/tpu_custom_call`` after its
    path (its copies and tuple elements share the ``op_name``, and
    :func:`kernel_calls_by_group` counts the kernels alone)."""
    scopes: Dict[str, str] = {}
    for line in text.splitlines():
        if 'op_name="' not in line:
            continue
        m = _INSTRUCTION.match(line)
        if m and m.group(2):
            kernel = "/" + KERNEL_CALL if _KERNEL_TARGET in line else ""
            scopes[m.group(1)] = m.group(2) + kernel
    return scopes


# -- the registry: one table a program and signature -------------------------

_REGISTRY_LOCK = threading.Lock()
_TABLES: Dict[Tuple[str, Any], ScopeTable] = {}
# Tables that have let their executable go hold names alone (some
# hundred kilobytes for a small program, megabytes for a large one); a
# process that builds engines without end (a test session) keeps the
# newest few. A serving engine has a program a prefill bucket and a
# handful more.
MAX_READ_TABLES = 64


def register(program: str, compiled: Any, owner: Any, key: Any = None) -> ScopeTable:
    """Keep a table for ``compiled`` under ``program`` (the jitted
    function's module name, ``jit_<fn>``: the name its runs carry on the
    trace's ``XLA Modules`` line) for ``owner``, who holds the
    executable anyway. ``key`` tells apart programs of one name (a
    prefill bucket, a batch signature); compiling the same one again
    replaces its table. Costs a dict entry and a finalizer on the owner:
    nothing is parsed here."""
    table = ScopeTable(program, compiled, owner)
    with _REGISTRY_LOCK:
        old = _TABLES.pop((program, key), None)
        if old is not None:
            old.release()
        _TABLES[(program, key)] = table
        read = [k for k, t in _TABLES.items() if not t.holds_executable]
        for k in read[:-MAX_READ_TABLES]:
            del _TABLES[k]
    return table


def tables(program: Optional[str] = None) -> List[ScopeTable]:
    """Registered tables of ``program`` (every program's if None),
    oldest first. Two compiles of one function (two batch signatures)
    share a name, and their instruction names collide: a reader of one
    program's runs takes the newest."""
    with _REGISTRY_LOCK:
        return [t for t in _TABLES.values() if program in (None, t.program)]


def clear() -> None:
    """Drop every table (tests; a process that frees its programs)."""
    with _REGISTRY_LOCK:
        _TABLES.clear()


TABLES_FILE = "scope_tables.json"


def dump_tables(directory: str) -> str:
    """Write every registered table beside a profiler capture, as
    ``{program: {instruction: op_name}}`` (programs of one name merged,
    the newest compile last): what ``scripts/trace_report.py`` needs to
    read the capture once the process that compiled is gone. Parses the
    tables: call it where a capture was made, not on the hot path."""
    merged: Dict[str, Dict[str, str]] = {}
    for table in tables():
        merged.setdefault(table.program, {}).update(table.scopes())
    os.makedirs(directory, exist_ok=True)
    path = os.path.join(directory, TABLES_FILE)
    with open(path, "w") as fh:
        json.dump(merged, fh)
    return path


def load_tables(directory: str) -> Dict[str, Dict[str, str]]:
    """What :func:`dump_tables` wrote under ``directory`` ({} if nothing)."""
    path = os.path.join(directory, TABLES_FILE)
    if not os.path.exists(path):
        return {}
    with open(path) as fh:
        return json.load(fh)


# -- the reduction -----------------------------------------------------------

def instruction_of(event_name: str) -> str:
    """The instruction an ``XLA Ops`` event ran: on the TPU the event's
    name is the whole HLO line and the instruction stands before
    `` = ``; elsewhere it is the name itself."""
    return event_name.split(" = ", 1)[0].strip().lstrip("%")


def innermost_durations(
    events: Iterable[Event], lo: Optional[int] = None, hi: Optional[int] = None
) -> List[Tuple[str, int]]:
    """``(name, ns)`` for each event of one device line, clipped to
    ``[lo, hi)``, where each instant counts once: for the event that
    started last among those running (a ``while`` over the operations of
    its body keeps only the time that no operation of the body covers)."""
    evs = []
    for name, a, b in events:
        a = a if lo is None else max(a, lo)
        b = b if hi is None else min(b, hi)
        if b > a:
            evs.append((a, -b, name))
    evs.sort()
    out: List[List[Any]] = []
    stack: List[Tuple[int, int]] = []  # (end, index into out), innermost last
    cur = 0

    def close_until(t: Optional[int]) -> None:
        nonlocal cur
        while stack and (t is None or stack[-1][0] <= t):
            end, i = stack.pop()
            if end > cur:
                out[i][1] += end - cur
                cur = end

    for a, neg_b, name in evs:
        close_until(a)
        if stack and a > cur:
            out[stack[-1][1]][1] += a - cur
        cur = max(cur, a)
        out.append([name, 0])
        stack.append((-neg_b, len(out) - 1))
    close_until(None)
    return [(name, ns) for name, ns in out]


def within(events: Sequence[Event], intervals: Sequence[Tuple[int, int]]) -> List[Event]:
    """Events whose middle lies in one of the sorted, disjoint
    ``intervals`` (the runs of one program on the ``XLA Modules`` line)."""
    starts = [a for a, _ in intervals]
    out = []
    for ev in events:
        mid = (ev[1] + ev[2]) // 2
        i = bisect.bisect_right(starts, mid) - 1
        if i >= 0 and mid < intervals[i][1]:
            out.append(ev)
    return out


def group_of(path: Optional[str], groups: Sequence[Tuple[str, Any]]) -> str:
    if path:
        for name, pattern in groups:
            if re.search(pattern, path):
                return name
    return UNSCOPED


def pass_of(path: Optional[str]) -> str:
    """The pass of a step that an instruction's ``op_name`` path puts it
    in, one of :data:`PASSES`, by the marks JAX's transforms leave:

    * ``recompute`` where a component of the path is
      ``rematted_computation``: a forward that ``jax.checkpoint`` runs
      again inside the backward (``.../transpose(jvp(M))/checkpoint/
      rematted_computation/block0/mlp/...``). The mark counts at any
      depth, and before the others: a checkpointed ``scan`` body
      replayed inside a replayed block, or inside the backward's own
      loop, is recomputed work wherever it stands.
    * ``backward`` where the path holds ``transpose(jvp(`` and no such
      component.
    * ``forward`` where it holds ``jvp(`` alone.
    * ``other`` for the rest: the optimizer, the metrics, copies the
      compiler put in with no path, and what it named anew, path and
      all (XLA:TPU's ``ragged-dot-*`` calls, whichever pass asked for
      them); the whole of a program that takes no gradient.

    A fusion counts for the pass of its root, as it does for its root's
    group (module docstring): a recomputed multiply fused into a
    backward product reads as backward."""
    if not path:
        return "other"
    if _RECOMPUTED.search(path):
        return "recompute"
    if BACKWARD in path:
        return "backward"
    return "forward" if FORWARD in path else "other"


def groups_in(scopes: Dict[str, str], groups: Groups) -> Set[str]:
    """The groups that at least one instruction of a table falls in. A
    table that lacks a group its program must have was not compiled from
    this tree's source: the persistent compile cache leaves metadata out
    of its key, so an executable that another tree put there carries
    that tree's names, and a reduction over them would put the missing
    group's seconds under another name without a word."""
    compiled = [(name, re.compile(p)) for name, p in groups]
    found = {group_of(path, compiled) for path in set(scopes.values())}
    return found - {UNSCOPED}


def kernel_calls_by_pass(scopes: Dict[str, str], groups: Groups) -> Dict[str, Dict[str, int]]:
    """How many Mosaic kernels (``tpu_custom_call`` instructions) a
    table holds under each group, ``unscoped`` for those in none, by
    the pass each stands in (:func:`pass_of`; every pass is there, 0
    where it holds none): whether a model part runs the kernel it was
    given, and whether block remat runs it again. A GPT-2 step on the
    flash kernels holds 12 forward and 12 backward under ``attn_core``;
    a block-diffusion layer's three passes under block remat, which
    keeps nothing of them, 18 forward, 18 recompute and 18 backward
    over six layers."""
    compiled = [(name, re.compile(p)) for name, p in groups]
    out: Dict[str, Dict[str, int]] = {}
    for path in scopes.values():
        if path.rsplit("/", 1)[-1] == KERNEL_CALL:
            by_pass = out.setdefault(group_of(path, compiled), dict.fromkeys(PASSES, 0))
            by_pass[pass_of(path)] += 1
    return out


def kernel_calls_by_group(scopes: Dict[str, str], groups: Groups) -> Dict[str, int]:
    """:func:`kernel_calls_by_pass` with the passes added up: a GPT-2
    step on the flash kernels holds 24 under ``attn_core``."""
    return {
        group: sum(by_pass.values())
        for group, by_pass in kernel_calls_by_pass(scopes, groups).items()
    }


def _empty_reduction(groups: Groups) -> Dict[str, Any]:
    return {
        "groups": {
            name: {"seconds": 0.0, "forward_s": 0.0, "recompute_s": 0.0, "backward_s": 0.0}
            for name, _ in groups
        },
        "by_pass": dict.fromkeys(PASSES, 0.0),
        "unscoped_s": 0.0, "total_s": 0.0, "unscoped_top": [],
    }


def device_seconds_by_scope(
    events: Iterable[Event],
    scopes: Dict[str, str],
    groups: Groups,
    window: Tuple[Optional[int], Optional[int]] = (None, None),
) -> Dict[str, Any]:
    """Device seconds of one device line's ``events`` by scope group
    and by pass.

    ``scopes`` is a :class:`ScopeTable`'s mapping; an event whose
    instruction is not in it, or whose path matches no group, counts as
    unscoped. Returns ``{"groups": {name: {"seconds", "forward_s",
    "recompute_s", "backward_s"}}, "by_pass": {pass: seconds},
    "unscoped_s", "total_s", "unscoped_top": [[instruction, seconds]]}``
    (the eight largest). Groups plus unscoped add up to ``total_s``, the
    union of the events (:func:`innermost_durations`), and so do the
    four passes of ``by_pass`` (:func:`pass_of`), which cover the whole
    program, unscoped instructions too, and need no groups. In a group,
    ``backward_s`` is everything under ``transpose(jvp(...))``, the
    recomputed forward with it (a rematerialised block is replayed
    inside the transposed computation), and ``recompute_s`` is that
    part: the backward proper is ``backward_s - recompute_s``
    (``by_pass["backward"]`` is the backward proper). A fusion counts
    whole for the group and the pass of its root (module docstring).
    """
    ns_of: Dict[str, int] = {}  # an instruction's nanoseconds, each event's added up
    for name, ns in innermost_durations(events, *window):
        if ns > 0:
            instruction = instruction_of(name)
            ns_of[instruction] = ns_of.get(instruction, 0) + ns
    compiled = [(name, re.compile(p)) for name, p in groups]
    out = _empty_reduction(groups)
    unscoped: Dict[str, float] = {}
    for instruction, ns in ns_of.items():
        seconds = ns / 1e9
        path = scopes.get(instruction)
        group, in_pass = group_of(path, compiled), pass_of(path)
        out["total_s"] += seconds
        out["by_pass"][in_pass] += seconds
        if group == UNSCOPED:
            out["unscoped_s"] += seconds
            unscoped[instruction] = seconds
            continue
        g = out["groups"][group]
        g["seconds"] += seconds
        if path and BACKWARD in path:
            g["backward_s"] += seconds
        if in_pass in ("forward", "recompute"):
            g[in_pass + "_s"] += seconds
    top = sorted(unscoped.items(), key=lambda kv: -kv[1])[:8]
    out["unscoped_top"] = [[k, v] for k, v in top]
    return out


def module_of(event_name: str) -> str:
    """The program an ``XLA Modules`` event ran: the event is named
    ``jit_local_step(<fingerprint>)``, the program ``jit_local_step``."""
    return event_name.split("(", 1)[0]


def program_by_scope(
    ops: Dict[int, Sequence[Event]],
    modules: Dict[int, Sequence[Event]],
    program: str,
    scopes: Dict[str, str],
    groups: Groups,
    window: Tuple[Optional[int], Optional[int]] = (None, None),
) -> Optional[Dict[str, Any]]:
    """:func:`device_seconds_by_scope` over the runs of ``program`` in a
    capture: per device (``ops`` and ``modules`` are its ``XLA Ops`` and
    ``XLA Modules`` lines), the operations inside the runs whose name is
    exactly ``program`` (``jit_local_step_microbatched`` is another
    program, with another table) and that lie whole inside ``window``.
    Seconds (by group and by pass) are sums over the runs and the
    devices; ``runs`` counts the runs and ``run_s`` sums their own
    durations. None where the program did not run."""
    lo, hi = window
    out = dict(_empty_reduction(groups), runs=0, run_s=0.0, devices=0)
    for dev, dev_ops in sorted(ops.items()):
        runs = sorted(
            (a, b) for name, a, b in modules.get(dev, ())
            if module_of(name) == program
            and (lo is None or a >= lo) and (hi is None or b <= hi)
        )
        if not runs:
            continue
        one = device_seconds_by_scope(within(dev_ops, runs), scopes, groups)
        out["devices"] += 1
        out["runs"] += len(runs)
        out["run_s"] += sum(b - a for a, b in runs) / 1e9
        for key in ("unscoped_s", "total_s", "unscoped_top"):
            out[key] += one[key]
        for in_pass, seconds in one["by_pass"].items():
            out["by_pass"][in_pass] += seconds
        for group, g in one["groups"].items():
            for key, seconds in g.items():
                out["groups"][group][key] += seconds
    if not out["runs"]:
        return None
    out["unscoped_top"] = sorted(out["unscoped_top"], key=lambda kv: -kv[1])[:8]
    return out


# -- a profiler capture as tuples --------------------------------------------

_DEVICE_PLANE = re.compile(r"^/device:(?:TPU|GPU):(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"


@dataclasses.dataclass
class Profile:
    """One capture: per device the operation and program events, and the
    bus's host spans (``ddl:`` prefix stripped), all on one clock."""
    ops: Dict[int, List[Event]]
    modules: Dict[int, List[Event]]
    host: List[Event]


def find_xplane(trace_dir: str) -> str:
    paths = sorted(
        glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
        key=os.path.getmtime,
    )
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def from_profile_data(data: Any) -> Profile:
    """A ``jax.profiler.ProfileData`` as a :class:`Profile`."""
    ops: Dict[int, List[Event]] = {}
    modules: Dict[int, List[Event]] = {}
    host: List[Event] = []
    for plane in data.planes:
        m = _DEVICE_PLANE.match(plane.name)
        if m:
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE):
                    evs = [
                        (e.name, int(e.start_ns), int(e.start_ns + e.duration_ns))
                        for e in line.events
                    ]
                    (ops if line.name == OPS_LINE else modules)[int(m.group(1))] = evs
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name.startswith(ANNOTATION_PREFIX):
                        host.append((
                            e.name[len(ANNOTATION_PREFIX):], int(e.start_ns),
                            int(e.start_ns + e.duration_ns),
                        ))
    return Profile(ops=ops, modules=modules, host=host)


def load_profile(path: str) -> Profile:
    """Read ``path`` (an ``.xplane.pb``, or a directory holding one)."""
    from jax.profiler import ProfileData

    if os.path.isdir(path):
        path = find_xplane(path)
    return from_profile_data(ProfileData.from_file(path))


def idle_gaps_by_span(
    ops: Sequence[Event], host: Sequence[Event],
    window: Optional[Tuple[int, int]] = None,
) -> Dict[str, float]:
    """Seconds in which no operation ran on one device line, summed by
    the innermost (shortest) host span over each gap's middle;
    ``unannotated`` where no span covers it."""
    if not ops:
        return {}
    lo, hi = window or (min(e[1] for e in ops), max(e[2] for e in ops))
    busy: List[List[int]] = []
    for _, a, b in sorted(ops, key=lambda e: e[1]):
        a, b = max(a, lo), min(b, hi)
        if b <= a:
            continue
        if busy and a <= busy[-1][1]:
            busy[-1][1] = max(busy[-1][1], b)
        else:
            busy.append([a, b])
    spans = sorted(host, key=lambda e: e[2] - e[1])
    gaps: Dict[str, float] = {}
    cur = lo
    for a, b in busy + [[hi, hi]]:
        if a > cur:
            mid = (cur + a) // 2
            owner = next((n for n, s, e in spans if s <= mid < e), "unannotated")
            gaps[owner] = gaps.get(owner, 0.0) + (a - cur) / 1e9
        cur = max(cur, b)
    return gaps
