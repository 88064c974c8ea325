"""Contract cross-checkers: env docs, env-free tiers, obs registry,
protocol scrub list.

These contracts live in prose/data and rot silently when code moves:

* ``env-docs`` — the ORCHESTRATION.md / OBSERVABILITY.md env tables are
  the operator's API. Every ``os.environ`` read in the package (and the
  ``e = os.environ if env is None else env`` from_env idiom) must name a
  var those docs carry — an undocumented knob is a contract the operator
  can't see.
* ``env-free-tiers`` — ``models/`` and ``ops/`` read no environment:
  what a model or an op does follows from its arguments and from what it
  can observe of the call (shapes, platform), so a traced program is a
  function of its config. A knob belongs in ``TrainConfig`` /
  ``ServeConfig`` (``from_env`` is the one reader) and reaches a model
  as a field.
* ``obs-registry`` — docs/OBSERVABILITY.md's "What is instrumented"
  section is the event-name registry every report/rollup/SLO consumer
  keys on. Every literal ``obs.counter/gauge/point/span`` name emitted
  anywhere in the package must appear there; an unregistered name is
  telemetry nothing will ever render.
* ``protocol-vars`` — recertify scrubs ``_PROTOCOL_VARS`` from the
  environment before each row so an ambient export can't leak into rows
  that leave it unset. Two ways that list rots: a protocol row defines a
  var the scrub list misses, and a new SERVE_*/STREAM_*/BENCH_* knob is
  parsed by a config surface without joining the list. Both checked
  here, against recertify's own AST (no import side effects).

All fail with the exact missing/stale names.
"""

from __future__ import annotations

import ast
import os
import re
from typing import Dict, List, Optional, Set, Tuple

from distributeddeeplearning_tpu.analysis import (
    Finding,
    PACKAGE_ROOT,
    REPO_ROOT,
    package_sources,
    register,
)

DOCS = ("docs", "README.md")
_ENV_NAME_RE = re.compile(r"^[A-Z][A-Z0-9_]{2,}$")

# Vars owned by the platform/runtime, not this repo's contract: they are
# read here but documented (and set) elsewhere. Keep minimal — a var of
# OURS belongs in the docs, not in this set.
EXTERNAL_ENV = {
    "TPU_WORKER_HOSTNAMES",  # TPU-VM metadata (jax.distributed autodetect)
    "JAX_PLATFORMS", "XLA_FLAGS",  # jax/XLA runtime selection
    # where jax keeps its persistent compile cache; read (never set) by
    # training/warmup.enable_compile_cache
    "JAX_COMPILATION_CACHE_DIR",
    "PATH", "HOME", "PWD", "USER",
}


# ---------------------------------------------------------------------------
# Shared extraction: env reads, doc tokens
# ---------------------------------------------------------------------------

def _str_const(node: ast.AST) -> Optional[str]:
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    return None


def _dotted(node: ast.AST) -> Optional[str]:
    parts: List[str] = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
        return ".".join(reversed(parts))
    return None


class _EnvReads(ast.NodeVisitor):
    """Collect ``(var, line)`` for every env read, including the
    ``e = os.environ if env is None else env`` / ``e = _env(env)``
    from_env idiom (names bound to an environ-or-override mapping)."""

    def __init__(self) -> None:
        self.reads: List[Tuple[str, int]] = []
        self._env_aliases: Set[str] = set()

    def _is_environ(self, node: ast.AST) -> bool:
        name = _dotted(node)
        if name in ("os.environ", "environ"):
            return True
        return isinstance(node, ast.Name) and node.id in self._env_aliases

    def visit_Assign(self, node: ast.Assign) -> None:
        v = node.value
        aliasing = False
        if isinstance(v, ast.IfExp) and (
            self._is_environ(v.body) or self._is_environ(v.orelse)
        ):
            aliasing = True
        if isinstance(v, ast.Call) and _dotted(v.func) in ("_env",):
            aliasing = True
        if self._is_environ(v):
            aliasing = True
        if aliasing:
            for t in node.targets:
                if isinstance(t, ast.Name):
                    self._env_aliases.add(t.id)
        self.generic_visit(node)

    def _note(self, var: Optional[str], line: int) -> None:
        if var is not None and _ENV_NAME_RE.match(var):
            self.reads.append((var, line))

    def visit_Call(self, node: ast.Call) -> None:
        name = _dotted(node.func)
        if name == "os.getenv" and node.args:
            self._note(_str_const(node.args[0]), node.lineno)
        elif (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in ("get", "pop", "setdefault")
            and self._is_environ(node.func.value)
            and node.args
        ):
            self._note(_str_const(node.args[0]), node.lineno)
        self.generic_visit(node)

    def visit_Subscript(self, node: ast.Subscript) -> None:
        if self._is_environ(node.value):
            self._note(_str_const(node.slice), node.lineno)
        self.generic_visit(node)

    def visit_Compare(self, node: ast.Compare) -> None:
        # "X" in e / "X" in os.environ
        if (
            len(node.ops) == 1
            and isinstance(node.ops[0], (ast.In, ast.NotIn))
            and self._is_environ(node.comparators[0])
        ):
            self._note(_str_const(node.left), node.lineno)
        self.generic_visit(node)


def env_reads(source: str) -> List[Tuple[str, int]]:
    v = _EnvReads()
    v.visit(ast.parse(source))
    return v.reads


_INLINE_CODE_RE = re.compile(r"`([^`\n]+)`")
_FENCE_RE = re.compile(r"^```.*?$(.*?)^```", re.M | re.S)
_UPPER_TOKEN_RE = re.compile(r"\b([A-Z][A-Z0-9_]{2,})\b")


def doc_texts() -> Dict[str, str]:
    out: Dict[str, str] = {}
    for entry in DOCS:
        path = os.path.join(REPO_ROOT, entry)
        if os.path.isdir(path):
            for name in sorted(os.listdir(path)):
                if name.endswith(".md"):
                    with open(os.path.join(path, name), encoding="utf-8") as f:
                        out[f"{entry}/{name}"] = f.read()
        elif os.path.isfile(path):
            with open(path, encoding="utf-8") as f:
                out[entry] = f.read()
    return out


def documented_env_vars() -> Set[str]:
    """Every UPPER_CASE token that appears as code in the docs (inline
    backticks or fenced blocks) — the documented env surface."""
    vars_: Set[str] = set()
    for text in doc_texts().values():
        for m in _INLINE_CODE_RE.finditer(text):
            vars_.update(_UPPER_TOKEN_RE.findall(m.group(1)))
        for m in _FENCE_RE.finditer(text):
            vars_.update(_UPPER_TOKEN_RE.findall(m.group(1)))
    return vars_


@register(
    "env-docs", "contract",
    "every os.environ read in the package names a var documented in the "
    "docs' env tables (ORCHESTRATION.md / OBSERVABILITY.md / ...)",
)
def run_env_docs() -> List[Finding]:
    documented = documented_env_vars() | EXTERNAL_ENV
    findings: List[Finding] = []
    sources = package_sources([PACKAGE_ROOT])
    for path, src in sorted(sources.items()):
        for var, line in env_reads(src):
            if var not in documented:
                findings.append(Finding(
                    "env-docs", path, line,
                    f"env var {var!r} is read here but documented nowhere "
                    f"in docs/*.md or README.md — add it to the relevant "
                    f"env table (the operator contract)",
                ))
    return findings


# ---------------------------------------------------------------------------
# env-free-tiers
# ---------------------------------------------------------------------------

ENV_FREE_TIERS = ("models", "ops")


def env_touches(source: str) -> List[int]:
    """Lines that touch the process environment at all: ``os.environ``
    (read, written or aliased), ``os.getenv``, ``os.putenv``, or either
    imported by name."""
    lines: List[int] = []
    for node in ast.walk(ast.parse(source)):
        if isinstance(node, ast.Attribute) and _dotted(node) in (
            "os.environ", "os.getenv", "os.putenv"
        ):
            lines.append(node.lineno)
        elif isinstance(node, ast.ImportFrom) and node.module == "os" and any(
            a.name in ("environ", "getenv", "putenv") for a in node.names
        ):
            lines.append(node.lineno)
    return sorted(set(lines))


@register(
    "env-free-tiers", "contract",
    "no file under models/ or ops/ touches the process environment: a "
    "model's or an op's behaviour follows from its arguments and the call",
)
def run_env_free_tiers() -> List[Finding]:
    roots = [os.path.join(PACKAGE_ROOT, tier) for tier in ENV_FREE_TIERS]
    return [
        Finding(
            "env-free-tiers", path, line,
            "the environment is touched under models/ or ops/ — take the "
            "value as an argument or a module field (config.from_env is "
            "the reader), or decide from what the call can observe",
        )
        for path, src in sorted(package_sources(roots).items())
        for line in env_touches(src)
    ]


# ---------------------------------------------------------------------------
# obs-registry
# ---------------------------------------------------------------------------

_EMIT_METHODS = {"counter", "gauge", "point", "span", "span_event"}
_BUS_RECEIVERS = {"obs", "bus", "_bus"}
_BUS_CALLS = {"get_bus", "current_bus"}


class _ObsEmits(ast.NodeVisitor):
    """Collect ``(name_or_prefix, is_prefix, kind, line)`` for every
    literal event emission (f-string names contribute their literal
    prefix, matched as a prefix against the registry)."""

    def __init__(self) -> None:
        self.emits: List[Tuple[str, bool, str, int]] = []

    def _is_bus(self, node: ast.AST) -> bool:
        if isinstance(node, ast.Name):
            return node.id in _BUS_RECEIVERS
        if isinstance(node, ast.Attribute):
            return node.attr in ("bus", "_bus") or (
                _dotted(node) or ""
            ).endswith(".obs")
        if isinstance(node, ast.Call):
            name = _dotted(node.func)
            return name is not None and name.split(".")[-1] in _BUS_CALLS
        return False

    def visit_Call(self, node: ast.Call) -> None:
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _EMIT_METHODS
            and self._is_bus(node.func.value)
            and node.args
        ):
            arg = node.args[0]
            name = _str_const(arg)
            if name is not None:
                self.emits.append((name, False, node.func.attr, node.lineno))
            elif isinstance(arg, ast.JoinedStr):
                prefix = ""
                for part in arg.values:
                    s = _str_const(part)
                    if s is None:
                        break
                    prefix += s
                if prefix:
                    self.emits.append(
                        (prefix, True, node.func.attr, node.lineno)
                    )
        self.generic_visit(node)


def obs_emits(source: str) -> List[Tuple[str, bool, str, int]]:
    v = _ObsEmits()
    v.visit(ast.parse(source))
    return v.emits


_EVENT_TOKEN_RE = re.compile(r"^[a-z][\w.*-]*$")


def registered_event_names() -> Set[str]:
    """The OBSERVABILITY.md registry: every inline-code token that looks
    like an event name (lowercase dotted identifier; ``*`` wildcards
    allowed, e.g. ``epoch.*``)."""
    path = os.path.join(REPO_ROOT, "docs", "OBSERVABILITY.md")
    with open(path, encoding="utf-8") as f:
        text = f.read()
    names: Set[str] = set()
    for m in _INLINE_CODE_RE.finditer(text):
        token = m.group(1).strip()
        if _EVENT_TOKEN_RE.match(token):
            names.add(token)
    return names


def _name_registered(
    name: str, is_prefix: bool, registry: Set[str]
) -> bool:
    if name in registry:
        return True
    for r in registry:
        if r.endswith("*") and name.startswith(r[:-1].rstrip(".")):
            return True
        # f-string emissions (`f"epoch.{k}"`): the literal prefix must
        # prefix at least one registered name.
        if is_prefix and r.startswith(name):
            return True
    return False


@register(
    "obs-registry", "contract",
    "every obs/bus emit name in the package appears in the "
    "docs/OBSERVABILITY.md event registry",
)
def run_obs_registry() -> List[Finding]:
    registry = registered_event_names()
    findings: List[Finding] = []
    sources = package_sources([PACKAGE_ROOT])
    for path, src in sorted(sources.items()):
        for name, is_prefix, kind, line in obs_emits(src):
            if not _name_registered(name, is_prefix, registry):
                what = f"{name}*" if is_prefix else name
                findings.append(Finding(
                    "obs-registry", path, line,
                    f"{kind} {what!r} is emitted here but absent from the "
                    f"docs/OBSERVABILITY.md registry — register it (the "
                    f"report/rollup/SLO consumers key on that list)",
                ))
    return findings


# ---------------------------------------------------------------------------
# obs-trace-ctx
# ---------------------------------------------------------------------------

#: The serving hot paths where every per-request emit must carry its
#: request's trace id (docs/OBSERVABILITY.md trace plane).
TRACE_HOT_PATHS = (
    "distributeddeeplearning_tpu/serving/scheduler.py",
    "distributeddeeplearning_tpu/serving/fleet/router.py",
)

#: Event-name families whose emit sites must execute under a bound
#: trace context. Prefix-matched: ``serve.request`` also covers
#: ``serve.request_done``; ``serve.decode`` covers ``serve.decode_step``
#: (the shared tick, bound to the server's own tick trace) and
#: ``serve.decode_share`` (the per-slot attribution span).
TRACED_FAMILIES = (
    "serve.request", "serve.prefill", "serve.decode",
    "serve.queue_wait", "serve.ttft", "serve.delivery",
)


def _binds_trace_ctx(node: ast.With) -> bool:
    for item in node.items:
        ce = item.context_expr
        if isinstance(ce, ast.Call):
            name = _dotted(ce.func)
            if name is not None and name.split(".")[-1] == "trace_ctx":
                return True
    return False


class _NakedTracedEmits(ast.NodeVisitor):
    """Find traced-family emits with no lexically enclosing
    ``with ...trace_ctx(...)``. Function boundaries are barriers: a
    nested ``def``'s body runs later, possibly outside the ``with``, so
    an outer binding does not cover it."""

    def __init__(self) -> None:
        self.naked: List[Tuple[str, str, int]] = []
        self._stack: List[str] = []  # "trace" | "with" | "barrier"
        self._is_bus = _ObsEmits()._is_bus

    def _covered(self) -> bool:
        for frame in reversed(self._stack):
            if frame == "trace":
                return True
            if frame == "barrier":
                return False
        return False

    def visit_With(self, node: ast.With) -> None:
        self._stack.append(
            "trace" if _binds_trace_ctx(node) else "with"
        )
        self.generic_visit(node)
        self._stack.pop()

    def _visit_barrier(self, node: ast.AST) -> None:
        self._stack.append("barrier")
        self.generic_visit(node)
        self._stack.pop()

    visit_FunctionDef = _visit_barrier
    visit_AsyncFunctionDef = _visit_barrier
    visit_Lambda = _visit_barrier

    def visit_Call(self, node: ast.Call) -> None:
        if (
            isinstance(node.func, ast.Attribute)
            and node.func.attr in _EMIT_METHODS
            and self._is_bus(node.func.value)
            and node.args
        ):
            name = _str_const(node.args[0])
            if (
                name is not None
                and name.startswith(TRACED_FAMILIES)
                and not self._covered()
            ):
                self.naked.append((name, node.func.attr, node.lineno))
        self.generic_visit(node)


@register(
    "obs-trace-ctx", "contract",
    "every serve.request/serve.prefill/serve.decode-family emit in the "
    "serving hot paths executes under a lexically bound obs.trace_ctx, "
    "so the record carries its request's trace id",
)
def run_obs_trace_ctx() -> List[Finding]:
    findings: List[Finding] = []
    for rel in TRACE_HOT_PATHS:
        path = os.path.join(REPO_ROOT, rel)
        with open(path, encoding="utf-8") as f:
            src = f.read()
        v = _NakedTracedEmits()
        v.visit(ast.parse(src))
        for name, kind, line in v.naked:
            findings.append(Finding(
                "obs-trace-ctx", rel, line,
                f"{kind} {name!r} is emitted outside any bound trace "
                f"context — wrap it in `with obs.trace_ctx(...)` so the "
                f"record carries its request's trace id (the critical-"
                f"path reconstructor in obs/traces.py keys on it)",
            ))
    return findings


# ---------------------------------------------------------------------------
# protocol-vars
# ---------------------------------------------------------------------------

_PROTOCOL_PREFIXES = ("SERVE_", "STREAM_", "BENCH_", "ARBITER_", "COLOC_")


def _recertify_tables() -> Tuple[Set[str], Dict[str, Set[str]], str]:
    """(``_PROTOCOL_VARS``, protocol → row env keys, path) parsed from
    recertify's AST — no import, no side effects."""
    path = os.path.join(REPO_ROOT, "scripts", "recertify.py")
    with open(path, encoding="utf-8") as f:
        tree = ast.parse(f.read())
    scrub: Set[str] = set()
    rows: Dict[str, Set[str]] = {}
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        target = node.targets[0]
        if not isinstance(target, ast.Name):
            continue
        if target.id == "_PROTOCOL_VARS" and isinstance(
            node.value, (ast.Tuple, ast.List)
        ):
            for elt in node.value.elts:
                s = _str_const(elt)
                if s:
                    scrub.add(s)
        if target.id == "PROTOCOLS" and isinstance(node.value, ast.Dict):
            for k, v in zip(node.value.keys, node.value.values):
                proto = _str_const(k)
                if proto is None or not isinstance(v, ast.Dict):
                    continue
                keys = {
                    s for s in (_str_const(kk) for kk in v.keys)
                    if s and not s.startswith("_")
                }
                rows[proto] = keys
    return scrub, rows, os.path.relpath(path, REPO_ROOT)


@register(
    "protocol-vars", "contract",
    "every env knob a recertify row defines, and every SERVE_*/STREAM_*/"
    "BENCH_* knob parsed by a config surface, is in recertify's "
    "_PROTOCOL_VARS scrub list",
)
def run_protocol_vars() -> List[Finding]:
    scrub, rows, rec_path = _recertify_tables()
    findings: List[Finding] = []
    if not scrub or not rows:
        findings.append(Finding(
            "protocol-vars", rec_path, 1,
            "could not parse _PROTOCOL_VARS / PROTOCOLS from recertify — "
            "the checker needs both as module-level literals",
        ))
        return findings
    for proto, keys in sorted(rows.items()):
        missing = sorted(keys - scrub)
        if missing:
            findings.append(Finding(
                "protocol-vars", rec_path, 1,
                f"protocol row {proto!r} defines {missing} but "
                f"_PROTOCOL_VARS does not scrub them — an ambient export "
                f"of these can leak into every other row",
            ))
    # Config-surface knobs: any SERVE_*/STREAM_*/BENCH_* var read by the
    # package or the bench/serve scripts joins the scrub list the moment
    # it exists (recertify itself is exempt — it IS the scrubber).
    for path, src in sorted(package_sources().items()):
        if path.endswith("scripts/recertify.py"):
            continue
        for var, line in env_reads(src):
            if var.startswith(_PROTOCOL_PREFIXES) and var not in scrub:
                findings.append(Finding(
                    "protocol-vars", path, line,
                    f"env knob {var!r} is parsed here but missing from "
                    f"recertify's _PROTOCOL_VARS — an ambient export "
                    f"would leak into protocol rows that leave it unset",
                ))
    return findings
