"""HLO-level audit: donation, collective placement, cache-key stability.

The dynamic oracles sample these properties on whatever configs a test
happens to build; this family lowers the real programs — each training
engine's compiled step (dp / pjit / sp / pp at tiny-LM scale) plus the
SlotEngine's closed program set (via :meth:`SlotEngine.program_specs`,
the same table warmup compiles) — on the forced-8-CPU-device mesh and
walks the compiled modules:

* ``hlo-donation`` — every donated input leaf (the state under
  ``donate_argnums=(0,)``, the KV pool under ``(1,)``) must actually be
  reclaimed by a call: the compiled program runs once and each donated
  device buffer ≥ 4 KiB must come back ``is_deleted()``. A donation
  that silently fails doubles the state's HBM footprint; XLA only
  warns.
* ``hlo-collectives`` — the dp step carries its gradient all-reduce;
  the ACCUM_STEPS variant carries NO collective inside the scan body
  (``while``-loop computations, transitively) and exactly as many
  all-reduces as the plain step — collectives run once per dispatch on
  the accumulated means, never once per microbatch.
* ``hlo-cache-key`` — building + lowering the same config twice must
  produce byte-identical HLO text. Nondeterministic lowering (an
  unordered dict in a closure, a fresh uncached constant) silently
  defeats the persistent compilation cache that cheap restarts and the
  recertify battery depend on.
* ``hlo-fused-decode`` — the SERVE_DECODE_KERNEL=fused decode program
  carries the fused-kernel evidence (the Pallas custom-call on TPU; the
  ``paged_decode_fused`` scope marker under CPU interpret mode) and
  contains NO full-sequence-length dequantized K/V buffer — the
  gather→dequant→HBM round-trip the kernel exists to eliminate. The
  detector self-calibrates: the stitched XLA twin of the same config
  MUST trip it, so a silently-broken detector is itself a finding.
  Fused programs also go through the cache-key rule.
* ``hlo-async-collective`` — the pjit/sp gradient all-reduces carry the
  ``training/overlap.py`` scope tag in their HLO metadata (provable on
  any backend, including this CPU CI), and wherever the backend DOES
  split them (``all-reduce-start``, TPU async flags), every start has a
  matching ``-done`` with real compute scheduled between — latency
  actually hidden, not just requested.

Everything here needs jax ≥ 8 CPU devices; the runners force
``JAX_PLATFORMS=cpu`` + ``--xla_force_host_platform_device_count=8``
when jax is not yet initialised (``scripts/ddlint.py`` sets both before
any import, tests inherit the conftest's).
"""

from __future__ import annotations

import re
from typing import Dict, List, Sequence, Set, Tuple

from distributeddeeplearning_tpu.analysis import Finding, register

# ---------------------------------------------------------------------------
# HLO text walking (pure string work — testable without jax)
# ---------------------------------------------------------------------------

_COMP_HEAD_RE = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s*(?:\([^{]*)?\{")
_WHILE_BODY_RE = re.compile(r"\bwhile\([^\n]*?body=%?([\w.\-]+)")
_CALLED_RE = re.compile(
    r"(?:to_apply|body|condition|branch_computations)=\{?%?([\w.\-{}, %]+)"
)
# The result type is one array type or — when XLA's combiner merges the
# gradient leaves into one collective — a parenthesised tuple of them.
_ALLREDUCE_RE = re.compile(
    r"=\s*(?:\([^()]*\)|\S+)\s+(all-reduce|all-reduce-start)\("
)


def hlo_computations(text: str) -> Dict[str, List[str]]:
    """Computation name → its instruction lines (HLO text blocks start
    at column 0 with ``%name (...) {`` or ``ENTRY ...``)."""
    comps: Dict[str, List[str]] = {}
    current: str = ""
    for line in text.splitlines():
        if line and not line[0].isspace():
            m = _COMP_HEAD_RE.match(line.strip())
            if m:
                current = m.group(1)
                comps[current] = []
                continue
        if current:
            comps[current].append(line)
    return comps


def while_body_closure(text: str) -> Set[str]:
    """Every computation reachable from a ``while`` loop's body —
    "inside the scan", transitively through to_apply/call edges."""
    comps = hlo_computations(text)
    roots: Set[str] = set(_WHILE_BODY_RE.findall(text))
    seen: Set[str] = set()
    frontier = list(roots)
    while frontier:
        name = frontier.pop()
        if name in seen or name not in comps:
            continue
        seen.add(name)
        for line in comps[name]:
            for m in _CALLED_RE.finditer(line):
                for ref in re.findall(r"%?([\w.\-]+)", m.group(1)):
                    if ref in comps and ref not in seen:
                        frontier.append(ref)
    return seen


def allreduce_sites(text: str) -> List[Tuple[str, str]]:
    """``(computation, instruction line)`` for every all-reduce."""
    out: List[Tuple[str, str]] = []
    for comp, lines in hlo_computations(text).items():
        for line in lines:
            if _ALLREDUCE_RE.search(line):
                out.append((comp, line.strip()))
    return out


# XLA declines to alias tiny buffers (index vectors, scalar counters)
# whose liveness doesn't pay for aliasing — verified at runtime: the
# SlotEngine's s32[num_slots] position vectors stay undeleted after a
# donated call while every KV tensor is reclaimed. Donation exists to
# keep the BIG buffers single-resident, so leaves under a page are out
# of scope for the rule.
DONATION_BYTE_FLOOR = 4096


def check_donation(
    compiled,
    args: Sequence,
    donate_argnums: Sequence[int],
    program: str,
    path: str,
) -> List[Finding]:
    """Execute ``compiled`` once and verify every donated device leaf at
    or above :data:`DONATION_BYTE_FLOOR` was reclaimed (``is_deleted``).

    Runtime deletion is donation's actual semantics — the compiled
    module's ``input_output_alias`` text reorders parameters, but a
    donated-and-aliased input buffer is *deleted* by the call, and one
    XLA declined to alias is not. The donated args must be
    device-resident jax arrays (the real states/pools are)."""
    import jax

    donated = [
        (f"arg{ai}{jax.tree_util.keystr(p)}", leaf)
        for ai in donate_argnums
        for p, leaf in jax.tree_util.tree_leaves_with_path(args[ai])
        if isinstance(leaf, jax.Array)
        and leaf.nbytes >= DONATION_BYTE_FLOOR
    ]
    if not donated:
        return [Finding(
            "hlo-donation", path, 1,
            f"{program}: no device-resident donated leaves >= "
            f"{DONATION_BYTE_FLOOR}B to audit — the donation check "
            f"needs placed example args",
        )]
    compiled(*args)
    missing = [p for p, leaf in donated if not leaf.is_deleted()]
    if not missing:
        return []
    head = missing[:6]
    more = f" (+{len(missing) - 6} more)" if len(missing) > 6 else ""
    return [Finding(
        "hlo-donation", path, 1,
        f"{program}: donation not delivered for {len(missing)} donated "
        f"leaves — {head}{more}; an unaliased donated buffer is "
        f"double-resident in HBM (XLA only warns)",
    )]


def check_scan_collectives(
    accum_text: str, plain_text: str, program: str, path: str
) -> List[Finding]:
    """No all-reduce inside the accum scan body; same all-reduce count
    as the plain step (once per dispatch, not per microbatch)."""
    findings: List[Finding] = []
    inside = while_body_closure(accum_text)
    if not inside:
        findings.append(Finding(
            "hlo-collectives", path, 1,
            f"{program}: no while-loop computation in the compiled "
            f"module — the ACCUM_STEPS scan is gone (unrolled or "
            f"dropped), so collective placement cannot be audited",
        ))
    in_scan = [
        (comp, line) for comp, line in allreduce_sites(accum_text)
        if comp in inside
    ]
    if in_scan:
        findings.append(Finding(
            "hlo-collectives", path, 1,
            f"{program}: {len(in_scan)} all-reduce(s) INSIDE the "
            f"ACCUM_STEPS scan body (e.g. in computation "
            f"{in_scan[0][0]!r}) — gradients must accumulate locally "
            f"and reduce once per dispatch",
        ))
    n_plain = len(allreduce_sites(plain_text))
    n_accum = len(allreduce_sites(accum_text))
    if n_plain == 0:
        findings.append(Finding(
            "hlo-collectives", path, 1,
            f"{program}: plain step compiled with ZERO all-reduces — "
            f"the gradient reduction is missing (or the mesh collapsed "
            f"to one device)",
        ))
    elif n_accum != n_plain:
        findings.append(Finding(
            "hlo-collectives", path, 1,
            f"{program}: accum step has {n_accum} all-reduces vs the "
            f"plain step's {n_plain} — collectives must run once per "
            f"dispatch on the accumulated means",
        ))
    return findings


def check_cache_key(
    text_a: str, text_b: str, program: str, path: str
) -> List[Finding]:
    if text_a == text_b:
        return []
    # Name the first differing line — the usual culprits are unordered
    # closures and fresh constants, both visible right at the diff.
    for la, lb in zip(text_a.splitlines(), text_b.splitlines()):
        if la != lb:
            diff = f"first diff: {la.strip()[:80]!r} vs {lb.strip()[:80]!r}"
            break
    else:
        diff = "texts differ in length"
    return [Finding(
        "hlo-cache-key", path, 1,
        f"{program}: two lowers of the same config are not "
        f"byte-identical ({diff}) — nondeterministic lowering defeats "
        f"the persistent compilation cache",
    )]


# Dequant detector: an f32 `multiply` whose output is a >= 4-dim
# tensor ([B, L, H, Dh] dense rows, [B, mb, bs, H, Dh] gathered blocks)
# holding at least a full KV pool's worth of elements is the stitched
# path's dequantize-into-HBM buffer. Attention/MLP activations at
# decode are [B, 1, ...] 3-dim tensors, and everything the fused
# kernel multiplies in f32 is block-sized or lane scratch — neither
# matches both conditions.
_F32_MUL_RE = re.compile(r"=\s*f32\[([\d,]*)\][^=]*\bmultiply\(")


def _full_kv_multiplies(text: str, min_elems: int) -> List[str]:
    """Instruction lines whose f32 multiply output is >= 4-dim and
    spans >= min_elems elements (the full-sequence dequantized K/V
    signature)."""
    out = []
    for line in text.splitlines():
        m = _F32_MUL_RE.search(line)
        if not m:
            continue
        dims = [int(d) for d in m.group(1).split(",") if d]
        if len(dims) < 4:
            continue
        n = 1
        for d in dims:
            n *= d
        if n >= min_elems:
            out.append(line.strip())
    return out


def check_fused_decode(
    fused_text: str, xla_text: str, min_elems: int, program: str,
    path: str,
) -> List[Finding]:
    """The fused decode program's two invariants + detector calibration
    against its stitched XLA twin (see module docstring)."""
    from distributeddeeplearning_tpu.ops.pallas.paged_decode import (
        FUSED_SCOPE,
    )

    findings: List[Finding] = []
    # Kernel evidence: the TPU lowering is a custom-call; the CPU
    # interpret lowering inlines the grid but keeps the named scope in
    # instruction metadata. Either form proves dispatch reached the
    # kernel.
    if "custom-call" not in fused_text and FUSED_SCOPE not in fused_text:
        findings.append(Finding(
            "hlo-fused-decode", path, 1,
            f"{program}: neither a Pallas custom-call nor the "
            f"{FUSED_SCOPE!r} scope marker appears in the lowered decode "
            f"program — SERVE_DECODE_KERNEL=fused never reached the "
            f"kernel (ops/pallas/paged_decode.py dispatch lost)",
        ))
    hits = _full_kv_multiplies(fused_text, min_elems)
    if hits:
        findings.append(Finding(
            "hlo-fused-decode", path, 1,
            f"{program}: fused decode still materialises a "
            f"full-sequence dequantized K/V buffer "
            f"({hits[0][:80]!r}) — the gather→dequant chain the kernel "
            f"exists to eliminate is back",
        ))
    if not _full_kv_multiplies(xla_text, min_elems):
        findings.append(Finding(
            "hlo-fused-decode", path, 1,
            f"{program}: the stitched XLA twin shows NO full-sequence "
            f"dequantized K/V multiply — the detector lost its signal "
            f"(threshold {min_elems} elems); fix _full_kv_multiplies "
            f"before trusting the fused assertion",
        ))
    return findings


_COMPUTE_OP_RE = re.compile(
    r"=\s*\S+\s+(fusion|dot|convolution|multiply|add|subtract|divide|"
    r"exponential|custom-call)\b"
)


def check_async_collectives(
    text: str, program: str, path: str,
) -> List[Finding]:
    """The overlap contract on one compiled train step: (a) >= 1
    all-reduce carries the ``training/overlap.py`` tag; (b) every
    ``all-reduce-start`` pairs with a ``-done`` and has compute
    scheduled between them (vacuously true where the backend never
    splits — the CPU CI proves (a), a TPU build proves both)."""
    from distributeddeeplearning_tpu.training.overlap import OVERLAP_SCOPE

    findings: List[Finding] = []
    sites = allreduce_sites(text)
    if not any(OVERLAP_SCOPE in line for _, line in sites):
        findings.append(Finding(
            "hlo-async-collective", path, 1,
            f"{program}: none of the {len(sites)} all-reduce sites "
            f"carries the {OVERLAP_SCOPE!r} tag — the step builder lost "
            f"the overlap scope (training/overlap.py; "
            f"TrainConfig.async_collectives)",
        ))
    for comp, lines in hlo_computations(text).items():
        starts: Dict[str, int] = {}
        for i, line in enumerate(lines):
            m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=.*"
                         r"\ball-reduce-start\b", line)
            if m:
                starts[m.group(1)] = i
        for name, i in starts.items():
            done = next(
                (j for j, line in enumerate(lines)
                 if "all-reduce-done" in line and name in line), None,
            )
            if done is None:
                findings.append(Finding(
                    "hlo-async-collective", path, 1,
                    f"{program}: all-reduce-start %{name} in {comp} has "
                    f"no matching all-reduce-done — unfinished async "
                    f"collective",
                ))
                continue
            between = [
                line for line in lines[i + 1:done]
                if _COMPUTE_OP_RE.search(line)
                and "all-reduce" not in line
            ]
            if not between:
                findings.append(Finding(
                    "hlo-async-collective", path, 1,
                    f"{program}: all-reduce-start %{name} in {comp} "
                    f"completes with no compute scheduled between start "
                    f"and done — the async pair hides nothing",
                ))
    return findings


# ---------------------------------------------------------------------------
# Program construction (tiny-LM scale, forced CPU mesh)
# ---------------------------------------------------------------------------

VOCAB, T = 32, 8


def _require_devices() -> None:
    import jax

    n = jax.device_count()
    if n < 8:
        raise RuntimeError(
            f"the HLO audit needs the forced 8-CPU-device mesh, got "
            f"{n} — run via scripts/ddlint.py (it exports JAX_PLATFORMS="
            f"cpu and --xla_force_host_platform_device_count=8 before "
            f"importing jax) or under tests/conftest.py"
        )


def _cfg(**kw):
    from distributeddeeplearning_tpu.config import TrainConfig

    base = dict(
        num_classes=VOCAB, batch_size_per_device=2, weight_decay=0.0,
        compute_dtype="float32",
    )
    base.update(kw)
    return TrainConfig(**base)


def _lm(**kw):
    import jax.numpy as jnp

    from distributeddeeplearning_tpu.models.transformer_lm import (
        TransformerLM,
    )

    return TransformerLM(
        variant="tiny", vocab_size=VOCAB, max_seq_len=T,
        dtype=jnp.float32, **kw,
    )


def _tx():
    import optax

    return optax.sgd(0.1, momentum=0.9)


def _token_batch(rows: int):
    import numpy as np

    rng = np.random.RandomState(0)
    data = rng.randint(0, VOCAB, size=(rows, T + 1)).astype(np.int32)
    return data[:, :-1], data[:, 1:]


def _train_step_bundles() -> List[dict]:
    """(program, lowered_a, lowered_b, args, donate) for each engine's
    donated train step — the builder runs TWICE per engine so the
    cache-key rule sees two independent closures."""
    import jax

    from distributeddeeplearning_tpu.parallel.mesh import create_mesh

    _require_devices()
    bundles: List[dict] = []

    def lower_twice(build):
        """build() -> (step_callable_with_lower, args). Runs build twice:
        lowered module A and B must match byte-for-byte."""
        step_a, args = build()
        step_b, _ = build()
        return step_a.lower(*args), step_b.lower(*args), args

    # dp (plain + accum twin for the collective-placement rule)
    def build_dp(accum: int):
        def build():
            from distributeddeeplearning_tpu.training.train_step import (
                create_train_state,
                make_train_step,
                replicate_state,
            )

            mesh = create_mesh(axes=("data",), shape=(8,))
            cfg = _cfg(accum_steps=accum)
            model = _lm()
            tx = _tx()
            state = replicate_state(
                create_train_state(
                    model, cfg, tx, input_shape=(1, T),
                    input_dtype=jax.numpy.int32,
                ),
                mesh,
            )
            step = make_train_step(model, tx, mesh, cfg, donate_state=True)
            return step, (state, _token_batch(16))

        return build

    low_a, low_b, args = lower_twice(build_dp(1))
    dp_plain = dict(
        program="dp train step", lowered=low_a, lowered_b=low_b,
        args=args, donate=(0,),
    )
    bundles.append(dp_plain)
    low_a, low_b, args = lower_twice(build_dp(2))
    bundles.append(dict(
        program="dp train step (ACCUM_STEPS=2)", lowered=low_a,
        lowered_b=low_b, args=args, donate=(0,), accum_twin_of=dp_plain,
    ))

    # pjit (GSPMD tensor parallel over data×model)
    def build_pjit():
        from distributeddeeplearning_tpu.training.pjit_step import (
            build_pjit_state,
            make_pjit_train_step,
        )

        mesh = create_mesh(axes=("data", "model"), shape=(4, 2))
        cfg = _cfg(engine="pjit")
        model = _lm()
        tx = _tx()
        state = build_pjit_state(
            model, cfg, tx, mesh, input_shape=(1, T),
            input_dtype=jax.numpy.int32,
        )
        step = make_pjit_train_step(model, tx, mesh, cfg)
        return step, (state, _token_batch(16))

    low_a, low_b, args = lower_twice(build_pjit)
    bundles.append(dict(
        program="pjit train step", lowered=low_a, lowered_b=low_b,
        args=args, donate=(0,),
    ))

    # sp (ring attention over data×seq)
    def build_sp():
        from distributeddeeplearning_tpu.training.sp_step import (
            make_sp_train_step,
        )
        from distributeddeeplearning_tpu.training.train_step import (
            create_train_state,
            replicate_state,
        )

        mesh = create_mesh(axes=("data", "seq"), shape=(2, 4))
        cfg = _cfg()
        model = _lm(attn_impl="ring", seq_axis="seq")
        tx = _tx()
        state = replicate_state(
            create_train_state(
                model, cfg, tx, input_shape=(1, T),
                input_dtype=jax.numpy.int32,
            ),
            mesh,
        )
        step = make_sp_train_step(model, tx, mesh, cfg)
        return step, (state, _token_batch(4))

    low_a, low_b, args = lower_twice(build_sp)
    bundles.append(dict(
        program="sp train step", lowered=low_a, lowered_b=low_b,
        args=args, donate=(0,),
    ))

    # pp (GPipe over data×pipe)
    def build_pp():
        from distributeddeeplearning_tpu.models.pipeline_lm import PipelineLM
        from distributeddeeplearning_tpu.training.pp_step import (
            create_pp_state,
            make_pp_train_step,
        )

        mesh = create_mesh(axes=("data", "pipe"), shape=(2, 4))
        cfg = _cfg(engine="pp", batch_size_per_device=2)
        model = PipelineLM(
            variant="tiny", vocab_size=VOCAB, max_seq_len=T,
            num_stages=4, n_layers=4, dtype=jax.numpy.float32,
        )
        tx = _tx()
        state = create_pp_state(model, cfg, tx, mesh, T)
        step = make_pp_train_step(
            model, tx, mesh, cfg, num_microbatches=2
        )
        return step, (state, _token_batch(4))

    low_a, low_b, args = lower_twice(build_pp)
    bundles.append(dict(
        program="pp train step", lowered=low_a, lowered_b=low_b,
        args=args, donate=(0,),
    ))
    return bundles


def _audit_slot_engine(findings: Dict[str, List[Finding]]) -> None:
    """Audit the SlotEngine's dense program set — the exact table
    :meth:`SlotEngine.warmup` compiles (``program_specs``). The donation
    check *executes* each program, consuming the donated pool, so the
    pool is rebuilt between programs."""
    import jax

    import flax.linen as nn

    _require_devices()
    from distributeddeeplearning_tpu.serving.engine import SlotEngine

    model = _lm()
    variables = model.init(
        jax.random.PRNGKey(0),
        jax.numpy.zeros((2, T), jax.numpy.int32),
        train=False,
    )
    params = nn.unbox(variables["params"])
    eng = SlotEngine(
        model, params, num_slots=2, max_len=T, buckets=(4, T)
    )
    n_programs = len(eng.program_specs())
    for i in range(n_programs):
        # Fresh pool per program: the previous donation check deleted it.
        eng._pool = None
        eng._draft_pool = None
        spec = eng.program_specs()[i]
        program = f"SlotEngine {spec.name}"
        jitted = jax.jit(spec.fn, donate_argnums=spec.donate_argnums)
        low_a = jitted.lower(*spec.example_args)
        low_b = jitted.lower(*spec.example_args)
        findings["hlo-cache-key"].extend(check_cache_key(
            low_a.as_text(), low_b.as_text(), program, _ANALYSIS_PATH,
        ))
        # example_args[1] is the engine's device-resident pool (what a
        # real tick donates), so the execution check sees true deletion.
        findings["hlo-donation"].extend(check_donation(
            low_a.compile(), spec.example_args, spec.donate_argnums,
            program, _ANALYSIS_PATH,
        ))


def _audit_fused_decode(findings: Dict[str, List[Finding]]) -> None:
    """Lower the fused decode program next to its stitched XLA twin
    (paged + int8 — the config whose dequant buffer is detectable) and
    run the fused invariants + cache-key stability on it."""
    import jax

    import flax.linen as nn

    _require_devices()
    from distributeddeeplearning_tpu.serving.engine import SlotEngine

    model = _lm()
    variables = model.init(
        jax.random.PRNGKey(0),
        jax.numpy.zeros((2, T), jax.numpy.int32),
        train=False,
    )
    params = nn.unbox(variables["params"])
    texts: Dict[str, str] = {}
    for kern in ("fused", "xla"):
        eng = SlotEngine(
            model, params, num_slots=2, max_len=T, buckets=(4, T),
            kv_layout="paged", block_size=4, kv_dtype="int8",
            decode_kernel=kern,
        )
        spec = next(s for s in eng.program_specs() if s.name == "decode")
        jitted = jax.jit(spec.fn, donate_argnums=spec.donate_argnums)
        low_a = jitted.lower(*spec.example_args)
        if kern == "fused":
            low_b = jitted.lower(*spec.example_args)
            findings["hlo-cache-key"].extend(check_cache_key(
                low_a.as_text(), low_b.as_text(),
                "SlotEngine decode (fused)", _ANALYSIS_PATH,
            ))
        texts[kern] = low_a.compile().as_text()
    # Full pool worth of elements: num_slots * max_len * hidden
    # (H * Dh = hidden; tiny variant hidden = 128).
    min_elems = 2 * T * 128
    findings["hlo-fused-decode"].extend(check_fused_decode(
        texts["fused"], texts["xla"], min_elems,
        "SlotEngine decode (paged int8)", _ANALYSIS_PATH,
    ))


_CACHE: Dict[str, List[Finding]] = {}
_ANALYSIS_PATH = "distributeddeeplearning_tpu/analysis/hlo_audit.py"


def _run_all() -> Dict[str, List[Finding]]:
    """Build + lower + compile everything once; route findings by rule.

    One pass feeds all three rules (compiles dominate the runtime; the
    walks are string work), memoised per process."""
    if _CACHE:
        return _CACHE
    findings: Dict[str, List[Finding]] = {
        "hlo-donation": [], "hlo-collectives": [], "hlo-cache-key": [],
        "hlo-fused-decode": [], "hlo-async-collective": [],
    }
    texts: Dict[str, str] = {}
    for b in _train_step_bundles():
        program = b["program"]
        findings["hlo-cache-key"].extend(check_cache_key(
            b["lowered"].as_text(), b["lowered_b"].as_text(),
            program, _ANALYSIS_PATH,
        ))
        compiled = b["lowered"].compile()
        texts[program] = compiled.as_text()
        findings["hlo-donation"].extend(check_donation(
            compiled, b["args"], b["donate"], program, _ANALYSIS_PATH,
        ))
        twin = b.get("accum_twin_of")
        if twin is not None:
            findings["hlo-collectives"].extend(check_scan_collectives(
                texts[program], texts[twin["program"]], program,
                _ANALYSIS_PATH,
            ))
    # The overlap tag is a step-builder invariant of the sharded
    # engines whose gradient reduction the builders own (pjit GSPMD +
    # sp shard_map; dp's reduction lives in train_step/accum, pp's in
    # its pipeline loop — out of the ASYNC_COLLECTIVES contract).
    for program in ("pjit train step", "sp train step"):
        findings["hlo-async-collective"].extend(check_async_collectives(
            texts[program], program, _ANALYSIS_PATH,
        ))
    _audit_slot_engine(findings)
    _audit_fused_decode(findings)
    _CACHE.update(findings)
    return _CACHE


@register(
    "hlo-donation", "hlo",
    "donated buffers (train-step state, SlotEngine KV pool) are actually "
    "aliased in the compiled modules",
)
def run_hlo_donation() -> List[Finding]:
    return list(_run_all()["hlo-donation"])


@register(
    "hlo-collectives", "hlo",
    "the dp step carries its gradient all-reduce; the ACCUM_STEPS scan "
    "body carries none (collectives once per dispatch)",
)
def run_hlo_collectives() -> List[Finding]:
    return list(_run_all()["hlo-collectives"])


@register(
    "hlo-cache-key", "hlo",
    "the same config lowers to byte-identical HLO twice (persistent "
    "compilation cache stability)",
)
def run_hlo_cache_key() -> List[Finding]:
    return list(_run_all()["hlo-cache-key"])


@register(
    "hlo-fused-decode", "hlo",
    "the SERVE_DECODE_KERNEL=fused decode program reaches the Pallas "
    "kernel and materialises no full-sequence dequantized K/V buffer "
    "(detector calibrated against the stitched XLA twin)",
)
def run_hlo_fused_decode() -> List[Finding]:
    return list(_run_all()["hlo-fused-decode"])


@register(
    "hlo-async-collective", "hlo",
    "pjit/sp gradient all-reduces carry the overlap tag; any "
    "all-reduce-start pairs with a -done with compute between "
    "(training/overlap.py, ASYNC_COLLECTIVES)",
)
def run_hlo_async_collective() -> List[Finding]:
    return list(_run_all()["hlo-async-collective"])
