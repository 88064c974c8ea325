"""ddlint oracles (distributeddeeplearning_tpu/analysis/ — docs/ANALYSIS.md).

Two claims, both pinned here:

1. **Each rule flags its fixture** — a known sync leak, a tracer-bool
   leak, a missing donation, a collective inside a scan body, an
   undocumented env read, an unregistered gauge, a protocol knob the
   scrub list misses. A rule that can't catch its own planted violation
   is decoration.
2. **Self-hosting** — the fast families (AST + contracts) run on the
   real package and return ZERO unsuppressed findings, so `make lint`
   stays green at HEAD and a regression is attributable to the change
   that introduced it. (The HLO family self-hosts through `make lint` /
   `make check`; its fixtures here use 1-device programs.)
"""

import textwrap

import numpy as np
import pytest

from distributeddeeplearning_tpu.analysis import (
    Finding,
    apply_suppressions,
    package_sources,
    parse_suppressions,
)
from distributeddeeplearning_tpu.analysis import contracts
from distributeddeeplearning_tpu.analysis import hlo_audit
from distributeddeeplearning_tpu.analysis.ast_sync import (
    HOT_PATHS,
    lint_source,
)


def _lint(src: str):
    return lint_source(textwrap.dedent(src), "fixture.py")


# -- AST family: host-sync ------------------------------------------------


def test_float_on_traced_value_flagged():
    findings = _lint("""
        import jax.numpy as jnp

        def step(batch):
            loss = jnp.mean(batch)
            return float(loss)  # the classic leak
    """)
    assert [f.rule for f in findings] == ["host-sync"]
    assert findings[0].line == 6


def test_item_and_np_asarray_on_traced_flagged():
    findings = _lint("""
        import jax.numpy as jnp
        import numpy as np

        def step(x):
            y = jnp.sum(x)
            a = y.item()
            b = np.asarray(y * 2)
            return a, b
    """)
    assert [f.rule for f in findings] == ["host-sync", "host-sync"]


def test_raw_device_get_and_block_until_ready_flagged():
    findings = _lint("""
        import jax

        def epoch_end(metrics, x):
            host = jax.device_get(metrics)
            x.block_until_ready()
            return host
    """)
    assert sorted(f.rule for f in findings) == ["host-sync", "host-sync"]


def test_tracer_bool_fixture_flagged():
    findings = _lint("""
        import jax.numpy as jnp

        def guard(x):
            mask = jnp.isfinite(x)
            if jnp.any(mask):
                return x
            while mask:
                pass
    """)
    assert [f.rule for f in findings] == ["tracer-bool", "tracer-bool"]


def test_hostsync_allowlist_and_metadata_not_flagged():
    findings = _lint("""
        import jax.numpy as jnp
        from distributeddeeplearning_tpu.utils import hostsync

        def epoch_end(acc, cfg):
            dev = jnp.mean(acc)
            host = hostsync.device_get(dev, label="epoch")  # accounted
            v = float(host)                  # host value: fine
            n = int(dev.shape[0])            # metadata: fine
            k = float(cfg.label_smoothing)   # config float: fine
            if jnp.ndim(dev) == 0:           # jnp.ndim is host: fine
                return v, n, k
    """)
    assert findings == []


def test_jax_tree_leaves_truthiness_not_flagged():
    findings = _lint("""
        import jax

        def place(params):
            leaves = jax.tree.leaves(params)
            if leaves and len(leaves) > 2:
                return leaves
    """)
    assert findings == []


# -- suppressions ---------------------------------------------------------


def test_suppression_marks_and_counts():
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def step(batch):
            loss = jnp.mean(batch)
            return float(loss)  # ddlint: ok(host-sync): boundary sync, measured
    """)
    findings = lint_source(src, "fix.py")
    assert len(findings) == 1
    out = apply_suppressions(findings, {"fix.py": src})
    assert out[0].suppressed and "measured" in out[0].reason


def test_suppression_binds_to_wrapped_statement_tail():
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def step(batch):
            loss = jnp.mean(batch)
            return float(
                loss
            )  # ddlint: ok(host-sync): tail-of-statement marker
    """)
    out = apply_suppressions(lint_source(src, "fix.py"), {"fix.py": src})
    assert [f.suppressed for f in out] == [True]


def test_reasonless_suppression_is_a_finding():
    src = "x = 1  # ddlint: ok(host-sync)\n"
    by_line, malformed = parse_suppressions(src)
    assert by_line == {} and len(malformed) == 1
    out = apply_suppressions([], {"fix.py": src})
    assert [f.rule for f in out] == ["bad-suppression"]


def test_wrong_rule_suppression_does_not_apply():
    src = textwrap.dedent("""
        import jax.numpy as jnp

        def step(batch):
            loss = jnp.mean(batch)
            return float(loss)  # ddlint: ok(tracer-bool): wrong rule named
    """)
    out = apply_suppressions(lint_source(src, "fix.py"), {"fix.py": src})
    assert [f.suppressed for f in out] == [False]


# -- contracts: env-docs --------------------------------------------------


def test_env_reads_extraction_covers_all_idioms():
    src = textwrap.dedent("""
        import os

        def from_env(env=None):
            e = os.environ if env is None else env
            a = os.environ.get("VAR_A", "1")
            b = os.getenv("VAR_B")
            c = os.environ["VAR_C"]
            d = e.get("VAR_D")
            if "VAR_E" in e:
                pass
            return a, b, c, d
    """)
    names = {v for v, _ in contracts.env_reads(src)}
    assert names == {"VAR_A", "VAR_B", "VAR_C", "VAR_D", "VAR_E"}


def test_undocumented_env_read_fixture():
    documented = contracts.documented_env_vars()
    assert "OBS_DIR" in documented  # the real contract is in the docs
    assert "DDL_TOTALLY_UNDOCUMENTED_KNOB" not in documented


def test_env_docs_self_hosting():
    open_findings = [f for f in contracts.run_env_docs() if not f.suppressed]
    out = apply_suppressions(open_findings, package_sources())
    assert [f.format() for f in out if not f.suppressed] == []


# -- contracts: env-free-tiers --------------------------------------------


def test_env_touches_fixture():
    src = textwrap.dedent("""
        import os
        from os import getenv

        def dense(features):
            if os.environ.get("SOME_KNOB", "") == "1":
                return 1
            e = os.environ
            return os.getenv("OTHER") or os.path.join("a", "b")
    """)
    assert contracts.env_touches(src) == [3, 6, 8, 9]
    assert contracts.env_touches("import os\np = os.path.sep\n") == []


def test_env_free_tiers_self_hosting():
    """``models/`` and ``ops/`` read no environment (until PR 29
    ``models/vit._dense`` read one at trace time for every Dense)."""
    assert [f.format() for f in contracts.run_env_free_tiers()] == []


# -- contracts: obs-registry ----------------------------------------------


def test_obs_emit_extraction_and_fstring_prefix():
    src = textwrap.dedent("""
        from distributeddeeplearning_tpu import obs

        def report(k, v, bus):
            obs.gauge("serve.not_a_registered_gauge", v)
            obs.counter("host_sync", 1)
            bus.gauge(f"epoch.{k}", v)
    """)
    emits = contracts.obs_emits(src)
    assert ("serve.not_a_registered_gauge", False, "gauge", 5) in emits
    assert ("epoch.", True, "gauge", 7) in emits
    registry = contracts.registered_event_names()
    assert contracts._name_registered("host_sync", False, registry)
    assert contracts._name_registered("epoch.", True, registry)
    assert not contracts._name_registered(
        "serve.not_a_registered_gauge", False, registry
    )


def test_obs_registry_self_hosting():
    out = apply_suppressions(
        contracts.run_obs_registry(), package_sources()
    )
    assert [f.format() for f in out if not f.suppressed] == []


# -- contracts: protocol-vars ---------------------------------------------


def test_recertify_tables_parse():
    scrub, rows, _ = contracts._recertify_tables()
    assert "BENCH_MODEL" in scrub and "SERVE_ADMISSION_POLICY" in scrub
    assert "resnet50" in rows and "serve_lm_chaos" in rows
    # every row's own keys are scrubbed (the in-AST half of the rule)
    for proto, keys in rows.items():
        assert keys <= scrub, (proto, keys - scrub)


def test_protocol_vars_fixture_missing_knob():
    # a SERVE_* knob nowhere in the scrub list must be caught by the
    # env-read half of the rule (simulated against the parsed tables)
    scrub, _, _ = contracts._recertify_tables()
    assert "SERVE_NOT_A_REAL_KNOB" not in scrub
    src = 'import os\nx = os.environ.get("SERVE_NOT_A_REAL_KNOB")\n'
    reads = contracts.env_reads(src)
    assert reads == [("SERVE_NOT_A_REAL_KNOB", 2)]


def test_protocol_vars_self_hosting_without_suppressions():
    out = apply_suppressions(
        contracts.run_protocol_vars(), package_sources()
    )
    assert [f.format() for f in out if not f.suppressed] == []
    # the only knobs ever suppressed here were the bench's device-init
    # retry policy; with it gone the rule holds with nothing waved through
    assert [f.format() for f in out if f.suppressed] == []


# -- HLO family fixtures (1-device / test-mesh programs) -------------------


def test_donation_fixture_missing_vs_delivered():
    import jax
    import jax.numpy as jnp

    def bump(state, x):
        return {"w": state["w"] + x}

    def fresh():
        return {"w": jax.device_put(jnp.zeros((64, 64), jnp.float32))}

    x = np.float32(1.0)
    state = fresh()
    donated = jax.jit(bump, donate_argnums=(0,)).lower(state, x).compile()
    assert hlo_audit.check_donation(
        donated, (state, x), (0,), "fixture donated", "fix.py"
    ) == []

    state2 = fresh()
    undonated = jax.jit(bump).lower(state2, x).compile()
    findings = hlo_audit.check_donation(
        undonated, (state2, x), (0,), "fixture undonated", "fix.py"
    )
    assert [f.rule for f in findings] == ["hlo-donation"]
    assert "fixture undonated" in findings[0].message


def test_scan_collective_placement_fixture(mesh8):
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import PartitionSpec as P

    def inside(state, batch):  # the violation: pmean per microbatch
        def body(carry, mb):
            g = lax.pmean(jnp.sum(mb * state["w"]), "data")
            return carry + g, g

        tot, _ = lax.scan(body, jnp.float32(0), batch.reshape(2, -1))
        return {"w": state["w"] - tot}

    def outside(state, batch):  # the design: accumulate, reduce once
        def body(carry, mb):
            return carry + jnp.sum(mb * state["w"]), mb

        # the per-shard partial sums vary over `data`, so the carry must
        # enter the scan varying too (as training/accum.py casts its own)
        zero = lax.pcast(jnp.float32(0), ("data",), to="varying")
        tot, _ = lax.scan(body, zero, batch.reshape(2, -1))
        return {"w": state["w"] - lax.pmean(tot, "data")}

    def compile_(fn):
        sh = jax.shard_map(
            fn, mesh=mesh8, in_specs=(P(), P("data")), out_specs=P()
        )
        return (
            jax.jit(sh)
            .lower({"w": jnp.ones(())}, jnp.ones((8, 4)))
            .compile()
            .as_text()
        )

    good, bad = compile_(outside), compile_(inside)
    assert hlo_audit.check_scan_collectives(
        good, good, "fixture", "fix.py"
    ) == []
    findings = hlo_audit.check_scan_collectives(
        bad, good, "fixture", "fix.py"
    )
    assert findings and any(
        "INSIDE" in f.message for f in findings
    ), [f.message for f in findings]


def test_cache_key_fixture():
    assert hlo_audit.check_cache_key("same", "same", "p", "f.py") == []
    findings = hlo_audit.check_cache_key(
        "line_a\nline_b", "line_a\nline_X", "p", "f.py"
    )
    assert [f.rule for f in findings] == ["hlo-cache-key"]
    assert "line_b" in findings[0].message


def test_hlo_text_walkers_on_synthetic_module():
    text = textwrap.dedent("""\
    HloModule jit_f, is_scheduled=true

    %scan_body.1 (p: (f32[], f32[4])) -> (f32[], f32[4]) {
      %ar.1 = f32[] all-reduce(f32[] %x), replica_groups={}, to_apply=%sum.2
      ROOT %t = (f32[], f32[4]) tuple(%ar.1, %y)
    }

    %sum.2 (a: f32[], b: f32[]) -> f32[] {
      ROOT %add = f32[] add(f32[] %a, f32[] %b)
    }

    ENTRY %main.9 (arg: f32[4]) -> f32[4] {
      %w = (f32[], f32[4]) while((f32[], f32[4]) %init), condition=%cond.3, body=%scan_body.1
      ROOT %out = f32[4] get-tuple-element((f32[], f32[4]) %w), index=1
    }
    """)
    comps = hlo_audit.hlo_computations(text)
    assert set(comps) == {"scan_body.1", "sum.2", "main.9"}
    assert hlo_audit.while_body_closure(text) == {"scan_body.1", "sum.2"}
    assert hlo_audit.allreduce_sites(text) == [
        ("scan_body.1",
         "%ar.1 = f32[] all-reduce(f32[] %x), replica_groups={}, "
         "to_apply=%sum.2"),
    ]
    # XLA's combiner merges gradient leaves into ONE tuple-typed
    # all-reduce; its uses (get-tuple-element of %all-reduce.1) and
    # op_name metadata must not count as sites
    combined = (
        "%all-reduce.1 = (f32[128]{0}, /*index=1*/f32[128,384]{1,0}) "
        "all-reduce(%a, %b), channel_id=1, to_apply=%region_1.0, "
        'metadata={op_name="jit(step)/overlap_allreduce/psum"}'
    )
    text2 = (
        "ENTRY %main.1 (p: f32[128]) -> f32[128] {\n"
        f"  {combined}\n"
        "  ROOT %g = f32[128]{0} get-tuple-element(%all-reduce.1), index=0\n"
        "}\n"
    )
    assert hlo_audit.allreduce_sites(text2) == [("main.1", combined)]


# -- SlotEngine program-set table (the warmup/lint shared surface) ---------


def test_program_specs_match_programs_expected():
    import jax
    import jax.numpy as jnp

    import flax.linen as nn

    from distributeddeeplearning_tpu.models.transformer_lm import (
        TransformerLM,
    )
    from distributeddeeplearning_tpu.serving.engine import SlotEngine

    model = TransformerLM(
        variant="tiny", vocab_size=32, max_seq_len=8, dtype=jnp.float32
    )
    params = nn.unbox(model.init(
        jax.random.PRNGKey(0), jnp.zeros((2, 8), jnp.int32), train=False
    )["params"])
    for kwargs in (
        {},  # dense
        {"spec_k": 2, "spec_draft": "ngram"},  # + batched verify
    ):
        eng = SlotEngine(
            model, params, num_slots=2, max_len=8, buckets=(4, 8),
            **kwargs,
        )
        specs = eng.program_specs()
        names = [s.name for s in specs]
        assert len(names) == len(set(names))
        assert len(specs) == eng.programs_expected, (names, kwargs)
        assert names[0] == "decode"
        assert {"prefill_b4", "prefill_b8"} <= set(names)
        if kwargs.get("spec_k"):
            assert "spec_verify" in names
        # nothing is compiled by listing the table
        assert eng.compile_count == 0 and not specs[0].installed


# -- AST hot-path list stays anchored to real files ------------------------


def test_hot_paths_exist():
    import os

    from distributeddeeplearning_tpu.analysis import PACKAGE_ROOT

    for rel in HOT_PATHS:
        assert os.path.isfile(os.path.join(PACKAGE_ROOT, rel)), rel


def test_ast_rules_self_hosting():
    from distributeddeeplearning_tpu.analysis.ast_sync import (
        run_host_sync,
        run_tracer_bool,
    )

    out = apply_suppressions(
        run_host_sync() + run_tracer_bool(), package_sources()
    )
    assert [f.format() for f in out if not f.suppressed] == []
