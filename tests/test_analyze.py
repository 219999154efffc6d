"""Static-analysis subsystem (repro.analyze): per-rule trigger fixtures,
clean-sweep gates, compile(verify=) wiring and the .tuning/ doctor.

Structure mirrors the acceptance contract: every cataloged rule id has a
fixture that triggers exactly that rule, and clean-sweep tests pin zero
error findings over the registered programs x config matrix and the real
source tree.
"""
import dataclasses
import json
import re
import textwrap
from pathlib import Path

import jax
import jax.numpy as jnp
import pytest

from repro import engine
from repro.engine import tune as tunelib
from repro.engine.config import EngineConfig
from repro.engine.parallel import ParallelConfig
from repro.engine.plan import OpSpec, ShardDecision, plan_op, with_precision
from repro.models import cnn

from repro.analyze import (AnalyzeError, AnalyzeWarning, catalog,
                           doctor_cache, lint_file, lint_tree,
                           verify_config, verify_program)
from repro.analyze import rules_ast, rules_plan, rules_shard, rules_tile
from repro.analyze.cli import CONFIG_MATRIX, main as cli_main, run_verify
from repro.analyze.diagnostics import Diagnostic, Report, finding, get_rule

ALL_RULE_IDS = {
    # plan
    "int8-silent-downgrade", "int8-unsupported-op", "epilogue-illegal-form",
    "tuning-key-batch-variant", "donation-hazard", "fallback-chain-unpinned",
    "program-capture-failed",
    # tile
    "tile-misaligned", "tile-vmem-overflow", "tile-precision-mismatch",
    "cache-malformed-entry", "cache-unreferenced-key",
    # shard
    "shard-indivisible", "shard-exact-breach", "shard-inexact-optin",
    # ast
    "raw-dense-bypass", "mutable-global", "fault-hook-unguarded",
    "kernel-nondeterminism",
}

DENSE = OpSpec(kind="dense", x_shape=(4, 256), w_shape=(256, 128),
               spec="mk,kn->mn")
GATHER = OpSpec(kind="gather", x_shape=(4, 16), w_shape=(1000, 64))


def rules_of(diags):
    return {d.rule for d in diags}


# ---------------------------------------------------------------------------
# diagnostics model
# ---------------------------------------------------------------------------

class TestDiagnosticsModel:
    def test_catalog_is_exactly_the_documented_rule_set(self):
        assert {r.id for r in catalog()} == ALL_RULE_IDS
        for r in catalog():
            assert r.severity in ("error", "warn", "info")
            assert r.layer in ("plan", "tile", "shard", "ast")
            assert r.contract          # every rule states its invariant

    def test_readme_rule_table_matches_catalog(self):
        readme = (Path(__file__).resolve().parents[1] / "README.md")
        rows = re.findall(r"^\| `([a-z0-9-]+)` \| (error|warn|info) \|",
                          readme.read_text(), re.M)
        assert dict(rows) == {r.id: r.severity for r in catalog()}

    def test_finding_inherits_catalog_severity(self):
        d = finding("shard-indivisible", "s", "m")
        assert d.severity == "error"
        assert finding("shard-indivisible", "s", "m",
                       severity="info").severity == "info"
        with pytest.raises(ValueError):
            Diagnostic(rule="x", severity="fatal", site="s", message="m")

    def test_report_gating_and_json(self):
        r = Report([finding("shard-indivisible", "a", "m"),
                    finding("cache-unreferenced-key", "b", "m")])
        assert not r.ok and len(r.errors) == 1
        blob = json.loads(r.to_json())
        assert blob["counts"] == {"error": 1, "warn": 0, "info": 1}
        assert blob["ok"] is False
        assert {d["rule"] for d in blob["diagnostics"]} == \
            {"shard-indivisible", "cache-unreferenced-key"}
        assert Report().ok

    def test_unknown_rule_id_is_an_error(self):
        with pytest.raises(KeyError):
            finding("no-such-rule", "s", "m")


# ---------------------------------------------------------------------------
# layer 1: shard rules
# ---------------------------------------------------------------------------

class TestShardRules:
    def test_shard_indivisible_triggers(self):
        from repro.engine import parallel as parlib
        pcfg = ParallelConfig(model=3, policy="shard_n")
        plan = parlib.attach(DENSE, plan_op(DENSE, "xla"), pcfg)
        diags = rules_shard.check_op_shard(DENSE, plan, pcfg, "s")
        assert rules_of(diags) == {"shard-indivisible"}   # 128 % 3 != 0

    def test_shard_exact_breach_triggers(self):
        pcfg = ParallelConfig(model=2, policy="auto", exact_only=True)
        plan = dataclasses.replace(
            plan_op(DENSE, "xla"),
            shard=ShardDecision(strategy="shard_k", ways=2))
        diags = rules_shard.check_op_shard(DENSE, plan, pcfg, "s")
        assert rules_of(diags) == {"shard-exact-breach"}

    def test_shard_inexact_optin_is_info_only(self):
        pcfg = ParallelConfig(model=2, policy="shard_k")
        from repro.engine import parallel as parlib
        plan = parlib.attach(DENSE, plan_op(DENSE, "xla"), pcfg)
        diags = rules_shard.check_op_shard(DENSE, plan, pcfg, "s")
        assert rules_of(diags) == {"shard-inexact-optin"}
        assert all(d.severity == "info" for d in diags)

    def test_divisible_forced_shard_is_clean(self):
        pcfg = ParallelConfig(model=2, policy="shard_n")
        from repro.engine import parallel as parlib
        plan = parlib.attach(DENSE, plan_op(DENSE, "xla"), pcfg)
        assert rules_shard.check_op_shard(DENSE, plan, pcfg, "s") == []


# ---------------------------------------------------------------------------
# layer 1: precision / epilogue / fallback rules
# ---------------------------------------------------------------------------

class TestPlanRules:
    def test_int8_silent_downgrade_triggers(self):
        cfg = EngineConfig(precision="int8")
        diags = rules_plan.check_op_precision(GATHER, cfg, "s")
        assert rules_of(diags) == {"int8-silent-downgrade"}

    def test_int8_unsupported_op_triggers_on_explicit(self):
        diags = rules_plan.check_op_precision(
            GATHER, EngineConfig(), "s", explicit="int8")
        assert rules_of(diags) == {"int8-unsupported-op"}

    def test_int8_supported_op_is_clean(self):
        cfg = EngineConfig(precision="int8")
        assert rules_plan.check_op_precision(DENSE, cfg, "s") == []
        assert rules_plan.check_op_precision(DENSE, cfg, "s",
                                             explicit="int8") == []

    def test_epilogue_illegal_form_triggers(self):
        # unknown activation
        diags = rules_plan.check_epilogue(DENSE, "s", act="swiglu2")
        assert rules_of(diags) == {"epilogue-illegal-form"}
        # trailing output label is x-side, bias ill-defined
        op = OpSpec(kind="dense", x_shape=(4, 256), w_shape=(256, 128),
                    spec="mk,kn->nm")
        diags = rules_plan.check_epilogue(op, "s", has_bias=True)
        assert rules_of(diags) == {"epilogue-illegal-form"}
        # bias length mismatch
        diags = rules_plan.check_epilogue(DENSE, "s", has_bias=True,
                                          bias_len=64)
        assert rules_of(diags) == {"epilogue-illegal-form"}
        # non-epilogue op kind
        diags = rules_plan.check_epilogue(GATHER, "s", has_bias=True)
        assert rules_of(diags) == {"epilogue-illegal-form"}

    def test_epilogue_legal_form_is_clean(self):
        assert rules_plan.check_epilogue(DENSE, "s", has_bias=True,
                                         bias_len=128, act="relu") == []

    def test_fallback_chain_unpinned_triggers(self):
        cfg = EngineConfig(backend="my-accel", fallback="chain")
        report = verify_config(cfg)
        assert rules_of(report) == {"fallback-chain-unpinned"}
        assert verify_config(EngineConfig(backend="pallas",
                                          fallback="chain")).ok


# ---------------------------------------------------------------------------
# layer 1: program-level rules (stub programs)
# ---------------------------------------------------------------------------

class _StubProgram:
    """Minimal duck-typed Program for program-level rules."""

    def __init__(self, name, ops=(), fn=None, in_avals=(), batch_size=None):
        self.name, self.ops, self.fn = name, tuple(ops), fn
        self.in_avals, self.batch_size = tuple(in_avals), batch_size


class _BatchVariantProgram(_StubProgram):
    """A deliberately broken program whose op shapes (and so tile keys)
    move with the batch size."""

    def __init__(self, batch=1):
        k = 256 + batch          # K leaks the batch -> key changes
        super().__init__(
            "stub_bv",
            ops=(OpSpec(kind="dense", x_shape=(batch, k),
                        w_shape=(k, 128), spec="mk,kn->mn"),),
            batch_size=batch)

    def with_batch(self, batch):
        return _BatchVariantProgram(batch)


class TestProgramRules:
    def test_tuning_key_batch_variant_triggers(self):
        report = verify_program(_BatchVariantProgram(), EngineConfig())
        assert "tuning-key-batch-variant" in rules_of(report)
        assert not report.ok

    def test_registered_programs_have_batch_invariant_keys(self):
        for name in sorted(cnn.CNNS):
            diags = rules_plan.check_batch_invariant_keys(
                cnn.program(name), EngineConfig())
            assert diags == []

    def test_donation_hazard_triggers(self):
        def f(x, w):
            return jnp.tanh(x @ w)

        prog = _StubProgram(
            "stub_don", fn=f,
            in_avals=(jax.ShapeDtypeStruct((4, 8), jnp.float32),
                      jax.ShapeDtypeStruct((8, 16), jnp.float32)))
        diags = rules_plan.check_donation(prog, (1,))   # w has no match
        assert rules_of(diags) == {"donation-hazard"}
        assert rules_plan.check_donation(prog, ()) == []
        # out-of-range index is a hazard too
        assert "donation-hazard" in rules_of(
            rules_plan.check_donation(prog, (7,)))

    def test_donation_of_threaded_state_is_clean(self):
        def step(state, x):
            return state + x.sum(), state * 0.0

        prog = _StubProgram(
            "stub_kv", fn=step,
            in_avals=(jax.ShapeDtypeStruct((32, 64), jnp.float32),
                      jax.ShapeDtypeStruct((32, 64), jnp.float32)))
        assert rules_plan.check_donation(prog, (0,)) == []

    def test_program_capture_failed_triggers(self):
        def broken():
            raise ValueError("boom")

        report = verify_program(_StubProgram("stub_bad", fn=broken))
        assert "program-capture-failed" in rules_of(report)
        assert not report.ok


# ---------------------------------------------------------------------------
# layer 1: tile / cache rules
# ---------------------------------------------------------------------------

@pytest.fixture
def tmp_cache(tmp_path):
    tunelib.set_cache_dir(tmp_path)
    try:
        yield tmp_path
    finally:
        tunelib.set_cache_dir(None)


def _write_cache(tmp_path, entries):
    path = tunelib.cache_path()
    path.write_text(json.dumps(
        {"version": tunelib.CACHE_VERSION,
         "device_kind": tunelib.device_kind(), "entries": entries}))
    tunelib._MEMO.clear()
    return path


class TestTileRules:
    def test_tile_misaligned_triggers(self):
        assert rules_of(rules_tile.check_dense_tile((12, 128, 128),
                                                    "fp32", "s")) == \
            {"tile-misaligned"}
        # fp32-aligned bm=8 is NOT int8-sublane-aligned (32 rows)
        assert rules_of(rules_tile.check_dense_tile((8, 128, 128),
                                                    "int8", "s")) == \
            {"tile-misaligned"}
        assert rules_tile.check_dense_tile((8, 128, 128), "fp32", "s") == []
        assert rules_tile.check_dense_tile((32, 128, 128), "int8", "s") == []

    def test_tile_vmem_overflow_triggers(self):
        diags = rules_tile.check_dense_tile((4096, 8192, 4096), "fp32", "s")
        assert rules_of(diags) == {"tile-vmem-overflow"}

    def test_vmem_formula_matches_candidate_generator(self):
        # the rule and the candidate generator share one footprint:
        # double-buffered blocks, (8|32, 128)-padded, plus accumulators
        assert tunelib.dense_vmem_bytes((8, 128, 128), "fp32") == \
            2 * 4 * (8 * 128 + 128 * 128 + 8 * 128) + 3 * 4 * 8 * 128
        assert tunelib.dense_vmem_bytes((32, 128, 128), "int8") == \
            (2 * (32 * 128 + 128 * 128 + 4 * 32 * 128 + 2 * 4 * 8 * 128)
             + 4 * 4 * 32 * 128 + 4 * (32 * 128 + 128 * 128))
        assert tunelib.dense_vmem_bytes((8, 128, 128), "fp32") \
            < tunelib.SCOPED_VMEM_BYTES
        # the compiler's boundary for fp32 (512, bk, 512) tiles lies
        # between bk=1536 (compiles) and bk=1792 (refused)
        assert tunelib.dense_vmem_bytes((512, 1536, 512), "fp32") \
            <= tunelib.SCOPED_VMEM_BYTES
        assert rules_of(rules_tile.check_dense_tile(
            (512, 1792, 512), "fp32", "s")) == {"tile-vmem-overflow"}
        op = OpSpec("dense", (64, 9216), (9216, 4096), spec="mk,kn->mn")
        for prec in ("fp32", "int8"):
            for tile in tunelib.candidates_for(op, limit=10 ** 6,
                                               precision=prec):
                assert rules_tile.check_dense_tile(tile, prec, "s") == []

    def test_tile_precision_mismatch_triggers(self, tmp_cache):
        op = DENSE
        key = tunelib.tile_key(op, "pallas", None, "fp32")
        _write_cache(tmp_cache, {key: {"kind": "dense", "precision": "int8",
                                       "tile": [8, 128, 128]}})
        cfg = EngineConfig(backend="pallas", tuning="cached")
        plan = with_precision(plan_op(op, "pallas"), op, "fp32")
        diags = rules_tile.check_op_tile(op, plan, cfg, "s")
        assert "tile-precision-mismatch" in rules_of(diags)

    def test_check_op_tile_audits_resolved_entry(self, tmp_cache):
        op = DENSE
        key = tunelib.tile_key(op, "pallas", None, "fp32")
        _write_cache(tmp_cache, {key: {"kind": "dense", "precision": "fp32",
                                       "tile": [12, 128, 128]}})
        cfg = EngineConfig(backend="pallas", tuning="cached")
        plan = with_precision(plan_op(op, "pallas"), op, "fp32")
        assert rules_of(rules_tile.check_op_tile(op, plan, cfg, "s")) == \
            {"tile-misaligned"}
        # tuning off: nothing resolves, nothing audited
        assert rules_tile.check_op_tile(
            op, plan, EngineConfig(backend="pallas"), "s") == []


class TestCacheDoctor:
    def test_cache_malformed_entry_triggers_and_repairs(self, tmp_cache):
        path = _write_cache(tmp_cache, {
            "deadbeef00000001": {"kind": "dense", "precision": "fp32",
                                 "tile": "nope"},
            "deadbeef00000002": {"kind": "dense", "precision": "fp32",
                                 "tile": [8, 128, 128], "desc": "good"},
        })
        diags, repaired = doctor_cache(path)
        assert "cache-malformed-entry" in rules_of(diags)
        assert repaired is None                      # report-only by default
        diags, repaired = doctor_cache(path, repair=True)
        assert set(repaired["entries"]) == {"deadbeef00000002"}

    def test_cache_unreferenced_key_is_info(self, tmp_cache):
        path = _write_cache(tmp_cache, {
            "deadbeef00000003": {"kind": "dense", "precision": "fp32",
                                 "tile": [8, 128, 128], "desc": "bench"}})
        diags, _ = doctor_cache(path, known_keys=set())
        assert rules_of(diags) == {"cache-unreferenced-key"}
        assert all(d.severity == "info" for d in diags)
        # a derivable key is not reported
        key = tunelib.tile_key(DENSE, "pallas", None, "fp32")
        path = _write_cache(tmp_cache, {
            key: {"kind": "dense", "precision": "fp32",
                  "tile": [8, 128, 128]}})
        diags, _ = doctor_cache(
            path, known_keys=rules_tile.derivable_keys([DENSE]))
        assert diags == []

    def test_stale_version_is_warn_not_error(self, tmp_cache):
        path = tunelib.cache_path()
        path.write_text(json.dumps({"version": 1, "entries": {}}))
        diags, _ = doctor_cache(path)
        assert rules_of(diags) == {"cache-malformed-entry"}
        assert all(d.severity == "warn" for d in diags)

    def test_committed_cache_is_healthy(self):
        repo = Path(__file__).resolve().parents[1]
        for path in sorted((repo / ".tuning").glob("*.json")):
            diags, _ = doctor_cache(path)
            assert [d for d in diags if d.severity == "error"] == []


# ---------------------------------------------------------------------------
# layer 2: AST rules (fixture files in a tmp package tree)
# ---------------------------------------------------------------------------

def _lint_snippet(tmp_path, rel, code):
    path = tmp_path / rel
    path.parent.mkdir(parents=True, exist_ok=True)
    path.write_text(textwrap.dedent(code))
    return lint_file(path, tmp_path)


class TestAstRules:
    def test_raw_dense_bypass_triggers(self, tmp_path):
        diags = _lint_snippet(tmp_path, "models/bad.py", """\
            import jax.numpy as jnp

            def f(x, w):
                y = jnp.einsum("ij,jk->ik", x, w)
                return y @ w
            """)
        assert rules_of(diags) == {"raw-dense-bypass"}
        assert len(diags) == 2                       # einsum + matmul

    def test_raw_dense_lax_conv_triggers(self, tmp_path):
        diags = _lint_snippet(tmp_path, "serve/bad.py", """\
            from jax import lax

            def f(x, w):
                return lax.conv_general_dilated(x, w, (1, 1), "SAME")
            """)
        assert rules_of(diags) == {"raw-dense-bypass"}

    def test_raw_dense_pragma_and_allowlists(self, tmp_path):
        clean = _lint_snippet(tmp_path, "models/ok.py", """\
            import jax.numpy as jnp

            def f(x, w):
                return jnp.einsum("ij,jk->ik", x, w)  # analyze: allow[raw-dense-bypass]
            """)
        assert clean == []
        # kernels/ implements the engine: exempt wholesale
        assert _lint_snippet(tmp_path, "kernels/impl.py", """\
            import jax.numpy as jnp

            def f(x, w):
                return jnp.dot(x, w)
            """) == []
        # allowlisted attention-family modules are exempt with a reason
        assert "models/flash.py" in rules_ast.RAW_DENSE_MODULE_ALLOW
        assert all(reason for reason in
                   rules_ast.RAW_DENSE_MODULE_ALLOW.values())

    def test_mutable_global_triggers(self, tmp_path):
        diags = _lint_snippet(tmp_path, "serve/state.py", """\
            _CACHE = {}
            _MODE = None

            def put(k, v):
                _CACHE[k] = v

            def set_mode(m):
                global _MODE
                _MODE = m
            """)
        assert rules_of(diags) == {"mutable-global"}
        assert len(diags) == 2

    def test_mutable_global_constants_and_pragmas_clean(self, tmp_path):
        assert _lint_snippet(tmp_path, "serve/tables.py", """\
            LOOKUP = {"a": 1, "b": 2}      # never mutated: a constant table
            _SLOT = []  # analyze: allow[mutable-global] sanctioned

            def use():
                _SLOT.append(1)
                return LOOKUP["a"]
            """) == []

    def test_fault_hook_unguarded_triggers(self, tmp_path):
        diags = _lint_snippet(tmp_path, "serve/hooks.py", """\
            from repro.serve import faults

            def chained():
                return faults.active().fire("x")

            def unguarded():
                inj = faults.active()
                return inj.fire("y")
            """)
        assert rules_of(diags) == {"fault-hook-unguarded"}
        assert len(diags) == 2

    def test_fault_hook_guarded_is_clean(self, tmp_path):
        assert _lint_snippet(tmp_path, "serve/hooks_ok.py", """\
            from repro.serve import faults

            def guarded(site):
                inj = faults.active()
                if inj is not None and inj.fire(site):
                    raise RuntimeError("injected")

            def early_out():
                inj = faults.active()
                if inj is None:
                    return False
                return inj.fire("z")
            """) == []

    def test_kernel_nondeterminism_triggers(self, tmp_path):
        diags = _lint_snippet(tmp_path, "kernels/k.py", """\
            import time
            import random

            def _scale_kernel(x_ref, o_ref):
                o_ref[...] = x_ref[...] * time.time()

            def _body(x_ref, o_ref):
                o_ref[...] = x_ref[...] + random.random()

            def run(pl, x):
                return pl.pallas_call(_body, out_shape=None)(x)
            """)
        assert rules_of(diags) == {"kernel-nondeterminism"}
        assert len(diags) == 2

    def test_kernel_determinism_allows_jax_random_and_hosts(self, tmp_path):
        assert _lint_snippet(tmp_path, "kernels/ok.py", """\
            import time
            import jax

            def _noise_kernel(key_ref, o_ref):
                o_ref[...] = jax.random.normal(key_ref[...], (8,))

            def host_timer():
                return time.time()       # not a kernel body: fine
            """) == []


# ---------------------------------------------------------------------------
# clean sweeps (the CI gates)
# ---------------------------------------------------------------------------

class TestCleanSweeps:
    def test_source_tree_lints_clean(self):
        report = lint_tree()
        assert report.ok, report.render()
        assert len(report) == 0, report.render()

    def test_registered_programs_verify_clean_across_matrix(self):
        report = run_verify()
        assert [d for d in report if d.severity == "error"] == [], \
            report.render()

    def test_config_matrix_spans_the_planning_axes(self):
        names = [n for n, _ in CONFIG_MATRIX]
        cfgs = [c for _, c in CONFIG_MATRIX]
        assert len(set(names)) == len(names) >= 8
        assert any(c.precision == "int8" for c in cfgs)
        assert any(c.tuning == "cached" for c in cfgs)
        assert any(c.fallback == "chain" for c in cfgs)
        assert any(c.parallel is not None and c.parallel.model > 1
                   for c in cfgs)


# ---------------------------------------------------------------------------
# engine.compile(verify=...) wiring
# ---------------------------------------------------------------------------

class TestCompileVerify:
    def test_error_mode_rejects_seeded_shard_violation(self):
        prog = cnn.program("alexnet")
        bad = EngineConfig(parallel=ParallelConfig(model=3,
                                                   policy="shard_n"))
        with pytest.raises(AnalyzeError) as ei:
            engine.compile(prog, bad, verify="error")
        assert "shard-indivisible" in str(ei.value)
        assert not ei.value.report.ok

    def test_error_mode_passes_clean_program(self):
        net = engine.compile(cnn.program("alexnet"), EngineConfig(),
                             verify="error")
        assert net is not None

    def test_warn_mode_warns_and_still_compiles(self):
        prog = cnn.program("alexnet")
        with pytest.warns(AnalyzeWarning, match="donation-hazard"):
            net = engine.compile(prog, EngineConfig(),
                                 donate_argnums=(1,), verify="warn")
        assert net is not None

    def test_off_is_the_default_and_silent(self, recwarn):
        engine.compile(cnn.program("alexnet"), EngineConfig(),
                       donate_argnums=(1,))
        assert [w for w in recwarn.list
                if issubclass(w.category, AnalyzeWarning)] == []

    def test_bad_verify_value_rejected(self):
        with pytest.raises(ValueError, match="verify"):
            engine.compile(cnn.program("alexnet"), EngineConfig(),
                           verify="loud")


# ---------------------------------------------------------------------------
# CLI
# ---------------------------------------------------------------------------

class TestCli:
    def test_rules_listing(self, capsys):
        assert cli_main(["--rules"]) == 0
        out = capsys.readouterr().out
        for rule_id in ALL_RULE_IDS:
            assert rule_id in out

    def test_ast_only_sweep_exits_zero(self, capsys, tmp_path):
        artifact = tmp_path / "report.json"
        assert cli_main(["--ast-only", "--json", str(artifact)]) == 0
        blob = json.loads(artifact.read_text())
        assert blob["ok"] is True and blob["counts"]["error"] == 0

    def test_tuning_doctor_exits_zero_on_committed_cache(self, capsys):
        assert cli_main(["--tuning"]) == 0

    def test_verify_only_single_program(self, capsys):
        assert cli_main(["--verify-only", "--programs", "alexnet"]) == 0
