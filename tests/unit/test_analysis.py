"""Shard-lint auditor tests (ISSUE 10, docs/analysis.md).

The injected-defect matrix: every rule class is proven by a defect that
makes it fire (strip a sharding constraint, drop a donation, force an
fp32 leak, add a host callback, unbound the jit key space, read after
donation) AND by the clean engine configs staying silent. Plus: the
report/suppression schema (pinned equal to bin/check_bench_schema.py's
stdlib copy), the repo AST linter (each DSL rule + the tier-1 self-run
against the committed baseline), and the HLO census ground-truthing the
wire estimator.
"""
import dataclasses
import importlib.util
import json
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from deepspeed_tpu.analysis import (AnalysisReport, AuditFindingsError,
                                    Finding, ProgramSpec, Suppressions,
                                    audit_program,
                                    recompile_storm_finding,
                                    replicated_leaf_finding,
                                    validate_analysis_report)
from deepspeed_tpu.analysis import astlint
from deepspeed_tpu.analysis import programs as collectors
from deepspeed_tpu.analysis.auditor import audit_programs
from deepspeed_tpu.analysis.findings import (ANALYSIS_REPORT_KEYS,
                                             FINDING_KEYS, SEVERITIES)
from deepspeed_tpu.analysis.rules import sequence_findings
from deepspeed_tpu.models import gpt2

pytestmark = pytest.mark.analysis

REPO = os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__))))


def _tiny_cfg():
    return gpt2.GPT2Config(vocab_size=256, max_seq_len=64, n_layers=2,
                           n_heads=2, d_model=64,
                           use_flash_attention=False, remat=False,
                           loss_chunk=0)


def _make_engine(extra=None, zero=None):
    cp = {"train_micro_batch_size_per_gpu": 2,
          "gradient_accumulation_steps": 1,
          "bf16": {"enabled": True},
          "zero_optimization": dict({"stage": 2}, **(zero or {})),
          "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
          "steps_per_print": 10 ** 9}
    cp.update(extra or {})
    engine, _, _, _ = deepspeed.initialize(
        model=gpt2.make_gpt2_model(config=_tiny_cfg()), config_params=cp)
    return engine


def _batch():
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, size=(16, 64)).astype(np.int32)
    return (ids, ids.copy())


# --------------------------------------------------------- shared core
def test_shared_rule_core_thresholds():
    assert replicated_leaf_finding("p", "x", 100, 8, threshold=101) is None
    assert replicated_leaf_finding("p", "x", 100, 1, threshold=10) is None
    f = replicated_leaf_finding("p", "arg0", 1 << 20, 8, threshold=1024)
    assert f is not None and f.check == "replicated_leaf"
    assert "REPLICATED" in f.message and "8x" in f.message
    assert recompile_storm_finding("fam", 3, threshold=3) is None
    f = recompile_storm_finding("fam", 4, threshold=3)
    assert f is not None and f.key == "recompile_storm:fam"


def test_runtime_observatory_shares_rule_core():
    """telemetry/programs.py imports the rule implementations (and the
    default thresholds) from analysis/rules.py — one implementation,
    one threshold config, no drift."""
    from deepspeed_tpu.telemetry import programs as tele_programs
    from deepspeed_tpu.analysis import rules
    assert tele_programs.RECOMPILE_STORM_THRESHOLD_DEFAULT is \
        rules.RECOMPILE_STORM_THRESHOLD_DEFAULT
    assert tele_programs.REPLICATED_LEAF_BYTES_DEFAULT is \
        rules.REPLICATED_LEAF_BYTES_DEFAULT
    assert tele_programs.recompile_storm_finding is \
        rules.recompile_storm_finding
    assert tele_programs.replicated_leaf_finding is \
        rules.replicated_leaf_finding
    # and the shared threshold config feeds BOTH paths
    engine = _make_engine({"telemetry": {
        "enabled": False, "programs": {"recompile_storm_threshold": 7,
                                       "replicated_leaf_bytes": 4096}}})
    acfg = engine._config.analysis_config
    assert acfg.storm_threshold == 7
    assert acfg.replicated_leaf_bytes == 4096


# ------------------------------------------------------- clean configs
@pytest.mark.parametrize("stage", [0, 1, 2, 3])
def test_clean_stages_are_silent(stage):
    engine = _make_engine(zero={"stage": stage})
    report = engine.audit(batch=_batch())
    assert report.findings == [], [f.key for f in report.findings]
    assert set(report.programs) == {"micro", "apply", "fused_train"}


def test_clean_offload_family():
    engine = _make_engine(zero={"stage": 2, "cpu_offload": True})
    report = engine.audit(batch=_batch())
    assert report.findings == [], [f.key for f in report.findings]
    # ISSUE 13: the audit also validates the lowered executor plan and
    # records its shape as plan/<name> alongside the program families
    assert set(report.programs) == {"micro", "fused_micros",
                                    "offload_check",
                                    "plan/offload_apply"}
    assert all(m["family"] == "offload"
               for name, m in report.programs.items()
               if not name.startswith("plan/"))
    assert report.programs["plan/offload_apply"]["family"] == "plan"
    assert report.programs["plan/offload_apply"]["plan_segments"] > 2


def test_clean_streamed_family():
    engine = _make_engine(zero={
        "stage": 3, "cpu_offload": True, "cpu_offload_params": True,
        "stage3_max_live_parameters": 120000})
    report = engine.audit(batch=_batch())
    assert report.findings == [], [f.key for f in report.findings]
    assert set(report.programs) == {
        "stream/e_fwd", "stream/g_fwd", "stream/h_grad", "stream/g_bwd",
        "stream/e_bwd", "plan/streamed_micro"}
    # the audited donation sets ARE the executed ones (one declaration)
    from deepspeed_tpu.runtime.zero.stream import STREAM_DONATE
    assert report.programs["stream/g_bwd"]["donate_argnums"] == \
        list(STREAM_DONATE["g_bwd"]) == [2]
    assert report.programs["stream/h_grad"]["donate_argnums"] == \
        list(STREAM_DONATE["h_grad"]) == [1]


def test_clean_inference_family():
    engine = deepspeed.init_inference(
        model=gpt2.make_gpt2_model(config=_tiny_cfg()),
        config={"inference": {"max_batch_size": 2,
                              "prefill_buckets": [8, 16],
                              "dtype": "fp32", "greedy": True}},
        audit=False)
    report = engine.audit()
    assert report.findings == [], [f.key for f in report.findings]
    # plan/serving_step: the lowered scheduler-step plan is audited
    # alongside the jit programs (docs/executor.md)
    assert set(report.programs) == {"prefill/b8", "prefill/b16",
                                    "decode", "plan/serving_step"}


def test_inference_spec_verify_program_audited():
    model = gpt2.make_gpt2_model(config=_tiny_cfg())
    engine = deepspeed.init_inference(
        model=model, draft_model=model,
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [8],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": 4,
            "speculative": {"enabled": True, "method": "model",
                            "num_draft_tokens": 2}}})
    report = engine.audit()
    assert report.findings == [], [f.key for f in report.findings]
    assert "spec_verify" in report.programs
    assert "decode" in report.programs


def test_init_inference_audit_flag_runs_audit():
    engine = deepspeed.init_inference(
        model=gpt2.make_gpt2_model(config=_tiny_cfg()),
        config={"inference": {"max_batch_size": 2,
                              "prefill_buckets": [8],
                              "dtype": "fp32", "greedy": True}},
        audit=True)
    assert engine is not None    # findings would have warned, not raised


def test_clean_pipeline_family():
    from deepspeed_tpu.models import gpt2_pipe
    net = gpt2_pipe.make_gpt2_pipeline(
        config=_tiny_cfg(), num_stages=2, num_dp=4, num_mp=1,
        activation_checkpoint_interval=0)
    engine, _, _, _ = deepspeed.initialize(
        model=net, config_params={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 2,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 1},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "steps_per_print": 10 ** 9})
    rng = np.random.RandomState(0)
    # one MICRO batch (global batch x seq); the collector derives the
    # (micro_batches, ...) stack the pipe loop consumes
    ids = rng.randint(0, 256, size=(8, 64)).astype(np.int32)
    report = engine.audit(batch=(ids, ids.copy()))
    # plan/pipe_step: the lowered 1F1B step plan is audited alongside
    # the jit program (docs/executor.md)
    assert set(report.programs) == {"pipe_train", "plan/pipe_step"}
    assert report.programs["pipe_train"]["family"] == "pipeline"
    assert report.findings == [], [f.key for f in report.findings]


# ---------------------------------------------------- injected defects
def test_defect_stripped_sharding_constraint_fires():
    engine = _make_engine()
    orig = engine.zero_plan.constrain
    engine.zero_plan.constrain = lambda tree, kind: tree
    try:
        report = engine.audit(batch=_batch())
    finally:
        engine.zero_plan.constrain = orig
    checks = {f.check for f in report.findings}
    assert "missing_sharding_constraint" in checks, checks


def test_defect_dropped_donation_fires():
    engine = _make_engine({"analysis": {"donation_min_bytes": 1024}})
    specs = collectors.collect_train_programs(engine, batch=_batch())
    micro = next(s for s in specs if s.name == "micro")
    bad = dataclasses.replace(micro, donate=())
    _, _, findings = audit_program(bad, engine._config.analysis_config)
    assert any(f.check == "donation_miss" for f in findings), \
        [f.key for f in findings]
    # and the engine's REAL donation set keeps the same program silent
    _, _, clean = audit_program(micro, engine._config.analysis_config)
    assert not any(f.check == "donation_miss" for f in clean)


def test_defect_unhonorable_donation_fires():
    engine = _make_engine({"analysis": {"donation_min_bytes": 1024}})
    specs = collectors.collect_train_programs(engine, batch=_batch())
    micro = next(s for s in specs if s.name == "micro")
    bad = dataclasses.replace(micro, donate=(0, 1))
    _, _, findings = audit_program(bad, engine._config.analysis_config)
    assert any(f.check == "donation_unhonored" for f in findings), \
        [f.key for f in findings]


def test_defect_read_after_donation_fires():
    seq = [{"program": "a", "reads": ("state",), "donates": ("state",)},
           {"program": "b", "reads": ("state",)}]
    findings = sequence_findings(seq)
    assert [f.check for f in findings] == ["read_after_donation"]
    assert findings[0].severity == "error"
    # a rebind between donation and read keeps the sequence clean
    seq = [{"program": "a", "reads": ("state",), "donates": ("state",),
            "produces": ("state",)},
           {"program": "b", "reads": ("state",)}]
    assert sequence_findings(seq) == []


def test_defect_fp32_leak_fires():
    engine = _make_engine()
    specs = collectors.collect_train_programs(engine, batch=_batch())
    micro = next(s for s in specs if s.name == "micro")
    orig_build = micro.build

    def bad_build():
        fn = orig_build()

        def wrapped(state, batch, rng, pld_theta=None):
            state = dict(state)
            # the classic leak: weights upcast to fp32 before the GEMMs
            state["params"] = jax.tree_util.tree_map(
                lambda p: p.astype(jnp.float32), state["params"])
            return fn(state, batch, rng, pld_theta)

        return wrapped

    bad = dataclasses.replace(micro, build=bad_build)
    _, _, findings = audit_program(bad, engine._config.analysis_config)
    assert any(f.check == "fp32_gemm_from_bf16" for f in findings), \
        [f.key for f in findings]
    # the intentional fp32 stability island (attention scores/softmax
    # over ACTIVATIONS) does NOT fire on the clean program
    _, _, clean = audit_program(micro, engine._config.analysis_config)
    assert not any(f.check == "fp32_gemm_from_bf16" for f in clean)


def test_defect_host_callback_fires():
    engine = _make_engine()
    orig_fn = engine.model.apply_fn

    def cb_apply(params, x, y, **kw):
        out = orig_fn(params, x, y, **kw)
        jax.debug.print("loss {l}", l=out)
        return out

    engine.model.apply_fn = cb_apply
    report = engine.audit(batch=_batch())
    assert any(f.check == "host_callback" for f in report.findings), \
        [f.key for f in report.findings]


def test_defect_weak_typed_operand_fires():
    def fn(x, t):
        return x * t

    spec = ProgramSpec(name="w", family="micro", build=lambda: fn,
                       args=(jax.ShapeDtypeStruct((4,), np.float32), 2.0))
    _, _, findings = audit_program(spec, None)
    assert [f.check for f in findings] == ["weak_typed_operand"]
    # the declared-stable exemption silences it
    spec = dataclasses.replace(spec, allow_weak=("1",))
    _, _, findings = audit_program(spec, None)
    assert findings == []


def test_defect_aot_recompile_storm_fires():
    engine = deepspeed.init_inference(
        model=gpt2.make_gpt2_model(config=_tiny_cfg()),
        config={"inference": {"max_batch_size": 2,
                              "prefill_buckets": [8, 16, 32],
                              "dtype": "fp32", "greedy": True},
                "telemetry": {"programs":
                              {"recompile_storm_threshold": 2}}})
    report = engine.audit()
    storms = [f for f in report.findings if f.check == "recompile_storm"]
    assert storms, [f.key for f in report.findings]
    assert "key space" in storms[0].message


def test_defect_replicated_leaf_fires():
    engine = _make_engine({"telemetry": {
        "enabled": False, "programs": {"replicated_leaf_bytes": 1024}}})
    report = engine.audit(batch=_batch())
    repl = [f for f in report.findings if f.check == "replicated_leaf"]
    assert repl, [f.key for f in report.findings]
    assert all(f.rule == "sharding_drift" for f in repl)


def test_strict_disposition_raises():
    engine = _make_engine({"analysis": {"strict": True}})
    orig = engine.zero_plan.constrain
    engine.zero_plan.constrain = lambda tree, kind: tree
    try:
        with pytest.raises(AuditFindingsError) as err:
            engine.audit(batch=_batch())
    finally:
        engine.zero_plan.constrain = orig
    assert "missing_sharding_constraint" in str(err.value)
    # argument override beats the config
    engine.zero_plan.constrain = lambda tree, kind: tree
    try:
        report = engine.audit(batch=_batch(), strict=False)
    finally:
        engine.zero_plan.constrain = orig
    assert report.findings


# --------------------------------------------------------- suppressions
def test_suppression_file_routes_findings(tmp_path):
    engine = _make_engine()
    sup = tmp_path / "suppressions.json"
    sup.write_text(json.dumps({"version": 1, "suppressions": [
        {"key": "missing_sharding_constraint:*",
         "reason": "intentional defect under test"}]}))
    engine._config.analysis_config.suppressions = str(sup)
    orig = engine.zero_plan.constrain
    engine.zero_plan.constrain = lambda tree, kind: tree
    try:
        report = engine.audit(batch=_batch())
    finally:
        engine.zero_plan.constrain = orig
    assert not any(f.check == "missing_sharding_constraint"
                   for f in report.findings)
    assert any(f.check == "missing_sharding_constraint"
               for f, _ in report.suppressed)


def test_stale_suppressions_surface_in_report(tmp_path):
    engine = _make_engine()
    sup = tmp_path / "suppressions.json"
    sup.write_text(json.dumps({"version": 1, "suppressions": [
        {"key": "never_matches:*", "reason": "left over"}]}))
    engine._config.analysis_config.suppressions = str(sup)
    report = engine.audit(batch=_batch())
    assert report.stale_suppressions == ["never_matches:*"]
    assert report.to_dict()["stale_suppressions"] == ["never_matches:*"]
    # stale entries never fail the audit (prunable, not fatal)
    assert report.findings == []


def test_ds_lint_cli_runs_without_jax_and_classifies_by_baseline(
        tmp_path):
    """The repo-lint CLI path must never import jax (runs on jax-less
    CI boxes), and its --json artifact must split occurrences the same
    way diff_baseline does (baselined occurrence i < allowed count ->
    suppressed, the rest -> findings)."""
    import subprocess
    import sys as _sys
    dirty = tmp_path / "dirty.py"
    base = tmp_path / "baseline.json"
    out = tmp_path / "report.json"
    script = (
        "import sys, importlib.util\n"
        "spec = importlib.util.spec_from_file_location('ds_lint', "
        "{lint!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "open({dirty!r}, 'w').write({src!r})\n"
        "m.run_repo_lint([{dirty!r}], {base!r}, True, None)\n"
        "open({dirty!r}, 'a').write({src2!r})\n"
        "rc = m.run_repo_lint([{dirty!r}], {base!r}, False, {out!r})\n"
        "assert 'jax' not in sys.modules, 'jax imported on lint path'\n"
        "sys.exit(rc)\n").format(
            lint=os.path.join(REPO, "bin", "ds_lint.py"),
            dirty=str(dirty), base=str(base), out=str(out),
            src=_DIRTY_SOURCE,
            src2=_DIRTY_SOURCE.replace("class Engine", "class Engine2"))
    proc = subprocess.run([_sys.executable, "-c", script],
                          capture_output=True, text=True)
    assert proc.returncode == 1, proc.stdout + proc.stderr  # new hits
    payload = json.loads(out.read_text())
    assert validate_analysis_report(payload) == []
    # the 4 baselined (Engine) occurrences stay suppressed; only the
    # duplicated class's 4 are findings — the artifact agrees with
    # diff_baseline instead of flipping whole keys to "new"
    assert payload["summary"]["suppressed"] == 4, payload["summary"]
    assert payload["summary"]["findings"] == 4, payload["summary"]


def test_suppressions_require_reason(tmp_path):
    with pytest.raises(ValueError, match="reason"):
        Suppressions([{"key": "x"}])
    sup = Suppressions([{"key": "a:*", "reason": "r"}])
    assert sup.match(Finding(rule="r", check="a", program="p",
                             message="m", key="a:p")) is not None
    assert sup.stale() == []
    assert sup.match(Finding(rule="r", check="b", program="p",
                             message="m", key="b:p")) is None


# --------------------------------------------------------- report shape
def test_report_roundtrip_and_schema(tmp_path):
    engine = _make_engine()
    path = tmp_path / "report.json"
    report = engine.audit(batch=_batch(), report_path=str(path))
    assert isinstance(report, AnalysisReport)
    payload = json.loads(path.read_text())
    assert validate_analysis_report(payload) == []
    assert payload["summary"]["programs_audited"] == 3
    # a corrupted report is rejected
    bad = dict(payload)
    bad.pop("summary")
    assert validate_analysis_report(bad)
    bad2 = dict(payload, findings=[{"rule": "x"}])
    assert validate_analysis_report(bad2)


def _load_checker():
    spec = importlib.util.spec_from_file_location(
        "check_bench_schema", os.path.join(REPO, "bin",
                                           "check_bench_schema.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_schema_checker_tables_pinned_equal():
    """bin/check_bench_schema.py's stdlib copies cannot drift from the
    writer-side source of truth."""
    checker = _load_checker()
    assert tuple(checker.ANALYSIS_REPORT_KEYS) == \
        tuple(ANALYSIS_REPORT_KEYS)
    assert tuple(checker.ANALYSIS_FINDING_KEYS) == tuple(FINDING_KEYS)
    assert tuple(checker.ANALYSIS_SEVERITIES) == tuple(SEVERITIES)


def test_schema_checker_validates_report_artifact(tmp_path):
    engine = _make_engine()
    path = tmp_path / "report.json"
    engine.audit(batch=_batch(), report_path=str(path))
    checker = _load_checker()
    assert checker.check_file(str(path)) == []
    # ds_lint --json artifacts take the same shape
    from deepspeed_tpu.analysis.findings import AnalysisReport as AR
    r = AR(job="repo-lint")
    r.findings.append(Finding(rule="DSL002", check="device-put-in-loop",
                              program="x.py", message="m",
                              key="DSL002:x.py::f"))
    lint_path = tmp_path / "lint.json"
    r.write(str(lint_path))
    assert checker.check_file(str(lint_path)) == []


# ------------------------------------------------------------ AST lint
_DIRTY_SOURCE = '''
import time
import jax

class Engine:
    def _micro_step_fn(self):
        def micro(state, batch):
            t0 = time.time()                 # DSL001
            return state, t0
        return micro

    def upload(self, leaves, dev):
        for leaf in leaves:
            jax.device_put(leaf, dev)        # DSL002
        while True:
            fn = jax.jit(lambda x: x)        # DSL004
            break

    def emit(self, rec):
        self.telemetry.add(rec)              # DSL003

    def emit_gated(self, rec):
        if self.telemetry is not None:
            self.telemetry.add(rec)          # gated: clean

    def emit_alias_gated(self, rec):
        tel = self.telemetry
        if tel is None:
            return
        tel.add(rec)                         # alias-gated: clean

    def emit_truthy_gated(self, rec):
        if self.telemetry:
            self.telemetry.add(rec)          # truthiness gate: clean

    def emit_not_gated(self, rec):
        if not self.telemetry:
            return
        self.telemetry.add(rec)              # not-gate: clean
'''

_CLEAN_SOURCE = '''
import time
import jax

def host_loop(items):
    t0 = time.time()                         # not in a traced builder
    return [x + 1 for x in items]

def _step_fn():
    def step(x):
        return x * 2                         # no wall clock inside
    return step
'''


def test_astlint_rules_fire_and_stay_quiet(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(_DIRTY_SOURCE)
    clean = tmp_path / "clean.py"
    clean.write_text(_CLEAN_SOURCE)
    findings = astlint.lint_paths([str(dirty)], base=str(tmp_path))
    rules = sorted({key.split(":")[0] for key in findings})
    assert rules == ["DSL001", "DSL002", "DSL003", "DSL004"], findings
    # the gated variants did NOT fire
    dsl3 = [k for k in findings if k.startswith("DSL003")]
    assert dsl3 == ["DSL003:dirty.py::Engine.emit"], dsl3
    assert astlint.lint_paths([str(clean)], base=str(tmp_path)) == {}


def test_astlint_baseline_diff(tmp_path):
    dirty = tmp_path / "dirty.py"
    dirty.write_text(_DIRTY_SOURCE)
    findings = astlint.lint_paths([str(dirty)], base=str(tmp_path))
    base_path = tmp_path / "baseline.json"
    astlint.write_baseline(str(base_path), findings)
    new, stale = astlint.diff_baseline(
        findings, astlint.load_baseline(str(base_path)))
    assert new == [] and stale == []
    # a NEW occurrence of a baselined rule still fails
    key = next(iter(findings))
    findings[key] = findings[key] + findings[key]
    new, _ = astlint.diff_baseline(
        findings, astlint.load_baseline(str(base_path)))
    assert len(new) == len(findings[key]) // 2
    # removing a hazard only reports the baseline entry as stale
    findings.pop(key)
    new, stale = astlint.diff_baseline(
        findings, astlint.load_baseline(str(base_path)))
    assert new == [] and stale == [key]


def test_repo_self_lint_clean_against_committed_baseline():
    """The tier-1 wiring of the ISSUE's CI satellite: bin/ds_lint.py's
    rule set over deepspeed_tpu/ must be clean against the committed
    baseline — new hot-path anti-patterns fail the suite."""
    findings = astlint.lint_paths(
        [os.path.join(REPO, "deepspeed_tpu")], base=REPO)
    baseline = astlint.load_baseline(
        os.path.join(REPO, "bin", "ds_lint_baseline.json"))
    new, _ = astlint.diff_baseline(findings, baseline)
    assert new == [], "new hot-path lint violations:\n" + "\n".join(
        f.message for f in new)


def test_lint_baseline_has_no_stale_entries():
    """Every key of the committed baseline still fires at least its
    count: an entry whose code or whose rule went would otherwise sit
    there and excuse the next occurrence under that key."""
    findings = astlint.lint_paths(
        [os.path.join(REPO, "deepspeed_tpu")], base=REPO)
    baseline = astlint.load_baseline(
        os.path.join(REPO, "bin", "ds_lint_baseline.json"))
    dead = {key: (allowed, len(findings.get(key, ())))
            for key, allowed in baseline.items()
            if len(findings.get(key, ())) < allowed}
    assert dead == {}, "baseline allows more than fires: {}".format(dead)


def test_repo_self_lint_is_baseline_clean():
    """``python bin/ds_lint.py`` with its own defaults (the paths it
    walks, the baseline it loads) is the command a PR is held to: it
    exits 0 over this checkout."""
    import subprocess
    import sys
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "bin", "ds_lint.py")],
        capture_output=True, text=True, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 above baseline; 0 stale" in proc.stdout, proc.stdout


# ----------------------------------------------------------- HLO layer
def test_hlo_census_parsers():
    from deepspeed_tpu.analysis.hlo import (_parse_permute_groups,
                                            _parse_replica_groups,
                                            _shape_bytes, _wire_bytes)
    assert _shape_bytes("f32[8,4]") == 128
    assert _shape_bytes("(bf16[4]{0}, s32[2])") == 16
    assert _parse_replica_groups("replica_groups={{0,1},{2,3}}") == \
        [frozenset({0, 1}), frozenset({2, 3})]
    iota = _parse_replica_groups("replica_groups=[2,4]<=[8]")
    assert iota == [frozenset({0, 1, 2, 3}), frozenset({4, 5, 6, 7})]
    trans = _parse_replica_groups("replica_groups=[2,4]<=[4,2]T(1,0)")
    assert trans == [frozenset({0, 2, 4, 6}), frozenset({1, 3, 5, 7})]
    pairs = _parse_permute_groups(
        "source_target_pairs={{0,2},{2,0},{1,3},{3,1}}")
    assert sorted(pairs, key=min) == [frozenset({0, 2}),
                                      frozenset({1, 3})]
    assert _wire_bytes("all-gather", 800, 8) == 700
    assert _wire_bytes("all-reduce", 800, 8) == 1400
    assert _wire_bytes("reduce-scatter", 100, 8) == 700
    assert _wire_bytes("collective-permute", 100, 8) == 100


def test_hlo_census_async_start_ops_not_overpriced():
    """TPU backends emit async `-start` pairs whose tuple shape bundles
    operand + result (+ scratch): the census must price the RESULT
    only, not the sum."""
    from deepspeed_tpu.analysis.hlo import _result_bytes, collective_census
    # (operand bf16[64], result bf16[512]) all-gather-start at g=8
    assert _result_bytes("(bf16[64], bf16[512])", "all-gather",
                         True) == 1024
    # reduce-scatter-start: result is the SMALL element
    assert _result_bytes("(f32[512], f32[64])", "reduce-scatter",
                         True) == 256
    # u32 scratch in a permute pair is ignored in favor of the payload
    assert _result_bytes("(bf16[256], bf16[256], u32[], u32[])",
                         "collective-permute", True) == 512
    # sync single-shape path unchanged
    assert _result_bytes("f32[128]", "all-reduce", False) == 512
    hlo = (
        "  %ag = (bf16[1024]{0}, bf16[8192]{0}) all-gather-start("
        "bf16[1024]{0} %p), replica_groups=[1,8]<=[8], dimensions={0}\n"
        "  %done = bf16[8192]{0} all-gather-done((bf16[1024]{0}, "
        "bf16[8192]{0}) %ag)\n")
    census = collective_census(hlo, min_bytes=1)
    assert len(census["ops"]) == 1
    # ring price of the 16384-byte gathered result: 16384 * 7/8
    assert census["ops"][0]["wire_bytes"] == 14336


def test_mesh_axis_groups():
    from deepspeed_tpu.parallel.topology import (build_mesh,
                                                 mesh_axis_groups)
    mesh = build_mesh(data=4, model=2)
    data_groups = mesh_axis_groups(mesh, "data")
    model_groups = mesh_axis_groups(mesh, "model")
    assert len(data_groups) == 2 and all(len(g) == 4
                                         for g in data_groups)
    assert len(model_groups) == 4 and all(len(g) == 2
                                          for g in model_groups)
    both = mesh_axis_groups(mesh, ("data", "model"))
    assert both == [frozenset(range(8))]


def test_tp_ways():
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.runtime.zero.partition import ZeroShardingPlan
    from jax.sharding import PartitionSpec as P
    mesh = build_mesh(data=4, model=2)
    plan = ZeroShardingPlan(
        mesh, stage=3,
        model_spec_fn=lambda path, shape:
        P(None, "model") if path == "w" else None)
    assert plan.tp_ways("w", (64, 64)) == 2
    assert plan.tp_ways("b", (64,)) == 1


@pytest.mark.slow
def test_hlo_census_ground_truths_wire_estimator():
    """The byte-for-byte contract: on the explicit-ring (cm) path the
    HLO ppermute census equals the estimator's allgather class exactly;
    the reconciliation payload lands in the report."""
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.runtime.engine import DeepSpeedEngine
    mesh = build_mesh(data=8)
    engine = DeepSpeedEngine(
        model=gpt2.make_gpt2_model(config=_tiny_cfg()), mesh=mesh,
        config_params={
            "train_micro_batch_size_per_gpu": 2,
            "gradient_accumulation_steps": 1,
            "bf16": {"enabled": True},
            "zero_optimization": {"stage": 3,
                                  "stage3_param_persistence_threshold": 0},
            "comm": {"collective_matmul": {"enabled": True, "chunks": 1}},
            "analysis": {"census_min_bytes": 1,
                         "suppressions": os.path.join(
                             REPO, "tests", "unit",
                             "analysis_suppressions.json")},
            "optimizer": {"type": "Adam", "params": {"lr": 1e-4}},
            "steps_per_print": 10 ** 9})
    rng = np.random.RandomState(0)
    ids = rng.randint(0, 256, size=(8, 64)).astype(np.int32)
    report = engine.audit(batch=(ids, ids.copy()), hlo=True)
    census = report.census
    assert census is not None, report.to_dict()
    assert census["match_ring_allgather"] is True, census
    assert census["hlo"]["ring_bytes"] == \
        census["estimator"]["allgather_bytes"] > 0, census
    assert report.findings == [], [f.key for f in report.findings]


@pytest.mark.slow
def test_defect_output_sharding_drift_fires():
    """Force the apply step to hand back a REPLICATED master: the
    compiled output-drift check must catch the un-sharding."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    engine = _make_engine()
    specs = collectors.collect_train_programs(engine, batch=_batch())
    apply_spec = next(s for s in specs if s.name == "apply")
    repl = NamedSharding(engine.mesh, P())
    orig_build = apply_spec.build

    def bad_build():
        fn = orig_build()

        def wrapped(state, hyper):
            new_state, metrics = fn(state, hyper)
            new_state = dict(new_state)
            new_state["master"] = jax.tree_util.tree_map(
                lambda m: jax.lax.with_sharding_constraint(m, repl),
                new_state["master"])
            return new_state, metrics

        return wrapped

    bad = dataclasses.replace(apply_spec, build=bad_build)
    report = audit_programs([bad], engine._config.analysis_config,
                            hlo=True, mesh=engine.mesh)
    drift = [f for f in report.findings
             if f.check == "output_sharding_drift"]
    assert drift, [f.key for f in report.findings]
    assert "REPLICATED" in drift[0].message
    # the clean spec compiles drift-free
    clean = audit_programs([apply_spec], engine._config.analysis_config,
                           hlo=True, mesh=engine.mesh)
    assert not any(f.check == "output_sharding_drift"
                   for f in clean.findings)


def test_h2d_split_program_donation_audit():
    """The ISSUE 10 satellite: audit-verify the H2D bucket split
    program's donated-buffer list. The flat staging buffer has NO
    aliasable output (every output is a reshaped slice), so donating it
    is provably unhonorable — the program now (correctly) donates
    nothing, and the auditor proves re-adding the donation would be a
    defect."""
    from deepspeed_tpu.runtime.zero.transfer import _split_fn_for
    import warnings
    layout = ((512 * 512, (512, 512)), (512 * 512, (512, 512)))
    fn = _split_fn_for(layout)
    # the jitted program runs donation-warning-free on every backend
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        fn(jnp.arange(2 * 512 * 512, dtype=jnp.float32))
    flat = jax.ShapeDtypeStruct((2 * 512 * 512,), np.float32)
    clean = ProgramSpec(name="h2d_split", family="streamed",
                        build=lambda: fn.__wrapped__, args=(flat,),
                        donate=())
    _, _, findings = audit_program(clean, None)
    assert findings == [], [f.key for f in findings]
    donated = dataclasses.replace(clean, donate=(0,))
    _, _, findings = audit_program(donated, None)
    assert [f.check for f in findings] == ["donation_unhonored"]


def test_decode_step_donation_audit():
    """Satellite twin: the fused decode program's donated-buffer list
    is exactly the KV pair — the auditor confirms nothing else above
    threshold could alias, and dropping the KV donation is flagged as
    an HBM-doubling miss."""
    engine = deepspeed.init_inference(
        model=gpt2.make_gpt2_model(config=_tiny_cfg()),
        config={"inference": {"max_batch_size": 2,
                              "prefill_buckets": [8],
                              "dtype": "fp32", "greedy": True},
                "analysis": {"donation_min_bytes": 1024}})
    specs = collectors.collect_inference_programs(engine)
    decode = next(s for s in specs if s.name == "decode")
    assert decode.donate_argnums == (1, 2)       # k_cache, v_cache
    _, _, clean = audit_program(decode, engine.analysis_config)
    assert not any(f.rule == "donation" for f in clean), \
        [f.key for f in clean]
    bad = dataclasses.replace(decode, donate=())
    _, _, findings = audit_program(bad, engine.analysis_config)
    missed = [f for f in findings if f.check == "donation_miss"]
    assert len(missed) >= 2, [f.key for f in findings]


# -------------------------------------------------------- audit errors
def test_untraceable_program_reports_audit_error():
    def broken():
        raise RuntimeError("builder exploded")

    spec = ProgramSpec(name="boom", family="micro", build=broken,
                       args=())
    _, _, findings = audit_program(spec, None)
    assert [f.check for f in findings] == ["audit_error"]
    assert findings[0].severity == "error"


def test_audit_without_batch_needs_sample():
    engine = _make_engine()
    with pytest.raises(ValueError, match="sample batch"):
        engine.audit()
    # an EVAL forward must not stand in for the training micro-batch
    # (eval rows are arbitrary and often replicated)
    engine.eval()
    x = np.zeros((3, 64), np.int32)
    engine(x, x.copy())
    engine.train()
    with pytest.raises(ValueError, match="sample batch"):
        engine.audit()


def test_census_counts_data_axis_all_to_all():
    """A data-axis collective in no wire class (a GSPMD resharding
    all-to-all) still counts toward the reconciled total — the
    'unplanned collective behind your back' must be flaggable."""
    from deepspeed_tpu.analysis.hlo import census_classes, reconcile_wire
    census = {"ops": [
        {"opcode": "all-to-all", "wire_bytes": 1 << 20, "axis": "data"},
        {"opcode": "all-gather", "wire_bytes": 2048, "axis": "data"},
        {"opcode": "all-to-all", "wire_bytes": 4096, "axis": "model"},
    ]}
    classes = census_classes(census, {"data"})
    assert classes["data_other_bytes"] == 1 << 20
    assert classes["data_total_bytes"] == (1 << 20) + 2048
    assert classes["other_axis_bytes"] == 4096
    payload, findings = reconcile_wire(
        [census], {"allgather_bytes_per_step": 2048,
                   "reduce_bytes_per_step": 0}, {"data"})
    assert [f.check for f in findings] == ["unpriced_collective"]
    assert payload["delta_total_bytes"] == 1 << 20


def test_audit_after_step_needs_no_batch():
    engine = _make_engine()
    ids, labels = _batch()
    loss = engine(ids, labels)
    engine.backward(loss)
    engine.step()
    report = engine.audit()
    assert set(report.programs) == {"micro", "apply", "fused_train"}
    assert report.findings == [], [f.key for f in report.findings]
