"""Audit orchestration: ProgramSpecs -> AnalysisReport -> disposition.

``audit_engine(engine, ...)`` is the one entry point both
``DeepSpeedEngine.audit()`` and ``InferenceEngine.audit()`` (and the
dryrun / CLI) call: it collects the engine's program specs
(analysis/programs.py), runs every jaxpr-level rule
(analysis/rules.py), optionally compiles each program for the HLO
collective census + output-sharding drift (analysis/hlo.py), routes
findings through the suppression file, and disposes per the
``analysis`` config section — warn (default), RAISE under
``analysis.strict``, and/or write the JSON report artifact
(``bin/check_bench_schema.py`` validates its shape).
"""
import numpy as np

import jax

from ..utils.logging import logger
from .findings import AnalysisReport, Finding, Suppressions
from .hlo import collective_census, reconcile_wire
from .ir import segment_summary
from .rules import audit_program, sequence_findings


class AuditFindingsError(RuntimeError):
    """Raised under ``analysis.strict`` when unsuppressed findings
    survive an audit."""

    def __init__(self, report):
        self.report = report
        lines = ["shard-lint: {} unsuppressed finding(s) "
                 "(analysis.strict=true):".format(len(report.findings))]
        lines += ["  - [{}] {}".format(f.key, f.message)
                  for f in report.findings]
        super().__init__("\n".join(lines))


def mesh_axis_labels(mesh):
    """{label: [frozenset(device ids)]} for every nontrivial mesh axis,
    plus the combined factored-data label when hpZ split the data axis."""
    from ..parallel.topology import (DATA_REPLICA_AXIS, DATA_SHARD_AXIS,
                                     mesh_axis_groups)
    labels = {}
    if mesh is None:
        return labels
    for ax in mesh.axis_names:
        if int(mesh.shape[ax]) > 1:
            labels[ax] = mesh_axis_groups(mesh, ax)
    factored = tuple(ax for ax in (DATA_REPLICA_AXIS, DATA_SHARD_AXIS)
                     if int(dict(mesh.shape).get(ax, 1)) > 1)
    if len(factored) > 1:
        labels["+".join(factored)] = mesh_axis_groups(mesh, factored)
    return labels


def data_axis_labels(mesh):
    """The label subset that carries ZeRO (data-axis) wire traffic."""
    from ..parallel.topology import (DATA_AXIS, DATA_REPLICA_AXIS,
                                     DATA_SHARD_AXIS)
    if mesh is None:
        return set()
    shape = dict(mesh.shape)
    out = {ax for ax in (DATA_AXIS, DATA_REPLICA_AXIS, DATA_SHARD_AXIS)
           if int(shape.get(ax, 1)) > 1}
    factored = tuple(ax for ax in (DATA_REPLICA_AXIS, DATA_SHARD_AXIS)
                     if int(shape.get(ax, 1)) > 1)
    if len(factored) > 1:
        out.add("+".join(factored))
    return out


def _output_drift_findings(spec, fn, compiled):
    """Compiled output shardings vs. the plan: every output leaf the
    spec expects data-sharded must not come back fully replicated."""
    expects = spec.meta.get("out_expect") or ()
    if not expects:
        return []
    try:
        out_shardings = compiled.output_shardings
        out_struct = jax.eval_shape(fn, *spec.args)
    except Exception as err:  # noqa: BLE001 - census is best-effort
        logger.info("shard-lint: output shardings unavailable for %r "
                    "(%s)", spec.name, err)
        return []
    # join by PATH, never by zip: the two trees flatten differently
    # around None leaves (offload state carries "master": None), and a
    # positional pairing would silently shift every entry after one
    from .rules import _kp_str, _spec_mentions
    flat_sh, _ = jax.tree_util.tree_flatten_with_path(
        out_shardings, is_leaf=lambda x: hasattr(x, "spec") or x is None)
    flat_st, _ = jax.tree_util.tree_flatten_with_path(
        out_struct, is_leaf=lambda x: x is None or hasattr(x, "shape"))
    shardings_by_path = {_kp_str(kp): sh for kp, sh in flat_sh}
    by_path = {}
    for kp, st in flat_st:
        path = _kp_str(kp)
        if st is not None and path in shardings_by_path:
            by_path[path] = (shardings_by_path[path], st)
    findings = []
    for path, axes in expects:
        ent = by_path.get(path)
        if ent is None:
            continue
        sh, st = ent
        nbytes = int(np.prod(st.shape, dtype=np.int64) *
                     np.dtype(st.dtype).itemsize) if st.shape else 0
        if sh is None or _spec_mentions(sh, set(axes)):
            continue
        findings.append(Finding(
            rule="sharding_drift", check="output_sharding_drift",
            program=spec.name,
            message="program {!r} output {} ({:.1f} MB) compiled back "
                    "REPLICATED but the ZeroShardingPlan shards it over "
                    "{} — the step un-shards state the plan paid to "
                    "partition (HBM grows every step)".format(
                        spec.name, path, nbytes / 2 ** 20, list(axes)),
            key="output_sharding_drift:{}:{}".format(spec.name, path),
            details={"path": path, "axes": list(axes),
                     "nbytes": nbytes}))
    return findings


def audit_programs(specs, config, job="audit", suppressions=None,
                   sequence=(), hlo=False, wire_est=None, mesh=None,
                   report_path=None, extra_findings=()):
    """Run the full rule set over ``specs`` and assemble the report.

    ``hlo=True`` additionally compiles each spec whose meta carries a
    ``wire_multiplier`` or ``out_expect`` and runs the collective
    census / output-drift checks; the summed census reconciles against
    ``wire_est`` when given. ``extra_findings``: pre-built findings
    (the lock sanitizer's) routed through the same suppression file.
    The walked collective sequences land in
    ``report.collective_families`` — the program-fingerprint source
    (ISSUE 15; analysis/concurrency/divergence.py).
    """
    from .concurrency.divergence import (collective_tokens,
                                         control_flow_findings)
    report = AnalysisReport(job=job)
    if isinstance(suppressions, str):
        suppressions = Suppressions.load(suppressions)
    axis_labels = mesh_axis_labels(mesh) if hlo else {}
    data_labels = data_axis_labels(mesh)
    census_list = []
    for spec in specs:
        closed, walk_result, findings = audit_program(spec, config)
        report.extend(findings, suppressions)
        meta = {"family": spec.family,
                "donate_argnums": list(spec.donate)}
        if walk_result is not None:
            meta["segments"] = segment_summary(walk_result)
            report.collective_families[spec.name] = \
                collective_tokens(walk_result)
            report.extend(control_flow_findings(spec.name, walk_result),
                          suppressions)
        if hlo and closed is not None and (
                spec.meta.get("wire_multiplier") or
                spec.meta.get("out_expect")):
            try:
                from ..runtime.executor.jit import jit_program
                fn = jit_program(spec.build(), donate=spec.donate)
                compiled = fn.lower(*spec.args).compile()
            except Exception as err:  # noqa: BLE001 - report, don't die
                report.add(Finding(
                    rule="sharding_drift", check="audit_error",
                    program=spec.name, severity="error",
                    message="program {!r} could not be compiled for the "
                            "HLO census: {}".format(spec.name, err),
                    key="audit_error:hlo:{}".format(spec.name)),
                    suppressions)
            else:
                report.extend(_output_drift_findings(spec, fn, compiled),
                              suppressions)
                mult = int(spec.meta.get("wire_multiplier") or 0)
                if mult > 0:
                    census = collective_census(
                        compiled.as_text(), axis_groups=axis_labels,
                        min_bytes=getattr(config, "census_min_bytes",
                                          1024))
                    for op in census["ops"]:
                        op["wire_bytes"] *= mult
                    census["total_bytes"] *= mult
                    for slot in census["by_axis"].values():
                        slot["wire_bytes"] *= mult
                    meta["collective_census"] = {
                        "total_bytes": census["total_bytes"],
                        "by_axis": census["by_axis"],
                    }
                    census_list.append(census)
        report.add_program(spec.name, **meta)
    if sequence:
        report.extend(sequence_findings(sequence), suppressions)
    if extra_findings:
        report.extend(extra_findings, suppressions)
    if hlo and census_list and wire_est is not None:
        sharded_grads = any(
            getattr(s.plan, "stage", 0) >= 2 for s in specs
            if s.plan is not None)
        payload, findings = reconcile_wire(
            census_list, wire_est, data_labels,
            program=job,
            min_bytes=getattr(config, "census_min_bytes", 1024),
            normalize_allreduce=sharded_grads and
            jax.default_backend() != "tpu")
        report.census = payload
        report.extend(findings, suppressions)
    if suppressions is not None:
        # a suppression whose finding no longer exists is a latent mask
        # for a future regression with the same key — surface it loudly
        # (it lands in the report as stale_suppressions, non-failing)
        report.stale_suppressions = suppressions.stale()
        for key in report.stale_suppressions:
            logger.warning(
                "shard-lint: suppression %r matched nothing this audit "
                "— prune it from %s", key,
                suppressions.path or "the suppression list")
    if report_path:
        report.write(report_path)
    return report


def dispose(report, config, raise_on_findings=None):
    """Warn each unsuppressed finding; raise under analysis.strict."""
    for f in report.findings:
        logger.warning("shard-lint: %s", f.message)
    strict = raise_on_findings if raise_on_findings is not None \
        else getattr(config, "strict", False)
    if strict and report.findings:
        raise AuditFindingsError(report)
    return report


def audit_plan(engine, report):
    """Lowered-plan verification (ISSUE 13): build the abstract segment
    plan of the engine's step path through the SAME entry point the
    executor uses (``ir.plan_of``) and run the plan-level rules —
    unique names, IR-vocabulary kinds, resolvable topologically-ordered
    deps. Plan problems are unsuppressable findings (a malformed plan
    is a bug in the lowering, never an accepted quirk); the plan's
    shape lands in the report's program table as ``plan/<name>``."""
    if getattr(engine, "stream_runner", None) is None and \
            getattr(engine, "host_state", None) is None and \
            getattr(engine, "pipe_module", None) is None and \
            not hasattr(engine, "prefill_buckets"):
        return None                 # micro/fused: one-segment plans
    from .ir import plan_of
    try:
        plan = plan_of(engine)
    except Exception as err:  # noqa: BLE001 - report, don't die
        report.add(Finding(
            rule="executor_plan", check="plan_build_error",
            program="plan", severity="error",
            message="segment plan could not be built for the audit: "
                    "{}".format(err),
            key="plan_build_error"))
        return None
    for i, problem in enumerate(plan.validate()):
        report.add(Finding(
            rule="executor_plan", check="plan_invalid",
            program="plan/" + plan.name, severity="error",
            message="segment plan {!r} is invalid: {}".format(
                plan.name, problem),
            key="plan_invalid:{}:{}".format(plan.name, i)))
    summary = plan.summary()
    report.add_program("plan/" + plan.name, family="plan",
                       plan_segments=summary["segments"],
                       per_kind=summary["per_kind"])
    return plan


def engine_program_specs(engine, batch=None):
    """The engine's step programs as ProgramSpecs (training or inference
    engine; ``batch`` as for :func:`audit_engine`)."""
    from . import programs as collectors
    if hasattr(engine, "prefill_buckets"):           # inference engine
        return collectors.collect_inference_programs(engine)
    return collectors.collect_train_programs(engine, batch=batch)


def lower_engine_program(engine, name, batch=None):
    """Lower ONE of the engine's step programs ahead of time — the
    engine's OWN jitted program, at the argument types its step will
    pass, so jax traces and lowers it once: ``.compile()`` on the
    result leaves the executable where the step's first call finds it.
    Returns the jax ``Lowered``; its ``.compile()`` gives ``as_text()``
    — whether a kernel is in the program (``tpu_custom_call``), which
    collectives the compiler put in — and ``memory_analysis()``, its
    bytes per device. Training engines: ``micro`` / ``apply`` /
    ``fused_train``; inference engines: ``decode`` / ``spec_verify``
    (greedy)."""
    specs = {s.name: s for s in engine_program_specs(engine, batch=batch)}
    if name not in specs:
        raise KeyError("engine has no step program {!r}: {}".format(
            name, sorted(specs)))
    spec = specs[name]
    if hasattr(engine, "prefill_buckets"):           # inference engine
        widths = {"decode": 1, "spec_verify": engine.spec_k + 1}
        fn = engine._get_decode_fn(True, 0, width=widths[name])
    else:
        key = name if name == "micro" else engine._regime_jit_key(name)
        fn = engine._get_jit(key, spec.build, donate=spec.donate)
    return fn.lower(*spec.args)


def audit_engine(engine, batch=None, hlo=None, report_path=None,
                 strict=None):
    """Ahead-of-time shard-lint over one engine's resolved step
    programs. ``engine`` is a DeepSpeedEngine (micro/fused/offload/
    streamed/pipeline paths) or an InferenceEngine
    (prefill/decode/spec-verify). Returns the
    :class:`AnalysisReport`; raises :class:`AuditFindingsError` when
    unsuppressed findings survive and strict is on (argument overrides
    the config).

    ``batch``: a sample micro-batch (arrays or ShapeDtypeStructs) for
    training engines that have not seen a step yet; ``hlo`` overrides
    ``analysis.hlo`` (compile + collective census + output drift).
    """
    from . import programs as collectors
    specs = engine_program_specs(engine, batch=batch)
    if hasattr(engine, "prefill_buckets"):           # inference engine
        config = engine.analysis_config
        sequence = collectors.inference_step_sequence(engine)
        mesh = engine.mesh
        wire_est = None
        job = "serve"
    else:
        config = engine._config.analysis_config
        sequence = collectors.train_step_sequence(engine)
        mesh = engine.mesh
        wire_est = None
        try:
            from ..runtime.comm.wire import estimate_engine_comm_bytes
            if engine.zero_plan.dp_size > 1 and \
                    engine.state.get("params") is not None:
                # min_component: drop estimator components below the
                # census threshold so the diff compares like-for-like
                # (the 1-bit exchange's scalar-scale gathers are a few
                # dozen bytes — below any census floor)
                wire_est = estimate_engine_comm_bytes(
                    engine, min_component=getattr(
                        config, "census_min_bytes", 1024))
        except Exception as err:  # noqa: BLE001 - estimator optional
            logger.info("shard-lint: wire estimate unavailable (%s)", err)
        job = "train"
    use_hlo = bool(config.hlo if hlo is None else hlo)
    # lock-sanitizer findings (docs/concurrency.md) ride the same
    # report — and the same suppression file — as the program rules
    from .concurrency import locksan
    san = locksan.current()
    report = audit_programs(
        specs, config, job=job,
        suppressions=config.suppressions, sequence=sequence,
        hlo=use_hlo, wire_est=wire_est, mesh=mesh,
        extra_findings=san.report() if san is not None else ())
    # lowered-plan verification rides the same report (and lands in
    # the same artifact) as the program rules — serving included, now
    # that the scheduler's step is a lowered serving_step plan
    plan = audit_plan(engine, report)
    # canonical program fingerprint (ISSUE 15): the collective order of
    # every walked program + the lowered plan topology, published into
    # this host's manifest so bin/ds_fleet.py can verify the whole
    # fleet lowered the SAME program
    if report.collective_families and \
            getattr(config, "concurrency_fingerprint", True):
        from .concurrency.divergence import (canonical_fingerprint,
                                             plan_tokens)
        fams = dict(report.collective_families)
        if plan is not None:
            fams["plan/" + plan.name] = plan_tokens(plan)
        report.fingerprint = canonical_fingerprint(fams)
        tel = getattr(engine, "telemetry", None)
        if tel is not None:
            tel.publish_fingerprint(report.fingerprint)
    out_path = report_path or config.report_path
    if out_path:
        report.write(out_path)
    return dispose(report, config, raise_on_findings=strict)
