"""Shard-lint: ahead-of-time SPMD program auditing (docs/analysis.md).

Abstract-evals every engine step program from ``ShapeDtypeStruct``s +
the resolved ``ZeroShardingPlan`` and walks the jaxpr (and optionally
the compiled HLO) for the failure modes that silently destroy MFU:
sharding drift, missed buffer donations, fp32 upcasts in the bf16 GEMM
path, host callbacks under jit, and recompile storms — before a single
step runs. ``bin/ds_lint.py`` adds the repo-wide AST hot-path linter.
"""
from .findings import (AnalysisReport, Finding, Suppressions,
                       validate_analysis_report)
from .rules import (ProgramSpec, RECOMPILE_STORM_THRESHOLD_DEFAULT,
                    REPLICATED_LEAF_BYTES_DEFAULT, audit_program,
                    recompile_storm_finding, replicated_leaf_finding)
from .auditor import (AuditFindingsError, audit_engine, audit_programs,
                      lower_engine_program, dispose)
from .config import ANALYSIS, DeepSpeedAnalysisConfig, KNOWN_ANALYSIS_KEYS

__all__ = [
    "AnalysisReport", "Finding", "Suppressions",
    "validate_analysis_report", "ProgramSpec", "audit_program",
    "audit_programs", "audit_engine", "lower_engine_program", "dispose",
    "AuditFindingsError",
    "DeepSpeedAnalysisConfig", "ANALYSIS", "KNOWN_ANALYSIS_KEYS",
    "replicated_leaf_finding", "recompile_storm_finding",
    "RECOMPILE_STORM_THRESHOLD_DEFAULT", "REPLICATED_LEAF_BYTES_DEFAULT",
]
