"""Shard-lint rule implementations.

Four rule classes over an abstract :class:`ProgramSpec` (a step program
described by its builder, example arg structs, donation set and the
resolved ``ZeroShardingPlan``):

  * **sharding_drift** — replicated input leaves above the byte
    threshold (shared implementation with the runtime compile
    observatory: :func:`replicated_leaf_finding`), and a
    sharding-constraint census proving the program still carries the
    plan's ``with_sharding_constraint`` calls (strip one and the count
    drops below the plan's expectation);
  * **donation** — dead input buffers that could be donated but are not
    (HBM doubling), donated buffers no output can alias (the donation
    is silently dropped), and donated-state reads after donation
    (:func:`sequence_findings` over the engine's declared step
    sequence);
  * **dtype_promotion** — fp32 GEMMs reachable from bf16 params (an
    upcast leaked into the matmul path; loss/norm/Adam math is
    naturally exempt because it is not GEMM-shaped — extend
    ``analysis.fp32_allowlist`` for intentional fp32 contractions);
  * **host_sync / recompile hazards** — host callbacks under jit
    (``pure_callback``/``debug_*`` force a device->host sync every
    step), weak-typed (Python-scalar) operands that fragment the
    compile cache, and ahead-of-time recompile-storm bounds (a program
    family whose key space exceeds the storm threshold *will* storm —
    shared implementation with the runtime detector:
    :func:`recompile_storm_finding`).

The two shared rule cores carry the SAME default thresholds the
runtime compile observatory uses (``telemetry.programs`` tunes both —
one threshold config, no drift; ``telemetry/programs.py`` imports them
from here).
"""
import dataclasses

import numpy as np

import jax

from .findings import Finding
from .ir import GEMM_PRIMS, HOST_PRIMS, dtype_itemsize, walk

# One home for the thresholds the runtime observatory and the AOT
# auditor share (telemetry/programs.py re-exports for back-compat).
RECOMPILE_STORM_THRESHOLD_DEFAULT = 32
REPLICATED_LEAF_BYTES_DEFAULT = 1 << 30
DONATION_MIN_BYTES_DEFAULT = 1 << 20
CENSUS_MIN_BYTES_DEFAULT = 1 << 10


# ------------------------------------------------------- shared rule core
def replicated_leaf_finding(program, leaf, nbytes, device_count,
                            threshold=REPLICATED_LEAF_BYTES_DEFAULT):
    """The ONE accidental-full-replication rule (used ahead-of-time by
    the auditor on program input structs and at runtime by the compile
    observatory on committed arg shardings). None when under threshold
    or off-mesh."""
    if device_count <= 1 or nbytes < threshold:
        return None
    return Finding(
        rule="sharding_drift", check="replicated_leaf", program=program,
        message="program {!r} takes a fully REPLICATED {:.1f} MB leaf "
                "({}) on a {}-device mesh — likely an accidental "
                "replication (missing partition rule); HBM pays {}x for "
                "it".format(program, nbytes / 2 ** 20, leaf, device_count,
                            device_count),
        key="replicated_leaf:{}:{}".format(program, leaf),
        details={"leaf": leaf, "nbytes": int(nbytes),
                 "device_count": int(device_count),
                 "threshold": int(threshold)})


def recompile_storm_finding(program, count,
                            threshold=RECOMPILE_STORM_THRESHOLD_DEFAULT,
                            hint="its input shapes are not stabilizing"):
    """The ONE recompile-storm rule (runtime: executable-cache growth /
    trace-family growth; ahead-of-time: a program family's static key
    space). None while under threshold."""
    if count <= threshold:
        return None
    return Finding(
        rule="host_sync", check="recompile_storm", program=program,
        message="program {!r} holds {} executables/traces (threshold {}) "
                "— a recompile storm; {}".format(program, count, threshold,
                                                 hint),
        key="recompile_storm:{}".format(program),
        details={"count": int(count), "threshold": int(threshold)})


# ------------------------------------------------------------ ProgramSpec
@dataclasses.dataclass
class ProgramSpec:
    """One step program, described abstractly (nothing executes).

    ``build``            zero-arg callable -> the traced python fn
                         (the engine's ``*_fn`` builder output);
    ``args``             tuple pytree of arrays / ShapeDtypeStructs /
                         scalars — the program's example operands;
    ``donate``           the donation set (argnums) the engine uses —
                         the same spelling ``runtime/executor/jit.
                         jit_program`` takes, so the audited declaration
                         IS the executed one;
    ``taint_paths``      flat-path prefixes ("0/params") whose low-
                         precision leaves seed the dtype-promotion
                         taint;
    ``keep_args``        flat-path prefixes the engine declares LIVE
                         after the call (excluded from donation_miss —
                         e.g. boundary activations kept for recompute);
    ``allow_weak``       flat-path prefixes exempt from the weak-typed-
                         operand hazard (declared stable scalar blocks,
                         e.g. the optimizer hyperparams);
    ``expected_constraints`` minimum number of sharding-constraint eqns
                         naming a ``constraint_axes`` axis the plan
                         expects in this program (0 = skip the census);
    ``trace_bound``      static key-space size of the program's family
                         (inference bucket lists); checked against the
                         storm threshold ahead-of-time.
    """
    name: str
    family: str
    build: object
    args: tuple
    donate: tuple = ()
    plan: object = None
    mesh: object = None
    taint_paths: tuple = ()
    keep_args: tuple = ()
    allow_weak: tuple = ()
    expected_constraints: int = 0
    constraint_axes: tuple = ()
    trace_bound: object = None
    meta: dict = dataclasses.field(default_factory=dict)

    @property
    def donate_argnums(self):
        """Jax spelling of :attr:`donate` (report/readers compat)."""
        return self.donate


def _kp_str(key_path):
    return "/".join(str(getattr(k, "key", getattr(k, "idx", k)))
                    for k in key_path)


def _abstract(leaf):
    """leaf -> ShapedArray (shape/dtype/weak_type) without touching
    data; handles arrays, ShapeDtypeStructs and Python scalars."""
    from jax.api_util import shaped_abstractify
    return shaped_abstractify(leaf)


def flat_arg_leaves(args):
    """Flatten a program's args exactly the way ``jax.make_jaxpr``
    flattens its invars: [(argnum, "argnum/tree/path", leaf)] in invar
    order."""
    out = []
    for argnum, arg in enumerate(args):
        flat, _ = jax.tree_util.tree_flatten_with_path(arg)
        for kp, leaf in flat:
            path = str(argnum)
            sub = _kp_str(kp)
            if sub:
                path += "/" + sub
            out.append((argnum, path, leaf))
    return out


def _leaf_nbytes(leaf):
    aval = _abstract(leaf)
    shape = tuple(getattr(aval, "shape", ()))
    itemsize = dtype_itemsize(aval.dtype)
    return int(np.prod(shape, dtype=np.int64)) * itemsize if shape \
        else itemsize


def _dtype_key(dtype):
    """Hashable dtype tag tolerating jax extended dtypes."""
    try:
        return np.dtype(dtype).str
    except TypeError:
        return str(dtype)


def _leaf_sharding(leaf):
    return getattr(leaf, "sharding", None)


def _match_prefix(path, prefixes):
    return any(path == p or path.startswith(p.rstrip("/") + "/")
               for p in prefixes)


def donated_flat_indices(spec):
    """Flat-leaf indices covered by the spec's donation set."""
    donated = set()
    for i, (argnum, _, _) in enumerate(flat_arg_leaves(spec.args)):
        if argnum in spec.donate:
            donated.add(i)
    return donated


# -------------------------------------------------------------- donation
def donation_findings(spec, closed_jaxpr,
                      min_bytes=DONATION_MIN_BYTES_DEFAULT):
    """Donation audit over one program's input/output avals."""
    findings = []
    leaves = flat_arg_leaves(spec.args)
    donated = donated_flat_indices(spec)
    out_pool = {}
    for aval in closed_jaxpr.out_avals:
        key = (tuple(aval.shape), _dtype_key(aval.dtype))
        out_pool[key] = out_pool.get(key, 0) + 1

    def take(key):
        if out_pool.get(key, 0) > 0:
            out_pool[key] -= 1
            return True
        return False

    # donated inputs claim their aliases first
    for i, (argnum, path, leaf) in enumerate(leaves):
        if i not in donated:
            continue
        aval = _abstract(leaf)
        key = (tuple(aval.shape), _dtype_key(aval.dtype))
        if not take(key) and _leaf_nbytes(leaf) >= min_bytes:
            findings.append(Finding(
                rule="donation", check="donation_unhonored",
                program=spec.name,
                message="program {!r} donates input {} ({:.1f} MB) but no "
                        "output matches its shape/dtype — XLA drops the "
                        "donation and the buffer is copied".format(
                            spec.name, path,
                            _leaf_nbytes(leaf) / 2 ** 20),
                key="donation_unhonored:{}:{}".format(spec.name, path),
                details={"path": path, "nbytes": _leaf_nbytes(leaf)}))
    # remaining big inputs that still match an unclaimed output could be
    # donated — each one doubles its HBM while the program runs
    for i, (argnum, path, leaf) in enumerate(leaves):
        if i in donated or _match_prefix(path, spec.keep_args):
            continue
        nbytes = _leaf_nbytes(leaf)
        if nbytes < min_bytes:
            continue
        aval = _abstract(leaf)
        key = (tuple(aval.shape), _dtype_key(aval.dtype))
        if take(key):
            findings.append(Finding(
                rule="donation", check="donation_miss", program=spec.name,
                message="program {!r} input {} ({:.1f} MB) matches an "
                        "output it could alias but is not donated — HBM "
                        "holds both copies across the step (add it to "
                        "donate_argnums, or declare it live via the "
                        "spec's keep_args)".format(
                            spec.name, path, nbytes / 2 ** 20),
                key="donation_miss:{}:{}".format(spec.name, path),
                details={"path": path, "nbytes": nbytes,
                         "argnum": argnum}))
    return findings


def sequence_findings(sequence):
    """Read-after-donation over the engine's declared step sequence:
    ``[{"program", "reads", "donates", "produces"}, ...]`` with state-
    field names. A field read after a prior program donated it — without
    an intervening producer rebinding it — is a use-after-free the
    runtime would surface as 'Buffer has been deleted or donated'."""
    findings = []
    dead = {}                      # field -> donor program
    for step in sequence:
        name = step.get("program", "?")
        for field in step.get("reads", ()):
            if field in dead:
                findings.append(Finding(
                    rule="donation", check="read_after_donation",
                    program=name, severity="error",
                    message="program {!r} reads state field {!r} after "
                            "program {!r} donated it without a rebind — "
                            "the buffer is gone at runtime".format(
                                name, field, dead[field]),
                    key="read_after_donation:{}:{}".format(name, field),
                    details={"field": field, "donor": dead[field]}))
        for field in step.get("donates", ()):
            dead.setdefault(field, name)
        for field in step.get("produces", ()):
            dead.pop(field, None)
    return findings


# ------------------------------------------------------- dtype promotion
def taint_vector(spec):
    """Per-flat-leaf taint seeds: low-precision leaves under the spec's
    taint_paths."""
    taint = []
    for _, path, leaf in flat_arg_leaves(spec.args):
        aval = _abstract(leaf)
        low = str(aval.dtype) in ("bfloat16", "float16")
        taint.append(low and _match_prefix(path, spec.taint_paths))
    return taint


def dtype_findings(spec, walk_result, fp32_allowlist=()):
    """fp32 GEMMs whose operand IS a (cast) bf16/fp16 param.

    The param-passthrough taint channel flags values that are still the
    weight itself after casts/layout moves/gathers — so a weight upcast
    into a float32 matmul fires, while intentional fp32 stability
    islands over ACTIVATIONS (attention scores/softmax, the loss, norm
    statistics, the fp32 Adam math) stay naturally exempt."""
    findings = []
    seen = set()
    for info in walk_result.eqns:
        if info.prim not in GEMM_PRIMS:
            continue
        if info.prim in fp32_allowlist:
            continue
        hot = False
        for i, v in enumerate(info.eqn.invars):
            aval = getattr(v, "aval", None)
            if aval is None or not hasattr(aval, "dtype"):
                continue
            if str(aval.dtype) == "float32" and \
                    i < len(info.in_taint2) and info.in_taint2[i]:
                hot = True
                break
        if not hot:
            continue
        out_shape = tuple(info.eqn.outvars[0].aval.shape) \
            if info.eqn.outvars else ()
        dedup = (info.path, out_shape)
        if dedup in seen:
            continue
        seen.add(dedup)
        findings.append(Finding(
            rule="dtype_promotion", check="fp32_gemm_from_bf16",
            program=spec.name,
            message="program {!r} feeds a bf16/fp16 param UPCAST to "
                    "float32 into a {} (out {}) at {} — the fp32 leak "
                    "drags the whole GEMM off the bf16 MXU path; cast "
                    "the weight back to the compute dtype, or allowlist "
                    "the op via analysis.fp32_allowlist".format(
                        spec.name, info.prim, list(out_shape), info.path),
            key="fp32_gemm_from_bf16:{}:{}".format(spec.name, info.path),
            details={"prim": info.prim, "path": info.path,
                     "out_shape": list(out_shape),
                     "trips": info.trips}))
    return findings


# ------------------------------------------------ host-sync / recompile
def host_sync_findings(spec, walk_result):
    findings = []
    for info in walk_result.by_prim(*HOST_PRIMS):
        findings.append(Finding(
            rule="host_sync", check="host_callback", program=spec.name,
            message="program {!r} traces a {!r} op at {} — a host "
                    "callback under jit forces a device<->host sync "
                    "every call (and pins the step to host latency); "
                    "move it outside the jitted step or behind a "
                    "debug-only gate".format(spec.name, info.prim,
                                             info.path),
            key="host_callback:{}:{}".format(spec.name, info.prim),
            details={"prim": info.prim, "path": info.path,
                     "trips": info.trips}))
    return findings


def hazard_findings(spec,
                    storm_threshold=RECOMPILE_STORM_THRESHOLD_DEFAULT):
    """Ahead-of-time recompile hazards: weak-typed (Python-scalar)
    operands and program families whose static key space exceeds the
    storm threshold."""
    findings = []
    for _, path, leaf in flat_arg_leaves(spec.args):
        if _match_prefix(path, spec.allow_weak):
            continue
        aval = _abstract(leaf)
        if getattr(aval, "weak_type", False):
            findings.append(Finding(
                rule="host_sync", check="weak_typed_operand",
                program=spec.name,
                message="program {!r} operand {} is weak-typed (a bare "
                        "Python scalar reached the jit boundary) — call "
                        "sites that mix scalar kinds fragment the "
                        "compile cache; pass jnp.asarray(x, dtype) "
                        "instead (or declare the block stable via the "
                        "spec's allow_weak)".format(spec.name, path),
                key="weak_typed_operand:{}:{}".format(spec.name, path),
                details={"path": path, "dtype": str(aval.dtype)}))
    if spec.trace_bound is not None:
        f = recompile_storm_finding(
            spec.name, int(spec.trace_bound), storm_threshold,
            hint="its static key space already exceeds the threshold — "
                 "bound it (e.g. inference.prefill_buckets)")
        if f is not None:
            findings.append(f)
    return findings


# ------------------------------------------------------- sharding drift
def _spec_mentions(sharding, axes):
    spec = getattr(sharding, "spec", None)
    if spec is None:
        return False
    for entry in spec:
        cands = entry if isinstance(entry, tuple) else (entry,)
        if any(ax in axes for ax in cands):
            return True
    return False


def sharding_findings(spec, walk_result,
                      replicated_leaf_bytes=REPLICATED_LEAF_BYTES_DEFAULT):
    """Replicated-input audit (shared core) + the sharding-constraint
    census against the plan's expectation."""
    findings = []
    n_dev = 1
    if spec.mesh is not None:
        n_dev = int(np.prod(list(dict(spec.mesh.shape).values()),
                            dtype=np.int64))
    if n_dev > 1:
        for _, path, leaf in flat_arg_leaves(spec.args):
            sharding = _leaf_sharding(leaf)
            if sharding is None or \
                    not getattr(sharding, "is_fully_replicated", False):
                continue
            f = replicated_leaf_finding(
                spec.name, path, _leaf_nbytes(leaf), n_dev,
                replicated_leaf_bytes)
            if f is not None:
                findings.append(f)
    if spec.expected_constraints > 0 and spec.constraint_axes:
        axes = set(spec.constraint_axes)
        count = 0
        for info in walk_result.by_prim("sharding_constraint"):
            if _spec_mentions(info.eqn.params.get("sharding"), axes):
                count += 1
        if count < spec.expected_constraints:
            findings.append(Finding(
                rule="sharding_drift", check="missing_sharding_constraint",
                program=spec.name,
                message="program {!r} carries {} sharding constraints "
                        "naming the plan's data axes {} but the resolved "
                        "ZeroShardingPlan expects at least {} — a "
                        "with_sharding_constraint was dropped and XLA is "
                        "free to place (and all-gather) that state "
                        "behind your back".format(
                            spec.name, count, sorted(axes),
                            spec.expected_constraints),
                key="missing_sharding_constraint:{}".format(spec.name),
                details={"found": count,
                         "expected": spec.expected_constraints,
                         "axes": sorted(axes)}))
    return findings


# ------------------------------------------------------------- auditing
def audit_program(spec, config=None):
    """Run every jaxpr-level rule class on one ProgramSpec.

    Returns (closed_jaxpr, walk_result, [Finding]); tracing errors
    surface as an ``audit_error`` finding rather than killing the whole
    report."""
    cfg = config
    storm = getattr(cfg, "storm_threshold",
                    RECOMPILE_STORM_THRESHOLD_DEFAULT)
    repl = getattr(cfg, "replicated_leaf_bytes",
                   REPLICATED_LEAF_BYTES_DEFAULT)
    don = getattr(cfg, "donation_min_bytes", DONATION_MIN_BYTES_DEFAULT)
    allow = tuple(getattr(cfg, "fp32_allowlist", ()) or ())
    try:
        fn = spec.build()
        closed = jax.make_jaxpr(fn)(*spec.args)
    except Exception as err:  # noqa: BLE001 - report, don't die
        return None, None, [Finding(
            rule="host_sync", check="audit_error", program=spec.name,
            severity="error",
            message="program {!r} could not be abstract-evaluated: "
                    "{}".format(spec.name, err),
            key="audit_error:{}".format(spec.name),
            details={"error": repr(err)})]
    taint = taint_vector(spec)
    walk_result = walk(closed, taint_in=taint, taint2_in=taint)
    findings = []
    findings += sharding_findings(spec, walk_result,
                                  replicated_leaf_bytes=repl)
    findings += donation_findings(spec, closed, min_bytes=don)
    findings += dtype_findings(spec, walk_result, fp32_allowlist=allow)
    findings += host_sync_findings(spec, walk_result)
    findings += hazard_findings(spec, storm_threshold=storm)
    return closed, walk_result, findings
