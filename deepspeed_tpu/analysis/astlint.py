"""Repo-wide AST hot-path linter (the ``bin/ds_lint.py`` core).

Static rules for the anti-patterns that degrade step time without ever
failing a test — each is a hazard the runtime telemetry can only see
AFTER the cost is paid:

  * **DSL001 time-in-traced-fn** — ``time.time()`` /
    ``time.monotonic()`` / ``time.perf_counter()`` inside a function
    NESTED in a ``*_fn`` builder (the repo's traced-program
    convention). Wall-clock reads trace as constants: the timing is a
    lie and the closure re-traces on nothing.
  * **DSL002 device-put-in-loop** — ``jax.device_put`` inside a
    ``for``/``while`` body: one un-jitted dispatch per leaf per
    iteration (the T3 finding the coalesced H2D batcher exists to
    kill; runtime/zero/transfer.py).
  * **DSL003 telemetry-gate-missing** — a ``<x>.telemetry.<attr>``
    read in a function with no ``telemetry``-None guard: the telemetry
    object is None whenever the config section is off, so the ungated
    access is a latent AttributeError on every production path.
  * **DSL004 jit-in-loop** — ``jax.jit(...)`` called inside a loop
    body: a fresh jit wrapper (and trace) per iteration; hoist the jit
    (or cache by key, the ``_get_jit`` pattern).
  * **DSL005 pallas-call-outside-ops** — a ``pl.pallas_call`` site
    outside ``deepspeed_tpu/ops/``: hand-written kernels live in ONE
    place (ops/pallas and the op packages; docs/pallas_kernels.md is
    the inventory), so dispatch layers import kernels rather than
    inlining them.
  * **DSL007 metric-name-outside-catalog** — a string-literal metric
    name passed to a ``.counter()``/``.gauge()``/``.histogram()``
    registry call that does not appear in docs/fleet.md's metric
    catalog: every exported series must be documented (name + labels)
    before it ships, or scrapers chase undocumented gauges
    (docs/fleet.md; the rule is inert when the catalog file is absent).
  * **DSL006 step-scheduling-outside-executor** — hand-written step
    scheduling outside ``deepspeed_tpu/runtime/executor/``: an async
    transfer issue (``copy_to_host_async``), a worker pool
    (``ThreadPoolExecutor`` / ``make_upload_pool``), or a donation
    declaration (a ``donate_argnums=`` call keyword). Since ISSUE 13
    the segment executor owns overlap construction, phase timing and
    donation for every step path; the surviving legacy sites (pipe
    engine, jit caches, the transfer batcher internals, the audit
    layer reading declarations) are baselined — NEW occurrences fail
    CI so new paths lower onto the executor instead of growing a
    seventh bespoke scheduler (docs/executor.md).

  * **DSL008 guarded-mutation-outside-lock** — a mutating call /
    subscript assign on a ``self.<attr>`` the class declares in its
    ``_GUARDED_BY`` map, with no enclosing ``with self.<lock>:`` for
    the declared lock. The static twin of the dynamic guarded-state
    checker (analysis/concurrency/locksan.py): the AST rule catches
    sites a run never exercised, the runtime proxy catches the threads
    the AST cannot see (``__init__`` is exempt — construction
    happens-before publication).
  * **DSL009 thread-without-daemon-story** — ``threading.Thread(...)``
    constructed without a ``daemon=`` keyword: the thread's lifetime is
    undeclared, and a non-daemon thread with no join/close path holds
    the interpreter open on every crash (docs/concurrency.md).
  * **DSL011 pallas-call-without-cost-estimate** — a ``pl.pallas_call``
    under ``deepspeed_tpu/ops/`` with no ``cost_estimate=`` keyword: a
    custom call XLA prices at zero flops silently corrupts MFU
    accounting and the bench scoreboard's regression gate the moment
    the kernel lands on a hot path. Every kernel declares its
    ``pl.CostEstimate`` (docs/pallas_kernels.md).
  * **DSL010 serving-field-outside-schema** — a dict literal tagged
    ``"kind": "serving_step"`` carrying a string key that is NOT in
    telemetry/record.py's pinned ``SERVING_STEP_KEYS`` /
    ``SERVING_SUBDICT_KEYS`` tables: a hand-rolled serving record with
    a freelance field ships a schema drift the validators then chase
    (record.py itself is exempt — it IS the schema; the rule is inert
    when the schema file is absent, so partial checkouts never
    false-fail).

Violations key as ``DSL###:<relpath>::<qualname>`` and count per key —
the committed baseline file maps keys to accepted counts, so existing
(reviewed) occurrences stay green while any NEW occurrence fails.
"""
import ast
import json
import os

from .findings import Finding

LINT_RULES = {
    "DSL001": "time-in-traced-fn",
    "DSL002": "device-put-in-loop",
    "DSL003": "telemetry-gate-missing",
    "DSL004": "jit-in-loop",
    "DSL005": "pallas-call-outside-ops",
    "DSL006": "step-scheduling-outside-executor",
    "DSL007": "metric-name-outside-catalog",
    "DSL008": "guarded-mutation-outside-lock",
    "DSL009": "thread-without-daemon-story",
    "DSL010": "serving-field-outside-schema",
    "DSL011": "pallas-call-without-cost-estimate",
}

# DSL008: mutating container methods (the static twin of the dynamic
# checker in concurrency/locksan.py — the AST rule catches the sites a
# run never exercised, the proxy catches the threads the AST cannot
# see)
_DSL008_MUTATORS = frozenset({
    "append", "appendleft", "extend", "extendleft", "insert", "pop",
    "popleft", "popitem", "remove", "discard", "clear", "add", "update",
    "setdefault", "sort", "reverse", "rotate",
})
# the class-level declaration both checkers read
_GUARDED_BY_NAME = "_GUARDED_BY"

# DSL007: registry-call method names + the metric-name literal shape
_METRIC_METHODS = {"counter", "gauge", "histogram"}
_METRIC_NAME_RE = None          # compiled lazily (module stays light)


def _looks_like_metric_name(text):
    global _METRIC_NAME_RE
    if _METRIC_NAME_RE is None:
        import re
        _METRIC_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*$")
    return bool(_METRIC_NAME_RE.match(text))


def load_metric_catalog(base):
    """docs/fleet.md's text, the DSL007 catalog — None (rule inert)
    when the file is absent so partial checkouts never false-fail."""
    path = os.path.join(base or ".", "docs", "fleet.md")
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        return fh.read()


# DSL010: the module that IS the serving-record schema (exempt from
# the rule), and the two pinned tables the rule reads out of it
_SERVING_SCHEMA_MODULE = "deepspeed_tpu/telemetry/record.py"


def load_serving_schema(base):
    """The serving-record field vocabulary (SERVING_STEP_KEYS +
    SERVING_SUBDICT_KEYS keys), AST-read from telemetry/record.py —
    None (DSL010 inert) when the schema file is absent or unreadable
    so partial checkouts never false-fail."""
    path = os.path.join(base or ".", *_SERVING_SCHEMA_MODULE.split("/"))
    if not os.path.exists(path):
        return None
    with open(path) as fh:
        try:
            tree = ast.parse(fh.read(), filename=path)
        except SyntaxError:
            return None
    fields = set()
    for node in ast.iter_child_nodes(tree):
        if not isinstance(node, ast.Assign):
            continue
        names = {t.id for t in node.targets if isinstance(t, ast.Name)}
        if "SERVING_STEP_KEYS" in names and \
                isinstance(node.value, (ast.Tuple, ast.List)):
            fields.update(
                elt.value for elt in node.value.elts
                if isinstance(elt, ast.Constant) and
                isinstance(elt.value, str))
        if "SERVING_SUBDICT_KEYS" in names and \
                isinstance(node.value, ast.Dict):
            fields.update(
                k.value for k in node.value.keys
                if isinstance(k, ast.Constant) and
                isinstance(k.value, str))
    return frozenset(fields) or None

# DSL005: the one directory kernels may live in
_OPS_PREFIX = "deepspeed_tpu/ops/"
# DSL006: the one directory step-scheduling machinery may live in
_EXECUTOR_PREFIX = "deepspeed_tpu/runtime/executor/"

_TIME_FNS = {"time", "monotonic", "perf_counter"}


def _attr_chain(node):
    """Attribute node -> dotted string tail ('self.telemetry.spans')."""
    parts = []
    while isinstance(node, ast.Attribute):
        parts.append(node.attr)
        node = node.value
    if isinstance(node, ast.Name):
        parts.append(node.id)
    return ".".join(reversed(parts))


class _FunctionLint(ast.NodeVisitor):
    """Per-function-body state: loop depth, telemetry guards/uses,
    enclosing ``with <lock>`` scopes (DSL008)."""

    def __init__(self, linter, qualname, in_builder, guarded=None):
        self.linter = linter
        self.qualname = qualname
        self.in_builder = in_builder       # nested under a *_fn builder
        self.loop_depth = 0
        self.telemetry_guarded = False
        self.telemetry_aliases = set()
        self.telemetry_uses = []           # [lineno]
        # DSL008 state: the owning class's _GUARDED_BY map and the
        # stack of lock attr names entered via `with self.<lock>:`
        self.guarded = guarded or {}
        self.with_locks = []

    # ---- nested functions delegate back to the linter (fresh state)
    def visit_FunctionDef(self, node):
        self.linter.visit_function(
            node, self.qualname,
            self.in_builder or self.qualname.endswith("_fn"),
            guarded=self.guarded)

    visit_AsyncFunctionDef = visit_FunctionDef

    # ------------------------------------------------------------ DSL008
    def visit_With(self, node):
        entered = set()
        for item in node.items:
            expr = item.context_expr
            chain = _attr_chain(expr) if isinstance(expr, ast.Attribute) \
                else ""
            if chain.startswith("self."):
                entered.add(chain.split(".")[-1])
        self.with_locks.append(entered)
        self.generic_visit(node)
        self.with_locks.pop()

    visit_AsyncWith = visit_With

    def _held_locks(self):
        held = set()
        for scope in self.with_locks:
            held |= scope
        return held

    def _guarded_attr_of(self, node):
        """'attr' when ``node`` is ``self.<attr>`` and the class
        declares it _GUARDED_BY; None otherwise."""
        if isinstance(node, ast.Attribute) and \
                isinstance(node.value, ast.Name) and \
                node.value.id == "self" and node.attr in self.guarded:
            return node.attr
        return None

    def _check_guarded_mutation(self, attr, lineno, how):
        # __init__ builds the structure before any thread can see it
        if attr is None or self.qualname.endswith("__init__"):
            return
        lock = self.guarded[attr]
        if lock in self._held_locks():
            return
        self.linter.report(
            "DSL008", self.qualname, lineno,
            "self.{} mutated ({}) outside `with self.{}` — the class "
            "declares it _GUARDED_BY that lock "
            "(docs/concurrency.md)".format(attr, how, lock))

    def visit_AugAssign(self, node):
        tgt = node.target
        if isinstance(tgt, ast.Subscript):
            self._check_guarded_mutation(
                self._guarded_attr_of(tgt.value), node.lineno,
                "augmented subscript assign")
        self.generic_visit(node)

    def visit_For(self, node):
        self.loop_depth += 1
        self.generic_visit(node)
        self.loop_depth -= 1

    visit_While = visit_For

    def visit_Assign(self, node):
        # alias: tel = self.telemetry (guards on the alias count)
        if isinstance(node.value, ast.Attribute) and \
                node.value.attr == "telemetry":
            for tgt in node.targets:
                if isinstance(tgt, ast.Name):
                    self.telemetry_aliases.add(tgt.id)
        # DSL008: self.<guarded>[k] = v outside the declared lock
        for tgt in node.targets:
            if isinstance(tgt, ast.Subscript):
                self._check_guarded_mutation(
                    self._guarded_attr_of(tgt.value), node.lineno,
                    "subscript assign")
        self.generic_visit(node)

    def _guards_telemetry(self, expr):
        """Whether ``expr`` mentions telemetry (or an alias), through
        ``not`` and boolean composition — a truthiness test like
        ``if self.telemetry:`` IS a None-gate in idiomatic Python."""
        if isinstance(expr, (ast.Attribute, ast.Name)):
            chain = _attr_chain(expr)
            return "telemetry" in chain or \
                chain in self.telemetry_aliases
        if isinstance(expr, ast.UnaryOp) and isinstance(expr.op, ast.Not):
            return self._guards_telemetry(expr.operand)
        if isinstance(expr, ast.BoolOp):
            return any(self._guards_telemetry(v) for v in expr.values)
        return False

    def visit_Compare(self, node):
        # <expr> is [not] None where <expr> mentions telemetry/an alias
        if any(isinstance(op, (ast.Is, ast.IsNot)) for op in node.ops):
            for operand in [node.left] + list(node.comparators):
                if self._guards_telemetry(operand):
                    self.telemetry_guarded = True
        self.generic_visit(node)

    def visit_If(self, node):
        if self._guards_telemetry(node.test):
            self.telemetry_guarded = True
        self.generic_visit(node)

    def visit_IfExp(self, node):
        if self._guards_telemetry(node.test):
            self.telemetry_guarded = True
        self.generic_visit(node)

    def visit_Attribute(self, node):
        # <x>.telemetry.<attr> read
        if isinstance(node.value, ast.Attribute) and \
                node.value.attr == "telemetry":
            self.telemetry_uses.append(node.lineno)
        self.generic_visit(node)

    # ------------------------------------------------------------ DSL010
    def visit_Dict(self, node):
        schema = self.linter.serving_schema
        if schema is not None and not self.linter.is_serving_schema:
            keys = {}
            for k, v in zip(node.keys, node.values):
                if isinstance(k, ast.Constant) and \
                        isinstance(k.value, str):
                    keys[k.value] = v
            kind = keys.get("kind")
            if isinstance(kind, ast.Constant) and \
                    kind.value == "serving_step":
                for name in sorted(set(keys) - set(schema)):
                    self.linter.report(
                        "DSL010", self.qualname, node.lineno,
                        "serving_step record literal carries field "
                        "{!r} outside telemetry/record.py's pinned "
                        "SERVING_STEP_KEYS/SERVING_SUBDICT_KEYS — "
                        "extend the schema tables (and their stdlib "
                        "copies) instead of freelancing a "
                        "field".format(name))
        self.generic_visit(node)

    def visit_Call(self, node):
        fn = node.func
        chain = _attr_chain(fn) if isinstance(fn, ast.Attribute) else ""
        if chain.startswith("time.") and \
                chain.split(".")[-1] in _TIME_FNS and self.in_builder:
            self.linter.report("DSL001", self.qualname, node.lineno,
                               "{}() inside a traced-fn builder body "
                               "traces as a constant".format(chain))
        if chain.endswith(".device_put") and self.loop_depth > 0:
            self.linter.report("DSL002", self.qualname, node.lineno,
                               "jax.device_put inside a loop body — one "
                               "un-jitted dispatch per iteration "
                               "(coalesce via the H2D batcher)")
        if chain == "jax.jit" and self.loop_depth > 0:
            self.linter.report("DSL004", self.qualname, node.lineno,
                               "jax.jit inside a loop body — a fresh "
                               "trace per iteration (hoist or cache by "
                               "key)")
        is_pallas_call = chain.endswith(".pallas_call") or (
            isinstance(fn, ast.Name) and fn.id == "pallas_call")
        catalog = self.linter.metric_catalog
        if catalog is not None and isinstance(fn, ast.Attribute) and \
                fn.attr in _METRIC_METHODS and node.args:
            arg = node.args[0]
            if isinstance(arg, ast.Constant) and \
                    isinstance(arg.value, str) and \
                    _looks_like_metric_name(arg.value) and \
                    arg.value not in catalog:
                self.linter.report(
                    "DSL007", self.qualname, node.lineno,
                    "metric {!r} is not in docs/fleet.md's catalog — "
                    "document every exported series (name + labels) "
                    "before shipping it".format(arg.value))
        if is_pallas_call and not self.linter.in_ops:
            self.linter.report("DSL005", self.qualname, node.lineno,
                               "pl.pallas_call outside deepspeed_tpu/"
                               "ops/ — kernels live in one place "
                               "(ops/pallas; docs/pallas_kernels.md)")
        # DSL011: every kernel in ops/ must declare its price — a
        # custom call without a CostEstimate reads as zero flops to
        # XLA's cost model, silently corrupting MFU and the scoreboard
        # regression gate the moment the kernel lands on a hot path.
        if is_pallas_call and self.linter.in_ops and \
                not any(kw.arg == "cost_estimate" for kw in node.keywords):
            self.linter.report(
                "DSL011", self.qualname, node.lineno,
                "pl.pallas_call without cost_estimate= — a zero-flop "
                "custom call corrupts MFU pricing and the scoreboard "
                "gate (pass pl.CostEstimate(flops=..., "
                "bytes_accessed=..., transcendentals=...); "
                "docs/pallas_kernels.md)")
        # DSL008: mutating-method call on a declared-guarded attribute
        if isinstance(fn, ast.Attribute) and fn.attr in _DSL008_MUTATORS:
            self._check_guarded_mutation(
                self._guarded_attr_of(fn.value), node.lineno,
                ".{}()".format(fn.attr))
        # DSL009: a thread constructed with no daemon story — a
        # non-daemon thread with no declared join/close path holds the
        # interpreter open on every crash (the repo's threads are
        # daemon + joined-with-timeout in close(); a reviewed baseline
        # entry is how a deliberate non-daemon thread ships)
        if chain == "threading.Thread" and \
                not any(kw.arg == "daemon" for kw in node.keywords):
            self.linter.report(
                "DSL009", self.qualname, node.lineno,
                "threading.Thread(...) without daemon= — declare the "
                "thread's lifetime (daemon=True, or daemon=False with "
                "a reviewed join/close story; docs/concurrency.md)")
        if not self.linter.in_executor:
            name_id = fn.id if isinstance(fn, ast.Name) else ""
            sched = None
            # split-tail match: a subscripted receiver
            # (bufs[0].copy_to_host_async()) truncates the chain to the
            # bare attribute name
            if chain.split(".")[-1] == "copy_to_host_async":
                sched = "async transfer issue (copy_to_host_async)"
            elif chain.endswith("ThreadPoolExecutor") or \
                    name_id == "ThreadPoolExecutor":
                sched = "worker pool (ThreadPoolExecutor)"
            elif chain.endswith("make_upload_pool") or \
                    name_id == "make_upload_pool":
                sched = "upload worker (make_upload_pool)"
            elif any(kw.arg == "donate_argnums"
                     for kw in node.keywords):
                sched = "donation declaration (donate_argnums=)"
            if sched:
                self.linter.report(
                    "DSL006", self.qualname, node.lineno,
                    "{} outside deepspeed_tpu/runtime/executor/ — "
                    "step scheduling lowers onto the segment executor "
                    "(docs/executor.md)".format(sched))
        self.generic_visit(node)

    def finish(self):
        if self.telemetry_uses and not self.telemetry_guarded:
            self.linter.report(
                "DSL003", self.qualname, self.telemetry_uses[0],
                "reads .telemetry.<attr> with no is-None gate in the "
                "function — telemetry is None whenever the config "
                "section is off")


class FileLinter:
    def __init__(self, relpath, metric_catalog=None,
                 serving_schema=None):
        self.relpath = relpath
        norm = relpath.replace(os.sep, "/")
        self.in_ops = norm.startswith(_OPS_PREFIX)
        self.in_executor = norm.startswith(_EXECUTOR_PREFIX)
        self.metric_catalog = metric_catalog
        self.serving_schema = serving_schema
        self.is_serving_schema = norm == _SERVING_SCHEMA_MODULE
        self.violations = []       # [(rule, qualname, lineno, message)]

    def report(self, rule, qualname, lineno, message):
        self.violations.append((rule, qualname, lineno, message))

    def visit_function(self, node, parent_qual, in_builder,
                       guarded=None):
        qual = "{}.{}".format(parent_qual, node.name) if parent_qual \
            else node.name
        state = _FunctionLint(self, qual, in_builder, guarded=guarded)
        for stmt in node.body:
            state.visit(stmt)
        state.finish()

    @staticmethod
    def _guarded_decl(class_node):
        """The class's ``_GUARDED_BY`` literal ({attr: lock_attr}), or
        {} — the DSL008 declaration (shared with the dynamic checker,
        concurrency/locksan.py)."""
        for stmt in class_node.body:
            if not isinstance(stmt, ast.Assign):
                continue
            if not any(isinstance(t, ast.Name) and
                       t.id == _GUARDED_BY_NAME for t in stmt.targets):
                continue
            if not isinstance(stmt.value, ast.Dict):
                return {}
            decl = {}
            for k, v in zip(stmt.value.keys, stmt.value.values):
                if isinstance(k, ast.Constant) and \
                        isinstance(k.value, str) and \
                        isinstance(v, ast.Constant) and \
                        isinstance(v.value, str):
                    decl[k.value] = v.value
            return decl
        return {}

    def run(self, tree):
        # walk module/class levels; functions get per-body state
        def top(node, prefix, guarded):
            for child in ast.iter_child_nodes(node):
                if isinstance(child, (ast.FunctionDef,
                                      ast.AsyncFunctionDef)):
                    self.visit_function(child, prefix, False,
                                        guarded=guarded)
                elif isinstance(child, ast.ClassDef):
                    name = "{}.{}".format(prefix, child.name) if prefix \
                        else child.name
                    top(child, name, self._guarded_decl(child))
        top(tree, "", {})
        return self.violations


def lint_file(path, relpath=None, metric_catalog=None,
              serving_schema=None):
    relpath = relpath or path
    with open(path) as fh:
        source = fh.read()
    try:
        tree = ast.parse(source, filename=path)
    except SyntaxError as err:
        return [("DSL000", "<module>", getattr(err, "lineno", 0),
                 "unparseable: {}".format(err))]
    return FileLinter(relpath, metric_catalog=metric_catalog,
                      serving_schema=serving_schema).run(tree)


def lint_paths(paths, base=None, metric_catalog=None,
               serving_schema=None):
    """-> {key: [Finding, ...]} over every .py file under ``paths``
    (key = 'RULE:relpath::qualname'; ``base`` anchors the relpaths —
    pass the repo root so baseline keys are stable under any cwd).
    ``metric_catalog``: DSL007's documented-name text; defaults to
    ``base``/docs/fleet.md when present. ``serving_schema``: DSL010's
    field vocabulary; defaults to the tables AST-read from
    ``base``/deepspeed_tpu/telemetry/record.py when present."""
    findings = {}
    files = []
    for root in paths:
        if os.path.isfile(root):
            files.append(root)
            continue
        for dirpath, _, names in os.walk(root):
            files += [os.path.join(dirpath, n) for n in sorted(names)
                      if n.endswith(".py")]
    base = base or os.getcwd()
    if metric_catalog is None:
        metric_catalog = load_metric_catalog(base)
    if serving_schema is None:
        serving_schema = load_serving_schema(base)
    for path in sorted(files):
        rel = os.path.relpath(path, base)
        for rule, qual, lineno, message in lint_file(
                path, rel, metric_catalog=metric_catalog,
                serving_schema=serving_schema):
            key = "{}:{}::{}".format(rule, rel.replace(os.sep, "/"), qual)
            findings.setdefault(key, []).append(Finding(
                rule=rule, check=LINT_RULES.get(rule, rule),
                program=rel.replace(os.sep, "/"),
                message="{}:{} [{}] {}".format(rel, lineno, rule, message),
                key=key,
                details={"line": lineno, "qualname": qual}))
    return findings


def load_baseline(path):
    if path is None or not os.path.exists(path):
        return {}
    with open(path) as fh:
        payload = json.load(fh)
    if not isinstance(payload, dict) or \
            not isinstance(payload.get("violations"), dict):
        raise ValueError(
            "{}: baseline must be an object with a 'violations' "
            "map".format(path))
    return {str(k): int(v) for k, v in payload["violations"].items()}


def write_baseline(path, findings):
    payload = {
        "comment": "ds_lint baseline: accepted (reviewed) hot-path lint "
                   "occurrences by key; regenerate with "
                   "bin/ds_lint.py --write-baseline",
        "violations": {k: len(v) for k, v in sorted(findings.items())},
    }
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
        fh.write("\n")
    return path


def diff_baseline(findings, baseline):
    """-> (new, stale): findings above their baselined count, and
    baseline keys no longer observed (candidates to prune)."""
    new = []
    for key, items in sorted(findings.items()):
        allowed = baseline.get(key, 0)
        if len(items) > allowed:
            new.extend(items[allowed:])
    stale = sorted(k for k in baseline if k not in findings)
    return new, stale
