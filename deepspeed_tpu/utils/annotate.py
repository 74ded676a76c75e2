"""The program's own boundaries in the PROFILER's trace
(docs/telemetry.md, "Program spans"), where the device's operations lie
on the same clock. A leaf: it imports nothing of this package, so the
timers, the engines and the plan executor can all use it. It has no
recorder, sink or switch of its own.

Beside it, the start-up record (docs/telemetry.md, "Start-up record"):
what a process spent between ``import deepspeed_tpu`` and its first
useful step, as rows kept in memory on ``time.perf_counter``.
:func:`setup_span` writes one at each boundary of an engine's
construction, ``utils/compile_cache.py`` one at each program's FIRST
call; no later call writes any. :func:`setup_record` returns them.
"""
import contextlib
import copy
import threading
import time

try:
    from jax.profiler import TraceAnnotation as _TraceAnnotation
except Exception:  # noqa: BLE001 - the timers must work without jax
    _TraceAnnotation = None
_NO_ANNOTATION = contextlib.nullcontext()


def annotate(name, **attrs):
    """Context manager that marks ``name`` (with ``attrs`` as the
    event's statistics) on the calling thread in the profiler's trace.
    Recorded only while a profiler session is active — an operator's
    ``telemetry.trace`` window, or any ``jax.profiler.start_trace`` —
    and otherwise an object made and dropped (under a microsecond).
    Nesting on one thread is the parent link. Pass only values already
    at hand, and keep it out of per-slot and per-token loops."""
    if _TraceAnnotation is None:
        return _NO_ANNOTATION
    return _TraceAnnotation(name, **attrs)


# -------------------------------------------------------- device scopes
# The names the program's ``jax.named_scope`` blocks give its mechanisms
# (docs/telemetry.md, "Device scopes": what each wraps, and which families
# have it). A scope ends up as a component of the ``op_name`` of every
# instruction traced under it; ``utils/compile_cache.program_scopes()`` reads
# them back. A test holds every ``named_scope`` literal to this list.
DEVICE_SCOPES = (
    "embed", "head", "head.loss", "sample", "optim.step", "mlp",
    "attn.proj", "attn.prefill", "attn.decode", "kv.write",
    "attn.window", "attn.full", "attn.chunk_blocks",
    "mamba.proj", "mamba.scan", "mamba.step", "short_conv",
    "moe.route", "moe.dispatch", "moe.combine", "moe.shared",
    "mla.project", "mla.kv_up", "mla.prefill_attn", "mla.absorb",
    "gdn.proj", "gdn.conv", "gdn.chunk", "gdn.step", "gdn.norm",
    "ssd.proj", "ssd.conv", "ssd.chunk", "ssd.step", "ssd.norm",
)


# ------------------------------------------------------ start-up record
SETUP_ROWS_MAX = 1024      # the last of them counts the rows dropped
_setup_rows = []
_setup_lock = threading.Lock()
_setup_open = threading.local()    # .spans: the rows open on this thread
_engines = {}                      # kind -> engines tagged so far


def _open_spans():
    try:
        return _setup_open.spans
    except AttributeError:
        spans = _setup_open.spans = []
        return spans


def open_setup_span():
    """The innermost set-up span open on the calling thread (its row,
    still without an end), or None."""
    spans = _open_spans()
    return spans[-1] if spans else None


def engine_tag(kind):
    """The ``engine`` attribute of an engine's rows: that of the
    ``setup.engine`` span of this ``kind`` open on the calling thread
    (``init_inference()`` opened it, the constructor asks), else a new
    one, ``<kind>-<n>`` (no ``#``, ``,`` or ``=``: a TraceAnnotation
    writes its attributes as ``name#key=value,...#``)."""
    for row in reversed(_open_spans()):
        if row["name"] == "setup.engine" and \
                row["attrs"].get("kind") == kind:
            return row["attrs"]["engine"]
    with _setup_lock:
        n = _engines[kind] = _engines.get(kind, 0) + 1
    return "{}-{}".format(kind, n)


def record_setup_row(row):
    """Append a finished row to the start-up record. The list is
    bounded: past ``SETUP_ROWS_MAX - 1`` rows a row is dropped, and one
    last row ``setup.dropped`` [rows] counts them."""
    with _setup_lock:
        if len(_setup_rows) < SETUP_ROWS_MAX - 1:
            _setup_rows.append(row)
        elif len(_setup_rows) < SETUP_ROWS_MAX:
            _setup_rows.append({
                "name": "setup.dropped", "start_s": row["start_s"],
                "end_s": row["end_s"], "parent": None,
                "attrs": {"rows": 1}})
        else:
            last = _setup_rows[-1]
            last["end_s"] = row["end_s"]
            last["attrs"]["rows"] += 1


def new_setup_row(name, start_s, end_s=None, **attrs):
    """A row ``{name, start_s, end_s, parent, attrs}`` under the
    innermost span open on this thread, whose ``engine`` it takes."""
    parent = open_setup_span()
    if parent is not None and "engine" in parent["attrs"]:
        attrs.setdefault("engine", parent["attrs"]["engine"])
    return {"name": name, "start_s": start_s, "end_s": end_s,
            "parent": parent["name"] if parent is not None else None,
            "attrs": attrs}


@contextlib.contextmanager
def setup_span(name, **attrs):
    """:func:`annotate`'s sibling for a boundary of set-up, which is
    over before a benchmark's profiler session can be open: the same
    ``TraceAnnotation`` (an operator's trace started before
    ``init_inference()`` shows it) plus one row of the start-up record.
    Yields the row's ``attrs``, for what is known only at the end
    (``bytes``). Not for a step: a row is written every time."""
    row = new_setup_row(name, None, **attrs)
    spans = _open_spans()
    spans.append(row)
    with annotate(name, **attrs):
        row["start_s"] = time.perf_counter()
        try:
            yield row["attrs"]
        finally:
            row["end_s"] = time.perf_counter()
            spans.pop()
            record_setup_row(row)


def is_trailing_setup_row(row):
    """Whether ``row`` (the object itself) is still the record's last
    word: only rows of its own name were written after it."""
    with _setup_lock:
        for last in reversed(_setup_rows):
            if last is row:
                return True
            if last["name"] != row["name"]:
                return False
    return False


def setup_record():
    """The start-up record: the rows written so far, oldest end first,
    each ``{name, start_s, end_s, parent, attrs}`` with times in seconds
    on ``time.perf_counter``. Names and attributes: docs/telemetry.md,
    "Start-up record"."""
    with _setup_lock:
        return copy.deepcopy(_setup_rows)


def startup_report(engine):
    """One engine's rows (those whose ``engine`` attribute is its tag),
    as ``{"engine", "rows", "seconds"}``: ``seconds`` sums the rows by
    name, the children of ``setup.engine`` among them."""
    rows = [row for row in setup_record()
            if row["attrs"].get("engine") == engine]
    seconds = {}
    for row in rows:
        seconds[row["name"]] = seconds.get(row["name"], 0.0) + \
            row["end_s"] - row["start_s"]
    return {"engine": engine, "rows": rows, "seconds": seconds}


def startup_line(engine):
    """The line an engine logs when ``initialize()`` or
    ``init_inference()`` returns: the phases of its ``setup.engine``
    and their seconds, and the small programs made meanwhile."""
    report = startup_report(engine)
    phases, small = {}, {"programs": 0, "compiled": 0, "seconds": 0.0}
    for row in report["rows"]:
        if row["name"] == "setup.programs.other":
            attrs = row["attrs"]
            small["programs"] += attrs["programs"]
            small["compiled"] += attrs["compiled"]
            small["seconds"] += attrs["trace_s"] + attrs["lower_s"] + \
                attrs["compile_s"]
        elif row["parent"] == "setup.engine":
            phases[row["name"]] = phases.get(row["name"], 0.0) + \
                row["end_s"] - row["start_s"]
    whole = report["seconds"].get("setup.engine", 0.0)
    return ("start-up engine={}: setup.engine {:.3f} s = {} + unnamed "
            "{:.3f}; {} small programs made meanwhile ({} compiled, not "
            "loaded) in {:.3f} s".format(
                engine, whole,
                " + ".join("{} {:.3f}".format(n, s)
                           for n, s in phases.items()) or "no phase",
                whole - sum(phases.values()), small["programs"],
                small["compiled"], small["seconds"]))
