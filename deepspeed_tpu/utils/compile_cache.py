"""Where the persistent XLA compile cache lives, and what it did.

Entry scripts (chip_smoke.py, benchmark/run.py) call
:func:`enable_compile_cache` once, before first backend use; nothing
calls it at import. The directory is placed from OUTSIDE when
``JAX_COMPILATION_CACHE_DIR`` is set (jax reads that variable itself, so
no directory is set in code); unset, it is the fixed
``<checkout>/.jax_cache`` — the path is part of the cache key, so it
never carries a temp dir, a pid or a time.

What it did: JAX reports every program's tracing, lowering and backend
compile (a load, where the persistent cache hit) as monitoring events.
:func:`listen` registers ONE listener for them, at the package's
import, which books each to the ``setup.program`` row open on the
calling thread (:func:`open_program_row`, from a program's making to
the end of its first call: ``runtime/executor/jit.py``) or, while no
such row is open, to a ``setup.programs.other`` row: the small programs
of set-up, summed.
The rows are the start-up record's (``utils/annotate.py``,
docs/telemetry.md "Start-up record"). Nothing here runs on a launch
that compiles nothing.

Which scope each compiled instruction was traced under: a program that
has run is kept (:func:`close_program_row`: its jitted function and its
first call's operands as shapes), and :func:`program_scopes` lowers and
compiles it once more, on request, to read the compiled text
(docs/telemetry.md "Device scopes").
"""
import contextlib
import logging
import os
import threading
import time

from . import annotate
from .logging import logger

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def compile_cache_dir():
    """The directory the cache uses: the environment's if set, else the
    fixed in-checkout one. Pure (touches no backend, no filesystem)."""
    return os.environ.get(ENV_VAR) or os.path.join(_CHECKOUT, ".jax_cache")


def enable_compile_cache():
    """Turn the persistent compile cache on; returns its directory."""
    import jax
    path = compile_cache_dir()
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_enable_compilation_cache", True)
    return path


# ------------------------------------------------------ what the cache did
_TRACE_EVENT = "/jax/core/compile/jaxpr_trace_duration"
_FIELD = {
    _TRACE_EVENT: "trace_s",
    "/jax/core/compile/jaxpr_to_mlir_module_duration": "lower_s",
    "/jax/core/compile/backend_compile_duration": "compile_s",
}
_CACHE_LOAD = "/jax/compilation_cache/cache_retrieval_time_sec"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"
# an event's start is its end less its duration, on another clock than
# JAX took the duration from: a parent's start within this much after
# its child's still encloses it
_NESTING_SLACK_S = 1e-4
_OTHER_NAMES_MAX = 64      # names a row's book lists; the rest as one
FIRST_CALL_LOG_S = 1.0     # a first call this long is logged at INFO

PROGRAM_ENGINES_KEPT = 4   # engines whose programs stay for the map
_listening = False
# .books: the program rows open on this thread; .other: (book, row) of
# the thread's newest ``setup.programs.other`` row; .asked, .hits,
# .load_s: what the persistent cache reported since the last compile
# event (JAX reports them inside a compile, before the event that names
# the program)
_open = threading.local()


class _Book:
    """What JAX reported while a row was open. Its events nest, across
    the three kinds too (a traced function calls jitted ones, a
    lowering rule traces a helper), and arrive in the order of their
    ends, so the seconds are the outermost events': an event takes back
    what the events it encloses added. The three sums then never
    exceed the wall they were taken in."""

    __slots__ = ("seconds", "tops", "compiles", "asked", "hits",
                 "cache_load_s", "names", "only", "engine")

    def __init__(self, only=None, engine=None):
        self.engine = engine   # of a program row: whose first call
        # a program row's book takes the program's OWN events alone
        # (the function's name, and its module's): the thousands a
        # trace of 24 layers reports of the functions it calls lie
        # inside them, and each costs the listener one comparison
        self.only = only and (only, "jit({})".format(only))
        self.seconds = {"trace_s": 0.0, "lower_s": 0.0, "compile_s": 0.0}
        self.tops = []         # (start, seconds, field, name), outermost
        self.compiles = self.asked = self.hits = 0
        self.cache_load_s = 0.0
        self.names = {}        # fun_name -> [events, seconds]

    def add(self, field, start, end, fun_name):
        tops = self.tops
        while tops and tops[-1][0] >= start - _NESTING_SLACK_S:
            _, seconds, inner, name = tops.pop()
            self.seconds[inner] -= seconds
            self.names[name][1] -= seconds
        # tracing reports the function, lowering and compiling its
        # module, ``jit(<function>)``: one name
        if fun_name.startswith("jit(") and fun_name.endswith(")"):
            fun_name = fun_name[4:-1]
        if fun_name not in self.names and \
                len(self.names) >= _OTHER_NAMES_MAX:
            fun_name = "(others)"
        tops.append((start, end - start, field, fun_name))
        if len(tops) > 65536:      # an event encloses those after it
            del tops[:32768]       # started: in practice a few hundred
        self.seconds[field] += end - start
        by_name = self.names.setdefault(fun_name, [0, 0.0])
        by_name[0] += 1
        by_name[1] += end - start
        if field == "compile_s":
            # what the cache said meanwhile was said of this program
            self.compiles += 1
            self.asked += getattr(_open, "asked", 0)
            self.hits += getattr(_open, "hits", 0)
            self.cache_load_s += getattr(_open, "load_s", 0.0)
            _open.asked = _open.hits = 0
            _open.load_s = 0.0

    def compiled(self):
        """Programs compiled and not loaded."""
        return self.compiles - self.hits

    def cache(self):
        """``hit``: every program was loaded from the persistent cache;
        ``miss``: the cache was asked and one was not there; ``off``:
        a compile that never asked it; None: nothing was compiled (an
        executable made ahead of the call)."""
        if not self.compiles:
            return None
        if self.hits >= self.compiles:
            return "hit"
        return "miss" if self.asked else "off"


def _other_book(now):
    """The ``setup.programs.other`` row that takes an event outside
    every program row: this thread's newest one while it is still the
    record's last word under the same span, else a new one (so each
    phase of set-up has its own, and a row's end says when its last
    program was made)."""
    parent = annotate.open_setup_span()
    parent = parent["name"] if parent is not None else None
    other = getattr(_open, "other", None)
    if other is not None and other[1]["parent"] == parent and \
            annotate.is_trailing_setup_row(other[1]):
        return other
    row = annotate.new_setup_row(
        "setup.programs.other", now, now, programs=0, compiled=0,
        trace_s=0.0, lower_s=0.0, compile_s=0.0, names={})
    annotate.record_setup_row(row)
    other = _open.other = _Book(), row
    return other


def _open_books():
    try:
        return _open.books
    except AttributeError:
        books = _open.books = []
        return books


def _on_duration(event, duration, **kwargs):
    away = getattr(_open, "away", None)
    if away is not None:
        away[event] = away.get(event, 0) + 1
        return
    field = _FIELD.get(event)
    if field is None:
        if event == _CACHE_LOAD:
            _open.load_s = getattr(_open, "load_s", 0.0) + duration
        return
    books = _open_books()
    if books:
        # while a program is being made its own events are the row's,
        # and the thousands of the functions it calls lie inside them
        name = kwargs.get("fun_name")
        for book in books:
            if name in book.only:
                now = time.perf_counter()
                book.add(field, now - duration, now, str(name))
        return
    now = time.perf_counter()
    book, row = _other_book(now - duration)
    book.add(field, now - duration, now, str(kwargs.get("fun_name")))
    row["end_s"] = now
    row["attrs"].update(book.seconds, programs=book.compiles,
                        compiled=book.compiled(), names=book.names)


@contextlib.contextmanager
def _looking_away():
    """While :func:`program_scopes` lowers a program again the listener
    books nothing: what JAX reports meanwhile is nobody's start-up.
    Yields {event: how many were reported}."""
    away = _open.away = {}
    try:
        yield away
    finally:
        _open.away = None


def _on_event(event, **_):
    if getattr(_open, "away", None) is not None:
        return
    if event == _CACHE_HIT:
        _open.hits = getattr(_open, "hits", 0) + 1
    elif event == _CACHE_ASKED:
        _open.asked = getattr(_open, "asked", 0) + 1


def listen():
    """Register the listener, once a process (the package's import
    does); a second call does nothing."""
    global _listening
    if _listening:
        return
    _listening = True
    import jax.monitoring
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)


def open_program_row(program, key, engine, step, fun_name, fn=None):
    """Open the ``setup.program`` row of a program just made, on the
    calling thread: until :func:`close_program_row` the row takes what
    JAX reports of the function called ``fun_name`` (its tracing, and
    its module's lowering and compile or load); ``fn`` is the jitted
    function itself, for :func:`program_scopes`. Nothing wraps the call
    itself: a frame more under a trace of 24 layers moved a whole
    phase of lowering across CPython's first data-stack chunk boundary
    and cost 3.5 s (PERF.md section 6, PR 40). -> the open row."""
    # a tuple as ``8/True/0``: a comma would end the attribute in a trace
    key = "/".join(map(str, key)) if isinstance(key, tuple) else str(key)
    row = annotate.new_setup_row("setup.program", None, program=program,
                                 key=key, engine=engine, step=step)
    span = annotate.annotate("setup.program", program=program, key=key,
                             engine=engine, step=step)
    span.__enter__()
    book = _Book(fun_name, engine)
    _open_books().append(book)
    row["start_s"] = time.perf_counter()
    return row, book, span, fn


def close_program_row(opened, discard=False, operands=None):
    """Close a row :func:`open_program_row` opened, once the program's
    first call is over (the caller has fenced it); ``discard``: the
    program was made to be looked at, not run (an audit): no row.
    ``operands``: what the first call was given (donated arrays still
    say their shape, dtype and sharding): with them the program is kept
    for :func:`program_scopes`. ``first_run_s`` is
    the row's wall less the three: the first execution, the host's work
    between the program's making and its call's end, and what JAX does
    between its events. Logged at INFO when the row took
    ``FIRST_CALL_LOG_S`` or more, and at WARNING when ``step`` is past
    0: a program first called after the engine's first step is a
    recompile in the middle of the work, and the row says which step
    and what."""
    row, book, span, fn = opened
    row["end_s"] = time.perf_counter()
    books = _open_books()
    if book in books:
        books.remove(book)
    span.__exit__(None, None, None)
    if discard:
        return
    wall = row["end_s"] - row["start_s"]
    attrs = row["attrs"]
    attrs.update(book.seconds, cache=book.cache(),
                 cache_load_s=book.cache_load_s,
                 first_run_s=max(0.0, wall - sum(book.seconds.values())))
    annotate.record_setup_row(row)
    if fn is not None and operands is not None:
        _keep_program(attrs, fn, operands)
    step = attrs["step"]
    if step > 0 or wall >= FIRST_CALL_LOG_S:
        logger.log(
            logging.WARNING if step > 0 else logging.INFO,
            "%s program=%s key=%s engine=%s step=%d: trace_s=%.3f "
            "lower_s=%.3f compile_s=%.3f cache=%s first_run_s=%.3f",
            "first call AFTER the engine's first step (a recompile in "
            "the middle of the work):" if step > 0 else "first call:",
            attrs["program"], attrs["key"], attrs["engine"], step,
            attrs["trace_s"], attrs["lower_s"], attrs["compile_s"],
            attrs["cache"], attrs["first_run_s"])


# ------------------------------------------- which scope an operation is
class _Kept:
    """A program that has run, as :func:`program_scopes` needs it: the
    jitted function (or, once its engine is closed, what it traced to)
    and its first call's operands as ``jax.ShapeDtypeStruct``s."""

    __slots__ = ("fn", "traced", "operands", "retraced")

    def __init__(self, fn, operands):
        self.fn, self.operands = fn, operands
        self.traced = self.retraced = None

    def trace(self):
        """What the function traces to for its first call's operands.
        A hit of JAX's trace cache reports the one event of the
        function itself; a miss runs its body again and reports the
        jitted functions that calls as well: such a map may not be
        that of the program that ran (``retraced``)."""
        if self.traced is None:
            with _looking_away() as away:
                self.traced = self.fn.trace(*self.operands)
            self.retraced = away.get(_TRACE_EVENT, 0) > 1
        return self.traced


_kept = {}         # (engine, program, key) -> _Kept, oldest engine first
_kept_lock = threading.Lock()


def _abstract(x):
    """An operand as the program's lowering sees it: shape, dtype and
    weak type, with the sharding of an array that was placed (one that
    was not reports the default device, which would pin a program of
    several devices to it). None and Python scalars pass through."""
    import jax
    if not (hasattr(x, "shape") and hasattr(x, "dtype")):
        return x
    sharding = getattr(x, "sharding", None)
    if not (isinstance(sharding, jax.sharding.NamedSharding) or
            getattr(x, "committed", False)):
        sharding = None
    return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype, sharding=sharding,
                                weak_type=getattr(x, "weak_type", False))


def _keep_program(attrs, fn, operands):
    import jax
    kept = _Kept(fn, jax.tree_util.tree_map(_abstract, tuple(operands)))
    with _kept_lock:
        _kept[attrs["engine"], attrs["program"], attrs["key"]] = kept
        engines = list(dict.fromkeys(key[0] for key in _kept))
        for key in [key for key in _kept
                    if key[0] in engines[:-PROGRAM_ENGINES_KEPT]]:
            del _kept[key]


def release_programs(engine):
    """``engine`` (its tag) will not run again: keep of its programs
    what they traced to, which holds no engine, and let the functions
    go (a training program's closes over its engine and state)."""
    with _kept_lock:
        programs = [(key, kept) for key, kept in _kept.items()
                    if key[0] == engine]
    for key, kept in programs:
        try:
            kept.trace()
        except Exception:  # noqa: BLE001 - a teardown goes on
            logger.debug("release_programs: %s not kept", key,
                         exc_info=True)
            with _kept_lock:
                _kept.pop(key, None)
        kept.fn = None


def program_scopes(engine=None):
    """Which scope every instruction of every program that has run was
    traced under (all of them, or those of the engine with this tag):
    a list of ``{"engine", "program", "key", "module", "retraced",
    "seconds", "instructions": {name: [op_name, result shape]}}``, a
    fusion's row followed by its members' distinct ``op_name``s
    (docs/telemetry.md, "Device scopes"). Each program is lowered from
    its first call's shapes (no new trace: the jitted function is the
    one that ran), compiled (a load, where the persistent cache holds
    it) and dropped once its text is parsed, one at a time. The
    start-up record's listener looks away meanwhile. The calling thread
    stalls for the lowerings (seconds a program): not for a step, and
    not inside a window whose trace should hold no compile."""
    from ..analysis.hlo import instruction_table
    with _kept_lock:
        programs = [(key, kept) for key, kept in _kept.items()
                    if engine is None or key[0] == engine]
    if {book.engine for book in _open_books()}.intersection(
            key[0] for key, _ in programs):
        raise RuntimeError("program_scopes() inside a step: a program's "
                           "first call is not over")
    entries = []
    for (tag, program, key), kept in programs:
        entry = {"engine": tag, "program": program, "key": key,
                 "module": None, "retraced": None, "instructions": {}}
        start = time.perf_counter()
        try:
            traced = kept.trace()
            entry["retraced"] = kept.retraced
            with _looking_away():
                text = traced.lower().compile().as_text()
        except Exception as err:  # noqa: BLE001 - a diagnostic goes on
            logger.warning("program_scopes: %s %s of %s could not be "
                           "lowered again", program, key, tag,
                           exc_info=True)
            entry["error"] = "{}: {}".format(type(err).__name__, err)
            text = ""
        entry["module"], table, computations = instruction_table(text)
        for name, (op_name, shape, _, calls) in table.items():
            row = entry["instructions"][name] = [op_name, shape]
            if calls is not None:
                row.append(sorted({table[member][0] for member in
                                   computations.get(calls, ())} - {""}))
        entry["seconds"] = time.perf_counter() - start
        entries.append(entry)
    return entries
