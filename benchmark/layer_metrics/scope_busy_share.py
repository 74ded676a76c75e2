"""A mechanism's share of the device's busy time, read by the SCOPE its
operations were traced under (docs/telemetry.md, "Device scopes").

A Mosaic kernel's event carries the kernel's name; everything XLA
compiles is ``%fusion.12 = bf16[384,8192] fusion(...)`` in a trace, a
kind and a shape. The program knows more: each instruction of a
compiled program keeps the ``jax.named_scope`` path it was traced under
(``jit(decode)/gdn.chunk/dot_general``), and
``deepspeed_tpu.utils.compile_cache.program_scopes()`` hands that out,
instruction by instruction, for every program an engine has run. An
event's name begins with its instruction's name, and the ``XLA
Modules`` line says which program's run an event lies in: that is the
join. Several programs can share a module name (a prefill program a
bucket, all ``jit_prefill``); the runs of one module line name are
given to the ONE entry whose instructions account for most of their
events by name and result shape.

Time is booked the way ``program_spans`` books idle gaps: the events of
the device's line nest (a ``while`` holds its body's operations), and
each stretch of busy time goes to the innermost event open there, so
the parts add up to ``Reduction.busy_s`` and a container keeps only
what its body leaves. Each stretch lands in one of three places:

- under the vocabulary's components of its instruction's ``op_name``,
  transform wrappers taken off (``transpose(jvp(mlp))`` is ``mlp``; the
  log keeps backward apart);
- ``unscoped``: mapped, and no component of the vocabulary
  (``deepspeed_tpu.utils.annotate.DEVICE_SCOPES``);
- ``unmapped``: in no run of the module line, in a run no entry
  accounts for, or an instruction its entry does not hold.

``mixed`` is reported beside them and is no fourth place: the seconds of
fusions whose members lie under different scopes than the fusion's own
``op_name``, under which they are booked. Where the join can be checked
it is: a Mosaic kernel's event is named by its kernel, and the mapped
``op_name``'s innermost scope has to be that name
(:func:`kernel_scope`); the disagreements are counted and logged.

Parameters: ``scopes`` (an event counts once if any of them is among
its components) or ``"unscoped": true``. Value: percent of the busy
time. None where the program hands out no map (a parent from before
the scopes), where no mapped instruction carries the scope, or where
``unmapped`` passes 2% of the busy time: it does not guess. The map is
taken once a run, after the window (``program_scopes()`` lowers and
compiles), and applied in one pass over the events; a probe calls
``table(run)``.
"""
import bisect
import collections
import json
import re

from .. import trace
from . import program_spans

UNMAPPED_LIMIT = 0.02       # of the busy time, past which nothing is read
_JITTED = re.compile(r"(^|/)p?jit\([^()]*\)")
_WRAPPER = re.compile(r"[A-Za-z_][\w.]*\(|\)")


def components(op_name):
    """``jit(fused)/transpose(jvp(mlp))/jit(_where)/mul`` -> ``["mlp",
    "mul"]``: the path's components with the names of jitted functions
    dropped and the wrappers of transforms taken off."""
    path = _WRAPPER.sub("", _JITTED.sub("", op_name))
    return [part for part in path.split("/") if part]


def kernel_scope(op_name):
    """The name a Mosaic kernel's event takes from its instruction's
    ``op_name``: the innermost scope, the component before
    ``pallas_call`` (the ``name=`` of the ``pallas_call``, which opens a
    scope of that name; without one whatever lies innermost, ``jvp()``
    for the flash kernels), written as an instruction's name is."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        depth += (ch == "(") - (ch == ")")
        if ch == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    if parts[-1] == "pallas_call":
        parts.pop()
    return re.sub(r"[^\w.]", "_", parts[-1]) if parts else ""


def _head(event_name):
    """A device event's name -> (instruction name, result shape with
    its layout taken off)."""
    name, _, rest = event_name.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape = rest[:end + 1]
    else:
        shape = rest.partition(" ")[0]
    return name.lstrip("%"), re.sub(r"\{[^}]*\}", "", shape)


class Table:
    """One trace's busy time by scope. Seconds are sums over the device
    planes; ``planes`` divides them like ``Reduction.busy_s``."""

    def __init__(self, vocabulary):
        self.vocabulary = frozenset(vocabulary)
        self.planes = 0
        self.by_path = collections.Counter()    # scopes of an event -> s
        self.backward = collections.Counter()   # scope -> s under transpose
        self.heaviest = collections.defaultdict(collections.Counter)
        self.unscoped_s = self.unmapped_s = self.mixed_s = 0.0
        self.kernels = self.kernel_disagreements = 0
        self.runs = collections.Counter()   # (program, key) -> its runs
        self.carried = set()    # scopes some mapped instruction carries
        self._scopes = {}       # op_name -> its vocabulary components

    def scopes_of(self, op_name):
        found = self._scopes.get(op_name)
        if found is None:
            found = self._scopes[op_name] = tuple(sorted(
                self.vocabulary.intersection(components(op_name))))
        return found

    def seconds(self, scopes=None):
        """Seconds of the events with any of ``scopes`` among their
        components (all scoped events without), each once, a plane."""
        wanted = None if scopes is None else set(scopes)
        total = sum(s for path, s in self.by_path.items()
                    if wanted is None or wanted.intersection(path))
        return total / max(1, self.planes)

    def rows(self, busy_s):
        """{scope: [seconds, percent of busy, the three heaviest
        operations under it]}, heaviest scope first."""
        by_scope = collections.Counter()
        for path, s in self.by_path.items():
            for scope in path:
                by_scope[scope] += s
        n = max(1, self.planes)
        return {scope: [round(s / n, 6), round(100.0 * s / n / busy_s, 3),
                        [op for op, _ in
                         self.heaviest[scope].most_common(3)]]
                for scope, s in by_scope.most_common()}


def _assign(names, entries):
    """The entry whose instructions account for most of the distinct
    (instruction, shape) pairs of one module line name's events, or
    None where none accounts for half of them."""
    best, most = None, 0
    for entry in entries:
        held = entry["instructions"]
        n = sum(1 for name, shape in names
                if name in held and held[name][1] == shape)
        if n > most:
            best, most = entry, n
    return best if names and 2 * most >= len(names) else None


def build(reduction, entries, vocabulary):
    """The :class:`Table` of one reduced trace under the programs'
    ``entries`` (``program_scopes()``'s list)."""
    table = Table(vocabulary)
    by_module = collections.defaultdict(list)
    for entry in entries:
        if entry.get("module"):
            by_module[entry["module"]].append(entry)
    heads = {}                           # event name -> (instruction, shape)
    mapped = {}                          # the entries some run was given
    for plane, events in reduction.device_events.items():
        table.planes += 1
        runs = sorted(reduction.device_modules.get(plane, ()),
                      key=lambda m: m[1])
        starts = [m[1] for m in runs]
        run_of, seen = [], collections.defaultdict(set)
        for name, _, start, _ in events:
            head = heads.get(name)
            if head is None:
                head = heads[name] = _head(name)
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < runs[i][2]:
                run_of.append(runs[i][0])
                seen[runs[i][0]].add(head)
            else:
                run_of.append(None)
        entry_of = {module: _assign(names, by_module.get(
            trace.program_of(module), ())) for module, names in seen.items()}
        own = collections.Counter()      # event index -> its own seconds
        for start, end, i in program_spans.innermost(
                [(i, ev[2], ev[3]) for i, ev in enumerate(events)]):
            own[i] += end - start
        for i, seconds in own.items():
            name, text = events[i][0], events[i][1]
            entry = entry_of.get(run_of[i])
            row = entry and entry["instructions"].get(heads[name][0])
            label = trace.short_name(name)[0]
            if not row or row[1] != heads[name][1]:
                table.unmapped_s += seconds
                table.heaviest["unmapped"][label] += seconds
                continue
            path = table.scopes_of(row[0])
            if "tpu_custom_call" in text:
                table.kernels += 1
                kernel = re.sub(r"\.\d+$", "", heads[name][0])
                if kernel_scope(row[0]) != kernel:
                    table.kernel_disagreements += 1
                    table.heaviest["kernel disagreements"][
                        "{} under {}".format(kernel, row[0])] += seconds
            if len(row) > 2 and any(table.scopes_of(member) != path
                                    for member in row[2]):
                table.mixed_s += seconds
                table.heaviest["mixed"][label] += seconds
            if not path:
                table.unscoped_s += seconds
                table.heaviest["unscoped"][label] += seconds
                continue
            table.by_path[path] += seconds
            for scope in path:
                table.heaviest[scope][label] += seconds
                if "transpose(" in row[0]:
                    table.backward[scope] += seconds
        mapped.update((id(e), e) for e in entry_of.values() if e)
        for module, _, _ in runs:
            entry = entry_of.get(module)
            table.runs[(entry["program"], entry["key"]) if entry
                       else (trace.program_of(module), "unmapped")] += 1
    for entry in mapped.values():
        table.carried.update(
            scope for row in entry["instructions"].values()
            for scope in table.scopes_of(row[0]))
    n = max(1, table.planes)
    table.unscoped_s /= n
    table.unmapped_s /= n
    table.mixed_s /= n
    return table


def _program():
    """(program_scopes, the vocabulary) of the program under test, or
    None where it has neither: a parent from before the scopes."""
    try:
        from deepspeed_tpu.utils.annotate import DEVICE_SCOPES
        from deepspeed_tpu.utils.compile_cache import program_scopes
    except ImportError:
        return None
    return program_scopes, DEVICE_SCOPES


def table(run):
    """The run's :class:`Table`, made and logged once; None where the
    program hands out no map or the trace holds no device event."""
    found = getattr(run, "scope_table", False)
    if found is not False:
        return found
    run.scope_table = None
    red, program = run.reduction, _program()
    if program is None or not red.device_events or red.busy_s <= 0:
        return None
    program_scopes, vocabulary = program
    entries = program_scopes()
    run.log("scopes: program_scopes() took {:.3f} s for {} programs: {}"
            .format(sum(e.get("seconds", 0.0) for e in entries),
                    len(entries), json.dumps([
                        [e["engine"], e["program"], e["key"], e["module"],
                         len(e["instructions"]),
                         round(e.get("seconds", 0.0), 3),
                         "retraced" if e.get("retraced") else
                         e.get("error", "")] for e in entries])))
    found = run.scope_table = build(red, entries, vocabulary)
    busy = red.busy_s
    scoped = found.seconds()
    share = lambda s: round(100.0 * s / busy, 3)
    top = lambda key: [[op, round(s / found.planes, 6)] for op, s in
                       found.heaviest[key].most_common(5)]
    run.log("scopes: runs of the module line given to each program: {}"
            .format(json.dumps({"{} {}".format(*key): n / found.planes
                                for key, n in sorted(found.runs.items())})))
    run.log("scopes {{scope: [s, % of busy, heaviest operations]}}: {}"
            .format(json.dumps(found.rows(busy))))
    if found.backward:
        run.log("scopes, the part under transpose() (backward) "
                "{{scope: s}}: {}".format(json.dumps({
                    scope: round(s / found.planes, 6)
                    for scope, s in found.backward.most_common()})))
    run.log("scopes: busy {:.6f} s = scoped {:.6f} ({}%) + unscoped {:.6f} "
            "({}%) + unmapped {:.6f} ({}%), off by {:.6f}; mixed {:.6f} "
            "({}%); unscoped: {}; unmapped: {}; mixed: {}".format(
                busy, scoped, share(scoped), found.unscoped_s,
                share(found.unscoped_s), found.unmapped_s,
                share(found.unmapped_s),
                busy - scoped - found.unscoped_s - found.unmapped_s,
                found.mixed_s, share(found.mixed_s), json.dumps(
                    top("unscoped")), json.dumps(top("unmapped")),
                json.dumps(top("mixed"))))
    run.log("scopes: {} Mosaic kernel events mapped, {} whose op_name's "
            "innermost scope is not the kernel's name{}".format(
                found.kernels, found.kernel_disagreements,
                ": " + json.dumps(top("kernel disagreements"))
                if found.kernel_disagreements else ""))
    if found.unmapped_s > UNMAPPED_LIMIT * busy:
        run.log("scopes: unmapped passes {:g}% of the busy time: no "
                "scope metric is read".format(100 * UNMAPPED_LIMIT))
    return found


def read(run, params):
    found = table(run)
    busy = run.reduction.busy_s
    if found is None or found.unmapped_s > UNMAPPED_LIMIT * busy:
        return None
    if params.get("unscoped"):
        return 100.0 * found.unscoped_s / busy
    if not found.carried.intersection(params["scopes"]):
        return None          # no program of the run has such a scope
    return 100.0 * found.seconds(params["scopes"]) / busy
