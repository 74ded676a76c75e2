"""Post-optimization HLO collective census.

Parses the compiled module text of a jitted program (``jax.jit(fn)
.lower(*structs).compile().as_text()`` — the per-device SPMD program
AFTER XLA's partitioner ran) and prices every collective instruction
with the same ring formulas ``runtime/comm/wire.py`` uses, so the
analytic wire estimator can finally be ground-truthed against what XLA
actually emits:

  * ``all-gather``          result_bytes * (g-1)/g
  * ``all-reduce``          result_bytes * 2(g-1)/g
  * ``reduce-scatter``      result_bytes * (g-1)      (input = g*result)
  * ``collective-permute``  result_bytes              (one ring hop)
  * ``all-to-all``          result_bytes * (g-1)/g

Each op is attributed to the mesh axis (or axis set) its replica groups
span — ``parallel.topology.mesh_axis_groups`` computes the ground-truth
device groupings per axis — so ZeRO's data-axis wire classes separate
cleanly from tensor-parallel (model-axis) traffic the estimator never
prices. ``reconcile_wire`` then diffs the census against
``estimate_step_comm_bytes``'s classes: collectives in the HLO the
estimator did not price (and vice versa) become findings.
"""
import re

import numpy as np

from .findings import Finding
from .rules import CENSUS_MIN_BYTES_DEFAULT

_DTYPE_BYTES = {
    "pred": 1, "s8": 1, "u8": 1, "s4": 1, "u4": 1,
    "s16": 2, "u16": 2, "f16": 2, "bf16": 2,
    "s32": 4, "u32": 4, "f32": 4,
    "s64": 8, "u64": 8, "f64": 8, "c64": 8,
    "c128": 16,
}

COLLECTIVE_OPS = (
    "all-gather", "all-reduce", "reduce-scatter", "collective-permute",
    "all-to-all",
)

_SHAPE_RE = re.compile(r"([a-z]+[0-9]*)\[([0-9,]*)\]")
# tuple result shapes stop at the first ')' — long tuples carry
# '/*index=N*/' comments (so '[^=]*' would reject them), but never
# nested parens
_INSTR_RE = re.compile(
    r"^\s*(?:ROOT\s+)?%?[\w.\-]+\s*=\s*(\([^()]*\)|[a-z]+[0-9]*\[[0-9,]*\]"
    r"(?:\{[^}]*\})?)\s+([a-z0-9\-]+)(?:-start)?\(", re.M)
_GROUPS_RE = re.compile(
    r"replica_groups=(\{\{[\d,{}\s]*\}\}|\[[\d,]+\]<=\[[\d,]+\]"
    r"(?:T\(([\d,]+)\))?)")
_PAIRS_RE = re.compile(r"source_target_pairs=\{([\d,{}\s]*)\}")
_OP_NAME_RE = re.compile(r'op_name="([^"]*)"')


_MODULE_RE = re.compile(r"^HloModule\s+([\w.\-]+)", re.M)
_CALLS_RE = re.compile(r"\bcalls=%?([\w.\-]+)")
_LAYOUT_RE = re.compile(r"\{[^}]*\}")


def _element_bytes(shape_text):
    """One HLO shape (or tuple-of-shapes) -> per-element byte sizes."""
    sizes = []
    for dtype, dims in _SHAPE_RE.findall(shape_text):
        if dtype not in _DTYPE_BYTES:
            continue                       # token/opaque/etc
        numel = 1
        if dims:
            numel = int(np.prod([int(d) for d in dims.split(",")],
                                dtype=np.int64))
        sizes.append(numel * _DTYPE_BYTES[dtype])
    return sizes


def _shape_bytes(shape_text):
    """One HLO shape (or tuple-of-shapes) -> total bytes per device."""
    return sum(_element_bytes(shape_text))


def _result_bytes(shape_text, opcode, is_async):
    """The RESULT size of one collective instruction. Async ``-start``
    ops carry tuple shapes bundling operand + result (+ u32 scratch):
    summing them would overprice the wire (operand + result per op).
    The result is the LARGEST element for gather-like ops (output >=
    input) and the SMALLEST for reduce-scatter (output = input / g);
    sync single-shape ops pass through unchanged."""
    sizes = _element_bytes(shape_text)
    if not sizes:
        return 0
    if not is_async:
        return sum(sizes)
    return min(sizes) if opcode == "reduce-scatter" else max(sizes)


def _parse_replica_groups(text):
    """replica_groups attribute -> list of frozenset(device ids)."""
    m = _GROUPS_RE.search(text)
    if not m:
        return None
    body = m.group(1)
    if body.startswith("{{") or body.startswith("{"):
        groups = []
        for grp in re.findall(r"\{([\d,\s]*)\}", body):
            ids = [int(x) for x in grp.replace(" ", "").split(",") if x]
            if ids:
                groups.append(frozenset(ids))
        return groups
    # iota form: [G,S]<=[dims] or [G,S]<=[dims]T(perm)
    m2 = re.match(r"\[([\d,]+)\]<=\[([\d,]+)\](?:T\(([\d,]+)\))?", body)
    if not m2:
        return None
    out_dims = [int(x) for x in m2.group(1).split(",")]
    src_dims = [int(x) for x in m2.group(2).split(",")]
    ids = np.arange(int(np.prod(src_dims, dtype=np.int64)))
    ids = ids.reshape(src_dims)
    if m2.group(3):
        perm = [int(x) for x in m2.group(3).split(",")]
        ids = ids.transpose(perm)
    ids = ids.reshape(out_dims)
    return [frozenset(int(d) for d in row) for row in ids]


def _parse_permute_groups(text):
    """source_target_pairs -> connected components (the ring groups)."""
    m = _PAIRS_RE.search(text)
    if not m:
        return None
    pairs = re.findall(r"\{(\d+)\s*,\s*(\d+)\}", m.group(0))
    if not pairs:
        return None
    parent = {}

    def find(x):
        parent.setdefault(x, x)
        while parent[x] != x:
            parent[x] = parent[parent[x]]
            x = parent[x]
        return x

    for a, b in pairs:
        ra, rb = find(int(a)), find(int(b))
        if ra != rb:
            parent[ra] = rb
    comps = {}
    for node in list(parent):
        comps.setdefault(find(node), set()).add(node)
    return [frozenset(c) for c in comps.values()]


def _wire_bytes(opcode, result_bytes, group_size):
    g = max(int(group_size), 1)
    ring = (g - 1) / g if g > 1 else 0.0
    if opcode == "all-gather":
        return result_bytes * ring
    if opcode == "all-reduce":
        return result_bytes * 2 * ring
    if opcode == "reduce-scatter":
        return result_bytes * (g - 1)
    if opcode == "collective-permute":
        return float(result_bytes)
    if opcode == "all-to-all":
        return result_bytes * ring
    return 0.0


def classify_groups(groups, axis_groups):
    """Match an op's replica groups against the mesh's per-axis(-set)
    ground truth. ``axis_groups``: {label: [frozenset(ids), ...]}."""
    if not groups:
        return "unknown"
    got = set(groups)
    for label, truth in axis_groups.items():
        if got <= set(truth):
            return label
    all_ids = frozenset().union(*groups)
    if len(groups) == 1 and all(len(g) > 1 for g in groups):
        return "world" if len(all_ids) > 1 else "self"
    return "other"


def collective_census(hlo_text, axis_groups=None,
                      min_bytes=CENSUS_MIN_BYTES_DEFAULT):
    """-> {"ops": [...], "by_axis": {...}, "total_bytes": int}.

    ``ops`` lists every collective instruction at/above ``min_bytes``
    wire volume with its opcode, per-device wire bytes (ring pricing),
    group size and mesh-axis attribution; smaller ops aggregate into
    ``below_threshold_bytes`` so nothing silently disappears.
    """
    axis_groups = axis_groups or {}
    ops = []
    below = 0.0
    for line in hlo_text.splitlines():
        m = _INSTR_RE.match(line)
        if m is None:
            continue
        shape_text, opcode = m.group(1), m.group(2)
        is_async = opcode.endswith("-start")
        if is_async:
            opcode = opcode[:-len("-start")]
        if opcode not in COLLECTIVE_OPS:
            continue
        if opcode == "collective-permute":
            groups = _parse_permute_groups(line)
        else:
            groups = _parse_replica_groups(line)
        gsize = max((len(g) for g in groups), default=1) if groups else 1
        result_bytes = _result_bytes(shape_text, opcode, is_async)
        wire = _wire_bytes(opcode, result_bytes, gsize)
        axis = classify_groups(groups, axis_groups)
        in_loop = "while" in line or "body" in line.split("=")[0]
        if wire < min_bytes:
            below += wire
            continue
        name_m = _OP_NAME_RE.search(line)
        op_name = name_m.group(1) if name_m else ""
        ops.append({
            "opcode": opcode,
            "wire_bytes": int(round(wire)),
            "result_bytes": int(result_bytes),
            "group_size": int(gsize),
            "axis": axis,
            "in_loop": bool(in_loop),
            # hand-written shard_map collectives (the quantized/1-bit
            # exchanges, ring bodies) — deterministic bytes the compiler
            # cannot reshape, vs GSPMD-inserted resharding it can
            "explicit": "shmap_body" in op_name,
            "op_name": op_name[-80:],
        })
    by_axis = {}
    for op in ops:
        slot = by_axis.setdefault(op["axis"], {"ops": 0, "wire_bytes": 0})
        slot["ops"] += 1
        slot["wire_bytes"] += op["wire_bytes"]
    return {
        "ops": ops,
        "by_axis": by_axis,
        "total_bytes": int(sum(op["wire_bytes"] for op in ops)),
        "below_threshold_bytes": int(round(below)),
    }


def _instruction_head(line):
    """One line of a module's text -> (instruction name, result shape
    with its layout taken off, opcode), or None where the line defines
    no instruction. A result is one shape or a tuple of them, and a
    tuple may nest and carry tiled layouts (``{1,0:T(8,128)(2,1)}``),
    so its end is found by counting brackets and not by an
    expression."""
    text = line.strip()
    if text.startswith("ROOT "):
        text = text[5:]
    name, eq, rest = text.partition(" = ")
    if not eq or " " in name or not rest:
        return None
    if rest[0] == "(":
        depth = 0
        for end, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        shape, rest = rest[:end + 1], rest[end + 1:]
    else:
        shape, _, rest = rest.partition(" ")
    opcode = rest.lstrip().partition("(")[0]
    if not opcode or not re.fullmatch(r"[a-z][a-z0-9\-]*", opcode):
        return None
    return name.lstrip("%"), _LAYOUT_RE.sub("", shape), opcode


def instruction_table(hlo_text):
    """-> (module name, {instruction name: (op_name, result shape,
    opcode, called computation or None)}) for every instruction of
    every computation of a compiled module's text
    (``compile().as_text()``), and {computation name: [the names of
    its instructions]}. The instructions of a fused computation are
    among them: a fusion's own ``op_name`` is ONE of its members'
    (``calls=`` says which computation holds them), so a fusion that
    runs two mechanisms' operations can be told from its members'
    names. An instruction's name is unique in its module, and a device
    event of a profiler's trace begins with it
    (``%fusion.12 = bf16[20,1024]{...} fusion(...)``)."""
    found = _MODULE_RE.search(hlo_text)
    module = found.group(1) if found else None
    table, computations, members = {}, {}, None
    for line in hlo_text.splitlines():
        if line.endswith("{") and "->" in line and " = " not in line:
            # `%fused_computation.3 (param_0: f32[8]) -> f32[8] {`
            head = line.strip()
            if head.startswith("ENTRY "):
                head = head[6:]
            members = computations.setdefault(
                head.partition(" ")[0].lstrip("%"), [])
            continue
        head = _instruction_head(line)
        if head is None:
            continue
        name, shape, opcode = head
        op_name = _OP_NAME_RE.search(line)
        calls = _CALLS_RE.search(line) if opcode == "fusion" else None
        table[name] = (op_name.group(1) if op_name else "", shape, opcode,
                       calls.group(1) if calls else None)
        if members is not None:
            members.append(name)
    return module, table, computations


def instruction_scopes(hlo_text):
    """-> {instruction name: ``op_name``} for every instruction of
    every computation of a compiled module's text, fused computations'
    members too: the scope path each was traced under
    (``jit(decode)/gdn.chunk/dot_general``; "" where XLA made the
    instruction itself). docs/telemetry.md, "Device scopes"."""
    return {name: row[0]
            for name, row in instruction_table(hlo_text)[1].items()}


def census_classes(census, data_labels, normalize_allreduce=False):
    """Fold one census into the wire estimator's class vocabulary for
    the DATA-axis labels: explicit gathers -> allgather, reductions ->
    reduce, ring ppermute hops -> ring (our own decompositions — the
    caller knows whether its rings serve gathers, reductions or both).

    ``normalize_allreduce``: price data-axis all-reduces at their
    reduce-scatter ring equivalent (half). Backends without XLA's
    ReduceScatterCreator pass (the CPU rung) leave GSPMD's
    all-reduce+dynamic-slice unrewritten where the TPU target emits a
    true reduce-scatter; pass True when the plan shards the gradients
    (stage >= 2) so the CPU census compares against the TPU-target
    model. The raw per-op list keeps the unnormalized bytes.
    """
    out = {"allgather_bytes": 0, "reduce_bytes": 0, "ring_bytes": 0,
           "data_other_bytes": 0, "other_axis_bytes": 0,
           "explicit_bytes": 0}
    for op in census["ops"]:
        if op.get("explicit") and op["axis"] in data_labels:
            # our hand-written shard_map collectives (quantized / 1-bit
            # exchange bodies): tallied separately — their bytes are
            # deterministic and must equal the estimator EXACTLY
            out["explicit_bytes"] += op["wire_bytes"]
        if op["axis"] not in data_labels:
            out["other_axis_bytes"] += op["wire_bytes"]
            continue
        if op["opcode"] == "all-gather":
            out["allgather_bytes"] += op["wire_bytes"]
        elif op["opcode"] in ("all-reduce", "reduce-scatter"):
            wire = op["wire_bytes"]
            if normalize_allreduce and op["opcode"] == "all-reduce":
                wire //= 2
            out["reduce_bytes"] += wire
        elif op["opcode"] == "collective-permute":
            out["ring_bytes"] += op["wire_bytes"]
        else:
            # a data-axis collective in NO wire class (e.g. a GSPMD
            # resharding all-to-all) is exactly the "unplanned
            # collective behind your back" this census exists to catch
            # — it must count toward the reconciled total
            out["data_other_bytes"] += op["wire_bytes"]
    out["data_total_bytes"] = (out["allgather_bytes"] +
                               out["reduce_bytes"] + out["ring_bytes"] +
                               out["data_other_bytes"])
    return out


def reconcile_wire(census_list, wire_est, data_labels, program="step",
                   min_bytes=CENSUS_MIN_BYTES_DEFAULT,
                   normalize_allreduce=False):
    """Diff the summed HLO census of one optimizer step's programs
    against the wire estimator's per-step classes.

    Returns (payload, findings): the payload embeds both sides and the
    per-class deltas; findings flag collectives the estimator did not
    price (census > estimate) and estimates the HLO does not back
    (estimate > census). ``normalize_allreduce``: see
    :func:`census_classes` — pass True when the plan shards the grads
    (stage >= 2) and the backend lacks the all-reduce->reduce-scatter
    rewrite.
    """
    classes = {"allgather_bytes": 0, "reduce_bytes": 0, "ring_bytes": 0,
               "data_other_bytes": 0, "other_axis_bytes": 0,
               "explicit_bytes": 0, "data_total_bytes": 0}
    for census in census_list:
        part = census_classes(census, data_labels,
                              normalize_allreduce=normalize_allreduce)
        for key in classes:
            classes[key] += part[key]
    est_ag = int(wire_est.get("allgather_bytes_per_step",
                              wire_est.get("allgather_bytes", 0)) or 0)
    est_rs = int(wire_est.get("reduce_bytes_per_step",
                              wire_est.get("reduce_bytes", 0)) or 0)
    # the compressed-comm tier's classes (wire.py): the in-collective
    # quantized gradient exchange reprices the reduce class (flat or the
    # hierarchical two-level formula — quantized_allreduce_bytes); the
    # 1-bit momentum exchange is its own class. Census-side these land
    # as data-axis collective-permutes (ring hops -> ring_bytes),
    # all-to-alls (the sign exchange -> data_other_bytes) and
    # all-gathers, so only the TOTAL reconciles class-exactly.
    est_opt = int(wire_est.get("optimizer_bytes_per_step", 0) or 0)
    est_total = est_ag + est_rs + est_opt
    payload = {
        "program": program,
        "estimator": {"allgather_bytes": est_ag, "reduce_bytes": est_rs,
                      "optimizer_bytes": est_opt,
                      "total_bytes": est_total},
        "quantized": bool(est_opt or
                          wire_est.get("quantized_collectives")),
        "hlo": classes,
        "delta_total_bytes": classes["data_total_bytes"] - est_total,
        "match_total": classes["data_total_bytes"] == est_total,
        # per-class comparison is only meaningful when no ring hops blur
        # the attribution (a ppermute ring can serve either class)
        "match_classes": (classes["ring_bytes"] == 0 and
                          classes["allgather_bytes"] == est_ag and
                          classes["reduce_bytes"] == est_rs),
        # the explicitly-decomposed class: when the program's stage-3
        # gathers run as OUR ppermute rings (collective_matmul), the
        # ring bytes are deterministic and must equal the estimator's
        # allgather class exactly — the byte-for-byte census contract
        # the dryrun analysis leg pins (None when no rings ran, or when
        # the rings serve the QUANTIZED reduce class instead)
        "match_ring_allgather": (classes["ring_bytes"] == est_ag
                                 if classes["ring_bytes"] and not est_opt
                                 and not wire_est.get(
                                     "quantized_collectives") else None),
        # the compressed-comm contract: the hand-written shard_map
        # exchanges (1-bit momentum + in-collective quantized reduce,
        # incl. the hierarchical two-level decomposition) have
        # deterministic instruction-level bytes — the census must equal
        # the estimator's exchange classes EXACTLY. None when no
        # quantized exchange is priced.
        "match_exchange": (
            classes["explicit_bytes"] == est_opt +
            (est_rs if wire_est.get("quantized_collectives") else 0)
            if (est_opt or wire_est.get("quantized_collectives"))
            else None),
    }
    findings = []
    if classes["data_total_bytes"] > est_total and \
            classes["data_total_bytes"] - est_total >= min_bytes:
        findings.append(Finding(
            rule="sharding_drift", check="unpriced_collective",
            program=program,
            message="the lowered step moves {:,} data-axis collective "
                    "bytes but the wire estimator prices {:,} — XLA "
                    "inserted {:,} bytes of collectives the plan did not "
                    "anticipate (an unplanned all-gather behind your "
                    "back)".format(classes["data_total_bytes"], est_total,
                                   classes["data_total_bytes"] - est_total),
            key="unpriced_collective:{}".format(program),
            details=payload))
    elif est_total > classes["data_total_bytes"] and \
            est_total - classes["data_total_bytes"] >= min_bytes:
        findings.append(Finding(
            rule="sharding_drift", check="overpriced_estimate",
            program=program,
            message="the wire estimator prices {:,} data-axis collective "
                    "bytes but the lowered step only moves {:,} — the "
                    "estimator books collectives XLA never emits (its "
                    "model has drifted from the program)".format(
                        est_total, classes["data_total_bytes"]),
            key="overpriced_estimate:{}".format(program),
            details=payload))
    return payload, findings
