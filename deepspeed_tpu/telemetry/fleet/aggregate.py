"""Multi-host aggregation: join per-host telemetry JSONLs into one
fleet-level record per optimizer step (ISSUE 14; docs/fleet.md).

Every process already writes its own ``telemetry.jsonl`` /
``spans.jsonl`` / ``trace_events.json`` under a role-suffixed
``job_name`` directory — this module adds the two missing pieces:

* a **per-host manifest** (``host_manifest.json``, written by the
  collector at init) naming the host/pid/process-index and the files
  it will write, so the merger discovers hosts structurally instead of
  guessing from directory names;
* a **merger** (:func:`merge_run`) that joins the per-host records ON
  OPTIMIZER STEP — steps are barrier-synchronized across the mesh, so
  the step index is the fleet clock — and estimates each host's wall
  offset from step-completion skew (the median of per-step wall deltas
  against a reference host; a skewed NTP clock shifts every delta by
  the same amount, while genuine per-step jitter has zero median).

Torn inputs degrade, never drop silently: a JSONL ending mid-line
(crash), a missing manifest, or a host whose record stream stops early
each produce a ``gaps`` entry AND keep the host's intact steps in the
merged view. A host that left a flight-recorder crash bundle
contributes the bundle's record ring for the steps its JSONL lost.

Stdlib-only (the fleet-package contract; see metrics.py).
"""
import glob
import json
import logging
import os
import socket
import time

from .straggler import (StragglerDetector, ici_health_from_record,
                        true_median)

logger = logging.getLogger("DeepSpeedTPU")

MANIFEST_NAME = "host_manifest.json"
KIND_MANIFEST = "host_manifest"
KIND_FLEET_STEP = "fleet_step"
KIND_FLEET_REPORT = "fleet_report"

# duplicated from telemetry/collector.py (stdlib-import contract);
# pinned equal by tests/unit/test_fleet.py
JSONL_NAME = "telemetry.jsonl"
SPANS_JSONL_NAME = "spans.jsonl"
CHROME_TRACE_NAME = "trace_events.json"

# every host manifest carries exactly these keys
HOST_MANIFEST_KEYS = (
    "kind", "job_name", "host", "pid", "process_index", "wall_start",
    "files", "metrics_port",
)

# optional manifest extension (ISSUE 15): the host's canonical program
# fingerprint — analysis/concurrency/divergence.py derives/publishes
# it; this module only compares. Keys duplicated from FINGERPRINT_KEYS
# there (stdlib-import contract); pinned equal by
# tests/unit/test_concurrency.py
MANIFEST_FINGERPRINT_KEY = "program_fingerprint"
FINGERPRINT_KEYS = ("version", "digest", "families")

# every merged fleet report carries exactly these top-level keys
# (bin/check_bench_schema.py holds the stdlib twin, pinned equal by
# tests/unit/test_concurrency.py)
FLEET_REPORT_KEYS = (
    "kind", "run_dir", "n_hosts", "hosts", "offsets", "records", "gaps",
    "straggler", "ici_health", "trace", "divergence", "rescale",
    "router",
)

# elastic rescale events (ISSUE 16): file name + kind + schema
# duplicated from runtime/elastic/events.py (stdlib-import contract);
# pinned equal by tests/unit/test_elastic_rescale.py
RESCALE_EVENTS_JSONL = "rescale_events.jsonl"
KIND_RESCALE_EVENT = "rescale_event"
RESCALE_EVENT_KEYS = (
    "kind", "event", "wall", "reason", "attempt",
    "old_world", "new_world", "old_mesh", "new_mesh",
    "outcome", "detail",
)

# disaggregated-serving router events (ISSUE 17): file name + kind +
# schema duplicated from inference/fleet/events.py (stdlib-import
# contract); pinned equal by tests/unit/test_serving_fleet.py
ROUTER_EVENTS_JSONL = "router_events.jsonl"
KIND_ROUTER_EVENT = "router_event"
ROUTER_EVENT_KEYS = (
    "kind", "wall", "decision", "request_uid", "host", "reason",
    "predicted_cost_s", "detail",
)
ROUTER_DECISIONS = ("admit", "deny", "route_away", "preempt_migrate",
                    "enroll", "enroll_refusal")
# serving-role vocabulary duplicated from telemetry/record.py
# (SERVING_ROLES), same pin
SERVING_ROLES = ("monolith", "prefill", "decode", "router")

# every merged fleet-step record carries exactly these keys
FLEET_STEP_KEYS = (
    "kind", "step", "n_hosts", "wall", "hosts", "step_time",
    "missing_hosts",
)
# per-host sub-dict keys inside a fleet-step record
FLEET_HOST_KEYS = (
    "wall", "wall_corrected", "offset_s", "step_time_s", "loss", "mfu",
    "phases", "per_kind", "hbm_peak", "ici_health",
)

_NUMERIC = (int, float)


# --------------------------------------------------------------- manifest
def write_host_manifest(output_dir, job_name, metrics_port=None,
                        process_index=None, process_count=None,
                        fingerprint=None, wall_start=None):
    """Write ``host_manifest.json`` atomically into this host's
    telemetry directory (collector init). Never raises — a manifest
    failure must not kill engine construction. ``fingerprint``: the
    optional canonical program fingerprint (ISSUE 15) — published when
    the engine audited/derived one, so the fleet doctor can verify
    every host lowered the SAME collective sequence. ``wall_start``:
    pass the collector's recorded start on RE-writes so a fingerprint
    published hours into a run does not replace the process-start
    timestamp with the audit time."""
    payload = {
        "kind": KIND_MANIFEST,
        "job_name": job_name,
        "host": socket.gethostname(),
        "pid": os.getpid(),
        "process_index": process_index,
        "wall_start": time.time() if wall_start is None else wall_start,
        "files": {"telemetry": JSONL_NAME, "spans": SPANS_JSONL_NAME,
                  "chrome_trace": CHROME_TRACE_NAME},
        "metrics_port": metrics_port,
    }
    if process_count is not None:
        payload["process_count"] = process_count
    if fingerprint is not None:
        payload[MANIFEST_FINGERPRINT_KEY] = fingerprint
    try:
        os.makedirs(output_dir, exist_ok=True)
        path = os.path.join(output_dir, MANIFEST_NAME)
        tmp = path + ".tmp"
        with open(tmp, "w") as fh:
            json.dump(payload, fh, indent=2)
        os.replace(tmp, path)
        return path
    except OSError as err:
        logger.warning("fleet: could not write %s (%s)", MANIFEST_NAME,
                       err)
        return None


def validate_host_manifest(payload):
    problems = []
    if not isinstance(payload, dict):
        return ["manifest is not a dict"]
    if payload.get("kind") != KIND_MANIFEST:
        return ["unknown manifest kind {!r}".format(payload.get("kind"))]
    for key in HOST_MANIFEST_KEYS:
        if key not in payload:
            problems.append("missing key {!r}".format(key))
    if not problems and not isinstance(payload.get("files"), dict):
        problems.append("files is not a dict")
    fp = payload.get(MANIFEST_FINGERPRINT_KEY)
    if fp is not None:
        if not isinstance(fp, dict):
            problems.append("program_fingerprint is not a dict")
        else:
            for key in FINGERPRINT_KEYS:
                if key not in fp:
                    problems.append(
                        "program_fingerprint missing {!r}".format(key))
            if not isinstance(fp.get("families", {}), dict):
                problems.append(
                    "program_fingerprint.families is not a dict")
    return problems


# ------------------------------------------------------- divergence
def compare_fingerprints(fingerprints):
    """Cross-host SPMD divergence check over the published manifest
    fingerprints (``{host: program_fingerprint dict}``; hosts that
    published none are reported but never flagged — absence is a
    coverage gap, not a divergence). The REFERENCE digest is the
    majority one (ties break to the alphabetically-first publishing
    host), so a single divergent host in an 8-host mesh is named as
    THE divergent one rather than flagging the seven agreeing hosts.
    Returns the ``divergence`` section of the fleet report;
    ``analysis/concurrency/divergence.py`` turns a mismatch into
    ``fleet_divergence`` findings."""
    published = {h: fp for h, fp in sorted((fingerprints or {}).items())
                 if isinstance(fp, dict) and fp.get("digest")}
    out = {
        "published": len(published),
        "unpublished_hosts": sorted(set(fingerprints or {})
                                    - set(published)),
        "digests": {h: fp["digest"] for h, fp in published.items()},
        "families": {h: fp.get("families") or {}
                     for h, fp in published.items()},
        "mismatch": False,
        "reference": None,
        "divergent_hosts": [],
    }
    if not published:
        return out
    votes = {}
    for host, fp in published.items():
        votes.setdefault(fp["digest"], []).append(host)
    # majority digest; ties break to the alphabetically-first host
    best = max(len(hosts) for hosts in votes.values())
    tied = [d for d, hosts in votes.items() if len(hosts) == best]
    ref_digest = min(tied, key=lambda d: votes[d][0])
    out["reference"] = votes[ref_digest][0]
    out["divergent_hosts"] = sorted(
        h for h, fp in published.items() if fp["digest"] != ref_digest)
    out["mismatch"] = bool(out["divergent_hosts"])
    if out["mismatch"]:
        logger.warning(
            "fleet divergence: host(s) %s published a DIFFERENT "
            "program fingerprint than reference host %s — the mesh "
            "will hang at the first divergent collective",
            ", ".join(out["divergent_hosts"]), out["reference"])
    return out


# ----------------------------------------------------------- JSONL reads
def read_jsonl_tolerant(path):
    """Parse a JSONL that may be TORN (the writer crashed mid-line):
    returns ``(records, problems)`` where a malformed FINAL line is
    reported as a torn tail (the expected crash shape) and a malformed
    interior line as corruption — both flagged, neither fatal."""
    records, problems = [], []
    try:
        with open(path) as fh:
            lines = fh.read().splitlines()
    except OSError as err:
        return [], ["unreadable {}: {}".format(path, err)]
    for i, line in enumerate(lines):
        if not line.strip():
            continue
        try:
            records.append(json.loads(line))
        except ValueError:
            if i == len(lines) - 1:
                problems.append(
                    "torn tail (crash mid-write) at {}:{}".format(
                        os.path.basename(path), i + 1))
            else:
                problems.append("corrupt line at {}:{}".format(
                    os.path.basename(path), i + 1))
    return records, problems


class HostView:
    """One host's loaded telemetry: manifest (or None), train/serving
    records, crash-bundle adoption state, and its gap strings."""

    def __init__(self, name, path):
        self.name = name
        self.path = path
        self.manifest = None
        self.records = []           # train_step records, step order
        self.serving_steps = 0
        # serving-step counts per fleet role ("monolith"/"prefill"/
        # "decode"/"router"; records with role null count as monolith)
        self.serving_roles = {}
        self.crashed = False
        self.crash_reason = None
        self.gaps = []

    def summary(self):
        return {
            "name": self.name,
            "steps": len(self.records),
            "serving_steps": self.serving_steps,
            "serving_roles": dict(self.serving_roles),
            "manifest": self.manifest is not None,
            "crashed": self.crashed,
            "crash_reason": self.crash_reason,
            "gaps": list(self.gaps),
        }


def load_host(path, name=None):
    """Load one host directory (a collector's ``<output_path>/<job>``):
    manifest + tolerant JSONL + crash-bundle record adoption."""
    host = HostView(name or os.path.basename(os.path.normpath(path)),
                    path)
    manifest_path = os.path.join(path, MANIFEST_NAME)
    if os.path.exists(manifest_path):
        try:
            with open(manifest_path) as fh:
                manifest = json.load(fh)
            problems = validate_host_manifest(manifest)
            if problems:
                host.gaps.append("invalid manifest: {}".format(
                    "; ".join(problems)))
            else:
                host.manifest = manifest
        except ValueError as err:
            host.gaps.append("unparseable manifest: {}".format(err))
    else:
        host.gaps.append("missing host manifest")
    jsonl = os.path.join(path, JSONL_NAME)
    records = []
    if os.path.exists(jsonl):
        records, problems = read_jsonl_tolerant(jsonl)
        host.gaps.extend(problems)
        # a rotated predecessor still holds the run's older steps
        if os.path.exists(jsonl + ".1"):
            older, older_problems = read_jsonl_tolerant(jsonl + ".1")
            records = older + records
            host.gaps.extend(older_problems)
    else:
        host.gaps.append("no {}".format(JSONL_NAME))
    def usable(rec):
        """A train record the merger can join: integer-able step +
        numeric wall. Anything else (older schema, a ring record with
        nulled fields, a brace-closing partial flush) degrades to a
        gaps entry — the tolerance contract covers VALID-JSON junk
        too, not just torn lines."""
        step, wall = rec.get("step"), rec.get("wall")
        return (isinstance(step, int) and not isinstance(step, bool)
                and isinstance(wall, _NUMERIC)
                and not isinstance(wall, bool))

    by_step = {}
    dropped = 0
    for rec in records:
        if rec.get("kind") == "train_step":
            if usable(rec):
                by_step[int(rec["step"])] = rec
            else:
                dropped += 1
        elif rec.get("kind") == "serving_step":
            host.serving_steps += 1
            role = rec.get("role")
            role = role if isinstance(role, str) and \
                role in SERVING_ROLES else "monolith"
            host.serving_roles[role] = host.serving_roles.get(role, 0) + 1
    if dropped:
        host.gaps.append("{} train record(s) without a usable "
                         "step/wall skipped".format(dropped))
    # crash bundles: the flight recorder's record ring covers the steps
    # the torn JSONL lost; the newest bundle names why the host died
    bundles = sorted(glob.glob(os.path.join(path, "crash",
                                            "bundle_*.json")))
    for bundle_path in bundles[-1:]:
        try:
            with open(bundle_path) as fh:
                bundle = json.load(fh)
        except ValueError as err:
            host.gaps.append("unparseable crash bundle {}: {}".format(
                os.path.basename(bundle_path), err))
            continue
        host.crashed = True
        host.crash_reason = bundle.get("reason")
        host.gaps.append("crash bundle: {}".format(host.crash_reason))
        adopted = 0
        for rec in bundle.get("records") or []:
            if isinstance(rec, dict) and \
                    rec.get("kind") == "train_step" and usable(rec) \
                    and int(rec["step"]) not in by_step:
                by_step[int(rec["step"])] = rec
                adopted += 1
        if adopted:
            host.gaps.append(
                "{} step record(s) adopted from the crash "
                "bundle".format(adopted))
    host.records = [by_step[s] for s in sorted(by_step)]
    return host


def discover_hosts(run_dir):
    """Every subdirectory of ``run_dir`` that looks like a collector
    output (has a manifest, a telemetry JSONL, or a crash directory) —
    plus ``run_dir`` itself when it IS one host's directory."""
    def is_host_dir(path):
        return any(os.path.exists(os.path.join(path, probe))
                   for probe in (MANIFEST_NAME, JSONL_NAME, "crash"))

    hosts = []
    if is_host_dir(run_dir):
        hosts.append(run_dir)
    for entry in sorted(os.listdir(run_dir)):
        path = os.path.join(run_dir, entry)
        if os.path.isdir(path) and is_host_dir(path):
            hosts.append(path)
    return hosts


# ------------------------------------------------------------ clock skew
def estimate_offsets(hosts):
    """Per-host wall offset (seconds) relative to the first host, from
    step-completion skew: steps are barrier-synchronized, so for each
    common step the wall delta between two hosts is clock offset plus
    per-step jitter — the MEDIAN delta over the common steps is the
    offset (jitter is zero-median; a skewed clock shifts every delta)."""
    if not hosts:
        return {}
    ref = hosts[0]
    ref_walls = {int(r["step"]): float(r["wall"]) for r in ref.records}
    offsets = {ref.name: 0.0}
    for host in hosts[1:]:
        deltas = [
            float(r["wall"]) - ref_walls[int(r["step"])]
            for r in host.records if int(r["step"]) in ref_walls]
        offsets[host.name] = true_median(deltas) if deltas else 0.0
    return offsets


# ---------------------------------------------------------------- merge
def _host_slot(rec, offset):
    offload = rec.get("offload") or {}
    hbm = rec.get("hbm") or {}
    health = ici_health_from_record(rec)
    return {
        "wall": float(rec["wall"]),
        "wall_corrected": float(rec["wall"]) - offset,
        "offset_s": round(offset, 6),
        "step_time_s": rec.get("step_time_s"),
        "loss": rec.get("loss"),
        "mfu": rec.get("mfu"),
        "phases": rec.get("phases") or {},
        "per_kind": offload.get("per_kind") or None,
        "hbm_peak": hbm.get("peak_bytes_in_use")
        if hbm.get("available") else None,
        "ici_health": health or None,
    }


def merge_records(hosts, offsets=None):
    """-> list of fleet-step records, one per optimizer step observed
    by ANY host; hosts missing a step are named in ``missing_hosts``
    (the merged view flags the gap rather than dropping the host)."""
    offsets = offsets if offsets is not None else estimate_offsets(hosts)
    by_step = {}
    for host in hosts:
        for rec in host.records:
            by_step.setdefault(int(rec["step"]), {})[host.name] = rec
    names = [h.name for h in hosts]
    merged = []
    for step in sorted(by_step):
        recs = by_step[step]
        slots = {name: _host_slot(rec, offsets.get(name, 0.0))
                 for name, rec in recs.items()}
        walls = sorted((slot["step_time_s"], name)
                       for name, slot in slots.items()
                       if slot["step_time_s"] is not None)
        step_time = None
        if walls:
            vals = [w for w, _ in walls]
            step_time = {
                "median": true_median(vals),
                "min": vals[0],
                "max": vals[-1],
                "max_host": walls[-1][1],
            }
        merged.append({
            "kind": KIND_FLEET_STEP,
            "step": step,
            "n_hosts": len(slots),
            "wall": min(s["wall_corrected"] for s in slots.values()),
            "hosts": slots,
            "step_time": step_time,
            "missing_hosts": sorted(n for n in names if n not in recs),
        })
    return merged


def validate_fleet_record(rec):
    """Schema check for one merged fleet-step record; list of problem
    strings, empty = valid (the test/dryrun contract, like
    validate_step_record)."""
    problems = []
    if not isinstance(rec, dict):
        return ["record is not a dict"]
    if rec.get("kind") != KIND_FLEET_STEP:
        return ["unknown record kind {!r}".format(rec.get("kind"))]
    for key in FLEET_STEP_KEYS:
        if key not in rec:
            problems.append("missing key {!r}".format(key))
    extra = sorted(set(rec) - set(FLEET_STEP_KEYS))
    if extra:
        problems.append("unexpected key(s) {}".format(extra))
    if problems:
        return problems
    for key in ("step", "n_hosts", "wall"):
        val = rec[key]
        if isinstance(val, bool) or not isinstance(val, _NUMERIC):
            problems.append("{} is not a number: {!r}".format(key, val))
    if not isinstance(rec["missing_hosts"], list):
        problems.append("missing_hosts is not a list")
    hosts = rec["hosts"]
    if not isinstance(hosts, dict) or not hosts:
        problems.append("hosts is not a non-empty dict")
        return problems
    for name, slot in hosts.items():
        if not isinstance(slot, dict):
            problems.append("hosts.{} is not a dict".format(name))
            continue
        for key in FLEET_HOST_KEYS:
            if key not in slot:
                problems.append("hosts.{} missing {!r}".format(name, key))
        for key in ("wall", "wall_corrected", "offset_s"):
            val = slot.get(key)
            if isinstance(val, bool) or not isinstance(val, _NUMERIC):
                problems.append(
                    "hosts.{}.{} is not a number: {!r}".format(
                        name, key, val))
    st = rec["step_time"]
    if st is not None:
        for key in ("median", "min", "max"):
            val = st.get(key) if isinstance(st, dict) else None
            if isinstance(val, bool) or not isinstance(val, _NUMERIC):
                problems.append(
                    "step_time.{} is not a number: {!r}".format(key, val))
    return problems


def merge_run(run_dir, factor=None, k=None, min_hosts=None,
              trace_out=None):
    """Merge a run directory (live or post-mortem) into one fleet
    report: discovery -> tolerant per-host loads -> clock-offset
    estimation -> per-step merge -> straggler/ICI attribution.
    ``trace_out``: also write the merged multi-process Chrome trace
    there, reusing the hosts this merge already loaded (the report
    gains a ``trace`` sub-dict and the trace parse's gaps are
    reported, not lost)."""
    host_dirs = discover_hosts(run_dir)
    if not host_dirs:
        raise FileNotFoundError(
            "no host telemetry directories under {!r} (a host dir "
            "holds {} or {})".format(run_dir, MANIFEST_NAME, JSONL_NAME))
    hosts = [load_host(p) for p in host_dirs]
    offsets = estimate_offsets(hosts)
    records = merge_records(hosts, offsets)
    trace = None
    if trace_out is not None:
        # before the summaries/gaps are built, so an unparseable
        # per-host trace lands in the report
        path, events, hosts_merged = merge_chrome_traces(
            hosts, offsets, trace_out)
        trace = {"path": os.path.abspath(path), "events": events,
                 "hosts_merged": hosts_merged}
    detector = StragglerDetector(factor=factor, k=k, min_hosts=min_hosts)
    for rec in records:
        detector.observe(rec)
    ici_last = {}
    for rec in records:
        for name, slot in rec["hosts"].items():
            if slot.get("ici_health"):
                ici_last.setdefault(name, {}).update(
                    {cls: v for cls, v in slot["ici_health"].items()
                     if v is not None})
    gaps = []
    for host in hosts:
        gaps.extend("{}: {}".format(host.name, g) for g in host.gaps)
    # SPMD divergence (ISSUE 15): compare the program fingerprints the
    # hosts' manifests published — a mismatch means one host lowered a
    # different collective sequence and the mesh WILL hang on a pod
    divergence = compare_fingerprints({
        h.name: (h.manifest or {}).get(MANIFEST_FINGERPRINT_KEY)
        for h in hosts})
    # elastic rescale events (ISSUE 16): each host appends its topology
    # changes to rescale_events.jsonl; the fleet view is their wall-
    # ordered union, so `ds_fleet` can show WHEN the run changed shape
    # next to the step records it produced at each shape
    rescale_events = []
    for host in hosts:
        path = os.path.join(host.path, RESCALE_EVENTS_JSONL)
        if not os.path.exists(path):
            continue
        events, problems = read_jsonl_tolerant(path)
        host.gaps.extend(problems)
        gaps.extend("{}: {}".format(host.name, p) for p in problems)
        for ev in events:
            if isinstance(ev, dict) and \
                    ev.get("kind") == KIND_RESCALE_EVENT:
                rescale_events.append(dict(ev, host=host.name))
    rescale_events.sort(
        key=lambda ev: ev["wall"]
        if isinstance(ev.get("wall"), _NUMERIC)
        and not isinstance(ev.get("wall"), bool) else 0.0)
    rescale = {
        "count": len(rescale_events),
        "completed": sum(1 for ev in rescale_events
                         if ev.get("event") == "rescale"),
        "events": rescale_events,
    }
    # disaggregated-serving router decisions (ISSUE 17): the front-end
    # router's event log rides the same per-host JSONL discipline as
    # rescale events; the fleet view is the wall-ordered union plus a
    # per-decision tally, so `ds_fleet` can show WHY each host did or
    # did not receive serving work
    router_events = []
    for host in hosts:
        path = os.path.join(host.path, ROUTER_EVENTS_JSONL)
        if not os.path.exists(path):
            continue
        events, problems = read_jsonl_tolerant(path)
        host.gaps.extend(problems)
        gaps.extend("{}: {}".format(host.name, p) for p in problems)
        for ev in events:
            if isinstance(ev, dict) and \
                    ev.get("kind") == KIND_ROUTER_EVENT:
                router_events.append(dict(ev, source=host.name))
    router_events.sort(
        key=lambda ev: ev["wall"]
        if isinstance(ev.get("wall"), _NUMERIC)
        and not isinstance(ev.get("wall"), bool) else 0.0)
    decisions = {}
    for ev in router_events:
        d = ev.get("decision")
        if isinstance(d, str):
            decisions[d] = decisions.get(d, 0) + 1
    router = {
        "count": len(router_events),
        "decisions": decisions,
        "events": router_events,
    }
    return {
        "kind": KIND_FLEET_REPORT,
        "run_dir": os.path.abspath(run_dir),
        "n_hosts": len(hosts),
        "hosts": [h.summary() for h in hosts],
        "offsets": {k_: round(v, 6) for k_, v in offsets.items()},
        "records": records,
        "gaps": gaps,
        "straggler": detector.report(),
        "ici_health": ici_last,
        "trace": trace,
        "divergence": divergence,
        "rescale": rescale,
        "router": router,
    }


# ------------------------------------------------------- merged traces
def _parse_trace_events(text):
    """Lenient Chrome-trace parse (the live/crashed file is the
    Perfetto-tolerated unclosed-array form) — the fleet twin of
    bin/check_bench_schema.py's parser."""
    text = text.strip()
    try:
        payload = json.loads(text)
    except ValueError:
        try:
            payload = json.loads(text.rstrip(",\n\t ") + "]")
        except ValueError:
            return None
    if isinstance(payload, dict):
        payload = payload.get("traceEvents")
    return payload if isinstance(payload, list) else None


def merge_chrome_traces(hosts, offsets, out_path):
    """Merge the per-host ``trace_events.json`` files into ONE
    Perfetto-loadable trace: each host becomes its own process lane
    (``pid`` = host index, a ``process_name`` metadata event naming
    it), with every timestamp offset-corrected onto the reference
    host's clock. Returns (path, events_written, hosts_merged)."""
    merged = []
    hosts_merged = 0
    for pid, host in enumerate(hosts):
        trace_path = os.path.join(host.path, CHROME_TRACE_NAME)
        if not os.path.exists(trace_path):
            continue
        with open(trace_path) as fh:
            events = _parse_trace_events(fh.read())
        if events is None:
            host.gaps.append("unparseable {}".format(CHROME_TRACE_NAME))
            continue
        hosts_merged += 1
        offset_us = offsets.get(host.name, 0.0) * 1e6
        merged.append({"name": "process_name", "ph": "M", "pid": pid,
                       "tid": 0, "ts": 0,
                       "args": {"name": host.name}})
        for ev in events:
            if not isinstance(ev, dict):
                continue
            ev = dict(ev, pid=pid)
            if isinstance(ev.get("ts"), _NUMERIC):
                ev["ts"] = ev["ts"] - offset_us
            merged.append(ev)
    _rehome_cross_host_requests(merged, len(hosts))
    with open(out_path, "w") as fh:
        json.dump(merged, fh)       # strict JSON: always loadable
    return out_path, len(merged), hosts_merged


def _rehome_cross_host_requests(merged, req_pid):
    """A disaggregated request is ONE trace: spans that carry the same
    ``args.trace_id`` from two or more host processes (the prefill
    role's work and the decode role's continuation) are re-homed into
    a shared ``requests`` process lane, one thread row per trace_id,
    so the handoff reads as a single per-request timeline instead of
    two unrelated fragments."""
    seen = {}                       # trace_id -> set of host pids
    for ev in merged:
        tid = _event_trace_id(ev)
        if tid is not None:
            seen.setdefault(tid, set()).add(ev.get("pid"))
    cross = sorted(t for t, pids in seen.items() if len(pids) >= 2)
    if not cross:
        return
    rows = {t: i for i, t in enumerate(cross)}
    for ev in merged:
        tid = _event_trace_id(ev)
        if tid in rows:
            ev["pid"] = req_pid
            ev["tid"] = rows[tid]
    merged.append({"name": "process_name", "ph": "M", "pid": req_pid,
                   "tid": 0, "ts": 0, "args": {"name": "requests"}})
    for tid, row in rows.items():
        merged.append({"name": "thread_name", "ph": "M", "pid": req_pid,
                       "tid": row, "ts": 0, "args": {"name": tid}})


def _event_trace_id(ev):
    if ev.get("ph") == "M":
        return None
    args = ev.get("args")
    if isinstance(args, dict):
        tid = args.get("trace_id")
        if isinstance(tid, str) and tid:
            return tid
    return None
