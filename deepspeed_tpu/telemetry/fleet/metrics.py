"""Fleet metrics plane: counter/gauge/histogram primitives fed from the
EXISTING StepRecord stream (ISSUE 14).

The :class:`MetricsSink` is registered in the telemetry collector's
sink list — the hot paths gain NO new instrumentation; every series
below is derived from the one StepRecord the step already emits (train
or serving), plus the watchdog's trip/TTFT counters at emit time. The
:class:`MetricsRegistry` renders the Prometheus text exposition format
(version 0.0.4) served by ``export.MetricsExporter`` over ``/metrics``.

Every exported series name MUST appear in docs/fleet.md's metric
catalog — ``bin/ds_lint.py`` rule **DSL007** greps the first-argument
string literal of each ``.counter()``/``.gauge()``/``.histogram()``
call site against that catalog, so an undocumented metric fails CI
(the baseline mechanism of the other DSL rules applies).

This module is STDLIB-ONLY and imports siblings only relatively, so
``bin/ds_fleet.py`` can mount the fleet package under a synthetic name
(the ``bin/ds_lint.py`` trick) and run on a box without jax.
"""
import re
import threading

from .straggler import ici_health_from_record

# record kinds, duplicated from telemetry/record.py (this module must
# stay stdlib-importable without the package __init__ chain); pinned
# equal by tests/unit/test_fleet.py
KIND_TRAIN = "train_step"
KIND_SERVING = "serving_step"

METRIC_KINDS = ("counter", "gauge", "histogram")

# default histogram buckets (seconds): spans ms-scale CPU steps to
# multi-second TPU steps; +Inf is implicit
DEFAULT_TIME_BUCKETS = (0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                        1.0, 2.5, 5.0, 10.0, 30.0)

_NAME_RE = re.compile(r"^[a-zA-Z_:][a-zA-Z0-9_:]*$")


def _escape_label(val):
    return str(val).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


def _unescape_label(val):
    # single left-to-right scan: ordered str.replace corrupts values
    # whose literal backslash precedes an 'n' or '"' ('a\nb' -> escaped
    # 'a\\nb' -> naive unescape eats the '\\' pair's tail as '\n')
    return re.sub(r'\\(.)',
                  lambda m: "\n" if m.group(1) == "n" else m.group(1),
                  val)


def _fmt_labels(labels):
    if not labels:
        return ""
    inner = ",".join('{}="{}"'.format(k, _escape_label(v))
                     for k, v in sorted(labels.items()))
    return "{" + inner + "}"


def _fmt_value(val):
    if val == float("inf"):
        return "+Inf"
    return repr(float(val))


class Metric:
    """One metric family: a name, a kind, and one sample per label
    set. Mutations go through the owning registry's lock."""

    __slots__ = ("name", "kind", "help", "buckets", "_samples", "_lock")

    # concurrency-sanitizer declaration (docs/concurrency.md): samples
    # are mutated by the emitting thread and rendered by the exporter's
    # handler threads — every access holds the family lock. (This
    # module is stdlib-only; the sanitizer wraps the lock from the
    # collector side — locksan.instrument_collector.)
    _GUARDED_BY = {"_samples": "_lock"}

    def __init__(self, name, kind, help_text="", buckets=None, lock=None):
        if not _NAME_RE.match(name):
            raise ValueError("invalid metric name {!r}".format(name))
        if kind not in METRIC_KINDS:
            raise ValueError("metric kind must be one of {}, got "
                             "{!r}".format(METRIC_KINDS, kind))
        self.name = name
        self.kind = kind
        self.help = help_text
        self.buckets = tuple(sorted(buckets or DEFAULT_TIME_BUCKETS)) \
            if kind == "histogram" else None
        # frozenset(label items) -> value | histogram state dict
        self._samples = {}
        self._lock = lock or threading.Lock()

    def _key(self, labels):
        return frozenset(labels.items()) if labels else frozenset()

    # ------------------------------------------------------------ counter
    def inc(self, amount=1.0, **labels):
        assert self.kind == "counter", self.name
        key = self._key(labels)
        with self._lock:
            self._samples[key] = self._samples.get(key, 0.0) + \
                float(amount)

    def set_to(self, value, **labels):
        """Counter fed from an already-cumulative source (e.g. a
        record's engine-lifetime token count): monotone — a value below
        the current one is kept (restart semantics are the scraper's
        problem, exactly like node_exporter counters)."""
        assert self.kind == "counter", self.name
        key = self._key(labels)
        with self._lock:
            self._samples[key] = max(self._samples.get(key, 0.0),
                                     float(value))

    # -------------------------------------------------------------- gauge
    def set(self, value, **labels):
        assert self.kind == "gauge", self.name
        with self._lock:
            self._samples[self._key(labels)] = float(value)

    # ---------------------------------------------------------- histogram
    def observe(self, value, **labels):
        assert self.kind == "histogram", self.name
        value = float(value)
        key = self._key(labels)
        with self._lock:
            state = self._samples.get(key)
            if state is None:
                state = {"buckets": [0] * len(self.buckets),
                         "sum": 0.0, "count": 0}
                self._samples[key] = state
            for i, edge in enumerate(self.buckets):
                if value <= edge:
                    state["buckets"][i] += 1
            state["sum"] += value
            state["count"] += 1

    # ------------------------------------------------------------- render
    def value(self, **labels):
        """Current sample value (tests/healthz), None when unset."""
        with self._lock:
            return self._samples.get(self._key(labels))

    def render(self, full_name, const_labels):
        lines = ["# HELP {} {}".format(full_name, self.help or full_name),
                 "# TYPE {} {}".format(full_name, self.kind)]
        with self._lock:
            # histogram state must copy DEEP: dict(v) still aliases the
            # live buckets list, and a concurrent observe() would bump
            # a bucket past the frozen count mid-render
            samples = {k: (dict(v, buckets=list(v["buckets"]))
                           if isinstance(v, dict) else v)
                       for k, v in self._samples.items()}
        for key in sorted(samples, key=lambda k: sorted(k)):
            labels = dict(const_labels)
            labels.update(dict(key))
            val = samples[key]
            if self.kind == "histogram":
                cumulative = 0
                for i, edge in enumerate(self.buckets):
                    cumulative = val["buckets"][i]
                    lines.append("{}_bucket{} {}".format(
                        full_name,
                        _fmt_labels(dict(labels, le=_fmt_value(edge))),
                        cumulative))
                lines.append("{}_bucket{} {}".format(
                    full_name, _fmt_labels(dict(labels, le="+Inf")),
                    val["count"]))
                lines.append("{}_sum{} {}".format(
                    full_name, _fmt_labels(labels),
                    _fmt_value(val["sum"])))
                lines.append("{}_count{} {}".format(
                    full_name, _fmt_labels(labels), val["count"]))
            else:
                lines.append("{}{} {}".format(
                    full_name, _fmt_labels(labels), _fmt_value(val)))
        return lines


class MetricsRegistry:
    """Holds the metric families and renders the exposition text. The
    ``namespace`` prefixes every family name (``telemetry.metrics.
    namespace``, default ``ds``); ``const_labels`` (job/host) ride
    every sample so a fleet scrape can tell processes apart."""

    # sanitizer declaration: the family table is registered from any
    # engine thread and walked by render_text on handler threads
    _GUARDED_BY = {"_metrics": "_lock"}

    def __init__(self, namespace="ds", const_labels=None):
        if namespace and not _NAME_RE.match(namespace):
            raise ValueError(
                "invalid metrics namespace {!r}".format(namespace))
        self.namespace = namespace
        self.const_labels = dict(const_labels or {})
        self._metrics = {}          # name -> Metric
        self._lock = threading.Lock()

    def _get(self, name, kind, help_text, buckets=None):
        with self._lock:
            metric = self._metrics.get(name)
            if metric is None:
                metric = Metric(name, kind, help_text, buckets=buckets)
                self._metrics[name] = metric
            elif metric.kind != kind:
                raise ValueError(
                    "metric {!r} already registered as {}".format(
                        name, metric.kind))
            return metric

    def counter(self, name, help_text=""):
        return self._get(name, "counter", help_text)

    def gauge(self, name, help_text=""):
        return self._get(name, "gauge", help_text)

    def histogram(self, name, help_text="", buckets=None):
        return self._get(name, "histogram", help_text, buckets=buckets)

    def full_name(self, name):
        return "{}_{}".format(self.namespace, name) if self.namespace \
            else name

    @property
    def series_count(self):
        with self._lock:
            return sum(len(m._samples) for m in self._metrics.values())

    def render_text(self):
        """The Prometheus text exposition (version 0.0.4) of every
        family, deterministic order."""
        lines = []
        with self._lock:
            metrics = sorted(self._metrics.items())
        for name, metric in metrics:
            lines.extend(metric.render(self.full_name(name),
                                       self.const_labels))
        return "\n".join(lines) + "\n"


def parse_prometheus_text(text):
    """Minimal stdlib parser for the exposition format: returns
    ``(families, problems)`` where families maps each ``# TYPE``d name
    to ``{"kind": ..., "samples": [(name, labels_dict, value), ...]}``
    (histogram ``_bucket``/``_sum``/``_count`` samples file under the
    family). Problems are format violations (samples with no TYPE line,
    unparseable values) — the dryrun fleet leg and tests validate every
    scrape through this."""
    families = {}
    problems = []
    sample_re = re.compile(
        r"^([a-zA-Z_:][a-zA-Z0-9_:]*)(\{(.*)\})?\s+(\S+)$")
    label_re = re.compile(r'([a-zA-Z_][a-zA-Z0-9_]*)="((?:[^"\\]|\\.)*)"')
    for lineno, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line:
            continue
        if line.startswith("# TYPE "):
            parts = line.split()
            if len(parts) != 4 or parts[3] not in METRIC_KINDS:
                problems.append("line {}: malformed TYPE: {!r}".format(
                    lineno, line))
                continue
            families[parts[2]] = {"kind": parts[3], "samples": []}
            continue
        if line.startswith("#"):
            continue
        m = sample_re.match(line)
        if not m:
            problems.append("line {}: unparseable sample: {!r}".format(
                lineno, line))
            continue
        name, _, label_text, value_text = m.groups()
        family = name
        for suffix in ("_bucket", "_sum", "_count"):
            base = name[:-len(suffix)] if name.endswith(suffix) else None
            if base and base in families and \
                    families[base]["kind"] == "histogram":
                family = base
                break
        if family not in families:
            problems.append(
                "line {}: sample {!r} has no preceding TYPE "
                "line".format(lineno, name))
            continue
        labels = {k: _unescape_label(v)
                  for k, v in label_re.findall(label_text or "")}
        try:
            value = float(value_text.replace("+Inf", "inf"))
        except ValueError:
            problems.append("line {}: non-numeric value {!r}".format(
                lineno, value_text))
            continue
        families[family]["samples"].append((name, labels, value))
    return families, problems


class FleetLocalState:
    """The collector's in-process view of the fleet layer: the last
    ici_health values its own records produced, plus whatever straggler
    flags were ingested from a merged fleet view
    (``TelemetryCollector.ingest_fleet`` — the live-feed seam the fleet
    doctor and ROADMAP items 3/4 consume)."""

    def __init__(self):
        self.straggler_flags = []
        self.ici_health = {}
        self.ingests = 0

    def snapshot(self):
        return {"straggler_flags": list(self.straggler_flags),
                "ici_health": dict(self.ici_health),
                "ingests": self.ingests}


class MetricsSink:
    """Telemetry sink (sinks.TelemetrySinks protocol): folds each
    StepRecord into the registry. Per-step cost is a handful of dict
    updates under one lock — measured against the same <5% budget as
    the rest of telemetry (the dryrun fleet leg runs the paired
    min-of-2 on/off comparison)."""

    def __init__(self, registry, watchdog=None, fleet=None,
                 nominal_bytes_per_s=None, host=None):
        self.registry = registry
        self.watchdog = watchdog
        self.fleet = fleet
        self.nominal_bytes_per_s = nominal_bytes_per_s
        # FleetLocalState.ici_health keys are ALWAYS '<host>:<class>'
        # (ingest_fleet writes the merged view's hosts that way; local
        # measurements use this collector's own hostname)
        self.host = host or "local"
        r = registry
        # ---- train families
        self._train_steps = r.counter(
            "train_steps_total", "optimizer steps emitted")
        self._step_time = r.histogram(
            "step_time_seconds", "optimizer step wall (s)")
        self._mfu = r.gauge("mfu", "model flops utilization, last step")
        self._tokens_rate = r.gauge(
            "tokens_per_sec_per_chip", "token throughput per chip")
        self._loss = r.gauge("loss", "training loss, last step")
        self._grad_norm = r.gauge("grad_norm", "gradient norm, last step")
        self._loss_scale = r.gauge("loss_scale", "dynamic loss scale")
        self._overflow = r.counter(
            "overflow_steps_total", "steps skipped on overflow")
        self._skipped = r.gauge(
            "skipped_steps", "cumulative overflow-skipped steps")
        self._hbm_live = r.gauge(
            "hbm_bytes_in_use", "per-process HBM live bytes")
        self._hbm_peak = r.gauge(
            "hbm_peak_bytes_in_use", "per-process HBM peak bytes")
        self._phase = r.counter(
            "phase_seconds_total", "cumulative per-phase wall (s)")
        self._wire = r.gauge(
            "wire_bytes_per_step", "bytes-on-wire per step per class")
        self._exposed = r.counter(
            "comm_exposed_seconds_total",
            "cumulative exposed (unhidden) collective wall per class")
        self._seg_run = r.counter(
            "segment_run_seconds_total",
            "cumulative executed-segment run wall per kind")
        self._seg_wait = r.counter(
            "segment_wait_seconds_total",
            "cumulative executed-segment exposed wait per kind")
        self._seg_eff = r.gauge(
            "segment_overlap_efficiency",
            "constructed transfer/compute overlap, last step")
        self._ici = r.gauge(
            "ici_health",
            "achieved/nominal ICI bandwidth per collective class")
        # ---- serving families
        self._serving_steps = r.counter(
            "serving_steps_total", "scheduler steps emitted")
        self._prefill_tokens = r.counter(
            "prefill_tokens_total", "prefill tokens (engine lifetime)")
        self._decode_tokens = r.counter(
            "decode_tokens_total", "decode tokens (engine lifetime)")
        self._slot_occ = r.gauge("slot_occupancy", "decode slot occupancy")
        self._queue = r.gauge("queue_depth", "admission queue depth")
        self._ttft_p50 = r.gauge("ttft_p50_seconds", "rolling TTFT p50")
        self._ttft_p95 = r.gauge("ttft_p95_seconds", "rolling TTFT p95")
        self._tpot_p95 = r.gauge("tpot_p95_seconds", "rolling TPOT p95")
        self._slo_burn = r.gauge(
            "ttft_slo_burn_rate",
            "TTFT SLO violations / samples (watchdog window)")
        self._pool_occ = r.gauge(
            "page_pool_occupancy", "KV page pool occupancy")
        self._prefix_rate = r.gauge(
            "prefix_hit_rate", "prefix-cache hit rate")
        self._spec_rate = r.gauge(
            "spec_acceptance_rate", "speculative acceptance rate")
        # ---- doctor families
        self._trips = r.counter(
            "watchdog_trips_total", "watchdog trips per alarm")

    # ------------------------------------------------------ sink protocol
    def emit(self, rec):
        kind = rec.get("kind")
        if kind == KIND_TRAIN:
            self._emit_train(rec)
        elif kind == KIND_SERVING:
            self._emit_serving(rec)
        self._emit_watchdog()

    def _emit_train(self, rec):
        self._train_steps.inc()
        self._step_time.observe(rec["step_time_s"])
        self._mfu.set(rec["mfu"])
        self._tokens_rate.set(rec["tokens_per_sec_per_chip"])
        if rec.get("loss") is not None:
            self._loss.set(rec["loss"])
        if rec.get("grad_norm") is not None:
            self._grad_norm.set(rec["grad_norm"])
        self._loss_scale.set(rec["loss_scale"])
        if rec.get("overflow"):
            self._overflow.inc()
        self._skipped.set(rec.get("skipped_steps", 0))
        hbm = rec.get("hbm") or {}
        if hbm.get("available"):
            self._hbm_live.set(hbm["bytes_in_use"])
            self._hbm_peak.set(hbm["peak_bytes_in_use"])
        for phase, dur in (rec.get("phases") or {}).items():
            self._phase.inc(dur, phase=phase)
        wire = rec.get("wire") or {}
        for cls, key in (("allgather", "allgather_bytes_per_step"),
                         ("reduce", "reduce_bytes_per_step"),
                         ("optimizer", "optimizer_bytes_per_step"),
                         ("total", "total_bytes_per_step")):
            val = wire.get(key)
            if val is not None:
                self._wire.set(val, **{"class": cls})
        for cls, ent in (rec.get("comm_overlap") or {}).items():
            self._exposed.inc(ent.get("exposed_s", 0.0), **{"class": cls})
        offload = rec.get("offload")
        if offload:
            for seg_kind, slot in (offload.get("per_kind") or {}).items():
                self._seg_run.inc(slot.get("run_s", 0.0), kind=seg_kind)
                self._seg_wait.inc(slot.get("wait_s", 0.0), kind=seg_kind)
            if offload.get("overlap_efficiency") is not None:
                self._seg_eff.set(offload["overlap_efficiency"])
        # per-class achieved/nominal ICI bandwidth from the record's
        # measured waits (straggler.py owns the math; None = not
        # measurable on this path, honestly unset)
        health = ici_health_from_record(
            rec, nominal_bytes_per_s=self.nominal_bytes_per_s)
        for cls, val in health.items():
            if val is not None:
                self._ici.set(val, **{"class": cls})
        if self.fleet is not None and health:
            self.fleet.ici_health.update(
                {"{}:{}".format(self.host, cls): val
                 for cls, val in health.items() if val is not None})

    def _emit_serving(self, rec):
        self._serving_steps.inc()
        self._prefill_tokens.set_to(rec["prefill_tokens"])
        self._decode_tokens.set_to(rec["decode_tokens"])
        self._slot_occ.set(rec["slot_occupancy"])
        self._queue.set(rec["queue_depth"])
        ttft = rec.get("ttft")
        if ttft:
            self._ttft_p50.set(ttft["p50_s"])
            self._ttft_p95.set(ttft["p95_s"])
        tpot = rec.get("tpot")
        if tpot:
            self._tpot_p95.set(tpot["p95_s"])
        if rec.get("page_pool"):
            self._pool_occ.set(rec["page_pool"]["occupancy"])
        if rec.get("prefix"):
            self._prefix_rate.set(rec["prefix"]["hit_rate"])
        if rec.get("speculative"):
            self._spec_rate.set(rec["speculative"]["acceptance_rate"])
        if self.watchdog is not None:
            burn = self.watchdog.ttft_burn_rate()
            if burn is not None:
                self._slo_burn.set(burn)

    def _emit_watchdog(self):
        if self.watchdog is None:
            return
        counts = {}
        # trips_snapshot, not .trips: the deadline thread appends trips
        # concurrently with this emit-time iteration
        for trip in self.watchdog.trips_snapshot():
            counts[trip["watchdog"]] = counts.get(trip["watchdog"], 0) + 1
        for name, count in counts.items():
            self._trips.set_to(count, watchdog=name)

    def close(self):
        pass
