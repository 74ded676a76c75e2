"""``telemetry`` ds_config section.

Validated with the same no-silent-no-ops policy as PR 4's stage-3 keys:
every key either drives a mechanism or is loudly rejected; unknown keys
inside the section (including the nested ``trace`` block) warn, and
raise when ``telemetry.strict`` is set. ``telemetry.strict`` also
hardens related observability keys elsewhere in the config — e.g.
``memory_breakdown`` raises instead of warning when the backend exposes
no ``memory_stats()``.

Shape::

    "telemetry": {
      "enabled": true,
      "output_path": "runs/telemetry",   // JSONL + trace root
      "job_name": "train",               // subdir; keeps multi-engine files apart
      "window": 50,                      // rolling-aggregate window (p50/p95)
      "strict": false,                   // unknown/unhonorable keys raise
      "jsonl_max_bytes": null,           // rotate telemetry/span JSONLs at this size
      "trace": {                         // on-demand xprof windows
        "start_step": 10,                // null = only the trigger file arms it
        "num_steps": 2,
        "trigger_file": null,            // touch this path -> trace next window
        "output_path": null              // default <output_path>/<job>/trace
      },
      "spans": {                         // span tracer (docs/diagnostics.md)
        "enabled": true,
        "chrome_trace": true,            // also write Perfetto-loadable trace_events.json
        "max_events_per_span": 256
      },
      "flight_recorder": {               // crash bundles
        "enabled": true,
        "capacity": 256,                 // record/span/log ring size
        "max_bundles": 8,                // retained bundle files
        "output_path": null,             // default <output_path>/<job>/crash
        "on_sigterm": false              // dump a bundle on SIGTERM/preemption
      },
      "watchdog": {                      // hang/anomaly alarms; each sub-key a
                                         // dict (tune), true (defaults) or false (off)
        "step_deadline": {"factor": 5.0, "min_steps": 5, "floor_s": 1.0,
                          "poll_s": 0.05, "action": "warn"},
        "nan_streak":    {"threshold": 3, "action": "warn"},
        "loss_spike":    {"zscore": 8.0, "window": 50, "min_steps": 10,
                          "action": "warn"},
        "ttft_slo":      {"slo_s": null, "every": 1, "action": "warn"},
        "pool_exhaustion": {"every": 100, "action": "warn"}
      },
      "programs": {                      // compile-observatory thresholds
        "recompile_storm_threshold": 32,
        "replicated_leaf_bytes": 1073741824
      },
      "metrics": {                       // fleet export plane (docs/fleet.md)
        "enabled": true,
        "port": 9400,                    // 0 = ephemeral (tests read it back)
        "namespace": "ds"                // series-name prefix
      }
    }

The spans / flight_recorder / watchdog subsystems are OFF unless their
section is present (an absent section keeps today's one is-not-None
check on the hot paths); the programs registry is alive whenever
telemetry is enabled (one dict update per program) and its section only
tunes thresholds.
"""
from ..utils.logging import logger
from .programs import (RECOMPILE_STORM_THRESHOLD_DEFAULT,
                       REPLICATED_LEAF_BYTES_DEFAULT)
from .recorder import (RECORDER_CAPACITY_DEFAULT,
                       RECORDER_MAX_BUNDLES_DEFAULT)
from .spans import SPANS_MAX_EVENTS_DEFAULT
from .watchdog import (LOSS_SPIKE_DEFAULTS, NAN_STREAK_DEFAULTS,
                       POOL_EXHAUSTION_DEFAULTS, STEP_DEADLINE_DEFAULTS,
                       STRAGGLER_DEFAULTS, TTFT_SLO_DEFAULTS,
                       WATCHDOG_ACTIONS)


def warn_or_raise_noop(msg, strict, flag="telemetry.strict"):
    """The no-silent-no-ops policy, in one place: a config key this
    runtime cannot honor warns loudly, and raises when the section's
    strict flag is set. Shared by the telemetry section, the engine's
    memory_breakdown check, and the zero_optimization key validator."""
    if strict:
        raise ValueError(msg + " (raising because {}=true)".format(flag))
    logger.warning(msg)

TELEMETRY = "telemetry"

TELEMETRY_ENABLED = "enabled"
TELEMETRY_ENABLED_DEFAULT = False
TELEMETRY_OUTPUT_PATH = "output_path"
TELEMETRY_OUTPUT_PATH_DEFAULT = "runs/telemetry"
TELEMETRY_JOB_NAME = "job_name"
TELEMETRY_WINDOW = "window"
TELEMETRY_WINDOW_DEFAULT = 50
TELEMETRY_STRICT = "strict"
TELEMETRY_TRACE = "trace"
TELEMETRY_JSONL_MAX_BYTES = "jsonl_max_bytes"
TELEMETRY_SPANS = "spans"
TELEMETRY_FLIGHT_RECORDER = "flight_recorder"
TELEMETRY_WATCHDOG = "watchdog"
TELEMETRY_PROGRAMS = "programs"
TELEMETRY_METRICS = "metrics"

METRICS_NAMESPACE_DEFAULT = "ds"

TRACE_START_STEP = "start_step"
TRACE_NUM_STEPS = "num_steps"
TRACE_NUM_STEPS_DEFAULT = 1
TRACE_TRIGGER_FILE = "trigger_file"
TRACE_OUTPUT_PATH = "output_path"

KNOWN_TELEMETRY_KEYS = {
    TELEMETRY_ENABLED, TELEMETRY_OUTPUT_PATH, TELEMETRY_JOB_NAME,
    TELEMETRY_WINDOW, TELEMETRY_STRICT, TELEMETRY_TRACE,
    TELEMETRY_JSONL_MAX_BYTES, TELEMETRY_SPANS,
    TELEMETRY_FLIGHT_RECORDER, TELEMETRY_WATCHDOG, TELEMETRY_PROGRAMS,
    TELEMETRY_METRICS,
}
KNOWN_TRACE_KEYS = {
    TRACE_START_STEP, TRACE_NUM_STEPS, TRACE_TRIGGER_FILE,
    TRACE_OUTPUT_PATH,
}
KNOWN_SPANS_KEYS = {"enabled", "chrome_trace", "max_events_per_span"}
KNOWN_FLIGHT_RECORDER_KEYS = {"enabled", "capacity", "max_bundles",
                              "output_path", "on_sigterm"}
KNOWN_WATCHDOG_KEYS = {"enabled", "step_deadline", "nan_streak",
                       "loss_spike", "ttft_slo", "pool_exhaustion",
                       "straggler", "controller"}
KNOWN_PROGRAMS_KEYS = {"recompile_storm_threshold",
                       "replicated_leaf_bytes"}
KNOWN_METRICS_KEYS = {"enabled", "port", "namespace"}


class DeepSpeedTelemetryConfig(object):
    """Typed view of the ``telemetry`` section of a ds_config dict."""

    def __init__(self, param_dict):
        d = (param_dict or {}).get(TELEMETRY, {})
        if d is None:
            d = {}
        if not isinstance(d, dict):
            raise ValueError(
                "telemetry section must be a dict, got {}".format(
                    type(d).__name__))
        self.strict = bool(d.get(TELEMETRY_STRICT, False))
        self._reject_unknown(d, KNOWN_TELEMETRY_KEYS, TELEMETRY)

        self.enabled = bool(d.get(TELEMETRY_ENABLED,
                                  TELEMETRY_ENABLED_DEFAULT))
        self.output_path = d.get(TELEMETRY_OUTPUT_PATH) or None
        if self.enabled and not self.output_path:
            # like the monitor's ./runs default: never silently drop
            # records the user asked for
            self.output_path = TELEMETRY_OUTPUT_PATH_DEFAULT
            logger.info("telemetry enabled with no output_path; writing "
                        "to ./%s", self.output_path)
        self.job_name = d.get(TELEMETRY_JOB_NAME) or None

        window = d.get(TELEMETRY_WINDOW, TELEMETRY_WINDOW_DEFAULT)
        if isinstance(window, bool) or not isinstance(window, int) or \
                window < 1:
            raise ValueError(
                "telemetry.{} must be an int >= 1, got {!r}".format(
                    TELEMETRY_WINDOW, window))
        self.window = window

        trace = d.get(TELEMETRY_TRACE)
        self.trace_enabled = trace is not None
        self.trace_start_step = None
        self.trace_num_steps = TRACE_NUM_STEPS_DEFAULT
        self.trace_trigger_file = None
        self.trace_output_path = None
        if trace is not None:
            if not isinstance(trace, dict):
                raise ValueError(
                    "telemetry.trace must be a dict, got {}".format(
                        type(trace).__name__))
            self._reject_unknown(trace, KNOWN_TRACE_KEYS,
                                 "telemetry.trace")
            start = trace.get(TRACE_START_STEP)
            if start is not None and (isinstance(start, bool) or
                                      not isinstance(start, int) or
                                      start < 0):
                raise ValueError(
                    "telemetry.trace.{} must be an int >= 0 or null, got "
                    "{!r}".format(TRACE_START_STEP, start))
            self.trace_start_step = start
            num = trace.get(TRACE_NUM_STEPS, TRACE_NUM_STEPS_DEFAULT)
            if isinstance(num, bool) or not isinstance(num, int) or num < 1:
                raise ValueError(
                    "telemetry.trace.{} must be an int >= 1, got "
                    "{!r}".format(TRACE_NUM_STEPS, num))
            self.trace_num_steps = num
            self.trace_trigger_file = trace.get(TRACE_TRIGGER_FILE) or None
            self.trace_output_path = trace.get(TRACE_OUTPUT_PATH) or None
            if self.trace_start_step is None and \
                    self.trace_trigger_file is None:
                self._noop(
                    "trace",
                    "neither start_step nor trigger_file is set, so the "
                    "window can never arm")

        max_bytes = d.get(TELEMETRY_JSONL_MAX_BYTES)
        if max_bytes is not None and (isinstance(max_bytes, bool) or
                                      not isinstance(max_bytes, int) or
                                      max_bytes < 4096):
            raise ValueError(
                "telemetry.{} must be an int >= 4096 or null, got "
                "{!r}".format(TELEMETRY_JSONL_MAX_BYTES, max_bytes))
        self.jsonl_max_bytes = max_bytes

        self._parse_spans(d.get(TELEMETRY_SPANS))
        self._parse_flight_recorder(d.get(TELEMETRY_FLIGHT_RECORDER))
        self._parse_watchdog(d.get(TELEMETRY_WATCHDOG))
        self._parse_programs(d.get(TELEMETRY_PROGRAMS))
        self._parse_metrics(d.get(TELEMETRY_METRICS))

    # ----------------------------------------------- diagnostics sections
    def _section_dict(self, section, name):
        if not isinstance(section, dict):
            raise ValueError(
                "telemetry.{} must be a dict, got {}".format(
                    name, type(section).__name__))
        return section

    def _pos_int(self, section, name, key, default, minimum=1):
        val = section.get(key, default)
        if isinstance(val, bool) or not isinstance(val, int) or \
                val < minimum:
            raise ValueError(
                "telemetry.{}.{} must be an int >= {}, got {!r}".format(
                    name, key, minimum, val))
        return val

    def _parse_spans(self, section):
        self.spans_enabled = False
        self.spans_chrome_trace = True
        self.spans_max_events = SPANS_MAX_EVENTS_DEFAULT
        if section is None:
            return
        section = self._section_dict(section, TELEMETRY_SPANS)
        self._reject_unknown(section, KNOWN_SPANS_KEYS, "telemetry.spans")
        self.spans_enabled = bool(section.get("enabled", True))
        self.spans_chrome_trace = bool(section.get("chrome_trace", True))
        self.spans_max_events = self._pos_int(
            section, TELEMETRY_SPANS, "max_events_per_span",
            SPANS_MAX_EVENTS_DEFAULT)

    def _parse_flight_recorder(self, section):
        self.recorder_enabled = False
        self.recorder_capacity = RECORDER_CAPACITY_DEFAULT
        self.recorder_max_bundles = RECORDER_MAX_BUNDLES_DEFAULT
        self.recorder_output_path = None
        self.recorder_on_sigterm = False
        if section is None:
            return
        section = self._section_dict(section, TELEMETRY_FLIGHT_RECORDER)
        self._reject_unknown(section, KNOWN_FLIGHT_RECORDER_KEYS,
                             "telemetry.flight_recorder")
        self.recorder_enabled = bool(section.get("enabled", True))
        self.recorder_capacity = self._pos_int(
            section, TELEMETRY_FLIGHT_RECORDER, "capacity",
            RECORDER_CAPACITY_DEFAULT)
        self.recorder_max_bundles = self._pos_int(
            section, TELEMETRY_FLIGHT_RECORDER, "max_bundles",
            RECORDER_MAX_BUNDLES_DEFAULT)
        self.recorder_output_path = section.get("output_path") or None
        self.recorder_on_sigterm = bool(section.get("on_sigterm", False))

    def _parse_watchdog(self, section):
        """-> self.watchdog: None (section absent) or a dict of parsed
        sub-configs for watchdog.Watchdog (a sub-key maps to None when
        disabled with ``false``)."""
        self.watchdog = None
        if section is None:
            return
        section = self._section_dict(section, TELEMETRY_WATCHDOG)
        self._reject_unknown(section, KNOWN_WATCHDOG_KEYS,
                             "telemetry.watchdog")
        # imported here: runtime.config imports this module
        from ..runtime.config import refuse_removed_controller
        refuse_removed_controller(section.get("controller", False),
                                  "telemetry.watchdog.controller")
        if not section.get("enabled", True):
            return
        defaults = {
            "step_deadline": STEP_DEADLINE_DEFAULTS,
            "nan_streak": NAN_STREAK_DEFAULTS,
            "loss_spike": LOSS_SPIKE_DEFAULTS,
            "ttft_slo": TTFT_SLO_DEFAULTS,
            "pool_exhaustion": POOL_EXHAUSTION_DEFAULTS,
            "straggler": STRAGGLER_DEFAULTS,
        }
        parsed = {}
        for name, base in defaults.items():
            sub = section.get(name, True)
            if sub is False:
                parsed[name] = None
                continue
            if sub is True:
                sub = {}
            if not isinstance(sub, dict):
                raise ValueError(
                    "telemetry.watchdog.{} must be a dict or a bool, got "
                    "{!r}".format(name, sub))
            unknown = sorted(set(sub) - set(base))
            if unknown:
                self._noop(
                    "watchdog.{}.{}".format(name, ", ".join(unknown)),
                    "unknown key(s) (accepted: {})".format(sorted(base)))
            merged = dict(base)
            merged.update({k: v for k, v in sub.items() if k in base})
            if merged["action"] not in WATCHDOG_ACTIONS:
                raise ValueError(
                    "telemetry.watchdog.{}.action must be one of {}, got "
                    "{!r}".format(name, WATCHDOG_ACTIONS,
                                  merged["action"]))
            for key, val in merged.items():
                if key == "action" or (key == "slo_s" and val is None):
                    continue
                if isinstance(val, bool) or \
                        not isinstance(val, (int, float)) or val <= 0:
                    raise ValueError(
                        "telemetry.watchdog.{}.{} must be a positive "
                        "number, got {!r}".format(name, key, val))
            parsed[name] = merged
        ttft = parsed.get("ttft_slo")
        if ttft is not None and ttft["slo_s"] is None:
            # no universal TTFT SLO exists: without slo_s the alarm can
            # never trip — drop it (silently: it IS the default state)
            parsed["ttft_slo"] = None
        self.watchdog = parsed

    def _parse_programs(self, section):
        self.programs_storm_threshold = RECOMPILE_STORM_THRESHOLD_DEFAULT
        self.programs_replicated_leaf_bytes = REPLICATED_LEAF_BYTES_DEFAULT
        if section is None:
            return
        section = self._section_dict(section, TELEMETRY_PROGRAMS)
        self._reject_unknown(section, KNOWN_PROGRAMS_KEYS,
                             "telemetry.programs")
        self.programs_storm_threshold = self._pos_int(
            section, TELEMETRY_PROGRAMS, "recompile_storm_threshold",
            RECOMPILE_STORM_THRESHOLD_DEFAULT)
        self.programs_replicated_leaf_bytes = self._pos_int(
            section, TELEMETRY_PROGRAMS, "replicated_leaf_bytes",
            REPLICATED_LEAF_BYTES_DEFAULT)

    def _parse_metrics(self, section):
        """Fleet metrics export plane (telemetry/fleet/, docs/fleet.md).
        Absent/disabled = structurally off: no registry, no sink, no
        HTTP thread (the PR 8 subsystem contract)."""
        self.metrics_enabled = False
        self.metrics_port = 0
        self.metrics_namespace = METRICS_NAMESPACE_DEFAULT
        if section is None:
            return
        section = self._section_dict(section, TELEMETRY_METRICS)
        self._reject_unknown(section, KNOWN_METRICS_KEYS,
                             "telemetry.metrics")
        self.metrics_enabled = bool(section.get("enabled", True))
        port = section.get("port", 0)
        if isinstance(port, bool) or not isinstance(port, int) or \
                not 0 <= port <= 65535:
            raise ValueError(
                "telemetry.metrics.port must be an int in [0, 65535] "
                "(0 = ephemeral), got {!r}".format(port))
        self.metrics_port = port
        namespace = section.get("namespace", METRICS_NAMESPACE_DEFAULT)
        if not isinstance(namespace, str) or not namespace:
            raise ValueError(
                "telemetry.metrics.namespace must be a non-empty "
                "string, got {!r}".format(namespace))
        self.metrics_namespace = namespace

    def _reject_unknown(self, d, known, section):
        unknown = sorted(k for k in d if k not in known)
        if unknown:
            self._noop(
                ", ".join(unknown),
                "unknown key(s) in the {!r} section (accepted: {})".format(
                    section, sorted(known)))

    def _noop(self, key, why):
        """A telemetry key this runtime cannot honor: warn loudly, raise
        under telemetry.strict — never a silent no-op (the PR 4 stage-3
        key policy, docs/telemetry.md)."""
        warn_or_raise_noop(
            "telemetry.{} has NO effect: {}".format(key, why), self.strict)
