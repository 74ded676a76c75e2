"""TelemetryCollector: assembles one StepRecord per optimizer step and
fans it through the sink layer.

Owned by the training engine (``engine.telemetry``), the pipeline
engine, and the inference engine; ``None`` when the ``telemetry``
config section is absent/disabled, so the hot paths pay literally one
``is not None`` check — zero overhead off. Enabled, the per-step cost
is a handful of ``time.time()`` reads, one ``memory_stats()`` poll, one
JSON line, and (once per compiled program) an XLA ``cost_analysis``
lowering — documented with measured numbers in docs/telemetry.md and
tests/perf/bench_telemetry_overhead.py."""
import os
import time

from ..utils.lifecycle import AtexitCloseMixin
from ..utils.logging import logger
from . import record as rec_mod
from .mfu import mfu_of, peak_flops_for
from .programs import ProgramRegistry
from .recorder import FlightRecorder
from .sinks import (ChromeTraceSink, JsonlSink, TelemetrySinks,
                    TensorBoardSink, WindowAggregator)
from .spans import SpanTracer
from .trace import TraceWindow
from .watchdog import Watchdog

JSONL_NAME = "telemetry.jsonl"
SPANS_JSONL_NAME = "spans.jsonl"
CHROME_TRACE_NAME = "trace_events.json"

# output dirs claimed by LIVE collectors in this process: an explicit
# telemetry.job_name would otherwise point a train and a serving engine
# sharing one ds_config at the SAME telemetry.jsonl, breaking the
# "keeps multi-engine files apart" contract (released by close())
_claimed_dirs = set()


def _walk_pallas_costs(jaxpr, acc):
    """Recurse through a (Closed)Jaxpr accumulating the declared
    ``pl.CostEstimate`` of every ``pallas_call`` eqn into ``acc``.
    The pallas_call eqns are nested inside custom_vjp/pjit sub-jaxprs,
    so a flat scan over the top-level eqns finds nothing."""
    inner = getattr(jaxpr, "jaxpr", jaxpr)
    for eqn in getattr(inner, "eqns", ()):
        if eqn.primitive.name == "pallas_call":
            ce = eqn.params.get("cost_estimate")
            if ce is not None:
                acc["flops"] += float(getattr(ce, "flops", 0) or 0)
                acc["transcendentals"] += float(
                    getattr(ce, "transcendentals", 0) or 0)
                acc["bytes accessed"] += float(
                    getattr(ce, "bytes_accessed", 0) or 0)
        for v in eqn.params.values():
            for item in (v if isinstance(v, (list, tuple)) else (v,)):
                if hasattr(item, "eqns") or hasattr(item, "jaxpr"):
                    _walk_pallas_costs(item, acc)


def pallas_declared_costs(fn, *args):
    """Sum of the ``pl.CostEstimate`` declarations carried by every
    ``pallas_call`` in ``fn``'s jaxpr for ``args``. This is the pricing
    of record when XLA ``cost_analysis`` cannot see through the custom
    call (interpret mode inlines real HLO, and TPU cost_analysis
    already includes the estimate — both of those yield nonzero flops,
    so this fallback only fires when the opaque call would otherwise
    price the step at zero and corrupt MFU). Returns ``{}`` when the
    program declares nothing (or cannot be traced)."""
    try:
        import jax
        closed = jax.make_jaxpr(lambda *a: fn(*a))(*args)
    except Exception:  # noqa: BLE001 — pricing must never break a step
        return {}
    acc = {"flops": 0.0, "transcendentals": 0.0, "bytes accessed": 0.0}
    _walk_pallas_costs(closed, acc)
    if not acc["flops"] and not acc["bytes accessed"]:
        return {}
    return acc


def costs_of_compiled(fn, *args):
    """Full XLA ``cost_analysis`` dict of a jitted callable for ``args``
    (exact for the program about to run). Some jax builds only expose
    costs on the compiled object — the one home for that fallback (the
    flops profiler and the telemetry collector both read it). When the
    analysis prices the program at zero flops (opaque custom calls the
    backend refuses to cost), the ``pl.CostEstimate`` declarations of
    any pallas_call eqns are summed instead so MFU accounting sees
    through the kernels. Returns ``{}`` when the backend exposes no
    costs and the program declares none."""
    lowered = fn.lower(*args)
    costs = lowered.cost_analysis()
    if isinstance(costs, list):
        costs = costs[0] if costs else {}
    if not costs:
        # LOUD: this AOT compile is NOT shared with the jit dispatch
        # cache, so on builds that only expose costs on the compiled
        # object each program is compiled twice when telemetry is on —
        # a real startup cost on big models that the <5% step-time
        # budget does not cover (it only prices the steady state)
        logger.info(
            "telemetry: lowered cost_analysis empty; compiling the "
            "program a second time (AOT) to price its flops — expect "
            "extra one-time compile latency per program")
        costs = lowered.compile().cost_analysis()
        if isinstance(costs, list):
            costs = costs[0] if costs else {}
        if costs:
            # the compiled executable is ONE SPMD partition, so its
            # extensive costs (flops, transcendentals, bytes accessed)
            # are per device, while lower()'s module has global shapes —
            # normalize ALL of them to the global scale every consumer
            # expects (mfu_of divides by n_devices; the flops profiler
            # reads flops AND "bytes accessed", which must share a
            # scale or its arithmetic intensity is off by n)
            try:
                import jax
                n = jax.device_count()
            except Exception:  # noqa: BLE001
                n = 1
            if n > 1:
                costs = {k: (float(v) * n
                             if k in ("flops", "transcendentals")
                             or k.startswith("bytes accessed") else v)
                         for k, v in costs.items()}
    if not float((costs or {}).get("flops", 0.0) or 0.0):
        declared = pallas_declared_costs(fn, *args)
        if declared:
            logger.info(
                "telemetry: cost_analysis priced the program at zero "
                "flops; using the pl.CostEstimate declarations of its "
                "pallas_call kernels instead (%.3e flops)",
                declared["flops"])
            merged = dict(costs or {})
            merged.update(declared)
            costs = merged
    return costs or {}


def flops_of_compiled(fn, *args):
    """Executed-program flops of a jitted callable for ``args``; 0.0
    when the backend exposes no costs."""
    return float(costs_of_compiled(fn, *args).get("flops", 0.0) or 0.0)


def collect_memory_stats():
    """Per-process HBM live/peak from ``memory_stats()``: max over the
    local devices (the governing chip). ``available=False`` when the
    backend exposes none (e.g. XLA:CPU)."""
    out = {"available": False, "bytes_in_use": None,
           "peak_bytes_in_use": None}
    try:
        import jax
        live = peak = None
        for dev in jax.local_devices():
            stats = dev.memory_stats() or None
            if not stats:
                continue
            b = int(stats.get("bytes_in_use", 0))
            p = int(stats.get("peak_bytes_in_use", b))
            live = b if live is None else max(live, b)
            peak = p if peak is None else max(peak, p)
        if live is not None:
            out = {"available": True, "bytes_in_use": live,
                   "peak_bytes_in_use": peak}
    except Exception:  # noqa: BLE001 - never perturb the step
        pass
    return out


class TelemetryCollector(AtexitCloseMixin):

    def __init__(self, tconfig, job_name="train", monitor=None):
        self.config = tconfig
        def claim_key(n):
            # normalized so two spellings of one directory ("runs/t",
            # "./runs/t/", an absolute path) cannot slip past the guard
            # and interleave two engines' records in one JSONL
            return os.path.abspath(os.path.join(tconfig.output_path, n))

        base = tconfig.job_name or job_name
        name = base
        if claim_key(name) in _claimed_dirs:
            # second engine colliding under one name: suffix the engine
            # role first (explicit shared job_name), then number — every
            # live collector keeps its own JSONL
            if tconfig.job_name and job_name != base:
                base = "{}-{}".format(tconfig.job_name, job_name)
            name, n = base, 2
            while claim_key(name) in _claimed_dirs:
                name = "{}-{}".format(base, n)
                n += 1
            logger.info(
                "telemetry: job_name %r already claimed by a live "
                "collector in this process — writing as %r to keep the "
                "JSONLs apart", tconfig.job_name or job_name, name)
        self.job_name = name
        self.output_dir = os.path.join(tconfig.output_path, self.job_name)
        self._claim_key = claim_key(name)
        _claimed_dirs.add(self._claim_key)
        self.jsonl_path = os.path.join(self.output_dir, JSONL_NAME)
        self.aggregator = WindowAggregator(tconfig.window)
        sinks = [JsonlSink(self.jsonl_path,
                           max_bytes=tconfig.jsonl_max_bytes),
                 self.aggregator]
        tb = TensorBoardSink(monitor)
        if tb.live:
            sinks.append(tb)

        # ------------------------------------------- diagnostics subsystems
        # (docs/diagnostics.md). The programs registry is alive whenever
        # telemetry is — one dict update per jitted program; spans /
        # flight recorder / watchdog exist only when their config
        # section does, so the engines' hot paths keep one is-not-None
        # check each when they are off.
        self.programs = ProgramRegistry(
            storm_threshold=tconfig.programs_storm_threshold,
            replicated_leaf_bytes=tconfig.programs_replicated_leaf_bytes)
        self.spans = None
        if tconfig.spans_enabled:
            span_sinks = [JsonlSink(
                os.path.join(self.output_dir, SPANS_JSONL_NAME),
                max_bytes=tconfig.jsonl_max_bytes)]
            if tconfig.spans_chrome_trace:
                span_sinks.append(ChromeTraceSink(
                    os.path.join(self.output_dir, CHROME_TRACE_NAME),
                    max_bytes=tconfig.jsonl_max_bytes))
            self.spans = SpanTracer(span_sinks,
                                    max_events=tconfig.spans_max_events,
                                    job_name=self.job_name)
        self.recorder = None
        if tconfig.recorder_enabled:
            self.recorder = FlightRecorder(
                tconfig.recorder_output_path or
                os.path.join(self.output_dir, "crash"),
                job_name=self.job_name,
                capacity=tconfig.recorder_capacity,
                max_bundles=tconfig.recorder_max_bundles,
                programs=self.programs,
                spans=self.spans,
                on_sigterm=tconfig.recorder_on_sigterm)
            sinks.append(self.recorder)     # rings every StepRecord
        self.watchdog = None
        if tconfig.watchdog is not None:
            self.watchdog = Watchdog(tconfig.watchdog,
                                     recorder=self.recorder,
                                     job_name=self.job_name)
            if self.recorder is not None:
                self.recorder.watchdog_state = self.watchdog.snapshot

        # ------------------------------------------------ fleet observatory
        # (docs/fleet.md): metrics plane + /metrics + /healthz export —
        # OFF = structurally absent (no registry, no sink, no HTTP
        # thread), like the other PR 8 subsystems. The MetricsSink rides
        # the existing record stream: zero new hot-path instrumentation.
        self.fleet = None
        self.elastic_observer = None
        self.metrics = None
        self.exporter = None
        # healthz() reads _wall_start and the exporter thread serves it
        # the moment it starts — every state it touches must exist first
        self._wall_start = time.time()
        if tconfig.metrics_enabled:
            import socket
            from .fleet import (FleetLocalState, MetricsExporter,
                                MetricsRegistry, MetricsSink)
            self.fleet = FleetLocalState()
            registry = MetricsRegistry(
                namespace=tconfig.metrics_namespace,
                const_labels={"job": self.job_name,
                              "host": socket.gethostname()})
            self.metrics = MetricsSink(registry, watchdog=self.watchdog,
                                       fleet=self.fleet,
                                       host=socket.gethostname())
            sinks.append(self.metrics)
            try:
                self.exporter = MetricsExporter(registry,
                                                port=tconfig.metrics_port,
                                                healthz=self.healthz)
            except OSError as err:
                # a bound port (two engines/processes sharing the
                # documented fixed port) must not kill engine
                # construction: the sink keeps folding records (the
                # bench metrics_scrape() path stays live), only the
                # HTTP plane is absent — and loudly so
                logger.warning(
                    "telemetry.metrics: could not bind the export "
                    "port %s (%s) — /metrics + /healthz disabled for "
                    "this collector; records still feed the registry "
                    "(use port 0 for an ephemeral port)",
                    tconfig.metrics_port, err)

        self.sinks = TelemetrySinks(sinks)
        self.trace = None
        if tconfig.trace_enabled:
            self.trace = TraceWindow(
                tconfig.trace_output_path or
                os.path.join(self.output_dir, "trace"),
                start_step=tconfig.trace_start_step,
                num_steps=tconfig.trace_num_steps,
                trigger_file=tconfig.trace_trigger_file)
        import jax
        self._device = jax.devices()[0].device_kind
        self._n_devices = jax.device_count()
        self.peak_flops_per_chip = peak_flops_for(self._device)
        # per-host manifest: the structural discovery seam the fleet
        # merger joins on (fleet/aggregate.py) — written for EVERY live
        # collector, metrics on or off, so any telemetry run is
        # mergeable post-mortem
        try:
            import jax
            process_index = jax.process_index()
            process_count = jax.process_count()
        except Exception:  # noqa: BLE001
            process_index = process_count = None
        from .fleet.aggregate import write_host_manifest
        # kept so publish_fingerprint() can RE-write the identical
        # manifest extended with the program fingerprint (ISSUE 15)
        self._manifest_meta = {
            "metrics_port": self.exporter.port
            if self.exporter is not None else None,
            "process_index": process_index,
            "process_count": process_count,
            "wall_start": self._wall_start,
        }
        write_host_manifest(self.output_dir, job_name=self.job_name,
                            **self._manifest_meta)
        # concurrency sanitizer (docs/concurrency.md): the fleet
        # modules are stdlib-only and cannot import the sanitizer
        # themselves — their locks are wrapped from here, post-
        # construction (no-op when the sanitizer is off)
        from ..analysis.concurrency import locksan
        locksan.instrument_collector(self)
        # same lifecycle contract as SummaryMonitor (utils/lifecycle.py):
        # the exit handler closes an active trace window and the JSONL
        # handle at process end, deregistered by close()
        self._register_atexit_close()
        logger.info("telemetry: records -> %s (window=%d%s)",
                    self.jsonl_path, tconfig.window,
                    ", xprof trace armed" if self.trace else "")

    @classmethod
    def from_config(cls, config, job_name="train", monitor=None,
                    enabled=True):
        """``None`` unless the config's telemetry section is enabled and
        this process is the writer — the zero-overhead-off contract."""
        return cls.from_section(getattr(config, "telemetry_config", None),
                                job_name=job_name, monitor=monitor,
                                enabled=enabled)

    @classmethod
    def from_section(cls, tconfig, job_name="train", monitor=None,
                     enabled=True):
        """The ONE home for the enable/writer gate (training and serving
        both route through it): ``None`` unless the section exists, is
        enabled, and ``enabled`` (the caller's writer-process check)
        holds."""
        if tconfig is None or not tconfig.enabled or not enabled:
            return None
        return cls(tconfig, job_name=job_name, monitor=monitor)

    # ------------------------------------------------------------- hooks
    def on_step_begin(self, step):
        if self.trace is not None:
            self.trace.on_step_begin(step)
        if self.watchdog is not None:
            self.watchdog.step_begin(step)

    def emit_train_step(self, *, step, step_time_s, loss, grad_norm,
                        loss_scale, overflow, skipped_steps, micro_steps,
                        tokens_per_step, model_flops_per_step, phases,
                        wire=None, comm_overlap=None, offload=None,
                        pipe=None, hbm=None, path=None, segments=None):
        n = max(self._n_devices, 1)
        dt = max(float(step_time_s), 1e-12)
        rec = rec_mod.make_train_record(
            step=step, step_time_s=step_time_s, loss=loss,
            grad_norm=grad_norm, loss_scale=loss_scale, overflow=overflow,
            skipped_steps=skipped_steps, micro_steps=micro_steps,
            tokens_per_step=tokens_per_step,
            tokens_per_sec_per_chip=float(tokens_per_step) / dt / n,
            model_flops_per_step=model_flops_per_step,
            mfu=mfu_of(model_flops_per_step, dt, n,
                       self.peak_flops_per_chip),
            peak_flops_per_chip=self.peak_flops_per_chip,
            device=self._device, n_devices=n,
            phases=phases,
            hbm=hbm if hbm is not None else collect_memory_stats(),
            wire=wire, comm_overlap=comm_overlap, offload=offload,
            pipe=pipe)
        self.sinks.emit(rec)
        if self.spans is not None:
            # span tree for this step, derived from the SAME window/phase
            # clocks the record carries (spans.py module docstring)
            attrs = {"loss": rec["loss"], "mfu": rec["mfu"]}
            if path:
                attrs["path"] = str(path)
            self.spans.emit_step_tree(
                "train_step", step=step, t0=rec["wall"] - dt,
                t1=rec["wall"], phases=rec["phases"], attrs=attrs,
                segments=segments)
        if self.watchdog is not None:
            self.watchdog.step_end()
            self.watchdog.observe_train(rec)
        if self.trace is not None:
            self.trace.on_step_end(step)
        return rec

    def emit_serving_step(self, *, step, metrics, active_slots,
                          queue_depth, occupancy, page_pool=None,
                          prefix=None, role=None):
        rec = rec_mod.make_serving_record(
            step=step, slot_occupancy=occupancy, queue_depth=queue_depth,
            active_slots=active_slots,
            prefill_tokens=metrics.prefill_tokens,
            prefill_tokens_per_sec=metrics.prefill_tokens_per_sec,
            decode_tokens=metrics.decode_tokens,
            decode_steps=metrics.decode_steps,
            decode_tokens_per_sec=metrics.decode_tokens_per_sec,
            ttft=metrics.ttft_dist(),
            tpot=metrics.tpot_dist(),
            page_pool=page_pool,
            prefix=prefix,
            speculative=metrics.spec_dist(),
            role=role)
        self.sinks.emit(rec)
        if self.watchdog is not None:
            self.watchdog.step_end()
            self.watchdog.observe_serving(rec)
        if self.trace is not None:
            # on_step_begin ran at the top of the scheduler step (the
            # window must wrap the decode work, not follow it)
            self.trace.on_step_end(step)
        return rec

    def snapshot(self):
        """Rolling-window aggregate (see sinks.WindowAggregator) — the
        payload of ``engine.telemetry_snapshot()`` and of the benches'
        ``extra.telemetry``."""
        out = self.aggregator.snapshot()
        if self.trace is not None:
            out["trace_windows_completed"] = self.trace.windows_completed
        if self.spans is not None:
            out["span_trees"] = self.spans.trees_exported
        if self.watchdog is not None and self.watchdog.trips:
            out["watchdog_trips"] = len(self.watchdog.trips)
        if self.programs.flags:
            out["program_flags"] = [f["key"] for f in self.programs.flags]
        if self.fleet is not None or self.exporter is not None:
            # the fleet observatory's one snapshot seam (docs/fleet.md):
            # straggler flags + last ici_health + export liveness ride
            # the EXISTING telemetry_snapshot() instead of a second API
            out["fleet"] = self.fleet_snapshot()
        return out

    # ---------------------------------------------------------------- fleet
    def fleet_snapshot(self):
        """``telemetry_snapshot()["fleet"]``: straggler flags and
        ici_health last values (FleetLocalState) + metrics-export
        liveness."""
        out = self.fleet.snapshot() if self.fleet is not None else \
            {"straggler_flags": [], "ici_health": {}, "ingests": 0}
        out["metrics_export"] = self.exporter.snapshot() \
            if self.exporter is not None else None
        return out

    def publish_fingerprint(self, fingerprint):
        """Extend this host's manifest with the canonical program
        fingerprint (analysis/concurrency/divergence.py derives it;
        ``engine.audit()`` calls this) — the seam the fleet doctor's
        divergence check joins on."""
        from .fleet.aggregate import write_host_manifest
        return write_host_manifest(
            self.output_dir, job_name=self.job_name,
            fingerprint=fingerprint, **self._manifest_meta)

    def ingest_fleet(self, report):
        """Feed a merged fleet view (fleet/aggregate.merge_run) into
        this process: stores the straggler flags / ici_health for the
        snapshot + /healthz, and trips the ``straggler`` watchdog (the
        PR 8 machinery) on each newly flagged host. The live seam
        ``bin/ds_fleet.py`` and the ROADMAP item 3/4 controllers use."""
        if self.fleet is None:
            from .fleet import FleetLocalState
            self.fleet = FleetLocalState()
        if not isinstance(report, dict):
            report = {"straggler": {"flags": list(report)}}
        straggler = report.get("straggler") or {}
        self.fleet.straggler_flags = list(straggler.get("flags", []))
        for host, classes in (report.get("ici_health") or {}).items():
            for cls, val in classes.items():
                self.fleet.ici_health["{}:{}".format(host, cls)] = val
        self.fleet.ingests += 1
        divergence = report.get("divergence") or {}
        if divergence.get("mismatch"):
            logger.warning(
                "fleet divergence ingested: host(s) %s lowered a "
                "different program than %s — audit them before the "
                "next step (docs/concurrency.md)",
                ", ".join(divergence.get("divergent_hosts", [])),
                divergence.get("reference"))
        if self.watchdog is not None:
            self.watchdog.observe_fleet(report)
        if self.elastic_observer is not None:
            # the ElasticRunner's eviction policy rides the same live
            # seam: k consecutive ingests flagging one host turn into a
            # proactive rescale (runtime/elastic/, docs/elasticity.md)
            try:
                self.elastic_observer(report)
            except Exception:  # noqa: BLE001 - an eviction decision
                # must never poison the telemetry ingest path
                logger.warning("elastic observer failed on fleet ingest",
                               exc_info=True)

    def set_elastic_observer(self, fn):
        """Register a callable fed every ingested fleet report (the
        ElasticRunner's ``observe_fleet``); pass None to detach."""
        self.elastic_observer = fn

    def healthz(self):
        """The ``/healthz`` JSON payload: watchdog trips, rolling-window
        MFU, TTFT-SLO burn rate, overflow/skip counters, and the fleet
        flags. ``status`` degrades on any watchdog trip or ingested
        straggler flag (the exporter answers 503 then)."""
        agg = self.aggregator.snapshot()
        # trips_snapshot: healthz runs on the exporter's handler
        # threads while the deadline/main threads append trips
        trips = self.watchdog.trips_snapshot() \
            if self.watchdog is not None else []
        fleet = self.fleet_snapshot()
        degraded = bool(trips) or bool(fleet["straggler_flags"])
        out = {
            "status": "degraded" if degraded else "ok",
            "job_name": self.job_name,
            "wall": time.time(),
            "uptime_s": round(time.time() - self._wall_start, 3),
            "steps": agg.get("steps", 0),
            "serving_steps": agg.get("serving_steps", 0),
            "mfu": agg.get("mfu"),
            "overflow_last": agg.get("overflow_last"),
            "skipped_steps": agg.get("skipped_steps", 0),
            "watchdog": {"trips": len(trips),
                         "last": trips[-1] if trips else None},
            "ttft_slo_burn_rate": self.watchdog.ttft_burn_rate()
            if self.watchdog is not None else None,
            "fleet": fleet,
        }
        return out

    def metrics_scrape(self):
        """The live registry rendered as exposition text (what a
        ``/metrics`` GET serves) plus series count — benches embed this
        under ``extra.metrics``. ``None`` when the metrics plane is
        off."""
        if self.metrics is None:
            return None
        return {"series": self.metrics.registry.series_count,
                "port": self.exporter.port
                if self.exporter is not None else None,
                "scrape": self.metrics.registry.render_text()}

    def close(self):
        """Idempotent: the first call stops any active trace window and
        the watchdog thread, detaches the flight recorder's log/signal
        hooks, closes the sinks, and drops the atexit registration."""
        if self._finish_close():
            return
        if self.trace is not None:
            self.trace.close()
        if self.watchdog is not None:
            self.watchdog.close()
        if self.recorder is not None:
            self.recorder.close()
        if self.spans is not None:
            self.spans.close()
        if self.exporter is not None:
            self.exporter.close()
        self.sinks.close()
        _claimed_dirs.discard(self._claim_key)
