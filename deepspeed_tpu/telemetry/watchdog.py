"""Run doctor watchdogs: hang/anomaly alarms over the telemetry stream.

Six alarms, each with a configurable action (``telemetry.watchdog``):

* **step_deadline** — a background thread arms a deadline at every step
  begin (``max(factor x rolling-median step time, floor_s)``, armed only
  after ``min_steps`` completed steps so compiles never trip it) and
  fires if the step does not COMPLETE in time: the only way to observe a
  hung collective/transfer, which by definition never reaches the
  end-of-step code;
* **nan_streak** — ``threshold`` consecutive steps with a non-finite
  loss or an overflow skip;
* **loss_spike** — loss z-score over a rolling window exceeds
  ``zscore``;
* **ttft_slo** — a serving request's time-to-first-token exceeded
  ``slo_s`` (off unless configured: there is no universal SLO);
* **pool_exhaustion** — paged-KV admission blocked or a decoder was
  preempted for pages (the serving engine is out of KV memory);
* **straggler** — a merged fleet view (telemetry/fleet/) flagged this
  run's host set: a host ``factor``x over the fleet-median step or
  segment wall for ``k`` consecutive steps, or a collective class whose
  measured ICI bandwidth fell below ``1/factor`` of nominal. Fed via
  :meth:`Watchdog.observe_fleet` by ``TelemetryCollector.ingest_fleet``
  (the ``bin/ds_fleet.py`` live seam); the detection itself lives in
  fleet/straggler.py.

Actions: ``warn`` logs; ``dump`` logs + writes a flight-recorder crash
bundle; ``raise`` logs + dumps + raises :class:`WatchdogError` (from the
deadline thread, where raising is impossible, it interrupts the main
thread instead). Every trip is also kept in ``trips`` — bundled into
crash bundles via ``snapshot()``.
"""
import threading
import time
from collections import deque

from ..analysis.concurrency import locksan
from ..utils.logging import logger
# the straggler thresholds live with the detector (fleet/straggler.py);
# re-exported here so telemetry/config.py reads one defaults table per
# watchdog like the five local ones below
from .fleet.straggler import STRAGGLER_DEFAULTS, describe_flag_ratio

WATCHDOG_ACTIONS = ("warn", "dump", "raise")

STEP_DEADLINE_DEFAULTS = {"factor": 5.0, "min_steps": 5, "floor_s": 1.0,
                          "poll_s": 0.05, "action": "warn"}
NAN_STREAK_DEFAULTS = {"threshold": 3, "action": "warn"}
LOSS_SPIKE_DEFAULTS = {"zscore": 8.0, "window": 50, "min_steps": 10,
                       "action": "warn"}
TTFT_SLO_DEFAULTS = {"slo_s": None, "every": 1, "action": "warn"}
POOL_EXHAUSTION_DEFAULTS = {"every": 100, "action": "warn"}

_MAX_TRIPS = 64


class WatchdogError(RuntimeError):
    """Raised (action == "raise") when a watchdog trips."""


class Watchdog:
    """Owns the alarm state machines; fed by the telemetry collector
    (records, step begin/end) and the serving scheduler (TTFT samples,
    pool-pressure events)."""

    # concurrency-sanitizer declaration (docs/concurrency.md): trips is
    # appended by BOTH the main thread and the deadline thread, and
    # snapshotted by the exporter's handler threads (/healthz) — every
    # access holds the state lock (read via trips_snapshot()).
    # _durations is shared between step hooks and the deadline loop.
    _GUARDED_BY = {"trips": "_lock", "_durations": "_lock"}

    def __init__(self, cfg, recorder=None, job_name="train"):
        """``cfg``: dict of parsed sub-configs (telemetry/config.py) —
        keys step_deadline / nan_streak / loss_spike / ttft_slo /
        pool_exhaustion, each a dict or None (disabled)."""
        self.cfg = cfg or {}
        self.recorder = recorder
        self.job_name = job_name
        self._lock = locksan.new_lock("watchdog.state")
        self.trips = locksan.guarded(self, "trips", [])
        self._nan_streak = 0
        self._nan_tripped = False
        spike = self.cfg.get("loss_spike")
        self._losses = deque(maxlen=int(spike["window"])) if spike else None
        self._ttft_violations = 0
        self._ttft_samples = 0
        self._pool_events = 0
        self._fleet_tripped = set()     # (host, metric) already tripped
        # step-deadline thread state
        self._dl_cfg = self.cfg.get("step_deadline")
        self._durations = locksan.guarded(self, "_durations",
                                          deque(maxlen=64))
        self._step_t0 = None
        self._armed_deadline = None        # monotonic deadline, or None
        self._armed_step = None
        self._stop = threading.Event()
        self._thread = None

    # ------------------------------------------------------------ tripping
    def _trip(self, name, detail, action, from_thread=False):
        trip = {"watchdog": name, "detail": detail, "action": action,
                "wall": time.time()}
        # under the lock: the deadline thread and the main thread both
        # trip, and the exporter's handler threads snapshot trips for
        # /healthz — an unlocked append raced those iterations (the
        # concurrency sanitizer's guarded_race rule keeps this honest)
        with self._lock:
            if len(self.trips) < _MAX_TRIPS:
                self.trips.append(trip)
        logger.warning("watchdog %s TRIPPED (%s): %s", name, action,
                       detail)
        if action in ("dump", "raise"):
            if self.recorder is not None:
                try:
                    self.recorder.dump("watchdog:" + name)
                except Exception:  # noqa: BLE001 - a failed dump must
                    # never kill the deadline thread (it would silently
                    # stop watching the NEXT hang)
                    logger.warning("watchdog %s: crash-bundle dump "
                                   "failed", name, exc_info=True)
            else:
                logger.warning(
                    "watchdog %s action %r needs telemetry."
                    "flight_recorder, which is off — no bundle written",
                    name, action)
        if action == "raise":
            err = WatchdogError("watchdog {} tripped: {}".format(name,
                                                                 detail))
            # the bundle for this trip is already written; the step-path
            # crash hook must not write a duplicate
            err._ds_dumped = True
            if from_thread:
                # a thread cannot raise into the main thread; interrupt
                # it (KeyboardInterrupt at the next bytecode boundary).
                # That interrupt is a FRESH exception object the step-
                # path hooks would dump again — mark it covered first.
                import _thread
                if self.recorder is not None:
                    self.recorder.cover_interrupt()
                logger.warning(
                    "watchdog %s: interrupting the main thread (raise "
                    "action from the deadline thread)", name)
                _thread.interrupt_main()
            else:
                raise err

    # -------------------------------------------------------- step deadline
    def step_begin(self, step):
        if self._dl_cfg is None:
            return
        with self._lock:
            self._step_t0 = time.monotonic()
            self._armed_step = step
            if len(self._durations) >= int(self._dl_cfg["min_steps"]):
                durs = sorted(self._durations)
                median = durs[len(durs) // 2]
                deadline = max(float(self._dl_cfg["factor"]) * median,
                               float(self._dl_cfg["floor_s"]))
                self._armed_deadline = self._step_t0 + deadline
                self._ensure_thread()
            else:
                self._armed_deadline = None

    def step_end(self):
        if self._dl_cfg is None:
            return
        with self._lock:
            if self._step_t0 is not None:
                self._durations.append(time.monotonic() - self._step_t0)
            self._step_t0 = None
            self._armed_deadline = None

    def _ensure_thread(self):
        if self._thread is None or not self._thread.is_alive():
            self._thread = threading.Thread(
                target=self._deadline_loop,
                name="ds-watchdog-{}".format(self.job_name), daemon=True)
            self._thread.start()

    def _deadline_loop(self):
        poll = float(self._dl_cfg["poll_s"])
        while not self._stop.wait(poll):
            with self._lock:
                deadline = self._armed_deadline
                step = self._armed_step
                overdue = deadline is not None and \
                    time.monotonic() > deadline
                if overdue:
                    waited = time.monotonic() - self._step_t0
                    self._armed_deadline = None   # one trip per hang
            if overdue:
                self._trip(
                    "step_deadline",
                    "step {} has not completed after {:.2f}s (deadline "
                    "{:.2f}x rolling median, floor {}s) — hung "
                    "collective/transfer?".format(
                        step, waited, float(self._dl_cfg["factor"]),
                        self._dl_cfg["floor_s"]),
                    self._dl_cfg["action"], from_thread=True)

    # ------------------------------------------------------------- records
    def observe_train(self, rec):
        """One emitted train StepRecord: NaN-streak + loss-spike."""
        loss = rec.get("loss")
        finite = loss is not None and loss == loss and \
            abs(loss) != float("inf")
        bad = (not finite) or bool(rec.get("overflow"))
        nan_cfg = self.cfg.get("nan_streak")
        if nan_cfg is not None:
            if bad:
                self._nan_streak += 1
                if not self._nan_tripped and \
                        self._nan_streak >= int(nan_cfg["threshold"]):
                    self._nan_tripped = True    # once per streak
                    self._trip(
                        "nan_streak",
                        "{} consecutive steps with non-finite loss or "
                        "overflow (step {}, loss {!r})".format(
                            self._nan_streak, rec.get("step"), loss),
                        nan_cfg["action"])
            else:
                self._nan_streak = 0
                self._nan_tripped = False
        spike_cfg = self.cfg.get("loss_spike")
        if spike_cfg is not None and finite:
            window = self._losses
            if len(window) >= int(spike_cfg["min_steps"]):
                mean = sum(window) / len(window)
                var = sum((x - mean) ** 2 for x in window) / len(window)
                std = var ** 0.5
                if std > 0:
                    z = (loss - mean) / std
                    if z >= float(spike_cfg["zscore"]):
                        window.clear()          # cooldown: refill first
                        self._trip(
                            "loss_spike",
                            "loss {:.6g} at step {} is {:.1f} sigma above "
                            "the rolling mean {:.6g}".format(
                                loss, rec.get("step"), z, mean),
                            spike_cfg["action"])
                        return
            window.append(loss)

    def observe_serving(self, rec):
        """One emitted serving StepRecord (pool gauge redundancy: the
        explicit observe_pool_event covers the hard failures)."""

    # ------------------------------------------------------------- serving
    def observe_ttft(self, seconds):
        cfg = self.cfg.get("ttft_slo")
        if cfg is None or cfg.get("slo_s") is None:
            return
        self._ttft_samples += 1
        if seconds <= float(cfg["slo_s"]):
            return
        self._ttft_violations += 1
        if (self._ttft_violations - 1) % max(int(cfg["every"]), 1) == 0:
            self._trip(
                "ttft_slo",
                "TTFT {:.3f}s exceeded the {:.3f}s SLO ({} violation(s) "
                "so far)".format(seconds, float(cfg["slo_s"]),
                                 self._ttft_violations),
                cfg["action"])

    def ttft_burn_rate(self):
        """TTFT-SLO burn: violations / samples since arm (None without
        a configured SLO or before the first sample) — the /healthz and
        ``ds_ttft_slo_burn_rate`` gauge payload."""
        cfg = self.cfg.get("ttft_slo")
        if cfg is None or cfg.get("slo_s") is None or \
                self._ttft_samples == 0:
            return None
        return self._ttft_violations / self._ttft_samples

    # -------------------------------------------------------------- fleet
    def observe_fleet(self, report):
        """Feed a merged fleet report (fleet/aggregate.merge_run shape
        or a bare flags list): each NEW (host, metric) straggler/ICI
        flag trips the ``straggler`` alarm once."""
        cfg = self.cfg.get("straggler")
        if cfg is None:
            return
        flags = report.get("straggler", {}).get("flags", []) \
            if isinstance(report, dict) else list(report)
        for flag in flags:
            key = (flag.get("host"), flag.get("metric"))
            if key in self._fleet_tripped:
                continue
            self._fleet_tripped.add(key)
            # ici:<class> ratios are inverted achieved/nominal
            # bandwidth, not fleet-median deviations — word them so
            self._trip(
                "straggler",
                "host {} {} for {} consecutive steps "
                "(first step {})".format(
                    flag.get("host"),
                    describe_flag_ratio(flag.get("metric"),
                                        flag.get("worst_ratio", 0.0)),
                    flag.get("steps"), flag.get("first_step")),
                cfg["action"])

    def observe_pool_event(self, kind):
        """``kind``: 'admission_blocked' | 'preemption' — the paged KV
        pool could not serve a request's growth."""
        cfg = self.cfg.get("pool_exhaustion")
        if cfg is None:
            return
        self._pool_events += 1
        if (self._pool_events - 1) % max(int(cfg["every"]), 1) == 0:
            self._trip(
                "pool_exhaustion",
                "KV page pool pressure: {} ({} event(s) so far) — the "
                "pool is undersized for this traffic".format(
                    kind, self._pool_events),
                cfg["action"])

    # ------------------------------------------------------------ snapshot
    def trips_snapshot(self):
        """Copy of the trip list under the state lock — the one correct
        way to read ``trips`` from another thread (the exporter's
        /healthz handlers, the metrics sink's emit)."""
        with self._lock:
            return list(self.trips)

    def snapshot(self):
        with self._lock:
            trips = list(self.trips)
            durations_tracked = len(self._durations)
        return {
            "trips": trips,
            "nan_streak": self._nan_streak,
            "ttft_violations": self._ttft_violations,
            "ttft_samples": self._ttft_samples,
            "pool_events": self._pool_events,
            "step_durations_tracked": durations_tracked,
        }

    def close(self):
        self._stop.set()
        thread = self._thread
        if thread is not None and thread.is_alive():
            thread.join(timeout=1.0)
        self._thread = None
