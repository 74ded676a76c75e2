"""Straggler + ICI-health attribution over the merged fleet view
(ISSUE 14; docs/fleet.md has the full semantics).

**Straggler**: a host whose step wall — or whose per-kind executed-
segment wall, when the step ran as a segment plan — deviates from the
fleet median by more than ``factor`` for ``k`` CONSECUTIVE steps. Steps
are barrier-synchronized across hosts, so the per-step fleet median is
a meaningful oracle; ``k`` consecutive deviations filter the one-off
GC/co-tenant spikes a single slow step cannot distinguish from a sick
host. Rides the PR 8 trip machinery: the ``straggler`` watchdog
(``telemetry.watchdog.straggler``) takes the detector's flags through
``Watchdog.observe_fleet`` with the usual warn/dump actions.

**ICI health**: per collective class, achieved bandwidth = the wire.py
bytes the class moves per step ÷ the MEASURED exposed-wait wall the
executor attributed to transfers/collectives (SEGMENT_KEYS
``per_kind[...].wait_s``), apportioned to classes by byte share,
against the nominal ``wire.ICI_GBPS`` table. ``health ~ 1`` = the link
delivers nominal; a degraded link (flaky ICI cable, a misrouted hop)
shows ``health < 1/factor`` for ``k`` steps and is flagged exactly like
a straggler. Paths with no measured waits (micro/fused: the collective
wall hides inside one XLA program) honestly report ``None`` rather
than a health score derived from the analytic estimate (which would be
1.0 by construction).

Stdlib-only (the fleet-package contract; see metrics.py): the nominal
ICI table imports lazily from wire.py and degrades to the CPU nominal
when jax is absent (post-mortem ``bin/ds_fleet.py`` on a jax-less box).
"""
import logging
import statistics

logger = logging.getLogger("DeepSpeedTPU")

# defaults for the `straggler` watchdog sub-config
# (telemetry/config.py parses; watchdog.py re-exports)
STRAGGLER_DEFAULTS = {"factor": 1.5, "k": 3, "min_hosts": 2,
                      "action": "warn"}

# per-kind walls below this floor are noise, not attribution signal
# (a 50 us host segment 1.5x over a 30 us median is jitter)
MIN_WALL_S = 1e-3

def true_median(values):
    """statistics.median (input need not be sorted): averages the
    middle pair on even lengths — the naive upper-middle pick makes a
    2-host fleet's slow host ITS OWN oracle (median == its wall), so a
    straggler in the smallest fleet would never flag."""
    return statistics.median(values)


# fallback nominal when wire.ICI_GBPS is unimportable (no jax): the
# same CPU nominal wire.py documents as never meaningful in absolute
# terms — health values stay comparable across runs of one box
FALLBACK_ICI_BYTES_PER_S = 10.0e9


def nominal_ici_bytes_per_s(device="cpu"):
    """Nominal per-chip ICI bytes/s for ``device`` from wire.ICI_GBPS
    (an unknown device kind raises); the CPU nominal when wire.py (jax)
    is unimportable."""
    try:
        from deepspeed_tpu.runtime.comm.wire import ici_bytes_per_s_for
    except ImportError:                     # jax-less fleet doctor
        return FALLBACK_ICI_BYTES_PER_S
    return ici_bytes_per_s_for(device)


def ici_health_from_record(rec, nominal_bytes_per_s=None):
    """``achieved/nominal`` bandwidth ratio from ONE train StepRecord:
    ``{class: health | None}`` (``{}`` when the record carries no comm
    classes). ``None`` per class = no measured exposed-wait wall to
    divide by on this step path.

    HONESTY CONTRACT: the executor measures ONE exposed-wait wall for
    the whole step (per segment KIND, not per collective class), so
    every byte-moving class receives the SAME blended ratio —
    total bytes / measured wait / nominal. Any per-class apportionment
    of one aggregate wall algebraically cancels back to this number,
    so none is pretended. The gauge localizes a degraded HOST/link
    (all of its classes sink together, and the ``ici:<class>`` streaks
    flag it); telling the classes apart needs per-class measured walls
    the executor does not yet record (docs/fleet.md)."""
    co = rec.get("comm_overlap") or {}
    classes = [cls for cls, ent in co.items() if ent.get("bytes")]
    if not classes:
        return {}
    if nominal_bytes_per_s is None:
        nominal_bytes_per_s = nominal_ici_bytes_per_s(
            rec.get("device", "cpu"))
    offload = rec.get("offload") or {}
    per_kind = offload.get("per_kind") or {}
    measured_wait = sum(
        float(per_kind.get(kind, {}).get("wait_s", 0.0) or 0.0)
        for kind in ("collective", "transfer"))
    if measured_wait <= 0:
        return {cls: None for cls in classes}   # nothing measured
    total_bytes = sum(float(co[cls].get("bytes") or 0)
                      for cls in classes)
    achieved = total_bytes / measured_wait
    health = round(achieved / float(nominal_bytes_per_s), 6)
    return {cls: health for cls in classes}


def describe_flag_ratio(metric, ratio):
    """Human wording for one flag's ``worst_ratio``: wall metrics carry
    a deviation vs the fleet median, ``ici:<class>`` metrics carry the
    INVERTED achieved/nominal bandwidth (see ``_ici_flags``) — the two
    numbers mean different things and must read differently."""
    ratio = float(ratio or 0.0)
    if str(metric).startswith("ici:"):
        health = (1.0 / ratio) if ratio else 0.0
        return "{} measured ICI bandwidth at {:.0%} of nominal".format(
            metric, health)
    return "{} {:.2f}x over the fleet median".format(metric, ratio)


class StragglerDetector:
    """Consumes merged fleet records (aggregate.merge_run) in step
    order; accumulates flags. One flag per streak per (host, metric):
    the flag's ``steps`` / ``last_step`` / ``worst_ratio`` keep
    updating while the streak lives."""

    def __init__(self, factor=None, k=None, min_hosts=None):
        self.factor = float(factor if factor is not None
                            else STRAGGLER_DEFAULTS["factor"])
        self.k = int(k if k is not None else STRAGGLER_DEFAULTS["k"])
        self.min_hosts = int(min_hosts if min_hosts is not None
                             else STRAGGLER_DEFAULTS["min_hosts"])
        self._streaks = {}          # (host, metric) -> streak dict
        self.flags = []
        self.steps_observed = 0

    # ------------------------------------------------------------ observe
    def _ratios(self, fleet_rec):
        """(host, metric, ratio) deviation candidates for one merged
        step: the step wall vs the fleet median, plus each per-kind
        segment wall vs its fleet median (lowered paths only)."""
        hosts = fleet_rec["hosts"]
        if len(hosts) < self.min_hosts:
            return
        walls = [h["step_time_s"] for h in hosts.values()
                 if h.get("step_time_s") is not None]
        if walls:
            median = true_median(walls)
            if median > 0:
                for name, h in hosts.items():
                    if h.get("step_time_s") is not None:
                        yield name, "step_wall", h["step_time_s"] / median
        kinds = {}
        for name, h in hosts.items():
            for kind, slot in (h.get("per_kind") or {}).items():
                # run_s can be null on degraded/adopted records — the
                # merged view must attribute, never crash, on them
                kinds.setdefault(kind, []).append(
                    (name, float(slot.get("run_s") or 0.0)))
        for kind, vals in kinds.items():
            if len(vals) < self.min_hosts:
                continue
            median = true_median(v for _, v in vals)
            if median < MIN_WALL_S:
                continue            # sub-ms walls are jitter, not signal
            for name, wall in vals:
                yield name, "segment:{}".format(kind), wall / median

    def _ici_flags(self, fleet_rec):
        """Degraded-link candidates: a host whose measured per-class
        ici_health sits below 1/factor (same streak machinery)."""
        for name, h in (fleet_rec["hosts"] or {}).items():
            for cls, health in (h.get("ici_health") or {}).items():
                if health is None:
                    continue
                # invert so "bigger = worse" like the wall ratios
                yield name, "ici:{}".format(cls), \
                    (1.0 / health) if health > 0 else float("inf")

    def observe(self, fleet_rec):
        """Feed one merged fleet step record (in step order)."""
        self.steps_observed += 1
        step = fleet_rec["step"]
        seen = set()
        candidates = list(self._ratios(fleet_rec)) + \
            list(self._ici_flags(fleet_rec))
        for host, metric, ratio in candidates:
            key = (host, metric)
            seen.add(key)
            if ratio < self.factor:
                self._streaks.pop(key, None)
                continue
            streak = self._streaks.get(key)
            if streak is None:
                streak = {"host": host, "metric": metric,
                          "first_step": step, "last_step": step,
                          "steps": 1, "worst_ratio": ratio,
                          "flag": None}
                self._streaks[key] = streak
            else:
                streak["steps"] += 1
                streak["last_step"] = step
                streak["worst_ratio"] = max(streak["worst_ratio"], ratio)
            if streak["steps"] >= self.k:
                if streak["flag"] is None:
                    flag = {k: v for k, v in streak.items() if k != "flag"}
                    streak["flag"] = flag
                    self.flags.append(flag)
                    logger.warning(
                        "fleet straggler: host %s %s for %d "
                        "consecutive steps (first step %d)", host,
                        describe_flag_ratio(metric,
                                            streak["worst_ratio"]),
                        streak["steps"], streak["first_step"])
                else:               # live flag keeps tracking the streak
                    for field in ("steps", "last_step", "worst_ratio"):
                        streak["flag"][field] = streak[field]
        # hosts absent this step break their streaks honestly
        for key in [k for k in self._streaks if k not in seen]:
            self._streaks.pop(key)

    # ------------------------------------------------------------- report
    def report(self):
        return {
            "factor": self.factor,
            "k": self.k,
            "min_hosts": self.min_hosts,
            "steps_observed": self.steps_observed,
            "flags": [dict(f) for f in self.flags],
            "flagged_hosts": sorted({f["host"] for f in self.flags}),
        }


def detect_stragglers(fleet_records, factor=None, k=None, min_hosts=None):
    """Run a fresh detector over merged records; returns its report."""
    det = StragglerDetector(factor=factor, k=k, min_hosts=min_hosts)
    for rec in fleet_records:
        det.observe(rec)
    return det.report()
