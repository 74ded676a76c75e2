"""Training-scalar monitor: TensorBoard when available, JSONL always.

Reference parity: the engine's SummaryWriter usage (engine.py:154-155,
256-281, 964-975, 1110-1124 — Train/Samples/{lr,loss,loss_scale} scalars
keyed by global samples). On TPU hosts TensorBoard may be absent, so every
scalar is also appended to ``events.jsonl`` in the output path — one
``{"tag", "value", "step", "wall"}`` object per line — which xprof-era
tooling and plain pandas both ingest.
"""
import json
import os
import time

from .lifecycle import AtexitCloseMixin
from .logging import logger


class SummaryMonitor(AtexitCloseMixin):
    """SummaryWriter-shaped facade (add_scalar/flush/close)."""

    def __init__(self, output_path, job_name="DeepSpeedJobName",
                 enabled=True):
        self.enabled = enabled
        if enabled and not output_path:
            # reference SummaryWriter defaults to ./runs; don't silently
            # drop scalars the user asked for
            output_path = "runs"
            logger.info("tensorboard enabled with no output_path; "
                        "writing to ./runs")
        self.output_path = os.path.join(output_path or "", job_name or "")
        self._tb = None
        self._jsonl = None
        self._closed = not enabled
        if not self.enabled:
            return
        os.makedirs(self.output_path, exist_ok=True)
        self._register_atexit_close()
        try:
            from torch.utils.tensorboard import SummaryWriter
            self._tb = SummaryWriter(log_dir=self.output_path)
        except Exception:  # noqa: BLE001 - tensorboard genuinely optional
            logger.info("tensorboard unavailable; monitor writes JSONL only")
        self._jsonl = open(os.path.join(self.output_path, "events.jsonl"),
                           "a", buffering=1)

    @classmethod
    def from_config(cls, config, enabled=True):
        return cls(config.tensorboard_output_path,
                   config.tensorboard_job_name,
                   enabled=enabled and config.tensorboard_enabled)

    def add_scalar(self, tag, value, step):
        if not self.enabled:
            return
        value = float(value)
        if self._tb is not None:
            self._tb.add_scalar(tag, value, step)
        if self._jsonl is not None:
            self._jsonl.write(json.dumps(
                {"tag": tag, "value": value, "step": int(step),
                 "wall": time.time()}) + "\n")

    def flush(self):
        if self._tb is not None:
            self._tb.flush()

    def close(self):
        """Idempotent: the first call releases the writers and drops the
        atexit registration; later calls are no-ops."""
        if self._finish_close():
            return
        if self._tb is not None:
            self._tb.close()
            self._tb = None
        if self._jsonl is not None:
            self._jsonl.close()
            self._jsonl = None


class ServingMetrics:
    """Inference-serving counters: prefill vs decode tokens/s, slot
    occupancy, queue depth.

    Filled by the continuous-batching scheduler
    (inference/scheduler.py) at decode-step granularity; pass a
    :class:`SummaryMonitor` to also mirror the scalars into the same
    TensorBoard/JSONL stream the training engine writes
    (``Serve/{prefill_tokens_per_sec,decode_tokens_per_sec,
    slot_occupancy,queue_depth}``)."""

    # request-latency samples kept for p50/p95 (bounded so a long-lived
    # serving engine cannot grow host memory without bound)
    LATENCY_WINDOW = 4096

    def __init__(self, monitor=None):
        from collections import deque
        self.monitor = monitor
        self.prefill_tokens = 0
        self.prefill_seconds = 0.0
        self.prefill_calls = 0
        self.decode_tokens = 0
        self.decode_seconds = 0.0
        self.decode_steps = 0
        self.schedule_steps = 0
        self.occupancy_sum = 0.0
        self.last_queue_depth = 0
        self.peak_queue_depth = 0
        # request latency: time-to-first-token and per-output-token
        self.ttfts = deque(maxlen=self.LATENCY_WINDOW)
        self.tpots = deque(maxlen=self.LATENCY_WINDOW)
        # arrival -> first admission into a slot
        self.queue_waits = deque(maxlen=self.LATENCY_WINDOW)
        self.completed_requests = 0
        self.completed_tokens = 0       # the goodput numerator
        # speculative decoding
        self.spec_proposed = 0
        self.spec_accepted = 0
        self.spec_steps = 0
        # bytes one cached token costs in the engine's pool, all layers
        # and pad lanes included (keys and values, or a latent row)
        self.kv_token_bytes = 0
        # the recurrent-state pool of a model that keeps one
        self.state_slots = 0
        self.state_bytes = 0
        self.state_live_slots = 0
        self.state_resets = 0
        # what the serving programs counted themselves, by the names
        # the model's decoder gave: {name: {"launches": n, attribute:
        # sum over launches}} (an expert model's "moe.load": rows,
        # experts_hit, hottest_rows)
        self.program_counters = {}
        # a model whose paged layers stand in groups: the pages the
        # decoding slots hold in each group as the last step found them,
        # and the pages each group has given back so far as they slid
        # out of a window (0 for a group without one)
        self.group_pages_live = []
        self.group_pages_freed = []

    def record_group_pages(self, live, freed):
        self.group_pages_live = list(live)
        self.group_pages_freed = list(freed)

    def record_counters(self, counters):
        """One launch's program counters, ``{name: {attribute:
        value}}``, added to the sums."""
        for name, attrs in counters.items():
            row = self.program_counters.setdefault(name, {"launches": 0})
            row["launches"] += 1
            for key, value in attrs.items():
                row[key] = row.get(key, 0) + value

    def record_state_pool(self, slots, nbytes, live_slots):
        """The recurrent-state pool as the step leaves it: its slots and
        bytes, and the slots that hold a live request's state."""
        self.state_slots, self.state_bytes = int(slots), int(nbytes)
        self.state_live_slots = int(live_slots)

    def record_state_reset(self):
        """One request's first prefill chunk started a slot's recurrent
        state from zeros (inside the chunk's program)."""
        self.state_resets += 1

    def record_prefill(self, tokens, seconds):
        self.prefill_tokens += int(tokens)
        self.prefill_seconds += float(seconds)
        self.prefill_calls += 1

    def record_decode(self, tokens, seconds):
        """One fused decode step: ``tokens`` = tokens EMITTED this step
        (live slots for plain decode; sum of accepted+1 for a
        speculative verify step)."""
        self.decode_tokens += int(tokens)
        self.decode_seconds += float(seconds)
        self.decode_steps += 1

    def record_ttft(self, seconds):
        self.ttfts.append(float(seconds))

    def record_queue_wait(self, seconds):
        """One request admitted into a slot ``seconds`` after it
        arrived (the part of its TTFT spent queued, before prefill)."""
        self.queue_waits.append(float(seconds))

    def record_completion(self, n_tokens, tpot_seconds):
        """One retired request: ``tpot_seconds`` is its mean
        time-per-output-token after the first (None for single-token
        completions)."""
        self.completed_requests += 1
        self.completed_tokens += int(n_tokens)
        if tpot_seconds is not None:
            self.tpots.append(float(tpot_seconds))

    def record_spec(self, proposed, accepted):
        """One slot's verify outcome: ``proposed`` drafts scored,
        ``accepted`` of them matched the target."""
        self.spec_proposed += int(proposed)
        self.spec_accepted += int(accepted)
        self.spec_steps += 1

    def record_schedule(self, occupancy, queue_depth, step):
        self.schedule_steps += 1
        self.occupancy_sum += float(occupancy)
        self.last_queue_depth = int(queue_depth)
        self.peak_queue_depth = max(self.peak_queue_depth, int(queue_depth))
        if self.monitor is not None:
            self.monitor.add_scalar("Serve/slot_occupancy", occupancy, step)
            self.monitor.add_scalar("Serve/queue_depth", queue_depth, step)
            self.monitor.add_scalar("Serve/prefill_tokens_per_sec",
                                    self.prefill_tokens_per_sec, step)
            self.monitor.add_scalar("Serve/decode_tokens_per_sec",
                                    self.decode_tokens_per_sec, step)

    @property
    def prefill_tokens_per_sec(self):
        return (self.prefill_tokens / self.prefill_seconds
                if self.prefill_seconds > 0 else 0.0)

    @property
    def decode_tokens_per_sec(self):
        return (self.decode_tokens / self.decode_seconds
                if self.decode_seconds > 0 else 0.0)

    @property
    def mean_occupancy(self):
        return (self.occupancy_sum / self.schedule_steps
                if self.schedule_steps else 0.0)

    @property
    def spec_acceptance_rate(self):
        return (self.spec_accepted / self.spec_proposed
                if self.spec_proposed else 0.0)

    @staticmethod
    def _latency_dist(samples):
        """{count, mean_s, p50_s, p95_s} over a latency deque — None
        when no request has produced a sample yet."""
        if not samples:
            return None
        import numpy as np
        vals = np.asarray(samples, np.float64)
        return {"count": len(samples),
                "mean_s": round(float(vals.mean()), 6),
                "p50_s": round(float(np.percentile(vals, 50)), 6),
                "p95_s": round(float(np.percentile(vals, 95)), 6)}

    def ttft_dist(self):
        return self._latency_dist(self.ttfts)

    def tpot_dist(self):
        return self._latency_dist(self.tpots)

    def queue_wait_dist(self):
        return self._latency_dist(self.queue_waits)

    def spec_dist(self):
        """{proposed, accepted, acceptance_rate} — None before the
        first verify step (spec off, or still prefill-only)."""
        if not self.spec_steps:
            return None
        return {"proposed": self.spec_proposed,
                "accepted": self.spec_accepted,
                "acceptance_rate": round(self.spec_acceptance_rate, 4)}

    def snapshot(self):
        out = {
            "prefill_tokens": self.prefill_tokens,
            "prefill_tokens_per_sec": round(self.prefill_tokens_per_sec, 2),
            "decode_tokens": self.decode_tokens,
            "decode_steps": self.decode_steps,
            "decode_tokens_per_sec": round(self.decode_tokens_per_sec, 2),
            "mean_slot_occupancy": round(self.mean_occupancy, 4),
            "peak_queue_depth": self.peak_queue_depth,
            "completed_requests": self.completed_requests,
            "completed_tokens": self.completed_tokens,
        }
        for name, dist in (("ttft", self.ttft_dist()),
                           ("tpot", self.tpot_dist()),
                           ("queue_wait", self.queue_wait_dist()),
                           ("speculative", self.spec_dist())):
            if dist is not None:
                out[name] = dist
        if self.kv_token_bytes:
            out["kv_token_bytes"] = self.kv_token_bytes
        if self.state_slots:
            out["state_pool"] = {"slots": self.state_slots,
                                 "bytes": self.state_bytes,
                                 "live_slots": self.state_live_slots,
                                 "resets": self.state_resets}
        if self.group_pages_live:
            out["page_groups"] = {"live": self.group_pages_live,
                                  "freed": self.group_pages_freed}
        if self.program_counters:
            out["program_counters"] = {
                name: dict(row)
                for name, row in self.program_counters.items()}
        return out
