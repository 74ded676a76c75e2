"""Wall-clock and throughput timers.

Reference parity: deepspeed/utils/timer.py (SynchronizedWallClockTimer :19,
ThroughputTimer :97). On TPU, synchronization uses
``jax.block_until_ready``-style barriers via ``jax.effects_barrier`` /
device sync instead of ``torch.cuda.synchronize``.
"""
import time

from .annotate import annotate
from .logging import logger


# cached scratch scalar for the fallback sync path: the old code
# device_put a FRESH host scalar on every timer start/stop, so
# wall_clock_breakdown perturbed exactly the transfer path it measured
_sync_scratch = None


def _device_synchronize():
    """Block until all pending device work is done (closest analogue of
    a CUDA sync); cheap when nothing is in flight. Enqueues a tiny op on
    a CACHED device scalar and blocks on it — the op orders after
    in-flight work on the stream, so blocking on it fences that work.
    NOTE ``jax.effects_barrier()`` is NOT a substitute: it only blocks
    on effect tokens (io_callback etc.), never on pending PURE jitted
    programs, so it returns immediately for an ordinary train step."""
    global _sync_scratch
    try:
        import jax
    except Exception:  # noqa: BLE001 - timers must work without jax
        return
    # "timer.sync" in the profiler's trace: what each fence costs
    with annotate("timer.sync"):
        for _ in range(2):
            try:
                if _sync_scratch is None:
                    _sync_scratch = jax.device_put(0.0)
                # (x + 0) enqueues one op; block_until_ready on the bare
                # cached array would return immediately without fencing
                (_sync_scratch + 0).block_until_ready()
                return
            except Exception:  # noqa: BLE001
                # the cached buffer can go stale (backend reset between
                # tests) — rebuild and retry ONCE so this interval still
                # fences; a second failure means no live backend to fence
                _sync_scratch = None


class SynchronizedWallClockTimer:
    """Named timers whose start/stop sync outstanding device work.

    ``fence``: what a start/stop waits on. None (training) is
    :func:`_device_synchronize`, an op sent behind whatever the process
    has in flight. A caller that holds the buffers its programs write
    passes a wait on those (the serving scheduler:
    ``InferenceEngine.wait``), which sends the device nothing."""

    class Timer:
        def __init__(self, name, fence=None):
            self.name_ = name
            self.fence_ = fence
            self.elapsed_ = 0.0
            self.started_ = False
            self.start_time = time.time()

        def _sync(self):
            if self.fence_ is None:
                _device_synchronize()
            else:
                with annotate("timer.sync"):
                    self.fence_()

        def start(self):
            assert not self.started_, "timer has already been started"
            self._sync()
            self.start_time = time.time()
            self.started_ = True

        def stop(self, reset=False):
            assert self.started_, "timer is not started"
            self._sync()
            if reset:
                self.elapsed_ = time.time() - self.start_time
            else:
                self.elapsed_ += time.time() - self.start_time
            self.started_ = False

        def reset(self):
            self.elapsed_ = 0.0
            self.started_ = False

        def elapsed(self, reset=True):
            started_ = self.started_
            if self.started_:
                self.stop()
            elapsed_ = self.elapsed_
            if reset:
                self.reset()
            if started_:
                self.start()
            return elapsed_

    def __init__(self, fence=None):
        self.fence = fence
        self.timers = {}

    def __call__(self, name):
        if name not in self.timers:
            self.timers[name] = self.Timer(name, self.fence)
        return self.timers[name]

    @staticmethod
    def memory_usage():
        try:
            import jax
            stats = jax.local_devices()[0].memory_stats() or {}
            alloc = stats.get("bytes_in_use", 0) / (1024 ** 3)
            peak = stats.get("peak_bytes_in_use", 0) / (1024 ** 3)
            return "mem (GB) | allocated: {:.2f} | peak: {:.2f}".format(alloc, peak)
        except Exception:
            return "mem (GB) | unavailable"

    def log(self, names, normalizer=1.0, reset=True, memory_breakdown=False):
        assert normalizer > 0.0
        string = "time (ms)"
        for name in names:
            if name in self.timers:
                elapsed_time = self.timers[name].elapsed(reset=reset) * 1000.0
                elapsed_time /= normalizer
                string += " | {}: {:.2f}".format(name, elapsed_time)
        if memory_breakdown:
            string += " | " + self.memory_usage()
        logger.info(string)


class ThroughputTimer:
    """Samples/sec tracker around train steps (reference timer.py:97)."""

    def __init__(self, batch_size, num_workers, start_step=2,
                 steps_per_output=50, monitor_memory=False, logging_fn=None):
        self.start_time = 0
        self.end_time = 0
        self.started = False
        self.batch_size = batch_size if batch_size else 1
        self.num_workers = num_workers
        self.start_step = start_step
        self.epoch_count = 0
        self.local_step_count = 0
        self.total_step_count = 0
        self.total_elapsed_time = 0
        self.steps_per_output = steps_per_output
        self.monitor_memory = monitor_memory
        self.logging = logging_fn or logger.info
        self.initialized = False

    def update_epoch_count(self):
        self.epoch_count += 1
        self.local_step_count = 0

    def _init_timer(self):
        self.initialized = True

    def start(self):
        self._init_timer()
        self.started = True
        if self.total_step_count >= self.start_step:
            _device_synchronize()
            self.start_time = time.time()

    def stop(self, report_speed=True):
        if not self.started:
            return
        self.started = False
        self.total_step_count += 1
        self.local_step_count += 1
        if self.total_step_count > self.start_step:
            _device_synchronize()
            self.end_time = time.time()
            duration = self.end_time - self.start_time
            self.total_elapsed_time += duration
            if self.local_step_count % self.steps_per_output == 0:
                if report_speed:
                    self.logging(
                        "{}/{}, SamplesPerSec={}".format(
                            self.epoch_count, self.local_step_count,
                            self.avg_samples_per_sec()))
                if self.monitor_memory:
                    self.logging(SynchronizedWallClockTimer.memory_usage())

    def avg_samples_per_sec(self):
        if self.total_step_count > self.start_step:
            samples_per_step = self.batch_size * self.num_workers
            total_step_offset = self.total_step_count - self.start_step
            avg_time_per_step = self.total_elapsed_time / total_step_offset
            return samples_per_step / avg_time_per_step
        return float("-inf")
