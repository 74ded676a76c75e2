"""Serving through the continuous-batching scheduler, driven from one
thread: submit what is due, take one ``scheduler.step()``, look at what
each request has got, repeat. Open loop: a request is due when the
traffic says so, whatever the server is doing, and its time to first
token counts from when it was DUE. A backlog is the same loop with
everything due at the start; it has to outlast the window, and a run
whose queue is empty when the window closes raises: its last steps ran
with slots that nothing was waiting for.

Workload parameters: ``traffic`` (generator + parameters), ``lead_s``
(seconds of the same traffic before the window opens, so that the
slots are at their steady occupancy when it does), ``drain_cap_s``
(longest wait for the window's requests after it closed), ``latency``
(true: the window's requests are drained and their tails reported;
needs ``percentile``), ``trace_seconds``.

A request's tokens are seen when the ``scheduler.step()`` that made
them returns: the scheduler hands nothing to its caller earlier, so
that is when a server built on it could first send each.

After the window a few of the requests that the scheduler retired
inside it, under load and from slots and pages that other requests
had used before, are handed to the family's output check with the
tokens the scheduler gave for them.
"""
import math
import time

import numpy as np

from .. import manifest, stats


# arrivals go on this long past the window (and its drain), so that the
# load on the window's last requests is the load on its first
_TAIL_S = 5.0


class _Request:
    __slots__ = ("index", "uid", "due", "prompt_len", "want", "seen",
                 "first_t", "last_t", "submitted_t")

    def __init__(self, index, due, prompt_len, want):
        self.index, self.uid = index, None
        self.due, self.prompt_len, self.want = due, prompt_len, want
        self.seen = 0              # generated tokens observed so far
        self.first_t = self.last_t = self.submitted_t = None


def latency_tails(sample, g0, q):
    """The q-th percentiles over the window's requests of the time to
    first token, counted from when the request was DUE (``g0 + due``),
    and of the time per output token after the first. A request with no
    first token, or not all of its answer, by the end of the drain is a
    miss: it counts as infinitely late and as failed."""
    ttft = [1e3 * (r.first_t - (g0 + r.due)) if r.first_t is not None
            else math.inf for r in sample]
    tpot = [1e3 * (r.last_t - r.first_t) / (r.want - 1)
            if r.seen >= r.want else math.inf
            for r in sample if r.want > 1]
    return {"ttft_ms": stats.percentile(ttft, q),
            "tpot_ms": stats.percentile(tpot, q),
            "failed": sum(r.seen < r.want for r in sample)}


def _backlog_ran_dry(requests, served_s):
    """The error for a backlog that did not outlast the window, with
    the rate below which its tokens would have lasted that long."""
    tokens = sum(r.prompt_len + r.want for r in requests)
    return RuntimeError(
        "the backlog ran dry: 0 of {} requests were still queued when the "
        "window closed; {} tokens over {:g} s keep the queue full only "
        "below {:.0f} tokens/s: raise arrivals.queued".format(
            len(requests), tokens, served_s, tokens / served_s))


def _warm_up(engine, scheduler_cls, vocab, rng):
    """One request in every prefill bucket, two tokens each: compiles
    (or loads) every prefill program and the decode program."""
    warm = scheduler_cls(engine)
    low = 1
    for bucket in engine.prefill_buckets:
        n = min(bucket, engine.max_seq_len - 2)
        warm.submit(rng.integers(0, vocab, max(low, n)).tolist(),
                    max_new_tokens=2, eos_token_id=None)
        low = bucket + 1
    warm.run()


def run(run):
    from deepspeed_tpu.inference.scheduler import \
        ContinuousBatchingScheduler
    from deepspeed_tpu.utils.monitor import ServingMetrics
    config, workload = run.config, run.workload
    family = manifest.plugin("models", config["family"])
    traffic = manifest.plugin("traffic", workload["traffic"]["generator"])
    vocab = config["model"]["padded_vocab_size"]
    seconds = run.window_seconds()
    latency = workload["latency"] and not run.trace
    backlog = workload["traffic"]["arrivals"]["process"] == "backlog"
    lead_s = workload["lead_s"]
    drain_cap_s = workload["drain_cap_s"] if latency else 0.0

    with run.spans.span("engine.build"):
        engine = family.build_serve_engine(config, run.seed)
    with run.spans.span("warm_up"):
        _warm_up(engine, ContinuousBatchingScheduler, vocab,
                 np.random.default_rng([run.seed, 3]))
    due, prompts, wants = traffic.generate(
        workload["traffic"], run.seed,
        lead_s + seconds + drain_cap_s + _TAIL_S, vocab, cycle_s=seconds)
    requests = [_Request(i, float(d), len(p), int(w))
                for i, (d, p, w) in enumerate(zip(due, prompts, wants))]
    prompts = [p.tolist() for p in prompts]

    metrics = ServingMetrics()
    scheduler = ContinuousBatchingScheduler(engine, metrics=metrics)
    live = {}                      # uid -> _Request, until it retires
    retired_in_window = []
    nxt = 0                        # next request to submit
    tokens_in_window = steps_in_window = active_sum = pages_read = 0
    window_open = False
    g0 = time.perf_counter()
    t_open_at, t_close_at = g0 + lead_s, g0 + lead_s + seconds
    sample = [r for r in requests if lead_s <= r.due < lead_s + seconds]
    prefill_s_at_open = 0.0
    backlog_left = 0               # requests still queued at the close

    def observe(req, n, now):
        new = n - req.seen
        if new <= 0:
            return 0
        first = req.seen == 0
        if first:
            req.first_t = now
        req.seen, req.last_t = n, now
        return new + (req.prompt_len if first else 0)

    while True:
        now = time.perf_counter()
        if not window_open and now >= t_open_at:
            run.open_window()
            window_open = True
            prefill_s_at_open = metrics.prefill_seconds
        if window_open and run.t_close is None and now >= t_close_at:
            # the step that straddled the end has returned: it is inside
            run.close_window()
            run.counters["prefill_seconds"] = \
                metrics.prefill_seconds - prefill_s_at_open
            backlog_left = len(scheduler.queue) + len(requests) - nxt
        if run.t_close is not None and (
                not latency or now - run.t_close >= drain_cap_s or
                all(r.seen >= r.want for r in sample)):
            break
        while nxt < len(requests) and g0 + requests[nxt].due <= now:
            req = requests[nxt]
            uid = scheduler.submit(prompts[nxt], max_new_tokens=req.want,
                                   eos_token_id=None)
            req.uid, req.submitted_t = uid, now
            live[uid] = req
            nxt += 1
        if not scheduler.has_work:
            if nxt >= len(requests):
                break
            with run.spans.span("loadgen.wait"):
                time.sleep(max(0.0, min(g0 + requests[nxt].due - now,
                                        0.002)))
            continue
        with run.spans.span("scheduler.step"):
            retired = scheduler.step()
        now = time.perf_counter()
        stepped = 0
        for uid in retired:
            req = live.pop(uid)
            stepped += observe(req, len(scheduler.results[uid]), now)
            if window_open and run.t_close is None:
                retired_in_window.append(req)
        for slot_req in scheduler.slots:
            if slot_req is not None:
                stepped += observe(live[slot_req.uid],
                                   len(slot_req.generated), now)
        if window_open and run.t_close is None:
            tokens_in_window += stepped
            steps_in_window += 1
            active_sum += scheduler.num_active
            pages_read += sum(
                engine.pages_for(int(engine.lengths[r.slot]))
                for r in scheduler.slots
                if r is not None and r.state == "decode")
    if backlog and backlog_left == 0:
        raise _backlog_ran_dry(requests, lead_s + seconds)
    if run.t_close is None:
        raise RuntimeError("the traffic ended before the window closed: "
                           "{} requests for {} s".format(len(requests),
                                                         seconds))

    window_s = run.t_close - run.t_open
    late = [r.submitted_t - (g0 + r.due) for r in requests[:nxt]]
    run.log("loadgen: submitted={} late_p50_ms={:.3f} late_max_ms={:.3f}"
            .format(nxt, 1e3 * stats.percentile(late, 50),
                    1e3 * max(late)))
    if backlog:
        run.log("backlog: left={} of {}".format(backlog_left,
                                                len(requests)))
        run.counters["backlog_left"] = backlog_left
    run.counters.update(
        steps=steps_in_window, active_slot_steps=active_sum,
        slot_steps=steps_in_window * engine.num_slots,
        tokens=tokens_in_window, window_seconds=window_s,
        live_kv_pages_read=pages_read,
        pool_page_steps=steps_in_window * engine.allocator.num_pages,
        submitted=nxt, pages=engine.allocator.num_pages,
        late_max_ms=1e3 * max(late))
    end_to_end = {"serve_tokens_per_s":
                  tokens_in_window / window_s / run.chips}
    if latency:
        q = workload["percentile"]
        tails = latency_tails(sample, g0, q)
        end_to_end["ttft_p{}_ms".format(q)] = tails["ttft_ms"]
        end_to_end["tpot_p{}_ms".format(q)] = tails["tpot_ms"]
        attempted, failed = len(sample), tails["failed"]
        medians = latency_tails(sample, g0, 50)
        run.log("latency: sample={} failed={} ttft_p50_ms={:.2f} "
                "ttft_p{}_ms={:.2f} tpot_p50_ms={:.2f} tpot_p{}_ms={:.2f}"
                .format(len(sample), failed, medians["ttft_ms"], q,
                        tails["ttft_ms"], medians["tpot_ms"], q,
                        tails["tpot_ms"]))
    else:
        # a backlog has no due times to miss: what counts is what the
        # window finished, and whether each answer came out whole
        attempted = len(retired_in_window)
        failed = sum(r.seen != r.want for r in retired_in_window)

    run.note_memory()
    for slot in range(engine.num_slots):
        engine.free_slot(slot)
    # spread over the window: its first retirements came from the
    # lead-in's slots, its last from slots and pages reused many times
    whole = [r for r in retired_in_window if r.seen == r.want]
    picked = sorted({round(i * (len(whole) - 1) /
                           max(1, config["check"]["served_requests"] - 1))
                     for i in range(config["check"]["served_requests"])
                     }) if whole else []
    served = [(prompts[whole[i].index],
               list(scheduler.results[whole[i].uid])) for i in picked]
    with run.spans.span("check"):
        got = family.serve_engine_outputs(config, run.seed, engine)
        family.release(engine.params, engine.kv.k, engine.kv.v)
        del engine, scheduler
        checks = family.serve_check(config, run.seed, got, served)
    return {"end_to_end": end_to_end, "attempted": attempted,
            "failed": failed, "checks": checks}
