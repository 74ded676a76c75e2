"""Closed-loop training: ``train_batch()`` back to back on seeded
batches, a new batch every step, from an input thread.

Workload parameters: ``traffic`` (generator + its parameters),
``warmup_steps`` (fenced steps after the two whose losses the output
check reads), ``in_flight`` (steps the host may run ahead of the
device), ``trace_seconds`` (length of the window in a traced run).
"""
import collections
import queue
import threading
import time

from .. import manifest


def _input_thread(source, out, stop):
    for batch in source:
        while not stop.is_set():
            try:
                out.put(batch, timeout=0.1)
                break
            except queue.Full:
                continue
        if stop.is_set():
            return


def run(run):
    import jax
    config, workload = run.config, run.workload
    family = manifest.plugin("models", config["family"])
    traffic = manifest.plugin("traffic", workload["traffic"]["generator"])

    with run.spans.span("engine.build"):
        engine = family.build_train_engine(config, run.seed)
    accumulation = engine.gradient_accumulation_steps()
    rows = engine.train_micro_batch_size_per_gpu() * engine.dp_world_size
    seq = workload["traffic"]["seq_len"]
    tokens_per_step = accumulation * rows * seq

    batches, stop = queue.Queue(maxsize=4), threading.Event()
    feeder = threading.Thread(
        target=_input_thread, daemon=True,
        args=(traffic.batches(workload["traffic"], run.seed, rows,
                              config["model"]["padded_vocab_size"],
                              accumulation), batches, stop))
    feeder.start()
    try:
        # the first two steps start from the seed's weights: their
        # losses are what the output check compares, and they compile
        check_batches, engine_losses, probe = [], [], None
        for _ in range(2):
            batch = batches.get()
            check_batches.append(batch[0].reshape(-1, seq))
            with run.spans.span("train_batch.first"):
                engine_losses.append(float(engine.train_batch(batch=batch)))
            if probe is None:
                probe = family.train_probe(engine, config["check"]["stride"])
        for _ in range(workload["warmup_steps"]):
            loss = engine.train_batch(batch=batches.get())
        jax.block_until_ready(loss)

        seconds = run.window_seconds()
        pending, done = collections.deque(), []
        run.open_window()
        while True:
            with run.spans.span("input.make_batch"):
                batch = batches.get()
            with run.spans.span("train_batch"):
                pending.append(engine.train_batch(batch=batch))
            # the host stays at most in_flight steps ahead: the device
            # always has a step queued, and the window closes within
            # in_flight steps of its length
            if len(pending) > workload["in_flight"]:
                with run.spans.span("fence"):
                    jax.block_until_ready(pending.popleft())
                done.append(time.perf_counter())
            if time.perf_counter() - run.t_open >= seconds:
                break
        with run.spans.span("fence"):
            while pending:
                jax.block_until_ready(pending.popleft())
                done.append(time.perf_counter())
        run.close_window()
    finally:
        stop.set()
        feeder.join(timeout=10)

    for start, end in zip([run.t_open] + done[:-1], done):
        run.spans.spans.append(("train_step", start, end))
    window_s = run.t_close - run.t_open
    rate = len(done) * tokens_per_step / window_s / run.chips
    run.counters.update(steps=len(done), tokens_per_step=tokens_per_step,
                        rows=rows, seq_len=seq)
    run.note_memory()
    engine.close()
    family.release(engine.state)
    del engine, loss
    with run.spans.span("check"):
        checks = family.train_check(config, run.seed, check_batches,
                                    engine_losses, probe)
    return {"end_to_end": {"train_tokens_per_s": rate},
            "attempted": len(done), "failed": 0, "checks": checks}
