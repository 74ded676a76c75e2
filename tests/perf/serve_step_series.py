"""A backlog cell's run with a long lead-in and every step of the
measured scheduler recorded, so that a window opened at ANY second can
be read from one run: how a cell's lead-in is chosen (PERF.md section 6,
PR 42) and what its ``serve_tokens_per_s`` spreads by. Needs a TPU.

    chiprun -- python tests/perf/serve_step_series.py run CELL SEED \
        LEAD_S chiprun_out/series_SEED.jsonl
    python tests/perf/serve_step_series.py read chiprun_out/series_*.jsonl

``run`` is ``python -m benchmark.run --workload CELL --seed SEED
--seconds <run_seconds> --trace 0`` with the workload's ``lead_s``
replaced and ``ContinuousBatchingScheduler.step`` wrapped; it ends
before the output check, which is not what this reads. A step's row:
``[t, retired, credited, generated, prefilled, decode, prefill, live]``:
seconds since the scheduler's first step, requests retired, prompt
tokens as the runner counts them (a prompt whole in the step its first
token appears), tokens generated, prompt tokens really prefilled, slots
decoding and prefilling, and each page group's live pages.

``read`` prints, for windows opened every 10 s, the runner's rate by
seed with its median and quartile distance, the rate of tokens really
prefilled and generated, retirements a second and the groups' live
shares; then the spread over EVERY window opened a second apart.
"""
import bisect
import itertools
import json
import os
import statistics
import sys
import time


def record(cell, seed, lead_s, out_path):
    sys.path.insert(0, os.getcwd())
    from benchmark import manifest, run as bench_run
    from deepspeed_tpu.inference.scheduler import \
        ContinuousBatchingScheduler as Scheduler

    load_workload = manifest.load_workload
    manifest.load_workload = lambda name: dict(load_workload(name),
                                               lead_s=float(lead_s))
    manifest_ = manifest.load_manifest()
    config = manifest.load_config(
        manifest_, manifest.find_cell(manifest_, cell)["config"])
    family = manifest.plugin("models", config["family"])
    step, series = Scheduler.step, {}      # id(scheduler) -> its state

    def recorded_step(self):
        mine = series.setdefault(id(self), {
            "t0": time.perf_counter(), "seen": {}, "chunk": {},
            "prompt": {}, "rows": []})
        seen, chunk, prompt = mine["seen"], mine["chunk"], mine["prompt"]
        retired = step(self)
        now = time.perf_counter() - mine["t0"]
        credited = generated = prefilled = 0
        for uid in retired:
            generated += len(self.results[uid]) - seen.pop(uid, 0)
            credited += prompt.pop(uid, 0)
            chunk.pop(uid, None)
        live = [r for r in self.slots if r is not None]
        for r in live:
            if r.uid not in seen:
                seen[r.uid], chunk[r.uid] = 0, 0
                prompt[r.uid] = len(r.prompt)
            if len(r.generated) > seen[r.uid]:
                credited += prompt.pop(r.uid, 0)
                generated += len(r.generated) - seen[r.uid]
                seen[r.uid] = len(r.generated)
            if r.chunks is not None and r.chunk_idx > chunk[r.uid]:
                prefilled += sum(
                    n for _, n in r.chunks[chunk[r.uid]:r.chunk_idx])
                chunk[r.uid] = r.chunk_idx
        states = [r.state for r in live]
        stats = self.engine.page_pool_stats()
        mine["rows"].append([
            round(now, 4), len(retired), credited, generated, prefilled,
            states.count("decode"), states.count("prefill"),
            [g["pages_in_use"] for g in stats.get("groups", [stats])]])
        return retired

    def stop_before_the_check(config, seed_, engine):
        rows = max(series.values(), key=lambda s: len(s["rows"]))["rows"]
        stats = engine.page_pool_stats()
        with open(out_path, "w") as f:
            json.dump({"cell": cell, "seed": int(seed),
                       "lead_s": float(lead_s),
                       "pools": [g["num_pages"] for g in
                                 stats.get("groups", [stats])],
                       "rows": rows}, f)
        print("series: {} steps over {:.1f} s -> {}".format(
            len(rows), rows[-1][0], out_path), flush=True)
        os._exit(0)

    Scheduler.step = recorded_step
    family.serve_engine_outputs = stop_before_the_check
    return bench_run.main([
        "--workload", cell, "--seed", str(seed), "--seconds",
        str(manifest_["run_seconds"]), "--trace", "0"])


def spread(values):
    """The quartile distance over the median, in %, as the driver
    takes it."""
    q = statistics.quantiles(values, n=4)
    return 100 * (q[2] - q[0]) / statistics.median(values)


def windows(run, seconds, first=0.0, every=1.0):
    """-> [(opened at, runner's tokens/s, prefilled + generated
    tokens/s, retired/s, [live share a group])] for windows of
    ``seconds`` opened ``every`` s apart; like the runner's, a window
    runs from the end of one step to the end of the step that straddles
    its close."""
    rows, pools = run["rows"], run["pools"]
    ends = [r[0] for r in rows]
    sums = [list(itertools.accumulate(col)) for col in (
        (r[2] + r[3] for r in rows), (r[4] + r[3] for r in rows),
        (r[1] for r in rows))]
    out, at = [], first
    while at + seconds <= ends[-1]:
        a = max(0, bisect.bisect_right(ends, at) - 1)
        b = bisect.bisect_right(ends, at + seconds) - 1
        span = ends[b] - ends[a]
        live = [100 * sum(r[7][g] for r in rows[a + 1:b + 1])
                / (b - a) / pool for g, pool in enumerate(pools)]
        out.append((at,) + tuple((s[b] - s[a]) / span for s in sums)
                   + (live,))
        at += every
    return out


def read(paths, seconds=51.0, level_from=40.0):
    runs = []
    for path in paths:
        with open(path) as f:
            runs.append(json.load(f))
    by_lead = {}
    for run in runs:
        for w in windows(run, seconds, every=10.0):
            by_lead.setdefault(w[0], []).append(w)
    print("opened at | runner's tokens/s by seed | median, spread % | "
          "prefilled + generated median | retired/s | live % a group")
    for at, ws in sorted(by_lead.items()):
        if len(ws) < 2:
            continue
        rates = [w[1] for w in ws]
        groups = zip(*(w[4] for w in ws))
        print("{:5.0f} | {} | {:.0f} {:.2f} | {:.0f} | {:.2f} | {}".format(
            at, " ".join("{:.0f}".format(r) for r in rates),
            statistics.median(rates), spread(rates),
            statistics.median(w[2] for w in ws),
            statistics.mean(w[3] for w in ws),
            " ".join("{:.1f}".format(statistics.mean(g))
                     for g in groups)))
    every = [w for run in runs
             for w in windows(run, seconds, first=level_from)]
    for name, column in (("the runner's count", 1),
                         ("prefilled + generated", 2), ("retired", 3)):
        values = [w[column] for w in every]
        if len(values) < 2:
            continue
        print("{} windows of {:g} s opened from {:g} s on, {}: median "
              "{:.6g}, spread {:.2f}%, standard deviation {:.2f}%".format(
                  len(values), seconds, level_from, name,
                  statistics.median(values), spread(values),
                  100 * statistics.pstdev(values)
                  / statistics.mean(values)))


if __name__ == "__main__":
    if sys.argv[1] == "run":
        sys.exit(record(*sys.argv[2:6]))
    read(sys.argv[2:])
