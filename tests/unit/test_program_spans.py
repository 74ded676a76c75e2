"""The serving step's program spans (utils.annotate): a tiny
paged engine and its scheduler run inside ONE jax.profiler session, and
the trace it leaves holds every span of the contract (docs/telemetry.md,
"Program spans") on a host plane, nested as drawn, with the attributes
a reader needs. The names are pinned here: a benchmark metric reads
each of them."""
import glob
import os
import sys

import pytest

import jax

import deepspeed_tpu as deepspeed
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.telemetry import spans as spans_mod
from deepspeed_tpu.utils.monitor import ServingMetrics

pytestmark = pytest.mark.inference

# name -> the span that encloses it (docs/telemetry.md)
CONTRACT = {
    "sched.step": None,
    "sched.plan": "sched.step",
    "sched.admit": "sched.step",
    "sched.admit.request": "sched.admit",
    "sched.prefill": "sched.step",
    "sched.prefill.chunk": "sched.prefill",
    "engine.prefill.prepare": "sched.prefill.chunk",
    "engine.prefill.dispatch": "sched.prefill.chunk",
    "engine.prefill.fetch": "sched.prefill.chunk",
    "sched.prefill.commit": "sched.prefill.chunk",
    "sched.decode": "sched.step",
    "sched.decode.pages": "sched.decode",
    "engine.decode.prepare": "sched.decode",
    "engine.decode.dispatch": "sched.decode",
    "engine.decode.fetch": "sched.decode",
    "sched.decode.commit": "sched.decode",
    "sched.retire": "sched.step",
    "timer.sync": None,          # under a chunk or under sched.decode
}
PROMPTS = [[5, 9, 2, 7, 1, 3, 8], list(range(1, 21)), [4, 4, 6]]


def _serve(engine):
    metrics = ServingMetrics()
    sched = ContinuousBatchingScheduler(engine, metrics=metrics)
    uids = [sched.submit(p, max_new_tokens=4, eos_token_id=None)
            for p in PROMPTS]
    results = sched.run()
    return [results[u] for u in uids], metrics, sched


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """(tokens with no session, tokens inside the session, the
    session's metrics, its program spans as (name, start, end, attrs)
    in start order per line, files left behind by the untraced run)."""
    cfg = gpt2.GPT2Config(vocab_size=128, max_seq_len=64, n_layers=2,
                          n_heads=2, d_model=32,
                          use_flash_attention=False, remat=False)
    engine = deepspeed.init_inference(
        model=gpt2.make_gpt2_model(config=cfg, seed=0),
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [8, 16, 32],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": 8, "prefill_chunk_tokens": 16}})
    cwd = tmp_path_factory.mktemp("untraced_cwd")
    here = os.getcwd()
    os.chdir(cwd)
    try:
        plain, _, _ = _serve(engine)       # also compiles every program
    finally:
        os.chdir(here)
    left_behind = os.listdir(cwd)
    trace_dir = str(tmp_path_factory.mktemp("program_spans_trace"))
    jax.profiler.start_trace(trace_dir)
    try:
        inside, metrics, sched = _serve(engine)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                     recursive=True)[-1]
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            found = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                      dict(ev.stats)) for ev in line.events
                     if ev.name.startswith(("sched.", "engine.",
                                            "timer.", "setup."))]
            if found:
                lines.append(sorted(found, key=lambda e: (e[1], -e[2])))
    return plain, inside, metrics, lines, left_behind, sched


def _parents(line):
    """[(event, enclosing event or None)] by the nesting of one line."""
    out, stack = [], []
    for ev in line:
        while stack and stack[-1][2] <= ev[1]:
            stack.pop()
        out.append((ev, stack[-1] if stack else None))
        stack.append(ev)
    return out


def test_annotations_lie_on_one_host_line(traced):
    lines = traced[3]
    assert len(lines) == 1          # the scheduler's thread, and no other


@pytest.mark.parametrize("name", sorted(CONTRACT))
def test_span_is_present_and_nested_as_drawn(traced, name):
    pairs = [(ev, parent) for ev, parent in _parents(traced[3][0])
             if ev[0] == name]
    assert pairs, "no {!r} event in the trace".format(name)
    for ev, parent in pairs:
        if name == "timer.sync":
            assert parent[0] in ("sched.prefill.chunk", "sched.decode")
        elif CONTRACT[name] is None:
            assert parent is None
        else:
            assert parent[0] == CONTRACT[name]
            # inside its parent; the next sibling starts after it ends
            # (`_parents` would have made an overlapping one its child)
            assert parent[1] <= ev[1] and ev[2] <= parent[2]


def test_no_other_program_span_names(traced):
    """The serving step's names and no other: the ``setup.`` spans of
    the start-up record are admitted only in a session that opens
    before the engine is built (test_setup_record.py), and this one
    opened after every program had run once."""
    assert {ev[0] for ev in traced[3][0]} == set(CONTRACT)


def test_step_and_request_attributes(traced):
    _, _, metrics, lines, _, sched = traced
    steps = [ev for ev in lines[0] if ev[0] == "sched.step"]
    assert [ev[3]["step"] for ev in steps] == list(range(len(steps)))
    assert len(steps) == sched.steps
    admitted = [ev[3] for ev in lines[0] if ev[0] == "sched.admit.request"]
    assert sorted(a["uid"] for a in admitted) == [0, 1, 2]
    # the counter received the same subtraction the span carries
    assert [a["queue_wait_us"] for a in admitted] == \
        [int(w * 1e6) for w in metrics.queue_waits]
    assert all(a["queue_wait_us"] >= 0 and a["resumed"] == 0
               for a in admitted)
    assert metrics.snapshot()["queue_wait"]["count"] == 3
    chunks = [ev[3] for ev in lines[0] if ev[0] == "sched.prefill.chunk"]
    # the 20-token prompt goes in two chunks of its own uid
    assert sorted((c["uid"], c["tokens"]) for c in chunks) == \
        [(0, 7), (1, 4), (1, 16), (2, 3)]
    # and nothing is written that nothing reads
    assert {k for ev in lines[0] for k in ev[3]} == {
        "step", "uid", "queue_wait_us", "resumed", "tokens", "padded",
        "first", "start", "kv_write"}
    # where each chunk begins (`chunk_attention_roofline.*` prices a
    # chunk's attention by it)
    assert sorted((c["uid"], c["start"], c["tokens"]) for c in chunks) == \
        [(0, 0, 7), (1, 0, 16), (1, 16, 4), (2, 0, 3)]
    # the bucket each chunk was padded to, and whose first chunk it was
    # (the one whose program starts a recurrent state from zeros)
    buckets = traced[5].engine.prefill_buckets
    assert all(c["padded"] == min(b for b in buckets if b >= c["tokens"])
               for c in chunks)
    assert sorted((c["uid"], c["first"]) for c in chunks) == \
        [(0, 1), (1, 0), (1, 1), (2, 1)]


def test_dispatch_spans_say_how_the_program_writes_the_pool(traced):
    """``kv_write``: a prefill chunk (a bucket of a page or more) moves
    whole pages, a decode launch single rows
    (``kv_cache.write_tokens``), each on every launch."""
    by_name = {name: [ev[3].get("kv_write") for ev in traced[3][0]
                      if ev[0] == name]
               for name in ("engine.prefill.dispatch",
                            "engine.decode.dispatch")}
    assert len(by_name["engine.prefill.dispatch"]) == 4
    assert set(by_name["engine.prefill.dispatch"]) == {"pages"}
    assert by_name["engine.decode.dispatch"] and \
        set(by_name["engine.decode.dispatch"]) == {"rows"}


def test_serving_program_names_are_pinned(traced):
    """A jitted program is named after its function in the profiler's
    trace (`jit_decode`, and the Mosaic call inside it `%decode.N`,
    which is how the benchmark finds the paged kernel)."""
    engine = traced[5].engine
    greedy, top_k, _, _ = engine._sampling_key(None)
    assert engine._get_decode_fn(greedy, top_k).__name__ == "decode"
    assert engine._get_prefill_fn(8, greedy, top_k).__name__ == "prefill"


def test_tokens_are_the_same_with_and_without_a_session(traced):
    plain, inside = traced[0], traced[1]
    assert plain == inside and all(len(t) == 4 for t in plain)


def test_no_session_leaves_nothing_behind_and_imports_nothing(traced):
    assert traced[4] == []
    before = set(sys.modules)
    with spans_mod.annotate("sched.step", step=0):
        pass
    assert set(sys.modules) == before


def test_the_timers_reach_annotate_without_the_telemetry_package():
    """utils must not import upward: `annotate` lives in a leaf that
    imports nothing of the package, and telemetry.spans re-exports it."""
    import ast
    from deepspeed_tpu.utils import annotate as leaf, timer

    def imported(module):
        with open(module.__file__) as f:
            tree = ast.parse(f.read())
        return {(node.level, node.module) for node in ast.walk(tree)
                if isinstance(node, ast.ImportFrom)} | {
            (0, alias.name) for node in ast.walk(tree)
            if isinstance(node, ast.Import) for alias in node.names}

    assert imported(leaf) == {
        (0, "contextlib"), (0, "jax.profiler"),
        # the start-up record's rows: a lock, a clock, a copy for readers
        (0, "copy"), (0, "threading"), (0, "time")}
    assert all(level < 2 for level, _ in imported(timer))
    assert spans_mod.annotate is leaf.annotate
