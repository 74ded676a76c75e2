"""The start-up record (docs/telemetry.md, "Start-up record"): what a
process spent between ``import deepspeed_tpu`` and its first useful
step, as rows in memory. A tiny serving engine and a tiny training
engine are built and run on the CPU; the names, their nesting and the
attributes are pinned here, because the benchmark's ``setup_*`` metrics
and an operator's ``engine.startup_report()`` read each of them."""
import glob
import logging
import os

import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.runtime.executor import jit as jit_seam
from deepspeed_tpu.utils import annotate, compile_cache
from deepspeed_tpu.utils.logging import logger

PHASES = {"inference": {"setup.params", "setup.cache", "setup.kernels"},
          "train": {"setup.params", "setup.optimizer"}}
PROGRAMS = {"inference": ["decode", "prefill"], "train": ["fused_train"]}
PROGRAM_ATTRS = {"program", "key", "engine", "step", "trace_s", "lower_s",
                 "compile_s", "cache", "cache_load_s", "first_run_s"}
OTHER_ATTRS = {"programs", "compiled", "trace_s", "lower_s", "compile_s",
               "names", "engine"}


def _config():
    return gpt2.GPT2Config(vocab_size=128, max_seq_len=64, n_layers=2,
                           n_heads=2, d_model=32,
                           use_flash_attention=False, remat=False)


def _build(kind):
    model = gpt2.make_gpt2_model(config=_config(), seed=0)
    if kind == "inference":
        return deepspeed_tpu.init_inference(
            model=model, config={"inference": {
                "max_batch_size": 2, "prefill_buckets": [8, 32],
                "dtype": "fp32", "greedy": True,
                "kv_block_size": 8}})
    engine, _, _, _ = deepspeed_tpu.initialize(
        model=model, config_params={
            "train_micro_batch_size_per_gpu": 1,
            "optimizer": {"type": "Adam", "params": {"lr": 1e-3}}})
    return engine


def _run(kind, engine, long_prompt=False):
    """One useful step: a short request served, or one batch trained."""
    if kind == "inference":
        prompt = list(range(1, 21)) if long_prompt else [5, 9, 2]
        return engine.generate([prompt], max_new_tokens=2)
    ids = np.arange(8 * 16, dtype=np.int32).reshape(1, 8, 16) % 128
    return float(engine.train_batch(batch=(ids, ids)))


@pytest.fixture(scope="module", params=["inference", "train"])
def built(request):
    """(kind, engine, its rows before any step, its rows after one)."""
    kind = request.param
    _build(kind)                  # the first build pays the imports
    engine = _build(kind)
    before = engine.startup_report()["rows"]
    _run(kind, engine)
    return kind, engine, before, engine.startup_report()["rows"]


def _named(rows, name):
    return [row for row in rows if row["name"] == name]


def _seconds(row):
    return row["end_s"] - row["start_s"]


def test_the_import_is_the_first_row():
    first = annotate.setup_record()[0]
    assert first["name"] == "setup.import" and first["parent"] is None
    assert 0 < _seconds(first) and first["attrs"] == {}


def test_span_names_and_nesting(built):
    kind, engine, before, _ = built
    whole = _named(before, "setup.engine")
    assert len(whole) == 1 and whole[0]["parent"] is None
    assert whole[0]["attrs"] == {"kind": kind, "engine": engine.startup_tag}
    assert engine.startup_tag.startswith(kind + "-")
    names = {row["name"] for row in before}
    assert names - {"setup.programs.other"} == PHASES[kind] | {"setup.engine"}
    for row in before:
        assert row["attrs"]["engine"] == engine.startup_tag
        if row["name"] in PHASES[kind]:
            assert row["parent"] == "setup.engine"
            assert whole[0]["start_s"] <= row["start_s"] <= row["end_s"] \
                <= whole[0]["end_s"]
        if row["name"] == "setup.programs.other":
            assert row["parent"] in PHASES[kind] | {"setup.engine"}
            assert set(row["attrs"]) == OTHER_ATTRS
    # what is known only at a phase's end
    assert all(row["attrs"]["bytes"] > 0 for name in
               PHASES[kind] - {"setup.kernels"} for row in _named(before,
                                                                   name))
    assert all(row["attrs"]["leaves"] > 0
               for row in _named(before, "setup.params"))


def test_the_phases_cover_the_engine(built):
    _, _, before, _ = built
    whole = _seconds(_named(before, "setup.engine")[0])
    phases = sum(_seconds(row) for row in before
                 if row["parent"] == "setup.engine"
                 and row["name"] != "setup.programs.other")
    assert phases <= whole
    assert whole - phases <= max(0.05 * whole, 0.2)


def test_one_program_row_for_each_program_and_none_on_a_later_call(built):
    kind, engine, before, after = built
    assert not _named(before, "setup.program")
    rows = _named(after, "setup.program")
    programs = sorted(row["attrs"]["program"] for row in rows)
    assert programs == PROGRAMS[kind]
    assert len(set(row["attrs"]["key"] for row in rows)) == len(rows)
    for row in rows:
        attrs = row["attrs"]
        assert set(attrs) == PROGRAM_ATTRS and row["parent"] is None
        assert attrs["engine"] == engine.startup_tag
        assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0
        assert attrs["compile_s"] > 0 and attrs["first_run_s"] >= 0
        assert attrs["cache"] in ("hit", "miss", "off")
        walls = attrs["trace_s"] + attrs["lower_s"] + attrs["compile_s"] \
            + attrs["first_run_s"]
        assert walls == pytest.approx(_seconds(row), abs=1e-6)
    assert rows[0]["attrs"]["step"] == 0
    count = len(annotate.setup_record())
    _run(kind, engine)
    _run(kind, engine)
    assert len(annotate.setup_record()) == count
    # and the engine's cache now holds the jitted functions themselves
    fns = list(engine._jit_cache.values()) if kind == "train" else \
        list(engine._prefill_fns.values()) + list(engine._decode_fns.values())
    assert fns and all(type(fn).__name__ == "PjitFunction" for fn in fns)


def test_a_program_first_called_after_the_first_launch_says_which_step():
    """A second prefill bucket adds exactly one row; it carries the
    launches before it and is logged once at WARNING."""
    kind, engine = "inference", _build("inference")
    _run(kind, engine)
    after = engine.startup_report()["rows"]
    messages = []
    handler = logging.Handler()
    handler.emit = lambda record: messages.append(
        (record.levelno, record.getMessage()))
    logger.addHandler(handler)
    try:
        _run(kind, engine, long_prompt=True)
        _run(kind, engine, long_prompt=True)
    finally:
        logger.removeHandler(handler)
    rows = _named(engine.startup_report()["rows"], "setup.program")
    assert len(rows) == len(_named(after, "setup.program")) + 1
    new = rows[-1]["attrs"]
    assert new["program"] == "prefill" and new["key"] == "32/True/0"
    assert new["step"] > 0
    warned = [m for level, m in messages if level == logging.WARNING
              and "first call AFTER" in m]
    assert len(warned) == 1 and "key=32/True/0" in warned[0]
    assert "step={}".format(new["step"]) in warned[0]


def test_startup_report_and_line(built):
    kind, engine, _, _ = built
    report = engine.startup_report()
    assert report["engine"] == engine.startup_tag
    assert report["seconds"]["setup.engine"] > 0
    assert set(report["seconds"]) >= PHASES[kind]
    line = engine.startup_line()
    assert line.startswith("start-up engine={}: setup.engine".format(
        engine.startup_tag))
    assert all(name in line for name in PHASES[kind])
    # another engine's rows are not this one's
    other = _build(kind)
    assert other.startup_tag != engine.startup_tag
    assert not {id(r) for r in other.startup_report()["rows"]} & \
        {id(r) for r in report["rows"]}
    assert len(_named(other.startup_report()["rows"], "setup.engine")) == 1


@pytest.fixture
def fresh_record(monkeypatch):
    """A record and a thread's open rows of the test's own, as a new
    process has them. What an xdist worker ran before this file decides
    both otherwise: the bounded record may be full (a full record takes
    no row), and a program that an earlier test made and never called
    leaves its row open on the thread, where it takes the events meant
    for the ``setup.programs.other`` row and the cache's loads."""
    import threading
    monkeypatch.setattr(annotate, "_setup_rows", [])
    monkeypatch.setattr(compile_cache, "_open", threading.local())


def test_an_event_outside_every_row_lands_in_the_other_row(fresh_record):
    def a_small_program_of_setup(x):
        return x * 3 + 1

    x = jnp.ones((3,))          # (made by small programs of its own)
    before = sum(row["attrs"]["programs"] for row in _named(
        annotate.setup_record(), "setup.programs.other"))
    rows_before = len(_named(annotate.setup_record(), "setup.program"))
    jax.jit(a_small_program_of_setup)(x)
    others = _named(annotate.setup_record(), "setup.programs.other")
    assert sum(row["attrs"]["programs"] for row in others) == before + 1
    last = others[-1]
    assert last["parent"] is None and "engine" not in last["attrs"]
    events, seconds = last["attrs"]["names"]["a_small_program_of_setup"]
    assert events == 3 and seconds > 0          # trace, lower, compile
    assert last["attrs"]["trace_s"] + last["attrs"]["lower_s"] + \
        last["attrs"]["compile_s"] >= seconds
    assert len(_named(annotate.setup_record(), "setup.program")) == \
        rows_before


def test_nested_events_count_once():
    """A traced function that calls jitted ones reports them inside its
    own event: the seconds of a kind are the outermost events'."""
    book = compile_cache._Book()
    book.add("trace_s", 1.0, 2.0, "inner")       # ends first
    book.add("trace_s", 2.5, 3.0, "inner")
    book.add("trace_s", 0.5, 4.0, "outer")       # encloses both
    book.add("trace_s", 5.0, 6.0, "next")        # a sibling of outer
    assert book.seconds["trace_s"] == pytest.approx(4.5)
    assert book.names["inner"] == [2, pytest.approx(0.0)]
    assert book.names["outer"] == [1, pytest.approx(3.5)]
    compile_cache._on_event("/jax/compilation_cache/cache_hits")  # stale
    compile_cache._open.hits = compile_cache._open.asked = 0
    book.add("compile_s", 6.0, 7.0, "next")
    assert book.compiles == 1 and book.cache() == "off"
    # across kinds too: a lowering that traced a helper on its way
    book.add("trace_s", 8.0, 8.5, "helper")
    book.add("lower_s", 7.5, 9.0, "next")
    assert book.seconds == {"trace_s": pytest.approx(4.5),
                            "lower_s": pytest.approx(1.5),
                            "compile_s": pytest.approx(1.0)}
    assert sum(book.seconds.values()) == pytest.approx(7.0)
    book.asked += 1
    assert book.cache() == "miss" and book.compiled() == 1
    book.hits += 1
    assert book.cache() == "hit" and book.compiled() == 0
    assert compile_cache._Book().cache() is None


def test_the_record_is_bounded(monkeypatch):
    monkeypatch.setattr(annotate, "_setup_rows", [])
    for i in range(annotate.SETUP_ROWS_MAX + 76):
        annotate.record_setup_row(annotate.new_setup_row(
            "setup.import", float(i), float(i) + 0.5))
    rows = annotate.setup_record()
    assert len(rows) == annotate.SETUP_ROWS_MAX == 1024
    assert all(row["name"] == "setup.import" for row in rows[:-1])
    assert rows[-1]["name"] == "setup.dropped"
    assert rows[-1]["attrs"] == {"rows": 77}
    assert rows[-1]["end_s"] == annotate.SETUP_ROWS_MAX + 75 + 0.5


def test_the_listener_is_registered_once_however_many_engines():
    from jax._src import monitoring
    _build("inference")
    _build("inference")
    compile_cache.listen()
    assert [fn for fn in monitoring.get_event_duration_listeners()
            if fn is compile_cache._on_duration] == \
        [compile_cache._on_duration]
    assert [fn for fn in monitoring.get_event_listeners()
            if fn is compile_cache._on_event] == [compile_cache._on_event]


def test_jit_program_returns_the_jitted_function_and_nothing_wraps_it(
        fresh_record):
    """The engine's cache holds what ``jit_program`` returned from the
    start; ``first_call`` opens the program's row, ``first_call_over``
    closes it, and the call between them is the caller's own."""
    def toy(x, y):
        return x + y, y

    fn = jit_seam.jit_program(toy, donate=(0,))
    assert type(fn).__name__ == "PjitFunction" and callable(fn.lower)
    x, y = jnp.ones(2), jnp.ones(2)
    programs = len(_named(annotate.setup_record(), "setup.program"))
    opened = jit_seam.first_call(fn, "toy", (2, "k"), "test-0", 7)
    assert len(_named(annotate.setup_record(), "setup.program")) == programs
    out = jax.block_until_ready(fn(x, y))
    jit_seam.first_call_over(opened)
    rows = _named(annotate.setup_record(), "setup.program")
    assert len(rows) == programs + 1 and float(out[0][0]) == 2.0
    attrs = rows[-1]["attrs"]
    assert set(attrs) == PROGRAM_ATTRS
    assert (attrs["program"], attrs["key"], attrs["step"]) == ("toy", "2/k", 7)
    assert attrs["trace_s"] > 0 and attrs["lower_s"] > 0
    assert attrs["compile_s"] > 0 and attrs["cache"] in ("hit", "miss", "off")
    assert attrs["trace_s"] + attrs["lower_s"] + attrs["compile_s"] + \
        attrs["first_run_s"] == pytest.approx(_seconds(rows[-1]), abs=1e-6)
    # once closed, the thread's events are the small programs' again
    assert compile_cache._open_books() == []


def test_two_rows_open_at_once_each_take_their_own_events():
    """A page copy's program is made, and run, between the decode
    program's making and its first call: both rows are open, and each
    takes the events under its own function's name."""
    def outer_program(x):
        return x * 2

    def inner_program(x):
        return x + 3

    outer = jit_seam.jit_program(outer_program)
    inner = jit_seam.jit_program(inner_program)
    x = jnp.ones(4)
    first = jit_seam.first_call(outer, "decode", "o", "test-0", 0)
    second = jit_seam.first_call(inner, "page_copy", "i", "test-0", 0)
    jax.block_until_ready(inner(x))
    jax.block_until_ready(outer(x))
    jit_seam.first_call_over(second)
    jit_seam.first_call_over(first)
    rows = _named(annotate.setup_record(), "setup.program")[-2:]
    assert [r["attrs"]["program"] for r in rows] == ["page_copy", "decode"]
    assert all(r["attrs"]["trace_s"] > 0 and r["attrs"]["compile_s"] > 0
               for r in rows)


@pytest.fixture
def persistent_cache(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache
    keys = ("jax_compilation_cache_dir", "jax_enable_compilation_cache",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {key: getattr(jax.config, key) for key in keys}
    for key, value in zip(keys, (str(tmp_path / "cache"), True, 0, 0)):
        jax.config.update(key, value)
    compilation_cache.reset_cache()
    yield
    for key, value in saved.items():
        jax.config.update(key, value)
    compilation_cache.reset_cache()


def test_the_row_says_whether_the_cache_had_the_program(persistent_cache,
                                                        fresh_record):
    """With a persistent cache that keeps every program, a first start
    compiles (``cache: miss``) and a second one, which has nothing in
    memory, loads (``hit``): ``compile_s`` is then the load."""
    def start():
        jax.clear_caches()            # a new process has no executable
        engine = _build("inference")
        _run("inference", engine)
        rows = engine.startup_report()["rows"]
        return ({row["attrs"]["program"]: row["attrs"]
                 for row in _named(rows, "setup.program")},
                sum(row["attrs"]["compiled"]
                    for row in _named(rows, "setup.programs.other")))

    cold, cold_small = start()
    warm, warm_small = start()
    assert set(cold) == set(warm) == {"prefill", "decode"}
    for program in cold:
        assert cold[program]["cache"] == "miss"
        assert cold[program]["cache_load_s"] == 0.0
        assert warm[program]["cache"] == "hit"
        assert 0 < warm[program]["cache_load_s"] <= \
            warm[program]["compile_s"]
        # tracing and lowering are paid again: no cache covers them
        assert warm[program]["trace_s"] > 0 and warm[program]["lower_s"] > 0
    assert warm_small == 0 <= cold_small


def test_a_session_opened_before_the_engine_shows_the_setup_spans(
        tmp_path):
    """The same boundaries as ``TraceAnnotation``s: an operator's trace
    started before ``init_inference()`` holds them (the serving step's
    session, opened after warm-up, holds none:
    test_program_spans.py::test_no_other_program_span_names)."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        engine = _build("inference")
        _run("inference", engine)
    finally:
        jax.profiler.stop_trace()
    path = glob.glob(os.path.join(str(tmp_path), "**", "*.xplane.pb"),
                     recursive=True)[-1]
    found = {}
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("setup."):
                    found.setdefault(ev.name, []).append(dict(ev.stats))
    assert set(found) == {"setup.engine", "setup.params", "setup.cache",
                          "setup.kernels", "setup.program"}
    assert found["setup.engine"][0] == {"kind": "inference",
                                        "engine": engine.startup_tag}
    assert sorted(s["program"] for s in found["setup.program"]) == \
        ["decode", "prefill"]
