"""Device scopes (docs/telemetry.md, "Device scopes"): which
``jax.named_scope`` every instruction of a compiled program was traced
under, read back from the program's text.

``analysis/hlo.py::instruction_scopes`` on a committed text that the
chip's compiler wrote; ``engine.program_scopes()`` on the tiny engine
of each of the seven serving families and on the tiny training engine:
one entry a program that ran, every name of the family's vocabulary
there, nothing of the start-up record or of the compile counts moved;
and a run that never asks for the map lowers and compiles nothing after
its programs' first calls."""
import gc
import importlib
import os
import re

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from deepspeed_tpu.analysis import hlo
from deepspeed_tpu.inference.kv_cache import read_scope
from deepspeed_tpu.runtime.executor import jit as jit_seam
from deepspeed_tpu.utils import annotate, compile_cache

HERE = os.path.dirname(os.path.abspath(__file__))
PACKAGE = os.path.join(os.path.dirname(os.path.dirname(HERE)),
                       "deepspeed_tpu")
_LOWER_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"
_lowered = []        # fun_name of every module this process lowered


def _on_lower(event, duration, fun_name=None, **_):
    if event == _LOWER_EVENT:
        _lowered.append(fun_name)


@pytest.fixture(scope="module")
def lowered_programs():
    """The names of the modules this process has lowered, in order (one
    listener a process: jax has no call that removes one)."""
    jax.monitoring.register_event_duration_secs_listener(_on_lower)
    return _lowered


def _scopes(entry):
    """The vocabulary's names among an entry's ``op_name`` components."""
    found = set()
    for row in entry["instructions"].values():
        path = re.sub(r"[A-Za-z_][\w.]*\(|\)", "", row[0])
        found.update(set(path.split("/")).intersection(
            annotate.DEVICE_SCOPES))
    return found


# ------------------------------------------------------ the text's parse
def test_instruction_scopes_on_a_text_the_chips_compiler_wrote():
    with open(os.path.join(HERE, "fixtures_hlo", "scoped_module.txt")) as f:
        text = f.read()
    module, table, computations = hlo.instruction_table(text)
    scopes = hlo.instruction_scopes(text)
    assert module == "jit_f" and set(scopes) == set(table)
    # a forward matmul's fusion and its backward, under the same scope
    assert scopes["convolution_tanh_fusion"] == \
        "jit(f)/jvp(attn.proj)/dot_general"
    assert scopes["fusion.15"] == \
        "jit(f)/transpose(jvp(attn.proj))/dot_general"
    # ... whose members lie under TWO scopes: the fusion has one name
    assert table["fusion.15"][2:] == ("fusion", "fused_computation.17")
    members = {scopes[name] for name in computations["fused_computation.17"]}
    assert {"jit(f)/transpose(jvp(attn.proj))/dot_general",
            "jit(f)/transpose(jvp(mlp))/dot_general"} <= members
    # a while and an instruction of its body; a tuple's shape whole,
    # layouts and tilings taken off
    assert table["while.1"][:3] == (
        "jit(f)/transpose(jvp())/while",
        "(s32[], bf16[256,512], s32[], s32[])", "while")
    assert scopes["multiply_add_fusion.9"].endswith(
        "while/body/closed_call/head.loss/add_any")
    assert "multiply_add_fusion.9" in \
        computations["wide.region_2.5.clone.sunk"]
    assert table["copy-start"][1] == \
        "(bf16[512,512], bf16[512,512], u32[])"
    # a Mosaic kernel's call, named by its pallas_call
    assert table["paged_attention.3"] == (
        "jit(f)/attn.decode/jit(_walk)/paged_attention/pallas_call",
        "bf16[256,512]",
        "custom-call", None)
    # what XLA made itself has no name
    assert scopes["copy-done"] == "" and scopes["tuple.15"] == ""
    assert table["x.1"][2] == "parameter"


def test_the_vocabulary_holds_every_scope_the_package_opens():
    literal = re.compile(r'"([a-z_]+(?:\.[a-z_]+)*)"')
    opened = set()
    for folder, _, names in os.walk(PACKAGE):
        for name in names:
            if not name.endswith(".py"):
                continue
            with open(os.path.join(folder, name)) as f:
                for line in f:
                    if "jax.named_scope(" in line:
                        opened.update(literal.findall(
                            line.split("jax.named_scope(", 1)[1]))
    opened.update({read_scope(1, 16), read_scope(16, 16)})
    assert read_scope(15, 16) == "attn.decode" and \
        read_scope(512, 16) == "attn.prefill"
    assert opened == set(annotate.DEVICE_SCOPES)
    assert len(set(annotate.DEVICE_SCOPES)) == len(annotate.DEVICE_SCOPES)


# ---------------------------------------------------- the seven families
def _gpt2():
    return importlib.import_module("test_setup_record")._build("inference")


def _family(module):
    return lambda: importlib.import_module(module)._engine(slots=2,
                                                           buckets=(8,))


# family -> (engine, what every program has, the prefill program's
# own, the decode program's own)
FAMILIES = {
    "gpt2": (_gpt2, {"embed", "attn.proj", "kv.write", "mlp", "head",
                     "sample"}, {"attn.prefill"}, {"attn.decode"}),
    "jamba": (_family("test_jamba"),
              {"embed", "mamba.proj", "attn.proj", "kv.write", "mlp",
               "head", "sample"},
              {"attn.prefill", "mamba.scan"}, {"attn.decode", "mamba.step"}),
    "lfm2": (_family("test_lfm2"),
             {"short_conv", "moe.route", "moe.dispatch", "moe.combine",
              "kv.write", "head", "sample"},
             {"attn.prefill"}, {"attn.decode"}),
    "deepseek_v3": (_family("test_deepseek_v3"),
                    {"mla.project", "moe.shared", "moe.route",
                     "moe.dispatch", "moe.combine", "kv.write", "head",
                     "sample"},
                    {"mla.kv_up", "mla.prefill_attn"}, {"mla.absorb"}),
    "mellum": (_family("test_mellum"),
               {"attn.window", "attn.full", "moe.route", "moe.dispatch",
                "moe.combine", "kv.write", "head", "sample"},
               {"attn.chunk_blocks"}, set()),
    "cohere2_moe": (_family("test_cohere2_moe"),
                    {"attn.window", "attn.full", "moe.shared", "moe.route",
                     "moe.dispatch", "moe.combine", "kv.write", "head",
                     "sample"},
                    {"attn.chunk_blocks"}, set()),
    "olmo_hybrid": (_family("test_olmo_hybrid"),
                    {"gdn.proj", "gdn.conv", "gdn.norm", "attn.full", "mlp",
                     "kv.write", "head", "sample"},
                    {"gdn.chunk", "attn.chunk_blocks"}, {"gdn.step"}),
}


@pytest.fixture(scope="module", params=sorted(FAMILIES))
def served(request, lowered_programs, compiled_programs):
    """(family, engine, its map, what the asking moved) after one short
    request served: the prompt fills a page, so its chunk writes pages
    and reads as a chunk does."""
    build = FAMILIES[request.param][0]
    with jax.default_matmul_precision("highest"):
        engine = build()
        engine.generate([[5, 9, 2, 7, 1, 3]], max_new_tokens=3)
        before = (len(annotate.setup_record()), dict(engine.compile_stats),
                  engine.launches, len(lowered_programs),
                  len(compiled_programs))
        # a second request of the same shapes: the map was never asked
        # for, and nothing of this PR runs after the first calls
        engine.generate([[8, 6, 4, 2, 9, 1]], max_new_tokens=3)
        idle = (len(lowered_programs) - before[3],
                len(compiled_programs) - before[4])
        before = before[:2] + (engine.launches,)
        entries = engine.program_scopes()
        after = (len(annotate.setup_record()), dict(engine.compile_stats),
                 engine.launches)
    return request.param, engine, entries, (before, after, idle)


def test_one_entry_a_program_that_ran_under_the_trace_contracts_names(served):
    family, engine, entries, _ = served
    assert sorted((e["program"], e["module"]) for e in entries) == \
        [("decode", "jit_decode"), ("prefill", "jit_prefill")]
    for entry in entries:
        assert entry["engine"] == engine.startup_tag
        assert entry["retraced"] is False and "error" not in entry
        assert entry["seconds"] > 0 and len(entry["instructions"]) > 50
        assert all(isinstance(row[0], str) and re.match(
            r"^(\(|[a-z]+[0-9]*\[)", row[1])
            for row in entry["instructions"].values())
    rows = [r["attrs"] for r in engine.startup_report()["rows"]
            if r["name"] == "setup.program"]
    assert sorted((r["program"], r["key"]) for r in rows) == \
        sorted((e["program"], e["key"]) for e in entries)


def test_every_name_of_the_familys_vocabulary_is_there(served):
    family, _, entries, _ = served
    _, everywhere, prefill, decode = FAMILIES[family]
    by_program = {e["program"]: _scopes(e) for e in entries}
    assert everywhere <= by_program["prefill"], \
        everywhere - by_program["prefill"]
    assert everywhere <= by_program["decode"], \
        everywhere - by_program["decode"]
    assert prefill <= by_program["prefill"] and \
        decode <= by_program["decode"]
    # a chunk's read and a step's are told apart, as the write tells them
    assert "attn.decode" not in by_program["prefill"]
    assert "attn.prefill" not in by_program["decode"]
    # a fusion's row carries its members' names
    fusions = [row for e in entries for row in e["instructions"].values()
               if len(row) > 2]
    assert fusions and all(isinstance(row[2], list) for row in fusions)


def test_asking_moves_nothing_and_not_asking_lowers_nothing(served):
    _, engine, _, (before, after, idle) = served
    # no setup.program and no setup.programs.other row, no trace counted,
    # no launch
    assert before == after
    assert idle == (0, 0)
    # twice gives the same map
    again = engine.program_scopes()
    assert [(e["program"], e["key"], e["retraced"]) for e in again] == \
        [(e["program"], e["key"], e["retraced"])
         for e in served[2]]
    assert len(annotate.setup_record()) == after[0]


def test_asking_inside_a_step_is_an_error():
    engine = _gpt2()
    engine.generate([[5, 9, 2]], max_new_tokens=2)
    # a program made and not called yet: its first call is not over
    engine._get_prefill_fn(32, True, 0)
    try:
        with pytest.raises(RuntimeError, match="inside a step"):
            engine.program_scopes()
        with pytest.raises(RuntimeError, match="inside a step"):
            compile_cache.program_scopes()
    finally:
        stats = dict(engine.compile_stats)
        engine._first_calls_over(discard=True)
        engine._prefill_fns.pop((32, True, 0))
        stats["prefill_traces"] -= 1
        engine.compile_stats = stats
    assert len(engine.program_scopes()) == 2


def test_the_map_outlives_the_engine_that_ran_the_programs():
    """The benchmark's runners drop their engine before any reader
    runs: the process-wide call still has the programs."""
    engine = _gpt2()
    engine.generate([[5, 9, 2]], max_new_tokens=2)
    tag = engine.startup_tag
    del engine
    gc.collect()
    # another engine's program, made and never called (tests leave such
    # rows open on a worker's thread), is not this engine's step
    other = jit_seam.first_call(jit_seam.jit_program(lambda x: x), "toy",
                                "leaked", "someone-else-0", 0)
    try:
        entries = compile_cache.program_scopes(tag)
    finally:
        jit_seam.first_call_over(other, discard=True)
    assert sorted(e["module"] for e in entries) == \
        ["jit_decode", "jit_prefill"]
    assert all(e["retraced"] is False and "error" not in e
               for e in entries)
    # of every engine's, where none is asked for by its tag
    assert tag in {key[0] for key in compile_cache._kept}


# ------------------------------------------------------------- training
@pytest.fixture(scope="module")
def trained(lowered_programs, compiled_programs):
    helper = importlib.import_module("test_setup_record")
    engine = helper._build("train")
    helper._run("train", engine)
    before = (len(annotate.setup_record()), len(lowered_programs),
              len(compiled_programs))
    helper._run("train", engine)
    idle = (len(lowered_programs) - before[1],
            len(compiled_programs) - before[2])
    return engine, before[0], idle


def test_the_training_engines_step_program_and_its_backward(trained):
    engine, rows, idle = trained
    assert idle == (0, 0)
    entry, = engine.program_scopes()
    assert (entry["program"], entry["module"], entry["retraced"]) == \
        ("fused_train", "jit_fused", False)
    assert {"embed", "attn.proj", "mlp", "head.loss",
            "optim.step"} <= _scopes(entry)
    names = [row[0] for row in entry["instructions"].values()]
    assert any("transpose(jvp(mlp))" in name for name in names)
    assert any("jvp(head.loss)" in name for name in names)
    assert len(annotate.setup_record()) == rows
    engine._pending_backward = True
    try:
        with pytest.raises(RuntimeError, match="inside a step"):
            engine.program_scopes()
    finally:
        engine._pending_backward = False


def test_a_closed_engine_keeps_what_its_programs_traced_to(trained):
    """``close()`` lets the step function go (it closes over the engine
    and its state) and keeps what it traced to: the map can still be
    asked for, as the benchmark does after its runner dropped the
    engine."""
    helper = importlib.import_module("test_setup_record")
    engine = helper._build("train")
    helper._run("train", engine)
    tag = engine.startup_tag
    kept, = [k for key, k in compile_cache._kept.items() if key[0] == tag]
    assert kept.fn is not None and kept.traced is None
    engine.close()
    assert kept.fn is None and kept.traced is not None
    del engine
    gc.collect()
    entry, = compile_cache.program_scopes(tag)
    assert entry["module"] == "jit_fused" and entry["retraced"] is False
    assert "optim.step" in _scopes(entry)


# ------------------------------------------------------- what is kept
def test_programs_are_kept_for_the_newest_engines_only():
    def toy(x, y):
        with jax.named_scope("mlp"):
            return x @ y, y

    x = jnp.ones((4, 4))
    for n in range(compile_cache.PROGRAM_ENGINES_KEPT + 2):
        fn = jit_seam.jit_program(toy)
        opened = jit_seam.first_call(fn, "toy", n, "kept-%d" % n, 0)
        out = jax.block_until_ready(fn(x, x))
        jit_seam.first_call_over(opened, operands=(x, x))
    tags = list(dict.fromkeys(key[0] for key in compile_cache._kept))
    assert len(tags) == compile_cache.PROGRAM_ENGINES_KEPT
    assert tags[-1] == "kept-%d" % n and "kept-0" not in tags
    entry, = compile_cache.program_scopes("kept-%d" % n)
    assert entry["module"] == "jit_toy" and _scopes(entry) == {"mlp"}
    assert float(out[0][0, 0]) == 4.0
    # a program made to be looked at, or one whose operands nobody
    # handed over, is not kept
    opened = jit_seam.first_call(fn, "toy", "audit", "kept-x", 0)
    jit_seam.first_call_over(opened, discard=True, operands=(x, x))
    opened = jit_seam.first_call(fn, "toy", "blind", "kept-x", 0)
    jit_seam.first_call_over(opened)
    assert compile_cache.program_scopes("kept-x") == []


def test_a_second_lowering_that_misses_the_trace_cache_says_so():
    def toy(x):
        with jax.named_scope("mlp"):
            return jnp.tanh(x) @ x

    fn = jit_seam.jit_program(toy)
    x = jnp.ones((4, 4))
    opened = jit_seam.first_call(fn, "toy", "a", "retraced-0", 0)
    jax.block_until_ready(fn(x))
    # operands of another shape than the call's: the body runs again
    jit_seam.first_call_over(opened, operands=(np.ones((8, 8), np.float32),))
    entry, = compile_cache.program_scopes("retraced-0")
    assert entry["retraced"] is True and entry["module"] == "jit_toy"
