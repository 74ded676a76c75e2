"""``benchmark/layer_metrics/scope_busy_share.py`` on a synthetic
trace and map: the device's busy time parts into scoped + unscoped +
unmapped, two programs of one module name are told apart by shape, the
wrappers of transforms come off, and nothing is read past 2% unmapped
or from a program that hands out no map."""
import json

import pytest

from benchmark import manifest, trace
from benchmark.layer_metrics import scope_busy_share

MANIFEST = manifest.load_manifest()
VOCABULARY = ("mlp", "attn.proj", "attn.prefill", "kv.write", "head.loss",
              "gdn.chunk")
PLANE = "/device:TPU:0"


def _event(name, shape, kind, start, end, more=""):
    text = "%{} = {}{{1,0:T(8,128)(2,1)}} {}(%p.1){}".format(
        name, shape, kind, more)
    return (text, text, start, end)


def _entry(program, key, module, instructions):
    return {"engine": "inference-1", "program": program, "key": key,
            "module": module, "retraced": False, "seconds": 0.25,
            "instructions": instructions}


# two prefill buckets share the module name and the instruction names;
# their shapes differ
PREFILL_8 = _entry("prefill", "8/True/0", "jit_prefill", {
    "fusion.1": ["jit(prefill)/attn.proj/dot_general", "bf16[8,96]",
                 ["jit(prefill)/attn.proj/dot_general",
                  "jit(prefill)/attn.proj/add"]],
    "fusion.2": ["jit(prefill)/attn.prefill/reduce_max", "f32[8,64]",
                 ["jit(prefill)/attn.prefill/reduce_max",
                  "jit(prefill)/mul"]],
    "kv_page_write.3": ["jit(prefill)/kv.write/jit(write)/kv_page_write/"
                        "pallas_call",
                        "(bf16[17,2,8,32], bf16[17,2,8,32])"],
    "copy.4": ["", "bf16[8,32]"]})
PREFILL_32 = _entry("prefill", "32/True/0", "jit_prefill", {
    "fusion.1": ["jit(prefill)/attn.proj/dot_general", "bf16[32,96]", []],
    "fusion.2": ["jit(prefill)/mlp/dot_general", "bf16[32,128]", []]})
TRAIN = _entry("fused_train", "fused_train", "jit_fused", {
    "while.9": ["jit(fused)/while/body/jvp(head.loss)/while",
                "(s32[], f32[20,1024])"],
    "fusion.7": ["jit(fused)/while/body/transpose(jvp(head.loss))/mul",
                 "bf16[20,1024]", []],
    "fusion.8": ["jit(fused)/while/body/transpose(jvp(mlp))/jit(gelu)/mul",
                 "bf16[20,4096]", []]})


class FakeRun:
    def __init__(self, events, modules):
        self.reduction = trace.Reduction({PLANE: events}, [],
                                         {PLANE: modules})
        self.logged = []

    def log(self, message):
        self.logged.append(message)


def _serving_trace():
    events = [
        # a run of the bucket of 8: 1 + 2 + 1 + 0.5 s
        _event("fusion.1", "bf16[8,96]", "fusion", 0.0, 1.0),
        _event("fusion.2", "f32[8,64]", "fusion", 1.0, 3.0),
        _event("kv_page_write.3", "(bf16[17,2,8,32], bf16[17,2,8,32])",
               "custom-call", 3.0, 4.0,
               ', custom_call_target="tpu_custom_call"'),
        _event("copy.4", "bf16[8,32]", "copy", 4.0, 4.5),
        # a run of the bucket of 32: 2 + 3 s
        _event("fusion.1", "bf16[32,96]", "fusion", 10.0, 12.0),
        _event("fusion.2", "bf16[32,128]", "fusion", 12.0, 15.0),
    ]
    modules = [("jit_prefill(111)", 0.0, 4.5), ("jit_prefill(222)", 10.0,
                                               15.0)]
    return events, modules


def test_components_take_jitted_names_and_wrappers_off():
    parts = scope_busy_share.components
    assert parts("jit(fused)/transpose(jvp(mlp))/jit(_where)/mul") == \
        ["mlp", "mul"]
    assert parts("jit(f)/while/body/closed_call/jvp(head.loss)/div") == \
        ["while", "body", "closed_call", "head.loss", "div"]
    assert parts("jit(f)/transpose(jvp(attn.full/attn.chunk_blocks))/"
                 "dot_general") == ["attn.full", "attn.chunk_blocks",
                                    "dot_general"]
    assert parts("jit(decode)/pjit(mlp)/add") == ["add"] and parts("") == []
    # the name a kernel's event takes: the innermost scope
    name = scope_busy_share.kernel_scope
    assert name("jit(decode)/attn.decode/jit(_walk)/paged_attention/"
                "pallas_call") == "paged_attention"
    assert name("jit(fused)/while/body/closed_call/jvp()/pallas_call") == \
        "jvp__"
    assert name("jit(fused)/while/body/transpose(jvp(a/b))/pallas_call") \
        == "transpose_jvp_a_b__"


def test_the_parts_add_up_and_two_programs_of_one_module_are_told_apart():
    events, modules = _serving_trace()
    run = FakeRun(events, modules)
    table = scope_busy_share.build(run.reduction, [PREFILL_8, PREFILL_32],
                                   VOCABULARY)
    busy = run.reduction.busy_s
    assert busy == pytest.approx(9.5)
    assert table.unmapped_s == 0 and table.unscoped_s == pytest.approx(0.5)
    assert table.seconds() + table.unscoped_s + table.unmapped_s == \
        pytest.approx(busy)
    # the bucket of 32's fusion.2 is the MLP's, the bucket of 8's the
    # chunk's read: the shape told the two entries apart
    assert table.seconds(["mlp"]) == pytest.approx(3.0)
    assert table.seconds(["attn.prefill"]) == pytest.approx(2.0)
    assert table.seconds(["attn.proj"]) == pytest.approx(3.0)
    assert table.seconds(["attn.proj", "mlp"]) == pytest.approx(6.0)
    assert table.runs == {("prefill", "8/True/0"): 1,
                          ("prefill", "32/True/0"): 1}
    # the fusion whose members lie under the scope and under none
    assert table.mixed_s == pytest.approx(2.0)
    # the kernel's op_name ends in the kernel's own name
    assert (table.kernels, table.kernel_disagreements) == (1, 0)
    rows = table.rows(busy)
    assert list(rows)[0] in ("mlp", "attn.proj") and \
        rows["kv.write"][:2] == [1.0, pytest.approx(100 / 9.5, abs=1e-3)]
    assert rows["kv.write"][2] == ["kv_page_write kernel"]


def test_a_container_keeps_what_its_body_leaves_and_backward_is_apart():
    events = [
        _event("while.9", "(s32[], f32[20,1024])", "while", 0.0, 10.0),
        _event("fusion.7", "bf16[20,1024]", "fusion", 1.0, 4.0),
        _event("fusion.8", "bf16[20,4096]", "fusion", 4.0, 9.0),
    ]
    run = FakeRun(events, [("jit_fused(5)", 0.0, 10.0)])
    table = scope_busy_share.build(run.reduction, [TRAIN], VOCABULARY)
    assert run.reduction.busy_s == pytest.approx(10.0)
    # the loop's own 2 s and its first fusion's 3 under head.loss
    assert table.seconds(["head.loss"]) == pytest.approx(5.0)
    assert table.seconds(["mlp"]) == pytest.approx(5.0)
    assert table.backward["head.loss"] == pytest.approx(3.0)
    assert table.backward["mlp"] == pytest.approx(5.0)
    assert table.seconds() == pytest.approx(10.0)


def test_a_run_no_entry_accounts_for_is_unmapped_and_nothing_is_read(
        monkeypatch):
    events, modules = _serving_trace()
    # a third program's run that the map does not hold: 1 s of 10.5
    events.append(_event("fusion.1", "bf16[64,96]", "fusion", 20.0, 21.0))
    modules.append(("jit_decode(333)", 20.0, 21.0))
    run = FakeRun(events, modules)
    monkeypatch.setattr(scope_busy_share, "_program", lambda: (
        lambda: [PREFILL_8, PREFILL_32], VOCABULARY))
    table = scope_busy_share.table(run)
    assert table.unmapped_s == pytest.approx(1.0)
    assert table.runs[("jit_decode", "unmapped")] == 1
    assert table.seconds() + table.unscoped_s + table.unmapped_s == \
        pytest.approx(run.reduction.busy_s)
    for name in ("kv_write_busy_share.chat", "unscoped_busy_share.chat"):
        assert scope_busy_share.read(
            run, manifest.load_layer_metric(name)) is None
    assert any("unmapped passes 2%" in line for line in run.logged)
    # made and logged once a run
    logged = len(run.logged)
    assert scope_busy_share.table(run) is table and len(run.logged) == logged


def test_the_metrics_read_their_scopes_and_log_one_table(monkeypatch):
    events, modules = _serving_trace()
    run = FakeRun(events, modules)
    monkeypatch.setattr(scope_busy_share, "_program", lambda: (
        lambda: [PREFILL_8, PREFILL_32], VOCABULARY))
    read = lambda name: scope_busy_share.read(
        run, manifest.load_layer_metric(name))
    assert read("kv_write_busy_share.chat") == pytest.approx(100 / 9.5)
    assert read("attn_prefill_busy_share.docs") == pytest.approx(200 / 9.5)
    assert read("unscoped_busy_share.docs") == pytest.approx(50 / 9.5)
    # no program of the run has such a scope: nothing to read
    assert read("gated_delta_chunk_busy_share.evals") is None
    assert read("mamba_proj_busy_share.rollouts") is None
    line, = [m for m in run.logged if m.startswith("scopes {scope:")]
    rows = json.loads(line.split(": ", 2)[2])
    assert rows["attn.prefill"][0] == 2.0 and rows["mlp"][2] == \
        ["fusion bf16[32,128]"]
    assert any("1 Mosaic kernel events mapped, 0 whose" in m
               for m in run.logged)
    # a kernel whose mapped name is another's is counted and named
    wrong = dict(PREFILL_8, instructions=dict(
        PREFILL_8["instructions"], **{"kv_page_write.3": [
            "jit(prefill)/kv.write/jit(write)/other_kernel/pallas_call",
            "(bf16[17,2,8,32], bf16[17,2,8,32])"]}))
    table = scope_busy_share.build(run.reduction, [wrong, PREFILL_32],
                                   VOCABULARY)
    assert (table.kernels, table.kernel_disagreements) == (1, 1)


def test_a_program_without_a_map_gives_nothing_and_does_not_raise(
        monkeypatch):
    events, modules = _serving_trace()
    run = FakeRun(events, modules)
    monkeypatch.setattr(scope_busy_share, "_program", lambda: None)
    for metric in MANIFEST["per_layer"]:
        params = manifest.load_layer_metric(metric["name"])
        if params["reader"] == "scope_busy_share":
            assert scope_busy_share.read(run, params) is None
    assert run.logged == []
    # and a trace without a device plane
    empty = FakeRun([], [])
    empty.reduction = trace.Reduction({}, [])
    assert scope_busy_share.table(empty) is None


@pytest.mark.parametrize("metric", [
    m for m in MANIFEST["per_layer"] if manifest.load_layer_metric(
        m["name"])["reader"] == "scope_busy_share"],
    ids=lambda m: m["name"])
def test_each_scope_metric_names_scopes_of_the_vocabulary(metric):
    from deepspeed_tpu.utils.annotate import DEVICE_SCOPES
    params = manifest.load_layer_metric(metric["name"])
    assert (metric["unit"], metric["better"], metric["source"],
            metric["layer"]) == ("%", "lower", "device_trace", "kernels")
    assert len(metric["workloads"]) == 1
    if metric["name"].startswith("unscoped_busy_share."):
        assert params["unscoped"] is True and "scopes" not in params
    else:
        assert params["scopes"] and set(params["scopes"]) <= \
            set(DEVICE_SCOPES)
    # none in the four cells whose per-layer sets their tests pin
    assert metric["workloads"][0].rsplit(".", 1)[1] in (
        "seq1024", "chat", "docs", "rollouts", "evals")
