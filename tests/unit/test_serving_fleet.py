"""Disaggregated serving fleet: roles, handoff codec, router, adapters.

The acceptance spec for ISSUE 17:

  * the fp page-slice codec is BITWISE: serialize -> deserialize moves
    the page payloads verbatim, so greedy streams through prefill ->
    handoff -> decode are byte-identical to the single-engine paged
    path;
  * the int8 handoff codec stays within the documented tolerance
    (``0.5 * blockwise_absmax / 127`` per lane, plus fp rounding);
  * torn/truncated/corrupted payloads are rejected LOUDLY
    (HandoffError) — never a silently wrong cache;
  * every schema copy pins equal: telemetry/record.py SERVING_ROLES /
    the nullable ``role`` field vs aggregate.py and
    bin/check_bench_schema.py; inference/fleet/events.py router-event
    vocabulary vs both stdlib copies;
  * the router refuses divergent fingerprints, denies by predicted
    cost, routes away from flagged hosts, and preempt-migrates live
    streams intact;
  * multi-tenant adapters: id 0 is the byte-identical base, tenants
    diverge, and the prefix cache never cross-hits namespaces;
  * DSL010 flags serving_step fields outside the pinned schema.
"""
import importlib.util
import json
import os
import struct

import numpy as np
import pytest

import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from deepspeed_tpu.inference.fleet import events
from deepspeed_tpu.inference.fleet.adapters import AdapterSet
from deepspeed_tpu.inference.fleet.handoff import (
    HandoffError, PageSlice, deserialize_slice, export_slice,
    serialize_slice)
from deepspeed_tpu.inference.fleet.router import FleetRouter
from deepspeed_tpu.inference.fleet.serve import DisaggServer
from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
from deepspeed_tpu.models import gpt2
from deepspeed_tpu.telemetry import record
from deepspeed_tpu.telemetry.fleet import aggregate

pytestmark = pytest.mark.serving_fleet

TINY = dict(vocab_size=128, max_seq_len=64, n_layers=2, n_heads=2,
            d_model=32, use_flash_attention=False, remat=False)
PS = 8                                   # page size used throughout

_REPO = os.path.join(os.path.dirname(__file__), "..", "..")


def tiny_model(seed=0, **over):
    cfg = gpt2.GPT2Config(**{**TINY, **over})
    return gpt2.make_gpt2_model(config=cfg, seed=seed)


def make_engine(model, **inference):
    inference.setdefault("max_batch_size", 3)
    inference.setdefault("prefill_buckets", [8, 16, 32])
    inference.setdefault("dtype", "fp32")
    inference.setdefault("greedy", True)
    return deepspeed.init_inference(model=model,
                                    config={"inference": inference})


def paged_engine(model, **inference):
    inference.setdefault("kv_block_size", PS)
    return make_engine(model, **inference)


@pytest.fixture(scope="module")
def model():
    return tiny_model()


def greedy_chain(model, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        ids = jnp.asarray(np.asarray(seq, np.int32)[None])
        hidden = gpt2.forward_hidden(model.params, ids, model.config,
                                     train=False)
        seq.append(int(np.asarray(hidden[0, -1] @ model.params["wte"].T)
                       .argmax()))
    return seq[len(prompt):]


def load_checker():
    path = os.path.join(_REPO, "bin", "check_bench_schema.py")
    spec = importlib.util.spec_from_file_location("_cbs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def random_slice(rs, n_pages=3, layers=2, heads=2, dh=16, length=17,
                 dtype=np.float32):
    shape = (n_pages, layers, heads, PS, dh)
    return PageSlice(
        rs.normal(size=shape).astype(dtype),
        rs.normal(size=shape).astype(dtype),
        PS, length, pending_token=int(rs.randint(0, 128)),
        context=rs.randint(0, 128, size=length).tolist())


# --------------------------------------------------- handoff codec


def test_fp_roundtrip_is_bitwise():
    """The fp codec moves page payloads VERBATIM: every byte of K and V
    survives serialize -> deserialize, along with the table metadata a
    decode host needs to resume."""
    rs = np.random.RandomState(0)
    sl = random_slice(rs)
    out = deserialize_slice(serialize_slice(sl))
    assert out.k_pages.tobytes() == sl.k_pages.tobytes()
    assert out.v_pages.tobytes() == sl.v_pages.tobytes()
    assert out.k_pages.shape == sl.k_pages.shape
    assert out.k_pages.dtype == sl.k_pages.dtype
    assert out.page_size == sl.page_size
    assert out.length == sl.length
    assert out.pending_token == sl.pending_token
    assert out.context == sl.context


def test_quantized_roundtrip_within_documented_tolerance():
    """The int8 path reconstructs every lane within the documented
    ``0.5 * blockwise_absmax / 127`` quantization step (plus fp
    rounding) and ships meaningfully fewer payload bytes than fp32."""
    rs = np.random.RandomState(1)
    sl = random_slice(rs, n_pages=4)
    block = 64
    data = serialize_slice(sl, quantize=True, block_size=block)
    out = deserialize_slice(data)
    for orig, got in ((sl.k_pages, out.k_pages),
                      (sl.v_pages, out.v_pages)):
        flat = orig.reshape(-1).astype(np.float64)
        pad = (-len(flat)) % block
        padded = np.pad(flat, (0, pad))
        absmax = np.abs(padded.reshape(-1, block)).max(axis=1)
        bound = 0.5 * absmax / 127.0 + 1e-5
        err = np.abs(np.pad(got.reshape(-1).astype(np.float64),
                            (0, pad)) - padded).reshape(-1, block)
        assert (err <= bound[:, None]).all(), \
            "max err {} vs bound {}".format(err.max(), bound.min())
    # int8 blocks + fp32 scales: well under the fp32 wire
    assert len(data) < 0.5 * len(serialize_slice(sl))
    assert out.context == sl.context and out.length == sl.length


@pytest.mark.faults
def test_torn_payloads_rejected_loudly():
    """Every way a handoff can tear — short head, bad magic, version
    skew, truncated header, corrupt header JSON, truncated payload,
    flipped payload byte — raises HandoffError instead of importing a
    silently wrong cache."""
    rs = np.random.RandomState(2)
    data = serialize_slice(random_slice(rs))
    head = struct.Struct(">4sHI")
    _magic, _version, header_len = head.unpack_from(data)

    with pytest.raises(HandoffError, match="shorter"):
        deserialize_slice(data[:head.size - 1])
    with pytest.raises(HandoffError, match="bad magic"):
        deserialize_slice(b"XXXX" + data[4:])
    with pytest.raises(HandoffError, match="version"):
        deserialize_slice(
            head.pack(b"DSKV", 99, header_len) + data[head.size:])
    with pytest.raises(HandoffError, match="truncated header"):
        deserialize_slice(data[:head.size + header_len // 2])
    corrupt = bytearray(data)
    corrupt[head.size + 2] ^= 0xFF          # inside the JSON header
    with pytest.raises(HandoffError):
        deserialize_slice(bytes(corrupt))
    with pytest.raises(HandoffError, match="truncated payload"):
        deserialize_slice(data[:-3])
    torn = bytearray(data)
    torn[-5] ^= 0x01                        # inside the payload
    with pytest.raises(HandoffError, match="checksum"):
        deserialize_slice(bytes(torn))
    # the pristine buffer still round-trips after all that
    assert deserialize_slice(data).length > 0


def test_export_import_roundtrip_through_engines(model):
    """export_slice lifts a live slot's pages bitwise: prefill on one
    paged engine, export, serialize, import into ANOTHER engine, and
    the decode continuation matches the host-side greedy oracle."""
    from deepspeed_tpu.inference.fleet.handoff import (can_import,
                                                       import_slice)
    src = paged_engine(model, max_batch_size=2)
    dst = paged_engine(model, max_batch_size=2)
    prompt = list(range(1, 20))
    token = src.prefill(0, prompt)
    sl = export_slice(src, 0, context=prompt, pending_token=token)
    out = deserialize_slice(serialize_slice(sl))
    assert out.k_pages.tobytes() == sl.k_pages.tobytes()
    assert can_import(dst, out)
    pending = import_slice(dst, 1, out)
    chain = greedy_chain(model, prompt, 5)
    assert pending == chain[0]
    got = [pending]
    for _ in range(4):
        assert dst.ensure_pages(1, int(dst.lengths[1]) + 1)
        toks = np.zeros(dst.num_slots, np.int32)
        toks[1] = got[-1]
        nxt = dst.decode_step(toks)
        dst.advance(1)
        got.append(int(nxt[1]))
    assert got == chain


# ----------------------------------------------- disaggregated server


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_disagg_streams_byte_identical_to_monolith(model, kernel):
    """Greedy streams through prefill -> serialized handoff -> decode
    equal the monolithic paged scheduler's streams token for token; with
    ``pallas`` the prefill host's chunks read their keys in
    ``chunk_attention`` and the decode hosts walk the pages, against the
    gather read's monolith."""
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, size=n).tolist()
               for n in (5, 11, 17, 26)]
    mono = paged_engine(model, max_batch_size=4, prefill_chunk_tokens=8)
    sched = ContinuousBatchingScheduler(mono)
    uids = [sched.submit(p, max_new_tokens=6) for p in prompts]
    oracle = sched.run()

    server = DisaggServer(
        {"pre0": paged_engine(model, max_batch_size=2,
                              prefill_chunk_tokens=8,
                              paged_attention_kernel=kernel)},
        {"dec0": paged_engine(model, max_batch_size=2,
                              paged_attention_kernel=kernel),
         "dec1": paged_engine(model, max_batch_size=2,
                              paged_attention_kernel=kernel)})
    for p in prompts:
        server.submit(p, max_new_tokens=6)
    out = server.run()
    assert [out[u] for u in sorted(out)] == [oracle[u] for u in uids]
    stats = server.handoff_stats()
    assert stats["handoffs"] == len(prompts)
    assert stats["payload_bytes"] > 0 and not stats["quantized"]
    counts = server.router.decision_counts()
    assert counts["admit"] == len(prompts)
    assert counts["enroll"] == 3


def test_disagg_migration_keeps_stream_intact(model):
    """Flagging a decode host mid-run preempt-migrates its youngest
    stream to the healthy host; outputs stay byte-identical and the
    flagged host receives no further decode placements."""
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, 128, size=n).tolist()
               for n in (7, 13, 21, 9)]
    oracle = [greedy_chain(model, p, 8) for p in prompts]
    server = DisaggServer(
        {"pre0": paged_engine(model, max_batch_size=2,
                              prefill_chunk_tokens=8)},
        {"dec0": paged_engine(model, max_batch_size=3),
         "dec1": paged_engine(model, max_batch_size=3)})
    for p in prompts:
        server.submit(p, max_new_tokens=8)
    # pump until the first-choice host (dec0: free-slot tie broken by
    # name) holds live decode work, then flag it
    for _ in range(30):
        server.step()
        if server.decode_roles["dec0"].active:
            break
    assert server.decode_roles["dec0"].active
    server.router.mark_straggler("dec0")
    assigned_before = server.router.hosts["dec0"].decode_assignments
    out = server.run()
    assert [out[u] for u in sorted(out)] == oracle
    assert server.router.migrations >= 1
    counts = server.router.decision_counts()
    assert counts.get("preempt_migrate", 0) >= 1
    assert counts.get("route_away", 0) >= 1
    # no new decode work landed on the flagged host
    assert server.router.hosts["dec0"].decode_assignments == \
        assigned_before


def test_disagg_quantized_handoff_opt_in(model):
    """quantize=True rides the int8 codec end to end: every request
    completes with sane token ids and the wire admits it shipped
    quantized payloads."""
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (6, 14)]
    server = DisaggServer(
        {"pre0": paged_engine(model, max_batch_size=2)},
        {"dec0": paged_engine(model, max_batch_size=2)},
        quantize=True, block_size=64)
    for p in prompts:
        server.submit(p, max_new_tokens=5)
    out = server.run()
    assert sorted(out) == [0, 1]
    for toks in out.values():
        assert len(toks) == 5
        assert all(0 <= t < TINY["vocab_size"] for t in toks)
    assert server.handoff_stats()["quantized"]


# ------------------------------------------------------------- router


class _FakeRole:
    def __init__(self, free=1):
        self.free = free

    def free_slots(self):
        return self.free


def test_router_refuses_divergent_fingerprint():
    router = FleetRouter()
    fp = {"version": 1, "digest": "ref-digest", "families": []}
    bad = {"version": 1, "digest": "DIVERGENT", "families": []}
    assert router.enroll("a", "prefill", fingerprint=fp)
    assert router.enroll("b", "decode", fingerprint=fp)
    assert not router.enroll("c", "decode", fingerprint=bad)
    assert "c" not in router.hosts
    counts = router.decision_counts()
    assert counts == {"enroll": 2, "enroll_refusal": 1}
    refusal = [e for e in router.events.events
               if e["decision"] == "enroll_refusal"][0]
    assert refusal["host"] == "c"
    assert refusal["detail"]["reference"] == "ref-digest"


def test_router_admission_prices_buckets_against_slo():
    router = FleetRouter(ttft_slo_s=0.1, admit_budget_factor=1.0)
    bucket_for = lambda n: 16                         # noqa: E731
    # no prices yet: admit on faith
    assert router.admit(0, 10, bucket_for)
    router.observe_prefill(16, 0.06)
    # 0.06 * (1 + 0 queued) fits the 0.1s budget
    assert router.admit(1, 10, bucket_for, queue_depth=0)
    # 0.06 * (1 + 2 queued) = 0.18 > 0.1: denied at the door
    assert not router.admit(2, 10, bucket_for, queue_depth=2)
    assert router.denied == [2]
    deny = [e for e in router.events.events
            if e["decision"] == "deny"][0]
    assert deny["request_uid"] == 2
    assert deny["predicted_cost_s"] == pytest.approx(0.06)
    # EWMA folds new walls in at alpha=0.4
    router.observe_prefill(16, 0.01)
    assert router.predicted_cost(10, bucket_for) == \
        pytest.approx(0.4 * 0.01 + 0.6 * 0.06)
    # unpriced buckets interpolate linearly from the nearest priced one
    assert router.predicted_cost(30, lambda n: 32) == \
        pytest.approx(router.predicted_cost(10, bucket_for) * 2)


def test_router_routes_away_from_flagged_hosts():
    router = FleetRouter()
    router.enroll("d0", "decode", role=_FakeRole(2))
    router.enroll("d1", "decode", role=_FakeRole(2))
    router.mark_straggler("d0")
    for _ in range(3):
        assert router.pick_decode_host(uid=7) == "d1"
    assert router.hosts["d0"].decode_assignments == 0
    counts = router.decision_counts()
    assert counts["route_away"] == 3
    away = [e for e in router.events.events
            if e["decision"] == "route_away"][0]
    assert away["host"] == "d0" and "straggler" in away["reason"]
    # clearing the flag restores eligibility (least-loaded wins)
    router.mark_straggler("d0", flagged=False)
    assert router.pick_decode_host() == "d0"


def test_router_ingests_fleet_report_flags():
    router = FleetRouter()
    router.enroll("d0", "decode", role=_FakeRole())
    router.enroll("d1", "decode", role=_FakeRole())
    router.ingest_fleet_report(
        {"straggler": {"flags": [{"host": "d0", "z": 4.0}]}})
    assert router.hosts["d0"].straggler
    assert not router.hosts["d1"].straggler
    router.observe_healthz("d1", {"status": "degraded"})
    assert router.pick_decode_host() is None         # nobody eligible
    router.ingest_fleet_report({"straggler": {"flags": []}})
    router.observe_healthz("d1", {"status": "ok"})
    assert router.pick_decode_host() in ("d0", "d1")


def test_router_events_land_on_disk_schema_valid(tmp_path):
    router = FleetRouter(event_dir=str(tmp_path))
    router.enroll("d0", "decode", role=_FakeRole())
    router.admit(0, 5, lambda n: 8)
    path = os.path.join(str(tmp_path), events.ROUTER_EVENTS_JSONL)
    with open(path) as fh:
        lines = [json.loads(line) for line in fh]
    assert len(lines) == 2
    for ev in lines:
        assert events.validate_router_event(ev) == [], ev


# ----------------------------------------------------- schema pinning


def test_router_event_validator_catches_drift():
    ev = events.make_router_event(decision="admit", request_uid=3,
                                  predicted_cost_s=0.01)
    assert events.validate_router_event(ev) == []
    bad = dict(ev)
    bad["decision"] = "shrug"
    assert any("decision" in p for p in
               events.validate_router_event(bad))
    missing = dict(ev)
    del missing["host"]
    assert any("missing" in p for p in
               events.validate_router_event(missing))
    extra = dict(ev, freelance=1)
    assert any("unexpected" in p for p in
               events.validate_router_event(extra))
    wrong_wall = dict(ev, wall="yesterday")
    assert any("wall" in p for p in
               events.validate_router_event(wrong_wall))
    assert events.validate_router_event("not a dict")


def test_serving_role_field_pinned_across_schema_copies():
    """The nullable ``role`` StepRecord field and SERVING_ROLES
    vocabulary stay identical across telemetry/record.py, the fleet
    merger's stdlib copy, and bin/check_bench_schema.py's copy."""
    assert "role" in record.SERVING_STEP_KEYS
    assert record.SERVING_ROLES == aggregate.SERVING_ROLES
    cbs = load_checker()
    assert cbs.SERVING_ROLES == record.SERVING_ROLES
    # a roled record validates; a freelance role does not
    kw = dict(step=0, slot_occupancy=0.5, queue_depth=0, active_slots=1,
              prefill_tokens=8, prefill_tokens_per_sec=1.0,
              decode_tokens=4, decode_steps=4,
              decode_tokens_per_sec=1.0)
    for role in record.SERVING_ROLES + (None,):
        rec = record.make_serving_record(role=role, **kw)
        assert record.validate_step_record(rec) == [], role
    bogus = record.make_serving_record(role="sidecar", **kw)
    assert any("role" in p for p in record.validate_step_record(bogus))


def test_router_event_schema_pinned_across_stdlib_copies():
    """events.py is the source of truth; aggregate.py and
    bin/check_bench_schema.py carry stdlib-only copies that must never
    drift (doctoring a crashed run can't import jax)."""
    assert aggregate.ROUTER_EVENT_KEYS == events.ROUTER_EVENT_KEYS
    assert aggregate.ROUTER_DECISIONS == events.ROUTER_DECISIONS
    assert aggregate.ROUTER_EVENTS_JSONL == events.ROUTER_EVENTS_JSONL
    assert aggregate.KIND_ROUTER_EVENT == events.KIND_ROUTER_EVENT
    cbs = load_checker()
    assert cbs.ROUTER_EVENT_KEYS == events.ROUTER_EVENT_KEYS
    assert cbs.ROUTER_DECISIONS == events.ROUTER_DECISIONS


# ----------------------------------------------------------- adapters


def test_adapter_set_registry_and_oracle():
    ads = AdapterSet(d_model=32, vocab_size=128, rank=4)
    assert len(ads) == 1 and ads.id_of("base") == 0
    aid = ads.add("tenant-a")
    assert aid == 1 and ads.id_of("tenant-a") == 1
    with pytest.raises(AssertionError):
        ads.add("tenant-a")
    hidden = np.random.RandomState(0).normal(size=(3, 32))
    # base delta is exactly zero; a fresh LoRA adapter (B=0) too
    assert not ads.logits_delta(hidden, 0).any()
    assert not ads.logits_delta(hidden, 1).any()
    B = np.random.RandomState(1).normal(size=(128, 4)).astype(np.float32)
    ads.add("tenant-b", B=B)
    delta = ads.logits_delta(hidden, 2)
    assert delta.shape == (3, 128) and np.abs(delta).sum() > 0


@pytest.mark.parametrize("kernel", ["xla", "pallas"])
def test_adapter_zero_is_byte_identical_base(model, kernel):
    """Attaching adapters switches the engine onto the adapter-aware
    program family; adapter id 0 (the all-zero BASE row) must still be
    byte-identical to the adapter-free engine (the gather read's), with
    the cache path's kernels too: they never see an adapter."""
    plain = paged_engine(model, max_batch_size=2)
    adapted = paged_engine(model, max_batch_size=2,
                           paged_attention_kernel=kernel)
    ads = AdapterSet(d_model=TINY["d_model"],
                     vocab_size=TINY["vocab_size"], rank=4)
    ads.add("tenant-a")
    adapted.attach_adapters(ads)
    prompts = [[3, 1, 4, 1, 5], list(range(2, 22))]
    assert adapted.generate(prompts, max_new_tokens=6) == \
        plain.generate(prompts, max_new_tokens=6)


def test_adapter_tenants_diverge_and_base_unpolluted(model):
    """A tenant with a trained (nonzero-B) adapter serves a different
    stream than the base, in the SAME mixed batch, while base traffic
    through the same engine stays on the oracle stream."""
    eng = paged_engine(model, max_batch_size=2)
    ads = AdapterSet(d_model=TINY["d_model"],
                     vocab_size=TINY["vocab_size"], rank=4)
    rs = np.random.RandomState(7)
    ads.add("tenant-a",
            A=rs.normal(0, 1.0, size=(4, TINY["d_model"])),
            B=rs.normal(0, 2.0, size=(TINY["vocab_size"], 4)))
    eng.attach_adapters(ads)
    prompt = [9, 2, 6, 5, 3, 5]
    sched = ContinuousBatchingScheduler(eng)
    u_base = sched.submit(prompt, max_new_tokens=6)
    u_ten = sched.submit(prompt, max_new_tokens=6,
                         adapter=ads.id_of("tenant-a"))
    out = sched.run()
    assert out[u_base] == greedy_chain(model, prompt, 6)
    assert out[u_ten] != out[u_base]


def test_adapter_prefix_cache_namespaced(model):
    """Two tenants with the SAME prompt never cross-hit each other's
    cached prefix pages; same-tenant re-use still hits."""
    eng = paged_engine(model, max_batch_size=1, prefix_caching=True,
                      prefill_buckets=[8, 16, 32])
    ads = AdapterSet(d_model=TINY["d_model"],
                     vocab_size=TINY["vocab_size"], rank=4)
    ads.add("tenant-a")
    eng.attach_adapters(ads)
    prompt = [5, 6, 7] * 5
    sched = ContinuousBatchingScheduler(eng)

    def one(adapter):
        uid = sched.submit(prompt, max_new_tokens=3, adapter=adapter)
        sched.run()
        return uid

    one(0)
    base_hits = eng.prefix_cache.hits
    one(1)                       # other tenant, same prompt: MUST miss
    assert eng.prefix_cache.hits == base_hits
    one(1)                       # same tenant again: hits
    assert eng.prefix_cache.hits > base_hits


# ------------------------------------------------------------- DSL010


_FREELANCE = '''
def emit():
    return {"kind": "serving_step", "step": 1, "wall": 0.0,
            "ttft_budget_burn": 0.9}
'''


def test_dsl010_flags_field_outside_serving_schema(tmp_path):
    from deepspeed_tpu.analysis import astlint
    schema = astlint.load_serving_schema(_REPO)
    assert schema is not None and "role" in schema
    assert "page_pool" in schema and "ttft" in schema
    path = str(tmp_path / "mod.py")
    with open(path, "w") as fh:
        fh.write(_FREELANCE)
    hits = [v for v in astlint.lint_file(path, relpath="mod.py",
                                         serving_schema=schema)
            if v[0] == "DSL010"]
    assert len(hits) == 1
    assert "ttft_budget_burn" in hits[0][3]
    # inert without a schema (partial checkout), and record.py itself
    # (the schema's home) is exempt
    assert not [v for v in astlint.lint_file(path, relpath="mod.py")
                if v[0] == "DSL010"]
    assert not [v for v in astlint.lint_file(
        path, relpath="deepspeed_tpu/telemetry/record.py",
        serving_schema=schema) if v[0] == "DSL010"]


def test_dsl010_accepts_schema_conformant_literal(tmp_path):
    from deepspeed_tpu.analysis import astlint
    schema = astlint.load_serving_schema(_REPO)
    path = str(tmp_path / "ok.py")
    with open(path, "w") as fh:
        fh.write('def emit():\n'
                 '    return {"kind": "serving_step", "step": 1,\n'
                 '            "role": "prefill", "ttft": None}\n')
    assert not [v for v in astlint.lint_file(path, relpath="ok.py",
                                             serving_schema=schema)
                if v[0] == "DSL010"]


# ------------------------- one request, one trace id across a hand-off
def test_page_slice_carries_trace_id_across_the_wire():
    k = np.arange(2 * 1 * 2 * 4 * 3, dtype=np.float32).reshape(
        2, 1, 2, 4, 3)
    sl = PageSlice(k, k + 1, page_size=4, length=5, pending_token=7,
                   context=[1, 2, 3, 4, 5], trace_id="serve-9-12")
    back = deserialize_slice(serialize_slice(sl))
    assert back.trace_id == "serve-9-12"
    # absence stays None (older slices, spans off)
    sl2 = PageSlice(k, k, page_size=4, length=5, pending_token=7,
                    context=[1])
    assert deserialize_slice(serialize_slice(sl2)).trace_id is None


def test_span_tracer_continues_a_carried_trace_id():
    from deepspeed_tpu.telemetry.spans import SpanTracer
    tracer = SpanTracer([])
    cont = tracer.begin("serving_request", trace_id="prefill-1-0")
    assert cont.trace_id == "prefill-1-0"
    minted = tracer.begin("serving_request")
    assert minted.trace_id != "prefill-1-0"


def test_merged_trace_rehomes_cross_host_requests():
    ev = lambda pid, tid_arg: {"name": "s", "ph": "X", "ts": 1.0,
                               "dur": 1.0, "pid": pid, "tid": 0,
                               "args": {"trace_id": tid_arg}}
    merged = [ev(0, "req-a"), ev(1, "req-a"),    # crosses hosts
              ev(0, "req-b"),                    # single-host: stays
              {"name": "x", "ph": "X", "ts": 0.0, "dur": 1.0,
               "pid": 1, "tid": 3}]              # no trace_id: stays
    aggregate._rehome_cross_host_requests(merged, req_pid=2)
    assert [e["pid"] for e in merged[:4]] == [2, 2, 0, 1]
    assert merged[0]["tid"] == merged[1]["tid"]
    names = [e for e in merged if e.get("ph") == "M"]
    assert {(m["name"], m["args"]["name"]) for m in names} == \
        {("process_name", "requests"), ("thread_name", "req-a")}
