"""StepRecord schema: the one per-step JSON object every engine emits.

A train-step record joins, for ONE optimizer step, what previously
lived in five silos: the synchronized phase breakdown (timer.py /
offload phase dicts), achieved flops from XLA ``cost_analysis`` turned
into MFU against the chip peak (mfu.py), per-device HBM live/peak from
``memory_stats()``, the wire.py bytes-on-wire estimate per collective
class, and the loss/grad-norm/loss-scale/overflow counters. Serving
emits a sibling ``serving_step`` record per scheduler step.

``validate_step_record`` is the golden-schema contract that
tests/unit/test_telemetry.py and bin/check_bench_schema.py enforce.
"""
import time

KIND_TRAIN = "train_step"
KIND_SERVING = "serving_step"

# every train_step record carries exactly these top-level keys
TRAIN_STEP_KEYS = (
    "kind", "step", "wall", "step_time_s",
    "loss", "grad_norm", "loss_scale", "overflow", "skipped_steps",
    "micro_steps",
    "tokens_per_step", "tokens_per_sec_per_chip",
    "model_flops_per_step", "mfu", "peak_flops_per_chip",
    "device", "n_devices",
    "phases", "phase_total_s",
    "hbm", "wire", "comm_overlap", "offload", "pipe",
)

SERVING_STEP_KEYS = (
    "kind", "step", "wall",
    "slot_occupancy", "queue_depth", "active_slots",
    "prefill_tokens", "prefill_tokens_per_sec",
    "decode_tokens", "decode_steps", "decode_tokens_per_sec",
    # request-latency aggregates + the serving-memory/spec gauges
    # (null until the engine feature producing them has fired):
    # ttft/tpot {count, mean_s, p50_s, p95_s}; page_pool {num_pages,
    # pages_in_use, occupancy}; prefix {lookups,
    # hits, hit_rate, ...} (prefix_caching only); speculative
    # {proposed, accepted, acceptance_rate} (speculative only)
    "ttft", "tpot", "page_pool", "prefix", "speculative",
    # disaggregated-fleet role (null on a monolith; "prefill"/"decode"
    # on split engines, "router" on front-end records) — the fleet
    # doctor attributes steps per role on it
    "role",
)

# the closed vocabulary a non-null serving `role` must come from
SERVING_ROLES = ("monolith", "prefill", "decode", "router")

# Unified per-segment/offload stats schema (ISSUE 13): the ONE shape
# both offload paths' StepRecord ``offload`` sub-dict uses — the
# streamed runner's transfer_snapshot() and the classic-offload
# executor stats emit exactly these keys (plus optional path extras),
# so telemetry consumers join on one schema. ``plan_segments``/
# ``per_kind`` come from the PlanExecutor (runtime/executor/) and
# cover the whole step window — every segment of every plan the step
# executed (gas micro-plans + apply on the streamed path), NOT one
# plan's size (that lives in the audit report's plan/<name> entry);
# ``upload_*``/``bucket_*`` from the coalescing H2D batcher;
# ``overlap_efficiency`` is the constructed transfer/compute overlap
# (T3-style compute/(compute+exposed waits)). Validated by
# ``validate_segment_stats`` here and by bin/check_bench_schema.py's
# stdlib copy (pinned equal by tests/unit/test_executor.py).
SEGMENT_KEYS = (
    "plan_segments", "per_kind", "overlap_efficiency",
    "upload_batches", "upload_elems", "upload_bytes",
    "bucket_elems", "bucket_occupancy",
)
# per-kind sub-dict numeric keys (kinds = the shard-lint IR vocabulary)
SEGMENT_KIND_KEYS = ("segments", "run_s", "wait_s")
# path-specific extras a SEGMENT_KEYS dict may additionally carry
SEGMENT_OPTIONAL_KEYS = (
    "segment_upload_bytes_peak", "groups", "collective_matmul",
    "work_chunks", "mode", "plans_executed", "segments_executed",
    "last_plan_segments", "rewrites",
)

# plan-rewrite stats sub-dict (PR 19): the executor's
# ``rewrite_snapshot()`` shape — the canonical copy lives with the
# passes in runtime/executor/rewrite.py; this module and
# bin/check_bench_schema.py's stdlib twin are pinned equal to it by
# tests/unit/test_executor.py
REWRITE_KEYS = ("enabled", "passes", "segments_moved",
                "predicted_exposed_wait_delta_s",
                "measured_exposed_wait_delta_s")
REWRITE_PASS_KEYS = ("name", "segments_moved",
                     "predicted_exposed_wait_delta_s")


def validate_rewrite_stats(stats):
    """Schema check for one REWRITE_KEYS stats dict (the ``rewrites``
    sub-dict of a bench's ``extra.executor``). Returns a list of
    problem strings."""
    problems = []
    if not isinstance(stats, dict):
        return ["rewrite stats is not a dict: {!r}".format(
            type(stats).__name__)]
    missing = [k for k in REWRITE_KEYS if k not in stats]
    for key in missing:
        problems.append("rewrites missing key {!r}".format(key))
    extra = sorted(set(stats) - set(REWRITE_KEYS))
    if extra:
        problems.append("rewrites unexpected key(s) {}".format(extra))
    if problems:
        return problems
    if not isinstance(stats["enabled"], bool):
        problems.append("rewrites.enabled is not a bool: {!r}".format(
            stats["enabled"]))
    moved = stats["segments_moved"]
    if isinstance(moved, bool) or not isinstance(moved, _NUMERIC) or \
            moved < 0:
        problems.append("rewrites.segments_moved is not a nonnegative "
                        "number: {!r}".format(moved))
    for key in ("predicted_exposed_wait_delta_s",
                "measured_exposed_wait_delta_s"):
        val = stats[key]
        if val is not None and (isinstance(val, bool) or
                                not isinstance(val, _NUMERIC)):
            problems.append(
                "rewrites.{} is neither null nor a number: {!r}".format(
                    key, val))
    passes = stats["passes"]
    if not isinstance(passes, (list, tuple)):
        return problems + ["rewrites.passes is not a list"]
    for i, entry in enumerate(passes):
        if not isinstance(entry, dict):
            problems.append("rewrites.passes[{}] is not a dict".format(i))
            continue
        if sorted(entry) != sorted(REWRITE_PASS_KEYS):
            problems.append(
                "rewrites.passes[{}] keys {} != {}".format(
                    i, sorted(entry), sorted(REWRITE_PASS_KEYS)))
    return problems


def validate_segment_stats(stats):
    """Schema check for one SEGMENT_KEYS stats dict (a StepRecord's
    ``offload`` sub-dict on the lowered paths, or a bench's
    ``extra.executor``). Returns a list of problem strings."""
    problems = []
    if not isinstance(stats, dict):
        return ["segment stats is not a dict: {!r}".format(
            type(stats).__name__)]
    for key in SEGMENT_KEYS:
        if key not in stats:
            problems.append("missing key {!r}".format(key))
    extra = sorted(set(stats) - set(SEGMENT_KEYS)
                   - set(SEGMENT_OPTIONAL_KEYS))
    if extra:
        problems.append("unexpected key(s) {}".format(extra))
    if problems:
        return problems
    for key in ("plan_segments", "upload_batches", "upload_elems",
                "upload_bytes", "bucket_elems"):
        val = stats[key]
        if isinstance(val, bool) or not isinstance(val, _NUMERIC) or \
                val < 0:
            problems.append(
                "{} is not a nonnegative number: {!r}".format(key, val))
    for key in ("overlap_efficiency", "bucket_occupancy"):
        val = stats[key]
        if val is not None and (isinstance(val, bool) or
                                not isinstance(val, _NUMERIC)):
            problems.append(
                "{} is neither null nor a number: {!r}".format(key, val))
    per_kind = stats["per_kind"]
    if not isinstance(per_kind, dict):
        problems.append("per_kind is not a dict")
        return problems
    for kind, slot in per_kind.items():
        if not isinstance(slot, dict):
            problems.append("per_kind.{} is not a dict".format(kind))
            continue
        for key in SEGMENT_KIND_KEYS:
            val = slot.get(key)
            if isinstance(val, bool) or not isinstance(val, _NUMERIC) \
                    or val < 0:
                problems.append(
                    "per_kind.{}.{} is not a nonnegative number: "
                    "{!r}".format(kind, key, val))
    if "rewrites" in stats and stats["rewrites"] is not None:
        problems.extend(validate_rewrite_stats(stats["rewrites"]))
    return problems


# nullable serving sub-dicts and the numeric keys each must carry
SERVING_SUBDICT_KEYS = {
    "ttft": ("count", "mean_s", "p50_s", "p95_s"),
    "tpot": ("count", "mean_s", "p50_s", "p95_s"),
    "page_pool": ("num_pages", "pages_in_use", "occupancy"),
    "prefix": ("lookups", "hits", "hit_rate"),
    "speculative": ("proposed", "accepted", "acceptance_rate"),
}

_NUMERIC = (int, float)


def make_train_record(*, step, step_time_s, loss, grad_norm, loss_scale,
                      overflow, skipped_steps, micro_steps,
                      tokens_per_step, tokens_per_sec_per_chip,
                      model_flops_per_step, mfu, peak_flops_per_chip,
                      device, n_devices, phases, hbm, wire=None,
                      comm_overlap=None, offload=None, pipe=None,
                      wall=None):
    phases = {str(k): float(v) for k, v in (phases or {}).items()}
    return {
        "kind": KIND_TRAIN,
        "step": int(step),
        "wall": float(wall if wall is not None else time.time()),
        "step_time_s": float(step_time_s),
        "loss": None if loss is None else float(loss),
        "grad_norm": None if grad_norm is None else float(grad_norm),
        "loss_scale": float(loss_scale),
        "overflow": bool(overflow),
        "skipped_steps": int(skipped_steps),
        "micro_steps": int(micro_steps),
        "tokens_per_step": int(tokens_per_step),
        "tokens_per_sec_per_chip": float(tokens_per_sec_per_chip),
        "model_flops_per_step": float(model_flops_per_step),
        "mfu": float(mfu),
        "peak_flops_per_chip": float(peak_flops_per_chip),
        "device": str(device),
        "n_devices": int(n_devices),
        "phases": phases,
        "phase_total_s": float(sum(phases.values())),
        "hbm": hbm,
        "wire": wire,
        # per-collective-class overlap efficiency (wire.overlap_report):
        # compute/(compute + exposed-collective), the T3-style scoreboard
        # for the collective-matmul fusions
        "comm_overlap": comm_overlap,
        "offload": offload,
        "pipe": pipe,
    }


def make_serving_record(*, step, slot_occupancy, queue_depth, active_slots,
                        prefill_tokens, prefill_tokens_per_sec,
                        decode_tokens, decode_steps, decode_tokens_per_sec,
                        ttft=None, tpot=None, page_pool=None, prefix=None,
                        speculative=None, role=None, wall=None):
    return {
        "kind": KIND_SERVING,
        "step": int(step),
        "wall": float(wall if wall is not None else time.time()),
        "slot_occupancy": float(slot_occupancy),
        "queue_depth": int(queue_depth),
        "active_slots": int(active_slots),
        "prefill_tokens": int(prefill_tokens),
        "prefill_tokens_per_sec": float(prefill_tokens_per_sec),
        "decode_tokens": int(decode_tokens),
        "decode_steps": int(decode_steps),
        "decode_tokens_per_sec": float(decode_tokens_per_sec),
        "ttft": ttft,
        "tpot": tpot,
        "page_pool": page_pool,
        "prefix": prefix,
        "speculative": speculative,
        "role": None if role is None else str(role),
    }


def validate_step_record(rec):
    """Schema check for one record dict. Returns a list of problem
    strings; empty list = valid."""
    problems = []
    if not isinstance(rec, dict):
        return ["record is not a dict: {!r}".format(type(rec).__name__)]
    kind = rec.get("kind")
    if kind == KIND_TRAIN:
        want = TRAIN_STEP_KEYS
    elif kind == KIND_SERVING:
        want = SERVING_STEP_KEYS
    else:
        return ["unknown record kind {!r}".format(kind)]
    for key in want:
        if key not in rec:
            problems.append("missing key {!r}".format(key))
    extra = sorted(set(rec) - set(want))
    if extra:
        problems.append("unexpected key(s) {}".format(extra))
    if problems:
        return problems

    def num(key, allow_none=False):
        val = rec[key]
        if val is None and allow_none:
            return
        if isinstance(val, bool) or not isinstance(val, _NUMERIC):
            problems.append("{} is not a number: {!r}".format(key, val))

    for key in ("step", "wall"):
        num(key)
    if kind == KIND_TRAIN:
        for key in ("step_time_s", "loss_scale", "micro_steps",
                    "tokens_per_step", "tokens_per_sec_per_chip",
                    "model_flops_per_step", "mfu", "peak_flops_per_chip",
                    "n_devices", "phase_total_s", "skipped_steps"):
            num(key)
        for key in ("loss", "grad_norm"):
            num(key, allow_none=True)
        if not isinstance(rec["overflow"], bool):
            problems.append("overflow is not a bool")
        phases = rec["phases"]
        if not isinstance(phases, dict):
            problems.append("phases is not a dict")
        else:
            for name, val in phases.items():
                if isinstance(val, bool) or not isinstance(val, _NUMERIC) \
                        or val < 0:
                    problems.append(
                        "phase {!r} is not a nonnegative number: "
                        "{!r}".format(name, val))
            if phases and abs(sum(phases.values()) -
                              rec["phase_total_s"]) > 1e-6:
                problems.append("phase_total_s != sum(phases)")
        hbm = rec["hbm"]
        if not isinstance(hbm, dict) or "available" not in hbm:
            problems.append("hbm is not a dict with 'available'")
        for key in ("wire", "comm_overlap", "offload", "pipe"):
            if rec[key] is not None and not isinstance(rec[key], dict):
                problems.append("{} is neither null nor a dict".format(key))
    else:
        for key in ("slot_occupancy", "queue_depth", "active_slots",
                    "prefill_tokens", "prefill_tokens_per_sec",
                    "decode_tokens", "decode_steps",
                    "decode_tokens_per_sec"):
            num(key)
        role = rec["role"]
        if role is not None and role not in SERVING_ROLES:
            problems.append(
                "role is neither null nor one of {}: {!r}".format(
                    list(SERVING_ROLES), role))
        for key, want_sub in SERVING_SUBDICT_KEYS.items():
            sub = rec[key]
            if sub is None:
                continue
            if not isinstance(sub, dict):
                problems.append(
                    "{} is neither null nor a dict".format(key))
                continue
            for sub_key in want_sub:
                val = sub.get(sub_key)
                if isinstance(val, bool) or not isinstance(val, _NUMERIC):
                    problems.append(
                        "{}.{} is not a number: {!r}".format(
                            key, sub_key, val))
    return problems
