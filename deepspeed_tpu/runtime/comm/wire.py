"""Per-step collective bytes-on-wire estimator for ZeRO configs.

An analytic model of the per-device wire volume the training step's
ZeRO collectives move, so the communication win of the ZeRO++ modes
(qwZ/hpZ/qgZ) is visible in BENCH_*.json and the dryrun even on the CPU
fallback rung where nothing rides a real interconnect.

Ring-collective pricing (what GSPMD lowers to on a mesh axis of size g):
  all-gather / reduce-scatter move ``payload * (g-1)/g`` bytes per device;
  an all-reduce is a reduce-scatter + all-gather: ``2 * payload * (g-1)/g``.

Counted per optimizer step (gas = gradient-accumulation micro-steps):
  * stage 3: each data-sharded param leaf is all-gathered
    ``gathers_per_micro`` times per micro-step (default 2 — forward +
    backward re-materialization; the shard-lint HLO census (PR 10,
    analysis/hlo.py) confirmed XLA rematerializes the explicit ring
    gathers for the backward rather than keeping the gathered weight
    live) over its gather group — the FULL data axis flat, only the
    ``data_shard`` sub-axis under hpZ. Tensor-parallel leaves move only
    their model-axis SHARE per device (``numel / plan.tp_ways``) —
    census ground truth the earlier estimate missed;
  * stage >= 2: each micro-step's gradients reduce-scatter over the
    full data axis; stage 0-1 all-reduce instead. The census also
    ground-truthed the REDUCTION dtype: the wgrad matmuls accumulate in
    fp32 and XLA reduces BEFORE the convert back to the grad dtype
    lands, so the wire moves fp32 — except for leaves gathered through
    an explicit custom-vjp ring (cm/qwZ), whose cotangent is
    constrained at the compute dtype by the custom_vjp boundary
    (``explicit_gather_grad_itemsize``);
  * stage 1-2: the updated params re-replicate once per step (the
    all-gather of updated partitions).

Quantized payloads price the codec's wire format: 1 byte/lane + one
scale (in the buffer's dtype) per ``block_size`` lanes. For qgZ this
prices the quantized reduce-scatter transport
(``quantized_reduce_scatter_local``); the pure-GSPMD engine path models
its numerics while the wire stays in the compute dtype — the JSON keys
are explicit about being estimates.
"""
import numpy as np

import jax

from .quantize import DEFAULT_BLOCK_SIZE

_FP32_BYTES = 4

# Nominal aggregate per-chip ICI bandwidth (bytes/s) for the analytic
# overlap model in overlap_report(): order-of-magnitude public figures,
# one home like mfu.PEAK_TFLOPS, keyed by the exact device_kind; an
# unknown kind raises. The cpu row is a nominal 10 GB/s kept only for
# the tier-1 StepRecords priced against it (see telemetry/mfu.py).
ICI_GBPS = {
    "TPU v2": 500.0, "TPU v3": 700.0, "TPU v4": 1200.0,
    "TPU v5 lite": 400.0, "TPU v5e": 400.0, "TPU v5": 1200.0,
    "TPU v5p": 1200.0, "TPU v6 lite": 700.0, "TPU v6e": 700.0,
    "cpu": 10.0,
}


def ici_bytes_per_s_for(device):
    """Nominal ICI bytes/s for one chip of ``device`` (a jax Device or a
    device-kind string)."""
    from ...telemetry.mfu import lookup_device_kind
    return lookup_device_kind(ICI_GBPS, device, "ICI bandwidth") * 1e9


def _ring_factor(group):
    return (group - 1) / group if group > 1 else 0.0


def decomposed_collective_bytes(payload_bytes, group, chunks=1):
    """Per-device wire bytes of a ring-DECOMPOSED all-gather or
    reduce-scatter of ``payload_bytes``: ``group - 1`` ppermute hops of
    one shard each — in any number of ``chunks`` pieces per hop —
    moving exactly ``payload * (g-1)/g`` bytes, IDENTICAL to the
    one-shot collective's ring pricing. ``chunks`` only changes the
    grain the scheduler can overlap, never the bytes (pinned by
    tests/unit/test_collective_matmul.py), which is why
    ``estimate_step_comm_bytes`` needs no fusion-aware branch: the
    estimates stay honest with collective_matmul on."""
    del chunks  # granularity, not volume
    return int(round(payload_bytes * _ring_factor(group)))


def overlap_report(wire_est, step_time_s, fused_classes, device):
    """Per-collective-class overlap efficiency for ONE step — the
    T3-style scoreboard ``compute / (compute + exposed_collective)``,
    embedded in the StepRecord as ``comm_overlap``.

    ANALYTIC estimate, not a measurement: each class's collective time
    is its ``wire_est`` bytes over the chip's nominal ICI bandwidth
    (``ici_bytes_per_s_for``); a ring-fused class exposes none of it
    (the hops hide under the partial GEMMs), an unfused class exposes
    all of it, and compute is the measured step wall minus the exposed
    total. ``fused_classes``: {"allgather": bool, "reduce": bool}.
    """
    if wire_est is None or not step_time_s or step_time_s <= 0:
        return None
    bw = ici_bytes_per_s_for(device)
    classes = {
        "allgather": float(wire_est.get("allgather_bytes_per_step", 0) or 0),
        "reduce": float(wire_est.get("reduce_bytes_per_step", 0) or 0),
    }
    # the 1-bit momentum exchange is its own class when live (the
    # compressed-comm tier, docs/onebit_adam.md)
    opt_bytes = float(wire_est.get("optimizer_bytes_per_step", 0) or 0)
    if opt_bytes:
        classes["optimizer"] = opt_bytes
    # per-class fp32-baseline reduction ratios from the estimator
    # (wire_est["reduction_x"]: weight/gradient/optimizer vocabulary)
    red = wire_est.get("reduction_x") or {}
    red_by_class = {"allgather": red.get("weight"),
                    "reduce": red.get("gradient"),
                    "optimizer": red.get("optimizer")}
    est = {k: v / bw for k, v in classes.items()}
    exposed = {k: (0.0 if fused_classes.get(k) else est[k])
               for k in classes}
    compute = max(float(step_time_s) - sum(exposed.values()), 1e-9)
    out = {}
    for k in classes:
        out[k] = {
            "bytes": int(classes[k]),
            "fused": bool(fused_classes.get(k)),
            "est_collective_s": round(est[k], 9),
            "exposed_s": round(exposed[k], 9),
            "overlap_efficiency": round(compute / (compute + exposed[k]),
                                        6),
            "reduction_x": red_by_class.get(k),
        }
    return out


def quantized_allreduce_bytes(numel, world, block_size=DEFAULT_BLOCK_SIZE,
                              levels=None, scale_itemsize=_FP32_BYTES,
                              min_component=0):
    """Per-device wire bytes of ONE in-collective quantized all-reduce
    (``quantized_all_reduce_local`` /
    ``hierarchical_all_reduce_local``): a ring reduce-scatter whose
    every hop moves one int8 chunk + its fp32 block scales (two
    collective-permute instructions per hop), then an int8 all-gather
    (+ scales gather). ``levels=(shard, replica)`` prices the two-level
    decomposition (2504.18658): the full payload over the shard group,
    the 1/shard chunk over the replica group. ``min_component`` drops
    per-INSTRUCTION components below the HLO census threshold so the
    estimate reconciles instruction-for-instruction
    (analysis/hlo.py)."""
    from .quantize import qc_padded_size
    padded = qc_padded_size(numel, world, block_size)

    def keep(b):
        return int(b) if b >= min_component else 0

    def level(n, g):
        if g <= 1:
            return 0
        chunk = n // g
        nblocks = chunk // block_size
        total = 0
        # ring RS: g-1 hops, each one q-chunk ppermute + one scales
        # ppermute (census prices a collective-permute at its payload)
        total += (g - 1) * (keep(chunk) +
                            keep(nblocks * scale_itemsize))
        # int8 AG back: result g*chunk -> (g-1)*chunk on the wire
        total += keep((g - 1) * chunk)
        total += keep((g - 1) * nblocks * scale_itemsize)
        return total

    if levels:
        shard, replica = levels
        assert shard * replica == world, (levels, world)
        return level(padded, shard) + level(padded // shard, replica)
    return level(padded, world)


def onebit_exchange_bytes(numel, world, scale_itemsize=_FP32_BYTES,
                          min_component=0, itemsize_bits=1):
    """Per-device wire bytes of ONE compressed momentum allreduce
    (runtime/comm/onebit.py): the worker ``all_to_all`` of packed sign
    chunks + scalar-scale all-gather, then the server sign all-gather +
    its scales — the reference 2-phase pipeline. ``itemsize_bits=32``
    prices the SAME exchange uncompressed (the fp32-equivalent
    denominator of the optimizer-class reduction ratio)."""
    from .onebit import onebit_padded_size
    padded = onebit_padded_size(numel, world)
    ring = _ring_factor(world)
    payload = padded * itemsize_bits // 8

    def keep(b):
        return int(b) if b >= min_component else 0

    total = 0
    total += keep(int(round(payload * ring)))              # worker a2a
    total += keep(int(round(world * scale_itemsize * ring)))
    total += keep(int(round(payload * ring)))              # server AG
    total += keep(int(round(world * scale_itemsize * ring)))
    return total


def _payload(numel, itemsize, quantized, scale_itemsize, block_size):
    if not quantized:
        return numel * itemsize
    nblocks = -(-numel // block_size)
    return numel * 1 + nblocks * scale_itemsize


def _price_tree(params, eligible_fn, stage, dp, gather_group, gas,
                compute_itemsize, grad_itemsize, quantized_weights,
                quantized_gradients, block_size, gathers_per_micro=2,
                explicit_gather_grad_itemsize=None, tp_ways_fn=None,
                replicate_itemsize=None, min_component=0):
    """The one pricing body both entry points share.

    ``eligible_fn(path, shape, numel) -> bool``: is this leaf a stage-3
    data-sharded (per-micro-step-gathered) param. Weight gathers price
    the shape-preserving codec (blocks tile the last dim — what
    ``qwz_gather`` actually ships); gradient reduces price the FLAT
    codec (``quantize_with_error_feedback`` uses ``block_size``-lane
    flat blocks). ``explicit_gather_grad_itemsize``: when set, eligible
    stage-3 leaves' gradient reduces price THIS itemsize (the explicit
    cm/qwZ ring cotangent stays in the compute dtype) while every other
    leaf reduces at ``grad_itemsize``. ``tp_ways_fn(path, shape)``:
    tensor-parallel split degree — per-device data-axis wire moves only
    the leaf's model-axis share (census ground truth; eligibility still
    judges the GLOBAL leaf).
    """
    from .quantize import _lastdim_block
    from ..zero.partition import _path_str
    if replicate_itemsize is None:
        replicate_itemsize = compute_itemsize
    totals = {"allgather_bytes": 0.0, "reduce_bytes": 0.0}

    def leaf(path, p):
        shape = np.shape(p)
        numel = int(np.prod(shape)) if shape else 1
        wire_numel = numel
        if tp_ways_fn is not None:
            wire_numel = numel // max(int(tp_ways_fn(path, shape)), 1)
        eligible = stage >= 3 and eligible_fn(path, shape, numel)
        if eligible:
            wblk = _lastdim_block(shape[-1], block_size) if shape else 1
            per_gather = _payload(wire_numel, compute_itemsize,
                                  quantized_weights, compute_itemsize,
                                  wblk) * _ring_factor(gather_group)
            totals["allgather_bytes"] += \
                gathers_per_micro * gas * per_gather
        elif stage in (1, 2) and dp > 1 and numel >= dp and \
                any(d % dp == 0 for d in shape):
            # updated-partition re-replication, once per step (the plan
            # only shards — and thus re-gathers — leaves with a
            # dp-divisible dim; others stay replicated). Census ground
            # truth (PR 12, mirroring PR 10's reduce-dtype finding): the
            # partitioner gathers the MASTER-dtype value and the convert
            # to the compute dtype lands after, so the wire moves
            # ``replicate_itemsize`` (fp32 under mixed precision).
            # ``min_component`` drops per-leaf instructions below the
            # census threshold when reconciling.
            leaf_wire = wire_numel * replicate_itemsize * _ring_factor(dp)
            if leaf_wire >= min_component:
                totals["allgather_bytes"] += leaf_wire
        if dp > 1:
            gi = grad_itemsize
            if eligible and explicit_gather_grad_itemsize is not None:
                gi = explicit_gather_grad_itemsize
            grad_payload = _payload(wire_numel, gi, quantized_gradients,
                                    gi, block_size)
            factor = _ring_factor(dp) if stage >= 2 \
                else 2 * _ring_factor(dp)
            totals["reduce_bytes"] += gas * grad_payload * factor

    jax.tree_util.tree_map_with_path(
        lambda kp, p: leaf(_path_str(kp), p), params)
    out = {k: int(round(v)) for k, v in totals.items()}
    out["total_bytes"] = out["allgather_bytes"] + out["reduce_bytes"]
    return out


def estimate_step_comm_bytes(plan, params, gas=1, compute_itemsize=4,
                             grad_itemsize=4, quantized_weights=False,
                             quantized_gradients=False,
                             block_size=DEFAULT_BLOCK_SIZE,
                             gathers_per_micro=2,
                             explicit_gather_grad_itemsize=None,
                             replicate_itemsize=None, min_component=0,
                             _force_flat_fp32=False):
    """Per-device collective bytes for ONE optimizer step under ``plan``.

    Returns ``{"allgather_bytes", "reduce_bytes", "total_bytes"}``.
    ``gathers_per_micro``: stage-3 weight materializations per
    micro-step — 2 (forward + backward re-materialization, the census-
    confirmed default). ``_force_flat_fp32`` reprices as flat (full data
    axis) fp32 with no quantization — the comparison baseline —
    INCLUDING flat-plan leaf eligibility, so the baseline never bills
    gathers for a leaf flat ZeRO-3 would keep replicated (it keeps the
    caller's gather count: the baseline compares wire FORMATS, not
    schedules).
    """
    if _force_flat_fp32:
        compute_itemsize = grad_itemsize = _FP32_BYTES
        quantized_weights = quantized_gradients = False
        explicit_gather_grad_itemsize = None
        replicate_itemsize = _FP32_BYTES
    return _price_tree(
        params,
        lambda path, shape, numel: plan.param_is_data_sharded(
            path, shape, flat=_force_flat_fp32),
        stage=plan.stage, dp=plan.dp_size,
        gather_group=plan.dp_size if _force_flat_fp32
        else plan.param_shard_size,
        gas=gas, compute_itemsize=compute_itemsize,
        grad_itemsize=grad_itemsize,
        quantized_weights=quantized_weights,
        quantized_gradients=quantized_gradients, block_size=block_size,
        gathers_per_micro=gathers_per_micro,
        explicit_gather_grad_itemsize=explicit_gather_grad_itemsize,
        tp_ways_fn=plan.tp_ways, replicate_itemsize=replicate_itemsize,
        min_component=min_component)


def project_comm_bytes(params, stage, dp, gas=1, compute_itemsize=4,
                       grad_itemsize=4, quantized_weights=False,
                       hierarchical_partition=0, quantized_gradients=False,
                       persistence_threshold=100000,
                       block_size=DEFAULT_BLOCK_SIZE):
    """Price a param tree's ZeRO collectives at a HYPOTHETICAL dp degree
    — no mesh/plan needed. Leaf eligibility approximates
    ZeroShardingPlan's rule (numel >= max(threshold, group) and a
    group-divisible dim). Lets a single-device CPU bench still report
    what the config would move on a pod."""
    gather_group = hierarchical_partition \
        if stage >= 3 and hierarchical_partition > 1 else dp
    return _price_tree(
        params,
        lambda path, shape, numel: bool(shape) and
        numel >= max(persistence_threshold, gather_group) and
        any(d % gather_group == 0 for d in shape),
        stage=stage, dp=dp, gather_group=gather_group, gas=gas,
        compute_itemsize=compute_itemsize, grad_itemsize=grad_itemsize,
        quantized_weights=quantized_weights,
        quantized_gradients=quantized_gradients, block_size=block_size)


def _compressed_comm_classes(engine, min_component=0):
    """The compressed-comm tier's per-step byte classes, when live:
    returns (reduce_bytes, optimizer_bytes, fp32_equiv_optimizer_bytes,
    regime) or None on the GSPMD oracle path.

    OneBitAdam warmup / quantized-collectives: the gradient (reduce)
    class is the in-collective int8 exchange — per STEP under OneBitAdam
    (the engine averages the accumulated stacked grads once in the
    apply), per MICRO-step in pure exchange mode — or the fp32 stacked
    mean for onebit-without-qc warmup. OneBitAdam frozen: gradients
    never cross the wire (reduce = 0); the 1-bit momentum exchange is
    its own ``optimizer`` class."""
    mode_fn = getattr(engine, "_local_grad_mode", None)
    mode = mode_fn() if mode_fn is not None else None
    if mode is None:
        return None
    import jax
    params = engine.state["params"] if engine.state is not None and \
        engine.state.get("params") is not None else engine.model.params
    numel = sum(int(np.prod(np.shape(p))) if np.shape(p) else 1
                for p in jax.tree_util.tree_leaves(params))
    dp = engine.zero_plan.dp_size
    gas = engine.gradient_accumulation_steps()
    qc = getattr(engine, "_qc", None)
    levels = None
    if isinstance(engine._batch_axis, tuple):
        replica_axis, shard_axis = engine._batch_axis
        levels = (int(engine.mesh.shape[shard_axis]),
                  int(engine.mesh.shape[replica_axis]))

    def qc_bytes():
        return quantized_allreduce_bytes(
            numel, dp, qc.block_size, levels=levels,
            min_component=min_component)

    if mode == "exchange":
        return gas * qc_bytes(), 0, 0, None
    frozen = engine._onebit_frozen()
    if frozen:
        opt = onebit_exchange_bytes(numel, dp,
                                    min_component=min_component)
        equiv = onebit_exchange_bytes(numel, dp, itemsize_bits=32,
                                      min_component=min_component)
        return 0, opt, equiv, "frozen"
    if getattr(engine, "_qc_enabled", False):
        # one exchange per step: the engine averages the ACCUMULATED
        # stacked grads through the quantized ring in the apply step
        return qc_bytes(), 0, 0, "warmup"
    # uncompressed warmup: the per-leaf stacked mean lowers to fp32
    # all-reduces over the data axis
    return int(round(2 * _ring_factor(dp) * _FP32_BYTES * numel)), 0, 0, \
        "warmup"


def estimate_engine_comm_bytes(engine, min_component=0):
    """The engine's live config priced against the flat-fp32 baseline.

    JSON-ready dict: current-config and fp32-flat per-step bytes plus
    reduction ratios (>= 1 means the config moves fewer bytes).
    ``min_component`` drops per-instruction components below the HLO
    census threshold — pass the census ``min_bytes`` when reconciling
    (analysis/hlo.reconcile_wire); the default 0 reports full bytes.
    """
    import jax.numpy as jnp
    plan = engine.zero_plan
    params = engine.state["params"] if engine.state is not None \
        else engine.model.params
    compute_itemsize = jnp.dtype(engine.compute_dtype).itemsize
    gas = engine.gradient_accumulation_steps()
    # census-ground-truthed step model (see module docstring): weights
    # re-materialize in the backward (2 gathers/micro — XLA recomputes
    # the ring chains rather than keeping gathered weights live);
    # gradients reduce in the fp32 wgrad-accumulation dtype, except
    # leaves routed through an explicit custom-vjp ring (cm/qwZ) whose
    # cotangent the boundary pins to the compute dtype; TP leaves move
    # only their model-axis share per device
    explicit_gather = bool(getattr(engine, "_cm_zero3", False) or
                           getattr(engine, "_qwz_enabled", False))
    cur = estimate_step_comm_bytes(
        plan, params, gas=gas, compute_itemsize=compute_itemsize,
        grad_itemsize=_FP32_BYTES,
        quantized_weights=engine.zero_quantized_weights(),
        quantized_gradients=engine.zero_quantized_gradients(),
        explicit_gather_grad_itemsize=compute_itemsize
        if explicit_gather else None,
        # stage 1-2 re-replication moves the MASTER dtype (census ground
        # truth: the partitioner gathers before the compute-dtype
        # convert lands)
        replicate_itemsize=_FP32_BYTES if engine.mixed_precision
        else compute_itemsize,
        min_component=min_component)
    base = estimate_step_comm_bytes(plan, params, gas=gas,
                                    _force_flat_fp32=True)

    def ratio(b, c):
        return round(b / c, 2) if c else None

    # compressed-comm tier (OneBitAdam / quantized_collectives): the
    # gradient class is replaced by the live exchange's bytes, and the
    # frozen-regime 1-bit momentum exchange is its own class
    comp = _compressed_comm_classes(engine, min_component=min_component)
    opt_bytes = equiv_opt = 0
    onebit_regime = None
    if comp is not None:
        cur = dict(cur)
        cur["reduce_bytes"], opt_bytes, equiv_opt, onebit_regime = comp
        cur["total_bytes"] = cur["allgather_bytes"] + \
            cur["reduce_bytes"] + opt_bytes

    out = {
        "zero_stage": plan.stage,
        "quantized_weights": engine.zero_quantized_weights(),
        "hierarchical_partition": engine.zero_hierarchical_partition(),
        "quantized_gradients": engine.zero_quantized_gradients(),
        "allgather_bytes_per_step": cur["allgather_bytes"],
        "reduce_bytes_per_step": cur["reduce_bytes"],
        "optimizer_bytes_per_step": opt_bytes,
        "total_bytes_per_step": cur["total_bytes"],
        "fp32_flat_allgather_bytes_per_step": base["allgather_bytes"],
        "fp32_flat_reduce_bytes_per_step": base["reduce_bytes"],
        "fp32_equiv_optimizer_bytes_per_step": equiv_opt,
        "fp32_flat_total_bytes_per_step": base["total_bytes"],
        "allgather_reduction_x": ratio(base["allgather_bytes"],
                                       cur["allgather_bytes"]),
        "total_reduction_x": ratio(base["total_bytes"],
                                   cur["total_bytes"]),
        # per-class fp32-baseline ratios (the bench extra.comm block):
        # weight = the param all-gathers; gradient = every byte carrying
        # gradient information (the grad reduce + the frozen-regime
        # momentum exchange that replaces it); optimizer = the momentum
        # exchange vs the SAME exchange uncompressed
        "reduction_x": {
            "weight": ratio(base["allgather_bytes"],
                            cur["allgather_bytes"]),
            "gradient": ratio(base["reduce_bytes"],
                              cur["reduce_bytes"] + opt_bytes),
            "optimizer": ratio(equiv_opt, opt_bytes),
        },
    }
    if onebit_regime is not None:
        out["onebit_regime"] = onebit_regime
    if getattr(engine, "_qc_enabled", False):
        qc = engine._qc
        out["quantized_collectives"] = {
            "enabled": True,
            "dtype": qc.dtype,
            "block_size": int(qc.block_size),
            "hierarchical": isinstance(engine._batch_axis, tuple),
        }
    cm = getattr(engine, "_cm", None)
    if cm is not None and cm.enabled:
        # marker only: a ring-decomposed collective moves the bytes of
        # the one-shot collective (decomposed_collective_bytes), so the
        # byte totals above hold verbatim with fusion on
        out["collective_matmul"] = {
            "enabled": True,
            "zero_gather_fused": bool(getattr(engine, "_cm_zero3", False)),
            "tensor_parallel_fused": bool(getattr(engine, "_cm_tp",
                                                  False)),
            "chunks": int(cm.chunks),
        }
    if plan.dp_size <= 1:
        # single-device rung (the CPU bench fallback): nothing crosses a
        # wire, so also project the same config at a nominal pod scale to
        # keep the configured comm behavior visible in the artifact
        dp = 8
        zc = engine._config.zero_config
        proj = project_comm_bytes(
            params, plan.stage, dp, gas=gas,
            compute_itemsize=compute_itemsize,
            grad_itemsize=compute_itemsize,
            quantized_weights=bool(zc.quantized_weights),
            hierarchical_partition=int(zc.hierarchical_partition or 0),
            quantized_gradients=bool(zc.quantized_gradients),
            persistence_threshold=zc.param_persistence_threshold)
        proj_base = project_comm_bytes(
            params, plan.stage, dp, gas=gas,
            persistence_threshold=zc.param_persistence_threshold)
        out["projected_dp{}".format(dp)] = {
            "total_bytes_per_step": proj["total_bytes"],
            "fp32_flat_total_bytes_per_step": proj_base["total_bytes"],
            "total_reduction_x": ratio(proj_base["total_bytes"],
                                       proj["total_bytes"]),
        }
    return out
