"""Classic ZeRO-Offload optimizer step, lowered onto the segment
executor.

This replaces the bespoke hand-scheduled shard pipeline that lived in
``engine._host_apply_step`` / ``engine._offload_update_loop``: the
same payloads (jitted overflow check, per-chunk D2H fetch, in-place
host Adam, coalesced H2D upload, jitted reshard) now run as a
:class:`~.plan.SegmentPlan` whose overlap — async D2H fetches streaming
ahead of the host Adam inside a bounded window, leaf uploads riding the
coalescing batcher behind the remaining chunks — is CONSTRUCTED by the
scheduler from declared deps instead of hand-interleaved loops.

Numerics are bit-exact with the bespoke implementation (and between
``serial`` and ``overlap`` modes): every chunk's Adam is elementwise on
disjoint views, the overflow/norm reductions are the same jitted
program, and the upload packing is value-preserving (pinned by
tests/unit/test_executor.py and the dryrun executor leg).

``build_update_plan(engine)`` with no payloads is the ABSTRACT twin
(``analysis.ir.plan_of``): the same topology from the host shard
registry's shapes alone, for the auditor.
"""
import os
import time

import numpy as np

import jax
import jax.numpy as jnp

from ..checkpointing import shard_key as _shard_key
from ..fp16 import loss_scaler as ls
from ..zero.transfer import H2DBatcher, chunk_rows, host_adam_chunk
from .plan import Segment, SegmentPlan


def _work_chunks(engine, flat_acc=None):
    """The flat (leaf, shard, row-chunk) work list of one offload step,
    derived from the HOST shard registry (replicated leaves dedupe to
    one entry — the same order the Adam consumes). With ``flat_acc``
    each item carries its live device grad buffer; without (the
    abstract/audit path) buffers stay None and only the topology is
    real."""
    hs = engine.host_state
    work = []           # (leaf_idx, shard_tup, buf, rows|None, buf_idx)
    shard_bufs = []
    for i, shards in enumerate(hs["shard_leaves"]):
        local = None
        if flat_acc is not None:
            local = {_shard_key(sh.index): sh.data
                     for sh in flat_acc[i].addressable_shards}
        for tup in shards:
            buf = local[_shard_key(tup[0])] if local is not None else None
            buf_idx = len(shard_bufs)
            shard_bufs.append(buf)
            chunks = chunk_rows(np.shape(tup[1]), engine._sub_group_size)
            whole = len(chunks) == 1
            for r0, r1 in chunks:
                work.append((i, tup, buf,
                             None if whole else (r0, r1), buf_idx))
    return work, shard_bufs


def resolve_adam_step(engine, sumsq, inv_scale, clip):
    """The host-Adam step preamble both lowered apply paths share
    (classic offload here, streamed in ``executor/stream.py``): grad
    norm + clip coefficient, the host step-counter bump, bias
    correction, and adam_w/kernel-lib resolution — one implementation
    so the two paths can never diverge. Returns
    ``(grad_norm, coef, hyper, bc1, bc2, adam_w, lib)``."""
    hs = engine.host_state
    hyper = engine._hyper()
    grad_norm = float(np.sqrt(float(sumsq)))
    coef = inv_scale
    if clip > 0 and grad_norm > clip:
        coef *= clip / (grad_norm + 1e-6)
    hs["step"] += 1
    step = hs["step"]
    beta1, beta2 = hyper["beta1"], hyper["beta2"]
    bias_correction = getattr(engine.optimizer, "bias_correction", True)
    bc1 = 1.0 - beta1 ** step if bias_correction else 1.0
    bc2 = 1.0 - beta2 ** step if bias_correction else 1.0
    adam_w = 1 if getattr(engine.optimizer, "adam_w_mode", True) else 0
    lib = engine._offload_lib()
    return grad_norm, coef, hyper, bc1, bc2, adam_w, lib


def build_update_plan(engine, work=None, payloads=None):
    """The offload update pipeline's segment plan: per-chunk
    ``d2h/<j> -> adam/<j>``, per-leaf ``upload/<i>`` after the leaf's
    last chunk, then ``upload_finish -> reshard``. ``payloads`` maps
    segment names to (run, start) callables; absent -> abstract plan
    (topology only, for ``ir.plan_of`` / the auditor)."""
    if work is None:
        work, _ = _work_chunks(engine)
    payloads = payloads or {}
    plan = SegmentPlan("offload_apply")
    plan.windows = {"d2h": engine._D2H_WINDOW}
    by_leaf = {}
    for j, item in enumerate(work):
        by_leaf.setdefault(item[0], []).append(j)
    upload_names = []
    leaf_bytes = {}
    for j, item in enumerate(work):
        i = item[0]
        rows = item[3]
        shape = np.shape(item[1][1])
        n = int(np.prod(shape)) if shape else 1
        if rows is not None and shape:
            n = (rows[1] - rows[0]) * \
                (int(np.prod(shape[1:])) if len(shape) > 1 else 1)
        leaf_bytes[i] = leaf_bytes.get(i, 0) + n * 4
        run, start = payloads.get("d2h/%d" % j, (None, None))
        plan.add(Segment(
            name="d2h/%d" % j, kind="transfer", async_ok=True,
            pool="d2h", phase="d2h_wait_s", run=run, start=start,
            nbytes=n * 4))
        run, _ = payloads.get("adam/%d" % j, (None, None))
        plan.add(Segment(
            name="adam/%d" % j, kind="host", deps=("d2h/%d" % j,),
            phase="host_adam_s", wait_phase="d2h_wait_s", run=run))
        if j == by_leaf[i][-1]:
            run, _ = payloads.get("upload/%d" % i, (None, None))
            plan.add(Segment(
                name="upload/%d" % i, kind="transfer",
                deps=tuple("adam/%d" % jj for jj in by_leaf[i]),
                phase="h2d_dispatch_s", run=run,
                nbytes=leaf_bytes[i]))
            upload_names.append("upload/%d" % i)
    run, _ = payloads.get("upload_finish", (None, None))
    plan.add(Segment(
        name="upload_finish", kind="transfer", deps=tuple(upload_names),
        phase="h2d_dispatch_s", run=run))
    run, _ = payloads.get("reshard", (None, None))
    plan.add(Segment(
        name="reshard", kind="compute", deps=("upload_finish",),
        phase="h2d_reshard_s", run=run))
    # reshard re-places the uploaded masters across the mesh — its
    # traffic price is the wire.py census-ground-truthed per-step bytes
    from .costs import price_plan, wire_collective_bytes
    wire = wire_collective_bytes(engine)
    price_plan(plan, engine=engine,
               nbytes={"reshard": wire} if wire else None)
    return plan


def run_offload_apply(engine):
    """The classic ZeRO-Offload optimizer step (engine
    ``_host_apply_step``): jitted overflow/norm check, then the lowered
    update plan; overflow skips the plan and resets the accumulators.
    Returns the metrics dict (and updates the loss scaler), exactly as
    the bespoke implementation did."""
    scaler = engine.state["scaler"]
    cur_scale = float(scaler.cur_scale)
    inv_scale = 1.0 / cur_scale
    clip = engine.gradient_clipping()

    # the same disjoint phase clocks the bespoke path reported;
    # "micros_and_check" includes waiting for the jitted micro steps to
    # finish — the check's value fetch is the first sync point
    phases = {"micros_and_check_s": 0.0, "d2h_wait_s": 0.0,
              "host_adam_s": 0.0, "h2d_dispatch_s": 0.0,
              "h2d_reshard_s": 0.0}
    engine.offload_phase_times = phases
    t_phase = time.time()
    check = engine._get_jit("offload_check", engine._offload_check_fn)
    finite, sumsq = check(engine.state["acc_grads"],
                          np.float32(inv_scale))
    hs = engine.host_state
    flat_acc = hs["treedef"].flatten_up_to(engine.state["acc_grads"])
    work, shard_bufs = _work_chunks(engine, flat_acc)
    engine.offload_work_chunks = len(work)

    # bounded async D2H warm-up: the first window of shard copies
    # streams behind the (round-trip) overflow fetch below; each d2h
    # segment's launch hook tops the window up from there. An unbounded
    # warm-up pins a device staging buffer per shard and OOMs at 1.5B.
    issued = [0]

    def _issue_upto(limit):
        while getattr(engine, "_async_d2h", True) and \
                issued[0] < min(limit, len(shard_bufs)):
            try:
                shard_bufs[issued[0]].copy_to_host_async()
            except Exception:  # noqa: BLE001 - plugin without async copy
                engine._async_d2h = False
                return
            issued[0] += 1

    _issue_upto(engine._D2H_WINDOW)
    # a sumsq that overflowed despite finite elements is an overflow
    # too: clipping against an inf norm would silently zero the update
    overflow = (not bool(finite)) or not np.isfinite(float(sumsq))
    phases["micros_and_check_s"] = time.time() - t_phase

    grad_norm = 0.0
    if not overflow:
        grad_norm, coef, hyper, bc1, bc2, adam_w, lib = \
            resolve_adam_step(engine, sumsq, inv_scale, clip)

        left_in_leaf = [0] * len(flat_acc)
        for i, *_ in work:
            left_in_leaf[i] += 1
        flat_params = [None] * len(flat_acc)

        # release the engine's references so device memory frees as the
        # plan consumes it: params' updated values come from the host
        # master; each acc leaf is dead once its last chunk fetched
        acc_specs = [(a.shape, a.dtype) for a in flat_acc]
        acc_shardings = [a.sharding for a in flat_acc]
        engine.state["params"] = None
        engine.state["acc_grads"] = None

        batcher = H2DBatcher(
            engine._h2d_bucket_elems, engine.compute_dtype,
            pool=engine._upload_pool(),
            jit_cache=engine._h2d_split_cache())

        payloads = {}
        for j, item in enumerate(work):
            payloads["d2h/%d" % j] = _d2h_payload(item, _issue_upto)
            payloads["adam/%d" % j] = _adam_payload(
                j, item, work, left_in_leaf, coef, hyper, bc1, bc2,
                adam_w, lib)
        for i in set(it[0] for it in work):
            payloads["upload/%d" % i] = (_upload_payload(
                engine, batcher, i, acc_specs, acc_shardings, hs,
                flat_acc), None)
        payloads["upload_finish"] = (_finish_payload(
            engine, batcher, flat_params, acc_specs, acc_shardings),
            None)
        payloads["reshard"] = (_reshard_payload(
            engine, flat_params, acc_specs, acc_shardings, hs), None)
        plan = build_update_plan(engine, work=work, payloads=payloads)

        try:
            engine.plan_executor().execute(plan, phases=phases)
        except BaseException:
            # a mid-step failure must not strand the engine with None
            # pytrees: the host masters hold the authoritative values —
            # rebuild params from them (best effort) and record the torn
            # step so a checkpoint taken after the re-raise carries the
            # fact instead of silently looking whole
            hs["torn_step"] = hs["step"]
            try:
                engine._restore_params_from_host(acc_specs,
                                                 acc_shardings, hs)
            except Exception:  # noqa: BLE001
                pass
            raise
        hs.pop("torn_step", None)
        if os.environ.get("DS_OFFLOAD_PROFILE"):
            # force the uploads/reshard to COMPLETE so the phase clock
            # captures the H2D wait (serializes the tail — profiling only)
            t0 = time.time()
            jax.block_until_ready(engine.state["params"])
            phases["h2d_reshard_s"] += time.time() - t0
    else:
        engine.state["acc_grads"] = jax.tree_util.tree_map(
            jnp.zeros_like, engine.state["acc_grads"])
        if "qg_error" in engine.state:
            # poisoned by the inf/nan grads this window quantized —
            # reset with the skip (mirrors _apply_step_fn)
            engine.state["qg_error"] = jax.tree_util.tree_map(
                jnp.zeros_like, engine.state["qg_error"])
    engine.state["scaler"] = ls.update_scale(scaler, overflow)
    return {"overflow": overflow, "grad_norm": grad_norm,
            "loss_scale": cur_scale}


# ----------------------------------------------------------- payloads
def _d2h_payload(item, issue_upto):
    def start(env):
        # ensure this chunk's buffer has an async copy in flight; the
        # scheduler's launch window bounds how far ahead this reaches
        issue_upto(item[4] + 1)

    def run(env):
        # writable fp32 copy for the in-place host Adam; a sub_group
        # row-chunk fetches only its slice
        rows = item[3]
        if rows is None:
            return np.array(item[2], dtype=np.float32)
        return np.array(item[2][rows[0]:rows[1]], dtype=np.float32)

    return run, start


def _adam_payload(j, item, work, left_in_leaf, coef, hyper, bc1, bc2,
                  adam_w, lib):
    def run(env):
        g = env["d2h/%d" % j]
        g *= coef              # unscale (+clip) in place on the host copy
        i, (idx, p, m, v), _, rows, _ = item
        if rows is not None:
            # sub_group chunk: in-place Adam on contiguous row-range
            # views of the host shard
            p = p[rows[0]:rows[1]]
            m = m[rows[0]:rows[1]]
            v = v[rows[0]:rows[1]]
        host_adam_chunk(lib, p, g, m, v, hyper, bc1, bc2, adam_w)
        # drop the consumed work reference so its buffers free
        work[j] = None
        left_in_leaf[i] -= 1

    return run, None


def _upload_payload(engine, batcher, i, acc_specs, acc_shardings, hs,
                    flat_acc):
    def run(env):
        # the leaf's last chunk stepped: queue its master shards on the
        # coalescing upload batcher (packing + device_put ride the
        # upload worker behind the remaining chunks' Adam)
        engine._enqueue_leaf_upload(
            batcher, i, acc_specs[i][0], acc_shardings[i],
            hs["shard_leaves"][i])
        flat_acc[i] = None

    return run


def _finish_payload(engine, batcher, flat_params, acc_specs,
                    acc_shardings):
    def run(env):
        uploaded = batcher.finish()
        engine.h2d_batches = batcher.batches
        engine.h2d_elems = batcher.elems
        engine.h2d_bucket_occupancy = batcher.occupancy()
        for i, sharding in enumerate(acc_shardings):
            flat_params[i] = engine._assemble_uploaded_leaf(
                uploaded, i, acc_specs[i][0], sharding)

    return run


def _reshard_payload(engine, flat_params, acc_specs, acc_shardings, hs):
    def run(env):
        engine._finish_offload_step(flat_params, acc_specs,
                                    acc_shardings, hs)

    return run
