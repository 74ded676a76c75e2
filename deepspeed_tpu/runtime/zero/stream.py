"""Streamed ZeRO-3 parameter offload: train beyond-HBM models on one chip.

Reference parity: ZeRO-3 Offload's parameter offload
(`deepspeed/runtime/zero/stage3.py:2281`, `partition_parameters.py:341`)
— the machinery behind the reference's 13B/40B-params-on-one-32GB-V100
story. There, parameters live in CPU memory and are fetched into device
memory per-submodule by the PartitionedParameterCoordinator. Here the
same discipline is re-founded for the jit world:

  * the fp32 master (and Adam moments) live in HOST memory
    (``engine.host_state``), exactly like classic ZeRO-Offload;
  * compute parameters have NO resident device copy at all. Each step
    streams them into HBM one LAYER GROUP at a time through the
    coalesced-transfer batcher (transfer.py), double-buffered: group
    k+1's H2D rides the upload worker while group k's jitted segment
    computes (async dispatch);
  * the forward runs segment-by-segment (embed -> block groups -> head)
    keeping only the group-boundary activations; the backward re-streams
    each group in reverse and computes its VJP (recomputing the group
    forward — the streaming analogue of activation checkpointing, ~1
    extra forward of compute for O(boundary) activation memory);
  * gradients leave the device as ONE packed fp32 buffer per segment
    (async D2H), are split into per-leaf host views, and accumulated —
    tied leaves (GPT-2's wte in embed AND head) sum their contributions;
  * the optimizer step is the host Adam, chunked by ``sub_group_size``.

HBM high-water mark: ~2 layer groups of parameters (current + prefetch)
+ the largest of the embed/head segments + boundary activations + one
segment's gradients — governed by ``stage3_max_live_parameters`` (the
live-parameter budget sizes the groups), NOT by total model size. That
raises the trainable ceiling past params+grads <= HBM
(docs/zero3_offload.md; demonstrated by tests/perf/bench_beyond_hbm.py).
"""
import time

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ...utils.logging import logger, log_dist
from .transfer import H2DBatcher


def _full_index(shape):
    """The whole-leaf shard index (streamed masters are unsharded)."""
    return tuple(slice(0, d, None) for d in shape)


# Donation sets of the streamed segment programs, by program key — the
# ONE declaration the jit path and the shard-lint auditor
# (analysis/programs.py) both read, so the audited donation list cannot
# drift from the executed one. Only inputs with an aliasable output are
# donated (XLA donation IS input->output aliasing; donating the dead
# uploaded weights would only buy a "donated buffer unusable" warning):
#
#   * ``h_grad`` donates the final boundary activation (arg 1) into its
#     own cotangent d_x — the (B, S, d) head-input buffer stops
#     double-residing during the loss/backward segment;
#   * ``g_bwd`` donates the incoming cotangent dx (arg 2) into d_xi —
#     the backward sweep updates its gradient wave in place instead of
#     holding two (B, S, d) buffers per group hop.
#
# Forward segments donate nothing: their activation inputs are KEPT as
# boundary activations for the backward recompute. Donation frees one
# (B, S, d) compute-dtype buffer per backward hop plus one at the head
# — at the PR 4 bench shapes (batch 8 x seq 1024 x d_model 1600, bf16)
# that is ~26 MB less live HBM through the entire backward sweep.
STREAM_DONATE = {
    "e_fwd": (), "g_fwd": (), "h_grad": (1,), "g_bwd": (2,), "e_bwd": (),
}


def _numel(tree):
    return sum(int(np.prod(np.shape(leaf))) if np.shape(leaf) else 1
               for leaf in jax.tree_util.tree_leaves(tree))


class StreamedOffloadRunner:
    """Drives the streamed train/eval step for one engine.

    The engine owns the host master/moment registry
    (``host_state["shard_leaves"]``, one full-leaf entry per master
    leaf); the runner re-derives its segment views from it each step, so
    a checkpoint load (which replaces the arrays) needs no rebinding
    hook.
    """

    def __init__(self, engine):
        self.engine = engine
        self.spec = engine.model.stream_spec
        if self.spec is None:
            raise ValueError(
                "zero_optimization.cpu_offload_params needs a model with "
                "a stream_spec (runtime/model.py StreamSpec); {} does "
                "not expose one".format(engine.model.name))
        if jax.process_count() > 1:
            raise NotImplementedError(
                "streamed parameter offload is single-process (multi-"
                "process runs keep classic cpu_offload)")
        self.mesh = engine.mesh
        self.cdtype = np.dtype(engine.compute_dtype)
        self._devices = list(self.mesh.devices.flat)
        self._replicated = NamedSharding(self.mesh, P())
        self._jit_cache = {}
        self._grad_bufs = None
        self._micro_finites = []
        self._micro_sumsqs = []
        self._micros_in_step = 0
        self.phase_times = {}
        # per-step upload accounting for telemetry (transfer_snapshot):
        # bucket occupancy + live-param upload volume, T3-style
        self._step_upload_batches = 0
        self._step_upload_elems = 0
        self._segment_upload_bytes_peak = 0
        # comm.collective_matmul composes with streaming through the
        # MODEL config, not the params: uploads land replicated, so the
        # ZeRO-3 ring gather has nothing to do here (the engine resolves
        # _cm_zero3 False under cpu_offload_params), but a TP model axis
        # still routes the segments' qkv/fc/proj GEMMs through the fused
        # ring ops — the segment programs built by _run pick the binding
        # up from the config at trace time.
        self.collective_matmul = getattr(
            getattr(engine.model, "config", None), "collective_matmul",
            None) is not None
        if self.collective_matmul:
            log_dist(
                "streamed offload: collective_matmul binding live — "
                "segment TP GEMMs run ring-fused", ranks=[0])
        self._plan_groups()

    # ------------------------------------------------------------ planning
    def _host_trees(self):
        """(master, exp_avg, exp_avg_sq) fp32 numpy trees, views of the
        engine's host_state registry."""
        hs = self.engine.host_state
        td = hs["treedef"]
        return (td.unflatten([s[0][1] for s in hs["shard_leaves"]]),
                td.unflatten([s[0][2] for s in hs["shard_leaves"]]),
                td.unflatten([s[0][3] for s in hs["shard_leaves"]]))

    def _plan_groups(self):
        """Size layer groups so ~2 groups (live + prefetched) plus the
        larger terminal segment fit ``stage3_max_live_parameters``."""
        masters, _, _ = self._host_trees()
        embed_t, blocks, head_t = self.spec.split(masters)
        self.n_layers = len(blocks)
        block_elems = [_numel(b) for b in blocks]
        terminal = max(_numel(embed_t), _numel(head_t))
        budget = self.engine.zero_plan.max_live_parameters
        if budget is None:
            budget = 10 ** 9
        per_group = max((budget - terminal) // 2, 1)
        groups, start, acc = [], 0, 0
        for i, n in enumerate(block_elems):
            if i > start and acc + n > per_group:
                groups.append((start, i))
                start, acc = i, 0
            acc += n
        groups.append((start, len(blocks)))
        self.groups = groups
        min_live = 2 * max(block_elems) + terminal
        if budget < min_live:
            logger.warning(
                "stage3_max_live_parameters=%d is below the streamed "
                "minimum for this model (~%d: two 1-layer groups + the "
                "largest terminal segment); streaming proceeds at that "
                "minimum", budget, min_live)
        log_dist(
            "streamed offload: {} layers in {} groups (budget {:,} "
            "elements, terminal {:,})".format(
                self.n_layers, len(groups), budget, terminal), ranks=[0])

    def release(self):
        """Drop this runner's compiled programs and live device buffers.
        ``engine.close()`` calls it on elastic teardown so the outgoing
        topology's HBM is free before the replacement engine compiles;
        the runner stays structurally valid (a later step would simply
        re-trace)."""
        self._jit_cache.clear()
        self._grad_bufs = None
        self._micro_finites = []
        self._micro_sumsqs = []

    # ------------------------------------------------------------- uploads
    def _start_upload(self, leaves):
        """Queue a segment's host leaves for coalesced upload to every
        mesh device (replicated); packing+device_put ride the background
        upload worker so they overlap the current segment's compute."""
        eng = self.engine
        batcher = H2DBatcher(eng._h2d_bucket_elems, self.cdtype,
                             pool=eng._upload_pool(),
                             jit_cache=eng._h2d_split_cache())
        for li, arr in enumerate(leaves):
            for dev in self._devices:
                batcher.add(li, arr, dev)
        batcher.flush()
        return batcher, [np.shape(a) for a in leaves]

    def _finish_upload(self, pending, bill_wait=True):
        """Block on a queued upload; return replicated global arrays.
        ``bill_wait=False`` when the executor runs this on its h2d
        worker — there the EXPOSED wait is billed by the scheduler at
        the consuming compute segment, so billing the worker's own wall
        here would double-count it."""
        t0 = time.time()
        batcher, shapes = pending
        res = batcher.finish()
        out = []
        for li, shape in enumerate(shapes):
            singles = list(res[li].values())
            out.append(jax.make_array_from_single_device_arrays(
                shape, self._replicated, singles))
        if bill_wait:
            self.phase_times["h2d_wait_s"] = \
                self.phase_times.get("h2d_wait_s", 0.0) + \
                (time.time() - t0)
        # upload accounting (per device replica; telemetry snapshot)
        elems = sum(int(np.prod(s)) if s else 1 for s in shapes)
        self._step_upload_batches += batcher.batches
        self._step_upload_elems += elems * len(self._devices)
        self._segment_upload_bytes_peak = max(
            self._segment_upload_bytes_peak,
            elems * self.cdtype.itemsize)
        return tuple(out)

    # ------------------------------------------------------------ jit fns
    def _jit(self, key, builder):
        if key not in self._jit_cache:
            from ..executor.jit import jit_program
            self._jit_cache[key] = self.engine._first_call(
                "stream." + str(key[0]), key, jit_program(
                    builder(), donate=STREAM_DONATE.get(key[0], ())))
        return self._jit_cache[key]

    def _run(self, key, builder, *args):
        """Invoke one streamed-segment program, accumulating its
        cost_analysis flops into the engine's step window when telemetry
        is live (cached per key — one lowering, then a dict lookup)."""
        fn = self._jit(key, builder)
        self.engine._tele_add_flops(("stream",) + tuple(key), fn, *args)
        return fn(*args)

    def transfer_snapshot(self, exec_stats=None):
        """Per-step upload/overlap stats for the telemetry record in
        the unified ``SEGMENT_KEYS`` schema (telemetry/record.py — the
        same shape the classic-offload executor stats use, validated by
        bin/check_bench_schema.py): T3-style overlap efficiency, bucket
        occupancy of the coalesced H2D batcher, and the executed plan's
        per-kind walls when the engine's PlanExecutor ran this step.
        Read-only — safe as a debugging probe; the telemetry emit path
        resets the per-step counters afterwards via
        reset_step_counters()."""
        eng = self.engine
        phases = getattr(eng, "offload_phase_times", None) or {}
        compute = sum(phases.get(k, 0.0) for k in
                      ("compute_fwd_s", "compute_bwd_s", "host_adam_s"))
        waits = sum(phases.get(k, 0.0) for k in
                    ("h2d_wait_s", "d2h_grads_s"))
        bucket_elems = eng._h2d_bucket_elems
        batches = self._step_upload_batches
        exec_stats = exec_stats or {}
        snap = {
            "plan_segments": int(exec_stats.get("plan_segments", 0)),
            "per_kind": exec_stats.get("per_kind", {}),
            "upload_batches": batches,
            "upload_elems": self._step_upload_elems,
            "upload_bytes": self._step_upload_elems *
            self.cdtype.itemsize,
            "segment_upload_bytes_peak": self._segment_upload_bytes_peak,
            "bucket_elems": bucket_elems,
            "bucket_occupancy": round(
                self._step_upload_elems / (batches * bucket_elems), 4)
            if batches and bucket_elems else None,
            "overlap_efficiency": round(compute / (compute + waits), 4)
            if (compute + waits) > 0 else None,
            "groups": len(self.groups),
            "collective_matmul": self.collective_matmul,
        }
        return snap

    def reset_step_counters(self):
        """Open the next step's upload-accounting window (called by the
        telemetry emit path after it embeds transfer_snapshot())."""
        self._step_upload_batches = 0
        self._step_upload_elems = 0
        self._segment_upload_bytes_peak = 0

    @staticmethod
    def _pack_grads(grad_leaves, finite, sumsq):
        """Segment gradients -> ONE fp32 vector [grads..., finite,
        sumsq]: a single D2H fetch carries the grads and the overflow/
        norm reductions."""
        flats = [g.astype(jnp.float32).ravel() for g in grad_leaves]
        return jnp.concatenate(
            flats + [finite.astype(jnp.float32)[None], sumsq[None]])

    @staticmethod
    def _finite_sumsq(grad_leaves, inv_scale):
        finite = jnp.bool_(True)
        sumsq = jnp.float32(0)
        for g in grad_leaves:
            finite = jnp.logical_and(finite, jnp.isfinite(g).all())
            g32 = g.astype(jnp.float32) * inv_scale
            sumsq = sumsq + jnp.sum(g32 * g32)
        return finite, sumsq

    def _embed_fwd_fn(self, e_def, has_rng):
        spec = self.spec

        def fn(e_leaves, batch, key):
            et = jax.tree_util.tree_unflatten(e_def, list(e_leaves))
            return spec.embed_apply(et, batch,
                                    key if has_rng else None, True)

        return fn

    def _group_fwd_fn(self, b_defs, has_rng):
        spec = self.spec

        def fn(b_leaves_tuple, x, keys):
            for i, (bdef, bl) in enumerate(zip(b_defs, b_leaves_tuple)):
                bt = jax.tree_util.tree_unflatten(bdef, list(bl))
                x = spec.block_apply(bt, x,
                                     keys[i] if has_rng else None, True)
            return x

        return fn

    def _group_bwd_fn(self, b_defs, has_rng):
        fwd = self._group_fwd_fn(b_defs, has_rng)
        pack = self._pack_grads
        fs = self._finite_sumsq

        def fn(b_leaves_tuple, x_in, dx, keys, inv_scale):
            _, vjp = jax.vjp(lambda bl, xi: fwd(bl, xi, keys),
                             b_leaves_tuple, x_in)
            d_bl, d_xi = vjp(dx)
            leaves = [g for bl in d_bl for g in bl]
            finite, sumsq = fs(leaves, inv_scale)
            return d_xi, pack(leaves, finite, sumsq)

        return fn

    def _head_grad_fn(self, h_def, has_rng):
        spec = self.spec
        pack = self._pack_grads
        fs = self._finite_sumsq

        def fn(h_leaves, x, batch, key, scale, inv_scale):
            def loss_fn(hl, xx):
                ht = jax.tree_util.tree_unflatten(h_def, list(hl))
                loss = spec.head_apply(ht, xx, batch,
                                       key if has_rng else None, True)
                return loss.astype(jnp.float32) * scale, loss

            (_, loss), (d_h, d_x) = jax.value_and_grad(
                loss_fn, argnums=(0, 1), has_aux=True)(h_leaves, x)
            finite, sumsq = fs(list(d_h), inv_scale)
            return loss, d_x, pack(list(d_h), finite, sumsq)

        return fn

    def _embed_bwd_fn(self, e_def, has_rng):
        spec = self.spec
        pack = self._pack_grads
        fs = self._finite_sumsq

        def fn(e_leaves, batch, dx, key, inv_scale):
            _, vjp = jax.vjp(
                lambda el: spec.embed_apply(
                    jax.tree_util.tree_unflatten(e_def, list(el)), batch,
                    key if has_rng else None, True), e_leaves)
            (d_el,) = vjp(dx)
            finite, sumsq = fs(list(d_el), inv_scale)
            return pack(list(d_el), finite, sumsq)

        return fn

    def _eval_fn(self, e_def, b_defs_by_k, h_def):
        """Segment-streamed eval loss (dropout off, no grads)."""
        spec = self.spec

        def embed(e_leaves, batch):
            et = jax.tree_util.tree_unflatten(e_def, list(e_leaves))
            return spec.embed_apply(et, batch, None, False)

        def group(b_defs):
            def fn(b_leaves_tuple, x):
                for bdef, bl in zip(b_defs, b_leaves_tuple):
                    bt = jax.tree_util.tree_unflatten(bdef, list(bl))
                    x = spec.block_apply(bt, x, None, False)
                return x
            return fn

        def head(h_leaves, x, batch):
            ht = jax.tree_util.tree_unflatten(h_def, list(h_leaves))
            return spec.head_apply(ht, x, batch, None, False)

        return embed, group, head

    # ------------------------------------------------------------ binding
    def _bind(self):
        """Per-step registry: segment views of the host master/moments
        plus the slot map that dedupes shared (tied) leaves."""
        masters, ms, vs = self._host_trees()
        e_m, b_m, h_m = self.spec.split(masters)
        e_mm, b_mm, h_mm = self.spec.split(ms)
        e_mv, b_mv, h_mv = self.spec.split(vs)

        self._slots = []            # (param, exp_avg, exp_avg_sq)
        slot_of = {}
        def register(tree, m_tree, v_tree):
            leaves, tdef = jax.tree_util.tree_flatten(tree)
            m_leaves = tdef.flatten_up_to(m_tree)
            v_leaves = tdef.flatten_up_to(v_tree)
            idxs = []
            for p, m, v in zip(leaves, m_leaves, v_leaves):
                if id(p) not in slot_of:
                    slot_of[id(p)] = len(self._slots)
                    self._slots.append((p, m, v))
                idxs.append(slot_of[id(p)])
            return leaves, tdef, idxs

        self._e_leaves, self._e_def, self._e_slots = register(
            e_m, e_mm, e_mv)
        self._b_leaves, self._b_defs, self._b_slots = [], [], []
        for bt, bmt, bvt in zip(b_m, b_mm, b_mv):
            lv, td, ix = register(bt, bmt, bvt)
            self._b_leaves.append(lv)
            self._b_defs.append(td)
            self._b_slots.append(ix)
        self._h_leaves, self._h_def, self._h_slots = register(
            h_m, h_mm, h_mv)
        # tied leaves (one slot referenced from 2+ segments): their
        # per-segment sumsq shortcut is invalid (||a||^2+||b||^2 !=
        # ||a+b||^2), so apply_step must price the accumulated buffers
        n_refs = (len(self._e_slots) + len(self._h_slots)
                  + sum(len(ix) for ix in self._b_slots))
        self._has_shared_slots = n_refs > len(self._slots)
        if self._grad_bufs is None or \
                len(self._grad_bufs) != len(self._slots):
            self._grad_bufs = [None] * len(self._slots)

    def _group_leaves(self, g):
        start, stop = self.groups[g]
        return [leaf for i in range(start, stop)
                for leaf in self._b_leaves[i]]

    # ------------------------------------------------------------- fetch
    def _accumulate_fetched(self, host, slot_idxs, shapes):
        """Split one fetched packed grad vector into per-leaf host views
        and accumulate per slot; returns the packed (finite, sumsq)
        tail. Called by the executor's ``resolve`` segment in the
        bespoke fetch order (runtime/executor/stream.py)."""
        off = 0
        for slot, shape in zip(slot_idxs, shapes):
            n = int(np.prod(shape)) if shape else 1
            view = host[off:off + n].reshape(shape)
            off += n
            if self._grad_bufs[slot] is None:
                # adopt the fetched view without copying — jax host
                # buffers are read-only, so a later accumulation
                # into this slot (tied leaf / gas>1) copies lazily
                self._grad_bufs[slot] = view
            elif self._grad_bufs[slot].flags.writeable:
                self._grad_bufs[slot] += view
            else:
                self._grad_bufs[slot] = self._grad_bufs[slot] + view
        return bool(host[off] > 0.5), float(host[off + 1])

    # ------------------------------------------------------------- steps
    def micro_step(self, batch, rng):
        """One streamed micro-step: forward + backward with grads
        accumulated into the host buffers. Returns the (unscaled) loss
        as a device scalar. Lowered onto the segment executor
        (runtime/executor/stream.py): the double-buffered upload /
        compute / grad-fetch interleaving that used to be hand-threaded
        here is now a SegmentPlan the scheduler overlaps."""
        from ..executor.stream import run_streamed_micro
        return run_streamed_micro(self, batch, rng)

    def apply_step(self):
        """Host Adam over the accumulated grads (chunked by
        sub_group_size), with classic offload's overflow-skip
        semantics, lowered onto the segment executor. Returns the
        metrics dict; the caller updates the scaler."""
        from ..executor.stream import run_streamed_apply
        return run_streamed_apply(self)

    def zero_grads(self):
        self._grad_bufs = [None] * len(self._grad_bufs or [])
        self._micro_finites = []
        self._micro_sumsqs = []
        self._micros_in_step = 0

    # -------------------------------------------------------------- eval
    def eval_loss(self, batch):
        """Streamed forward-only loss (dropout off)."""
        # _finish_upload bills h2d waits and the per-step upload
        # counters; an eval between optimizer steps must not leak them
        # into the NEXT train record's phases/transfer stats
        saved = (dict(self.phase_times), self._step_upload_batches,
                 self._step_upload_elems, self._segment_upload_bytes_peak)
        try:
            return self._eval_loss(batch)
        finally:
            (self.phase_times, self._step_upload_batches,
             self._step_upload_elems,
             self._segment_upload_bytes_peak) = saved

    def _eval_loss(self, batch):
        self._bind()
        e_def, b_defs, h_def = self._e_def, self._b_defs, self._h_def
        embed, group, head = self._eval_fn(e_def, b_defs, h_def)
        G = len(self.groups)
        pending = self._start_upload(self._e_leaves)
        e_dev = self._finish_upload(pending)
        pending = self._start_upload(self._group_leaves(0)) if G else None
        x = self._jit(("e_eval",), lambda: embed)(tuple(e_dev), batch)
        del e_dev
        for g in range(G):
            bl = self._finish_upload(pending)
            pending = (self._start_upload(self._group_leaves(g + 1))
                       if g + 1 < G
                       else self._start_upload(self._h_leaves))
            start, stop = self.groups[g]
            fn = self._jit(("g_eval", tuple(b_defs[start:stop])),
                           lambda: group(tuple(b_defs[start:stop])))
            x = fn(self._split_group(bl, g), x)
            del bl
        h_dev = self._finish_upload(pending)
        return self._jit(("h_eval",), lambda: head)(tuple(h_dev), x,
                                                    batch)

    def _split_group(self, flat_leaves, g):
        """Flat uploaded leaf tuple -> tuple of per-block leaf tuples."""
        start, stop = self.groups[g]
        out, off = [], 0
        for i in range(start, stop):
            n = len(self._b_leaves[i])
            out.append(tuple(flat_leaves[off:off + n]))
            off += n
        return tuple(out)
