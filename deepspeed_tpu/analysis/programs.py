"""ProgramSpec collectors: each engine's step programs, described
abstractly for the auditor.

The collectors reach through the engines' OWN builder seams
(``_micro_step_fn`` / ``_fused_train_fn`` / ``_pipe_grads_fn`` / the
streamed runner's segment builders / the inference prefill/decode
factories) so the audited jaxprs are byte-identical to what the
engines jit — there is no parallel re-implementation to drift.

Program families covered (the acceptance matrix):

  * ``micro``      — the micro-step + optimizer-apply pair;
  * ``fused``      — the one-jit scan-over-micros + apply program;
  * ``offload``    — classic ZeRO-Offload's on-device micros scan and
                     the jitted overflow/norm check (host Adam is not a
                     device program);
  * ``streamed``   — the five segment programs of the beyond-HBM
                     runner (embed/group fwd, head grad, group/embed
                     bwd);
  * ``pipeline``   — the 1F1B pipe-loop program (fused or offload
                     split);
  * ``inference``  — bucketed prefill, fused decode, and the
                     speculative verify pass.
"""
import numpy as np

import jax

from .rules import ProgramSpec, _kp_str, _spec_mentions


def _sds(x):
    """array-ish -> ShapeDtypeStruct (mesh sharding preserved); scalars
    and None pass through (make_jaxpr abstracts them itself). Only
    NamedShardings are kept: an uncommitted array reports a
    SingleDeviceSharding that would pin the lowered program to one
    device and clash with the mesh-committed operands."""
    if x is None:
        return None
    if hasattr(x, "shape") and hasattr(x, "dtype"):
        from jax.sharding import NamedSharding
        sharding = getattr(x, "sharding", None)
        if not isinstance(sharding, NamedSharding):
            sharding = None
        return jax.ShapeDtypeStruct(tuple(x.shape), x.dtype,
                                    sharding=sharding)
    return x


def sds_tree(tree):
    return jax.tree_util.tree_map(_sds, tree)


def _rng_struct():
    key = jax.random.PRNGKey(0)
    return jax.ShapeDtypeStruct(tuple(key.shape), key.dtype)


# --------------------------------------------------------------- train
def _batch_struct(engine, batch):
    """Sample micro-batch -> SDS tree with the shardings _to_device
    would commit (no placement happens)."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    def put(x):
        if hasattr(x, "shape") and hasattr(x, "dtype"):
            shape, dtype = tuple(x.shape), x.dtype
        else:
            arr = np.asarray(x)
            shape, dtype = arr.shape, arr.dtype
        if len(shape) == 0 or shape[0] % engine.dp_world_size != 0:
            sharding = NamedSharding(engine.mesh, P())
        else:
            sharding = engine._batch_sharding(len(shape))
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)

    return jax.tree_util.tree_map(put, tuple(batch))


def _stacked_struct(engine, micro_struct):
    """Micro-batch SDS tree -> the (gas, ...) stacked struct the fused
    path consumes (mirrors _to_device_stacked's shardings)."""
    from jax.sharding import NamedSharding, PartitionSpec as P
    gas = engine.gradient_accumulation_steps()

    def put(s):
        shape = (gas,) + tuple(s.shape)
        if len(shape) <= 2 and (len(shape) < 2 or
                                shape[1] % engine.dp_world_size != 0):
            sharding = NamedSharding(engine.mesh, P())
        elif shape[1] % engine.dp_world_size != 0:
            sharding = NamedSharding(engine.mesh, P())
        else:
            sharding = NamedSharding(
                engine.mesh,
                P(None, engine._batch_axis, *([None] * (len(shape) - 2))))
        return jax.ShapeDtypeStruct(shape, s.dtype, sharding=sharding)

    return jax.tree_util.tree_map(put, micro_struct)


def _resolve_batch(engine, batch):
    if batch is not None:
        return _batch_struct(engine, batch)
    micro = getattr(engine, "_audit_batch_struct", None)
    stacked = getattr(engine, "_audit_batch_struct_stacked", None)
    if micro is None and stacked is not None:
        micro = jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(
                tuple(s.shape[1:]), s.dtype,
                sharding=getattr(engine, "_batch_sharding")(len(s.shape) - 1)
                if len(s.shape) >= 2 and
                s.shape[1] % engine.dp_world_size == 0 else None),
            stacked)
    if micro is None:
        raise ValueError(
            "audit needs a sample batch: pass engine.audit(batch=...) "
            "(arrays or ShapeDtypeStructs shaped like one micro-batch), "
            "or run one training step first")
    return micro


def _count_sharded(plan, tree, kind, axes):
    if tree is None:
        return 0
    shardings = plan.tree_shardings(tree, kind)
    leaves = jax.tree_util.tree_leaves(
        shardings, is_leaf=lambda x: hasattr(x, "spec"))
    return sum(1 for s in leaves if _spec_mentions(s, set(axes)))


def _state_out_expect(engine, state_struct, prefix="0"):
    """[(output path, expected axes)] for the state leaves the plan
    data-shards — fed to the compiled output-drift check."""
    plan = engine.zero_plan
    axes = set(plan.data_axes) | set(plan.param_data_axes)
    if not axes:
        return []
    out = []
    for field, kind in (("params", "param"), ("master", "master"),
                        ("acc_grads", "grad")):
        tree = state_struct.get(field) if isinstance(state_struct, dict) \
            else None
        if tree is None:
            continue
        flat, _ = jax.tree_util.tree_flatten_with_path(tree)
        for kp, leaf in flat:
            path = _kp_str(kp)
            sharding = {"param": plan.param_sharding,
                        "master": plan.master_sharding,
                        "grad": plan.grad_sharding}[kind](
                            path, tuple(leaf.shape))
            mentioned = [ax for ax in axes if _spec_mentions(sharding,
                                                             {ax})]
            if mentioned:
                out.append(("{}/{}/{}".format(prefix, field, path),
                            tuple(mentioned)))
    return out


def train_step_sequence(engine):
    """The engine's declared step-order/donation dataflow (state-field
    granularity) for the read-after-donation rule."""
    gas = engine.gradient_accumulation_steps()
    seq = []
    if engine.stream_runner is not None or engine.host_state is not None:
        # host-optimizer paths never donate device state across programs
        return seq
    for _ in range(gas):
        seq.append({"program": "micro", "reads": ("state", "batch"),
                    "donates": ("state",), "produces": ("state",)})
    seq.append({"program": "apply", "reads": ("state",),
                "donates": ("state",), "produces": ("state",)})
    return seq


def collect_train_programs(engine, batch=None):
    plan = engine.zero_plan
    mesh = engine.mesh
    state_struct = sds_tree(engine.state)
    micro_b = _resolve_batch(engine, batch)
    stacked_b = getattr(engine, "_audit_batch_struct_stacked", None)
    if stacked_b is None:
        stacked_b = _stacked_struct(engine, micro_b)
    rng = _rng_struct()
    pld = engine._pld_theta()
    hyper = engine._hyper()
    axes = tuple(sorted(set(plan.data_axes) | set(plan.param_data_axes)))

    if getattr(engine, "stream_runner", None) is not None:
        return _collect_streamed(engine, micro_b, rng)

    if hasattr(engine, "_pipeline_train_fn"):
        return _collect_pipeline(engine, state_struct, stacked_b, rng,
                                 hyper, axes)

    acc = engine.state.get("acc_grads")
    n_grad = _count_sharded(plan, acc, "grad", axes)
    n_master = _count_sharded(plan, acc, "master", axes)
    out_expect = _state_out_expect(engine, state_struct)
    common = dict(plan=plan, mesh=mesh, taint_paths=("0/params",))
    specs = []
    if engine.host_state is not None:
        # classic ZeRO-Offload: on-device micros (single + fused scan),
        # plus the jitted overflow/norm check; Adam runs on host
        specs.append(ProgramSpec(
            name="micro", family="offload", build=engine._micro_step_fn,
            args=(state_struct, micro_b, rng, pld), donate=(0,),
            expected_constraints=n_grad, constraint_axes=axes,
            meta={"out_expect": out_expect}, **common))
        specs.append(ProgramSpec(
            name="fused_micros", family="offload",
            build=engine._fused_micros_fn,
            args=(state_struct, stacked_b, rng, pld), donate=(0,),
            expected_constraints=n_grad, constraint_axes=axes,
            meta={"out_expect": out_expect}, **common))
        specs.append(ProgramSpec(
            name="offload_check", family="offload",
            build=engine._offload_check_fn,
            args=(state_struct["acc_grads"], np.float32(1.0)),
            plan=plan, mesh=mesh))
        return specs

    gas = engine.gradient_accumulation_steps()
    specs.append(ProgramSpec(
        name="micro", family="micro", build=engine._micro_step_fn,
        args=(state_struct, micro_b, rng, pld), donate=(0,),
        expected_constraints=n_grad, constraint_axes=axes,
        meta={"out_expect": out_expect, "wire_multiplier": gas},
        **common))
    specs.append(ProgramSpec(
        name="apply", family="micro", build=engine._apply_step_fn,
        args=(state_struct, hyper), donate=(0,),
        expected_constraints=max(n_master, n_grad), constraint_axes=axes,
        meta={"out_expect": out_expect, "wire_multiplier": 1},
        **common))
    specs.append(ProgramSpec(
        name="fused_train", family="fused", build=engine._fused_train_fn,
        args=(state_struct, stacked_b, rng, hyper, pld),
        donate=(0,),
        expected_constraints=n_grad + max(n_master, n_grad),
        constraint_axes=axes, meta={"out_expect": out_expect}, **common))
    return specs


def _collect_pipeline(engine, state_struct, stacked_b, rng, hyper, axes):
    plan = engine.zero_plan
    acc = engine.state.get("acc_grads")
    n_grad = _count_sharded(plan, acc, "grad", axes)
    n_master = _count_sharded(plan, acc, "master", axes)
    out_expect = _state_out_expect(engine, state_struct)
    common = dict(plan=plan, mesh=engine.mesh, taint_paths=("0/params",))
    if engine.host_state is not None:
        return [ProgramSpec(
            name="pipe_micros", family="pipeline",
            build=engine._pipe_grads_fn,
            args=(state_struct, stacked_b, rng), donate=(0,),
            expected_constraints=n_grad, constraint_axes=axes,
            meta={"out_expect": out_expect}, **common)]
    return [ProgramSpec(
        name="pipe_train", family="pipeline",
        build=engine._fused_train_fn,
        args=(state_struct, stacked_b, rng, hyper), donate=(0,),
        expected_constraints=n_grad + max(n_master, n_grad),
        constraint_axes=axes, meta={"out_expect": out_expect}, **common)]


# ------------------------------------------------------------ streamed
def _collect_streamed(engine, micro_b, rng):
    """The five streamed-offload segment programs, with intermediate
    activation structs derived by chained eval_shape (the auditor never
    uploads or runs anything)."""
    from ..runtime.zero.stream import STREAM_DONATE
    runner = engine.stream_runner
    runner._bind()
    cdtype = np.dtype(engine.compute_dtype)
    repl = runner._replicated

    def seg_sds(leaves):
        return tuple(
            jax.ShapeDtypeStruct(np.shape(p), cdtype, sharding=repl)
            for p in leaves)

    e_sds = seg_sds(runner._e_leaves)
    h_sds = seg_sds(runner._h_leaves)
    g0 = seg_sds(runner._group_leaves(0))
    g0_split = runner._split_group(list(g0), 0)
    start, stop = runner.groups[0]
    b_defs = tuple(runner._b_defs[start:stop])
    has_rng = engine.model.accepts_rng
    key = _rng_struct() if has_rng else None
    n_blocks = stop - start
    gkeys = jax.ShapeDtypeStruct((n_blocks,) + tuple(key.shape),
                                 key.dtype) if has_rng else None
    scale = np.float32(1.0)
    inv_scale = np.float32(1.0)

    e_fwd = runner._embed_fwd_fn(runner._e_def, has_rng)
    x_struct = jax.eval_shape(e_fwd, e_sds, micro_b, key)
    g_fwd = runner._group_fwd_fn(b_defs, has_rng)
    x_out = jax.eval_shape(g_fwd, g0_split, x_struct, gkeys)
    # the head consumes the LAST group's boundary activation; equal-width
    # transformer blocks keep the struct constant across groups, so the
    # first group's output struct stands in for it
    h_grad = runner._head_grad_fn(runner._h_def, has_rng)
    _, dx_struct, _ = jax.eval_shape(h_grad, h_sds, x_out, micro_b, key,
                                     scale, inv_scale)

    common = dict(plan=engine.zero_plan, mesh=engine.mesh, family="streamed")
    return [
        ProgramSpec(
            name="stream/e_fwd",
            build=lambda: runner._embed_fwd_fn(runner._e_def, has_rng),
            args=(e_sds, micro_b, key),
            donate=STREAM_DONATE["e_fwd"], **common),
        ProgramSpec(
            name="stream/g_fwd",
            build=lambda: runner._group_fwd_fn(b_defs, has_rng),
            args=(g0_split, x_struct, gkeys),
            donate=STREAM_DONATE["g_fwd"],
            # the boundary activation input is KEPT for the backward
            # recompute — liveness the donation rule cannot see
            keep_args=("1",), **common),
        ProgramSpec(
            name="stream/h_grad",
            build=lambda: runner._head_grad_fn(runner._h_def, has_rng),
            args=(h_sds, x_out, micro_b, key, scale, inv_scale),
            donate=STREAM_DONATE["h_grad"], **common),
        ProgramSpec(
            name="stream/g_bwd",
            build=lambda: runner._group_bwd_fn(b_defs, has_rng),
            args=(g0_split, x_struct, dx_struct, gkeys, inv_scale),
            donate=STREAM_DONATE["g_bwd"],
            # x_in stays live only because dx claimed the alias; the
            # uploaded weights have no aliasable output (donating them
            # would only buy an XLA warning)
            keep_args=("0", "1"), **common),
        ProgramSpec(
            name="stream/e_bwd",
            build=lambda: runner._embed_bwd_fn(runner._e_def, has_rng),
            args=(e_sds, micro_b, dx_struct, key, inv_scale),
            donate=STREAM_DONATE["e_bwd"],
            keep_args=("0",), **common),
    ]


# ----------------------------------------------------------- inference
def inference_step_sequence(engine):
    seq = [{"program": "prefill", "reads": ("params", "kv"),
            "donates": ("kv",), "produces": ("kv",)},
           {"program": "decode", "reads": ("params", "kv"),
            "donates": ("kv",), "produces": ("kv",)}]
    if engine.spec_k:
        seq.append({"program": "spec_verify", "reads": ("params", "kv"),
                    "donates": ("kv",), "produces": ("kv",)})
    return seq


def collect_inference_programs(engine):
    params = sds_tree(engine.params)
    pools = tuple(_sds(a) for a in engine._pools())
    rng = _rng_struct()
    temp = np.float32(1.0)
    top_p = np.float32(1.0)
    n_buckets = len(engine.prefill_buckets)
    # a model with recurrent layers: its state arrays ride behind the
    # page pool, donated like it, then the slot (prefill) or the
    # advance mask (decode)
    pool = getattr(engine, "state", None)
    state = tuple(_sds(a) for a in pool.buffers()) if pool else ()
    donate = tuple(range(1, 1 + len(pools) + len(state)))
    groups = engine.page_groups

    def tables(*lead):
        """The page tables a program is handed: one (.., max_pages)
        array, or for a decoder with page groups a table and a base a
        group (inference/engine.py)."""
        if not getattr(engine, "_grouped", False):
            return jax.ShapeDtypeStruct(lead + (engine.max_pages,), np.int32)
        return (tuple(jax.ShapeDtypeStruct(lead + (g.max_pages,), np.int32)
                      for g in groups),
                tuple(jax.ShapeDtypeStruct(lead, np.int32) for _ in groups))

    specs = []
    greedy, top_k = True, 0
    for bucket in engine.prefill_buckets:
        ids = jax.ShapeDtypeStruct((1, bucket), np.int32)
        specs.append(ProgramSpec(
            name="prefill/b{}".format(bucket), family="inference",
            build=lambda b=bucket: _unjitted(engine, "prefill", b, greedy,
                                             top_k),
            args=(params,) + pools + state + (
                (np.int32(0),) if state else ()) + (
                ids, tables(), np.int32(0), np.int32(1), rng, temp, top_p),
            donate=donate, mesh=engine.mesh,
            # no allow_weak needed: every scalar operand is an explicit
            # np.int32/np.float32 (strong-typed)
            taint_paths=("0",), trace_bound=n_buckets))
    widths = [("decode", 1)]
    if engine.spec_k:
        widths.append(("spec_verify", engine.spec_k + 1))
    for name, width in widths:
        tokens = jax.ShapeDtypeStruct((engine.num_slots, width), np.int32)
        lengths = jax.ShapeDtypeStruct((engine.num_slots,), np.int32)
        specs.append(ProgramSpec(
            name=name, family="inference",
            build=lambda w=width: _unjitted(engine, "decode", greedy,
                                            top_k, w),
            args=(params,) + pools + state + (
                (jax.ShapeDtypeStruct((engine.num_slots,), np.bool_),)
                if state else ()) + (tokens, lengths,
                                     tables(engine.num_slots), rng, temp,
                                     top_p),
            donate=donate, mesh=engine.mesh,
            taint_paths=("0",), trace_bound=len(widths)))
    return specs


def _unjitted(engine, family, *key):
    """The ``family`` ("prefill" | "decode") factory's traced fn
    WITHOUT entering the engine's jit cache (the audit must not inflate
    compile_stats or the trace registry)."""
    cache = "_{}_fns".format(family)
    fns, stats = getattr(engine, cache), dict(engine.compile_stats)
    tele, rows = engine.telemetry, engine._first_calls
    setattr(engine, cache, {})
    engine.telemetry, engine._first_calls = None, []
    try:
        fn = getattr(engine, "_get_{}_fn".format(family))(*key)
    finally:
        setattr(engine, cache, fns)
        engine.compile_stats = stats
        engine.telemetry = tele
        engine._first_calls_over(discard=True)   # nor the start-up record
        engine._first_calls = rows
    return fn.__wrapped__
