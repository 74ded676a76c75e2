"""DeepSpeedEngine: the central training wrapper.

Reference parity: deepspeed/runtime/engine.py (DeepSpeedEngine :97). The
user-facing semantics — ``loss = engine(batch); engine.backward(loss);
engine.step()``, gradient-accumulation boundaries, loss scaling,
overflow-skip, LR schedules, checkpoint save/load — are preserved. The
internals are re-founded for TPU:

  * one fp32-master train-state pytree of ``jax.Array``s, placed with
    NamedShardings computed from the ZeRO stage (zero/partition.py);
  * ``forward`` runs a single jitted value-and-grad micro-step that
    accumulates scaled gradients into a sharded buffer (the reference's
    backward hooks + IPG buckets, stage2.py:585-649, become dataflow);
  * ``step`` runs a jitted apply-step: overflow check (psum'd isfinite),
    unscale, clip, optimizer update on the master shard, branchless
    overflow-skip (``jnp.where``), re-cast/all-gather of compute params, and
    the dynamic loss-scale update — all one XLA program;
  * a fused ``train_batch`` path lax.scans the micro-steps for benchmarks.

No torch, no NCCL: collectives are inserted by XLA from shardings.
"""
import os
import time
from typing import Any, Dict

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from ..parallel.topology import MeshGrid, DATA_AXIS, build_mesh
from ..utils.annotate import (engine_tag, setup_span, startup_line,
                              startup_report)
from ..utils.compile_cache import program_scopes, release_programs
from ..utils.logging import logger, log_dist
from ..utils.timer import SynchronizedWallClockTimer, ThroughputTimer
from . import checkpointing as ckpt
from .config import DeepSpeedConfig
from .constants import (ADAM_OPTIMIZER, LAMB_OPTIMIZER, ONEBIT_ADAM_OPTIMIZER,
                        ROUTE_TRAIN)
from .dataloader import DeepSpeedDataLoader, RepeatingLoader
from .fp16 import loss_scaler as ls
from .lr_schedules import SCHEDULE_CLASSES
from .model import Model, as_model
from .progressive_layer_drop import ProgressiveLayerDrop
from .utils import (CheckOverflow, clip_grad_norm_, get_grad_norm,
                    count_parameters, see_memory_usage)
from .zero.partition import ZeroShardingPlan
from .zero.constants import (
    ZERO_OPTIMIZATION_SUB_GROUP_SIZE_DEFAULT as ZERO_SUB_GROUP_DEFAULT,
    ZERO_OPTIMIZATION_PREFETCH_BUCKET_SIZE_DEFAULT as ZERO_PREFETCH_DEFAULT)

MEMORY_OPT_ALLREDUCE_SIZE = 500000000

FORWARD_MICRO_TIMER = "forward_microstep"
BACKWARD_MICRO_TIMER = "backward_microstep"
STEP_MICRO_TIMER = "step_microstep"


# the checkpoint-format-defining helpers live with the serialization code;
# aliased here for the engine's many call sites
_shard_key = ckpt.shard_key
_key_to_index = ckpt.key_to_index


def _unique_shard_indices(arr):
    """This process's unique addressable shard indices of a jax array
    (replicated placements collapse to one entry)."""
    seen, out = set(), []
    for sh in arr.addressable_shards:
        key = _shard_key(sh.index)
        if key not in seen:
            seen.add(key)
            out.append(sh.index)
    return out


def _tree_size(*trees):
    """``{leaves, bytes}`` of some trees of arrays, for a set-up span."""
    leaves = jax.tree_util.tree_leaves(trees)
    return {"leaves": len(leaves),
            "bytes": sum(int(getattr(x, "nbytes", 0)) for x in leaves)}


def _program_name(key):
    """A step program's ``program`` in the start-up record: the
    ``_jit_cache`` key's first word (``micro``, ``apply``,
    ``fused_train``, ...); the whole key is the row's ``key``."""
    return str(key[0]) if isinstance(key, tuple) and key else str(key)


class DeepSpeedEngine:
    """Wraps a model to provide distributed data-parallel (+ZeRO) training on
    a TPU mesh with the DeepSpeed train API."""

    # ZeRO-Offload D2H prefetch depth (shards in flight ahead of the host
    # Adam); each in-flight copy pins a device staging buffer, so this
    # bounds the extra HBM the overlapped step may use.
    _D2H_WINDOW = 4

    def __init__(self, args=None, model=None, optimizer=None,
                 model_parameters=None, training_data=None, lr_scheduler=None,
                 mpu=None, dist_init_required=None, collate_fn=None,
                 config_params=None, dont_change_device=False, mesh=None):
        # this engine's rows of the start-up record (docs/telemetry.md,
        # "Start-up record") carry it
        self.startup_tag = engine_tag("train")
        self._first_call_row = None   # of a program made and not yet run
        self._first_call_operands = None    # what its first call is given
        self.client_optimizer = optimizer
        self.client_lr_scheduler = lr_scheduler
        self.training_data = training_data
        self.collate_fn = collate_fn
        self.mpu = mpu
        self.global_steps = 0
        self.global_samples = 0
        self.micro_steps = 0
        self.skipped_steps = 0
        self.loaded_checkpoint_dp_world_size = None
        self.warn_unscaled_loss = True

        self._resolve_config(args, config_params)
        self._configure_mesh(mpu, mesh)
        self._config = DeepSpeedConfig(self._config_file, mpu=None,
                                       param_dict=self._config_dict,
                                       mesh=self.mesh)
        # transient-IO retry policy for every checkpoint read/write
        # (ds_config "checkpoint" block; process-wide by design — the
        # storage backend is shared, so the last engine configured wins)
        ckpt.set_retry_policy(
            retries=self._config.checkpoint_io_retries,
            backoff_seconds=self._config.checkpoint_io_backoff_seconds)
        # concurrency sanitizer (analysis.concurrency, docs/
        # concurrency.md): installed BEFORE the telemetry subsystems so
        # the recorder/watchdog locks they create come out instrumented;
        # process-global (the lock-order graph spans engines), so a
        # second engine reuses the active instance
        if self._config.analysis_config.concurrency_enabled:
            from ..analysis.concurrency import locksan
            if locksan.current() is None:
                locksan.install(locksan.LockSanitizer(
                    stack_depth=self._config.analysis_config
                    .concurrency_stack_depth))
        self.model = as_model(model, model_parameters)
        if self.model.bind_mesh is not None:
            self.model.bind_mesh(self.mesh)
        # resolved kernel tri-states (observable via telemetry_snapshot,
        # like the serving engine's paged_attention_kernel); None = the
        # ds_config key was absent
        self.flash_attention_backend = None
        self.fused_optimizer_kernel = None
        self._configure_precision()
        self._configure_zero()
        self._configure_comm()
        self._apply_transformer_overrides()
        self._configure_optimizer(optimizer)
        self._configure_lr_scheduler(lr_scheduler)
        self._configure_pld()
        if "activation_checkpointing" in (self._config._param_dict or {}):
            # reference: user calls deepspeed.checkpointing.configure();
            # when the config section is present the engine applies it —
            # unless the user already configured (their kwargs win), and
            # never fatally (configs like contiguous+no-num_checkpoints
            # need the manual call with explicit kwargs)
            from .activation_checkpointing import checkpointing as act_ckpt
            if not act_ckpt.is_configured():
                try:
                    act_ckpt.configure(self.mpu,
                                       deepspeed_config=self._config)
                except Exception as err:  # noqa: BLE001
                    logger.warning(
                        "activation_checkpointing config could not be "
                        "auto-applied (%s); call deepspeed_tpu."
                        "checkpointing.configure() with explicit kwargs",
                        err)
        self._init_state()

        self.training_dataloader = self.deepspeed_io(training_data) \
            if training_data is not None else None

        from ..utils.monitor import SummaryMonitor
        # rank-0 writer (reference :154); gate BEFORE construction so
        # non-writer ranks never create files/handles
        self.monitor = SummaryMonitor.from_config(
            self._config, enabled=jax.process_index() == 0)

        # unified per-step telemetry (docs/telemetry.md): None unless the
        # "telemetry" config section enables it — the hot paths pay one
        # `is not None` check when off
        from ..telemetry import TelemetryCollector
        self.telemetry = TelemetryCollector.from_config(
            self._config, job_name="train", monitor=self.monitor,
            enabled=jax.process_index() == 0)
        self._tele_flops_cache = {}
        self._tele_wire = "unset"
        self._window_t0 = None
        self._window_step = 0
        self._window_tokens = 0
        self._window_flops = 0.0
        self._step_hbm = None
        self._step_path = "micro"
        # segment-plan executor (runtime/executor/, docs/executor.md):
        # every step path runs as a SegmentPlan through one scheduler;
        # runtime.executor "off" = serial oracle, "on"/"auto" = the
        # overlap-constructing schedule (built lazily on first use)
        self._executor_mode = "serial" \
            if self._config.runtime_executor == "off" else "overlap"
        # plan rewrite passes (runtime/executor/rewrite.py): the
        # strict-validated runtime.executor_rewrites dict (enabled,
        # passes, bounds); applied in overlap mode only
        self._executor_rewrites = self._config.runtime_executor_rewrites
        self._plan_executor = None
        # elastic rescale trail (runtime/elastic/): an ElasticRunner
        # swaps in its SHARED events list so the crash bundle's topology
        # section survives engine rebuilds; a never-rescaled engine
        # carries an empty history
        self._rescale_history = []
        self._onebit_pristine = None
        if self.telemetry is not None and \
                self.telemetry.recorder is not None:
            # flight recorder context (docs/diagnostics.md): resolved at
            # DUMP time, so the bundle reflects the state at the crash
            self.telemetry.recorder.set_context(
                "ds_config", lambda: self._config._param_dict)
            self.telemetry.recorder.set_context(
                "engine", self._flight_state)
            self.telemetry.recorder.set_context(
                "topology", self._topology_context)
        self._check_memory_breakdown()

        self.timers = SynchronizedWallClockTimer()
        self.tput_timer = ThroughputTimer(
            batch_size=self.train_micro_batch_size_per_gpu(),
            num_workers=self.dp_world_size,
            steps_per_output=self.steps_per_print(),
            monitor_memory=False)

        self._jit_cache: Dict[Any, Any] = {}
        # first-seen batch shapes, kept as ShapeDtypeStructs so
        # engine.audit() can abstract-eval the step programs without a
        # sample batch (one is-None check per _to_device call)
        self._audit_batch_struct = None
        self._audit_batch_struct_stacked = None
        self._mode = ROUTE_TRAIN
        self._last_loss = None
        self._step_metrics = {}
        self._rng = jax.random.PRNGKey(
            int(os.environ.get("DEEPSPEED_SEED", 42)))

        # sparse embedding-gradient exchange (reference CSR allreduce,
        # engine.py:1285-1341): models opt in via their config (e.g.
        # GPT2Config.sparse_embedding_grads -> ops/sparse_grads.py); the
        # engine records the module names for checkpoint parity and flags
        # a config/model mismatch
        self.csr_tensor_module_names = set()
        model_cfg = getattr(self.model, "config", None)
        if getattr(model_cfg, "sparse_embedding_grads", False):
            # only record when the exchange is actually LIVE: without a
            # nontrivial mesh axis sparse_embedding_lookup falls back to
            # the dense path and the checkpoint must not claim otherwise
            grad_mesh = getattr(model_cfg, "embedding_grad_mesh", None)
            axis_size = (int(dict(grad_mesh.shape).get(DATA_AXIS, 1))
                         if grad_mesh is not None else 1)
            if axis_size > 1:
                self.csr_tensor_module_names.add("wte")
            else:
                logger.warning(
                    "sparse_embedding_grads is set but embedding_grad_mesh "
                    "has no nontrivial '%s' axis — the lookup falls back "
                    "to dense gradients", DATA_AXIS)
        if self.sparse_gradients_enabled() and \
                not self.csr_tensor_module_names:
            logger.warning(
                "sparse_gradients is enabled in ds_config but the model "
                "does not route any embedding through "
                "sparse_embedding_lookup (e.g. "
                "GPT2Config.sparse_embedding_grads=True with "
                "embedding_grad_mesh); gradients stay dense")

        if self._config.dump_state:
            self._config.print("DeepSpeedEngine configuration")

        n_params = count_parameters(self.state["params"]) \
            if self.state.get("params") is not None else sum(
                int(np.prod(s)) if s else 1
                for s in self.host_state["leaf_shapes"])
        log_dist(
            "DeepSpeedEngine ready: params={:,} zero_stage={} dtype={} "
            "mesh={}".format(n_params,
                             self.zero_optimization_stage(),
                             self.compute_dtype, dict(self.mesh.shape)),
            ranks=[0])

    # ------------------------------------------------------------------ setup
    def _resolve_config(self, args, config_params):
        config_file = None
        config_dict = None
        if config_params is not None:
            if isinstance(config_params, str):
                config_file = config_params
            else:
                config_dict = config_params
        elif args is not None and getattr(args, "deepspeed_config", None):
            config_file = args.deepspeed_config
        assert config_file is not None or config_dict is not None, \
            "DeepSpeed requires --deepspeed_config or a config dict"
        self._config_file = config_file
        self._config_dict = config_dict

    def _configure_mesh(self, mpu, mesh):
        if mesh is not None:
            self.mesh = mesh
        elif mpu is not None and hasattr(mpu, "mesh"):
            self.mesh = mpu.mesh
        elif mpu is not None and hasattr(mpu, "get_model_parallel_world_size"):
            # Foreign (Megatron-style) mpu: honor its model-parallel degree by
            # building a (data, model) mesh (reference engine.py:568-579).
            mp = int(mpu.get_model_parallel_world_size())
            assert jax.device_count() % mp == 0, \
                "device count {} not divisible by model parallel size {}".format(
                    jax.device_count(), mp)
            self.mesh = build_mesh(data=jax.device_count() // mp, model=mp)
        else:
            self.mesh = build_mesh(data=jax.device_count())
        self.grid = mpu if isinstance(mpu, MeshGrid) else None
        # one source of truth for batch-dim sharding; meshes may drop the
        # size-1 data axis (e.g. pure-sequence meshes)
        self._batch_axis = DATA_AXIS if DATA_AXIS in self.mesh.shape else None
        if self._batch_axis is None and jax.process_count() > 1:
            # each process feeds different samples (deepspeed_io), which a
            # replicated batch sharding would silently mis-treat as equal
            raise NotImplementedError(
                "multi-process runs need a 'data' mesh axis to shard the "
                "batch over")
        self.dp_world_size = int(self.mesh.shape.get(DATA_AXIS, 1))
        self.mp_world_size = int(self.mesh.shape.get("model", 1))
        self.global_rank = jax.process_index()
        self.world_size = self.dp_world_size

    def _configure_precision(self):
        if self._config.amp_enabled:
            # Reference routes "amp" through NVIDIA apex (engine.py:580-600);
            # on TPU the equivalent mixed-precision mode is bf16 compute with
            # fp32 master state, so amp is reinterpreted — loudly, because any
            # apex-specific opts (opt_level, ...) are dropped.
            log_dist(
                "'amp' config block is reinterpreted as bf16 mixed precision "
                "on TPU; amp-specific options {} are ignored".format(
                    self._config.amp_params or "{}"), ranks=[0])
        if self._config.bf16_enabled or self._config.amp_enabled:
            self.compute_dtype = jnp.bfloat16
        elif self._config.fp16_enabled:
            # On TPU bf16 is the fast half type; fp16 kept for parity runs on
            # other backends (reference does module.half(), engine.py:560).
            self.compute_dtype = jnp.float16 \
                if jax.default_backend() != "tpu" else jnp.bfloat16
        else:
            self.compute_dtype = jnp.float32
        self.mixed_precision = self.compute_dtype != jnp.float32

    def _configure_zero(self):
        zc = self._config.zero_config
        stage = self._config.zero_optimization_stage
        hpz = int(zc.hierarchical_partition or 0)
        if hpz > 1 and not self._config.zero_enabled:
            logger.warning(
                "zero_hierarchical_partition=%d ignored: ZeRO is "
                "disabled (zero_optimization.stage=0)", hpz)
        if hpz > 1 and self._config.zero_enabled:
            # hpZ (ZeRO++ hierarchical partitioning): factor the data axis
            # into (replica, shard) sub-axes so stage-3 params shard only
            # within the shard group and per-step gathers ride the short
            # intra-replica hop. Placement of master/opt/grad state is
            # unchanged (they shard over BOTH sub-axes).
            from ..parallel.topology import (factor_data_axis, PIPE_AXIS,
                                             DATA_REPLICA_AXIS,
                                             DATA_SHARD_AXIS)
            if stage < 3:
                logger.warning(
                    "zero_hierarchical_partition=%d has no effect below "
                    "ZeRO stage 3 (params are not data-sharded); ignoring",
                    hpz)
            elif PIPE_AXIS in self.mesh.shape:
                raise ValueError(
                    "zero_hierarchical_partition is not a certified "
                    "combination with pipeline parallelism (the pipe "
                    "loop's shard_map specs name the flat 'data' axis)")
            elif self._batch_axis != DATA_AXIS:
                raise ValueError(
                    "zero_hierarchical_partition needs a 'data' mesh axis "
                    "to factor; mesh has {}".format(dict(self.mesh.shape)))
            else:
                self.mesh = factor_data_axis(self.mesh, hpz)
                self._batch_axis = (DATA_REPLICA_AXIS, DATA_SHARD_AXIS)
        # comm.quantized_collectives.hierarchical=N: factor the data axis
        # for the two-level in-collective decomposition (2504.18658) even
        # below stage 3 (where hpZ itself is inert). Placement of
        # master/grad state is identical to the flat plan (it shards over
        # BOTH sub-axes); only the collective decomposition changes.
        qc = self._config.comm_config.quantized_collectives
        if qc.enabled and qc.hierarchical >= 2:
            from ..parallel.topology import (factor_data_axis as _factor,
                                             DATA_REPLICA_AXIS as _DR,
                                             DATA_SHARD_AXIS as _DS,
                                             DATA_AXIS as _DA)
            if _DS in self.mesh.shape:
                if int(self.mesh.shape[_DS]) != qc.hierarchical:
                    raise ValueError(
                        "comm.quantized_collectives.hierarchical={} "
                        "conflicts with the hpZ-factored mesh (data_shard"
                        "={}); use hierarchical=0 to follow the mesh"
                        .format(qc.hierarchical,
                                int(self.mesh.shape[_DS])))
            elif self._batch_axis != _DA:
                raise ValueError(
                    "comm.quantized_collectives.hierarchical needs a "
                    "'data' mesh axis to factor; mesh has {}".format(
                        dict(self.mesh.shape)))
            elif int(self.mesh.shape[_DA]) <= 1:
                # leave the mesh flat: _configure_quantized_collectives
                # warns the documented dp<=1 no-op (raises under strict)
                pass
            elif int(self.mesh.shape[_DA]) % qc.hierarchical != 0:
                # name OUR key — factor_data_axis's own error names
                # zero_hierarchical_partition, which the user never set
                raise ValueError(
                    "comm.quantized_collectives.hierarchical={} must "
                    "divide the data-parallel degree {}".format(
                        qc.hierarchical, int(self.mesh.shape[_DA])))
            else:
                self.mesh = _factor(self.mesh, qc.hierarchical)
                self._batch_axis = (_DR, _DS)
        self.zero_plan = ZeroShardingPlan(
            self.mesh, stage=stage,
            param_persistence_threshold=zc.param_persistence_threshold,
            model_spec_fn=self.model.partition_spec_fn,
            max_live_parameters=(int(zc.max_live_parameters)
                                 if stage >= 3 and zc.max_live_parameters
                                 is not None else None))
        if self.zero_plan.max_live_parameters is not None and \
                self.model.params is not None:
            persistent, demoted = \
                self.zero_plan.configure_live_budget(self.model.params)
            if demoted:
                log_dist(
                    "stage3_max_live_parameters={:,}: demoted {} "
                    "persistent leaves to data-sharded (persistent set "
                    "now {:,} elements)".format(
                        self.zero_plan.max_live_parameters, len(demoted),
                        persistent), ranks=[0])
            if persistent is not None and \
                    persistent > self.zero_plan.max_live_parameters:
                self._zero_key_noop(
                    "stage3_max_live_parameters",
                    "un-shardable persistent parameters alone hold {:,} "
                    "elements > budget {:,} — the budget cannot be "
                    "honored on this model/mesh".format(
                        persistent, self.zero_plan.max_live_parameters))
        self._validate_zero_keys(zc, stage)
        # qwZ / qgZ (ZeRO++ quantized collectives): resolved here so the
        # jitted step builders can close over plain bools
        self._qwz_enabled = bool(zc.quantized_weights) and stage >= 3 \
            and self.zero_plan.param_data_axes != ()
        if zc.quantized_weights and stage < 3:
            logger.warning(
                "zero_quantized_weights has no effect below ZeRO stage 3 "
                "(there is no per-step weight all-gather); ignoring")
        self._qgz_enabled = bool(zc.quantized_gradients) and \
            self._config.zero_enabled and stage >= 2
        if zc.quantized_gradients and not self._qgz_enabled:
            logger.warning(
                "zero_quantized_gradients needs ZeRO stage >= 2 (the "
                "gradient reduce-scatter partition); ignoring")
        # cpu_offload_params: streamed parameter offload (beyond-HBM
        # ZeRO-3; runtime/zero/stream.py). Params are host-resident and
        # streamed per layer group into HBM inside the step.
        self._params_offload = bool(zc.cpu_offload_params) and \
            self._config.zero_enabled
        if zc.cpu_offload_params and not self._config.zero_enabled:
            raise ValueError(
                "zero_optimization.cpu_offload_params requires ZeRO "
                "(zero_optimization.stage=3)")
        if self._params_offload and stage < 3:
            raise ValueError(
                "zero_optimization.cpu_offload_params is a ZeRO-3 "
                "feature (params must be partitionable); got stage {}"
                .format(stage))
        if self._params_offload and not zc.cpu_offload:
            log_dist(
                "cpu_offload_params without cpu_offload: the fp32 master "
                "and Adam moments are host-resident anyway (the streamed "
                "step's optimizer runs on host)", ranks=[0])
        # sub_group_size: element chunk size of the offload shard
        # pipeline's D2H->host-Adam work items (reference stage3.py
        # sub_group partitioning of the optimizer step); the huge default
        # leaves one chunk per shard.
        self._sub_group_size = int(zc.sub_group_size) \
            if zc.sub_group_size else ZERO_SUB_GROUP_DEFAULT
        # stage3_prefetch_bucket_size: element size of each coalesced
        # host->device transfer bucket (offload param uploads ride few
        # large device_puts instead of one per shard — see _H2DBatcher)
        self._h2d_bucket_elems = int(zc.prefetch_bucket_size) \
            if zc.prefetch_bucket_size else ZERO_PREFETCH_DEFAULT

    def _configure_comm(self):
        """comm.collective_matmul: ring-decomposed all-gather/reduce-
        scatter GEMMs (parallel/collective_matmul.py). Resolves which
        fusion sites are live on this mesh/config:

          * ``_cm_zero3``: the stage-3 per-leaf weight all-gather runs
            as an explicit ppermute ring (composing with qwZ so the
            rotated chunks stay int8 blocks + scales on the wire);
          * ``_cm_tp``: the model's TP matmul sites run the fused
            column/row ops — communicated to the model by attaching a
            CollectiveMatmulBinding to its config.

        Off (the default) leaves every path exactly as before; the
        unfused XLA program stays the numerics oracle."""
        self._configure_quantized_collectives()
        cm = self._config.comm_config.collective_matmul
        self._cm = cm
        self._cm_zero3 = False
        self._cm_tp = False
        model_cfg = getattr(self.model, "config", None)
        if getattr(model_cfg, "collective_matmul", None) is not None and \
                not (cm.enabled and cm.tensor_parallel):
            # the binding lives on the (possibly shared) model config
            # object because the model's apply_fn closed over it — a
            # previous engine's attach leaks into this one. This engine
            # would RUN fused TP GEMMs while reporting them unfused;
            # A/B comparisons need models built from fresh configs.
            logger.warning(
                "model config already carries a collective_matmul "
                "binding (attached by a caller or a previous engine) "
                "but this engine's comm.collective_matmul does not "
                "enable TP fusion — the fused GEMMs still run, and "
                "this engine's telemetry will not flag them; build "
                "models from fresh configs for fused-vs-unfused "
                "comparisons")
        if not cm.enabled:
            return
        from ..parallel.topology import PIPE_AXIS, MODEL_AXIS
        from ..telemetry.config import warn_or_raise_noop
        if PIPE_AXIS in self.mesh.shape:
            raise ValueError(
                "comm.collective_matmul is not a certified combination "
                "with pipeline parallelism (the pipe loop owns its "
                "shard_map specs)")
        stage = self._config.zero_optimization_stage
        zc = self._config.zero_config
        self._cm_zero3 = bool(
            cm.zero_gather and stage >= 3 and
            self.zero_plan.param_data_axes != () and
            not bool(zc.cpu_offload_params))
        mp = int(self.mesh.shape.get(MODEL_AXIS, 1))
        if cm.tensor_parallel and mp > 1:
            if hasattr(model_cfg, "collective_matmul"):
                from ..parallel.collective_matmul import \
                    CollectiveMatmulBinding
                model_cfg.collective_matmul = CollectiveMatmulBinding(
                    mesh=self.mesh, axis=MODEL_AXIS,
                    chunks=int(cm.chunks), dtype=cm.dtype,
                    backend=cm.backend)
                self._cm_tp = True
            else:
                warn_or_raise_noop(
                    "comm.collective_matmul.tensor_parallel has NO "
                    "effect: model {!r} exposes no collective_matmul "
                    "config field".format(self.model.name), cm.strict,
                    flag="comm.collective_matmul.strict")
        if not (self._cm_zero3 or self._cm_tp):
            warn_or_raise_noop(
                "comm.collective_matmul is enabled but no fusion site "
                "is live (needs ZeRO stage >= 3 data-sharded params "
                "without cpu_offload_params, and/or a model mesh axis "
                "> 1 on a binding-aware model)", cm.strict,
                flag="comm.collective_matmul.strict")
        else:
            log_dist(
                "collective_matmul ON: zero3_ring_gather={} tp_fused={} "
                "chunks={} dtype={} backend={}".format(
                    self._cm_zero3, self._cm_tp, cm.chunks, cm.dtype,
                    cm.backend),
                ranks=[0])

    def _configure_quantized_collectives(self):
        """comm.quantized_collectives: replace the data-parallel gradient
        allreduce with the in-collective int8 exchange
        (runtime/comm/quantize.py, EQuARX 2506.17615). The micro step
        computes per-device LOCAL gradients inside shard_map and averages
        them through the quantized ring, so the compiled program's
        data-axis wire is int8 blocks + scales instead of fp32 — the PR
        10 HLO census verifies the bytes. Certified combinations only:
        the local-grad body runs the model fully manual over the data
        axis, so tensor/sequence/pipeline parallelism are rejected, and
        ZeRO-3 (data-sharded compute params) cannot feed it."""
        from ..telemetry.config import warn_or_raise_noop
        qc = self._config.comm_config.quantized_collectives
        self._qc = qc
        self._qc_enabled = False
        if not qc.enabled:
            return
        self._certify_local_grad_comm("comm.quantized_collectives")
        if bool(self._config.zero_config.cpu_offload_params):
            raise ValueError(
                "comm.quantized_collectives is not a certified "
                "combination with cpu_offload_params (the streamed "
                "runner owns its own gradient path)")
        dp = int(np.prod([self.mesh.shape[a] for a in
                          (self._batch_axis if isinstance(
                              self._batch_axis, tuple)
                           else (self._batch_axis,))], dtype=np.int64))
        if dp <= 1:
            warn_or_raise_noop(
                "comm.quantized_collectives has NO effect: the mesh has "
                "no data-parallel degree to exchange over", qc.strict,
                flag="comm.quantized_collectives.strict")
            return
        self._qc_enabled = True
        log_dist(
            "quantized_collectives ON: dtype={} block_size={} "
            "hierarchical={} mesh={}".format(
                qc.dtype, qc.block_size,
                "({})".format(dict(self.mesh.shape))
                if isinstance(self._batch_axis, tuple) else "flat",
                dict(self.mesh.shape)), ranks=[0])

    def _apply_transformer_overrides(self):
        """``transformer.flash_attention``: resolve the tri-state
        ("auto"|"pallas"|"xla", bools legacy) against the live backend
        (ops.transformer.attention.resolve_flash_backend — a forced
        "pallas" off-TPU runs the interpreter with a loud one-time
        warning instead of silently flipping the dense flag) and pin the
        result on the model config. The resolved value is observable as
        ``self.flash_attention_backend`` and in ``telemetry_snapshot()``,
        mirroring the serving engine's ``paged_attention_kernel``."""
        flash = self._config.transformer_flash_attention
        if flash is None:
            return
        from ..ops.transformer.attention import resolve_flash_backend
        resolved = resolve_flash_backend(flash)
        self.flash_attention_backend = resolved
        model_cfg = getattr(self.model, "config", None)
        if hasattr(model_cfg, "use_flash_attention"):
            model_cfg.use_flash_attention = resolved != "xla"
            if hasattr(model_cfg, "flash_attention_backend"):
                model_cfg.flash_attention_backend = resolved
            log_dist("transformer.flash_attention={} resolved to {!r} "
                     "for model {!r}".format(flash, resolved,
                                             self.model.name),
                     ranks=[0])
        else:
            logger.warning(
                "transformer.flash_attention has NO effect: model %r "
                "exposes no use_flash_attention config field",
                self.model.name)

    def _zero_key_noop(self, key, why):
        """A zero_optimization key this runtime cannot honor: warn
        loudly, or raise when zero_optimization.strict is set — never a
        silent no-op (docs/zero3_offload.md)."""
        from ..telemetry.config import warn_or_raise_noop
        warn_or_raise_noop(
            "zero_optimization.{} has NO effect in this runtime: {}"
            .format(key, why),
            getattr(self._config.zero_config, "strict", False),
            flag="zero_optimization.strict")

    def _validate_zero_keys(self, zc, stage):
        """Every parsed zero_optimization key either drives a mechanism
        or is loudly rejected here (VERDICT round 5: silent config no-ops
        are the worst option). Live keys after this PR:
        cpu_offload/cpu_offload_params (offload paths),
        sub_group_size (offload shard-pipeline chunk),
        stage3_max_live_parameters (persistence demotion + streamed
        group sizing), stage3_prefetch_bucket_size (coalesced H2D bucket),
        stage3_param_persistence_threshold (plan),
        ZeRO++ keys (quantize/hpZ). Subsumed-by-XLA keys (overlap_comm,
        reduce_scatter, bucket sizes, contiguous_gradients,
        allgather_partitions) are semantically satisfied by GSPMD —
        documented in docs/zero3_offload.md, not no-ops."""
        from .zero.constants import (
            ZERO_OPTIMIZATION_MAX_REUSE_DISTANCE_DEFAULT)
        if zc.max_reuse_distance is not None and \
                zc.max_reuse_distance != \
                ZERO_OPTIMIZATION_MAX_REUSE_DISTANCE_DEFAULT:
            self._zero_key_noop(
                "stage3_max_reuse_distance",
                "gather/release distance is XLA's memory-aware latency-"
                "hiding schedule; there is no trace-order coordinator to "
                "give the knob meaning")
        if zc.cpu_offload_use_pin_memory:
            self._zero_key_noop(
                "cpu_offload_use_pin_memory",
                "jax exposes no host-pinning control; offload staging "
                "buffers are plain (already DMA-able) host memory")
        if zc.gather_fp16_weights_on_model_save and stage >= 3:
            # trivially satisfied, not a no-op: save_checkpoint always
            # writes the FULL gathered compute-dtype module tree
            # (checkpointing.tree_to_numpy gathers sharded leaves)
            log_dist(
                "stage3_gather_fp16_weights_on_model_save: checkpoint "
                "saves always gather the full compute-dtype weights on "
                "this runtime", ranks=[0])

    def _configure_optimizer(self, client_optimizer):
        from ..ops.adam.fused_adam import FusedAdam, DeepSpeedCPUAdam
        from ..ops.lamb.fused_lamb import FusedLamb

        if client_optimizer is not None:
            if self.zero_cpu_offload() and \
                    getattr(client_optimizer, "adam_w_mode", None) is None:
                # the host step implements Adam only; a client optimizer
                # without Adam semantics would be silently replaced by it
                raise ValueError(
                    "zero_optimization.cpu_offload requires an Adam-family "
                    "optimizer; got client optimizer {}".format(
                        type(client_optimizer).__name__))
            self.optimizer = client_optimizer
            log_dist("Using client optimizer {}".format(
                type(client_optimizer).__name__), ranks=[0])
            self._resolve_onebit_mode()
            return

        name = (self._config.optimizer_name or "adam").lower()
        params = dict(self._config.optimizer_params or {})
        # Route optimizer-level max_grad_norm into the engine's clipping
        # (reference passes it to the FP16 wrapper, config.py warning path).
        max_grad_norm = params.pop("max_grad_norm", None)
        if max_grad_norm and not self._config.gradient_clipping:
            self._config.gradient_clipping = float(max_grad_norm)
        # optimizer.params.fused_kernel: tri-state for the Pallas apply
        # kernels (ops/adam/pallas_adam.py, ops/lamb/pallas_lamb.py),
        # same spelling as transformer.flash_attention. "auto" (default)
        # leaves the optimizer's own backend pick (default_use_pallas);
        # "pallas" forces the kernel — off-TPU it runs the interpreter
        # (the optimizer's update() resolves that) with a loud warning
        # here; "xla" pins the jnp oracle.
        fused_kernel = params.pop("fused_kernel", None)
        if fused_kernel is not None:
            if not isinstance(fused_kernel, str) or \
                    fused_kernel.lower() not in ("auto", "pallas", "xla"):
                raise ValueError(
                    "optimizer.params.fused_kernel must be one of "
                    "auto|pallas|xla, got {!r}".format(fused_kernel))
            fused_kernel = fused_kernel.lower()
            if name not in (ADAM_OPTIMIZER, "adamw", LAMB_OPTIMIZER):
                logger.warning(
                    "optimizer.params.fused_kernel has NO effect: "
                    "optimizer %r has no Pallas apply kernel", name)
            elif fused_kernel != "auto":
                params.setdefault("use_pallas", fused_kernel == "pallas")
                if fused_kernel == "pallas" and \
                        jax.default_backend() != "tpu":
                    logger.warning(
                        "optimizer.params.fused_kernel: 'pallas' forced "
                        "on the %s backend — the fused %s apply runs "
                        "under the Pallas INTERPRETER (orders of "
                        "magnitude slower; parity/debug only)",
                        jax.default_backend(), name)
        self.fused_optimizer_kernel = fused_kernel
        if name in (ADAM_OPTIMIZER, "adamw"):
            if self.zero_cpu_offload():
                self.optimizer = DeepSpeedCPUAdam(**params)
            else:
                self.optimizer = FusedAdam(**params)
        elif name == LAMB_OPTIMIZER:
            self.optimizer = FusedLamb(**params)
        elif name == ONEBIT_ADAM_OPTIMIZER:
            from ..runtime.fp16.onebit_adam import OnebitAdam
            self.optimizer = OnebitAdam(mesh=self.mesh, **params)
        elif name == "sgd":
            from ..ops.sgd import SGD
            self.optimizer = SGD(**params)
        else:
            raise ValueError("Unknown optimizer: {}".format(name))
        if self.zero_optimization() and \
                not getattr(self.optimizer, "supports_zero", True):
            # reference zero/utils.py is_zero_supported_optimizer
            raise ValueError(
                "{} is not compatible with ZeRO (zero_optimization.stage "
                ">= 1)".format(type(self.optimizer).__name__))
        if self.zero_cpu_offload() \
                and name not in (ADAM_OPTIMIZER, "adamw"):
            # the host step is Adam-only (reference restricts offload to
            # DeepSpeedCPUAdam the same way)
            raise ValueError(
                "zero_optimization.cpu_offload requires the Adam/AdamW "
                "optimizer, got '{}'".format(name))
        self._resolve_onebit_mode()
        log_dist("Using DeepSpeed optimizer: {}".format(name), ranks=[0])

    def _certify_local_grad_comm(self, feature):
        """The ONE certified-combination gate every local-grad comm
        feature (quantized_collectives, OneBitAdam) passes: the body
        runs the model fully manual over the data axis, so non-data mesh
        axes are rejected; ZeRO-3's data-sharded compute params cannot
        feed it; qgZ would double-quantize the same reduction."""
        from ..parallel.topology import (MODEL_AXIS, PIPE_AXIS,
                                         SEQUENCE_AXIS)
        for axis in (PIPE_AXIS, MODEL_AXIS, SEQUENCE_AXIS):
            if axis in self.mesh.shape and self.mesh.shape[axis] > 1:
                raise ValueError(
                    "{} is not a certified combination with the '{}' "
                    "mesh axis (the local-grad exchange runs the model "
                    "fully manual over the data axis only)".format(
                        feature, axis))
        if self._config.zero_optimization_stage >= 3:
            raise ValueError(
                "{} is not compatible with ZeRO stage 3 (data-sharded "
                "compute params cannot feed the local-grad shard_map "
                "body; stages 0-2 are supported — use "
                "zero_quantized_weights/zero_quantized_gradients at "
                "stage 3, docs/onebit_adam.md)".format(feature))
        if self._config.zero_config.quantized_gradients:
            raise ValueError(
                "{} with zero_quantized_gradients (qgZ) double-"
                "quantizes the gradient reduction — enable one (the "
                "local-grad exchange moves real compressed wire; qgZ "
                "models the codec on the GSPMD path)".format(feature))

    def _resolve_onebit_mode(self):
        """OneBitAdam: the micro step computes per-worker LOCAL grads
        (stacked, shard_map over the data axis) and the apply step runs
        the compressed momentum exchange — certified combinations only
        (docs/onebit_adam.md)."""
        from .fp16.onebit_adam import OnebitAdam
        self._onebit_mode = isinstance(self.optimizer, OnebitAdam)
        if not self._onebit_mode:
            return
        self._certify_local_grad_comm("OneBitAdam")
        stage = self._config.zero_optimization_stage
        if self.zero_cpu_offload():
            raise ValueError(
                "OneBitAdam is not compatible with cpu_offload (the "
                "compressed exchange runs on device; the host step is "
                "plain Adam)")
        if self.gradient_clipping():
            raise ValueError(
                "OneBitAdam does not support gradient_clipping: the "
                "global grad norm is never materialized in the "
                "compressed regime (grads stay per-worker local)")
        if float(getattr(self.optimizer, "weight_decay", 0.0) or 0.0) \
                and stage >= 1:
            raise ValueError(
                "OneBitAdam weight_decay needs replicated params (the "
                "L2 term feeds the fused flat momentum on every "
                "worker); use ZeRO stage 0 or weight_decay=0")
        self.optimizer.configure_comm(self.mesh)

    def _configure_lr_scheduler(self, client_lr_scheduler):
        if client_lr_scheduler is not None:
            self.lr_scheduler = client_lr_scheduler
            return
        name = self._config.scheduler_name
        if name is not None:
            cls = SCHEDULE_CLASSES.get(name)
            if cls is None:
                raise ValueError("Unknown lr schedule: {}".format(name))
            params = self._config.scheduler_params or {}
            self.lr_scheduler = cls(self.optimizer, **params)
            log_dist("DeepSpeed using configured LR scheduler = {}".format(name),
                     ranks=[0])
        else:
            self.lr_scheduler = None

    def _configure_pld(self):
        if self._config.pld_enabled:
            pld_params = self._config.pld_params or {}
            self.progressive_layer_drop = ProgressiveLayerDrop(**pld_params)
        else:
            self.progressive_layer_drop = None

    def _init_state(self):
        """Place params/master/opt/grad-accum arrays with ZeRO shardings."""
        self.host_state = None
        self.stream_runner = None
        if self.zero_params_offload() or self.zero_cpu_offload():
            # the master and the moments stay on the host: one phase
            with setup_span("setup.optimizer", engine=self.startup_tag):
                self._init_offload_state(self.zero_plan)
        else:
            self._init_device_state(self.zero_plan)

    def _init_offload_state(self, plan):
        if self.zero_params_offload():
            # Streamed parameter offload (cpu_offload_params): the fp32
            # master + Adam moments live in HOST memory like classic
            # ZeRO-Offload, but compute params have NO resident device
            # copy — each step streams them into HBM one layer group at
            # a time (runtime/zero/stream.py). The host registry keeps
            # the classic offload layout (one full-leaf entry per
            # master leaf) so every checkpoint path works unchanged.
            master_np = jax.tree_util.tree_map(
                lambda p: np.array(p, dtype=np.float32, copy=True),
                self.model.params)
            flat_master, treedef = jax.tree_util.tree_flatten(master_np)
            from .zero.stream import _full_index
            self.host_state = {
                "shard_leaves": [
                    [(_full_index(p.shape), p,
                      np.zeros(p.shape, np.float32),
                      np.zeros(p.shape, np.float32))]
                    for p in flat_master],
                "treedef": treedef,
                "leaf_shapes": [p.shape for p in flat_master],
                "step": 0,
                "streamed": True,
            }
            self.state = {
                "params": None,      # transient, streamed per group
                "master": None,
                "opt": None,
                "acc_grads": None,   # accumulated in host buffers
                "scaler": ls.loss_scaler_from_config(self._config),
            }
            del master_np, flat_master
            self.model.params = None
            from .zero.stream import StreamedOffloadRunner
            self.stream_runner = StreamedOffloadRunner(self)
            return
        if self.zero_cpu_offload():
            # True ZeRO-Offload (reference stage2/3 cpu_offload): fp32
            # master + Adam moments live in HOST memory as numpy; HBM only
            # holds compute-dtype params + fp32 grad accumulators. The
            # optimizer step runs on host cores (_host_apply_step).
            #
            # Multi-process (reference stage2.py:780-908 distributed
            # offload): every process keeps only the host shards matching
            # its ADDRESSABLE acc_grad shards (the ZeRO grad partition), so
            # host memory, PCIe transfer and the host Adam all split
            # process-ways. Single-process is the degenerate one-shard (or
            # all-shards) case of the same machinery.
            #
            # the bf16-state HBM levers do not apply here: the host step
            # consumes fp32 numpy shards end to end
            if self._config.grad_accum_dtype == "bf16":
                logger.warning(
                    "data_types.grad_accum_dtype=bf16 ignored: the host "
                    "offload step consumes fp32 accumulated grads")
            if getattr(self.optimizer, "moments_dtype", jnp.float32) \
                    != jnp.float32:
                logger.warning(
                    "optimizer moments_dtype=%s ignored under "
                    "cpu_offload: host shard moments are fp32 numpy",
                    jnp.dtype(self.optimizer.moments_dtype).name)
            # np.array(copy=True): np.asarray of a jax array is a READ-ONLY
            # view aliasing the runtime's buffer — the in-place host Adam
            # would crash (or scribble on JAX-owned memory via the C ptr)
            master_np = jax.tree_util.tree_map(
                lambda p: np.array(p, dtype=np.float32, copy=True),
                self.model.params)
            param_sh = plan.tree_shardings(master_np, "param")
            grad_sh = plan.tree_shardings(master_np, "grad")
            compute_params = jax.tree_util.tree_map(
                self._host_to_device, master_np, param_sh)
            acc_grads = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(
                    jnp.zeros(p.shape, jnp.float32), s), master_np, grad_sh)
            # flat per-leaf shard lists [(index, master, exp_avg,
            # exp_avg_sq)], one entry per UNIQUE addressable shard index of
            # the grad sharding (replicated leaves dedupe to one full-size
            # entry); aligned with tree_flatten(acc_grads)
            flat_master, treedef = jax.tree_util.tree_flatten(master_np)
            flat_acc = treedef.flatten_up_to(acc_grads)
            shard_leaves = [
                [(idx, np.array(p[idx], dtype=np.float32, copy=True),
                  np.zeros(p[idx].shape, np.float32),
                  np.zeros(p[idx].shape, np.float32))
                 for idx in _unique_shard_indices(g)]
                for p, g in zip(flat_master, flat_acc)]
            self.host_state = {
                "shard_leaves": shard_leaves,
                "treedef": treedef,
                "leaf_shapes": [np.shape(p) for p in flat_master],
                "step": 0,
                # static for the engine's life; cached for the per-step H2D
                "param_shardings": param_sh,
            }
            self.state = {
                "params": compute_params,
                "master": None,
                "opt": None,
                "acc_grads": acc_grads,
                "scaler": ls.loss_scaler_from_config(self._config),
                # no skip_count here: the host optimizer step observes the
                # overflow flag every step, so the host counter is already
                # exact on the offload path
            }
            self._init_qg_error(acc_grads)
            self.model.params = None

    def _init_device_state(self, plan):
        # copy=True: jnp.asarray of same-dtype input is a VIEW of the
        # caller's arrays; the jitted step donates engine state, so an
        # aliased user array would be invalidated ("Buffer has been deleted
        # or donated") if the caller builds a second engine from it
        with setup_span("setup.params", engine=self.startup_tag) as attrs:
            params_f32 = jax.tree_util.tree_map(
                lambda p: jnp.array(p, dtype=jnp.float32, copy=True),
                self.model.params)

            param_sh = plan.tree_shardings(params_f32, "param")
            master_sh = plan.tree_shardings(params_f32, "master")
            grad_sh = plan.tree_shardings(params_f32, "grad")

            compute_params = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(
                    jnp.asarray(p, self.compute_dtype), s),
                params_f32, param_sh)

            if self.mixed_precision:
                master = jax.tree_util.tree_map(
                    lambda p, s: jax.device_put(p, s), params_f32,
                    master_sh)
            else:
                master = None
            attrs.update(_tree_size(compute_params, master))

        with setup_span("setup.optimizer",
                        engine=self.startup_tag) as attrs:
            opt_target = master if self.mixed_precision else compute_params
            opt_state = self.optimizer.init_state(opt_target)
            # all per-param moments/buffers live with the master shards; state
            # shapes may differ from param shapes (e.g. OnebitAdam's flat error
            # buffers), so shardings come from each subtree's own leaves —
            # unless the optimizer declares a placement (state_placements():
            # OnebitAdam keeps the fused momentum replicated and the error
            # tensors per-worker)
            opt_state = {
                key: val if key == "step" else jax.tree_util.tree_map(
                    lambda m, s: jax.device_put(m, s), val,
                    self._opt_state_shardings(key, val))
                for key, val in opt_state.items()
            }
            acc_dtype = jnp.float32
            if self._config.grad_accum_dtype == "bf16":
                # (the cpu_offload path warned and returned above)
                if self.gradient_accumulation_steps() > 1:
                    logger.warning(
                        "grad_accum_dtype=bf16 with gradient_accumulation_"
                        "steps=%d: bf16 summation across micro-steps is "
                        "lossy (it is exact only at 1 step)",
                        self.gradient_accumulation_steps())
                elif self.compute_dtype != jnp.bfloat16:
                    logger.warning(
                        "grad_accum_dtype=bf16 truncates %s gradients: "
                        "storage is lossless only when the compute dtype "
                        "is bf16 too", jnp.dtype(self.compute_dtype).name)
                acc_dtype = jnp.bfloat16
            if self._onebit_mode:
                # per-worker LOCAL gradient accumulators: a leading (world,)
                # dim sharded one row per device — the local-grad micro step
                # writes its own row, the 1-bit exchange consumes them. The
                # accumulation dtype stays fp32 (the exchange math is fp32).
                if acc_dtype != jnp.float32:
                    logger.warning(
                        "grad_accum_dtype=bf16 ignored under OneBitAdam: the "
                        "compressed exchange consumes fp32 local grads")
                w = self.dp_world_size
                stacked_sh = self._stacked_grad_sharding()
                acc_grads = jax.tree_util.tree_map(
                    lambda p: jax.device_put(
                        jnp.zeros((w,) + p.shape, dtype=jnp.float32),
                        stacked_sh), params_f32)
            else:
                acc_grads = jax.tree_util.tree_map(
                    lambda p, s: jax.device_put(
                        jnp.zeros(p.shape, dtype=acc_dtype), s),
                    params_f32, grad_sh)

            # the scalar leaves are committed replicated on the mesh, as every
            # step program returns them: left uncommitted, the SECOND call of
            # each step program sees new input types and compiles it all again
            replicated = NamedSharding(self.mesh, P())
            opt_state["step"] = jax.device_put(opt_state["step"], replicated)
            self.state = {
                "params": compute_params,
                "master": master,
                "opt": opt_state,
                "acc_grads": acc_grads,
                "scaler": jax.device_put(
                    ls.loss_scaler_from_config(self._config), replicated),
                # device-resident skipped-step counter: keeps skipped_steps
                # exact even when the overflow flag is only fetched
                # periodically
                "skip_count": jax.device_put(jnp.int32(0), replicated),
            }
            self._init_qg_error(acc_grads)
            attrs.update(_tree_size(opt_state, acc_grads))
        del params_f32
        self.model.params = None  # single source of truth is the state

    def _init_qg_error(self, acc_grads):
        """qgZ error-feedback accumulator, sharded like the grads it
        compensates (fp32: residuals are sub-int8-lsb sized; stored in
        unscaled units — see _micro_step_fn)."""
        if not self._qgz_enabled:
            return
        self.state["qg_error"] = jax.tree_util.tree_map(
            lambda g: jax.device_put(
                jnp.zeros(g.shape, jnp.float32), g.sharding),
            acc_grads)

    # ----------------------------------------------------------- data plumbing
    def deepspeed_io(self, dataset, batch_size=None, route=ROUTE_TRAIN,
                     data_sampler=None, collate_fn=None, num_local_io_workers=None):
        if batch_size is None:
            batch_size = self.train_micro_batch_size_per_gpu() * \
                self._local_dp_share()
        return DeepSpeedDataLoader(
            dataset, batch_size=batch_size,
            collate_fn=collate_fn or self.collate_fn,
            data_parallel_world_size=jax.process_count(),
            data_parallel_rank=jax.process_index(),
            shuffle=(route == ROUTE_TRAIN))

    def _local_dp_share(self):
        """How many of the dp shards this process feeds."""
        return max(self.dp_world_size // jax.process_count(), 1)

    def _batch_sharding(self, ndim):
        return NamedSharding(self.mesh,
                             P(self._batch_axis, *([None] * (ndim - 1))))

    def _to_device(self, batch):
        """Numpy batch (global or per-process) -> sharded jax.Arrays."""
        def put(x):
            x = np.asarray(x)
            if x.ndim == 0 or x.shape[0] % self.dp_world_size != 0:
                return jax.device_put(x, NamedSharding(self.mesh, P()))
            sharding = self._batch_sharding(x.ndim)
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)
        placed = jax.tree_util.tree_map(put, batch)
        # TRAIN-mode forwards only: an eval batch (arbitrary rows, often
        # replicated) must never stand in for the training micro-batch
        # the audit abstract-evals the step programs with
        if self._audit_batch_struct is None and self._mode == ROUTE_TRAIN:
            self._audit_batch_struct = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                placed)
        return placed

    # ------------------------------------------------------------- jitted fns
    def _hyper(self):
        h = self.optimizer.hyperparams()
        return {k: np.asarray(v, dtype=np.float32) for k, v in h.items()}

    def _loss_of(self, out):
        if isinstance(out, (tuple, list)):
            return out[0]
        return out

    def _qwz_gather_tree_fn(self):
        """qwZ: params tree -> gathered-params tree (None when disabled).

        Each data-sharded stage-3 leaf goes through ``qwz_gather``: the
        all-gather XLA emits moves int8 blocks + per-block scales instead
        of the compute dtype, and the straight-through vjp routes the
        cotangent back as the sharded-layout reduce-scatter."""
        if not getattr(self, "_qwz_enabled", False):
            return None
        from .comm.quantize import qwz_gather
        from .zero.partition import _path_str
        plan = self.zero_plan

        def gather(params):
            def leaf(path, p):
                shape = np.shape(p)
                if not plan.param_is_data_sharded(path, shape):
                    return p
                return qwz_gather(p, plan.gather_sharding(path, shape),
                                  plan.param_sharding(path, shape))
            return jax.tree_util.tree_map_with_path(
                lambda kp, p: leaf(_path_str(kp), p), params)

        return gather

    def _param_gather_tree_fn(self):
        """The stage-3 weight-materialization seam of the jitted steps:
        the collective-matmul ring gather when comm.collective_matmul
        is live for ZeRO-3 (carrying qwZ's int8 blocks + scales on the
        rotated chunks when both are on), else the qwZ sharding-
        constraint gather, else None (plain GSPMD gathers)."""
        if getattr(self, "_cm_zero3", False):
            from ..parallel.collective_matmul import make_zero3_gather_fn
            from .comm.quantize import DEFAULT_BLOCK_SIZE
            return make_zero3_gather_fn(
                self.zero_plan, self.mesh, chunks=self._cm.chunks,
                quantized=getattr(self, "_qwz_enabled", False),
                block_size=DEFAULT_BLOCK_SIZE)
        return self._qwz_gather_tree_fn()

    def _opt_state_shardings(self, key, val):
        """Sharding tree for one optimizer-state subtree, honoring the
        optimizer's placement hints (state_placements()): "replicated"
        (OnebitAdam's fused momentum — every worker compresses the full
        buffer), "stacked" (per-worker rows over the data axis), default
        = the master-shard plan."""
        hints = getattr(self.optimizer, "state_placements", None)
        kind = (hints() if hints is not None else {}).get(key, "master")
        if kind == "replicated":
            rep = self.zero_plan.replicated()
            return jax.tree_util.tree_map(lambda _: rep, val)
        if kind == "stacked":
            sh = self._stacked_grad_sharding()
            return jax.tree_util.tree_map(lambda _: sh, val)
        return self.zero_plan.tree_shardings(val, "master")

    def _opt_constrain(self, key, val):
        """with_sharding_constraint one optimizer-state subtree to its
        resolved placement (the in-jit twin of _opt_state_shardings)."""
        return jax.tree_util.tree_map(
            lambda x, s: jax.lax.with_sharding_constraint(x, s), val,
            self._opt_state_shardings(key, val))

    def _stacked_grad_sharding(self):
        """One row per device over the data axis (or its factored
        sub-axes): the layout of per-worker local grads / error state."""
        return NamedSharding(self.mesh, P(self._batch_axis))

    def _constrain_grads(self, tree):
        """Sharding constraint for the accumulated-gradient tree: the
        stacked per-worker layout under OneBitAdam, the ZeRO grad plan
        otherwise."""
        if getattr(self, "_onebit_mode", False):
            sh = self._stacked_grad_sharding()
            return jax.tree_util.tree_map(
                lambda x: jax.lax.with_sharding_constraint(x, sh), tree)
        return self.zero_plan.constrain(tree, "grad")

    def _local_grad_mode(self):
        """Which local-gradient micro-step variant is live: "stacked"
        (OneBitAdam — grads stay per-worker for the momentum exchange),
        "exchange" (quantized_collectives with a plain optimizer — grads
        average through the in-collective int8 ring inside the micro
        step), or None (the GSPMD oracle path)."""
        if getattr(self, "_onebit_mode", False):
            return "stacked"
        if getattr(self, "_qc_enabled", False):
            return "exchange"
        return None

    def _flat_grad_meta(self):
        """The fused flat-gradient-buffer layout the quantized exchange
        rides (comm.quantize.FusedFlatLayout — the SAME layout helper
        OnebitAdam's momentum buffer uses), padded to whole blocks per
        rank chunk (qc_padded_size)."""
        if getattr(self, "_flat_meta_cache", None) is not None:
            return self._flat_meta_cache
        from .comm.quantize import FusedFlatLayout, qc_padded_size
        params = self.state["params"] if self.state is not None and \
            self.state.get("params") is not None else self.model.params
        self._flat_meta_cache = FusedFlatLayout(
            params, lambda n: qc_padded_size(n, self.dp_world_size,
                                             self._qc.block_size))
        return self._flat_meta_cache

    def _qc_exchange_fn(self):
        """The in-collective quantized all-reduce over a fused flat fp32
        buffer, resolved for this mesh: the two-level hierarchical
        decomposition on a factored data axis, the flat EQuARX ring
        otherwise. Returns a per-device body: (padded,) local partials ->
        (padded,) fp32 global SUM (call inside shard_map)."""
        from .comm.quantize import (hierarchical_all_reduce_local,
                                    quantized_all_reduce_local)
        block = self._qc.block_size
        if isinstance(self._batch_axis, tuple):
            replica_axis, shard_axis = self._batch_axis
            wr = int(self.mesh.shape[replica_axis])
            ws = int(self.mesh.shape[shard_axis])

            def exchange(flat):
                return hierarchical_all_reduce_local(
                    flat, shard_axis, replica_axis, ws, wr, block)
        else:
            axis = self._batch_axis
            world = self.dp_world_size

            def exchange(flat):
                return quantized_all_reduce_local(flat, axis, world,
                                                  block)
        return exchange

    def _micro_step_fn(self):
        if self._local_grad_mode() is not None:
            return self._local_grad_micro_fn()
        apply_fn = self.model.apply_fn
        gas = self.gradient_accumulation_steps()
        plan = self.zero_plan
        model = self.model
        qwz = self._param_gather_tree_fn()
        qgz = getattr(self, "_qgz_enabled", False)
        if qgz:
            from .comm.quantize import quantize_with_error_feedback

        def micro(state, batch, rng, pld_theta=None):
            kwargs = {**model.rng_kwargs(rng), **model.mode_kwargs(True)}
            if self.progressive_layer_drop:
                # theta must arrive as a TRACED operand — reading
                # get_theta() here would constant-fold the schedule's
                # initial value into the compiled step
                if model.accepts_kwarg("progressive_layer_drop"):
                    kwargs["progressive_layer_drop"] = True
                if model.accepts_kwarg("pld_theta"):
                    kwargs["pld_theta"] = pld_theta

            def loss_fn(compute_params):
                if qwz is not None:
                    compute_params = qwz(compute_params)
                out = apply_fn(compute_params, *batch, **kwargs)
                loss = self._loss_of(out)
                scaled = loss.astype(jnp.float32) * \
                    (state["scaler"].cur_scale / gas)
                return scaled, loss

            (_, loss), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(state["params"])
            new_state = dict(state)
            if qgz:
                # qgZ: each micro-step's gradient contribution passes
                # through the error-compensated int8 codec before
                # accumulation — the numerics of a quantized gradient
                # reduce-scatter, with the residual carried across steps
                # so the long-run average stays unbiased. The residual is
                # stored in UNSCALED units (grads carry the loss scale),
                # so a dynamic-scale change between steps cannot inject a
                # wrong-magnitude correction.
                cur_scale = state["scaler"].cur_scale
                qd_and_err = jax.tree_util.tree_map(
                    lambda g, e: quantize_with_error_feedback(
                        g, e, scale=cur_scale),
                    grads, state["qg_error"])
                grads = jax.tree_util.tree_map(
                    lambda p, qe: qe[0], grads, qd_and_err)
                new_state["qg_error"] = plan.constrain(
                    jax.tree_util.tree_map(
                        lambda p, qe: qe[1], grads, qd_and_err),
                    "grad")
            new_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), state["acc_grads"],
                grads)
            new_acc = plan.constrain(new_acc, "grad")
            new_state["acc_grads"] = new_acc
            return new_state, loss

        return micro

    def _local_grad_micro_fn(self):
        """The local-gradient micro step (OneBitAdam / quantized
        collectives): forward + backward run FULLY MANUAL over the data
        axis inside shard_map, so each device's gradients are its OWN
        micro-batch shard's — no GSPMD fp32 gradient psum is ever
        emitted. "stacked" mode (OneBitAdam) accumulates the per-worker
        grads as (world, ...) rows for the 1-bit momentum exchange;
        "exchange" mode averages them through the in-collective int8
        ring (EQuARX) right here, so downstream the step is byte-for-
        byte the GSPMD program minus the fp32 reduce. The scalar loss is
        pmean'd for reporting (a handful of wire bytes)."""
        apply_fn = self.model.apply_fn
        gas = self.gradient_accumulation_steps()
        model = self.model
        mode = self._local_grad_mode()
        mesh = self.mesh
        axes = self._batch_axis
        world = self.dp_world_size
        meta = self._flat_grad_meta() if mode == "exchange" else None
        exchange = self._qc_exchange_fn() if mode == "exchange" else None
        pld_live = self.progressive_layer_drop is not None
        from ..parallel.topology import shard_map_compat

        def micro(state, batch, rng, pld_theta=None):
            leaves, batch_def = jax.tree_util.tree_flatten(batch)
            specs = tuple(
                P(axes) if getattr(leaf, "ndim", 0) >= 1 and
                leaf.shape[0] % world == 0 else P()
                for leaf in leaves)
            scale = state["scaler"].cur_scale

            def per_dev(compute_params, *local_leaves):
                local_batch = jax.tree_util.tree_unflatten(
                    batch_def, list(local_leaves))
                lrng = rng
                if lrng is not None and world > 1:
                    # honest per-device dropout masks: fold the device's
                    # position into the key (the GSPMD path draws one
                    # global mask; statistically equivalent)
                    lrng = jax.random.fold_in(
                        lrng, jax.lax.axis_index(axes))
                kwargs = {**model.rng_kwargs(lrng),
                          **model.mode_kwargs(True)}
                if pld_live:
                    if model.accepts_kwarg("progressive_layer_drop"):
                        kwargs["progressive_layer_drop"] = True
                    if model.accepts_kwarg("pld_theta"):
                        kwargs["pld_theta"] = pld_theta

                def loss_fn(p):
                    out = apply_fn(p, *local_batch, **kwargs)
                    loss = self._loss_of(out)
                    scaled = loss.astype(jnp.float32) * (scale / gas)
                    return scaled, loss

                (_, loss), grads = jax.value_and_grad(
                    loss_fn, has_aux=True)(compute_params)
                loss = jax.lax.pmean(loss, axes)
                if mode == "exchange":
                    flat = meta.flatten(grads)
                    summed = exchange(flat)
                    mean = summed * jnp.float32(1.0 / world)
                    return loss, meta.unflatten_like(mean, grads)
                return loss, jax.tree_util.tree_map(
                    lambda g: g[None].astype(jnp.float32), grads)

            out_spec = P() if mode == "exchange" else P(axes)
            sharded = shard_map_compat(
                per_dev, mesh=mesh, in_specs=(P(),) + specs,
                out_specs=(P(), out_spec))
            loss, grads = sharded(state["params"], *leaves)
            new_state = dict(state)
            new_acc = jax.tree_util.tree_map(
                lambda a, g: a + g.astype(a.dtype), state["acc_grads"],
                grads)
            new_state["acc_grads"] = self._constrain_grads(new_acc)
            return new_state, loss

        return micro

    def _onebit_frozen(self):
        """Whether the NEXT optimizer step runs OneBitAdam's compressed
        regime — host-side, so the engine compiles one program per
        regime (global_steps counts attempted steps; under overflow
        skips it can run ahead of the device step counter by
        skipped_steps, documented in docs/onebit_adam.md)."""
        return getattr(self, "_onebit_mode", False) and \
            self.optimizer.frozen_at(self.global_steps)

    def _regime_jit_key(self, base):
        """Jit-cache key for a step program that differs by OneBitAdam
        regime; invalidates the cached wire estimate when the regime
        flips (the compressed wire differs from warmup's)."""
        if not getattr(self, "_onebit_mode", False):
            return base
        frozen = self._onebit_frozen()
        if frozen != getattr(self, "_onebit_last_regime", None):
            self._onebit_last_regime = frozen
            self._tele_wire = "unset"
        return base + ("@ob_frozen" if frozen else "@ob_warmup")

    def _apply_step_fn(self, frozen=None):
        plan = self.zero_plan
        optimizer = self.optimizer
        clip = self.gradient_clipping()
        mixed = self.mixed_precision
        compute_dtype = self.compute_dtype
        onebit = getattr(self, "_onebit_mode", False)
        if frozen is None:
            frozen = self._onebit_frozen()
        qc_meta = qc_exchange = None
        if onebit and not frozen and getattr(self, "_qc_enabled", False):
            qc_meta = self._flat_grad_meta()
            qc_exchange = self._qc_exchange_fn()
        world = self.dp_world_size

        def _onebit_grads(grads):
            """Per-worker stacked grads -> (update grads, grad_norm).
            Warmup: average the workers (through the in-collective int8
            ring when quantized_collectives is on, the plain fp32
            allreduce otherwise) — exact Adam follows. Frozen: grads
            STAY per-worker (the 1-bit momentum exchange consumes them);
            grad_norm is the RMS-over-workers estimate
            sqrt(sum_w ||g_w||^2 / w) — equal to the true norm when
            workers agree, an upper bound otherwise (the averaged
            gradient is never materialized in this regime)."""
            if frozen:
                norm = get_grad_norm(grads) / \
                    jnp.sqrt(jnp.float32(world))
                return grads, True, norm
            if qc_exchange is not None:
                from jax.sharding import PartitionSpec as SMP
                from ..parallel.topology import shard_map_compat

                def per_dev(stacked_leaves):
                    flat = qc_meta.flatten(
                        jax.tree_util.tree_map(lambda g: g[0],
                                               stacked_leaves))
                    summed = qc_exchange(flat)
                    return summed * jnp.float32(1.0 / world)

                sharded = shard_map_compat(
                    per_dev, mesh=self.mesh,
                    in_specs=(SMP(self._batch_axis),), out_specs=SMP())
                mean_flat = sharded(grads)
                like = jax.tree_util.tree_map(lambda g: g[0], grads)
                avg = qc_meta.unflatten_like(mean_flat, like)
            else:
                avg = jax.tree_util.tree_map(
                    lambda g: g.mean(axis=0), grads)
            return avg, False, get_grad_norm(avg)

        def apply_step(state, hyper):
            scaler = state["scaler"]
            grads = state["acc_grads"]
            overflow = CheckOverflow.has_overflow(grads)
            inv_scale = 1.0 / scaler.cur_scale
            # accumulation may be stored bf16 (grad_accum_dtype); the
            # unscale/clip/update math always runs fp32
            grads = jax.tree_util.tree_map(
                lambda g: g.astype(jnp.float32) * inv_scale, grads)
            target = state["master"] if mixed else state["params"]
            if onebit:
                # clip is rejected at config time for OneBitAdam
                grads, stacked, grad_norm = _onebit_grads(grads)
                new_target, new_opt = optimizer.update(
                    grads, state["opt"], target, lr=hyper["lr"],
                    beta1=hyper["beta1"], beta2=hyper["beta2"],
                    eps=hyper["eps"],
                    weight_decay=hyper["weight_decay"],
                    frozen=frozen, averaged=not stacked)
            else:
                if clip > 0:
                    grads, grad_norm = clip_grad_norm_(grads, clip)
                else:
                    grad_norm = get_grad_norm(grads)
                new_target, new_opt = optimizer.update(
                    grads, state["opt"], target, lr=hyper["lr"],
                    beta1=hyper["beta1"], beta2=hyper["beta2"],
                    eps=hyper["eps"],
                    weight_decay=hyper["weight_decay"])

            # Branchless overflow-skip (reference engine.py:1073-1083 +
            # stage2.py overflow path): select old state when overflowed.
            keep = lambda new, old: jax.tree_util.tree_map(
                lambda n, o: jnp.where(overflow, o, n), new, old)
            new_target = keep(new_target, target)
            new_opt = keep(new_opt, state["opt"])

            new_state = dict(state)
            new_state["opt"] = new_opt
            if mixed:
                new_state["master"] = plan.constrain(new_target, "master")
                new_params = jax.tree_util.tree_map(
                    lambda m: m.astype(compute_dtype), new_target)
                # stage<3: re-replicate (the all-gather of updated partitions,
                # stage2.py:1419-1513); stage 3: stays sharded.
                new_state["params"] = plan.constrain(new_params, "param")
            else:
                new_state["params"] = plan.constrain(new_target, "param")
            new_state["acc_grads"] = self._constrain_grads(
                jax.tree_util.tree_map(jnp.zeros_like,
                                       state["acc_grads"]))
            new_state["opt"] = {
                key: val if key == "step" else self._opt_constrain(key,
                                                                   val)
                for key, val in new_opt.items()
            }
            # an overflowed window compressed inf/nan through the 1-bit
            # codec — the worker/server residuals are poisoned; zero them
            # with the skip, like qg_error below (the optimizer declares
            # which subtrees are error feedback)
            for err_key in getattr(optimizer, "error_state_keys", ()):
                if err_key in new_state["opt"]:
                    new_state["opt"][err_key] = jax.tree_util.tree_map(
                        lambda e: jnp.where(overflow, jnp.zeros_like(e),
                                            e),
                        new_state["opt"][err_key])
            new_state["scaler"] = ls.update_scale(scaler, overflow)
            if "qg_error" in state:
                # an overflowed micro window quantized inf/nan grads, so
                # the qgZ residual is poisoned — reset it with the skip
                # (a stale-scale residual is also dropped here, matching
                # the reference's error-state reset on overflow)
                new_state["qg_error"] = jax.tree_util.tree_map(
                    lambda e: jnp.where(overflow, jnp.zeros_like(e), e),
                    state["qg_error"])
            if "skip_count" in state:
                new_state["skip_count"] = (
                    state["skip_count"] + overflow.astype(jnp.int32))

            metrics = {
                "overflow": overflow,
                "grad_norm": grad_norm,
                "loss_scale": scaler.cur_scale,
            }
            return new_state, metrics

        return apply_step

    def _get_jit(self, key, builder, donate=(), **jit_kwargs):
        if self._first_call_row is not None:
            # the engine asks for its next program: the one it made
            # last has been called
            self._first_call_over()
        if key not in self._jit_cache:
            from .executor.jit import jit_program
            self._jit_cache[key] = self._first_call(
                _program_name(key), key,
                jit_program(builder(), donate=donate, **jit_kwargs))
        return self._jit_cache[key]

    def _first_call(self, program, key, fn):
        """A step program just made enters the start-up record
        (docs/telemetry.md, "Start-up record"): its ``setup.program``
        row stays open until the engine asks for another program or the
        step ends, fenced on the engine's state. Nothing wraps the call
        itself. -> ``fn``."""
        from .executor.jit import first_call
        if self._first_call_row is not None:
            self._first_call_over()
        self._first_call_row = first_call(fn, program, key,
                                          self.startup_tag,
                                          self.global_steps)
        return fn

    def _first_call_over(self):
        from .executor.jit import first_call_over
        jax.block_until_ready(self.state)
        first_call_over(self._first_call_row,
                        operands=self._first_call_operands)
        self._first_call_row = self._first_call_operands = None

    def startup_report(self):
        """This engine's rows of the start-up record (docs/telemetry.md,
        "Start-up record"): its ``setup.engine`` and phases, and one
        ``setup.program`` row for each step program that has run."""
        if self._first_call_row is not None:
            self._first_call_over()
        return startup_report(self.startup_tag)

    def startup_line(self):
        return startup_line(self.startup_tag)

    def program_scopes(self):
        """Which scope each instruction of this engine's compiled step
        programs was traced under, one entry a program that has run
        (docs/telemetry.md, "Device scopes"). Lowers and compiles (or
        loads) each once more: seconds a program where the executable
        is found again, its whole compile where not; for after a trace
        window and not inside one; an error inside a step."""
        if self._first_call_row is not None or \
                getattr(self, "_pending_backward", False):
            raise RuntimeError("program_scopes() inside a step")
        return program_scopes(self.startup_tag)

    # -------------------------------------------------------------- telemetry
    def _check_memory_breakdown(self):
        """``memory_breakdown`` drives per-step HBM reporting (telemetry
        records + monitor scalars + see_memory_usage at print
        boundaries). A backend without ``memory_stats()`` cannot honor
        it: warn loudly, raise under telemetry.strict — never a silent
        no-op (the PR 4 stage-3 key policy)."""
        if not self._config.memory_breakdown:
            return
        from ..telemetry.collector import collect_memory_stats
        if collect_memory_stats()["available"]:
            return
        from ..telemetry.config import warn_or_raise_noop
        warn_or_raise_noop(
            "memory_breakdown=true but backend {!r} exposes no "
            "memory_stats() — per-step HBM live/peak reporting is "
            "unavailable on this runtime".format(jax.default_backend()),
            getattr(self._config.telemetry_config, "strict", False))

    def telemetry_snapshot(self):
        """Rolling-window aggregate of the emitted StepRecords (p50/p95
        step time, MFU, tokens/s/chip, phase means, wire bytes) — ``{}``
        when telemetry is disabled. Benches embed this under
        ``extra.telemetry``. Resolved kernel tri-states ride along under
        ``kernels`` (observable like the serving engine's
        paged_attention_kernel) whenever either ds_config key was set."""
        out = self.telemetry.snapshot() if self.telemetry is not None \
            else {}
        if out and (self.flash_attention_backend is not None or
                    self.fused_optimizer_kernel is not None):
            out = dict(out)
            out["kernels"] = {
                "flash_attention": self.flash_attention_backend,
                "fused_optimizer": self.fused_optimizer_kernel,
            }
        return out

    def resolved_kernels(self):
        """What the step programs actually run, whether or not a
        ds_config key asked: ``{"flash_attention", "fused_optimizer"}``
        each "pallas" (compiled kernel) | "interpret" | "xla" | None (the
        model / optimizer has no such kernel)."""
        out = {"flash_attention": self.flash_attention_backend,
               "fused_optimizer": None}
        model_cfg = getattr(self.model, "config", None)
        if out["flash_attention"] is None and \
                hasattr(model_cfg, "use_flash_attention"):
            from ..ops.transformer.attention import resolve_flash_backend
            out["flash_attention"] = \
                getattr(model_cfg, "flash_attention_backend", None) or \
                resolve_flash_backend(bool(model_cfg.use_flash_attention))
        if hasattr(self.optimizer, "resolved_kernel"):
            out["fused_optimizer"] = self.optimizer.resolved_kernel()
        return out

    def _tele_flops(self, key, fn, *args):
        """Executed flops of the jitted program behind ``key`` via XLA
        cost_analysis, computed ONCE per key (training shapes are static
        per program; a re-jit under the same key at new shapes keeps the
        first estimate) and cached — so the per-step cost is one dict
        lookup. Must be called BEFORE invoking fns that donate their
        arguments."""
        cached = self._tele_flops_cache.get(key)
        if cached is not None:
            return cached
        from ..telemetry import costs_of_compiled
        try:
            costs = costs_of_compiled(fn, *args)
            flops = float(costs.get("flops", 0.0) or 0.0)
            # compile observatory: the registry keeps the FULL cost dict
            self.telemetry.programs.price(key, costs)
        except Exception as err:  # noqa: BLE001 - never perturb the step
            logger.info("telemetry: cost_analysis unavailable for %r (%s)",
                        key, err)
            flops = 0.0
        self._tele_flops_cache[key] = flops
        return flops

    def _tele_add_flops(self, key, fn, *args):
        """Accumulate ``fn``'s executed flops into the live step window
        (no-op when telemetry is off) — the ONE accounting seam, also
        used by runners that own their own jit caches (zero/stream.py's
        ``_run``); the engine's window privates are never mutated from
        another module. The compile observatory rides the same seam:
        every priced program is registered/counted here."""
        if self.telemetry is not None:
            self._window_flops += self._tele_flops(key, fn, *args)
            self.telemetry.programs.observe_call(key, fn, args)

    def _jit_priced(self, key, builder, *args, donate=(0,)):
        """``_get_jit`` plus telemetry flops accounting in one place,
        priced with ``args`` BEFORE the returned fn runs (it donates
        them). Every jitted train path must obtain its fn through this
        (zero/stream.py's ``_run`` is the offload twin) or
        ``_window_flops`` silently undercounts and MFU deflates."""
        fn = self._get_jit(key, builder, donate=donate)
        if self._first_call_row is not None:
            # made just now: what its first call is given
            # (docs/telemetry.md, "Device scopes")
            self._first_call_operands = args
        self._tele_add_flops(key, fn, *args)
        return fn

    def _telemetry_wire(self):
        """wire.py per-step bytes-on-wire estimate for the live config,
        computed once (static across steps at fixed shapes)."""
        if self._tele_wire == "unset":
            try:
                from .comm.wire import estimate_engine_comm_bytes
                self._tele_wire = estimate_engine_comm_bytes(self)
            except Exception as err:  # noqa: BLE001
                logger.info("telemetry: wire estimate unavailable (%s)",
                            err)
                self._tele_wire = None
        return self._tele_wire

    def _telemetry_comm_overlap(self, step_time_s):
        """Per-class overlap efficiency for this step's StepRecord:
        wire.py's analytic compute/(compute+exposed-collective) model
        against the measured step wall, with each class marked fused
        only when THIS config's decomposition actually hides it.
        wire.py's classes are the ZeRO collectives: the allgather class
        (stage-3 weight gathers / stage-1-2 re-replication) is fused
        exactly by the zero3 ring gather; the reduce class (the DP
        gradient reduce-scatter) is never fused here — the ring
        gather's backward deliberately leaves it to GSPMD. The TP
        activation gathers/scatters the row/column ops hide are not in
        wire's classes at all: their scoreboard is step_time_s/MFU."""
        if self.telemetry is None:
            return None
        from .comm.wire import overlap_report
        fused = {
            "allgather": bool(getattr(self, "_cm_zero3", False)),
            "reduce": False,
            # the 1-bit momentum exchange (its class appears when live)
            # is never ring-fused into compute
            "optimizer": False,
        }
        return overlap_report(self._telemetry_wire(), step_time_s, fused,
                              self.telemetry._device)

    def _telemetry_window_begin(self):
        """Open the per-optimizer-step measurement window (wall clock,
        token and flops accumulators) and advance the trace window."""
        if self.telemetry is None:
            return
        self._window_t0 = time.time()
        self._window_step = self.global_steps
        self._window_tokens = 0
        self._window_flops = 0.0
        self.telemetry.on_step_begin(self._window_step)

    def _telemetry_micro_begin(self, batch):
        """Micro-path hook: open the window at the first micro of a
        grad-accum window, and count this micro's tokens."""
        if self.telemetry is None or self._mode != ROUTE_TRAIN:
            return
        if self.micro_steps % self.gradient_accumulation_steps() == 0:
            self._telemetry_window_begin()
        self._telemetry_add_tokens(batch)

    def _telemetry_add_tokens(self, batch):
        """Count the first input leaf's elements as this micro's tokens
        (ids batches: batch x seq; the labels leaf is not re-counted)."""
        if self.telemetry is None:
            return
        leaves = jax.tree_util.tree_leaves(batch)
        if leaves:
            shape = getattr(leaves[0], "shape", None)
            self._window_tokens += int(np.prod(shape)) if shape else 1

    def _telemetry_phases(self):
        """The step's disjoint phase clocks: the synchronized micro
        timers when wall_clock_breakdown is on, merged with the offload/
        streamed phase dict when that path ran. Overlapping clocks are
        excluded so phases stay disjoint: classic offload spans only the
        optimizer apply (the step timer would double-bill it); the
        STREAMED phase dict covers the whole step — fwd, bwd, and
        transfers all run inside micro_step — so there the micro timers
        are drained but not billed."""
        phases = {}
        offload = getattr(self, "offload_phase_times", None) or {}
        streamed = self.stream_runner is not None
        if self.wall_clock_breakdown():
            for name in (FORWARD_MICRO_TIMER, BACKWARD_MICRO_TIMER,
                         STEP_MICRO_TIMER):
                t = self.timers.timers.get(name)
                if t is not None and not t.started_:
                    # drained on EVERY path so timer state stays
                    # per-step; the value is only REPORTED where it is
                    # not already covered (streamed phase dicts replace
                    # the micro timers; the offload dict owns the step
                    # phase — reporting both would double-bill the wall)
                    val = t.elapsed(reset=True)
                    if val > 0 and not streamed and not (
                            offload and name == STEP_MICRO_TIMER):
                        phases[name] = val
        for key, val in offload.items():
            phases[key] = phases.get(key, 0.0) + float(val)
        return phases

    def _telemetry_offload_stats(self, exec_stats=None):
        """The StepRecord's ``offload`` sub-dict in the unified
        SEGMENT_KEYS schema (telemetry/record.py): per-kind executed-
        segment walls from the PlanExecutor joined with the path's
        upload counters — one shape for the streamed and classic
        offload paths (validated by bin/check_bench_schema.py)."""
        if self.stream_runner is not None:
            snap = self.stream_runner.transfer_snapshot(
                exec_stats=exec_stats)
            self.stream_runner.reset_step_counters()
            return snap
        if self.host_state is not None:
            exec_stats = exec_stats or {}
            occ = getattr(self, "h2d_bucket_occupancy", None)
            elems = int(getattr(self, "h2d_elems", 0) or 0)
            itemsize = np.dtype(self.compute_dtype).itemsize
            return {
                "plan_segments": int(exec_stats.get("plan_segments", 0)),
                "per_kind": exec_stats.get("per_kind", {}),
                # constructed transfer/compute overlap: host-Adam wall
                # the D2H stream hid vs the residual it could not (the
                # bespoke pre-executor path reported NO efficiency here)
                "overlap_efficiency": exec_stats.get(
                    "overlap_efficiency"),
                "upload_batches": int(getattr(self, "h2d_batches", 0)
                                      or 0),
                "upload_elems": elems,
                "upload_bytes": elems * itemsize,
                "bucket_elems": self._h2d_bucket_elems,
                "bucket_occupancy": round(occ, 4) if occ else None,
                "work_chunks": int(getattr(self, "offload_work_chunks",
                                           0) or 0),
            }
        return None

    def _emit_train_telemetry(self, loss, pipe=None):
        """Assemble and emit this optimizer step's StepRecord. NOTE:
        reading grad_norm/overflow forces one device value fetch per
        step on paths that otherwise defer it — part of telemetry's
        documented <5% overhead budget (docs/telemetry.md)."""
        # executor per-step accounting: snapshot the per-kind stats,
        # then drain the segment records (the drain also opens the next
        # step's window, so it runs even when telemetry is off)
        ex = self._plan_executor
        exec_stats = ex.step_snapshot() if ex is not None else None
        exec_segments = ex.drain_step_records() if ex is not None \
            else None
        tel = self.telemetry
        if tel is None or self._window_t0 is None:
            return
        metrics = self._step_metrics or {}
        grad_norm = metrics.get("grad_norm")
        try:
            grad_norm = None if grad_norm is None else float(grad_norm)
        except Exception:  # noqa: BLE001
            grad_norm = None
        loss = None if loss is None else float(loss)
        overflow = bool(metrics.get("overflow", False))
        # the wall clock is read only AFTER the value fetches above:
        # grad_norm/overflow are outputs of the step's jitted program on
        # every device path, so on async backends the fetch blocks until
        # the step actually finishes — otherwise step_time_s would price
        # host dispatch only and overstate MFU/tokens-per-sec (paths with
        # wall_clock_breakdown on are synced by the timers already)
        dt = time.time() - self._window_t0
        self._window_t0 = None
        loss_scale = metrics.get("loss_scale")
        loss_scale = float(loss_scale) if loss_scale is not None \
            else float(self.state["scaler"].cur_scale)
        # memory_breakdown's monitor mirror already polled memory_stats()
        # this step; hand it over instead of polling every device twice
        hbm = self._step_hbm
        self._step_hbm = None
        tel.emit_train_step(
            path=self._resolved_step_path(),
            step=self._window_step,
            hbm=hbm,
            step_time_s=dt,
            loss=loss,
            grad_norm=grad_norm,
            loss_scale=loss_scale,
            overflow=overflow,
            skipped_steps=self.skipped_steps,
            micro_steps=self.gradient_accumulation_steps(),
            tokens_per_step=self._window_tokens,
            model_flops_per_step=self._window_flops,
            phases=self._telemetry_phases(),
            wire=self._telemetry_wire(),
            comm_overlap=self._telemetry_comm_overlap(dt),
            offload=self._telemetry_offload_stats(exec_stats),
            pipe=pipe,
            # segment-derived span trees on the multi-segment lowered
            # paths (span tree == executed plan); micro/fused keep the
            # phase-derived tree (their plan is one segment — the phase
            # clocks say more)
            segments=exec_segments if exec_segments and (
                self.stream_runner is not None or
                self.host_state is not None) else None)

    # ----------------------------------------------------------- diagnostics
    def _resolved_step_path(self):
        """The executing step path's label — shared by the span tree's
        ``path`` attr and the crash bundle's ``step_path`` so the two
        diagnostics surfaces cannot drift."""
        if self.stream_runner is not None:
            return "streamed"
        if self.host_state is not None:
            return "offload"
        return self._step_path

    def _flight_state(self):
        """Engine snapshot for crash bundles (resolved at dump time)."""
        return {
            "role": "train",
            "global_steps": self.global_steps,
            "micro_steps": self.micro_steps,
            "skipped_steps": self.skipped_steps,
            "mode": self._mode,
            "step_path": self._resolved_step_path(),
            "zero_stage": self.zero_optimization_stage(),
            "compute_dtype": str(np.dtype(self.compute_dtype)),
            "mesh": {str(k): int(v) for k, v in self.mesh.shape.items()},
            "jit_programs": sorted(str(k) for k in self._jit_cache),
        }

    def _topology_context(self):
        """Crash-bundle ``topology`` section (resolved at dump time):
        which topology was LIVE at the crash, plus the elastic rescale
        history shared across engine generations by an ElasticRunner."""
        import jax
        return {
            "mesh": {str(k): int(v) for k, v in self.mesh.shape.items()},
            "process_index": jax.process_index(),
            "process_count": jax.process_count(),
            "dp_world_size": self.dp_world_size,
            "zero_plan": self.zero_plan.topology()
            if getattr(self, "zero_plan", None) is not None else None,
            "rescale_history": list(self._rescale_history),
        }

    def _tele_crash(self, where, err):
        """Flight-recorder hook for unhandled step-path exceptions: dump
        a crash bundle (once per exception object — nested wrappers and
        watchdog raise-trips are deduplicated), never mask the error."""
        tel = self.telemetry
        if tel is None or tel.recorder is None:
            return
        try:
            tel.recorder.dump("exception:" + where, exc=err)
        except Exception:  # noqa: BLE001 - the real error must propagate
            logger.warning("flight recorder dump failed during %s",
                           where, exc_info=True)

    def debug_dump(self, reason="debug_dump"):
        """Write a flight-recorder crash bundle on demand (the operator
        seam: inspect a LIVE run that looks wrong without killing it).
        Returns the bundle path, or None (loudly) when
        ``telemetry.flight_recorder`` is off."""
        tel = self.telemetry
        if tel is None or tel.recorder is None:
            logger.warning(
                "debug_dump: telemetry.flight_recorder is not enabled — "
                "no bundle written (add the flight_recorder section to "
                "the telemetry config)")
            return None
        return tel.recorder.dump(reason)

    def audit(self, batch=None, hlo=None, report_path=None, strict=None):
        """Ahead-of-time shard-lint (docs/analysis.md): abstract-eval
        this engine's resolved step programs from ShapeDtypeStructs +
        the ZeroShardingPlan and walk the jaxpr for sharding drift,
        donation misses, fp32 upcasts in the bf16 GEMM path, host
        callbacks and recompile hazards — before anything compiles.

        ``batch``: one sample micro-batch (arrays or structs); optional
        after the first training step (the engine records the shapes).
        ``hlo=True`` additionally compiles the step programs and
        ground-truths the wire estimator against the HLO collective
        census. Findings warn (raise under ``analysis.strict``; the
        ``strict`` argument overrides); returns the AnalysisReport."""
        from ..analysis import audit_engine
        return audit_engine(self, batch=batch, hlo=hlo,
                            report_path=report_path, strict=strict)

    # -------------------------------------------------------------- train API
    def train(self, mode=True):
        self._mode = ROUTE_TRAIN if mode else "eval"
        return self

    def eval(self):
        return self.train(False)

    @property
    def module(self):
        return self.model

    def __call__(self, *inputs, **kwargs):
        return self.forward(*inputs, **kwargs)

    def forward(self, *inputs, **kwargs):
        """Run a micro-batch. In train mode also computes and accumulates
        gradients (the reference's separate autograd backward becomes part of
        the same XLA program; ``backward()`` is then bookkeeping)."""
        try:
            return self._forward_impl(*inputs, **kwargs)
        except BaseException as err:
            # BaseException on purpose: a SimulatedKill/KeyboardInterrupt
            # mid-step is exactly when the flight recorder must fire
            self._tele_crash("forward", err)
            raise

    def _forward_impl(self, *inputs, **kwargs):
        if len(inputs) == 1 and isinstance(inputs[0], (tuple, list)):
            inputs = tuple(inputs[0])
        batch = self._to_device(inputs)
        if self.stream_runner is not None:
            # streamed parameter offload: forward AND backward run as
            # one segment-streamed pass (grads accumulate into the host
            # buffers), exactly as the monolithic train forward fuses
            # value_and_grad; backward() stays bookkeeping
            if self._mode != ROUTE_TRAIN:
                loss = self.stream_runner.eval_loss(batch)
                self._last_loss = loss
                return loss
            self._telemetry_micro_begin(batch)
            if self.wall_clock_breakdown():
                self.timers(FORWARD_MICRO_TIMER).start()
            self._rng, step_rng = jax.random.split(self._rng)
            loss = self.stream_runner.micro_step(batch, step_rng)
            if self.wall_clock_breakdown():
                self.timers(FORWARD_MICRO_TIMER).stop()
            self._last_loss = loss
            self._pending_backward = True
            return loss
        flops_profiler = self._maybe_start_flops_profiler()

        if self._mode != ROUTE_TRAIN:
            eval_fn = self._get_jit("eval", self._eval_fn)
            loss = eval_fn(self.state["params"], batch)
            self._last_loss = loss
            return loss

        self._telemetry_micro_begin(batch)
        self._step_path = "micro"
        if self.wall_clock_breakdown():
            self.timers(FORWARD_MICRO_TIMER).start()
        self._rng, step_rng = jax.random.split(self._rng)
        micro = self._jit_priced("micro", self._micro_step_fn,
                                 self.state, batch, step_rng,
                                 self._pld_theta())
        if flops_profiler:
            # cost-analyze the EXACT executable about to run, via the
            # telemetry helper that owns the compiled-object fallback
            from ..telemetry.collector import costs_of_compiled
            # actual profiled sequence length (per-module attribution must
            # price the run's shapes, not config.max_seq_len)
            leaf = jax.tree_util.tree_leaves(batch)[0]
            self._profile_seq = (int(leaf.shape[1])
                                 if getattr(leaf, "ndim", 0) >= 2 else None)
            self._flops_costs = costs_of_compiled(
                micro, self.state, batch, step_rng, self._pld_theta())
        self.state, loss = micro(self.state, batch, step_rng,
                                 self._pld_theta())
        if self.wall_clock_breakdown():
            self.timers(FORWARD_MICRO_TIMER).stop()
        self._last_loss = loss
        self._pending_backward = True
        if flops_profiler:
            self._stop_flops_profiler()
        return loss

    def _eval_fn(self):
        apply_fn = self.model.apply_fn
        model = self.model
        qwz = self._param_gather_tree_fn()

        def eval_step(params, batch):
            if qwz is not None:
                # eval sees the same int8-gathered weights training does
                params = qwz(params)
            out = apply_fn(params, *batch, **model.mode_kwargs(False))
            return self._loss_of(out)

        return eval_step

    def backward(self, loss, allreduce_gradients=True, release_loss=False):
        """Bookkeeping for API parity: gradients were produced (and
        constrained to their ZeRO sharding) during ``forward``; the DP mean is
        inserted by XLA at the boundary."""
        assert getattr(self, "_pending_backward", False), \
            "backward() called without a prior train-mode forward()"
        self._pending_backward = False
        if self.wall_clock_breakdown():
            self.timers(BACKWARD_MICRO_TIMER).start()
            self.timers(BACKWARD_MICRO_TIMER).stop()
        return loss

    def is_gradient_accumulation_boundary(self):
        return (self.micro_steps + 1) % self.gradient_accumulation_steps() == 0

    def zero_grad(self):
        if self.stream_runner is not None:
            self.stream_runner.zero_grads()
            return
        self.state["acc_grads"] = jax.tree_util.tree_map(
            jnp.zeros_like, self.state["acc_grads"])

    def step(self, lr_kwargs=None):
        """Optimizer step at gradient-accumulation boundaries
        (reference engine.py:1088-1173)."""
        try:
            return self._step_impl(lr_kwargs)
        except BaseException as err:
            self._tele_crash("train_step", err)
            raise
        finally:
            if self._first_call_row is not None:
                self._first_call_over()

    def _step_impl(self, lr_kwargs=None):
        if self.wall_clock_breakdown():
            self.timers(STEP_MICRO_TIMER).start()

        boundary = self.is_gradient_accumulation_boundary()
        if boundary:
            self._take_model_step(lr_kwargs)

        self.micro_steps += 1
        self.global_samples += self.train_micro_batch_size_per_gpu() * \
            self.dp_world_size
        if boundary:
            self._write_monitor_scalars(self._last_loss)
        if self.wall_clock_breakdown():
            self.timers(STEP_MICRO_TIMER).stop()
        if boundary:
            self._emit_train_telemetry(self._last_loss)

    def _write_monitor_scalars(self, loss):
        """Train/Samples/{lr,train_loss,loss_scale} at each global step
        (reference engine.py:1110-1124)."""
        if not self.monitor.enabled:
            return
        self.monitor.add_scalar("Train/Samples/lr", self.get_lr()[0],
                                self.global_samples)
        if loss is not None:
            self.monitor.add_scalar("Train/Samples/train_loss", float(loss),
                                    self.global_samples)
        self.monitor.add_scalar("Train/Samples/loss_scale",
                                float(self._step_metrics["loss_scale"]),
                                self.global_samples)
        if self.memory_breakdown():
            # memory_breakdown wired to PER-STEP HBM reporting (telemetry
            # records always carry hbm; this mirrors it into the monitor
            # stream). Unavailable backends warned/raised at engine init.
            from ..telemetry.collector import collect_memory_stats
            stats = collect_memory_stats()
            self._step_hbm = stats  # reused by this step's StepRecord
            if stats["available"]:
                self.monitor.add_scalar("Train/Samples/hbm_bytes_in_use",
                                        stats["bytes_in_use"],
                                        self.global_samples)
                self.monitor.add_scalar(
                    "Train/Samples/hbm_peak_bytes_in_use",
                    stats["peak_bytes_in_use"], self.global_samples)
        self.monitor.flush()

    def _offload_check_fn(self):
        """(all-finite, UNSCALED sum of squares) over the GLOBAL
        acc_grads — a tiny jitted reduction whose replicated outputs every
        process can fetch, replacing a host-side full-gradient scan (which
        a process with only its shards could not do). The squares are taken
        AFTER unscaling so a large loss scale cannot push a finite
        gradient's square past fp32 range; a non-finite sumsq that survives
        the elementwise check is treated as overflow by the caller."""

        def check(grads, inv_scale):
            leaves = jax.tree_util.tree_leaves(grads)
            finite = jnp.bool_(True)
            sumsq = jnp.float32(0)
            for g in leaves:
                finite = jnp.logical_and(finite, jnp.isfinite(g).all())
                sumsq = sumsq + jnp.sum(
                    (g.astype(jnp.float32) * inv_scale) ** 2)
            return finite, sumsq

        return check

    def _host_apply_step(self):
        """ZeRO-Offload optimizer step, shard-wise and OVERLAPPED
        (reference stage2.py:283-286, 780-908 + csrc/adam/cpu_adam.cpp),
        lowered onto the segment executor (runtime/executor/offload.py,
        docs/executor.md): each process D2Hs only its ADDRESSABLE
        acc_grad shards, runs the host Adam on its host master/moment
        shards, H2Ds the updated shards and reshards to the param
        layout on device. The transfer/compute overlap the bespoke
        shard pipeline hand-threaded here is now CONSTRUCTED by the
        PlanExecutor from the declared segment deps (async D2H fetches
        in a bounded window ahead of the host Adam, leaf uploads riding
        the coalescing batcher behind the remaining chunks)."""
        from .executor.offload import run_offload_apply
        return run_offload_apply(self)

    def plan_executor(self):
        """The engine's PlanExecutor (runtime/executor/scheduler.py),
        built lazily: mode resolves from the strict-validated
        ``runtime.executor`` tri-state (off = serial oracle, on/auto =
        constructed overlap)."""
        if self._plan_executor is None:
            from .executor import PlanExecutor
            self._plan_executor = PlanExecutor(
                mode=self._executor_mode,
                windows={"d2h": self._D2H_WINDOW},
                rewrites=self._executor_rewrites
                if self._executor_rewrites.get("enabled") else None)
        return self._plan_executor

    def executor_snapshot(self):
        """Engine-lifetime executor counters (mode, plans/segments
        executed, per-kind walls, constructed overlap) — the payload of
        the benches' ``extra.executor``."""
        if self._plan_executor is None:
            return {"mode": self._executor_mode, "plans_executed": 0,
                    "segments_executed": 0, "last_plan_segments": 0}
        return self._plan_executor.lifetime_snapshot()

    def _finish_offload_step(self, flat_params, acc_specs, acc_shardings,
                             hs):
        """Reshard the uploaded grad-layout leaves into the param layout
        and re-zero the accumulators on device."""
        grad_layout = hs["treedef"].unflatten(flat_params)
        reshard = self._get_jit(
            "offload_reshard",
            lambda: lambda t: t,
            out_shardings=hs["param_shardings"])
        self.state["params"] = reshard(grad_layout)
        del grad_layout
        # fresh zero accumulators, allocated ON DEVICE from the saved
        # specs (a host-side zeros + device_put would push the full
        # fp32 gradient over the wire every step); the cache key carries
        # the specs VERBATIM (not a truncated hash — a collision across
        # spec changes would replay a stale-shaped closure) so a
        # shape/sharding change across steps can never alias
        zeros_fn = self._get_jit(
            "acc_zeros:%s" % repr(acc_specs),
            lambda: (lambda: tuple(jnp.zeros(s, d)
                                   for s, d in acc_specs)),
            out_shardings=tuple(acc_shardings))
        self.state["acc_grads"] = hs["treedef"].unflatten(
            list(zeros_fn()))

    def _restore_params_from_host(self, acc_specs, acc_shardings, hs):
        """Disaster path: rebuild device params + zero accumulators from
        the host master shards after a failed overlapped step."""
        flat_params = [
            self._leaf_shards_to_device(spec[0], sh, shards)
            for spec, sh, shards in zip(acc_specs, acc_shardings,
                                        hs["shard_leaves"])]
        self._finish_offload_step(flat_params, acc_specs, acc_shardings,
                                  hs)

    def _upload_pool(self):
        from .executor.pools import upload_pool
        if getattr(self, "_h2d_pool", None) is None:
            self._h2d_pool = upload_pool()
        return self._h2d_pool

    def _h2d_split_cache(self):
        """Jitted bucket-split programs, shared across steps so each
        bucket layout compiles once."""
        if getattr(self, "_h2d_splits", None) is None:
            self._h2d_splits = {}
        return self._h2d_splits

    def _enqueue_leaf_upload(self, batcher, i, shape, sharding, shards):
        """Queue one leaf's updated host master shards on the upload
        batcher, keyed so _assemble_uploaded_leaf can rebuild the global
        array."""
        by_key = {_shard_key(idx): p for idx, p, _, _ in shards}
        for dev, idx in \
                sharding.addressable_devices_indices_map(shape).items():
            batcher.add((i, _shard_key(idx)), by_key[_shard_key(idx)],
                        dev)

    def _assemble_uploaded_leaf(self, uploaded, i, shape, sharding):
        """Batched-upload results for leaf ``i`` -> a grad-layout global
        device array."""
        singles = [
            uploaded[(i, _shard_key(idx))][dev]
            for dev, idx in
            sharding.addressable_devices_indices_map(shape).items()]
        return jax.make_array_from_single_device_arrays(
            shape, sharding, singles)

    def _leaf_shards_to_device(self, shape, sharding, shards):
        """One leaf's updated host master shards -> a grad-layout global
        device array (synchronous coalesced H2D in compute dtype). Takes
        the leaf's (shape, sharding) spec rather than the grad array so
        the caller can free the gradient buffer first. Only the disaster
        path uses this now — the hot path batches leaves across the step
        (_enqueue_leaf_upload)."""
        from .zero.transfer import H2DBatcher
        batcher = H2DBatcher(self._h2d_bucket_elems, self.compute_dtype,
                             jit_cache=self._h2d_split_cache())
        self._enqueue_leaf_upload(batcher, 0, shape, sharding, shards)
        return self._assemble_uploaded_leaf(batcher.finish(), 0, shape,
                                            sharding)

    def _host_to_device(self, p_np, sharding):
        """Host fp32 leaf -> sharded compute-dtype device array WITHOUT
        materializing the full array on one device (jnp.asarray-then-
        device_put would transit device 0 unsharded — fatal for exactly
        the large-model case offload targets). Cast in numpy first
        (np.dtype(bf16) resolves via ml_dtypes, halving the transfer),
        then device_put straight onto the NamedSharding."""
        return jax.device_put(p_np.astype(np.dtype(self.compute_dtype)),
                              sharding)

    def _offload_lib(self):
        """The native SIMD Adam when built; None -> numpy fallback. Only
        plain Adam/AdamW offloads (reference restricts the same way)."""
        if getattr(self, "_offload_lib_cache", "unset") != "unset":
            return self._offload_lib_cache
        lib = None
        if not getattr(self.optimizer, "adam_w_mode", None) is None:
            try:
                from ..ops.op_builder.cpu_adam import CPUAdamBuilder
                lib = CPUAdamBuilder().load()
            except Exception as err:  # noqa: BLE001
                logger.warning(
                    "ZeRO-Offload: native CPU Adam unavailable (%s); "
                    "using the numpy fallback", err)
        self._offload_lib_cache = lib
        return lib

    def _adapt_state_dict(self, sd):
        """Hook for subclasses to re-partition a loaded state dict before
        placement (PipelineEngine re-shards body layers across a different
        stage count)."""
        return sd

    def _pld_theta(self):
        """Current PLD keep-prob as a traced-operand scalar (1.0 = off)."""
        if self.progressive_layer_drop:
            return jnp.float32(self.progressive_layer_drop.get_theta())
        return jnp.float32(1.0)

    def _overflow_fetch_needed(self):
        """Whether the optimizer step's overflow flag must be read back to
        the host this step. Only dynamic loss scaling (fp16) needs it per
        step — skipped_steps/lr-skip semantics depend on it. With a static
        scale the reference does no overflow bookkeeping either, and the
        fetch is a per-step device sync worth avoiding."""
        if self.host_state is not None:
            return True     # offload: metrics are already host values
        # fp16 checks overflow per step even with a STATIC scale (the
        # reference's FP16_Optimizer always runs CheckOverflow); only
        # bf16/fp32 — where the reference has no overflow machinery — skip
        return (bool(self.state["scaler"].dynamic)
                or self.compute_dtype == jnp.float16)

    def _read_overflow(self, metrics):
        """The optimizer step's overflow flag, fetched per-step for fp16
        (reference FP16_Optimizer semantics) and only at steps_per_print
        boundaries for bf16/fp32 — the in-jit guard still no-ops a
        non-finite step on device every step, and the periodic check keeps
        a persistently-overflowing run observable (skipped_steps/log)
        without a per-step device sync. At those boundaries skipped_steps
        is re-synced from the device-resident skip_count counter, so the
        host total stays exact over the unfetched window (the lr scheduler
        still advances on unfetched skipped steps — the documented cost of
        avoiding the sync)."""
        if self._overflow_fetch_needed():
            return bool(metrics["overflow"])
        if (self.global_steps + 1) % self.steps_per_print() == 0:
            # one device fetch per print window only;
            # -1 compensates the caller's += 1 for this step's overflow
            overflow = bool(metrics["overflow"])
            self._sync_skipped_steps(exclude_current_overflow=overflow)
            return overflow
        return False

    def _sync_skipped_steps(self, exclude_current_overflow=False):
        """Re-sync the host skipped_steps counter from the device-resident
        skip_count, which is exact even over windows where the overflow
        flag was never fetched. max() keeps paths where the host counter
        is already authoritative (per-step fetch, host offload) intact."""
        if self.state is None or "skip_count" not in self.state:
            return
        device_skips = int(self.state["skip_count"])
        if exclude_current_overflow:
            device_skips -= 1
        self.skipped_steps = max(self.skipped_steps, device_skips)

    def _stream_apply_step(self):
        """Streamed-offload optimizer step + scaler update; exposes the
        streamed phase clocks under the name the offload benches read."""
        metrics = self.stream_runner.apply_step()
        self.state["scaler"] = ls.update_scale(
            self.state["scaler"], metrics["overflow"])
        self.offload_phase_times = self.stream_runner.phase_times
        self.stream_runner.phase_times = {}
        return metrics

    def _take_model_step(self, lr_kwargs=None):
        if self.stream_runner is not None:
            metrics = self._stream_apply_step()
        elif self.host_state is not None:
            metrics = self._host_apply_step()
        else:
            apply_fn = self._jit_priced(self._regime_jit_key("apply"),
                                        self._apply_step_fn,
                                        self.state, self._hyper())
            # one-segment plan: the apply program rides the same
            # executor (and per-step accounting) as the offload plans
            self.state, metrics = self.plan_executor().run_program(
                "apply", "compute",
                lambda: apply_fn(self.state, self._hyper()))
        self._step_metrics = {k: v for k, v in metrics.items()}
        overflow = self._read_overflow(metrics)
        if overflow:
            self.skipped_steps += 1
            log_dist("OVERFLOW! Skipping step. Attempted loss scale: {}".format(
                float(metrics["loss_scale"])), ranks=[0])
        else:
            if self.lr_scheduler is not None:
                self.lr_scheduler.step(**(lr_kwargs or {}))
        if self.progressive_layer_drop:
            self.progressive_layer_drop.update_state(self.global_steps)
        self.global_steps += 1
        if self.global_steps % self.steps_per_print() == 0:
            log_dist("step={}, lr={}, loss_scale={}".format(
                self.global_steps, self.get_lr(),
                float(metrics["loss_scale"])), ranks=[0])
            if self.memory_breakdown():
                see_memory_usage(
                    "step {}".format(self.global_steps), force=True)

    # -------------------------------------------------- fused train-batch path
    def train_batch(self, data_iter=None, batch=None):
        """TPU-idiomatic fused path: all grad-accum micro-steps + the
        optimizer step in ONE jitted program (lax.scan over micro-batches)."""
        try:
            return self._train_batch_impl(data_iter=data_iter, batch=batch)
        except BaseException as err:
            self._tele_crash("train_batch", err)
            raise
        finally:
            if self._first_call_row is not None:
                self._first_call_over()

    def _train_batch_impl(self, data_iter=None, batch=None):
        self._step_path = "fused"
        gas = self.gradient_accumulation_steps()
        if batch is None:
            assert data_iter is not None
            micro_batches = [next(data_iter) for _ in range(gas)]
            batch = jax.tree_util.tree_map(
                lambda *xs: np.stack(xs), *micro_batches)
        self._telemetry_window_begin()
        if self.stream_runner is not None:
            # streamed parameter offload: the micro-steps stream layer
            # groups host->HBM; there is no fused lax.scan (params never
            # all co-reside on device)
            losses = []
            for i in range(gas):
                micro = jax.tree_util.tree_map(
                    lambda x: np.asarray(x)[i], batch)
                dev_micro = self._to_device(tuple(
                    jax.tree_util.tree_leaves(micro)))
                self._telemetry_add_tokens(dev_micro)
                self._rng, step_rng = jax.random.split(self._rng)
                losses.append(self.stream_runner.micro_step(dev_micro,
                                                            step_rng))
            mean_loss = float(np.mean([float(x) for x in losses]))
            metrics = self._stream_apply_step()
        elif self.host_state is not None:
            batch = self._to_device_stacked(batch)
            self._telemetry_add_tokens(batch)
            self._rng, step_rng = jax.random.split(self._rng)
            fused = self._jit_priced("fused_micros", self._fused_micros_fn,
                                     self.state, batch, step_rng,
                                     self._pld_theta())
            self.state, mean_loss = self.plan_executor().run_program(
                "fused_micros", "compute",
                lambda: fused(self.state, batch, step_rng,
                              self._pld_theta()))
            metrics = self._host_apply_step()
        else:
            batch = self._to_device_stacked(batch)
            self._telemetry_add_tokens(batch)
            self._rng, step_rng = jax.random.split(self._rng)
            fused = self._jit_priced(self._regime_jit_key("fused_train"),
                                     self._fused_train_fn,
                                     self.state, batch, step_rng,
                                     self._hyper(), self._pld_theta())
            # one-segment plan: the fused train program rides the same
            # executor (and per-step accounting) as the offload plans
            self.state, (mean_loss, metrics) = \
                self.plan_executor().run_program(
                    "fused_train", "compute",
                    lambda: fused(self.state, batch, step_rng,
                                  self._hyper(), self._pld_theta()))
        overflow = self._read_overflow(metrics)
        if overflow:
            self.skipped_steps += 1
            log_dist("OVERFLOW! Skipping step. Attempted loss scale: {}"
                     .format(float(metrics["loss_scale"])), ranks=[0])
        elif self.lr_scheduler is not None:
            self.lr_scheduler.step()
        if self.progressive_layer_drop:
            self.progressive_layer_drop.update_state(self.global_steps)
        self.global_steps += 1
        self.micro_steps += gas
        self.global_samples += self.train_batch_size()
        self._step_metrics = metrics
        self._last_loss = mean_loss
        self._write_monitor_scalars(mean_loss)
        self._emit_train_telemetry(mean_loss)
        return mean_loss

    def _to_device_stacked(self, batch):
        """Batch stacked as (gas, global_batch, ...) -> sharded arrays."""
        def put(x):
            x = np.asarray(x)
            if x.ndim <= 1 or x.shape[1] % self.dp_world_size != 0:
                return jax.device_put(x, NamedSharding(self.mesh, P()))
            sharding = NamedSharding(
                self.mesh,
                P(None, self._batch_axis, *([None] * (x.ndim - 2))))
            if jax.process_count() > 1:
                return jax.make_array_from_process_local_data(sharding, x)
            return jax.device_put(x, sharding)
        placed = jax.tree_util.tree_map(put, batch)
        if self._audit_batch_struct_stacked is None:
            self._audit_batch_struct_stacked = jax.tree_util.tree_map(
                lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                               sharding=a.sharding),
                placed)
        return placed

    def _fused_micros_fn(self):
        """Offload variant of the fused path: scan the micro-steps on
        device, leave the optimizer apply to the host."""
        micro = self._micro_step_fn()
        gas = self.gradient_accumulation_steps()

        def fused(state, stacked_batch, rng, pld_theta):
            rngs = jax.random.split(rng, gas)
            leaves, treedef = jax.tree_util.tree_flatten(stacked_batch)

            def scan_body(carry, xs):
                rng_i = xs[0]
                batch_i = jax.tree_util.tree_unflatten(treedef, list(xs[1:]))
                return micro(carry, batch_i, rng_i, pld_theta)

            state, losses = jax.lax.scan(scan_body, state,
                                         (rngs, *leaves), length=gas)
            return state, jnp.mean(losses)

        return fused

    def _fused_train_fn(self):
        micro = self._micro_step_fn()
        apply_step = self._apply_step_fn()
        gas = self.gradient_accumulation_steps()

        def fused(state, stacked_batch, rng, hyper, pld_theta):
            rngs = jax.random.split(rng, gas)

            def body(carry, xs):
                batch_i, rng_i = xs
                new_state, loss = micro(carry, batch_i, rng_i, pld_theta)
                return new_state, loss

            leaves, treedef = jax.tree_util.tree_flatten(stacked_batch)
            def scan_body(carry, xs):
                rng_i = xs[0]
                batch_i = jax.tree_util.tree_unflatten(treedef, list(xs[1:]))
                return body(carry, (batch_i, rng_i))

            state, losses = jax.lax.scan(scan_body, state,
                                         (rngs, *leaves), length=gas)
            with jax.named_scope("optim.step"):
                state, metrics = apply_step(state, hyper)
            return state, (jnp.mean(losses), metrics)

        return fused

    # ------------------------------------------------------------- accessors
    def train_batch_size(self):
        return self._config.train_batch_size

    def train_micro_batch_size_per_gpu(self):
        return self._config.train_micro_batch_size_per_gpu

    def gradient_accumulation_steps(self):
        return self._config.gradient_accumulation_steps

    def steps_per_print(self):
        return self._config.steps_per_print

    def sparse_attention_config(self):
        """The parsed ds_config "sparse_attention" dict, or None — the
        reference engine's accessor (engine.py sparse_attention_config):
        models consume it to build their sparse attention, e.g.
        GPT2Config(sparse_attention=engine.sparse_attention_config())
        or SparseAttentionUtils for BERT."""
        return self._config.sparse_attention

    def zero_optimization(self):
        return self._config.zero_enabled

    def zero_optimization_stage(self):
        return self._config.zero_optimization_stage

    def zero_cpu_offload(self):
        # offload is a ZeRO feature: a stage-0 config with the flag set
        # must not activate the host Adam path (reference ties it to the
        # ZeRO optimizers too). cpu_offload_params implies the optimizer
        # state is host-resident as well (the streamed step's Adam runs
        # on host by construction).
        return self.zero_optimization() and \
            (self._config.zero_config.cpu_offload or
             self.zero_params_offload())

    def zero_params_offload(self):
        """Streamed parameter offload live (cpu_offload_params): compute
        params are host-resident, streamed per layer group into HBM
        inside the step (runtime/zero/stream.py)."""
        return getattr(self, "_params_offload", False)

    def zero_quantized_weights(self):
        """qwZ live: stage-3 weight all-gathers ride int8 blocks."""
        return getattr(self, "_qwz_enabled", False)

    def zero_hierarchical_partition(self):
        """hpZ live: the secondary-partition (shard sub-axis) size, or 0."""
        plan = getattr(self, "zero_plan", None)
        if plan is not None and plan.hierarchical:
            return plan.param_shard_size
        return 0

    def zero_quantized_gradients(self):
        """qgZ live: micro-step grads pass the error-compensated codec."""
        return getattr(self, "_qgz_enabled", False)

    def fp16_enabled(self):
        return self._config.fp16_enabled

    def bfloat16_enabled(self):
        return self._config.bf16_enabled

    def amp_enabled(self):
        return self._config.amp_enabled

    def gradient_clipping(self):
        return self._config.gradient_clipping

    def allreduce_always_fp32(self):
        return self._config.allreduce_always_fp32

    def postscale_gradients(self):
        return not self._config.prescale_gradients

    def gradient_predivide_factor(self):
        return self._config.gradient_predivide_factor

    def sparse_gradients_enabled(self):
        return self._config.sparse_gradients_enabled

    def wall_clock_breakdown(self):
        return self._config.wall_clock_breakdown

    def memory_breakdown(self):
        return self._config.memory_breakdown

    def progressive_layer_drop_enabled(self):
        return self._config.pld_enabled

    def elasticity_enabled(self):
        return self._config.elasticity_enabled

    def get_lr(self):
        return [float(getattr(self.optimizer, "lr", 0.0))]

    def get_mom(self):
        betas = getattr(self.optimizer, "betas", None)
        return [betas] if betas is not None else None

    def loss_scale(self):
        return float(self.state["scaler"].cur_scale)

    @property
    def cur_scale(self):
        return self.loss_scale()

    def get_global_grad_norm(self):
        gn = self._step_metrics.get("grad_norm")
        return float(gn) if gn is not None else None

    def get_params(self):
        """Current compute-dtype parameter pytree."""
        return self._module_view()

    def _module_view(self):
        """The checkpoint/module view of the compute parameters. Under
        streamed offload there is no resident device copy — the view is
        the host master cast to compute dtype."""
        if self.state.get("params") is not None:
            return self.state["params"]
        if self.stream_runner is not None:
            cd = np.dtype(self.compute_dtype)
            return jax.tree_util.tree_map(
                lambda p: p.astype(cd), self.get_master_params())
        return self.state["params"]

    def get_master_params(self):
        if self.host_state is not None:
            return self._assemble_host_tree(field=1)
        return self.state["master"] if self.mixed_precision \
            else self.state["params"]

    def _assemble_host_tree(self, field):
        """Full fp32 tree from the host shards (field: 1 master, 2 exp_avg,
        3 exp_avg_sq). Only possible when this process's shards cover every
        leaf (single-process, or replicated layouts) — a partitioned
        multi-process layout raises; the per-process zero checkpoint files
        own the shards there."""
        hs = self.host_state
        leaves = []
        for shape, shards in zip(hs["leaf_shapes"], hs["shard_leaves"]):
            out = np.empty(shape, np.float32)
            covered = 0
            for tup in shards:
                out[tup[0]] = tup[field]
                covered += int(tup[field].size)
            if covered < int(np.prod(shape)):
                raise RuntimeError(
                    "host optimizer state is partitioned across processes; "
                    "use the per-process zero checkpoint files instead of a "
                    "gathered view")
            leaves.append(out)
        return hs["treedef"].unflatten(leaves)

    def _opt_state_view(self):
        if self.host_state is not None:
            return {
                "step": self.host_state["step"],
                "exp_avg": self._assemble_host_tree(field=2),
                "exp_avg_sq": self._assemble_host_tree(field=3),
            }
        return self.state["opt"]

    # --------------------------------------------------------------- profiler
    def _maybe_start_flops_profiler(self):
        cfg = self._config.flops_profiler_config
        if cfg.enabled and self.global_steps == cfg.profile_step \
                and self._mode == ROUTE_TRAIN:
            self._flops_profiler_active = True
            return True
        return False

    def _stop_flops_profiler(self):
        if getattr(self, "_flops_profiler_active", False):
            from ..profiling.flops_profiler.profiler import FlopsProfiler
            prof = FlopsProfiler(self)
            costs = getattr(self, "_flops_costs", None) or {}
            prof.flops = costs.get("flops", 0.0)
            prof.bytes_accessed = costs.get("bytes accessed", 0.0)
            self.flops_profiler = prof
            prof.print_model_profile()
            # per-module table (reference profiler.py:515-677) when the
            # model ships a profile spec (e.g. models/gpt2.py)
            spec_fn = getattr(self.model, "profile_spec_fn", None)
            if spec_fn is not None:
                cfg = self._config.flops_profiler_config
                try:
                    spec = spec_fn(self.train_micro_batch_size_per_gpu(),
                                   seq=getattr(self, "_profile_seq", None))
                except TypeError:   # spec builder without a seq kwarg
                    spec = spec_fn(self.train_micro_batch_size_per_gpu())
                prof.print_module_table(
                    spec,
                    module_depth=cfg.module_depth,
                    top_modules=cfg.top_modules,
                    detailed=cfg.detailed)
            self._flops_profiler_active = False

    # ------------------------------------------------------------- checkpoint
    def _get_ckpt_tag(self, tag):
        return tag if tag is not None else "global_step{}".format(
            self.global_steps)

    def save_checkpoint(self, save_dir, tag=None, client_state=None,
                        save_latest=True, async_save=False,
                        _write_manifest=True):
        """Save model+optimizer+scheduler+counters
        (reference engine.py:1569-1685).

        Every file write is atomic (tmp + fsync + rename), the tag's
        ``manifest.json`` (file list + CRC32s) is written after every
        content file, and ``latest`` moves only after the manifest — a
        crash at any point leaves ``latest`` naming a complete,
        checksum-verifiable checkpoint (docs/checkpoint_recovery.md).
        ``async_save``: pickle+write runs on a serial background thread
        (device state is still gathered synchronously, so training may
        continue mutating it); single-process only — multi-process saves
        need the inter-file barrier and stay synchronous.
        ``_write_manifest=False`` is for subclasses (pipe engine) that
        append more tag files and must finalize the manifest themselves."""
        tag = self._get_ckpt_tag(tag)
        self._validate_tag(tag)
        client_state = client_state or {}
        async_save = async_save and jax.process_count() == 1
        # at most one save in flight: surface any prior async failure
        # here rather than silently dropping it, and let still-queued
        # background writes land before we re-write the same paths
        self._drain_ckpt_writes()
        ckpt.wait_pending_writes()

        is_writer = jax.process_index() == 0
        # bf16/static-scale runs only fetch the overflow flag at print
        # boundaries; without this the saved value would freeze the
        # unfetched window's drift into the checkpoint
        self._sync_skipped_steps()
        # partitioned multi-process offload: the gathered master/opt views
        # are unavailable (each process owns shards); the per-process zero
        # files below carry the state instead
        offload_sharded = (self.host_state is not None
                           and jax.process_count() > 1)
        # device-state ZeRO: master/opt go ONLY into per-process zero shard
        # files (reference zero_pp_rank layout, engine.py:1350-1377) — the
        # model file carries neither, so nothing funnels the full optimizer
        # tree through rank 0 and nothing is stored twice
        zero_sharded = self.host_state is None and self.zero_optimization()
        sd = {
            "module": ckpt.tree_to_numpy(self._module_view()),
            "optimizer": None if (offload_sharded or zero_sharded)
                else ckpt.tree_to_numpy(self._opt_state_view()),
            "master": ckpt.tree_to_numpy(self.get_master_params())
                if ((self.mixed_precision or self.host_state is not None)
                    and not offload_sharded and not zero_sharded)
                else None,
            "scaler": ckpt.tree_to_numpy(
                {"cur_scale": self.state["scaler"].cur_scale,
                 "cur_hysteresis": self.state["scaler"].cur_hysteresis,
                 "last_overflow_iter": self.state["scaler"].last_overflow_iter,
                 "cur_iter": self.state["scaler"].cur_iter}),
            "lr_scheduler": self.lr_scheduler.state_dict()
                if self.lr_scheduler is not None else None,
            # qgZ error feedback (docs/zeropp.md): leaves are
            # param-shaped, so the gathered tree reshards structurally
            # on an elastic restore like master/opt do; the zero-sharded
            # path carries it in the per-process shard files instead
            "qg_error": ckpt.tree_to_numpy(self.state["qg_error"])
                if (self.state is not None
                    and self.state.get("qg_error") is not None
                    and not offload_sharded and not zero_sharded)
                else None,
            "csr_tensor_module_names": set(self.csr_tensor_module_names),
            "skipped_steps": self.skipped_steps,
            "global_steps": self.global_steps,
            "global_samples": self.global_samples,
            "dp_world_size": self.dp_world_size,
            "mp_world_size": self.mp_world_size,
        }
        pristine = getattr(self, "_onebit_pristine", None)
        if pristine is not None and \
                pristine.get("steps") == self.global_steps:
            # 1-bit elastic pass-through: no step has consumed the
            # folded worker residuals since the resharded load, so the
            # ORIGINAL per-worker rows are still the truth — re-emit
            # them and a later rescale back to their world restores the
            # error feedback bit-exactly (runtime/fp16/onebit_adam.py)
            sd["onebit_pristine"] = pristine["payload"]
        if self.host_state is not None and "torn_step" in self.host_state:
            # a failed overlapped offload step left the host masters
            # PARTIALLY stepped (see _host_apply_step's disaster path);
            # surface it so a resumed run knows the optimizer step was
            # torn rather than trusting the checkpoint as whole
            sd["torn_offload_step"] = self.host_state["torn_step"]
        sd.update(client_state)

        futures, records = [], []

        def note(res):
            # sync writes return integrity records, async ones futures of
            # those records; both feed the tag manifest
            if res is not None:
                (futures if hasattr(res, "result") else records).append(res)

        if is_writer:
            path = ckpt.model_ckpt_name(save_dir, tag,
                                        mp_rank=0)
            note(ckpt.save_state_dict(path, sd, async_save=async_save))
            logger.info("Saved checkpoint: {}".format(path))
        if offload_sharded:
            # EVERY process writes its own zero file with its host shards
            # (reference zero_pp_rank_N layout); keys serialize the shard
            # index so load re-slots them exactly
            zpath = ckpt.zero_ckpt_name(save_dir, tag,
                                        dp_rank=jax.process_index())
            note(ckpt.save_state_dict(zpath, {
                "offload_shards": [
                    [(_shard_key(idx), p, m, v) for idx, p, m, v in shards]
                    for shards in self.host_state["shard_leaves"]],
                "offload_step": self.host_state["step"],
                # a torn step is RANK-LOCAL (one process's update loop
                # failed); persist it in this rank's own zero file so a
                # multi-process resume sees it even when the writer rank
                # was healthy
                "torn_step": self.host_state.get("torn_step"),
            }, async_save=async_save))
        elif zero_sharded:
            # EVERY process writes its addressable master/opt shards to its
            # own zero file; keys serialize the shard index so load
            # re-slots them exactly — and, because every shard carries its
            # index into the FULL leaf, any process set can reassemble the
            # gathered tree, keeping elastic resharding on load
            zpath = ckpt.zero_ckpt_name(save_dir, tag,
                                        dp_rank=jax.process_index())
            note(ckpt.save_state_dict(zpath, {
                "device_shards": self._device_zero_shard_payload(is_writer),
            }, async_save=async_save))
        if jax.process_count() > 1:
            # EVERY process's files must land before the manifest and
            # `latest` move: a crash after the pointer update may
            # otherwise leave `latest` naming a checkpoint whose zero
            # shards never finished (reference barriers around checkpoint
            # IO, engine.py:1610)
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(
                "save_checkpoint_files:{}".format(tag))
        if _write_manifest:
            self._finalize_ckpt_tag(save_dir, tag, records, futures,
                                    save_latest, async_save)
        self._ckpt_futures = [f for f in futures if f is not None]
        self._ckpt_records = records
        if jax.process_count() > 1:
            # a process must not proceed to (and possibly load) a
            # checkpoint other writers haven't finished
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices(
                "save_checkpoint:{}".format(tag))
        return True

    def _ckpt_manifest_meta(self):
        return {"global_step": int(self.global_steps),
                "dp_world_size": int(self.dp_world_size),
                "mp_world_size": int(self.mp_world_size)}

    def _finalize_ckpt_tag(self, save_dir, tag, records, futures,
                           save_latest, async_save):
        """Close out a checkpoint tag, writer-rank only: manifest.json
        LAST among the tag's files (its presence defines completeness),
        then the ``latest`` pointer, then retention GC. In async mode
        each step is queued on the serial writer pool gated on everything
        before it, so a failure anywhere leaves the manifest unwritten
        and ``latest`` naming the previous complete tag."""
        if jax.process_index() != 0:
            return
        meta = self._ckpt_manifest_meta()
        if async_save:
            futures.append(ckpt.write_manifest_after(
                save_dir, tag, futures, meta))
        else:
            records.append(ckpt.write_manifest(save_dir, tag, records, meta))
        if not save_latest:
            return
        if async_save:
            # the serial pool guarantees the latest task runs after this
            # process's shard+manifest writes; save_latest_after also
            # REFUSES the update if any of them failed, so `latest` can
            # never name a tag with a missing or unverifiable file
            futures.append(ckpt.save_latest_after(save_dir, tag, futures))
        else:
            ckpt.save_latest(save_dir, tag)
        keep_last_n = getattr(self._config, "checkpoint_keep_last_n", None)
        if keep_last_n:
            if async_save:
                futures.append(ckpt.prune_after(
                    save_dir, keep_last_n, futures))
            else:
                ckpt.prune_checkpoints(save_dir, keep_last_n)

    def wait_pending_writes(self):
        """Block until every queued checkpoint write has landed — this
        engine's in-flight async futures (re-raising the first failure)
        and anything else on the global background writer pool. Call
        before handing the checkpoint dir to another consumer."""
        self._drain_ckpt_writes()
        ckpt.wait_pending_writes()

    def close(self):
        """Tear this engine down for replacement (elastic rescale): land
        in-flight checkpoint writes, stop the background upload worker,
        release streamed-offload buffers, and close telemetry/monitor —
        the collector's close() releases its claimed host directory so
        the NEXT engine generation reuses the same telemetry dir
        (append-mode JSONL keeps one continuous record stream).
        Idempotent; the engine must not step afterwards."""
        if getattr(self, "_closed", False):
            return
        self._closed = True
        # a step program closes over this engine and its state
        release_programs(self.startup_tag)
        try:
            self._drain_ckpt_writes()
            ckpt.wait_pending_writes()
        except BaseException:  # noqa: BLE001 - teardown must not mask
            logger.warning("close: pending checkpoint writes failed",
                           exc_info=True)
        if getattr(self, "stream_runner", None) is not None:
            self.stream_runner.release()
        pool = getattr(self, "_h2d_pool", None)
        if pool is not None:
            pool.shutdown(wait=True)
            self._h2d_pool = None
        if self.telemetry is not None:
            self.telemetry.close()
        if self.monitor is not None:
            self.monitor.close()

    def _drain_ckpt_writes(self):
        """Block on any in-flight async checkpoint writes (re-raising the
        first background failure). Called before the next save, before a
        load, and available to callers that need the files on disk NOW.
        The list is cleared FIRST so one failed write raises once, not on
        every subsequent save/load forever."""
        futs = getattr(self, "_ckpt_futures", ())
        self._ckpt_futures = []
        first_err = None
        for fut in futs:  # serial pool: results arrive in submit order
            try:
                fut.result()
            except BaseException as err:  # noqa: BLE001
                first_err = first_err or err
        if first_err is not None:
            raise first_err

    def _device_zero_shard_payload(self, is_writer):
        """This process's addressable master/opt shards (device-state ZeRO
        save; reference per-rank zero files, engine.py:1350-1377)."""
        payload = {
            "master": ckpt.shard_lists_of_tree(self.state["master"],
                                               is_writer)
            if self.mixed_precision else None,
            "opt": {
                key: (np.asarray(val) if key == "step"
                      else ckpt.shard_lists_of_tree(val, is_writer))
                for key, val in self.state["opt"].items()
            },
            "qg_error": ckpt.shard_lists_of_tree(
                self.state["qg_error"], is_writer)
            if self.state.get("qg_error") is not None else None,
        }
        return payload

    def _zero_shard_paths(self, load_dir, tag):
        import glob
        pattern = os.path.join(
            load_dir, str(tag), "zero_pp_rank_*_mp_rank_00_optim_states.pt")
        return sorted(glob.glob(pattern))

    def _load_device_zero_state(self, load_dir, tag, sd,
                                load_optimizer_states):
        """Reassemble master/opt from per-process zero shard files into the
        gathered ``sd`` slots, so the normal (elastic, plan-agnostic)
        placement code runs unchanged. Understands both the device-state
        layout (``device_shards``) and, for cross-engine resume, the
        offload layout (``offload_shards``: (key, master, m, v) per
        acc-grad leaf)."""
        paths = self._zero_shard_paths(load_dir, tag)
        if not paths:
            if load_optimizer_states:
                # a ZeRO checkpoint with neither gathered state nor shard
                # files would otherwise silently resume with zeroed
                # moments (round-2 ADVICE)
                logger.warning(
                    "checkpoint %s/%s carries no optimizer state (no "
                    "gathered tree, no zero shard files) — optimizer "
                    "state starts fresh", load_dir, tag)
            return
        payloads = [ckpt.load_state_dict(p) for p in paths]

        if "offload_shards" in payloads[0]:
            # offload-written checkpoint loaded into a device-state engine:
            # entries are (key, master, exp_avg, exp_avg_sq) per leaf;
            # leaves are param-shaped, so the SAVED module tree supplies
            # shapes/structure
            module_flat, module_def = jax.tree_util.tree_flatten(
                sd["module"])

            def per_file(field):
                return [[(np.shape(module_flat[i]),
                          [(e[0], e[field]) for e in shards])
                         for i, shards in enumerate(p["offload_shards"])]
                        for p in payloads]

            master = ckpt.assemble_shard_lists(per_file(1), "master")
            sd["master"] = jax.tree_util.tree_unflatten(module_def, master)
            if load_optimizer_states:
                ea = ckpt.assemble_shard_lists(per_file(2), "exp_avg")
                ev = ckpt.assemble_shard_lists(per_file(3), "exp_avg_sq")
                sd["optimizer"] = {
                    "step": int(payloads[0]["offload_step"]),
                    "exp_avg": jax.tree_util.tree_unflatten(module_def, ea),
                    "exp_avg_sq": jax.tree_util.tree_unflatten(module_def,
                                                               ev),
                }
            return

        device = [p["device_shards"] for p in payloads]
        # streamed offload has no device params tree; the host registry's
        # treedef is the same structure
        params_def = (self.host_state["treedef"]
                      if self.state.get("params") is None
                      and self.host_state is not None
                      else jax.tree_util.tree_flatten(
                          self.state["params"])[1])
        mixed = self.mixed_precision or self.host_state is not None
        if device[0].get("master") is not None and mixed:
            master = ckpt.assemble_shard_lists(
                [d["master"] for d in device], "master")
            sd["master"] = jax.tree_util.tree_unflatten(params_def, master)
        if load_optimizer_states:
            # opt subtree structure comes from the live state; an OFFLOAD
            # engine loading a device checkpoint has opt=None (moments live
            # on host) — its Adam moments are params-structured
            live_opt = self.state.get("opt")
            keys = (live_opt.keys() if live_opt is not None
                    else device[0]["opt"].keys())
            opt = {}
            for key in keys:
                if key not in device[0]["opt"]:
                    logger.warning(
                        "zero shard files carry no '%s' optimizer state "
                        "(saved under a different optimizer) — it starts "
                        "fresh", key)
                    continue
                if key == "step":
                    opt["step"] = np.asarray(device[0]["opt"]["step"])
                    continue
                tmpl_def = (jax.tree_util.tree_flatten(live_opt[key])[1]
                            if live_opt is not None else params_def)
                leaves = ckpt.assemble_shard_lists(
                    [d["opt"][key] for d in device], "opt/" + key)
                opt[key] = jax.tree_util.tree_unflatten(tmpl_def, leaves)
            sd["optimizer"] = opt
        if device[0].get("qg_error") is not None:
            qg = ckpt.assemble_shard_lists(
                [d["qg_error"] for d in device], "qg_error")
            sd["qg_error"] = jax.tree_util.tree_unflatten(params_def, qg)

    def _load_host_state(self, load_dir, tag, sd, load_optimizer_states,
                         load_from_fp32_weights):
        """Restore the ZeRO-Offload host shards.

        A checkpoint written by a MULTI-process offload run carries its
        master/optimizer state ONLY in per-process zero shard files
        (sd["master"] is None there) — resuming it requires the exact same
        shard layout (same process count / ZeRO partitioning); a mismatch
        raises instead of silently resetting state differently per rank.
        Checkpoints with full gathered trees restore by slicing this
        process's shard indices out of them."""
        hs = self.host_state
        zpath = ckpt.zero_ckpt_name(load_dir, tag,
                                    dp_rank=jax.process_index())
        zsd = None
        if os.path.isfile(zpath):
            zsd = ckpt.load_state_dict(zpath)
        if zsd is not None and zsd.get("torn_step") is not None:
            logger.warning(
                "Zero shard file {} records a TORN offload step ({}): "
                "this rank's masters were partially stepped when the "
                "checkpoint was written. Resume is usable but re-run the "
                "step's batch; loss may blip.".format(
                    zpath, zsd["torn_step"]))
        if zsd is not None and "device_shards" in zsd:
            # device-state ZeRO checkpoint loaded into an OFFLOAD engine:
            # reassemble the gathered trees from every process's shard
            # file, then restore through the gathered path below
            self._load_device_zero_state(load_dir, tag, sd,
                                         load_optimizer_states)
            zsd = None
        sharded_only = sd.get("master") is None and \
            sd.get("optimizer") is None
        if zsd is not None and "offload_shards" in zsd:
            want = [[_shard_key(idx) for idx, *_ in shards]
                    for shards in hs["shard_leaves"]]
            got = [[tuple(map(tuple, key)) for key, *_ in shards]
                   for shards in zsd["offload_shards"]]
            if want == got:
                # master always restores from the exact fp32 shards unless
                # the caller explicitly asked for a half-precision recast;
                # moments/step only when the optimizer state is wanted
                recast = not load_from_fp32_weights
                module_flat = hs["treedef"].flatten_up_to(sd["module"]) \
                    if recast else None
                hs["shard_leaves"] = [
                    [(_key_to_index(key),
                      np.array(np.asarray(module_flat[i])[_key_to_index(key)],
                               dtype=np.float32, copy=True) if recast
                      else np.array(p, np.float32),
                      np.array(m, np.float32) if load_optimizer_states
                      else np.zeros(np.shape(p), np.float32),
                      np.array(v, np.float32) if load_optimizer_states
                      else np.zeros(np.shape(p), np.float32))
                     for key, p, m, v in shards]
                    for i, shards in enumerate(zsd["offload_shards"])]
                hs["step"] = int(zsd["offload_step"]) \
                    if load_optimizer_states else 0
                return
            if sharded_only:
                raise RuntimeError(
                    "offload checkpoint {} was written with a different "
                    "shard layout (process count / ZeRO partitioning) and "
                    "has no gathered master to re-slice — resume with the "
                    "layout it was saved under".format(zpath))
            logger.warning(
                "zero shard file %s has a different shard layout; falling "
                "back to the gathered checkpoint trees", zpath)
        elif sharded_only:
            raise RuntimeError(
                "offload checkpoint has per-process shard files but none "
                "for process {} ({}) — it was written with a different "
                "process count; resume with the layout it was saved "
                "under".format(jax.process_index(), zpath))

        src = sd["master"] if (load_from_fp32_weights
                               and sd.get("master") is not None) \
            else sd["module"]
        flat_src = hs["treedef"].flatten_up_to(src)
        opt = sd.get("optimizer") if load_optimizer_states else None
        flat_m = hs["treedef"].flatten_up_to(opt["exp_avg"]) if opt else None
        flat_v = hs["treedef"].flatten_up_to(opt["exp_avg_sq"]) if opt \
            else None
        hs["shard_leaves"] = [
            [(idx,
              np.array(np.asarray(full)[idx], dtype=np.float32, copy=True),
              np.array(np.asarray(flat_m[i])[idx], dtype=np.float32,
                       copy=True) if opt else np.zeros(
                           np.asarray(full)[idx].shape, np.float32),
              np.array(np.asarray(flat_v[i])[idx], dtype=np.float32,
                       copy=True) if opt else np.zeros(
                           np.asarray(full)[idx].shape, np.float32))
             for idx, *_ in shards]
            for i, (full, shards) in enumerate(
                zip(flat_src, hs["shard_leaves"]))]
        hs["step"] = int(opt["step"]) if opt else 0

    def _validate_tag(self, tag):
        if not self._config.checkpoint_tag_validation_enabled:
            return
        # All processes must agree on the tag; with >1 process compare via a
        # broadcast-from-0 (reference uses min/max hash allreduce).
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils
            agreed = multihost_utils.broadcast_one_to_all(
                np.frombuffer(str(tag).encode()[:32].ljust(32), dtype=np.uint8))
            mine = np.frombuffer(str(tag).encode()[:32].ljust(32),
                                 dtype=np.uint8)
            if not np.array_equal(agreed, mine):
                msg = "Checkpoint tag '{}' differs across processes".format(tag)
                if self._config.checkpoint_tag_validation_fail:
                    raise ValueError(msg)
                logger.warning(msg)

    def load_checkpoint(self, load_dir, tag=None, load_module_strict=True,
                        load_optimizer_states=True,
                        load_lr_scheduler_states=True,
                        load_from_fp32_weights=True):
        """Load a checkpoint; returns (path, client_state)
        (reference engine.py:1379-1482).

        Elastic resharding is structural: state dicts store FULL (gathered)
        trees, and loading device_puts each leaf with the CURRENT engine's
        plan — a checkpoint written at dp=8 loads into a dp=4 or 3D mesh
        unchanged (the reference needs bespoke re-slicing,
        stage1.py:1048-1107; GSPMD makes it a placement detail).

        ``load_from_fp32_weights``: restore the fp32 master from the saved
        fp32 shards (exact resume) vs recast from the fp16/bf16 params
        (reference stage2.py:1741-1763 toggle).

        Integrity + last-good fallback (docs/checkpoint_recovery.md): the
        chosen tag's manifest and file checksums are verified first; on
        any mismatch/missing file — or corruption surfacing mid-load —
        the scan walks backward through prior tags to the newest complete
        one, logging exactly what was rejected and why, instead of
        crashing or loading torn state. The fallback applies when
        ``tag=None`` (resume-from-latest); an explicitly named tag that
        fails returns ``(None, None)`` rather than silently substituting
        different weights. Tags predating the manifest format load
        unverified with a warning.
        """
        self._drain_ckpt_writes()
        ckpt.wait_pending_writes()
        requested = tag
        if tag is None:
            tag = ckpt.read_latest(load_dir)

        def _reject(bad_tag, why):
            logger.error("checkpoint tag %r under %s rejected: %s",
                         bad_tag, load_dir, why)

        tried = []
        verified_by_scan = False
        while True:
            if tag is None:
                if requested is not None:
                    # the caller named this tag explicitly: quietly
                    # loading some OTHER tag would resume on the wrong
                    # weights with no programmatic signal — fail instead
                    # (tag=None opts into the last-good fallback)
                    break
                tag = ckpt.newest_complete_tag(load_dir, exclude=tried,
                                               on_reject=_reject)
                if tag is None:
                    break
                verified_by_scan = True
                logger.warning(
                    "falling back to newest complete checkpoint tag %r "
                    "under %s", tag, load_dir)
            tried.append(tag)
            # a tag the scan returned already passed the full CRC check —
            # don't re-read a multi-GB checkpoint just to verify it twice
            ok, reason = (True, None) if verified_by_scan \
                else ckpt.verify_tag(load_dir, tag)
            if ok or reason == ckpt.NO_MANIFEST:
                if not ok:
                    logger.warning(
                        "checkpoint %s/%s predates the manifest format — "
                        "loading without integrity verification",
                        load_dir, tag)
                try:
                    return self._load_checkpoint_tag(
                        load_dir, tag, load_module_strict,
                        load_optimizer_states, load_lr_scheduler_states,
                        load_from_fp32_weights)
                except ckpt.CheckpointCorruptionError as err:
                    if ok:
                        # the bytes CRC-verified, yet unpickling failed:
                        # that is not bit-rot but an environment/pickle
                        # compatibility problem every other tag would
                        # repeat — crash loudly instead of silently
                        # walking back to (None, None) and a fresh start
                        raise
                    _reject(tag, err)
            else:
                _reject(tag, reason)
            tag = None  # scan for the next-newest complete tag

        logger.warning(
            "Unable to find a loadable checkpoint under {} (requested "
            "tag: {}); pass a valid tag or check the rejection log "
            "above".format(load_dir, requested if requested is not None
                           else "latest"))
        return None, None

    def _load_checkpoint_tag(self, load_dir, tag, load_module_strict,
                             load_optimizer_states,
                             load_lr_scheduler_states,
                             load_from_fp32_weights):
        path = ckpt.model_ckpt_name(load_dir, tag, mp_rank=0)
        if not os.path.isfile(path):
            raise ckpt.CheckpointCorruptionError(
                "model states file {} does not exist".format(path))
        sd = ckpt.load_state_dict(path)
        sd = self._adapt_state_dict(sd)

        if sd.get("torn_offload_step") is not None:
            logger.warning(
                "Checkpoint {} was written after a FAILED overlapped "
                "offload step (torn optimizer step {}): some master "
                "shards stepped, some did not. Resume is usable but the "
                "step's batch should be re-run; loss may blip.".format(
                    path, sd["torn_offload_step"]))

        if self.host_state is None and sd.get("optimizer") is None:
            # ZeRO-sharded checkpoint: reassemble gathered trees from the
            # per-process zero files before the plan-agnostic placement
            self._load_device_zero_state(load_dir, tag, sd,
                                         load_optimizer_states)
            sd = self._adapt_state_dict(sd)

        plan = self.zero_plan
        if self.state["params"] is not None:
            param_sh = plan.tree_shardings(self.state["params"], "param")
            self.state["params"] = jax.tree_util.tree_map(
                lambda x, old, s: jax.device_put(
                    jnp.asarray(x, dtype=old.dtype), s),
                sd["module"], self.state["params"], param_sh)

        if self.host_state is not None:
            self._load_host_state(load_dir, tag, sd, load_optimizer_states,
                                  load_from_fp32_weights)
        elif self.mixed_precision and load_from_fp32_weights and \
                sd.get("master") is not None:
            master_sh = plan.tree_shardings(self.state["master"], "master")
            self.state["master"] = jax.tree_util.tree_map(
                lambda x, s: jax.device_put(jnp.asarray(x, jnp.float32), s),
                sd["master"], master_sh)
        elif self.mixed_precision:
            # recompute master from the (lower-precision) params
            master_sh = plan.tree_shardings(self.state["master"], "master")
            self.state["master"] = jax.tree_util.tree_map(
                lambda p, s: jax.device_put(jnp.asarray(p, jnp.float32), s),
                self.state["params"], master_sh)

        if self.host_state is None and load_optimizer_states and \
                sd.get("optimizer") is not None:
            opt = sd["optimizer"]
            saved_dp = sd.get("dp_world_size")
            pristine = sd.get("onebit_pristine")
            reshard = getattr(self.optimizer, "reshard_state", None)
            self._onebit_pristine = None
            if callable(reshard) and saved_dp is not None and \
                    int(saved_dp) != int(self.dp_world_size):
                # elastic restore across world sizes: world-size-
                # dependent subtrees (1-bit error feedback) are
                # canonicalised to this engine's layout; world-agnostic
                # ones pass through untouched
                opt = reshard(opt, int(saved_dp), pristine=pristine)
                pristine = getattr(self.optimizer, "_reshard_pristine",
                                   pristine)
            if pristine is not None:
                # carry the original per-worker error rows until a step
                # consumes them (save_checkpoint re-emits the sidecar
                # only while global_steps is unchanged)
                self._onebit_pristine = {"payload": pristine,
                                         "steps": None}
            # shardings from each subtree's own leaf shapes (error buffers
            # etc. are not param-shaped)
            self.state["opt"] = {
                key: jnp.asarray(val) if key == "step" else
                jax.tree_util.tree_map(
                    lambda x, s: jax.device_put(jnp.asarray(x, jnp.float32), s),
                    val, self._opt_state_shardings(key, val))
                for key, val in opt.items()
            }

        if sd.get("qg_error") is not None and self.state is not None \
                and self.state.get("qg_error") is not None:
            # param-shaped leaves: device_put onto the LIVE buffers'
            # shardings reshards structurally across world sizes
            self.state["qg_error"] = jax.tree_util.tree_map(
                lambda x, live: jax.device_put(
                    jnp.asarray(x, jnp.float32), live.sharding),
                sd["qg_error"], self.state["qg_error"])

        if sd.get("scaler") is not None:
            sc = sd["scaler"]
            self.state["scaler"] = self.state["scaler"]._replace(
                cur_scale=jnp.asarray(sc["cur_scale"], jnp.float32),
                cur_hysteresis=jnp.asarray(sc["cur_hysteresis"], jnp.int32),
                last_overflow_iter=jnp.asarray(sc["last_overflow_iter"],
                                               jnp.int32),
                cur_iter=jnp.asarray(sc["cur_iter"], jnp.int32))

        if load_lr_scheduler_states and self.lr_scheduler is not None and \
                sd.get("lr_scheduler") is not None:
            self.lr_scheduler.load_state_dict(sd["lr_scheduler"])

        self.global_steps = sd.get("global_steps", 0)
        if getattr(self, "_onebit_pristine", None) is not None:
            self._onebit_pristine["steps"] = self.global_steps
        self.global_samples = sd.get(
            "global_samples", self.global_steps * self.train_batch_size())
        self.skipped_steps = sd.get("skipped_steps", 0)
        if self.state is not None and "skip_count" in self.state:
            # keep the device counter aligned so periodic re-syncs stay exact
            self.state["skip_count"] = jnp.int32(self.skipped_steps)
        self.loaded_checkpoint_dp_world_size = sd.get("dp_world_size")

        known = {"module", "optimizer", "master", "scaler", "lr_scheduler",
                 "qg_error", "onebit_pristine", "csr_tensor_module_names",
                 "skipped_steps", "global_steps", "global_samples",
                 "dp_world_size", "mp_world_size"}
        client_state = {k: v for k, v in sd.items() if k not in known}
        logger.info("Loaded checkpoint: {} @ global_step={}".format(
            path, self.global_steps))
        return path, client_state
