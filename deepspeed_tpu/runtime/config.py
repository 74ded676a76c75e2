"""``ds_config.json`` parser.

Reference parity: deepspeed/runtime/config.py (DeepSpeedConfig at :519,
batch-triple inference :679-725, sanity checks :750-787). The JSON surface is
identical; ``world_size`` is the number of data-parallel shards of the device
mesh rather than a torch process-group size.

TPU-native additions (non-breaking): a ``bf16`` block (preferred on TPU —
no loss scaler needed), accepted alongside the reference's ``fp16`` block.
"""
import json
import logging

from .constants import *
from .config_utils import (get_scalar_param, dict_raise_error_on_duplicate_keys)
from .comm.config import COMM, KNOWN_COMM_KEYS, DeepSpeedCommConfig
from .zero.config import DeepSpeedZeroConfig
from .zero.constants import (ZERO_OPTIMIZATION, ZERO_OPTIMIZATION_DISABLED,
                             MAX_STAGE_ZERO_OPTIMIZATION)
from .activation_checkpointing.config import DeepSpeedActivationCheckpointingConfig
from ..profiling.config import DeepSpeedFlopsProfilerConfig
from ..inference.config import DeepSpeedInferenceConfig, INFERENCE
from ..telemetry.config import (DeepSpeedTelemetryConfig, TELEMETRY,
                                KNOWN_TELEMETRY_KEYS)
from ..analysis.config import (DeepSpeedAnalysisConfig, ANALYSIS,
                               KNOWN_ANALYSIS_KEYS)
from ..utils.logging import logger

TENSOR_CORE_ALIGN_SIZE = 8


class DeepSpeedConfigError(Exception):
    pass


class ValidationMode:
    WARN = "WARN"
    IGNORE = "IGNORE"
    FAIL = "FAIL"


def get_amp_enabled(param_dict):
    if AMP in param_dict:
        return get_scalar_param(param_dict[AMP], AMP_ENABLED, AMP_ENABLED_DEFAULT)
    return False


def get_amp_params(param_dict):
    if AMP in param_dict:
        amp_params = dict(param_dict[AMP])
        amp_params.pop(AMP_ENABLED, None)
        return amp_params
    return False


def get_fp16_enabled(param_dict):
    if FP16 in param_dict:
        return get_scalar_param(param_dict[FP16], FP16_ENABLED, FP16_ENABLED_DEFAULT)
    return False


def get_bf16_enabled(param_dict):
    if BF16 in param_dict:
        return get_scalar_param(param_dict[BF16], BF16_ENABLED, BF16_ENABLED_DEFAULT)
    return False


def get_loss_scale(param_dict):
    if get_fp16_enabled(param_dict):
        return get_scalar_param(param_dict[FP16], FP16_LOSS_SCALE,
                                FP16_LOSS_SCALE_DEFAULT)
    return FP16_LOSS_SCALE_DEFAULT


def get_initial_dynamic_scale(param_dict):
    if get_fp16_enabled(param_dict):
        initial_scale_power = get_scalar_param(param_dict[FP16],
                                               FP16_INITIAL_SCALE_POWER,
                                               FP16_INITIAL_SCALE_POWER_DEFAULT)
    else:
        initial_scale_power = FP16_INITIAL_SCALE_POWER_DEFAULT
    return 2 ** initial_scale_power


def get_dynamic_loss_scale_args(param_dict):
    loss_scale_args = None
    if get_fp16_enabled(param_dict):
        fp16_dict = param_dict[FP16]
        dynamic_keys = (FP16_INITIAL_SCALE_POWER, FP16_LOSS_SCALE_WINDOW,
                        FP16_MIN_LOSS_SCALE, FP16_HYSTERESIS)
        if any(key in fp16_dict for key in dynamic_keys):
            init_scale = get_scalar_param(fp16_dict, FP16_INITIAL_SCALE_POWER,
                                          FP16_INITIAL_SCALE_POWER_DEFAULT)
            scale_window = get_scalar_param(fp16_dict, FP16_LOSS_SCALE_WINDOW,
                                            FP16_LOSS_SCALE_WINDOW_DEFAULT)
            delayed_shift = get_scalar_param(fp16_dict, FP16_HYSTERESIS,
                                             FP16_HYSTERESIS_DEFAULT)
            min_loss_scale = get_scalar_param(fp16_dict, FP16_MIN_LOSS_SCALE,
                                              FP16_MIN_LOSS_SCALE_DEFAULT)
            loss_scale_args = {
                "init_scale": 2 ** init_scale,
                "scale_window": scale_window,
                "delayed_shift": delayed_shift,
                "min_scale": min_loss_scale,
            }
    return loss_scale_args


def get_gradient_accumulation_steps(param_dict):
    return get_scalar_param(param_dict, GRADIENT_ACCUMULATION_STEPS,
                            GRADIENT_ACCUMULATION_STEPS_DEFAULT)


def get_sparse_gradients_enabled(param_dict):
    return get_scalar_param(param_dict, SPARSE_GRADIENTS, SPARSE_GRADIENTS_DEFAULT)


def get_allreduce_always_fp32(param_dict):
    return get_scalar_param(param_dict, FP32_ALLREDUCE, FP32_ALLREDUCE_DEFAULT)


def get_prescale_gradients(param_dict):
    return get_scalar_param(param_dict, PRESCALE_GRADIENTS,
                            PRESCALE_GRADIENTS_DEFAULT)


def get_gradient_predivide_factor(param_dict):
    return get_scalar_param(param_dict, GRADIENT_PREDIVIDE_FACTOR,
                            GRADIENT_PREDIVIDE_FACTOR_DEFAULT)


def get_steps_per_print(param_dict):
    return get_scalar_param(param_dict, STEPS_PER_PRINT, STEPS_PER_PRINT_DEFAULT)


def get_disable_allgather(param_dict):
    return get_scalar_param(param_dict, DISABLE_ALLGATHER, DISABLE_ALLGATHER_DEFAULT)


def get_dump_state(param_dict):
    return get_scalar_param(param_dict, DUMP_STATE, DUMP_STATE_DEFAULT)


def get_gradient_clipping(param_dict):
    return get_scalar_param(param_dict, GRADIENT_CLIPPING,
                            GRADIENT_CLIPPING_DEFAULT)


def get_grad_accum_dtype(param_dict):
    """data_types.grad_accum_dtype: storage dtype of the gradient
    accumulation buffer. "bf16" halves its HBM (2N vs 4N bytes) and is
    LOSSLESS at gradient_accumulation_steps=1 (micro grads arrive bf16
    from the compute dtype; storing them wider adds no information);
    with real accumulation (gas>1) bf16 summation is lossy — the engine
    warns. None (default) keeps fp32."""
    sub = param_dict.get("data_types") or {}
    if not isinstance(sub, dict):
        raise DeepSpeedConfigError(
            f"data_types must be a dict, got {type(sub).__name__}")
    val = sub.get("grad_accum_dtype")
    if val is None:
        return None
    norm = str(val).lower()
    if norm not in ("fp32", "float32", "bf16", "bfloat16"):
        raise DeepSpeedConfigError(
            f"data_types.grad_accum_dtype={val!r}: want fp32 or bf16")
    return "bf16" if norm in ("bf16", "bfloat16") else "fp32"


def get_sparse_attention(param_dict):
    if SPARSE_ATTENTION not in param_dict:
        return None
    sparsity = param_dict[SPARSE_ATTENTION]
    mode = get_scalar_param(sparsity, SPARSE_MODE, SPARSE_MODE_DEFAULT)
    if mode == SPARSE_DENSE_MODE:
        return get_sparse_dense_config(sparsity)
    elif mode == SPARSE_FIXED_MODE:
        return get_sparse_fixed_config(sparsity)
    elif mode == SPARSE_VARIABLE_MODE:
        return get_sparse_variable_config(sparsity)
    elif mode == SPARSE_BIGBIRD_MODE:
        return get_sparse_bigbird_config(sparsity)
    elif mode == SPARSE_BSLONGFORMER_MODE:
        return get_sparse_bslongformer_config(sparsity)
    elif mode == SPARSE_SLIDING_WINDOW_MODE:
        return get_sparse_sliding_window_config(sparsity)
    else:
        raise NotImplementedError(
            "Given sparsity mode, {}, has not been implemented yet!".format(mode))


def get_sparse_dense_config(sparsity):
    block = get_scalar_param(sparsity, SPARSE_BLOCK, SPARSE_BLOCK_DEFAULT)
    return {SPARSE_MODE: SPARSE_DENSE_MODE, SPARSE_BLOCK: block}


def get_sparse_fixed_config(sparsity):
    return {
        SPARSE_MODE: SPARSE_FIXED_MODE,
        SPARSE_BLOCK:
            get_scalar_param(sparsity, SPARSE_BLOCK, SPARSE_BLOCK_DEFAULT),
        SPARSE_DIFFERENT_LAYOUT_PER_HEAD:
            get_scalar_param(sparsity, SPARSE_DIFFERENT_LAYOUT_PER_HEAD,
                             SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT),
        SPARSE_NUM_LOCAL_BLOCKS:
            get_scalar_param(sparsity, SPARSE_NUM_LOCAL_BLOCKS,
                             SPARSE_NUM_LOCAL_BLOCKS_DEFAULT),
        SPARSE_NUM_GLOBAL_BLOCKS:
            get_scalar_param(sparsity, SPARSE_NUM_GLOBAL_BLOCKS,
                             SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT),
        SPARSE_ATTENTION_TYPE:
            get_scalar_param(sparsity, SPARSE_ATTENTION_TYPE,
                             SPARSE_ATTENTION_TYPE_DEFAULT),
        SPARSE_HORIZONTAL_GLOBAL_ATTENTION:
            get_scalar_param(sparsity, SPARSE_HORIZONTAL_GLOBAL_ATTENTION,
                             SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT),
        SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS:
            get_scalar_param(sparsity, SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS,
                             SPARSE_NUM_DIFFERENT_GLOBAL_PATTERNS_DEFAULT),
    }


def get_sparse_variable_config(sparsity):
    return {
        SPARSE_MODE: SPARSE_VARIABLE_MODE,
        SPARSE_BLOCK:
            get_scalar_param(sparsity, SPARSE_BLOCK, SPARSE_BLOCK_DEFAULT),
        SPARSE_DIFFERENT_LAYOUT_PER_HEAD:
            get_scalar_param(sparsity, SPARSE_DIFFERENT_LAYOUT_PER_HEAD,
                             SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT),
        SPARSE_NUM_RANDOM_BLOCKS:
            get_scalar_param(sparsity, SPARSE_NUM_RANDOM_BLOCKS,
                             SPARSE_NUM_RANDOM_BLOCKS_DEFAULT),
        SPARSE_LOCAL_WINDOW_BLOCKS:
            get_scalar_param(sparsity, SPARSE_LOCAL_WINDOW_BLOCKS,
                             SPARSE_LOCAL_WINDOW_BLOCKS_DEFAULT),
        SPARSE_GLOBAL_BLOCK_INDICES:
            get_scalar_param(sparsity, SPARSE_GLOBAL_BLOCK_INDICES,
                             SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT),
        SPARSE_GLOBAL_BLOCK_END_INDICES:
            get_scalar_param(sparsity, SPARSE_GLOBAL_BLOCK_END_INDICES,
                             SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT),
        SPARSE_ATTENTION_TYPE:
            get_scalar_param(sparsity, SPARSE_ATTENTION_TYPE,
                             SPARSE_ATTENTION_TYPE_DEFAULT),
        SPARSE_HORIZONTAL_GLOBAL_ATTENTION:
            get_scalar_param(sparsity, SPARSE_HORIZONTAL_GLOBAL_ATTENTION,
                             SPARSE_HORIZONTAL_GLOBAL_ATTENTION_DEFAULT),
    }


def get_sparse_bigbird_config(sparsity):
    return {
        SPARSE_MODE: SPARSE_BIGBIRD_MODE,
        SPARSE_BLOCK:
            get_scalar_param(sparsity, SPARSE_BLOCK, SPARSE_BLOCK_DEFAULT),
        SPARSE_DIFFERENT_LAYOUT_PER_HEAD:
            get_scalar_param(sparsity, SPARSE_DIFFERENT_LAYOUT_PER_HEAD,
                             SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT),
        SPARSE_NUM_RANDOM_BLOCKS:
            get_scalar_param(sparsity, SPARSE_NUM_RANDOM_BLOCKS,
                             SPARSE_NUM_RANDOM_BLOCKS_DEFAULT),
        SPARSE_NUM_SLIDING_WINDOW_BLOCKS:
            get_scalar_param(sparsity, SPARSE_NUM_SLIDING_WINDOW_BLOCKS,
                             SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT),
        SPARSE_NUM_GLOBAL_BLOCKS:
            get_scalar_param(sparsity, SPARSE_NUM_GLOBAL_BLOCKS,
                             SPARSE_NUM_GLOBAL_BLOCKS_DEFAULT),
    }


def get_sparse_sliding_window_config(sparsity):
    return {
        SPARSE_MODE: SPARSE_SLIDING_WINDOW_MODE,
        SPARSE_BLOCK:
            get_scalar_param(sparsity, SPARSE_BLOCK, SPARSE_BLOCK_DEFAULT),
        SPARSE_NUM_SLIDING_WINDOW_BLOCKS:
            get_scalar_param(sparsity, SPARSE_NUM_SLIDING_WINDOW_BLOCKS,
                             SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT),
    }


def get_sparse_bslongformer_config(sparsity):
    return {
        SPARSE_MODE: SPARSE_BSLONGFORMER_MODE,
        SPARSE_BLOCK:
            get_scalar_param(sparsity, SPARSE_BLOCK, SPARSE_BLOCK_DEFAULT),
        SPARSE_DIFFERENT_LAYOUT_PER_HEAD:
            get_scalar_param(sparsity, SPARSE_DIFFERENT_LAYOUT_PER_HEAD,
                             SPARSE_DIFFERENT_LAYOUT_PER_HEAD_DEFAULT),
        SPARSE_NUM_SLIDING_WINDOW_BLOCKS:
            get_scalar_param(sparsity, SPARSE_NUM_SLIDING_WINDOW_BLOCKS,
                             SPARSE_NUM_SLIDING_WINDOW_BLOCKS_DEFAULT),
        SPARSE_GLOBAL_BLOCK_INDICES:
            get_scalar_param(sparsity, SPARSE_GLOBAL_BLOCK_INDICES,
                             SPARSE_GLOBAL_BLOCK_INDICES_DEFAULT),
        SPARSE_GLOBAL_BLOCK_END_INDICES:
            get_scalar_param(sparsity, SPARSE_GLOBAL_BLOCK_END_INDICES,
                             SPARSE_GLOBAL_BLOCK_END_INDICES_DEFAULT),
    }


def get_optimizer_name(param_dict):
    if OPTIMIZER in param_dict and TYPE in param_dict[OPTIMIZER]:
        return param_dict[OPTIMIZER][TYPE]
    return OPTIMIZER_TYPE_DEFAULT


def get_optimizer_params(param_dict):
    if get_optimizer_name(param_dict) is not None and \
            OPTIMIZER_PARAMS in param_dict[OPTIMIZER]:
        return param_dict[OPTIMIZER][OPTIMIZER_PARAMS]
    return None


def get_optimizer_gradient_clipping(param_dict):
    optimizer_params = get_optimizer_params(param_dict)
    if optimizer_params is not None and MAX_GRAD_NORM in optimizer_params:
        return optimizer_params[MAX_GRAD_NORM]
    return None


def get_optimizer_legacy_fusion(param_dict):
    if OPTIMIZER in param_dict and LEGACY_FUSION in param_dict[OPTIMIZER]:
        return param_dict[OPTIMIZER][LEGACY_FUSION]
    return LEGACY_FUSION_DEFAULT


def get_zero_allow_untested_optimizer(param_dict):
    return get_scalar_param(param_dict, ZERO_ALLOW_UNTESTED_OPTIMIZER,
                            ZERO_ALLOW_UNTESTED_OPTIMIZER_DEFAULT)


def get_scheduler_name(param_dict):
    if SCHEDULER in param_dict and TYPE in param_dict[SCHEDULER]:
        return param_dict[SCHEDULER][TYPE]
    return SCHEDULER_TYPE_DEFAULT


def get_scheduler_params(param_dict):
    if get_scheduler_name(param_dict) is not None and \
            SCHEDULER_PARAMS in param_dict[SCHEDULER]:
        return param_dict[SCHEDULER][SCHEDULER_PARAMS]
    return None


def get_train_batch_size(param_dict):
    return get_scalar_param(param_dict, TRAIN_BATCH_SIZE, TRAIN_BATCH_SIZE_DEFAULT)


def get_train_micro_batch_size_per_gpu(param_dict):
    return get_scalar_param(param_dict, TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                            TRAIN_MICRO_BATCH_SIZE_PER_GPU_DEFAULT)


def get_wall_clock_breakdown(param_dict):
    return get_scalar_param(param_dict, WALL_CLOCK_BREAKDOWN,
                            WALL_CLOCK_BREAKDOWN_DEFAULT)


def get_memory_breakdown(param_dict):
    return get_scalar_param(param_dict, MEMORY_BREAKDOWN, MEMORY_BREAKDOWN_DEFAULT)


def get_tensorboard_enabled(param_dict):
    if TENSORBOARD in param_dict:
        return get_scalar_param(param_dict[TENSORBOARD], TENSORBOARD_ENABLED,
                                TENSORBOARD_ENABLED_DEFAULT)
    return False


def get_tensorboard_output_path(param_dict):
    if get_tensorboard_enabled(param_dict):
        return get_scalar_param(param_dict[TENSORBOARD], TENSORBOARD_OUTPUT_PATH,
                                TENSORBOARD_OUTPUT_PATH_DEFAULT)
    return TENSORBOARD_OUTPUT_PATH_DEFAULT


def get_tensorboard_job_name(param_dict):
    if get_tensorboard_enabled(param_dict):
        return get_scalar_param(param_dict[TENSORBOARD], TENSORBOARD_JOB_NAME,
                                TENSORBOARD_JOB_NAME_DEFAULT)
    return TENSORBOARD_JOB_NAME_DEFAULT


def get_checkpoint_params(param_dict):
    return param_dict.get(CHECKPOINT, {})


def get_checkpoint_tag_validation_mode(checkpoint_params):
    tag_validation_mode = checkpoint_params.get(CHECKPOINT_TAG_VALIDATION,
                                                CHECKPOINT_TAG_VALIDATION_DEFAULT)
    tag_validation_mode = tag_validation_mode.upper()
    if tag_validation_mode in (ValidationMode.WARN, ValidationMode.IGNORE,
                               ValidationMode.FAIL):
        return tag_validation_mode
    raise DeepSpeedConfigError(
        "Checkpoint config contains invalid tag_validation "
        "value of {}, expecting one of {}".format(
            tag_validation_mode,
            [ValidationMode.WARN, ValidationMode.IGNORE, ValidationMode.FAIL]))


def get_checkpoint_io_retries(checkpoint_params):
    val = checkpoint_params.get(CHECKPOINT_IO_RETRIES,
                                CHECKPOINT_IO_RETRIES_DEFAULT)
    if isinstance(val, bool) or not isinstance(val, int) or val < 0:
        raise DeepSpeedConfigError(
            "checkpoint.{} must be an int >= 0, got {!r}".format(
                CHECKPOINT_IO_RETRIES, val))
    return val


def get_checkpoint_io_backoff(checkpoint_params):
    val = checkpoint_params.get(CHECKPOINT_IO_RETRY_BACKOFF,
                                CHECKPOINT_IO_RETRY_BACKOFF_DEFAULT)
    if isinstance(val, bool) or not isinstance(val, (int, float)) or val < 0:
        raise DeepSpeedConfigError(
            "checkpoint.{} must be a number >= 0, got {!r}".format(
                CHECKPOINT_IO_RETRY_BACKOFF, val))
    return float(val)


def get_checkpoint_keep_last_n(checkpoint_params):
    val = checkpoint_params.get(CHECKPOINT_KEEP_LAST_N,
                                CHECKPOINT_KEEP_LAST_N_DEFAULT)
    if val is None:
        return None
    if isinstance(val, bool) or not isinstance(val, int) or val < 1:
        raise DeepSpeedConfigError(
            "checkpoint.{} must be an int >= 1 (or null to disable "
            "pruning), got {!r}".format(CHECKPOINT_KEEP_LAST_N, val))
    return val


TRANSFORMER = "transformer"
TRANSFORMER_FLASH_ATTENTION = "flash_attention"

#############################################
# Runtime executor (docs/executor.md)
#############################################
RUNTIME = "runtime"
RUNTIME_EXECUTOR = "executor"
RUNTIME_EXECUTOR_DEFAULT = "auto"
RUNTIME_EXECUTOR_MODES = ("auto", "on", "off")


def get_runtime_executor(param_dict):
    """``runtime.executor``: tri-state gate for the segment-plan
    executor's constructed overlap (``runtime/executor/``). ``auto``
    (default) and ``on`` run plans with async transfer/compute overlap;
    ``off`` runs every plan serially in plan order — the bit-exact
    oracle mode for A/B debugging. Strict-validated: any other value
    raises (an enum typo silently falling back would un-A/B the
    comparison it exists for)."""
    sub = param_dict.get(RUNTIME) or {}
    if not isinstance(sub, dict):
        raise DeepSpeedConfigError(
            "runtime must be a dict, got {}".format(type(sub).__name__))
    val = sub.get(RUNTIME_EXECUTOR, RUNTIME_EXECUTOR_DEFAULT)
    if not isinstance(val, str) or \
            val.lower() not in RUNTIME_EXECUTOR_MODES:
        raise DeepSpeedConfigError(
            "runtime.{} must be one of {}, got {!r}".format(
                RUNTIME_EXECUTOR, "|".join(RUNTIME_EXECUTOR_MODES), val))
    return val.lower()


RUNTIME_EXECUTOR_REWRITES = "executor_rewrites"
RUNTIME_EXECUTOR_REWRITE_PASSES = ("hoist", "widen", "fuse")
RUNTIME_EXECUTOR_REWRITES_KEYS = (
    "enabled", "passes", "max_window", "hoist_max_live_bytes")
RUNTIME_EXECUTOR_REWRITES_MAX_WINDOW_DEFAULT = 8
RUNTIME_EXECUTOR_REWRITES_LIVE_BYTES_DEFAULT = 1 << 28


def get_runtime_executor_rewrites(param_dict):
    """``runtime.executor_rewrites``: the plan rewrite passes
    (``runtime/executor/rewrite.py``, docs/executor.md) applied at
    plan-build time in overlap mode — collective/transfer hoisting,
    prefetch-window widening, small-segment fusion. Default OFF (the
    lowered plans execute exactly as declared). ``true`` enables every
    pass; a dict selects passes and bounds (``max_window``: widening
    ceiling per pool; ``hoist_max_live_bytes``: the live-bytes window a
    hoist may extend a result's lifetime across). Strict-validated like
    ``runtime.executor``: unknown keys or pass names raise — a typo'd
    pass silently not running would fake an A/B result."""
    sub = param_dict.get(RUNTIME) or {}
    if not isinstance(sub, dict):
        raise DeepSpeedConfigError(
            "runtime must be a dict, got {}".format(type(sub).__name__))
    val = sub.get(RUNTIME_EXECUTOR_REWRITES, False)
    if isinstance(val, bool):
        val = {"enabled": val}
    if not isinstance(val, dict):
        raise DeepSpeedConfigError(
            "runtime.{} must be a bool or a dict, got {!r}".format(
                RUNTIME_EXECUTOR_REWRITES, val))
    for key in val:
        if key not in RUNTIME_EXECUTOR_REWRITES_KEYS:
            raise DeepSpeedConfigError(
                "unknown key {!r} in runtime.{} (accepted: {})".format(
                    key, RUNTIME_EXECUTOR_REWRITES,
                    ", ".join(RUNTIME_EXECUTOR_REWRITES_KEYS)))
    enabled = val.get("enabled", True)
    if not isinstance(enabled, bool):
        raise DeepSpeedConfigError(
            "runtime.{}.enabled must be a bool, got {!r}".format(
                RUNTIME_EXECUTOR_REWRITES, enabled))
    passes = val.get("passes", list(RUNTIME_EXECUTOR_REWRITE_PASSES))
    if not isinstance(passes, (list, tuple)) or not all(
            isinstance(p, str) for p in passes):
        raise DeepSpeedConfigError(
            "runtime.{}.passes must be a list of pass names, got "
            "{!r}".format(RUNTIME_EXECUTOR_REWRITES, passes))
    for p in passes:
        if p not in RUNTIME_EXECUTOR_REWRITE_PASSES:
            raise DeepSpeedConfigError(
                "unknown rewrite pass {!r} in runtime.{}.passes "
                "(accepted: {})".format(
                    p, RUNTIME_EXECUTOR_REWRITES,
                    "|".join(RUNTIME_EXECUTOR_REWRITE_PASSES)))
    max_window = val.get("max_window",
                         RUNTIME_EXECUTOR_REWRITES_MAX_WINDOW_DEFAULT)
    if isinstance(max_window, bool) or not isinstance(max_window, int) \
            or max_window < 1:
        raise DeepSpeedConfigError(
            "runtime.{}.max_window must be an int >= 1, got {!r}".format(
                RUNTIME_EXECUTOR_REWRITES, max_window))
    live_bytes = val.get("hoist_max_live_bytes",
                         RUNTIME_EXECUTOR_REWRITES_LIVE_BYTES_DEFAULT)
    if isinstance(live_bytes, bool) or not isinstance(live_bytes, int) \
            or live_bytes < 1:
        raise DeepSpeedConfigError(
            "runtime.{}.hoist_max_live_bytes must be an int >= 1, got "
            "{!r}".format(RUNTIME_EXECUTOR_REWRITES, live_bytes))
    return {"enabled": enabled, "passes": tuple(passes),
            "max_window": max_window,
            "hoist_max_live_bytes": live_bytes}


CONTROLLER = "controller"


def refuse_removed_controller(value, key=CONTROLLER):
    """An old ds_config may still carry the run-time controller's keys
    (``controller``, ``telemetry.watchdog.controller``): ``false`` (the
    controller off) is accepted and means nothing; a config that asks
    for the controller is refused, not ignored."""
    if value is not False:
        raise DeepSpeedConfigError(
            "{} is set, but the run-time controller was removed in PR 31: "
            "the knobs it moved (executor windows, spec_k, "
            "prefill_chunk_tokens, prefill_buckets, quantized "
            "collectives) are set in the config".format(key))


TRANSFORMER_FLASH_ATTENTION_MODES = ("auto", "pallas", "xla")


def get_transformer_flash_attention(param_dict):
    """``transformer.flash_attention``: tri-state gate for the Pallas
    flash-attention kernel on the dense training path, mirroring
    ``inference.paged_attention_kernel``. ``None`` (key or section
    absent) leaves the model config's own default. ``"auto"`` takes the
    kernel exactly on TPU and the XLA reference elsewhere; ``"pallas"``
    forces the kernel — off-TPU it runs under the Pallas interpreter
    with a LOUD one-time warning (parity/debug) instead of silently
    going dense; ``"xla"`` pins the reference oracle. The legacy bools
    still parse: true -> "auto", false -> "xla". Strict-validated like
    runtime.executor — an enum typo raises instead of silently changing
    the kernel under a benchmark."""
    sub = param_dict.get(TRANSFORMER) or {}
    if not isinstance(sub, dict):
        raise DeepSpeedConfigError(
            "transformer must be a dict, got {}".format(type(sub).__name__))
    val = sub.get(TRANSFORMER_FLASH_ATTENTION)
    if val is None:
        return None
    if isinstance(val, bool):
        return "auto" if val else "xla"
    if not isinstance(val, str) or \
            val.lower() not in TRANSFORMER_FLASH_ATTENTION_MODES:
        raise DeepSpeedConfigError(
            "transformer.{} must be a bool, null or one of {}, got {!r}"
            .format(TRANSFORMER_FLASH_ATTENTION,
                    "|".join(TRANSFORMER_FLASH_ATTENTION_MODES), val))
    return val.lower()


def get_pld_enabled(param_dict):
    if PROGRESSIVE_LAYER_DROP in param_dict:
        return get_scalar_param(param_dict[PROGRESSIVE_LAYER_DROP], PLD_ENABLED,
                                PLD_ENABLED_DEFAULT)
    return False


def get_pld_params(param_dict):
    if PROGRESSIVE_LAYER_DROP in param_dict:
        pld_params = dict(param_dict[PROGRESSIVE_LAYER_DROP])
        pld_params.pop(PLD_ENABLED, None)
        return pld_params
    return False


class DeepSpeedConfig(object):
    """Typed view of a full ``ds_config`` dict (or json file path).

    ``world_size`` is the data-parallel world size: for a mesh
    (data, model, pipe) it is the size of the ``data`` axis — matching the
    reference where world_size = total ranks / model-parallel size
    (reference config.py:529-539).
    """

    def __init__(self, json_file, mpu=None, param_dict=None, mesh=None,
                 inference_only=False):
        super(DeepSpeedConfig, self).__init__()
        # init_inference sets this: an inference-only parse needs no
        # training batch triple. Keyed on the CALLER, not on the presence
        # of an "inference" section — one config may drive both
        # initialize() and init_inference(), and the training path must
        # keep validating its triple.
        self._inference_only = inference_only

        if param_dict is None:
            with open(json_file, "r") as f:
                self._param_dict = json.load(
                    f, object_pairs_hook=dict_raise_error_on_duplicate_keys)
        else:
            self._param_dict = param_dict

        try:
            import jax
            self.global_rank = jax.process_index()
            total_devices = jax.device_count()
        except Exception:
            self.global_rank = 0
            total_devices = 1

        if mesh is not None:
            self.world_size = int(mesh.shape.get("data", 1))
        elif mpu is not None:
            self.world_size = total_devices // mpu.get_model_parallel_world_size()
        else:
            self.world_size = total_devices

        # If elasticity is enabled, it overrides the batch config for the
        # current world size and pins an immutable fingerprint.
        self.elasticity_enabled = False
        if self._param_dict.get("elasticity", {}).get("enabled", False):
            self._configure_elasticity()

        self._initialize_params(self._param_dict)
        self._configure_train_batch_size()
        self._validate_known_keys()
        self._do_sanity_check()

    def _configure_elasticity(self):
        from ..elasticity import (compute_elastic_config, elasticity_enabled,
                                  ensure_immutable_elastic_config,
                                  IGNORE_NON_ELASTIC_BATCH_INFO,
                                  IGNORE_NON_ELASTIC_BATCH_INFO_DEFAULT,
                                  ELASTICITY)
        from ..version import __version__
        self.elasticity_enabled = elasticity_enabled(self._param_dict)

        elastic_dict = self._param_dict[ELASTICITY]
        ignore_non_elastic_batch_info = elastic_dict.get(
            IGNORE_NON_ELASTIC_BATCH_INFO, IGNORE_NON_ELASTIC_BATCH_INFO_DEFAULT)
        if not ignore_non_elastic_batch_info:
            batch_params = [TRAIN_BATCH_SIZE, TRAIN_MICRO_BATCH_SIZE_PER_GPU,
                            GRADIENT_ACCUMULATION_STEPS]
            if any(p in self._param_dict for p in batch_params):
                raise DeepSpeedConfigError(
                    "One or more batch related parameters were found in your "
                    "ds_config ({}). These parameters *will not be used* since "
                    "elastic training is enabled, which takes control of these "
                    "parameters. If you want to suppress this error set '{}': "
                    "true in your elasticity config.".format(
                        ", ".join(batch_params), IGNORE_NON_ELASTIC_BATCH_INFO))

        ensure_immutable_elastic_config(elastic_dict)
        final_batch_size, valid_gpus, micro_batch_size = compute_elastic_config(
            ds_config=self._param_dict,
            target_deepspeed_version=__version__,
            world_size=self.world_size)
        self.elastic_valid_world_sizes = valid_gpus
        gradient_accu_steps = final_batch_size // (micro_batch_size *
                                                   self.world_size)
        self._param_dict[TRAIN_BATCH_SIZE] = final_batch_size
        self._param_dict[TRAIN_MICRO_BATCH_SIZE_PER_GPU] = micro_batch_size
        self._param_dict[GRADIENT_ACCUMULATION_STEPS] = gradient_accu_steps

    def validate_elastic_world_size(self, world_size):
        """Preflight a PROPOSED world size for an elastic rescale
        (runtime/elastic/): the same candidate-batch math that ran at
        init, re-run for the target topology BEFORE any teardown.
        Raises ``ElasticityIncompatibleWorldSize`` (with the valid
        counts, or the divisibility that failed) when the target cannot
        preserve the global batch; returns the
        ``(final_batch, micro_batch, grad_accum)`` triple the rescaled
        engine will train with."""
        from ..elasticity import (ElasticityIncompatibleWorldSize,
                                  compute_elastic_config)
        from ..version import __version__
        world_size = int(world_size)
        if world_size < 1:
            raise ElasticityIncompatibleWorldSize(
                "world size {} is not positive".format(world_size))
        if self.elasticity_enabled:
            final_batch, _valid, micro = compute_elastic_config(
                ds_config=self._param_dict,
                target_deepspeed_version=__version__,
                world_size=world_size)
            return (final_batch, micro,
                    final_batch // (micro * world_size))
        # non-elastic config: the rescale must keep the SAME global
        # batch by re-deriving the batch triple for the TARGET world
        # from the EXPLICIT keys only — the values this config derived
        # for ITS world (e.g. micro = batch/world) do not transfer
        batch = get_train_batch_size(self._param_dict)
        micro = get_train_micro_batch_size_per_gpu(self._param_dict)
        grad_acc = get_gradient_accumulation_steps(self._param_dict)
        if batch is None:
            # no pinned global batch — any world works (micro * accum
            # scales the global batch with the world, like init does)
            return (None, micro, grad_acc or 1)
        fixed = (micro if micro is not None else grad_acc) or 1
        if batch % (fixed * world_size) != 0:
            raise ElasticityIncompatibleWorldSize(
                "world size {} cannot preserve train_batch_size={} "
                "({} {} x world {} does not divide it; add an "
                "elasticity section for candidate world sizes)".format(
                    world_size, batch,
                    "micro batch" if micro is not None
                    else "grad-accum", fixed, world_size))
        if micro is not None:
            return (batch, micro, batch // (micro * world_size))
        if grad_acc is not None:
            return (batch, batch // (grad_acc * world_size), grad_acc)
        return (batch, batch // world_size, 1)

    def _initialize_params(self, param_dict):
        self.train_batch_size = get_train_batch_size(param_dict)
        self.train_micro_batch_size_per_gpu = \
            get_train_micro_batch_size_per_gpu(param_dict)
        self.gradient_accumulation_steps = get_gradient_accumulation_steps(param_dict)
        self.steps_per_print = get_steps_per_print(param_dict)
        self.dump_state = get_dump_state(param_dict)

        self.disable_allgather = get_disable_allgather(param_dict)
        self.allreduce_always_fp32 = get_allreduce_always_fp32(param_dict)
        self.prescale_gradients = get_prescale_gradients(param_dict)
        self.gradient_predivide_factor = get_gradient_predivide_factor(param_dict)
        self.sparse_gradients_enabled = get_sparse_gradients_enabled(param_dict)

        self.zero_config = DeepSpeedZeroConfig(param_dict)
        self.zero_optimization_stage = self.zero_config.stage
        self.zero_enabled = self.zero_optimization_stage > 0

        self.activation_checkpointing_config = \
            DeepSpeedActivationCheckpointingConfig(param_dict)
        self.flops_profiler_config = DeepSpeedFlopsProfilerConfig(param_dict)
        self.inference_config = DeepSpeedInferenceConfig(param_dict)
        self.telemetry_config = DeepSpeedTelemetryConfig(param_dict)
        # the auditor shares the observatory's thresholds (one config)
        self.analysis_config = DeepSpeedAnalysisConfig(
            param_dict, telemetry_config=self.telemetry_config)
        self.comm_config = DeepSpeedCommConfig(param_dict)
        self.transformer_flash_attention = \
            get_transformer_flash_attention(param_dict)
        self.runtime_executor = get_runtime_executor(param_dict)
        self.runtime_executor_rewrites = \
            get_runtime_executor_rewrites(param_dict)
        refuse_removed_controller(param_dict.get(CONTROLLER, False))

        self.gradient_clipping = get_gradient_clipping(param_dict)
        self.grad_accum_dtype = get_grad_accum_dtype(param_dict)
        self.fp16_enabled = get_fp16_enabled(param_dict)
        self.bf16_enabled = get_bf16_enabled(param_dict)
        self.amp_enabled = get_amp_enabled(param_dict)
        self.amp_params = get_amp_params(param_dict)
        self.loss_scale = get_loss_scale(param_dict)
        self.initial_dynamic_scale = get_initial_dynamic_scale(param_dict)
        self.dynamic_loss_scale_args = get_dynamic_loss_scale_args(param_dict)

        self.optimizer_name = get_optimizer_name(param_dict)
        if self.optimizer_name is not None and \
                self.optimizer_name.lower() in DEEPSPEED_OPTIMIZERS:
            self.optimizer_name = self.optimizer_name.lower()
        self.optimizer_params = get_optimizer_params(param_dict)
        self.optimizer_legacy_fusion = get_optimizer_legacy_fusion(param_dict)

        self.zero_allow_untested_optimizer = \
            get_zero_allow_untested_optimizer(param_dict)

        self.scheduler_name = get_scheduler_name(param_dict)
        self.scheduler_params = get_scheduler_params(param_dict)

        self.wall_clock_breakdown = get_wall_clock_breakdown(param_dict)
        self.memory_breakdown = get_memory_breakdown(param_dict)
        self.tensorboard_enabled = get_tensorboard_enabled(param_dict)
        self.tensorboard_output_path = get_tensorboard_output_path(param_dict)
        self.tensorboard_job_name = get_tensorboard_job_name(param_dict)

        self.sparse_attention = get_sparse_attention(param_dict)

        self.pld_enabled = get_pld_enabled(param_dict)
        self.pld_params = get_pld_params(param_dict)

        checkpoint_params = get_checkpoint_params(param_dict)
        validation_mode = get_checkpoint_tag_validation_mode(checkpoint_params)
        self.checkpoint_tag_validation_enabled = \
            validation_mode != ValidationMode.IGNORE
        self.checkpoint_tag_validation_fail = validation_mode == ValidationMode.FAIL
        self.checkpoint_io_retries = get_checkpoint_io_retries(checkpoint_params)
        self.checkpoint_io_backoff_seconds = \
            get_checkpoint_io_backoff(checkpoint_params)
        self.checkpoint_keep_last_n = \
            get_checkpoint_keep_last_n(checkpoint_params)

    def _batch_assertion(self):
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps

        assert train_batch > 0, \
            "Train batch size: {} has to be greater than 0".format(train_batch)
        assert micro_batch > 0, \
            "Micro batch size per device: {} has to be greater than 0".format(
                micro_batch)
        assert grad_acc > 0, \
            "Gradient accumulation steps: {} has to be greater than 0".format(
                grad_acc)
        assert train_batch == micro_batch * grad_acc * self.world_size, (
            "Check batch related parameters. train_batch_size is not equal to "
            "micro_batch_per_gpu * gradient_acc_step * world_size: "
            "{} != {} * {} * {}".format(train_batch, micro_batch, grad_acc,
                                        self.world_size))

    def _set_batch_related_parameters(self):
        """Infer the missing member(s) of the batch triple
        (train_batch, micro_batch, grad_accum); any two determine the third."""
        train_batch = self.train_batch_size
        micro_batch = self.train_micro_batch_size_per_gpu
        grad_acc = self.gradient_accumulation_steps

        if all(v is not None for v in (train_batch, micro_batch, grad_acc)):
            return
        elif train_batch is not None and micro_batch is not None:
            self.gradient_accumulation_steps = \
                train_batch // micro_batch // self.world_size
        elif train_batch is not None and grad_acc is not None:
            self.train_micro_batch_size_per_gpu = \
                train_batch // self.world_size // grad_acc
        elif micro_batch is not None and grad_acc is not None:
            self.train_batch_size = micro_batch * grad_acc * self.world_size
        elif train_batch is not None:
            self.gradient_accumulation_steps = 1
            self.train_micro_batch_size_per_gpu = train_batch // self.world_size
        elif micro_batch is not None:
            self.train_batch_size = micro_batch * self.world_size
            self.gradient_accumulation_steps = 1
        elif self._inference_only:
            # init_inference parse: no training batch triple required
            self.train_micro_batch_size_per_gpu = 1
            self.gradient_accumulation_steps = 1
            self.train_batch_size = self.world_size
        else:
            raise AssertionError(
                "Either train_batch_size or train_micro_batch_size_per_gpu "
                "needs to be provided")

    def _configure_train_batch_size(self):
        self._set_batch_related_parameters()
        self._batch_assertion()

    # The accepted config surface. docs/_pages/config-json.md documents
    # exactly these keys; _validate_known_keys keeps doc and parser from
    # drifting (unknown keys warn by default, raise under
    # "config_validation": "strict", silent under "ignore").
    KNOWN_TOP_LEVEL_KEYS = {
        "train_batch_size", "train_micro_batch_size_per_gpu",
        "gradient_accumulation_steps", "optimizer", "scheduler",
        "fp16", "bf16", "amp", "gradient_clipping",
        "zero_optimization", "zero_allow_untested_optimizer",
        "steps_per_print", "wall_clock_breakdown", "dump_state",
        "memory_breakdown", "tensorboard", "flops_profiler",
        "activation_checkpointing", "sparse_attention",
        "progressive_layer_drop", "elasticity", "checkpoint",
        "sparse_gradients", "prescale_gradients",
        "gradient_predivide_factor", "disable_allgather", "fp32_allreduce",
        "vocabulary_size", "config_validation", "data_types",
        INFERENCE, TELEMETRY, COMM, TRANSFORMER, ANALYSIS, RUNTIME,
        CONTROLLER,
        # deprecated boolean form + its companion (read_zero_config_deprecated)
        "allgather_size",
    }
    KNOWN_SUBDICT_KEYS = {
        "fp16": {"enabled", "loss_scale", "initial_scale_power",
                 "loss_scale_window", "hysteresis", "min_loss_scale"},
        "bf16": {"enabled"},
        "zero_optimization": {
            "stage", "allgather_partitions", "allgather_bucket_size",
            "overlap_comm", "reduce_scatter",
            "reduce_bucket_size", "contiguous_gradients", "cpu_offload",
            "cpu_offload_params", "cpu_offload_use_pin_memory",
            "sub_group_size", "stage3_prefetch_bucket_size",
            "stage3_max_live_parameters", "stage3_max_reuse_distance",
            "stage3_param_persistence_threshold", "elastic_checkpoint",
            "load_from_fp32_weights",
            "stage3_gather_fp16_weights_on_model_save",
            # ZeRO++ comm-efficiency modes (docs/zeropp.md)
            "zero_quantized_weights", "zero_hierarchical_partition",
            "zero_quantized_gradients",
            # no-silent-no-ops enforcement (docs/zero3_offload.md)
            "strict",
            # short alias of stage3_param_persistence_threshold (the
            # zero.Init config-dict spelling)
            "param_persistence_threshold"},
        "flops_profiler": {"enabled", "profile_step", "module_depth",
                           "top_modules", "detailed"},
        "activation_checkpointing": {
            "partition_activations", "contiguous_memory_optimization",
            "cpu_checkpointing", "number_checkpoints",
            "synchronize_checkpoint_boundary", "profile"},
        "progressive_layer_drop": {"enabled", "theta", "gamma"},
        "tensorboard": {"enabled", "output_path", "job_name"},
        "checkpoint": {"tag_validation", "io_retries",
                       "io_retry_backoff_seconds", "keep_last_n"},
        "data_types": {"grad_accum_dtype"},
        INFERENCE: DeepSpeedInferenceConfig.KNOWN_KEYS,
        TELEMETRY: KNOWN_TELEMETRY_KEYS,
        ANALYSIS: KNOWN_ANALYSIS_KEYS,
        # nested collective_matmul keys are validated (strict-aware) by
        # CollectiveMatmulConfig itself (runtime/comm/config.py)
        COMM: KNOWN_COMM_KEYS,
        TRANSFORMER: {TRANSFORMER_FLASH_ATTENTION},
        RUNTIME: {RUNTIME_EXECUTOR, RUNTIME_EXECUTOR_REWRITES},
        "elasticity": {"enabled", "max_train_batch_size",
                       "micro_batch_sizes", "min_gpus", "max_gpus",
                       "min_time", "prefer_larger_batch",
                       "ignore_non_elastic_batch_info", "version",
                       # runtime rescale policy (ISSUE 16,
                       # runtime/elastic/, docs/elasticity.md)
                       "rescale_retries", "rescale_backoff_seconds",
                       "eviction_severity", "eviction_windows",
                       "preemption_notice_file", "fingerprint_gate"},
        # optimizer/scheduler "params" and "amp" bodies are free-form
        # passthrough (per-type / apex-parity); sparse_attention keys vary
        # by mode and are validated by the layout builders themselves
    }

    def _validate_known_keys(self):
        mode = str(self._param_dict.get("config_validation", "warn")).lower()
        if mode not in ("warn", "strict", "ignore"):
            raise DeepSpeedConfigError(
                "config_validation must be one of warn|strict|ignore, got "
                "{!r}".format(mode))
        if mode == "ignore":
            return
        problems = []
        for key in self._param_dict:
            if key not in self.KNOWN_TOP_LEVEL_KEYS:
                problems.append("unknown top-level key {!r}".format(key))
        for section, known in self.KNOWN_SUBDICT_KEYS.items():
            sub = self._param_dict.get(section)
            if not isinstance(sub, dict):
                continue
            for key in sub:
                if key not in known:
                    problems.append("unknown key {!r} in {!r}".format(
                        key, section))
        if not problems:
            return
        msg = ("DeepSpeedConfig: {} (the accepted surface is documented in "
               "docs/_pages/config-json.md; set \"config_validation\": "
               "\"ignore\" to bypass)").format("; ".join(problems))
        if mode == "strict":
            raise DeepSpeedConfigError(msg)
        logger.warning(msg)

    def _do_sanity_check(self):
        self._do_error_check()
        self._do_warning_check()

    def print(self, name):
        logger.info("{}:".format(name))
        for arg in sorted(vars(self)):
            if arg != "_param_dict":
                dots = "." * (29 - len(arg))
                logger.info("  {} {} {}".format(arg, dots, getattr(self, arg)))
        logger.info("  json = {}".format(
            json.dumps(self._param_dict, sort_keys=True, indent=4,
                       separators=(",", ":"))))

    def _do_error_check(self):
        assert self.train_micro_batch_size_per_gpu, \
            "DeepSpeedConfig: {} is not defined".format(
                TRAIN_MICRO_BATCH_SIZE_PER_GPU)
        assert self.gradient_accumulation_steps, \
            "DeepSpeedConfig: {} is not defined".format(GRADIENT_ACCUMULATION_STEPS)
        if self.zero_enabled:
            # Reference requires fp16 for ZeRO; bf16 is the TPU-native
            # equivalent and is accepted as well.
            assert self.fp16_enabled or self.bf16_enabled, \
                "DeepSpeedConfig: ZeRO is only supported if fp16/bf16 is enabled"
            assert self.zero_optimization_stage <= MAX_STAGE_ZERO_OPTIMIZATION, \
                "DeepSpeedConfig: Maximum supported ZeRO stage is {}".format(
                    MAX_STAGE_ZERO_OPTIMIZATION)

    def _do_warning_check(self):
        fp16_enabled = self.fp16_enabled or self.zero_enabled
        vocabulary_size = self._param_dict.get(VOCABULARY_SIZE,
                                               VOCABULARY_SIZE_DEFAULT)
        if vocabulary_size and vocabulary_size % TENSOR_CORE_ALIGN_SIZE != 0:
            logger.warning(
                "DeepSpeedConfig: vocabulary size {} is not aligned to {}, may "
                "impact MXU utilization.".format(vocabulary_size,
                                                TENSOR_CORE_ALIGN_SIZE))
        if self.optimizer_params is not None and \
                MAX_GRAD_NORM in self.optimizer_params.keys() and \
                self.optimizer_params[MAX_GRAD_NORM] > 0:
            if fp16_enabled:
                if self.global_rank == 0:
                    logger.warning(
                        "DeepSpeedConfig: In FP16 mode, DeepSpeed will pass "
                        "{}:{} to FP16 wrapper".format(
                            MAX_GRAD_NORM, self.optimizer_params[MAX_GRAD_NORM]))
            else:
                if self.global_rank == 0:
                    logger.warning(
                        "DeepSpeedConfig: In FP32 mode, DeepSpeed does not "
                        "permit MAX_GRAD_NORM ({}) > 0, setting to zero".format(
                            self.optimizer_params[MAX_GRAD_NORM]))
                self.optimizer_params[MAX_GRAD_NORM] = 0.0
