"""Activation checkpointing (rematerialization) for TPU.

Reference parity: deepspeed/runtime/activation_checkpointing/checkpointing.py
(CheckpointFunction :379-705, configure :788-867, CudaRNGStatesTracker
:150-266). The torch version re-runs the forward inside backward with manually
saved/restored CUDA RNG states; under JAX, ``jax.checkpoint`` gives
recompute-in-backward natively and PRNG keys are explicit values, so recompute
sees bit-identical dropout by construction — the RNG tracker survives only as
an API-compatible key-derivation helper.

Option mapping (reference module globals :52-56):
  PARTITION_ACTIVATIONS  -> saved residuals sharded over the 'model' mesh axis
                            via a sharding constraint inside the remat'd fn
                            (reference shards checkpointed activations across
                            MP ranks, :268-316).
  PA_TO_CPU              -> remat policy that offloads saved residuals to
                            pinned host memory when the backend supports it
                            (reference copies checkpoint tensors to host).
  CONTIGUOUS_CHECKPOINTING -> accepted for parity; XLA owns layout, no ring
                            buffers needed.
  SYNCHRONIZE            -> block_until_ready around the call (profiling aid).
  PROFILE_TIME           -> wall-clock timing of fwd via utils/timer.
"""
import contextlib
import functools

import jax
import jax.numpy as jnp

from ...utils.logging import logger
from ...utils.timer import SynchronizedWallClockTimer

# --------------------------------------------------------------------------
# module-level option state (reference :43-56)
# --------------------------------------------------------------------------
PARTITION_ACTIVATIONS = False
CPU_CHECKPOINT = False
CONTIGUOUS_CHECKPOINTING = False
SYNCHRONIZE = False
PROFILE_TIME = False

num_layers = None
mp_size = 1
mpu = None

deepspeed_checkpointing_enabled = False

timers = None

_MODEL_AXIS = "model"


# --------------------------------------------------------------------------
# RNG state tracking (reference CudaRNGStatesTracker :150-266)
# --------------------------------------------------------------------------
_MODEL_PARALLEL_RNG_TRACKER_NAME = "model-parallel-rng"


class RNGStatesTracker:
    """Named PRNG-key tracker.

    The reference forks/restores CUDA RNG states so that recompute inside
    backward sees the same dropout mask. JAX PRNG keys are pure values —
    recompute is identical automatically — so this tracker only maintains
    named keys for model-parallel-aware dropout (each named stream advances
    deterministically via ``jax.random.fold_in``).
    """

    def __init__(self):
        self.states_ = {}
        self.seeds_ = set()

    def reset(self):
        self.states_ = {}
        self.seeds_ = set()

    def get_states(self):
        return dict(self.states_)

    def set_states(self, states):
        self.states_ = dict(states)

    def add(self, name, seed):
        if seed in self.seeds_:
            raise Exception("seed {} already exists".format(seed))
        self.seeds_.add(seed)
        if name in self.states_:
            raise Exception("state {} already exists".format(name))
        self.states_[name] = jax.random.PRNGKey(seed)

    @contextlib.contextmanager
    def fork(self, name=_MODEL_PARALLEL_RNG_TRACKER_NAME):
        """Yield the named key and advance the stream on exit."""
        if name not in self.states_:
            raise Exception("state {} does not exist".format(name))
        key = self.states_[name]
        try:
            yield key
        finally:
            self.states_[name] = jax.random.fold_in(key, 1)


_CUDA_RNG_STATE_TRACKER = RNGStatesTracker()


def get_cuda_rng_tracker():
    """Reference API name kept (checkpointing.py:240); returns the tracker."""
    return _CUDA_RNG_STATE_TRACKER


def model_parallel_cuda_manual_seed(seed, tp_rank=0):
    """Seed the default + model-parallel RNG streams (reference :243-266).

    Data-parallel stream = ``seed``; model-parallel stream offset by
    2718 + tp_rank so TP ranks draw different dropout on sliced activations.
    """
    model_parallel_seed = seed + 2718 + tp_rank
    _CUDA_RNG_STATE_TRACKER.reset()
    _CUDA_RNG_STATE_TRACKER.add("default", seed)
    _CUDA_RNG_STATE_TRACKER.add(_MODEL_PARALLEL_RNG_TRACKER_NAME,
                                model_parallel_seed)


# --------------------------------------------------------------------------
# remat policies
# --------------------------------------------------------------------------
def _offload_policy():
    """Host-offload remat policy for PA_TO_CPU."""
    return jax.checkpoint_policies.save_and_offload_only_these_names(
        names_which_can_be_saved=[],
        names_which_can_be_offloaded=["checkpointed"],
        offload_src="device", offload_dst="pinned_host")


def _shard_over_model_axis(tree):
    """Apply a sharding constraint splitting each leaf's last dim over the
    model axis when divisible (reference partitions checkpointed activations
    across MP ranks, :268-316). Outside jit / without a mesh this is an
    identity."""
    from jax.sharding import PartitionSpec as P

    def constrain(x):
        if not hasattr(x, "ndim") or x.ndim == 0:
            return x
        spec = [None] * x.ndim
        spec[-1] = _MODEL_AXIS
        try:
            return jax.lax.with_sharding_constraint(x, P(*spec))
        except Exception:
            return x

    return jax.tree_util.tree_map(constrain, tree)


def checkpoint(function, *args):
    """Recompute-in-backward wrapper (reference ``checkpoint()`` :706).

    Returns ``function(*args)`` with residuals dropped and recomputed during
    the backward pass. Differentiable; composes with jit/pjit/scan.
    """
    policy = _offload_policy() if CPU_CHECKPOINT else \
        jax.checkpoint_policies.nothing_saveable

    if PARTITION_ACTIVATIONS or CPU_CHECKPOINT:
        def fn(*a):
            if CPU_CHECKPOINT:
                # Tag the residuals so the offload policy can match them
                # (save_and_offload_only_these_names keys on checkpoint_name).
                from jax.ad_checkpoint import checkpoint_name
                a = jax.tree_util.tree_map(
                    lambda x: checkpoint_name(x, "checkpointed")
                    if hasattr(x, "ndim") else x, a)
            if PARTITION_ACTIVATIONS:
                a = _shard_over_model_axis(a)
            return function(*a)
    else:
        fn = function

    wrapped = jax.checkpoint(fn, policy=policy)

    if PROFILE_TIME and timers is not None:
        timers("forward").start()
    out = wrapped(*args)
    if SYNCHRONIZE:
        try:
            jax.block_until_ready(out)
        except Exception:
            pass
    if PROFILE_TIME and timers is not None:
        timers("forward").stop()
    return out


def checkpoint_wrapper(function):
    """Decorator form: ``fn = checkpoint_wrapper(fn)``."""
    @functools.wraps(function)
    def wrapped(*args):
        return checkpoint(function, *args)
    return wrapped


# --------------------------------------------------------------------------
# configuration surface (reference :706-877)
# --------------------------------------------------------------------------
def set_num_layers(nlayers):
    global num_layers
    num_layers = nlayers


def reset():
    """Reference ``reset()``: clears contiguous buffers; here a no-op that
    keeps API parity (XLA owns activation memory)."""


def partition_activations_in_checkpoint(partition_activation):
    global PARTITION_ACTIVATIONS
    PARTITION_ACTIVATIONS = partition_activation
    if PARTITION_ACTIVATIONS:
        logger.info("**************Partition Activations {}************".
                    format(PARTITION_ACTIVATIONS))


def configure(mpu_=None,
              deepspeed_config=None,
              partition_activations=None,
              contiguous_checkpointing=None,
              num_checkpoints=None,
              checkpoint_in_cpu=None,
              synchronize=None,
              profile=None):
    """Configure module options (reference ``configure()`` :788-867).

    Explicit kwargs override values from ``deepspeed_config`` (a parsed
    DeepSpeedConfig or a path/dict accepted by DeepSpeedConfig).
    """
    global mpu, num_layers, deepspeed_checkpointing_enabled, timers
    global PARTITION_ACTIVATIONS, CONTIGUOUS_CHECKPOINTING, \
        CPU_CHECKPOINT, SYNCHRONIZE, PROFILE_TIME

    deepspeed_checkpointing_enabled = True
    mpu = mpu_

    if deepspeed_config is not None:
        from ..config import DeepSpeedConfig
        if not isinstance(deepspeed_config, DeepSpeedConfig):
            deepspeed_config = DeepSpeedConfig(deepspeed_config)
        cfg = deepspeed_config.activation_checkpointing_config
        PARTITION_ACTIVATIONS = cfg.partition_activations
        CONTIGUOUS_CHECKPOINTING = cfg.contiguous_memory_optimization
        num_layers = cfg.number_checkpoints
        CPU_CHECKPOINT = cfg.cpu_checkpointing
        SYNCHRONIZE = cfg.synchronize_checkpoint_boundary
        PROFILE_TIME = cfg.profile

    if partition_activations is not None:
        PARTITION_ACTIVATIONS = partition_activations
    if contiguous_checkpointing is not None:
        CONTIGUOUS_CHECKPOINTING = contiguous_checkpointing
    if num_checkpoints is not None:
        num_layers = num_checkpoints
    if checkpoint_in_cpu is not None:
        CPU_CHECKPOINT = checkpoint_in_cpu
    if synchronize is not None:
        SYNCHRONIZE = synchronize
    if profile is not None:
        PROFILE_TIME = profile

    if PROFILE_TIME and timers is None:
        timers = SynchronizedWallClockTimer()

    if CONTIGUOUS_CHECKPOINTING:
        assert num_layers is not None, \
            "Must specify the number of checkpoints with contiguous memory " \
            "optimization"
    if CONTIGUOUS_CHECKPOINTING and not PARTITION_ACTIVATIONS:
        raise ValueError("Contiguous memory optimization requires partitioned "
                         "activations")


def is_configured():
    """True once ``configure()`` has been called (reference :870)."""
    return deepspeed_checkpointing_enabled
