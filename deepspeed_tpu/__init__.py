"""DeepSpeed-TPU: a TPU-native training framework with the DeepSpeed API.

Public surface parity with reference deepspeed/__init__.py: ``initialize()``,
``add_config_arguments()``, ``init_distributed``, ``zero``, pipeline module
types, ops. Internals are JAX/XLA/pjit/Pallas over a device mesh — no
torch, no NCCL.
"""
import time as _time
_IMPORT_START_S = _time.perf_counter()

from .version import __version__, __version_info__

from .utils.distributed import init_distributed
from .utils.logging import logger, log_dist
from .runtime.config import DeepSpeedConfig, DeepSpeedConfigError
from .runtime.activation_checkpointing import checkpointing
from . import zero
from .utils.annotate import (engine_tag, new_setup_row, record_setup_row,
                             setup_span)
from .utils.compile_cache import listen as _listen

try:
    from .git_version_info import git_hash as __git_hash__, \
        git_branch as __git_branch__
except ImportError:
    __git_hash__ = None
    __git_branch__ = None


def initialize(args=None, model=None, optimizer=None, model_parameters=None,
               training_data=None, lr_scheduler=None, mpu=None,
               dist_init_required=None, collate_fn=None, config=None,
               config_params=None):
    """Initialize the DeepSpeed-TPU engine.

    Mirrors reference deepspeed/__init__.py:52. Returns a tuple of
    ``(engine, optimizer, training_dataloader, lr_scheduler)``.

    ``model`` is a :class:`deepspeed_tpu.Model` (apply_fn + params pytree), a
    flax module instance paired with params via ``model_parameters``, or a
    :class:`deepspeed_tpu.pipe.PipelineModule` for pipeline parallelism.
    """
    # the whole of it is the start-up record's ``setup.engine``
    # (docs/telemetry.md, "Start-up record")
    with setup_span("setup.engine", kind="train",
                    engine=engine_tag("train")):
        from .runtime.engine import DeepSpeedEngine
        try:
            from .runtime.pipe.module import PipelineModule
            from .runtime.pipe.engine import PipelineEngine
        except ImportError:  # pipeline stack not built yet
            PipelineModule = ()
            PipelineEngine = None

        assert model is not None, "deepspeed.initialize requires a model"

        log_dist("DeepSpeedTPU info: version={}".format(__version__),
                 ranks=[0])

        if dist_init_required is None or dist_init_required:
            init_distributed()

        if config is None and config_params is not None:
            config = config_params

        if not isinstance(model, PipelineModule):
            engine = DeepSpeedEngine(args=args,
                                     model=model,
                                     optimizer=optimizer,
                                     model_parameters=model_parameters,
                                     training_data=training_data,
                                     lr_scheduler=lr_scheduler,
                                     mpu=mpu,
                                     dist_init_required=dist_init_required,
                                     collate_fn=collate_fn,
                                     config_params=config)
        else:
            assert mpu is None, "mpu must be None with pipeline parallelism"
            engine = PipelineEngine(args=args,
                                    model=model,
                                    optimizer=optimizer,
                                    model_parameters=model_parameters,
                                    training_data=training_data,
                                    lr_scheduler=lr_scheduler,
                                    mpu=model.mpu(),
                                    dist_init_required=dist_init_required,
                                    collate_fn=collate_fn,
                                    config_params=config)
    log_dist(engine.startup_line(), ranks=[0])

    return_items = [engine, engine.optimizer, engine.training_dataloader,
                    engine.lr_scheduler]
    return tuple(return_items)


def init_inference(model=None, config=None, mp_size=1, mesh=None,
                   dtype=None, injection_policy=None,
                   replace_method="auto", seed=0, draft_model=None,
                   audit=False):
    """Initialize the DeepSpeed-TPU inference engine.

    Mirrors reference ``deepspeed.init_inference(model, mp_size, dtype,
    injection_policy, replace_method, ...)`` alongside :func:`initialize`.
    Returns an :class:`deepspeed_tpu.inference.InferenceEngine` with a
    preallocated pool of KV pages and a page table a slot, jitted
    prefill/decode paths and a continuous-batching scheduler
    (``engine.generate(prompts)``). With no ``inference`` section the
    pool holds ``max_batch_size * max_seq_len`` tokens.

    ``model`` is a :class:`deepspeed_tpu.Model` that carries a decoder
    (inference/decoder.py; ``models.gpt2.make_gpt2_model`` and the other
    families' ``make_*_model`` attach one). ``config`` is a
    ds_config dict/path whose ``inference`` section sets max_batch_size,
    max_seq_len, prefill_buckets, dtype and sampling defaults. ``mp_size``
    > 1 (or an explicit ``mesh`` with a ``model`` axis) shards params with
    the model's Megatron partition specs and the page pool over its
    packed heads axis. When ``replace_method`` is truthy (default "auto") and
    ``model.params`` is an HF-flax GPT-2 tree (a ``transformer`` subtree),
    the params are converted IN PLACE via
    ``module_inject.hf_gpt2_to_gpt2_params`` using ``injection_policy``
    (default ``HFGPT2LayerPolicy``) — mirroring the reference's
    module-mutating injection.

    ``inference.kv_block_size`` / ``num_pages`` / ``kv_pool_fraction``
    size the pool; ``prefix_caching`` and ``speculative`` build on it
    (docs/inference.md). ``draft_model`` supplies the small GPT-2 drafter that
    ``inference.speculative.method: "model"`` requires.

    ``audit=True`` runs the ahead-of-time shard-lint
    (``engine.audit()``, docs/analysis.md) over the prefill/decode/
    spec-verify programs before the engine is returned — findings warn,
    or raise when the config sets ``analysis.strict``.
    """
    with setup_span("setup.engine", kind="inference",
                    engine=engine_tag("inference")):
        from .inference.engine import InferenceEngine

        assert model is not None, "deepspeed.init_inference requires a model"

        params = getattr(model, "params", None)
        if replace_method and isinstance(params, dict):
            tree = params.get("params", params)
            if isinstance(tree, dict) and "transformer" in tree:
                from .module_inject import (hf_gpt2_to_gpt2_params,
                                            HFGPT2LayerPolicy)
                with setup_span("setup.params"):
                    model.params = hf_gpt2_to_gpt2_params(
                        params,
                        policy=injection_policy or HFGPT2LayerPolicy)

        log_dist("DeepSpeedTPU inference info: version={}".format(
            __version__), ranks=[0])

        if mesh is None and mp_size > 1:
            from .parallel.topology import build_mesh
            import jax
            assert jax.device_count() % mp_size == 0, \
                "mp_size {} does not divide device count {}".format(
                    mp_size, jax.device_count())
            mesh = build_mesh(data=jax.device_count() // mp_size,
                              model=mp_size)

        engine = InferenceEngine(model, config=config, mesh=mesh,
                                 dtype=dtype, seed=seed,
                                 draft_model=draft_model)
        if audit:
            engine.audit()
    log_dist(engine.startup_line(), ranks=[0])
    return engine


def _add_core_arguments(parser):
    """Add DeepSpeed args group (reference __init__.py:148)."""
    group = parser.add_argument_group("DeepSpeed", "DeepSpeed configurations")
    group.add_argument("--deepspeed", default=False, action="store_true",
                       help="Enable DeepSpeed (helper flag for user code, no "
                            "impact on DeepSpeed backend)")
    group.add_argument("--deepspeed_config", default=None, type=str,
                       help="DeepSpeed json configuration file.")
    group.add_argument("--deepspeed_mpi", default=False, action="store_true",
                       help="Run via MPI; discover the job launch info from "
                            "the MPI environment.")
    return parser


def add_config_arguments(parser):
    """Update an argument parser to enable the DeepSpeed-TPU runtime
    (reference __init__.py:199)."""
    parser = _add_core_arguments(parser)
    return parser


# the start-up record (docs/telemetry.md, "Start-up record"): what JAX
# reports of every program's making is booked from here on, and the
# import itself is the record's first row
_listen()
record_setup_row(new_setup_row("setup.import", _IMPORT_START_S,
                               _time.perf_counter()))
