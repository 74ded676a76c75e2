"""Model container: the nn.Module-equivalent handed to ``initialize()``.

The reference wraps a ``torch.nn.Module`` whose ``forward(*inputs)`` returns
the loss (engine.py:886-929). Here a model is a pure apply function plus a
params pytree. Flax modules are adapted automatically.
"""
import inspect


class StreamSpec:
    """Layer-group decomposition contract for streamed parameter offload
    (``zero_optimization.cpu_offload_params``; runtime/zero/stream.py).

    A model that can be trained beyond-HBM exposes its forward as three
    jittable segments the runner streams parameters into one layer group
    at a time:

      ``split(params) -> (embed_tree, [block_tree, ...], head_tree)``
        Restructure the params tree into an embedding segment, per-layer
        block segments, and a head segment. Leaf VALUES must be the
        original tree's objects — a tied weight appearing in two segments
        (e.g. GPT-2's ``wte`` in embed and head) must be the SAME object,
        so the runner can sum both gradient contributions and step the
        master once.
      ``embed_apply(embed_tree, batch, rng, train) -> x``
      ``block_apply(block_tree, x, rng, train) -> x``      (one layer)
      ``head_apply(head_tree, x, batch, rng, train) -> loss``  (fp32 scalar)

    ``batch`` is the full input tuple the engine received (the spec picks
    what each segment needs, e.g. ids for embed, labels for head). The
    composition ``head(blocks(embed(batch)))`` must equal the model's
    ``apply_fn`` loss so the streamed step matches the monolithic one.
    """

    def __init__(self, split, embed_apply, block_apply, head_apply):
        self.split = split
        self.embed_apply = embed_apply
        self.block_apply = block_apply
        self.head_apply = head_apply


class Model:
    """(apply_fn, params) pair.

    ``apply_fn(params, *inputs)`` must return the scalar loss (training
    convention, as the reference's ``module(*inputs)``), or a tuple whose
    first element is the loss. If the function accepts an ``rng`` keyword the
    engine threads a fresh PRNG key per micro-step (dropout etc.); if it
    accepts ``train`` the engine passes the current mode.

    ``partition_spec_fn(path, shape) -> PartitionSpec|None`` may be provided
    for tensor-parallel parameter layouts.
    """

    def __init__(self, apply_fn, params, partition_spec_fn=None, name=None):
        self.apply_fn = apply_fn
        self.params = params
        self.partition_spec_fn = partition_spec_fn
        # optional StreamSpec for streamed parameter offload
        # (cpu_offload_params); models attach it post-construction
        self.stream_spec = None
        # optional ``bind_mesh(mesh)``: a model whose kernels cannot be
        # partitioned by GSPMD (Pallas) attaches it; the engine calls it
        # once with the mesh its step programs span, before it traces
        self.bind_mesh = None
        self.name = name or getattr(apply_fn, "__name__", "model")
        sig_params = _signature_params(apply_fn)
        self.accepts_rng = "rng" in sig_params or "rngs" in sig_params
        self.rng_kwarg = "rngs" if "rngs" in sig_params else "rng"
        # Mode kwarg: either train=bool or the flax-common deterministic=bool.
        if "train" in sig_params:
            self.mode_kwarg = "train"
        elif "deterministic" in sig_params:
            self.mode_kwarg = "deterministic"
        else:
            self.mode_kwarg = None
        self.accepts_kwargs = any(
            p.kind == inspect.Parameter.VAR_KEYWORD for p in sig_params.values())
        self.param_names = set(sig_params)

    def accepts_kwarg(self, name):
        return self.accepts_kwargs or name in self.param_names

    def mode_kwargs(self, train):
        if self.mode_kwarg == "train":
            return {"train": train}
        if self.mode_kwarg == "deterministic":
            return {"deterministic": not train}
        return {}

    def rng_kwargs(self, rng):
        if not self.accepts_rng:
            return {}
        if self.rng_kwarg == "rngs":
            return {"rngs": {"dropout": rng}}
        return {"rng": rng}


def _signature_params(fn):
    try:
        return inspect.signature(fn).parameters
    except (TypeError, ValueError):
        return {}


def as_model(model, model_parameters=None):
    """Coerce user input to a :class:`Model`.

    Accepts: a Model; a flax linen Module (+ params/variables in
    ``model_parameters``); or a bare callable (+ params).
    """
    if isinstance(model, Model):
        return model

    try:
        from flax import linen as nn
        is_flax = isinstance(model, nn.Module)
    except ImportError:
        is_flax = False

    if is_flax:
        assert model_parameters is not None, \
            "flax modules require model_parameters (params or variables dict)"
        variables = model_parameters
        if not (isinstance(variables, dict) and "params" in variables):
            variables = {"params": model_parameters}

        def apply_fn(params, *inputs, **kwargs):
            vs = dict(variables)
            vs["params"] = params
            return model.apply(vs, *inputs, **kwargs)

        sig = _signature_params(model.__call__)
        m = Model(apply_fn, variables["params"],
                  name=type(model).__name__)
        m.accepts_rng = True  # flax apply always takes rngs
        m.rng_kwarg = "rngs"
        if "train" in sig:
            m.mode_kwarg = "train"
        elif "deterministic" in sig:
            m.mode_kwarg = "deterministic"
        else:
            m.mode_kwarg = None
        return m

    if callable(model):
        params = model_parameters
        if params is None:
            params = getattr(model, "params", None)
        assert params is not None, \
            "callable models require model_parameters (a params pytree)"
        return Model(model, params)

    raise TypeError("Cannot interpret model of type {}".format(type(model)))
