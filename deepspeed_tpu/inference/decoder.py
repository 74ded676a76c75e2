"""What ``init_inference()`` asks of a model: the decoder protocol.

The serving engine imports no model module. The model handed to it
carries a ``decoder`` (``make_gpt2_model``, ``make_jamba_model``,
``make_lfm2_model`` and ``make_deepseek_v3_model`` attach one) with:

* ``config``: ``vocab_size``, ``max_seq_len``, ``d_model``;
* ``cache_spec()`` -> :class:`CacheSpec`: what it keeps. One of three
  kinds, or the first two together: keys and values (``kv_layers``
  layers of ``kv_heads x d_head``, a PAIR of pools, in pages or
  slots); per-slot recurrent ``state`` arrays beside them; or, with
  ``page_lanes``, pages whose rows the decoder lays out itself (latent
  attention: ONE pool of ``kv_layers`` layers, each token's row the
  latent all heads share, padded to whole lanes; paged layout only);
* ``serving_config(mesh)`` -> the model config the serving programs
  close over (deterministic, dense; raises for a mesh it cannot span);
  ``decode_config(config, paged_attention_kernel)`` -> the decode
  program family's variant of it;
* ``serving_params(params, dtype)`` -> the weights as served;
* ``forward_hidden(params, ids, config, cache=, positions=,
  page_tables=, valid_lens=, page_size=[, state_slot= |
  state_advance=])`` -> ``(hidden, cache)`` over the cache pytree
  ``(*paged pools, *state arrays)`` (``(k, v, ...)``, or the one pool
  of ``page_lanes``); the two ``state_*`` arguments are passed
  to a ``recurrent`` decoder only: ``state_slot`` with a prefill chunk
  (one slot; ``positions == 0`` marks a request's first chunk, which
  must start from a zero state whatever the slot held),
  ``state_advance`` (slots,) bool with a decode step (the slots whose
  state this step may advance);
* ``logits(params, hidden)``: the head;
* optionally ``counters``, a tuple of names: ``forward_hidden`` is then
  called with ``counters=True`` and returns ``(hidden, cache,
  values)``, one small integer array a name, which the serving
  programs return beside their tokens and the engine fetches WITH the
  tokens. ``counter_attrs(name, value)`` makes of a fetched value the
  attributes (a dict of ints) of one span of that name a launch in the
  profiler's trace, which the scheduler also sums into
  ``ServingMetrics.program_counters[name]``. Engine and scheduler know
  the names only (LFM2's ``moe.load``: the rows each expert got).

``recurrent`` is true where the pages are NOT the whole of a request's
state: prefix sharing, drafting and the fleet's page hand-off refuse
such a model at construction.
"""
from dataclasses import dataclass


@dataclass(frozen=True)
class StateSpec:
    """One per-slot recurrent state array of shape ``lead + (slots,) +
    tail``: layer-major, so that a program reads and writes one layer's
    region of it in place."""
    name: str
    lead: tuple
    tail: tuple
    dtype: object


@dataclass(frozen=True)
class CacheSpec:
    """``page_lanes``: None for the ``(k, v)`` pair of ``kv_heads *
    d_head`` lanes each; else the lanes of a row of the ONE pool the
    decoder lays out itself (640: latent rows, and no ``v``). A
    multiple of the chip's 128 lanes: a pool whose minor dimension is
    not stops the program on the chip."""
    kv_layers: int
    kv_heads: int
    d_head: int
    state: tuple = ()
    page_lanes: int = None


def decoder_of(model, module=None):
    """The decoder ``model`` (or the ``Model`` made of it) carries."""
    for holder in (module, model):
        decoder = getattr(holder, "decoder", None)
        if decoder is not None:
            return decoder
    raise AssertionError(
        "init_inference needs a model with a decoder at .decoder "
        "(inference/decoder.py; e.g. models.gpt2.make_gpt2_model, "
        "models.jamba.make_jamba_model, models.lfm2.make_lfm2_model, "
        "models.deepseek_v3.make_deepseek_v3_model)")


def refuse_recurrent(engine_or_decoder, what):
    """One sentence for every feature that takes the pages for the
    whole of a request's state."""
    decoder = getattr(engine_or_decoder, "decoder", engine_or_decoder)
    if getattr(decoder, "recurrent", False):
        raise ValueError(
            "{} cannot serve a model with recurrent layers: its state "
            "is not in the pages".format(what))


def refuse_latent(spec, what):
    """One sentence for every feature that takes a page for a ``(k,
    v)`` pair of ``kv_heads x d_head`` rows."""
    if spec.page_lanes is not None:
        raise ValueError(
            "{} cannot serve a model with latent pages: its page rows "
            "are not keys and values".format(what))
