"""What ``init_inference()`` asks of a model: the decoder protocol.

The serving engine imports no model module. The model handed to it
carries a ``decoder`` (``make_gpt2_model``, ``make_jamba_model``,
``make_lfm2_model``, ``make_deepseek_v3_model``, ``make_mellum_model``,
``make_cohere2_moe_model``, ``make_olmo_hybrid_model`` and
``make_granite_moe_hybrid_model`` attach one) with:

* ``config``: ``vocab_size``, ``max_seq_len``, ``d_model``;
* ``cache_spec()`` -> :class:`CacheSpec`: what it keeps. One of three
  kinds, or the first two together: keys and values (``kv_layers``
  layers of ``kv_heads x d_head``, a PAIR of page pools); per-slot
  recurrent ``state`` arrays beside them; or, with ``page_lanes``,
  pages whose rows the decoder lays out itself (latent attention: ONE
  pool of ``kv_layers`` layers, each token's row the latent all heads
  share, padded to whole lanes). The paged layers may stand in GROUPS
  (``CacheSpec.groups``): each group has a pool pair, an allocator and
  a page table a slot of its own, and an optional ``window``: a windowed
  group's layers see the last ``window`` keys only, its table slides
  (column 0 is the first page that holds a visible key) and the pages
  that slid out go back to its allocator;
* ``serving_config(mesh)`` -> the model config the serving programs
  close over (deterministic, dense; raises for a mesh it cannot span);
  ``decode_config(config, paged_attention_kernel)`` -> the decode
  program family's variant of it for the engine's resolved read path;
  optionally ``prefill_config(config, paged_attention_kernel)`` -> the
  prefill family's (a decoder whose chunks have a kernel of their own:
  ``chunk_attention`` in Mellum, Command A+, Olmo Hybrid and GPT-2);
  without it prefill closes over ``serving_config``'s, the XLA path;
* ``serving_params(params, dtype)`` -> the weights as served;
* ``forward_hidden(params, ids, config, cache=, positions=,
  page_tables=, valid_lens=, page_size=[, state_slot= |
  state_advance=])`` -> ``(hidden, cache)`` over the cache pytree
  ``(*paged pools, *state arrays)`` (``(k, v, ...)``, or the one pool
  of ``page_lanes``; with groups ``(k, v)`` a group, in the groups'
  order, ``page_tables`` then a tuple, one table a group, and
  ``page_bases`` (slots,) int32 a group: the absolute position of the
  first token of the table's column 0, which is 0 for a group without
  a window); the two ``state_*`` arguments are passed
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
such a model at construction, as they refuse a windowed group (a page
is then not the whole of a position's state in every layer either).
Which feature needs what of a cache is said once, in ``FEATURES``
below; ``refuse`` is what the engine, the drafter and the fleet's roles
call.
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
class PageGroup:
    """One group of paged layers: ``layers`` of them share a pool pair,
    an allocator and a page table a slot. ``window``: None where every
    key stays visible; else the keys a query sees, its own among them
    (query ``t`` sees key ``j`` iff ``0 <= t - j < window``), and the
    group's pages go back to their allocator as they slide out of it.
    Every group's rows are the ``CacheSpec``'s ``kv_heads * d_head``
    lanes."""
    layers: int
    window: int = None


@dataclass(frozen=True)
class CacheSpec:
    """``page_lanes``: None for the ``(k, v)`` pair of ``kv_heads *
    d_head`` lanes each; else the lanes of a row of the ONE pool the
    decoder lays out itself (640: latent rows, and no ``v``). A
    multiple of the chip's 128 lanes: a pool whose minor dimension is
    not stops the program on the chip. ``groups``: the paged layers in
    :class:`PageGroup` s, ``kv_layers`` in all; empty for the one group
    of ``kv_layers`` layers and no window that every spec was before
    there were groups."""
    kv_layers: int
    kv_heads: int
    d_head: int
    state: tuple = ()
    page_lanes: int = None
    groups: tuple = ()

    def __post_init__(self):
        if self.groups:
            assert sum(g.layers for g in self.groups) == self.kv_layers, \
                "the groups hold {} layers, kv_layers says {}".format(
                    sum(g.layers for g in self.groups), self.kv_layers)
            assert self.page_lanes is None, \
                "pages a decoder lays out itself come in one group"

    @property
    def page_groups(self):
        """The groups, or the one group a spec without them is."""
        return self.groups or (PageGroup(self.kv_layers),)

    @property
    def windowed(self):
        return any(g.window is not None for g in self.groups)

    @property
    def one_table(self):
        """A slot's pages are ONE table's, each the whole of its
        positions' state in every paged layer: no window, one group."""
        return not self.windowed and len(self.groups) <= 1


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
        "models.deepseek_v3.make_deepseek_v3_model, "
        "models.mellum.make_mellum_model, "
        "models.cohere2_moe.make_cohere2_moe_model, "
        "models.olmo_hybrid.make_olmo_hybrid_model, "
        "models.granite_moe_hybrid.make_granite_moe_hybrid_model; its "
        "cache_spec() may put the paged layers in groups)")


# What a serving feature needs of a cache, each need as (what the model
# has that breaks it, why that breaks it, the test of decoder and spec):
# the sentence a refusal says. A cache kind that meets a need in a new
# way changes the test here and nothing in the engine.
_PAGES_HOLD_THE_STATE = (
    "recurrent layers", "its state is not in the pages",
    lambda decoder, spec: getattr(decoder, "recurrent", False))
_ROWS_ARE_KEYS_AND_VALUES = (
    "latent pages", "its page rows are not keys and values",
    lambda decoder, spec: spec.page_lanes is not None)
_ONE_TABLE = (
    "sliding-window layers or several page groups",
    "a page is not the whole of a position's state in every layer",
    lambda decoder, spec: not spec.one_table)
# a slot's pages are the whole of its state: nothing beside them, and
# each the whole of its positions' state in every paged layer
_WHOLE_STATE = (_PAGES_HOLD_THE_STATE, _ONE_TABLE)
# ... and what they hold is keys and values
_WHOLE_STATE_IN_KEYS_AND_VALUES = _WHOLE_STATE + (_ROWS_ARE_KEYS_AND_VALUES,)

# feature -> (what a refusal calls it, what it needs of a cache).
# Latent pages keep prefix sharing: a shared page is shared whatever
# its rows hold.
FEATURES = {
    "prefix_caching": ("prefix caching (inference.prefix_caching)",
                       _WHOLE_STATE),
    "speculative": ("speculative decoding (inference.speculative)",
                    _WHOLE_STATE_IN_KEYS_AND_VALUES),
    "handoff": ("the fleet's page hand-off (inference.fleet)",
                _WHOLE_STATE_IN_KEYS_AND_VALUES),
    # the model drafter keeps the DRAFT model's keys and values in a
    # contiguous cache of its own (inference/speculative.py)
    "draft_cache": ("a draft model's contiguous cache",
                    _WHOLE_STATE_IN_KEYS_AND_VALUES),
}


def refuse(decoder, spec, feature):
    """Raise where ``feature`` (a key of ``FEATURES``) cannot serve the
    model of ``decoder`` and its ``cache_spec()`` ``spec``: the one
    place that says what a cache kind cannot serve."""
    what, needs = FEATURES[feature]
    for model, why, unmet in needs:
        if unmet(decoder, spec):
            raise ValueError("{} cannot serve a model with {}: {}".format(
                what, model, why))
