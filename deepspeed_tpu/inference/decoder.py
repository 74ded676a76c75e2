"""What ``init_inference()`` asks of a model: the decoder protocol.

The serving engine imports no model module. The model handed to it
carries a ``decoder`` (``make_gpt2_model``, ``make_jamba_model``,
``make_lfm2_model``, ``make_deepseek_v3_model`` and ``make_mellum_model``
attach one) with:

* ``config``: ``vocab_size``, ``max_seq_len``, ``d_model``;
* ``cache_spec()`` -> :class:`CacheSpec`: what it keeps. One of three
  kinds, or the first two together: keys and values (``kv_layers``
  layers of ``kv_heads x d_head``, a PAIR of pools, in pages or
  slots); per-slot recurrent ``state`` arrays beside them; or, with
  ``page_lanes``, pages whose rows the decoder lays out itself (latent
  attention: ONE pool of ``kv_layers`` layers, each token's row the
  latent all heads share, padded to whole lanes; paged layout only).
  The paged layers may stand in GROUPS (``CacheSpec.groups``, paged
  layout only): each group has a pool pair, an allocator and a page
  table a slot of its own, and an optional ``window``: a windowed
  group's layers see the last ``window`` keys only, its table slides
  (column 0 is the first page that holds a visible key) and the pages
  that slid out go back to its allocator;
* ``serving_config(mesh)`` -> the model config the serving programs
  close over (deterministic, dense; raises for a mesh it cannot span);
  ``decode_config(config, paged_attention_kernel)`` -> the decode
  program family's variant of it for the engine's resolved read path;
  optionally ``prefill_config(config, paged_attention_kernel)`` -> the
  prefill family's (a decoder whose chunks have a kernel of their own:
  Mellum's ``chunk_attention``); without it prefill closes over
  ``serving_config``'s, the XLA path;
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
        "models.mellum.make_mellum_model; its cache_spec() may put the "
        "paged layers in groups)")


def _refuse(refused, what, model, why):
    """The one sentence of every refusal below."""
    if refused:
        raise ValueError("{} cannot serve a model with {}: {}".format(
            what, model, why))


def refuse_recurrent(engine_or_decoder, what):
    """For every feature that takes the pages for the whole of a
    request's state."""
    decoder = getattr(engine_or_decoder, "decoder", engine_or_decoder)
    _refuse(getattr(decoder, "recurrent", False), what,
            "recurrent layers", "its state is not in the pages")


def refuse_latent(spec, what):
    """For every feature that takes a page for a ``(k, v)`` pair of
    ``kv_heads x d_head`` rows."""
    _refuse(spec.page_lanes is not None, what, "latent pages",
            "its page rows are not keys and values")


def refuse_windowed(spec, what):
    """For every feature that takes a page for the whole of a
    position's state in every layer: a windowed group gives back the
    pages that slid out, and several groups have a table each."""
    _refuse(not spec.one_table, what,
            "sliding-window layers or several page groups",
            "a page is not the whole of a position's state in every layer")
