"""InferenceEngine: cached serving of any model that carries a decoder
(inference/decoder.py): GPT-2, whose layers all keep keys and values,
and Jamba, whose state-space layers keep a per-slot recurrent state
beside the two attention layers' pages. No model module is imported
here, and what a cache kind cannot serve is the decoder module's to
say (``decoder.refuse``).

The serving counterpart of ``runtime/engine.py``'s training engine,
returned by ``deepspeed_tpu.init_inference()``. Jitted hot paths:

  * ``prefill`` — embed one request's prompt (or one CHUNK of it, padded
    to a length bucket so the number of jit traces is bounded by the
    bucket list), write its K/V into the request's pages, sample the
    first token on the final chunk;
  * ``decode_step`` — one token for EVERY slot in a single fused step
    (slots, 1) -> logits -> sample, writing K/V at each slot's live
    length. Inactive slots compute garbage that the scheduler ignores;
    their cache writes are masked/garbage-paged out.
  * ``verify_step`` — speculative decoding: score ``k`` drafted tokens
    per slot in one fused (slots, k+1) pass; the scheduler accepts the
    longest prefix the target agrees with (inference/speculative.py).

One KV layout: a pooled ``(pages, layers, page_size, heads * d_head)``
buffer pair a page group plus host-side page tables
(inference/paging.py) — pages allocate on demand as sequences grow,
shared prompt prefixes map one set of pages into many tables
(copy-on-write), and HBM scales with live tokens instead of ``slots *
max_seq``. With nothing set the pool holds ``slots * max_seq`` tokens.

Tensor parallelism: params are placed via the model's
``partition_spec_fn`` (Megatron column/row layout) and the page pools
shard their packed heads (kv_cache.PAGED_KV_CACHE_SPEC), so XLA runs
decode with each model shard attending over exactly the heads it owns.
"""
import numpy as np

import jax
import jax.numpy as jnp

from ..runtime.executor.jit import (first_call, first_call_over,
                                    jit_program)
from ..utils.annotate import (annotate, engine_tag, setup_span,
                              startup_line, startup_report)
from ..utils.compile_cache import program_scopes
from ..utils.logging import logger
from .config import DeepSpeedInferenceConfig
from .decoder import decoder_of, refuse
from .kv_cache import PagedKVCache, StatePool, write_path
from .paging import (GARBAGE_PAGE, GroupPages, PagePoolExhausted,
                     PrefixCache)
from .sampling import make_sampler

_UNSET = object()    # "argument not given" (None means "no EOS token")


def _parse_configs(config, mesh=None):
    """-> (inference_config, telemetry_config-or-None,
    analysis_config-or-None, runtime_cfg). One ds_config drives both
    training and serving; the serving engine reads its own section
    plus the shared telemetry/analysis sections and the ``runtime``
    executor gates (the scheduler step runs as a segment plan on the
    same PlanExecutor machinery the training engine uses)."""
    from ..runtime.config import (RUNTIME_EXECUTOR_DEFAULT,
                                  get_runtime_executor_rewrites)
    default_runtime = {"executor": RUNTIME_EXECUTOR_DEFAULT,
                       "executor_rewrites":
                       get_runtime_executor_rewrites({})}
    if isinstance(config, DeepSpeedInferenceConfig):
        return config, None, None, default_runtime
    from ..runtime.config import DeepSpeedConfig
    if isinstance(config, DeepSpeedConfig):
        return (config.inference_config, config.telemetry_config,
                config.analysis_config,
                {"executor": config.runtime_executor,
                 "executor_rewrites": config.runtime_executor_rewrites})
    if config is None:
        return DeepSpeedInferenceConfig({}), None, None, default_runtime
    if isinstance(config, dict):
        full = DeepSpeedConfig(None, param_dict=config, mesh=mesh,
                               inference_only=True)
    else:
        full = DeepSpeedConfig(config, mesh=mesh, inference_only=True)
    return (full.inference_config, full.telemetry_config,
            full.analysis_config,
            {"executor": full.runtime_executor,
             "executor_rewrites": full.runtime_executor_rewrites})


class InferenceEngine:
    """Incremental-decode engine over a ``runtime.model.Model`` that
    carries a decoder (``make_gpt2_model`` and ``make_jamba_model``
    attach one; inference/decoder.py says what it gives). Prompt/token
    values are plain ints; all device state (params, KV cache,
    recurrent state) lives on ``mesh`` when one is given."""

    def __init__(self, model, config=None, mesh=None, dtype=None, seed=0,
                 draft_model=None):
        from ..runtime.model import as_model
        # this engine's rows of the start-up record (docs/telemetry.md,
        # "Start-up record") carry it; ``launches`` is their ``step``;
        # ``_first_calls``: the rows of programs made and not yet run
        self.startup_tag = engine_tag("inference")
        self.launches = 0
        self._first_calls = []
        self._first_operands = {}  # id(program) -> its first call's
        self.module = as_model(model)
        self.decoder = decoder_of(model, self.module)
        model_config = self.decoder.config
        # counters the decoder's serving programs return beside their
        # tokens (inference/decoder.py), by name; what they count is
        # the decoder's business
        self.counter_names = tuple(getattr(self.decoder, "counters", ()))
        self.last_counters = {}    # name -> attributes of the last launch
        self.inference_config, telemetry_config, analysis_config, \
            runtime_cfg = _parse_configs(config, mesh=mesh)
        # segment-plan executor (runtime/executor/, docs/executor.md):
        # the continuous-batching scheduler step runs as a SegmentPlan;
        # runtime.executor "off" = serial oracle, else overlap mode
        self._executor_mode = "serial" \
            if runtime_cfg["executor"] == "off" else "overlap"
        self._executor_rewrites = runtime_cfg["executor_rewrites"]
        self._plan_executor = None
        if analysis_config is None:
            from ..analysis.config import DeepSpeedAnalysisConfig
            analysis_config = DeepSpeedAnalysisConfig({})
        self.analysis_config = analysis_config
        # concurrency sanitizer (docs/concurrency.md): installed before
        # the telemetry subsystems so their locks come out instrumented
        # (process-global; a training engine may already own it)
        if analysis_config.concurrency_enabled:
            from ..analysis.concurrency import locksan
            if locksan.current() is None:
                locksan.install(locksan.LockSanitizer(
                    stack_depth=analysis_config.concurrency_stack_depth))
        # dtype override is engine-local state: the config object may be
        # shared with other engines (or the training engine) and must not
        # be mutated
        if dtype is not None:
            name = dtype if isinstance(dtype, str) else np.dtype(dtype).name
            parsed = DeepSpeedInferenceConfig({"inference": {"dtype": name}})
            self.dtype, self.dtype_name = parsed.dtype, parsed.dtype_name
        else:
            self.dtype = self.inference_config.dtype
            self.dtype_name = self.inference_config.dtype_name
        self.mesh = mesh

        # the config the serving programs close over (the decoder's
        # deterministic, dense variant; it refuses a mesh it cannot span)
        self.model_config = self.decoder.serving_config(mesh)

        ic = self.inference_config
        self.max_seq_len = ic.max_seq_len or model_config.max_seq_len
        assert self.max_seq_len <= model_config.max_seq_len, \
            "inference.max_seq_len {} exceeds the model's positional " \
            "table {}".format(self.max_seq_len, model_config.max_seq_len)
        self.num_slots = ic.max_batch_size
        self.prefill_buckets = ic.resolve_buckets(self.max_seq_len)

        with setup_span("setup.params", engine=self.startup_tag) as attrs:
            self.params = self._place_params(
                self.decoder.serving_params(self.module.params,
                                            self.dtype))
            leaves = jax.tree_util.tree_leaves(self.params)
            attrs.update(leaves=len(leaves),
                         bytes=sum(int(x.nbytes) for x in leaves))

        # ------------------------------------------------------ KV cache
        spec = self.decoder.cache_spec()
        self.page_size = ic.kv_block_size
        # what this cache kind cannot serve (inference/decoder.py)
        for feature, on in (("prefix_caching", ic.prefix_caching),
                            ("speculative", ic.spec_enabled),
                            ("handoff", ic.fleet_role is not None)):
            if on:
                refuse(self.decoder, spec, feature)
        with setup_span("setup.cache", engine=self.startup_tag) as attrs:
            # per-slot recurrent state, a pool of its own beside the pages
            # (None for a model whose pages are its whole state)
            self.state = StatePool.allocate(spec.state, self.num_slots) \
                if spec.state else None
            # a decoder that declares groups is handed a table a group
            # and each table's base; any other the one table, as ever
            self._grouped = bool(spec.groups)
            self.max_pages = -(-self.max_seq_len // self.page_size)
            # a pool pair, an allocator and a table a slot for each
            # group; GARBAGE_PAGE everywhere a slot has no allocation
            # (jit writes there are redirected and reads
            # position-masked)
            self.kv_groups, self.page_groups = [], []
            for g, group in enumerate(spec.page_groups):
                num_pages = self._group_num_pages(ic, g, group)
                self.kv_groups.append(PagedKVCache.allocate(
                    num_pages, group.layers, spec.kv_heads,
                    self.page_size, spec.d_head, self.dtype, mesh=mesh,
                    lanes=spec.page_lanes))
                self.page_groups.append(GroupPages(
                    num_pages, self.num_slots, self.max_pages,
                    self.page_size, window=group.window,
                    chunk_tokens=self.prefill_buckets[-1]))
            # the FIRST group under the names there were before there
            # were groups (the same objects: the fleet's hand-off and
            # the tests read and write them)
            first = self.page_groups[0]
            self.kv = self.kv_groups[0]
            self.allocator = first.allocator
            self.page_tables, self.page_counts = first.tables, \
                first.counts
            self._windowed = tuple(g for g in self.page_groups
                                   if g.window is not None)
            # what one cached token costs, pad lanes included: a reader
            # of the pool's counters need not know the model
            self.kv_token_bytes = sum(kv.token_bytes
                                      for kv in self.kv_groups)
            # pages matched at admission time per slot, so the first-
            # chunk extension match knows where to resume
            self._admit_matched = {}
            self.prefix_cache = (
                PrefixCache(self.allocator, self.page_size)
                if ic.prefix_caching else None)
            attrs["bytes"] = sum(int(kv.nbytes) for kv in self.kv_groups) \
                + (int(self.state.nbytes) if self.state else 0)

        # the paged read paths, resolved once (docs/pallas_kernels.md):
        # decode's, and prefill's where the decoder has one (Mellum)
        with setup_span("setup.kernels", engine=self.startup_tag) as attrs:
            self.paged_attention_kernel = \
                self._resolve_paged_attention_kernel()
            attrs["prefill_attn"] = self.prefill_attention_kernel = getattr(
                self._prefill_config(), "paged_attention_kernel", "xla")

        # host mirror of each slot's live length (tokens whose K/V are in
        # the cache); the scheduler owns slot assignment on top of this
        self.lengths = np.zeros((self.num_slots,), np.int32)

        # ------------------------------------ disaggregated-fleet state
        # role label for serving telemetry (None = monolith; fleet roles
        # stamp "prefill"/"decode" on every serving_step record)
        self.serving_role = ic.fleet_role
        # multi-tenant LoRA-style adapters (inference/fleet/adapters.py):
        # a readout-only logits delta per slot, so ONE page pool serves
        # every tenant. adapter id 0 is the all-zero base (byte-identical
        # to the adapter-free program on the same inputs).
        self.adapters = None
        self._adapter_stack = None
        self.slot_adapters = np.zeros((self.num_slots,), np.int32)

        # ------------------------------------------- speculative decoding
        self.drafter = None
        self.spec_k = 0
        if ic.spec_enabled:
            self.spec_k = ic.spec_num_draft_tokens
            if ic.spec_method == "model":
                from .speculative import ModelDrafter
                assert draft_model is not None, \
                    "inference.speculative.method 'model' needs " \
                    "init_inference(..., draft_model=<small gpt2 Model>)"
                with setup_span("setup.cache",
                                engine=self.startup_tag) as attrs:
                    self.drafter = ModelDrafter(
                        draft_model, self.num_slots, self.max_seq_len,
                        self.dtype, mesh=mesh, engine=self)
                    attrs["bytes"] = int(self.drafter.kv.nbytes)
            else:
                from .speculative import NGramDrafter
                self.drafter = NGramDrafter(ic.spec_ngram_max,
                                            ic.spec_ngram_min)

        self._rng = jax.random.PRNGKey(seed)
        self._prefill_fns = {}     # (bucket, greedy, top_k) -> jit fn
        self._decode_fns = {}      # (width, greedy, top_k) -> jit fn
        self._page_copy_fn = None
        self.compile_stats = {"prefill_traces": 0, "decode_traces": 0}

        # serving telemetry (docs/telemetry.md): the continuous-batching
        # scheduler emits one serving_step record per decode step through
        # the same sink layer the training engine writes; None = off
        from ..telemetry import TelemetryCollector
        # engine-lifetime serving record index + counters: generate()
        # builds a fresh scheduler per call but all records append to ONE
        # telemetry.jsonl, so `step` must keep counting across calls for
        # the join-on-step contract (docs/telemetry.md) — and the metrics
        # the records embed must be cumulative over the same lifetime, or
        # per-step deltas go negative at every generate() boundary
        self.serving_record_steps = 0
        from ..utils.monitor import ServingMetrics
        self.serving_metrics = ServingMetrics()
        self.telemetry = TelemetryCollector.from_section(
            telemetry_config, job_name="serve",
            enabled=jax.process_index() == 0)
        if self.telemetry is not None and \
                self.telemetry.recorder is not None:
            # flight recorder context (docs/diagnostics.md): page-pool /
            # allocator / compile state, resolved at dump time
            self.telemetry.recorder.set_context(
                "ds_config", lambda: vars(self.inference_config))
            self.telemetry.recorder.set_context(
                "engine", self._flight_state)
        logger.info(
            "InferenceEngine: slots={} max_seq={} buckets={} dtype={} "
            "kv_cache={:.1f} MB state_pool={:.1f} MB pages={}x{} "
            "paged_attn={} prefill_attn={} token_bytes={}{}".format(
                self.num_slots, self.max_seq_len, self.prefill_buckets,
                self.dtype_name,
                sum(kv.nbytes for kv in self.kv_groups) / 2 ** 20,
                self.state.nbytes / 2 ** 20 if self.state else 0.0,
                "+".join("{}{}".format(
                    g.allocator.num_pages,
                    "" if g.window is None else "(window {}, table "
                    "{})".format(g.window, g.max_pages))
                    for g in self.page_groups), self.page_size,
                self.paged_attention_kernel,
                self.prefill_attention_kernel, self.kv_token_bytes,
                " spec_k={} drafter={}".format(
                    self.spec_k, type(self.drafter).__name__)
                if self.drafter is not None else ""))

    def _group_num_pages(self, ic, g, group):
        """The pool size of page group ``g``: ``inference.num_pages``
        (its entry ``g`` where that is a list). A windowed group that
        the config gives no count gets what every slot's promise and
        the one chunk beyond them need (paging.GroupPages); one that it
        does must hold a chunk's pages at the least."""
        if group.window is None:
            return ic.resolve_num_pages(self.num_slots, self.max_seq_len,
                                        group=g)
        steady, width = GroupPages.spans(
            group.window, self.page_size, self.prefill_buckets[-1],
            self.max_pages)
        if isinstance(ic.num_pages, list):
            return ic.resolve_num_pages(self.num_slots,
                                        width * self.page_size, group=g)
        return self.num_slots * steady + width - steady

    def startup_report(self):
        """This engine's rows of the start-up record (docs/telemetry.md,
        "Start-up record"): its ``setup.engine`` and phases, and one
        ``setup.program`` row for each program that has run."""
        if self._first_calls:
            self.wait()
            self._first_calls_over()
        return startup_report(self.startup_tag)

    def startup_line(self):
        return startup_line(self.startup_tag)

    def program_scopes(self):
        """Which scope each instruction of this engine's compiled
        programs was traced under, one entry a program that has run
        (docs/telemetry.md, "Device scopes"). Lowers and compiles (or
        loads) each once more: seconds a program, for after a trace
        window and not inside one; an error inside a step."""
        if self._first_calls:
            raise RuntimeError("program_scopes() inside a step: a "
                               "program's first call is not over")
        self.wait()
        return program_scopes(self.startup_tag)

    def telemetry_snapshot(self):
        """Rolling serving aggregate (occupancy/queue-depth p50/p95,
        token rates) — ``{}`` when telemetry is disabled."""
        return self.telemetry.snapshot() if self.telemetry is not None \
            else {}

    # -------------------------------------------------------- diagnostics
    def _flight_state(self):
        """Serving-engine snapshot for crash bundles (resolved at dump
        time): slot lengths, page-pool/allocator occupancy, prefix-cache
        stats, and the prefill/decode trace counts."""
        state = {
            "role": "serve",
            "num_slots": self.num_slots,
            "max_seq_len": self.max_seq_len,
            "lengths": [int(n) for n in self.lengths],
            "compile_stats": dict(self.compile_stats),
            "serving_record_steps": self.serving_record_steps,
            "page_pool": self.page_pool_stats(),
            "prefix": self.prefix_stats(),
            "page_counts": [int(n) for n in self.page_counts],
        }
        if self._grouped:
            state["page_groups"] = [g.stats() for g in self.page_groups]
        return state

    def debug_dump(self, reason="debug_dump"):
        """Write a flight-recorder crash bundle on demand; returns the
        bundle path, or None (loudly) when the recorder is off."""
        if self.telemetry is None or self.telemetry.recorder is None:
            logger.warning(
                "debug_dump: telemetry.flight_recorder is not enabled — "
                "no bundle written (add the flight_recorder section to "
                "the telemetry config)")
            return None
        return self.telemetry.recorder.dump(reason)

    def audit(self, hlo=None, report_path=None, strict=None):
        """Ahead-of-time shard-lint (docs/analysis.md) over the serving
        programs — every prefill bucket, the fused decode and the
        speculative verify pass — from their ShapeDtypeStructs: KV
        donation audit, replicated-leaf/sharding drift, fp32 upcasts,
        host callbacks, and the AOT recompile-storm bound on the bucket
        list. ``init_inference(..., audit=True)`` runs this at engine
        build. Findings warn (raise under ``analysis.strict``; the
        ``strict`` argument overrides); returns the AnalysisReport."""
        from ..analysis import audit_engine
        return audit_engine(self, hlo=hlo, report_path=report_path,
                            strict=strict)

    def _resolve_paged_attention_kernel(self):
        """``inference.paged_attention_kernel`` tri-state -> the paged
        read path ("pallas" | "xla") of the decode family, and of the
        prefill family where the decoder has one (``_prefill_config``).
        On a mesh the walk is shard_mapped, heads over ``model``."""
        key = self.inference_config.paged_attention_kernel
        if key != "auto":
            return key
        # "auto": the kernel earns its keep on TPU; off-TPU the
        # interpreter is a numerics-pinning vehicle, not a fast path
        # (ops/pallas/common.py owns the one backend predicate)
        from ..ops.pallas.common import default_interpret
        return "xla" if default_interpret() else "pallas"

    # ---------------------------------------------------------- placement

    def _place_params(self, params):
        if self.mesh is not None and \
                self.module.partition_spec_fn is not None:
            from ..runtime.zero.partition import ZeroShardingPlan
            plan = ZeroShardingPlan(
                self.mesh, stage=0,
                model_spec_fn=self.module.partition_spec_fn)
            shardings = plan.tree_shardings(params, "param")
            params = jax.tree_util.tree_map(jax.device_put, params,
                                            shardings)
        return params

    # ---------------------------------------------- multi-tenant adapters

    def attach_adapters(self, adapter_set):
        """Attach an :class:`inference.fleet.adapters.AdapterSet`: every
        slot gains a per-request LoRA-style logits delta served from the
        shared page pool (the KV path is adapter-independent — only the
        readout changes). Switches the engine onto the adapter-aware
        program family; slots default to adapter 0 (the all-zero base,
        byte-identical to the adapter-free programs)."""
        assert adapter_set.d_model == self.model_config.d_model, \
            "adapter d_model {} != model d_model {}".format(
                adapter_set.d_model, self.model_config.d_model)
        assert adapter_set.vocab_size == self.model_config.vocab_size, \
            "adapter vocab {} != model vocab {}".format(
                adapter_set.vocab_size, self.model_config.vocab_size)
        self.adapters = adapter_set
        self._adapter_stack = adapter_set.stacked(dtype=self.dtype,
                                                  mesh=self.mesh)
        self.slot_adapters[:] = 0

    def assign_adapter(self, slot, adapter_id):
        """Pin ``slot``'s requests to one tenant's adapter (0 = base)."""
        assert self.adapters is not None, \
            "assign_adapter before attach_adapters"
        assert 0 <= adapter_id < len(self.adapters), \
            "adapter id {} out of range [0, {})".format(
                adapter_id, len(self.adapters))
        self.slot_adapters[slot] = adapter_id

    def _prefix_namespace(self, slot):
        """Prefix-cache namespace for ``slot``: tenants never cross-hit
        each other's cached prompt pages (the pages hold adapter-
        independent K/V, but a cross-tenant hit would leak prompt
        CONTENT between tenants through timing). Base traffic (adapter
        0, or no adapters attached) keeps the unnamespaced chain."""
        if self.adapters is None:
            return None
        aid = int(self.slot_adapters[slot])
        return aid if aid else None

    # ----------------------------------------------------------- jit fns

    def _sampling_key(self, sampling):
        ic = self.inference_config
        s = sampling or {}
        greedy = bool(s.get("greedy", ic.greedy))
        # greedy ignores top_k: normalize it out of the jit cache key so a
        # sampling override can't recompile an identical argmax program.
        # Clamp to the vocab — lax.top_k(k > vocab) is an opaque trace
        # error, and k == vocab is already "no filtering".
        top_k = 0 if greedy else min(int(s.get("top_k", ic.top_k)),
                                     self.model_config.vocab_size)
        temperature = float(s.get("temperature", ic.temperature))
        top_p = float(s.get("top_p", ic.top_p))
        return greedy, top_k, temperature, top_p

    def _state_buffers(self):
        return self.state.buffers() if self.state is not None else ()

    def _pools(self):
        """The cache's page pools, group after group."""
        return sum((kv.buffers() for kv in self.kv_groups), ())

    def _after_eviction(self, take, group, slot, upto_tokens):
        """``take(slot, upto_tokens)`` (a group's ``admit`` or ``grow``,
        which just found its pool short) once more, after the prefix
        cache gave up what the slot lacks."""
        if self.prefix_cache is None:
            return False
        self.prefix_cache.evict(group.shortfall(slot, upto_tokens))
        return take(slot, upto_tokens)

    def wait(self):
        """Block until every serving program launched so far is over:
        each returns the cache in place of the buffers it was given, so
        the cache is ready when the program is. Sends the device
        nothing, and returns in microseconds once the last launch's
        tokens are on the host (the scheduler's timers fence on this)."""
        buffers = self._pools() + self._state_buffers()
        if self.drafter is not None and self.drafter.needs_model:
            buffers += self.drafter.kv.buffers()
        for buffer in buffers:
            # the array's own method: jax.block_until_ready flattens a
            # tree first, which costs more than the wait (two a launch)
            buffer.block_until_ready()

    def _update_cache(self, buffers):
        """What a program returned in place of its donated buffers: the
        page pools, then the recurrent state arrays."""
        at = 0
        for kv in self.kv_groups:
            n = len(kv.buffers())
            kv.update(tuple(buffers[at:at + n]))
            at += n
        if self.state is not None:
            self.state.update(tuple(buffers[at:]))

    def _launch(self, fn, args):
        """Run a serving program on the cache it donates. What it
        returns is the cache back in place, the chosen tokens, the
        decoder's counters (``counter_names``) and the logits. ->
        (tokens, counters), still on the device."""
        pools = self._pools()
        if self._first_calls:
            # made and not called yet: what its first call is given
            # (docs/telemetry.md, "Device scopes")
            self._first_operands[id(fn)] = (self.params, *pools, *args)
        out = fn(self.params, *pools, *args)
        n_cache = len(pools) + len(self._state_buffers())
        self._update_cache(out[:n_cache])
        return out[n_cache], tuple(out[n_cache + 1:-1])

    def _note_counters(self, values):
        """One launch's counters, fetched with its tokens: each becomes
        a span of its name in the profiler's trace, with the attributes
        the decoder makes of it, and ``last_counters`` for the
        scheduler's metrics."""
        self.launches += 1
        if self._first_calls:
            # the tokens are on the host: a program this launch ran for
            # the first time has run (docs/telemetry.md, "Start-up
            # record"; nothing wraps the call itself)
            self._first_calls_over()
        self.last_counters = {
            name: self.decoder.counter_attrs(name, value)
            for name, value in zip(self.counter_names, values)}
        for name, attrs in self.last_counters.items():
            with annotate(name, **attrs):
                pass

    def _kv_write_attr(self, tokens):
        """The ``kv_write`` attribute of a dispatch span: how the program
        of ``tokens`` new tokens a slot writes them into the page pools
        (``kv_cache.write_path``, the branch its trace took)."""
        return {"kv_write": write_path(tokens, self.page_size)}

    def _get_prefill_fn(self, bucket, greedy, top_k):
        # attached adapters: an extended family (extra readout operands)
        key = (bucket, greedy, top_k, "adapters") \
            if self.adapters is not None else (bucket, greedy, top_k)
        fn = self._prefill_fns.get(key)
        if fn is not None:
            return fn
        cfg = self._prefill_config()
        forward, head = self.decoder.forward_hidden, self.decoder.logits
        sampler = make_sampler(greedy, top_k)
        ps = self.page_size
        n_kv, n_state = len(self._pools()), len(self._state_buffers())
        counted = {"counters": True} if self.counter_names else {}
        grouped = self._grouped

        def prefill(params, *rest):
            # rest: the paged pools ((k, v), or the one pool a decoder
            # lays out itself); the recurrent state arrays (none for a
            # model without) and, with them, slot (scalar int32: whose
            # state); then ids (1, bucket); page_row (max_pages,);
            # start/length scalar int32 — the chunk covers positions
            # [start, start+length); padded tokens are masked out of
            # the cache write (kv_cache.write_tokens) and leave a
            # recurrent state as it was (a decoder with page groups:
            # page_row is (a row a group, each row's base)); rng,
            # temperature, top_p; adapter args (when attached):
            # (a_stack (n,r,d), b_stack (n,V,r), adapter_id scalar) — a
            # per-tenant logits delta; the cache writes are
            # adapter-independent.
            pools, rest = rest[:n_kv], rest[n_kv:]
            state, rest = rest[:n_state], rest[n_state:]
            kwargs = dict(counted)
            if n_state:
                kwargs["state_slot"], rest = rest[0], rest[1:]
            ids, page_row, start, length, rng, temperature, top_p, \
                *adapter_args = rest
            if grouped:
                rows, bases = page_row
                kwargs["page_bases"] = tuple(b[None] for b in bases)
                tables = tuple(row[None] for row in rows)
            else:
                tables = page_row[None]
            hidden, cache, *counters = forward(
                params, ids, cfg, cache=pools + state,
                positions=start[None], page_tables=tables,
                valid_lens=length[None], page_size=ps, **kwargs)
            last = jnp.take(hidden[0], length - 1, axis=0)     # (d,)
            logits = head(params, last[None])                  # (1, V)
            if adapter_args:
                a_stack, b_stack, aid = adapter_args
                logits = logits + \
                    (b_stack[aid] @ (a_stack[aid] @ last))[None]
            token = sampler(logits, rng, temperature, top_p)[0]
            return (*cache, token, *sum(counters, ()), logits[0])

        # the function's name is the program's in a profiler trace
        # (module `jit_prefill`): a contract, pinned by a test. Every
        # cache buffer is donated and comes back in place
        return self._new_program(
            self._prefill_fns, key, "prefill",
            jit_program(prefill,
                        donate=tuple(range(1, 1 + n_kv + n_state))))

    def _new_program(self, cache, key, family, fn):
        """The one intake of a serving program just made: into its
        cache so that its first call writes the ``setup.program`` row
        (docs/telemetry.md, "Start-up record"), into ``compile_stats``,
        and into the compile observatory, where every new trace is a
        distinct program and an unbounded bucket list shows up as a
        recompile storm."""
        self.compile_stats[family + "_traces"] += 1
        if self.telemetry is not None:
            self.telemetry.programs.observe_trace(family, key)
        program = "verify" if family == "decode" and key[0] > 1 else family
        cache[key] = fn
        self._first_calls.append(first_call(
            fn, program, key, self.startup_tag, self.launches))
        return fn

    def _first_calls_over(self, discard=False):
        for opened in self._first_calls:
            first_call_over(opened, discard=discard,
                            operands=self._first_operands.pop(
                                id(opened[-1]), None))
        self._first_calls = []
        if not discard:
            self._first_operands.clear()

    def _get_decode_fn(self, greedy, top_k, width=1):
        """The fused all-slot decode program: ``width`` new tokens per
        slot (1 = plain decode; k+1 = the speculative verify pass —
        one program family serves both)."""
        key = (width, greedy, top_k, "adapters") \
            if self.adapters is not None else (width, greedy, top_k)
        fn = self._decode_fns.get(key)
        if fn is not None:
            return fn
        # the decode family's read path (docs/pallas_kernels.md dispatch
        # rules): under "pallas" every paged decoder walks its pages in
        # a kernel; self.model_config keeps the XLA path, the oracle of
        # every comparison (prefill's variant: _prefill_config)
        cfg = self.decoder.decode_config(self.model_config,
                                         self.paged_attention_kernel)
        forward, head = self.decoder.forward_hidden, self.decoder.logits
        sampler = make_sampler(greedy, top_k)
        ps = self.page_size
        n_kv, n_state = len(self._pools()), len(self._state_buffers())
        counted = {"counters": True} if self.counter_names else {}
        grouped = self._grouped

        def _adapter_delta(hidden, a_stack, b_stack, adapter_ids):
            # per-slot LoRA readout: gather each slot's (A, B) pair and
            # add its low-rank logits delta. adapter_ids (slots,) int32;
            # hidden (slots, width, d).
            a = a_stack[adapter_ids]                   # (slots, r, d)
            h = jnp.einsum("swd,srd->swr", hidden, a)  # (slots, width, r)
            return jnp.einsum("swr,svr->swv", h,
                              b_stack[adapter_ids])    # (slots, width, V)

        def decode(params, *rest):
            # rest: the paged pools; the recurrent state arrays and,
            # with them, advance (slots,) bool (the slots whose state
            # this step advances); then tokens (slots, width); lengths
            # (slots,) int32; page_tables (a decoder with page groups:
            # (a table a group, each table's bases)); rng, temperature,
            # top_p; adapter args
            pools, rest = rest[:n_kv], rest[n_kv:]
            state, rest = rest[:n_state], rest[n_state:]
            kwargs = dict(counted)
            if n_state:
                kwargs["state_advance"], rest = rest[0], rest[1:]
            tokens, lengths, page_tables, rng, temperature, top_p, \
                *adapter_args = rest
            if grouped:
                page_tables, kwargs["page_bases"] = page_tables
            hidden, cache, *counters = forward(
                params, tokens, cfg, cache=pools + state,
                positions=lengths, page_tables=page_tables,
                valid_lens=jnp.full_like(lengths, tokens.shape[1]),
                page_size=ps, **kwargs)
            logits = head(params, hidden)
            if adapter_args:
                logits = logits + _adapter_delta(hidden, *adapter_args)
            flat = logits.reshape(-1, logits.shape[-1])
            chosen = sampler(flat, rng, temperature,
                             top_p).reshape(tokens.shape)
            return (*cache, chosen, *sum(counters, ()), logits)

        # the function's name is the program's in a profiler trace:
        # module `jit_decode`, and its Mosaic call `%decode.N`, by which
        # the benchmark finds the paged kernel. Pinned by a test
        return self._new_program(
            self._decode_fns, key, "decode",
            jit_program(decode,
                        donate=tuple(range(1, 1 + n_kv + n_state))))

    def _next_rng(self, greedy):
        """The key a launch hands its sampler. A greedy program never
        reads it, so it gets the stored key as it is (same type and
        shape: the same program) and the device runs no split."""
        if greedy:
            return self._rng
        self._rng, key = jax.random.split(self._rng)
        return key

    # --------------------------------------------------- paged host state

    def pages_for(self, n_tokens):
        return -(-n_tokens // self.page_size)

    def plan_executor(self):
        """The serving engine's PlanExecutor (the training engine's
        twin seam): the continuous-batching scheduler runs each step
        as an admit -> prefill -> decode -> retire segment plan
        (runtime/executor/serving.py)."""
        if self._plan_executor is None:
            from ..runtime.executor import PlanExecutor
            self._plan_executor = PlanExecutor(
                mode=self._executor_mode,
                rewrites=self._executor_rewrites
                if self._executor_rewrites.get("enabled") else None)
        return self._plan_executor

    def executor_snapshot(self):
        """Engine-lifetime executor counters (bench extra.executor),
        mirroring the training engine's seam."""
        if self._plan_executor is None:
            return {"mode": self._executor_mode, "plans_executed": 0,
                    "segments_executed": 0, "last_plan_segments": 0}
        return self._plan_executor.lifetime_snapshot()

    def page_pool_stats(self):
        """``{num_pages, pages_in_use, occupancy}`` of the first page
        group, and for a decoder with page groups ``groups``: the same
        of each, a windowed one's table width, promises and the pages
        it gave back as they slid out."""
        stats = self.allocator.stats()
        if self._grouped:
            stats["groups"] = [g.stats() for g in self.page_groups]
        return stats

    def group_page_counts(self, slots):
        """-> (pages the ``slots`` hold in each group, pages each group
        gave back as they slid out so far): what a step's span and the
        serving metrics say of a decoder with page groups."""
        return ([int(g.counts[slots].sum()) for g in self.page_groups],
                [g.freed for g in self.page_groups])

    def prefix_stats(self):
        return self.prefix_cache.stats() if self.prefix_cache is not None \
            else None

    def try_admit(self, slot, context):
        """Paged admission: match the prompt against the prefix cache
        FIRST (mapping shared pages into this slot's table, refcounted)
        and allocate fresh pages only for the unmatched suffix — under
        pool pressure a second user of a 100-page system prompt needs
        ~its private pages free, not the whole prompt's worth, and the
        eviction ladder never has to eat the very entries the request
        is about to use. Returns True, or False when the pool cannot
        hold the suffix — the caller keeps the request queued. A second
        match pass runs at first-chunk time (:meth:`match_prefix`) to
        pick up pages a same-step burst sibling registers between
        admission and prefill."""
        n = len(context)
        first, matched = self.page_groups[0], []
        if self.prefix_cache is not None:
            # cap the match below the full prompt: the first sampled
            # token's logits must come from at least one real forward
            matched, _ = self.prefix_cache.match(
                context, n - 1, namespace=self._prefix_namespace(slot))
            # the shared pages lead the slot's row, admit takes the rest
            first.tables[slot, :len(matched)] = matched
            first.counts[slot] = len(matched)
        # every group has room, or none is touched: a group without a
        # window takes the context's pages, a windowed one the promise
        # of a decode step's (paging.GroupPages.admit)
        for i, group in enumerate(self.page_groups):
            if group.admit(slot, n) or self._after_eviction(
                    group.admit, group, slot, n):
                continue
            if matched:
                # refs AND stats roll back: a queued request retrying
                # admission every step must not inflate the hit gauges
                first.tables[slot, :len(matched)] = GARBAGE_PAGE
                first.counts[slot] = 0
                self.prefix_cache.unmatch(matched)
            for taken in self.page_groups[:i]:
                taken.release(slot)
            return False
        self._admit_matched[slot] = len(matched)
        return True

    def match_prefix(self, slot, context):
        """Second match phase, at first-chunk time: extend the
        admission match with pages a same-step burst sibling registered
        in between (the burst's first member prefills and registers one
        loop iteration before its siblings' first chunks). Newly
        matched shared pages replace the slot's freshly-allocated ones,
        which return to the pool. Returns the TOTAL number of leading
        tokens already resident (the prefill start offset)."""
        if self.prefix_cache is None:
            return 0
        have = int(self._admit_matched.get(slot, 0))
        extra, _ = self.prefix_cache.match(
            context, len(context) - 1, skip_pages=have,
            count_lookup=False, namespace=self._prefix_namespace(slot))
        row = self.page_tables[slot]
        for j, page in enumerate(extra, start=have):
            self.allocator.free(int(row[j]))
            row[j] = page
        return (have + len(extra)) * self.page_size

    def ensure_pages(self, slot, upto_tokens):
        """Every group's pages for ``slot``'s positions below
        ``upto_tokens``, a windowed group first giving back what no
        query at the slot's length or later can see. False when a pool
        is exhausted (after trying prefix-cache eviction): the
        scheduler preempts; the other groups keep what they took (the
        slot uses or frees it)."""
        for group in self._windowed:
            group.slide(slot, int(self.lengths[slot]))
        ok = True
        for group in self.page_groups:
            ok = (group.grow(slot, upto_tokens) or self._after_eviction(
                group.grow, group, slot, upto_tokens)) and ok
        return ok

    def register_prefix(self, slot, context):
        """Record the prompt's FULL pages in the prefix cache once its
        prefill completed (the cache takes its own refs; retiring this
        sequence won't free them)."""
        if self.prefix_cache is None:
            return
        full = len(context) // self.page_size
        if full:
            self.prefix_cache.register(
                context, self.page_tables[slot, :full].tolist(),
                namespace=self._prefix_namespace(slot))

    def _page_copy(self, src, dst):
        pools = self.kv.buffers()
        if self._page_copy_fn is None:
            def copy(src, dst, *pools):
                return tuple(p.at[dst].set(p[src]) for p in pools)
            self._page_copy_fn = jit_program(
                copy, donate=tuple(range(2, 2 + len(pools))))
            self._first_calls.append(first_call(
                self._page_copy_fn, "page_copy", len(pools),
                self.startup_tag, self.launches))
        self.kv.update(self._page_copy_fn(jnp.int32(src), jnp.int32(dst),
                                          *pools))

    def _cow_writes(self, slot, first_pos, last_pos):
        """Copy-on-write: fork any SHARED page the coming write range
        ``[first_pos, last_pos]`` touches (refcount > 1 means a prefix
        consumer or the prefix cache also maps it). Full-page prefix
        sharing never appends into a shared page, so this is the safety
        net that makes sharing granularity a policy choice rather than
        a correctness constraint."""
        if not self.allocator.shared_pages:
            # no page is held twice (only prefix sharing does that):
            # nothing to fork, and the walk over every slot's pages was
            # 1-2 ms of each decode step at 384 slots
            return
        lo = first_pos // self.page_size
        hi = min(last_pos // self.page_size,
                 int(self.page_counts[slot]) - 1)
        for j in range(lo, hi + 1):
            page = int(self.page_tables[slot, j])
            if page != GARBAGE_PAGE and self.allocator.refcount(page) > 1:
                new, forked = self.allocator.fork(page)
                if forked:
                    self._page_copy(page, new)
                    self.page_tables[slot, j] = new

    # ------------------------------------------------------------ serving

    def bucket_for(self, length):
        for b in self.prefill_buckets:
            if length <= b:
                return b
        raise ValueError(
            "prompt length {} exceeds the largest prefill bucket {} "
            "(inference.prefill_buckets / max_seq_len)".format(
                length, self.prefill_buckets[-1]))

    def prefill_chunk(self, slot, tokens, start, sampling=None):
        """Embed ``tokens`` (one prompt chunk) into ``slot`` at absolute
        positions ``[start, start+len)`` and return the sampled token
        from the chunk's last position (only meaningful on the FINAL
        chunk — earlier chunks' callers discard it). The slot must
        already hold pages covering the range (``try_admit``)."""
        assert 0 <= slot < self.num_slots
        n = len(tokens)
        assert n >= 1, "empty prefill chunk"
        assert start + n < self.max_seq_len, \
            "chunk end {} leaves no room to decode (max_seq_len " \
            "{})".format(start + n, self.max_seq_len)
        with annotate("engine.prefill.prepare"):
            bucket = self.bucket_for(n)
            greedy, top_k, temperature, top_p = \
                self._sampling_key(sampling)
            fn = self._get_prefill_fn(bucket, greedy, top_k)
            # host values go to the program as numpy (copies, so that
            # the engine's own tables can change while a launch is in
            # flight): the call uploads them together, where a
            # jnp.asarray each was a transfer and a dispatch of its own
            # (3 to 6 ms of a step at 384 slots, PERF.md section 6)
            ids = np.zeros((1, bucket), np.int32)
            ids[0, :n] = np.asarray(tokens, np.int32)
            extra = ()
            if self.adapters is not None:
                a_stack, b_stack = self._adapter_stack
                extra = (a_stack, b_stack,
                         np.int32(self.slot_adapters[slot]))
            if self._grouped:
                # the chunk's keys and, in a windowed group, the window
                # before them
                assert self.lengths[slot] == start, \
                    "a chunk at {} of a slot {} tokens long".format(
                        start, self.lengths[slot])
                if not self.ensure_pages(slot, start + n):
                    raise PagePoolExhausted(
                        "no pages for a prefill chunk of {} tokens at "
                        "{}".format(n, start))
                where = (tuple(g.tables[slot].copy()
                               for g in self.page_groups),
                         tuple(np.int32(g.base[slot] * self.page_size)
                               for g in self.page_groups))
            else:
                self._cow_writes(slot, start, start + n - 1)
                where = self.page_tables[slot].copy()
            state = self._state_buffers()
            if state:
                # the program of a request's first chunk (start 0)
                # zeroes the slot's state itself: no launch of its own
                state += (np.int32(slot),)
            args = state + (
                ids, where, np.int32(start), np.int32(n),
                self._next_rng(greedy), np.float32(temperature),
                np.float32(top_p)) + extra
        with annotate("engine.prefill.dispatch",
                      **self._kv_write_attr(bucket)):
            token, counters = self._launch(fn, args)
            self.lengths[slot] = start + n
            # the launch has its own copy of the tables: what no later
            # query sees goes back now, not at the next chunk
            for group in self._windowed:
                group.slide(slot, start + n)
        with annotate("engine.prefill.fetch"):
            token, counters = jax.device_get((token, counters))
        self._note_counters(counters)
        return int(token)

    def prefill(self, slot, prompt, sampling=None):
        """Single-shot prefill of a whole prompt (the unchunked path:
        admission + one chunk). Returns the first sampled token."""
        n = len(prompt)
        assert n >= 1, "empty prompt"
        assert n < self.max_seq_len, \
            "prompt length {} leaves no room to decode (max_seq_len " \
            "{})".format(n, self.max_seq_len)
        if int(self.page_counts[slot]) < self.pages_for(n):
            assert self.ensure_pages(slot, n), "KV page pool exhausted"
        return self.prefill_chunk(slot, prompt, 0, sampling=sampling)

    def decode_step(self, tokens, sampling=None, active=None):
        """One decode step for ALL slots: ``tokens`` (slots,) or
        (slots, width) are each slot's pending token (+ drafted tokens
        for the speculative verify pass; anything for inactive slots).
        Returns the same-shaped int array of chosen tokens — for
        width=1 the sampled next token per slot; the caller decides
        which slots' results are live and calls :meth:`advance`.
        ``active``: the slots that are decoding (default: all). Keys
        and values written for any other slot are masked or land in
        the garbage page, but a recurrent state has no mask: only the
        active slots' state advances (a slot between two chunks of its
        prompt keeps what the first chunk left)."""
        tokens = np.array(tokens, np.int32)      # a copy: see prefill_chunk
        squeeze = tokens.ndim == 1
        if squeeze:
            tokens = tokens[:, None]
        assert tokens.shape[0] == self.num_slots
        width = tokens.shape[1]
        greedy, top_k, temperature, top_p = self._sampling_key(sampling)
        with annotate("engine.decode.prepare"):
            fn = self._get_decode_fn(greedy, top_k, width=width)
            extra = ()
            if self.adapters is not None:
                a_stack, b_stack = self._adapter_stack
                extra = (a_stack, b_stack,
                         self.slot_adapters.astype(np.int32))
            for slot in range(self.num_slots):
                if self.lengths[slot] > 0:
                    self._cow_writes(
                        slot, int(self.lengths[slot]),
                        int(self.lengths[slot]) + width - 1)
            state = self._state_buffers()
            if state:
                advance = np.ones((self.num_slots,), bool)
                if active is not None:
                    advance[:] = False
                    advance[list(active)] = True
                state += (advance,)
            if self._grouped:
                tables = ((tuple(g.tables.copy() for g in self.page_groups),
                           tuple(g.base * np.int32(self.page_size)
                                 for g in self.page_groups)),)
            else:
                tables = (self.page_tables.copy(),)
            args = state + (tokens, self.lengths.copy()) + tables + (
                self._next_rng(greedy), np.float32(temperature),
                np.float32(top_p)) + extra
        with annotate("engine.decode.dispatch",
                      **self._kv_write_attr(width)):
            chosen, counters = self._launch(fn, args)
        with annotate("engine.decode.fetch"):
            chosen, counters = jax.device_get((chosen, counters))
        self._note_counters(counters)
        return chosen[:, 0] if squeeze else chosen

    def verify_step(self, tokens, sampling=None):
        """Speculative verify: ``tokens`` (slots, k+1) = each slot's
        pending token followed by its k drafts. Returns (slots, k+1)
        ``chosen`` tokens — row i's entry j is the target's choice for
        the position AFTER tokens[i, :j+1]; the scheduler accepts the
        longest prefix with drafts[j] == chosen[j-1]."""
        return self.decode_step(tokens, sampling=sampling)

    def _prefill_config(self):
        """The config the PREFILL family's programs close over:
        ``model_config``, or what the decoder makes of it for the
        resolved read path (decoder.py, ``prefill_config``: a decoder
        whose chunks have a kernel of their own)."""
        make = getattr(self.decoder, "prefill_config", None)
        return self.model_config if make is None else \
            make(self.model_config, self.paged_attention_kernel)

    def advance(self, slot, n=1):
        """Account ``n`` committed cache writes for ``slot`` (its live
        length grew by n: 1 per plain decode step, accepted+1 per
        speculative verify step)."""
        self.lengths[slot] += n

    def can_decode(self, slot):
        return self.lengths[slot] < self.max_seq_len

    def free_slot(self, slot):
        """Retire a slot: release its pages back to the pool (shared
        prefix pages just drop one reference) and zero its length."""
        for group in self.page_groups:
            group.release(slot)
        self._admit_matched.pop(slot, None)
        self.lengths[slot] = 0
        self.slot_adapters[slot] = 0

    def generate(self, prompts, max_new_tokens=None, sampling=None,
                 eos_token_id=_UNSET, metrics=None):
        """Generate completions for ``prompts`` via the continuous-batching
        scheduler. Returns a list of generated-token lists, prompt order.
        ``eos_token_id`` left unset falls through to the config default
        (``inference.eos_token_id``); pass None to disable early stop."""
        from .scheduler import ContinuousBatchingScheduler
        if metrics is None:
            metrics = self.serving_metrics
        sched = ContinuousBatchingScheduler(self, metrics=metrics,
                                            sampling=sampling)
        kwargs = ({} if eos_token_id is _UNSET
                  else {"eos_token_id": eos_token_id})
        uids = [sched.submit(p, max_new_tokens=max_new_tokens, **kwargs)
                for p in prompts]
        results = sched.run()
        return [results[u] for u in uids]
