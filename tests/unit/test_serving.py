"""Production serving: paged KV cache, prefix sharing, speculative decode.

The acceptance spec for ISSUE 7:

  * paged decode matches the uncached dense forward (logits atol 1e-5
    across mixed lengths and page boundaries; token streams equal);
  * the page allocator's refcount/free-on-retire invariants hold,
    including copy-on-write forks of shared prefix pages;
  * greedy speculative decode emits the byte-identical token stream of
    the greedy autoregressive baseline (ngram AND model drafters);
  * stale K/V beyond a sequence's live length can never leak into
    attention (NaN-poison tests);
  * chunked prefill interleaves with the decode batch instead of
    stalling it; pool exhaustion preempts-and-recomputes correctly.
"""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

import deepspeed_tpu as deepspeed
from deepspeed_tpu.inference.paging import (GARBAGE_PAGE, PageAllocator,
                                            PagePoolExhausted, PrefixCache,
                                            plan_chunks)
from deepspeed_tpu.models import gpt2

pytestmark = pytest.mark.serving

TINY = dict(vocab_size=128, max_seq_len=64, n_layers=2, n_heads=2,
            d_model=32, use_flash_attention=False, remat=False)
PS = 8                                   # page size used throughout


def tiny_model(seed=0, **over):
    cfg = gpt2.GPT2Config(**{**TINY, **over})
    return gpt2.make_gpt2_model(config=cfg, seed=seed)


def make_engine(model, **inference):
    inference.setdefault("max_batch_size", 3)
    inference.setdefault("prefill_buckets", [8, 16, 32])
    inference.setdefault("dtype", "fp32")
    inference.setdefault("greedy", True)
    return deepspeed.init_inference(model=model,
                                    config={"inference": inference})


def paged_engine(model, **inference):
    inference.setdefault("kv_block_size", PS)
    return make_engine(model, **inference)


@pytest.fixture(scope="module")
def model():
    return tiny_model()


_DENSE = {}          # id(model) -> (model, its jitted dense forward)


def dense_logits(model, seq):
    """Every position's logits from the uncached forward over the
    whole of ``seq``: the reference, which shares no cache, page table,
    bucket or scheduler with what it judges. One program a model: the
    sequence rides zero-padded to ``max_seq_len`` (causal attention:
    no position sees the pad behind it)."""
    if id(model) not in _DENSE:
        config = model.config
        _DENSE[id(model)] = model, jax.jit(
            lambda params, ids: gpt2.forward_hidden(
                params, ids, config, train=False)[0] @ params["wte"].T)
    ids = np.zeros((1, model.config.max_seq_len), np.int32)
    ids[0, :len(seq)] = seq
    return np.asarray(_DENSE[id(model)][1](model.params,
                                           jnp.asarray(ids)))[:len(seq)]


def greedy_chain(model, prompt, n):
    seq = list(prompt)
    for _ in range(n):
        seq.append(int(dense_logits(model, seq)[-1].argmax()))
    return seq[len(prompt):]


class DenseReference:
    """What the serving tests hold an engine's greedy streams to: the
    dense chain of each prompt. No serving path at all."""

    def __init__(self, model):
        self.model = model

    def generate(self, prompts, max_new_tokens):
        return [greedy_chain(self.model, p, max_new_tokens)
                for p in prompts]

    def prefill(self, prompt):
        """The first token after ``prompt``."""
        return greedy_chain(self.model, prompt, 1)[0]


@pytest.fixture(scope="module")
def oracle(model):
    return DenseReference(model)


# ------------------------------------------------------ paged == dense


def test_paged_decode_logits_match_dense_forward_across_page_boundaries(
        model):
    """Mixed prompt lengths straddling page boundaries (PS-1, PS, PS+5):
    per-step decode LOGITS from the paged engine match the dense
    forward's at the same positions within 1e-5 while sequences cross
    page boundaries as they grow."""
    eng = paged_engine(model)
    rs = np.random.RandomState(3)
    prompts = [rs.randint(0, 128, size=n).tolist()
               for n in (PS - 1, PS, PS + 5)]
    steps = 2 * PS + 3                   # decode across >= 2 boundaries
    fed = rs.randint(0, 128, size=(steps, len(prompts)))
    got = []
    for slot, p in enumerate(prompts):
        eng.prefill(slot, p)
    for step in range(steps):
        for slot in range(len(prompts)):
            assert eng.ensure_pages(slot, int(eng.lengths[slot]) + 1)
        greedy, top_k, t, tp = eng._sampling_key(None)
        fn = eng._get_decode_fn(greedy, top_k)
        k, v, _, step_logits = fn(
            eng.params, eng.kv.k, eng.kv.v,
            jnp.asarray(fed[step, :, None], jnp.int32),
            jnp.asarray(eng.lengths), jnp.asarray(eng.page_tables),
            jax.random.PRNGKey(0), jnp.float32(t), jnp.float32(tp))
        eng.kv.update((k, v))
        got.append(np.asarray(step_logits)[:, 0])
        for slot in range(len(prompts)):
            eng.advance(slot)
    for slot, p in enumerate(prompts):
        eng.free_slot(slot)
        # the token fed at step i sits at position len(p) + i
        want = dense_logits(model, p + fed[:, slot].tolist())[len(p):]
        for step in range(steps):
            np.testing.assert_allclose(
                got[step][slot], want[step], atol=1e-5,
                err_msg="slot {} step {}".format(slot, step))


def test_one_page_a_slot_is_the_contiguous_cache_as_a_case(model, oracle):
    """A second ENGINE's opinion, where one is wanted: ``kv_block_size
    == max_seq_len`` is one page a slot, the contiguous cache's
    numerics as a case of the one path. Same streams as small pages and
    as the reference."""
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (5, 11, 14, 26)]
    one_page = paged_engine(model, kv_block_size=TINY["max_seq_len"])
    assert one_page.max_pages == 1
    out = one_page.generate(prompts, max_new_tokens=12)
    assert out == paged_engine(model).generate(prompts, max_new_tokens=12)
    assert out == oracle.generate(prompts, max_new_tokens=12)


def test_paged_generate_matches_dense_streams(model, oracle):
    rs = np.random.RandomState(0)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (5, 11, 14, 26)]
    eng = paged_engine(model)
    assert eng.generate(prompts, max_new_tokens=12) == \
        oracle.generate(prompts, max_new_tokens=12)
    # free-on-retire: every page back in the pool
    assert eng.allocator.pages_in_use == 0


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32],
                         ids=["bf16", "f32"])
def test_paged_read_gathers_the_rows_own_pages(dtype):
    """The XLA read of the pool, alone: one gather on (page, layer)
    returns the very bits that slicing the layer's whole slab out and
    taking the rows' pages from it did (the read before PR 27) — three
    rows with different tables, garbage-page entries past each live
    window, a page two rows share, and a page that is rewritten and
    handed to another row between two reads."""
    pages, layers, hd = 12, 3, 32
    bits = {2: np.uint16, 4: np.uint32}[jnp.dtype(dtype).itemsize]
    rng = np.random.default_rng(0)
    pool = jnp.asarray(
        rng.standard_normal((pages + 1, layers, PS, hd)), dtype)
    pool = pool.at[GARBAGE_PAGE].set(jnp.nan)        # never attended
    tables = np.array([[3, 7, 1, GARBAGE_PAGE, GARBAGE_PAGE],
                       [5, GARBAGE_PAGE, GARBAGE_PAGE, GARBAGE_PAGE,
                        GARBAGE_PAGE],
                       [9, 3, 11, 2, 4]], np.int32)  # page 3: shared
    read = jax.jit(gpt2._gather_pages, static_argnums=2)

    def same_bits(pool, tables):
        for layer in range(layers):
            old = jnp.take(pool[:, layer], jnp.asarray(tables), axis=0)
            for got in (read(pool, jnp.asarray(tables), layer),
                        gpt2._gather_pages(pool, jnp.asarray(tables),
                                           layer)):
                assert got.shape == (3, 5, PS, hd) and got.dtype == dtype
                np.testing.assert_array_equal(
                    np.asarray(got).view(bits), np.asarray(old).view(bits))

    same_bits(pool, tables)
    # row 1 retires; its page 5 is rewritten and becomes row 0's fourth
    pool = pool.at[5].set(
        jnp.asarray(rng.standard_normal((layers, PS, hd)), dtype))
    tables[1, 0], tables[0, 3] = GARBAGE_PAGE, 5
    same_bits(pool, tables)
    got = gpt2._gather_pages(pool, jnp.asarray(tables), 1)
    np.testing.assert_array_equal(
        np.asarray(got[0, 3]).view(bits), np.asarray(pool[5, 1]).view(bits))


# ------------------------------------------------- allocator invariants


def test_page_allocator_refcounts_and_exhaustion():
    alloc = PageAllocator(4)
    pages = [alloc.alloc() for _ in range(4)]
    assert sorted(pages) == [1, 2, 3, 4]       # page 0 never handed out
    assert alloc.pages_in_use == 4 and not alloc.can_alloc(1)
    with pytest.raises(PagePoolExhausted):
        alloc.alloc()
    alloc.ref(pages[0])                         # share it
    alloc.free(pages[0])
    assert alloc.refcount(pages[0]) == 1        # still held by the sharer
    alloc.free(pages[0])
    assert alloc.refcount(pages[0]) == 0 and alloc.can_alloc(1)
    with pytest.raises(AssertionError, match="double free"):
        alloc.free(pages[0])
    # garbage-page ops are inert / rejected
    alloc.free(GARBAGE_PAGE)                    # no-op
    with pytest.raises(AssertionError):
        alloc.ref(GARBAGE_PAGE)


def test_page_allocator_cow_fork():
    alloc = PageAllocator(4)
    page = alloc.alloc()
    same, forked = alloc.fork(page)
    assert same == page and not forked          # unshared: no fork
    alloc.ref(page)                             # refcount 2 (shared)
    new, forked = alloc.fork(page)
    assert forked and new != page
    assert alloc.refcount(page) == 1 and alloc.refcount(new) == 1


def test_engine_cow_forks_shared_partial_page(model, oracle):
    """Two slots sharing a PARTIAL page (a forked sequence): the first
    decode write into it must fork, not corrupt the sibling."""
    eng = paged_engine(model)
    prompt = list(range(1, PS + 5))             # 12 tokens: 1 full + 1 partial page
    eng.prefill(0, prompt)
    # fork slot 0 -> slot 1: share its pages, bump refcounts
    n_pages = int(eng.page_counts[0])
    for j in range(n_pages):
        page = int(eng.page_tables[0, j])
        eng.page_tables[1, j] = page
        eng.allocator.ref(page)
    eng.page_counts[1] = n_pages
    eng.lengths[1] = eng.lengths[0]
    shared_partial = int(eng.page_tables[0, 1])
    assert eng.allocator.refcount(shared_partial) == 2

    # both slots decode at position 12 — INSIDE the shared partial page
    first = oracle.prefill(prompt)
    tokens = np.zeros(eng.num_slots, np.int32)
    tokens[0] = tokens[1] = first
    nxt = eng.decode_step(tokens)
    eng.advance(0), eng.advance(1)
    # the write forked the page: tables diverged, refcounts back to 1
    assert eng.page_tables[0, 1] != eng.page_tables[1, 1]
    assert eng.allocator.refcount(int(eng.page_tables[0, 1])) == 1
    assert eng.allocator.refcount(int(eng.page_tables[1, 1])) == 1
    # and both slots decode the true greedy continuation
    want = greedy_chain(model, prompt + [first], 1)[0]
    assert int(nxt[0]) == want and int(nxt[1]) == want
    eng.free_slot(0), eng.free_slot(1)
    assert eng.allocator.pages_in_use == 0


# ------------------------------------------------------- prefix sharing


def test_prefix_sharing_hits_and_matches_baseline(model, oracle):
    eng = paged_engine(model, prefix_caching=True)
    rs = np.random.RandomState(7)
    system = rs.randint(0, 128, size=2 * PS + 3).tolist()   # 2 full pages
    tails = [rs.randint(0, 128, size=n).tolist() for n in (4, 7, 2)]
    prompts = [system + t for t in tails]
    outs = [eng.generate([p], max_new_tokens=5)[0] for p in prompts]
    stats = eng.prefix_stats()
    assert stats["hits"] >= 2                  # 2nd and 3rd prompt hit
    assert stats["shared_pages"] >= 4 and stats["tokens_saved"] >= 4 * PS
    assert outs == [oracle.generate([p], max_new_tokens=5)[0]
                    for p in prompts]
    # retired sequences released their refs; only cache-held pages remain
    held = eng.allocator.pages_in_use
    assert held == eng.prefix_stats()["entries"]
    eng.prefix_cache.clear()
    assert eng.allocator.pages_in_use == 0


def test_prefix_sharing_within_one_burst(model, oracle):
    """N requests with one system prompt arriving in the SAME
    generate() call share its pages: matching runs at first-chunk time
    and registration happens per chunk, so the burst's first member
    seeds the rest one loop iteration later."""
    eng = paged_engine(model, max_batch_size=4, prefix_caching=True)
    rs = np.random.RandomState(9)
    system = rs.randint(0, 128, size=2 * PS).tolist()    # 2 full pages
    prompts = [system + rs.randint(0, 128, size=n).tolist()
               for n in (3, 6, 2, 5)]
    outs = eng.generate(prompts, max_new_tokens=4)
    stats = eng.prefix_stats()
    assert stats["hits"] >= 3, stats          # members 2..4 all hit
    assert outs == oracle.generate(prompts, max_new_tokens=4)


def test_prefix_cache_register_match_evict():
    alloc = PageAllocator(8)
    cache = PrefixCache(alloc, page_size=4)
    tokens = list(range(11))                    # 2 full pages + partial
    pages = [alloc.alloc(), alloc.alloc()]
    cache.register(tokens, pages)
    assert alloc.refcount(pages[0]) == 2        # owner + cache
    # full match capped below the whole prompt
    got, n = cache.match(tokens, len(tokens) - 1)
    assert got == pages and n == 8
    for p in got:
        alloc.free(p)                           # caller returns its refs
    # a diverging second page breaks the chain after page 1
    other = tokens[:4] + [99, 98, 97, 96, 95]
    got, n = cache.match(other, len(other) - 1)
    assert got == pages[:1] and n == 4
    alloc.free(got[0])
    # eviction under pressure releases the cache's refs LRU-first
    for p in pages:
        alloc.free(p)                           # owner retires
    assert alloc.pages_in_use == 2              # cache refs keep them
    cache.evict(alloc.num_pages)                # demand everything
    assert alloc.pages_in_use == 0


# -------------------------------------------------- speculative decode


def test_spec_greedy_ngram_byte_identical(model, oracle):
    eng = paged_engine(model, speculative={
        "enabled": True, "method": "ngram", "num_draft_tokens": 4})
    rs = np.random.RandomState(1)
    prompts = [([3, 7, 9] * 6)[:14],                       # repetitive
               rs.randint(0, 128, size=9).tolist(),        # random
               rs.randint(0, 128, size=17).tolist()]
    assert eng.generate(prompts, max_new_tokens=11) == \
        oracle.generate(prompts, max_new_tokens=11)
    spec = eng.serving_metrics.spec_dist()
    assert spec is not None and spec["proposed"] > 0
    assert 0.0 <= spec["acceptance_rate"] <= 1.0


def test_spec_greedy_model_drafter_byte_identical(model, oracle):
    """Draft model == target model: every draft accepted (rate 1.0) and
    the stream is byte-identical; a DIFFERENT tiny drafter still yields
    the identical stream (greedy acceptance is draft-agnostic)."""
    same = deepspeed.init_inference(
        model=model, draft_model=tiny_model(),
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [8, 16, 32],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": PS,
            "speculative": {"enabled": True, "method": "model",
                            "num_draft_tokens": 3}}})
    rs = np.random.RandomState(2)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (6, 13)]
    want = oracle.generate(prompts, max_new_tokens=9)
    assert same.generate(prompts, max_new_tokens=9) == want
    assert same.serving_metrics.spec_dist()["acceptance_rate"] == 1.0

    other = deepspeed.init_inference(
        model=model, draft_model=tiny_model(seed=123, n_layers=1),
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [8, 16, 32],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": PS,
            "speculative": {"enabled": True, "method": "model",
                            "num_draft_tokens": 3}}})
    assert other.generate(prompts, max_new_tokens=9) == want


def test_spec_respects_eos_and_budget(model, oracle):
    """EOS inside an accepted draft run truncates exactly like the
    baseline, and max_new_tokens never overshoots."""
    eng = paged_engine(model, speculative={
        "enabled": True, "method": "ngram", "num_draft_tokens": 4})
    prompt = [7, 7, 7]
    free_run = oracle.generate([prompt], max_new_tokens=8)[0]
    eos = free_run[2]
    assert eng.generate([prompt], max_new_tokens=8,
                        eos_token_id=eos)[0] == \
        free_run[:free_run.index(eos) + 1]
    out = eng.generate([prompt], max_new_tokens=5)[0]
    assert out == free_run[:5]
    assert eng.lengths.tolist() == [0] * eng.num_slots


def test_spec_verify_pass_at_the_cache_end(model):
    """k_eff clamps near the cache ceiling: no verify pass writes past
    max_seq (an engine built with no ``kv_*`` key: the default pool)."""
    eng = make_engine(model, prefill_buckets=[8, 16, 32, 64],
                      speculative={
                          "enabled": True, "method": "ngram",
                          "num_draft_tokens": 4})
    long_prompt = list(range(30)) * 2                   # 60 of 64
    out = eng.generate([long_prompt], max_new_tokens=50)[0]
    # decode stops when the cache fills: 60 -> 64 leaves 4 writes + the
    # final sampled-but-not-embedded token
    n_new = TINY["max_seq_len"] - len(long_prompt) + 1
    assert out == greedy_chain(model, long_prompt, n_new)
    assert len(out) == n_new


def test_model_drafter_survives_plain_decode_interludes(model, oracle):
    """While any slot sits near the cache ceiling, steps run plain
    decode (k_eff 0) — the model drafter must still embed each
    committed token into ITS cache, or speculation resumes over a
    stale hole once the near-ceiling slot retires (acceptance would
    collapse below the target-as-drafter 1.0 invariant)."""
    eng = deepspeed.init_inference(
        model=model, draft_model=model,
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [8, 16, 32, 64],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": PS,
            "speculative": {"enabled": True, "method": "model",
                            "num_draft_tokens": 3}}})
    near_ceiling = list(range(1, 59))             # 58 of 64: forces k_eff 0
    short = [5, 3, 8, 1]
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
    sched = ContinuousBatchingScheduler(eng)
    u_long = sched.submit(near_ceiling, max_new_tokens=10)   # caps at 7
    u_short = sched.submit(short, max_new_tokens=25)
    res = sched.run()
    assert res[u_short] == greedy_chain(model, short, 25)
    assert len(res[u_long]) == 64 - 58 + 1
    # speculation resumed after the long request retired, and every
    # draft kept matching the target (no stale drafter hole)
    spec = eng.serving_metrics.spec_dist()
    assert spec is not None and spec["acceptance_rate"] == 1.0, spec


def test_spec_sampled_acceptance_reproducible(model):
    """Non-greedy speculative decode: same seed -> same stream, right
    lengths (sequential-sampling semantics through the verify pass)."""
    kw = dict(max_batch_size=1, prefill_buckets=[8], greedy=False,
              top_k=8, temperature=0.9,
              kv_block_size=PS,
              speculative={"enabled": True, "method": "ngram",
                           "num_draft_tokens": 3})
    a = make_engine(model, **kw)
    b = make_engine(model, **kw)
    prompt = [3, 1, 4, 1, 5]
    out = a.generate([prompt], max_new_tokens=6)
    assert out == b.generate([prompt], max_new_tokens=6)
    assert len(out[0]) == 6


# ------------------------------------------------------ chunked prefill


def test_chunked_prefill_matches_unchunked(model, oracle):
    eng = paged_engine(model, prefill_chunk_tokens=8,
                       prefill_buckets=[8, 16, 32])
    rs = np.random.RandomState(4)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (29, 5, 18)]
    assert eng.generate(prompts, max_new_tokens=6) == \
        oracle.generate(prompts, max_new_tokens=6)


def test_chunked_prefill_does_not_stall_decode(model):
    """A decoding request keeps emitting tokens on every scheduler step
    while a long prompt prefills chunk by chunk next to it."""
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
    eng = paged_engine(model, max_batch_size=2, prefill_chunk_tokens=8)
    sched = ContinuousBatchingScheduler(eng)
    short = sched.submit([1, 2, 3], max_new_tokens=20)
    sched.step()                                # short admitted + decoding
    req_short = sched.slots[0]
    long_uid = sched.submit(list(range(1, 30)), max_new_tokens=2)
    grew = []
    for _ in range(3):                          # 29 tokens = 4 chunks
        before = len(req_short.generated)
        sched.step()
        grew.append(len(req_short.generated) - before)
        long_req = sched.slots[1]
        assert long_req is not None and long_req.state == "prefill"
    assert all(g == 1 for g in grew), grew      # decode never stalled
    results = sched.run()
    assert len(results[short]) == 20 and len(results[long_uid]) == 2


def test_plan_chunks_covers_and_respects_bounds():
    bucket_for = lambda n: min(b for b in (8, 16, 32) if n >= 0 and b >= n)
    assert plan_chunks(29, 8, bucket_for, 64) == \
        [(0, 8), (8, 8), (16, 8), (24, 5)]
    assert plan_chunks(5, 8, bucket_for, 64) == [(0, 5)]
    assert plan_chunks(20, None, bucket_for, 64) == [(0, 20)]
    # a chunk whose padded bucket would overrun max_seq merges back
    # into one unchunked prefill (once the slot layout's write safety;
    # it still decides the programs a prompt runs): with
    # max_seq 60, the final chunk (48, 11) pads to bucket 16 -> 64 > 60
    assert plan_chunks(59, 16, bucket_for, 60) == [(0, 59)]
    # ... while max_seq 64 fits every padded chunk and stays chunked
    assert plan_chunks(60, 16, bucket_for, 64) == \
        [(0, 16), (16, 16), (32, 16), (48, 12)]


# ------------------------------------------------- stale-KV poisoning


@pytest.mark.parametrize("layout", ["paged"])
def test_stale_kv_beyond_length_never_leaks(model, layout):
    """Freed pages are reused WITHOUT clearing: poison everything past
    the live lengths with NaN and decode must be unaffected — the
    absolute-position mask (models/gpt2.py _attend_cache_rows) is the
    only thing standing between stale K/V and the softmax."""
    eng = paged_engine(model)
    prompt = [9, 4, 2, 8, 1]
    first = eng.prefill(0, prompt)
    # poison every UNALLOCATED page (incl. garbage page 0) and the
    # allocated tail beyond the live length
    k, v = eng.kv.buffers()
    live = [int(eng.page_tables[0, j])
            for j in range(int(eng.page_counts[0]))]
    dead = [p for p in range(eng.kv.k.shape[0]) if p not in live]
    k = k.at[jnp.asarray(dead)].set(jnp.nan)
    v = v.at[jnp.asarray(dead)].set(jnp.nan)
    off = len(prompt) % PS
    k = k.at[live[-1], :, off:, :].set(jnp.nan)
    v = v.at[live[-1], :, off:, :].set(jnp.nan)
    eng.kv.update((k, v))
    tokens = np.zeros(eng.num_slots, np.int32)
    tokens[0] = first
    nxt = eng.decode_step(tokens)
    want = greedy_chain(model, prompt + [first], 1)[0]
    assert int(nxt[0]) == want
    eng.free_slot(0)


def test_paged_prefill_into_poisoned_pool_is_clean(model):
    """Bucket-padded paged prefill redirects pad writes to the garbage
    page, so a freshly-allocated page's tail keeps its recycled content
    INSIDE the bucket span — poison the whole pool with NaN before any
    prefill and generation must still be exact (V is zeroed beyond each
    row's true valid length, not the padded width)."""
    eng = paged_engine(model, max_batch_size=2)
    k, v = eng.kv.buffers()
    eng.kv.update((k.at[:].set(jnp.nan), v.at[:].set(jnp.nan)))
    prompt = [9, 4, 2, 8, 1]                  # pads to bucket 8 > 5
    out = eng.generate([prompt], max_new_tokens=4)[0]
    assert out == greedy_chain(model, prompt, 4)


def test_prefix_hit_admits_with_suffix_only_pages(model, oracle):
    """Admission charges only the UNMATCHED suffix against the pool: a
    second user of a cached long system prompt admits even when the
    pool could not hold the whole prompt fresh."""
    eng = paged_engine(model, max_batch_size=2, prefix_caching=True,
                       max_seq_len=48, num_pages=6)   # 48 tokens total
    rs = np.random.RandomState(11)
    system = rs.randint(0, 128, size=3 * PS).tolist()    # 3 full pages
    first = system + rs.randint(0, 128, size=3).tolist()
    out1 = eng.generate([first], max_new_tokens=3)[0]
    # 3 pages now live in the prefix cache; only 3 remain free — the
    # second prompt needs 4 pages, so without the match crediting its
    # 3 shared pages admission would have to EVICT the cached prefix
    assert eng.allocator.free_pages == 3
    second = system + rs.randint(0, 128, size=2).tolist()
    out2 = eng.generate([second], max_new_tokens=3)[0]
    assert eng.prefix_stats()["hits"] >= 1
    # no eviction happened: the cached prefix survived the admission
    assert eng.prefix_stats()["entries"] == 3
    assert [out1, out2] == [
        oracle.generate([p], max_new_tokens=3)[0] for p in (first, second)]


# ------------------------------------------------ preemption + pressure


def test_pool_exhaustion_preempts_and_recovers(model, oracle):
    """A pool too small for all concurrent sequences preempts the
    youngest decoder (recompute discipline) and still produces the
    byte-identical greedy streams."""
    # 3 slots x up to ~40 tokens each, but only 9 pages (72 tokens)
    eng = paged_engine(model, max_batch_size=3, num_pages=9)
    rs = np.random.RandomState(5)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (12, 14, 10)]
    out = eng.generate(prompts, max_new_tokens=24)
    assert out == oracle.generate(prompts, max_new_tokens=24)
    assert eng.allocator.pages_in_use == 0


# ------------------------------------------- a chunk's read, a kernel


@pytest.mark.parametrize("d_model", [32, 128],
                         ids=["a_slice_a_head", "two_heads_a_lane_tile"])
def test_a_chunks_kernel_read_gives_the_gather_reads_streams(d_model):
    """``paged_attention_kernel: pallas`` on one chip sends a prompt
    chunk through ``chunk_attention`` (``GPT2Decoder.prefill_config``)
    and a decode or verify step through the page walk: with a float32
    pool the greedy streams are the gather read's byte for byte, with
    the prefix cache hit (a chunk that starts past 0), a prompt in
    chunks and the n-gram drafter's verify steps on; at two heads of 16
    lanes (a slice a head) and of 64 (the two folded over one lane
    tile, GPT-2's layout on the chip)."""
    model = tiny_model(d_model=d_model)
    rs = np.random.RandomState(21)
    system = rs.randint(0, 128, size=2 * PS).tolist()
    prompts = [system + rs.randint(0, 128, size=n).tolist()
               for n in (3, 21, 9)] + [rs.randint(0, 128, size=5).tolist()]
    streams = {}
    for kernel in ("xla", "pallas"):
        eng = paged_engine(
            model, max_batch_size=2, paged_attention_kernel=kernel,
            prefix_caching=True, prefill_chunk_tokens=16,
            speculative={"enabled": True, "method": "ngram",
                         "num_draft_tokens": 3})
        assert (eng.paged_attention_kernel,
                eng.prefill_attention_kernel) == (kernel, kernel)
        streams[kernel] = eng.generate(prompts, max_new_tokens=6)
        assert eng.prefix_stats()["hits"] >= 1
    assert streams["pallas"] == streams["xla"]
    assert streams["xla"] == DenseReference(model).generate(
        prompts, max_new_tokens=6)


# ----------------------------------------------------------- sharding


@pytest.mark.parametrize("kernel", ["auto", "pallas"])
def test_paged_cache_sharded_over_heads_decode_parity(model, oracle, kernel):
    """TP mesh: the paged pool shards its packed heads axis over the
    model axis, and paged+spec decode on the mesh still matches the
    dense reference — on the
    XLA gather path (``auto`` off a TPU) and with the Pallas kernel
    shard_mapped over the mesh, heads split over ``model``. A prompt
    chunk's read stays the gather there: ``chunk_attention`` has no
    ``shard_map`` wrapper, so the prefill family keeps the serving
    config."""
    from deepspeed_tpu.parallel.topology import build_mesh
    from deepspeed_tpu.inference.kv_cache import PAGED_KV_CACHE_SPEC
    mesh = build_mesh(data=4, model=2)
    eng = deepspeed.init_inference(model=model, mesh=mesh, config={
        "inference": {"max_batch_size": 2, "prefill_buckets": [16, 32],
                      "dtype": "fp32", "greedy": True,
                      "kv_block_size": PS,
                      "paged_attention_kernel": kernel,
                      "prefix_caching": True,
                      "speculative": {"enabled": True, "method": "ngram",
                                      "num_draft_tokens": 3}}})
    assert eng.paged_attention_kernel == \
        ("pallas" if kernel == "pallas" else "xla")
    assert eng.prefill_attention_kernel == "xla"
    assert eng._prefill_config() is eng.model_config
    assert eng.kv.k.sharding.spec == PAGED_KV_CACHE_SPEC
    rs = np.random.RandomState(8)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (7, 12)]
    assert eng.generate(prompts, max_new_tokens=5) == \
        oracle.generate(prompts, max_new_tokens=5)


# ------------------------------------------------------ config surface


def test_paged_config_validation():
    from deepspeed_tpu.inference.config import (DeepSpeedInferenceConfig,
                                                DeepSpeedInferenceConfigError)
    ic = DeepSpeedInferenceConfig({"inference": {
        "kv_layout": "paged", "kv_block_size": 8, "num_pages": 32,
        "prefix_caching": True, "prefill_chunk_tokens": 64,
        "speculative": {"enabled": True, "method": "ngram",
                        "num_draft_tokens": 5}}})
    assert not hasattr(ic, "kv_layout")     # read, and selects nothing
    assert ic.resolve_num_pages(4, 64) == 32
    # fraction-of-slots*max_seq sizing (default fraction 1.0)
    frac = DeepSpeedInferenceConfig({"inference": {
        "kv_block_size": 8,
        "kv_pool_fraction": 0.5}})
    assert frac.resolve_num_pages(4, 64) == 16      # 0.5 * 4*64 / 8
    # prefix caching and a fleet role need nothing else said: every
    # engine has pages
    assert DeepSpeedInferenceConfig(
        {"inference": {"prefix_caching": True}}).prefix_caching
    assert DeepSpeedInferenceConfig({"inference": {
        "fleet": {"role": "prefill"}}}).fleet_role == "prefill"
    for bad in ({"kv_layout": "blocked"},
                {"kv_block_size": 0},
                {"num_pages": 4, "kv_pool_fraction": 0.5},   # pick one
                {"prefill_chunk_tokens": 0},
                {"speculative": {"enabled": True, "method": "oracle"}},
                {"speculative": {"num_draft_tokens": 0}},
                {"speculative": {"drafts": 4}}):             # unknown key
        with pytest.raises(DeepSpeedInferenceConfigError):
            DeepSpeedInferenceConfig({"inference": bad})
    with pytest.raises(DeepSpeedInferenceConfigError, match="cannot hold"):
        DeepSpeedInferenceConfig({"inference": {
            "kv_block_size": 8,
            "num_pages": 2}}).resolve_num_pages(4, 64)


@pytest.mark.parametrize("value", ["slot", "Slot", "blocked", None, 1])
def test_kv_layout_names_the_removal(value):
    """The key is read so that configs which say "paged" keep loading;
    any other value is told, in one sentence, that the layout went."""
    from deepspeed_tpu.inference.config import (DeepSpeedInferenceConfig,
                                                DeepSpeedInferenceConfigError)
    with pytest.raises(DeepSpeedInferenceConfigError,
                       match="the slot layout was removed in PR 48; "
                       "every engine serves from pages; drop the key"):
        DeepSpeedInferenceConfig({"inference": {"kv_layout": value}})


def test_init_inference_with_nothing_set_serves_from_pages(model, oracle):
    """``init_inference(model)`` and no ``inference`` section: the path
    the benchmark measures. The pool holds ``max_batch_size *
    max_seq_len`` tokens, and the streams are the dense chain's."""
    eng = deepspeed.init_inference(model=model)
    ic = eng.inference_config
    stats = eng.page_pool_stats()
    assert stats["num_pages"] * eng.page_size == \
        ic.max_batch_size * TINY["max_seq_len"]
    assert stats["pages_in_use"] == 0
    assert eng.kv.k.shape[0] == stats["num_pages"] + 1   # + garbage page
    assert eng.page_tables.shape == (ic.max_batch_size, eng.max_pages)
    rs = np.random.RandomState(12)
    prompts = [rs.randint(0, 128, size=n).tolist() for n in (5, 17, 33)]
    assert eng.generate(prompts, max_new_tokens=8) == \
        oracle.generate(prompts, max_new_tokens=8)
    assert eng.allocator.pages_in_use == 0


def test_model_drafter_requires_draft_model(model):
    with pytest.raises(AssertionError, match="draft_model"):
        make_engine(model, speculative={"enabled": True,
                                        "method": "model"})


# ----------------------------------------------------------- telemetry


def test_serving_records_carry_new_fields(model, tmp_path):
    """One serving_step record per scheduler step with schema-valid
    ttft/tpot/page_pool/prefix/speculative fields (bin/check_bench_schema
    and the dryrun leg read the same contract)."""
    import json
    from deepspeed_tpu.telemetry.record import validate_step_record
    eng = deepspeed.init_inference(
        model=model,
        config={"inference": {
            "max_batch_size": 2, "prefill_buckets": [8, 16, 32],
            "dtype": "fp32", "greedy": True,
            "kv_block_size": PS, "prefix_caching": True,
            "speculative": {"enabled": True, "method": "ngram",
                            "num_draft_tokens": 3}},
            "telemetry": {"enabled": True,
                          "output_path": str(tmp_path)}})
    shared = [5, 6, 7] * 6
    # two calls: prefix registration happens at prefill completion, so
    # the second request must ARRIVE after the first prefilled to hit
    eng.generate([shared[:14]], max_new_tokens=6)
    eng.generate([shared[:17]], max_new_tokens=6)
    with open(eng.telemetry.jsonl_path) as fh:
        recs = [json.loads(line) for line in fh]
    assert recs
    for rec in recs:
        assert not validate_step_record(rec), validate_step_record(rec)
    last = recs[-1]
    assert last["ttft"]["count"] == 2 and last["ttft"]["p95_s"] > 0
    assert last["tpot"]["count"] == 2
    assert last["page_pool"]["num_pages"] == eng.allocator.num_pages
    assert 0 <= last["page_pool"]["occupancy"] <= 1
    assert last["prefix"]["lookups"] == 2 and last["prefix"]["hits"] >= 1
    assert last["speculative"]["proposed"] > 0
    assert 0 < last["speculative"]["acceptance_rate"] <= 1
    snap = eng.telemetry_snapshot()["serving"]
    for key in ("ttft", "tpot", "page_pool", "prefix", "speculative"):
        assert key in snap, key


def test_bench_schema_checker_table_matches_record_schema():
    """bin/check_bench_schema.py keeps a LOCAL copy of the serving
    sub-dict key table (it must stay a bare stdlib script — no jax
    import from bin/); pin the copy to telemetry/record.py so the two
    cannot drift."""
    import importlib.util
    import os
    from deepspeed_tpu.telemetry.record import SERVING_SUBDICT_KEYS
    path = os.path.join(os.path.dirname(__file__), "..", "..", "bin",
                        "check_bench_schema.py")
    spec = importlib.util.spec_from_file_location("_cbs", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    assert mod.SERVING_SUBDICT_KEYS == SERVING_SUBDICT_KEYS


# ------------------------------------------- the program family is closed


def _jamba_engine():
    from deepspeed_tpu.models import jamba
    hf = {"attn_layer_offset": 1, "attn_layer_period": 2,
          "hidden_size": 32, "intermediate_size": 64,
          "mamba_conv_bias": True, "mamba_d_conv": 4, "mamba_d_state": 8,
          "mamba_dt_rank": 4, "mamba_expand": 2, "mamba_proj_bias": False,
          "max_position_embeddings": 64, "num_attention_heads": 2,
          "num_experts": 1, "num_hidden_layers": 2,
          "num_key_value_heads": 1, "rms_norm_eps": 1e-6,
          "vocab_size": 128, "initializer_range": 0.1}
    cfg = jamba.config_from_hf(hf, dtype=jnp.float32)
    return paged_engine(jamba.make_jamba_model(cfg, seed=0),
                        paged_attention_kernel="xla", max_seq_len=64,
                        num_pages=24, prefill_buckets=[8, 16])


def _deepseek_v3_engine(**inference):
    from deepspeed_tpu.models import deepseek_v3
    cfg = deepseek_v3.DeepseekV3Config(
        vocab_size=128, d_model=32, n_layers=2, n_heads=2, qk_nope=16,
        qk_rope=8, v_head=16, kv_rank=120, d_ff=64, d_expert=16,
        n_experts=4, top_k=2, max_seq_len=64, dtype=jnp.float32)
    return paged_engine(deepseek_v3.make_deepseek_v3_model(cfg, seed=0),
                        paged_attention_kernel="xla", max_seq_len=64,
                        num_pages=24, prefill_buckets=[8, 16], **inference)


_NGRAM = {"enabled": True, "method": "ngram", "num_draft_tokens": 3}
_MODEL_DRAFT = {"enabled": True, "method": "model", "num_draft_tokens": 3}

# case -> (engine, new tokens, prompt lengths of the first pass, of the
# second); what a case adds to plain greedy serving is in its engine
_FAMILY = {
    "gpt2_greedy": (
        lambda: paged_engine(tiny_model()), 6, (5, 11, 26), (7, 14, 30, 3)),
    "gpt2_sampled": (
        lambda: paged_engine(tiny_model(), greedy=False, top_k=8,
                             temperature=0.9),
        6, (5, 11, 26), (7, 14, 30, 3)),
    "gpt2_chunked_prefill": (
        lambda: paged_engine(tiny_model(), prefill_chunk_tokens=8),
        6, (29, 5, 18), (27, 7, 12)),
    "gpt2_prefix_cache": (
        lambda: paged_engine(tiny_model(), prefix_caching=True),
        5, (20, 23, 9, 30), (21, 25, 11, 28)),
    "gpt2_ngram_drafter": (
        lambda: paged_engine(tiny_model(), speculative=_NGRAM),
        9, (14, 6, 17, 30), (12, 7, 20, 27)),
    "gpt2_model_drafter": (
        lambda: deepspeed.init_inference(
            model=tiny_model(),
            draft_model=tiny_model(seed=123, n_layers=1),
            config={"inference": {
                "max_batch_size": 2, "prefill_buckets": [8, 16, 32],
                "dtype": "fp32", "greedy": True,
                "kv_block_size": PS, "speculative": _MODEL_DRAFT}}),
        9, (6, 13, 29), (4, 15, 25)),
    # 58 of 64 positions: while that slot lives, every step is plain
    # decode; once it retires the short request speculates again
    "gpt2_drafter_near_ceiling": (
        lambda: paged_engine(tiny_model(), speculative=_NGRAM,
                             prefill_buckets=[8, 16, 32, 64],
                             max_batch_size=2),
        25, (58, 4, 12, 30), (57, 6, 10, 20)),
    # 9 pages for three answers of up to 38 tokens: the youngest is
    # preempted and prefills its prompt and its tokens so far again
    "gpt2_preemption": (
        lambda: paged_engine(tiny_model(), num_pages=9),
        24, (12, 14, 10, 30, 5), (11, 13, 9, 26, 6)),
    "jamba_greedy": (
        _jamba_engine, 6, (5, 11, 16), (7, 14, 3)),
    # 23 tokens over a largest bucket of 16: two chunks, the second on
    # the state the first left
    "jamba_two_chunks": (
        _jamba_engine, 6, (23, 5, 11), (21, 7, 19)),
    # latent pages: 37 tokens over a largest bucket of 16 are three
    # chunks, the later ones reading the earlier ones' rows from the
    # pages in a loop whose length the data gives, not a program's shape
    "deepseek_v3_three_chunks": (
        _deepseek_v3_engine, 6, (37, 5, 11), (35, 7, 19)),
    "deepseek_v3_prefix_cache": (
        lambda: _deepseek_v3_engine(prefix_caching=True),
        5, (20, 23, 9, 30), (21, 25, 11, 28)),
}


@pytest.mark.parametrize("case", sorted(_FAMILY))
def test_serving_program_family_is_closed(case, compiled_programs):
    """What a configuration fixes at construction fixes the programs:
    after a first pass over every bucket and both decode widths, other
    prompts in the same buckets compile nothing — what the chip's check
    calls ``compiles_in_window == 0``, here for every path of the
    scheduler that picks a program."""
    from deepspeed_tpu.inference.scheduler import ContinuousBatchingScheduler
    build, new_tokens, first, second = _FAMILY[case]
    engine = build()
    shared = [9, 4, 7, 1, 8, 2, 6, 3] * 2       # two whole pages

    def serve(lengths, salt):
        rs = np.random.RandomState(salt)
        sched = ContinuousBatchingScheduler(engine)
        for n in lengths:
            prompt = rs.randint(0, 128, size=n).tolist()
            if engine.prefix_cache is not None and n > len(shared):
                prompt[:len(shared)] = shared
            sched.submit(prompt, max_new_tokens=new_tokens,
                         eos_token_id=None)
        sched.run()
        return sched

    serve(first, 1)
    stats, before = dict(engine.compile_stats), len(compiled_programs)
    sched = serve(second, 2)
    assert compiled_programs[before:] == []
    assert engine.compile_stats == stats
    if case.endswith("_prefix_cache"):
        assert engine.prefix_stats()["hits"] >= 2
    if case == "gpt2_drafter_near_ceiling":
        assert stats["decode_traces"] == 2       # "two widths", no third
    if case == "gpt2_preemption":
        assert sched.preemptions >= 1
