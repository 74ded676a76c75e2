"""Host-side page management for the paged KV cache.

The paged layout (inference/kv_cache.py ``PagedKVCache``) splits the KV
pool into fixed-size pages; what maps a sequence's logical positions
onto physical pages lives HERE, on the host, because allocation is
control flow, not math:

  * :class:`PageAllocator` — free list + per-page refcounts. Page 0 is
    the reserved GARBAGE page: it is never handed out, padded/invalid
    writes inside the jitted programs are redirected to it, and page
    tables of retired slots point at it. Refcounts > 1 mean the page is
    shared (prefix sharing); writes into a shared page must fork it
    first (:meth:`PageAllocator.fork` + a device-side page copy by the
    engine) — classic copy-on-write.
  * :class:`PrefixCache` — hash-matched common prefixes. Keys chain per
    FULL page (vLLM's block-hash discipline): page j's key hashes
    (key_{j-1}, page-j tokens), so a hit at depth j certifies the whole
    prefix. The cache holds its own reference on every registered page,
    so retiring the sequence that populated it does not free the pages;
    LRU eviction drops that reference.
  * :class:`GroupPages` — one GROUP of paged layers' allocator and
    page table a slot (inference/decoder.py ``PageGroup``). A group
    with a ``window`` keeps a SLIDING table: column 0 is the first page
    that holds a key a coming query can see, the pages before it went
    back to the allocator when they slid out, and the table's width is
    bounded by the window and the largest chunk whatever
    ``max_seq_len`` is.
  * :func:`plan_chunks` — chunked-prefill schedule (a plan whose
    padded chunk would pass max_seq merges into one prefill where a
    bucket holds it: a guard the removed slot layout needed and the
    masked paged write does not; ROADMAP names it as a debt).
"""
from collections import OrderedDict

import numpy as np

GARBAGE_PAGE = 0


class PagePoolExhausted(Exception):
    """Raised by strict allocation; the scheduler's admission/preemption
    paths use :meth:`PageAllocator.can_alloc` instead of catching."""


class PageAllocator:
    """Refcounted allocator over physical pages ``1 .. num_pages``.

    ``num_pages`` counts USABLE pages; the physical buffer has one more
    (the garbage page 0). Invariants (pinned by tests/unit/
    test_serving.py): a page is either free (refcount 0, in the free
    list) or held (refcount >= 1); alloc never returns page 0; free of
    a free page raises; every retire path ends with the sequence's
    pages back at their pre-admission refcounts.
    """

    def __init__(self, num_pages):
        assert num_pages >= 1, "page pool needs at least one usable page"
        self.num_pages = int(num_pages)
        # LIFO free list: recently-freed pages are re-used first (their
        # cache lines / HBM pages are warm)
        self._free = list(range(self.num_pages, 0, -1))
        self._refs = [0] * (self.num_pages + 1)
        # pages held more than once: while there are none, no write can
        # land in a shared page and nobody need look (engine._cow_writes)
        self.shared_pages = 0

    @property
    def free_pages(self):
        return len(self._free)

    @property
    def pages_in_use(self):
        return self.num_pages - len(self._free)

    def can_alloc(self, n):
        return len(self._free) >= n

    def refcount(self, page):
        return self._refs[page]

    def alloc(self):
        """-> one fresh page (refcount 1). Raises PagePoolExhausted."""
        if not self._free:
            raise PagePoolExhausted(
                "KV page pool exhausted ({} pages)".format(self.num_pages))
        page = self._free.pop()
        assert self._refs[page] == 0
        self._refs[page] = 1
        return page

    def ref(self, page):
        """Add a reference to a held page (prefix sharing / fork source)."""
        assert page != GARBAGE_PAGE, "cannot reference the garbage page"
        assert self._refs[page] >= 1, \
            "ref of unheld page {}".format(page)
        self._refs[page] += 1
        self.shared_pages += self._refs[page] == 2

    def free(self, page):
        """Drop one reference; the page returns to the pool at zero."""
        if page == GARBAGE_PAGE:
            return
        assert self._refs[page] >= 1, \
            "double free of page {}".format(page)
        self._refs[page] -= 1
        self.shared_pages -= self._refs[page] == 1
        if self._refs[page] == 0:
            self._free.append(page)

    def fork(self, page):
        """Copy-on-write fork: if ``page`` is shared (refcount > 1),
        allocate a fresh page, move one reference onto it, and return
        ``(new_page, True)`` — the CALLER must copy the page's device
        contents before any write. Unshared pages return unchanged."""
        if self._refs[page] <= 1:
            return page, False
        new = self.alloc()
        self._refs[page] -= 1
        self.shared_pages -= self._refs[page] == 1
        return new, True

    def stats(self):
        return {"num_pages": self.num_pages,
                "pages_in_use": self.pages_in_use,
                "occupancy": (self.pages_in_use / self.num_pages
                              if self.num_pages else 0.0)}


class GroupPages:
    """The host side of one group of paged layers: an allocator, a
    table ``tables[slot]`` and the columns of it that hold pages
    (``counts[slot]``). Column ``c`` of a slot's row holds the page of
    its tokens ``[(base[slot] + c) * page_size, + page_size)``.

    Without a ``window`` ``base`` stays 0 and a slot keeps every page
    until :meth:`release`: the table and the allocator as they were
    before there were groups.

    With a ``window`` (query ``t`` sees key ``j`` iff ``0 <= t - j <
    window``) the table SLIDES: :meth:`slide` to the position of the
    next query gives back every page none of whose tokens that query or
    a later one can see, moves the row left and raises ``base``, so
    that column 0 is always the first page with a visible key. A slot
    then holds at most ``steady`` pages between two launches (a decode
    step's ``window`` keys) and ``max_pages`` during a chunk of
    ``chunk_tokens`` (its keys, and the ``window - 1`` before them):
    the table is that wide whatever ``max_seq_len`` is. Admission does
    not take pages from such a group, it takes a PROMISE of ``steady``
    of them (``reserved``), and one chunk's worth beyond every promise
    is kept for the one chunk that runs at a time: a slot admitted can
    always get the pages of its next launch, so no request waits or is
    preempted for this group's pages."""

    def __init__(self, num_pages, num_slots, max_pages, page_size,
                 window=None, chunk_tokens=1):
        self.allocator = PageAllocator(num_pages)
        self.page_size, self.window = int(page_size), window
        self.steady = self.reserved = 0
        if window is not None:
            self.steady, max_pages = self.spans(
                window, page_size, chunk_tokens, max_pages)
            assert num_pages >= max_pages, \
                "a windowed pool of {} pages cannot hold one chunk's {} " \
                "pages".format(num_pages, max_pages)
        self.max_pages = int(max_pages)
        self.tables = np.full((num_slots, self.max_pages), GARBAGE_PAGE,
                              np.int32)
        self.counts = np.zeros((num_slots,), np.int32)
        # the logical page in column 0 (0 for ever without a window)
        self.base = np.zeros((num_slots,), np.int32)
        self._promised = np.zeros((num_slots,), bool)
        self.freed = 0         # pages given back as they slid out

    @staticmethod
    def spans(window, page_size, chunk_tokens, max_pages):
        """-> (pages a slot of a windowed group holds for a decode
        step, pages it holds for a chunk of ``chunk_tokens``: the
        table's width). ``q`` queries in a row see ``window + q - 1``
        keys, which begin anywhere in a page."""
        span = lambda q: min(max_pages, (window + q - 2) // page_size + 2)
        return span(1), span(chunk_tokens)

    def pages_for(self, n_tokens):
        return -(-n_tokens // self.page_size)

    def admit(self, slot, n_tokens):
        """Room for a request of ``n_tokens`` so far: its pages
        (without a window), or the promise of ``steady`` pages. False,
        with nothing taken, where the pool has none."""
        if self.window is None:
            return self.grow(slot, n_tokens)
        chunk = self.max_pages - self.steady
        if self.reserved + self.steady + chunk > self.allocator.num_pages:
            return False
        self.reserved += self.steady
        self._promised[slot] = True
        return True

    def slide(self, slot, position):
        """Give back the pages no query at ``position`` or later can
        see. -> how many."""
        if self.window is None:
            return 0
        first = max(0, position - self.window + 1) // self.page_size
        gone = first - int(self.base[slot])
        if gone <= 0:
            return 0
        row, held = self.tables[slot], int(self.counts[slot])
        n = min(gone, held)
        for page in row[:n].tolist():
            self.allocator.free(page)
        row[:held - n] = row[n:held]
        row[held - n:held] = GARBAGE_PAGE
        self.counts[slot], self.base[slot] = held - n, first
        self.freed += n
        return n

    def shortfall(self, slot, upto_tokens):
        """Pages the slot lacks to cover ``upto_tokens`` positions."""
        need = min(self.pages_for(upto_tokens) - int(self.base[slot]),
                   self.max_pages)
        return max(0, need - int(self.counts[slot]))

    def grow(self, slot, upto_tokens):
        """Pages for the slot's positions below ``upto_tokens``. False,
        with nothing taken, where the pool has too few."""
        cur = int(self.counts[slot])
        need = min(-(-upto_tokens // self.page_size) - int(self.base[slot]),
                   self.max_pages)
        if need <= cur:                 # every decode step but one in 16
            return True
        if not self.allocator.can_alloc(need - cur):
            return False
        for j in range(cur, need):
            self.tables[slot, j] = self.allocator.alloc()
        self.counts[slot] = need
        return True

    def release(self, slot):
        """Every page of the slot back to the pool (a shared one drops
        a reference), and its promise."""
        for page in self.tables[slot, :int(self.counts[slot])].tolist():
            self.allocator.free(page)
        self.tables[slot, :] = GARBAGE_PAGE
        self.counts[slot] = self.base[slot] = 0
        if self._promised[slot]:
            self._promised[slot] = False
            self.reserved -= self.steady

    def stats(self):
        out = self.allocator.stats()
        if self.window is not None:
            out.update(window=self.window, table_width=self.max_pages,
                       pages_promised=self.reserved,
                       pages_freed_sliding=self.freed)
        return out


class PrefixCache:
    """Hash-matched shared prompt prefixes at full-page granularity.

    ``match(tokens)`` walks the prompt's full pages left to right
    through the chained-hash map and returns the longest registered
    run of pages; ``register(tokens, pages)`` records a prompt's full
    pages after its prefill. Registered pages carry one cache-owned
    reference (taken via the allocator) so sequence retirement cannot
    free them out from under a future hit; eviction (LRU over entries,
    capped at ``max_entries`` pages total) releases that reference.

    Matching never covers the whole prompt: the caller caps the match
    so at least one prompt token still runs through the model (logits
    for the first sampled token have to come from somewhere).
    """

    def __init__(self, allocator, page_size, max_entries=1024):
        self.allocator = allocator
        self.page_size = int(page_size)
        self.max_entries = int(max_entries)
        # chain key -> page id, LRU ordered (move_to_end on hit)
        self._entries = OrderedDict()
        self.lookups = 0
        self.hits = 0          # lookups that matched >= 1 page
        self.hit_pages = 0     # total pages mapped from the cache
        self.tokens_saved = 0  # prompt tokens NOT re-embedded

    def _chain_keys(self, tokens, namespace=None):
        """Chained hash per full page of ``tokens``. A non-None
        ``namespace`` (e.g. a tenant's adapter id) seeds the chain, so
        namespaced entries never collide with the base chain or with
        other namespaces — tenants cannot cross-hit each other's
        prompts."""
        keys = []
        key = None if namespace is None else ("ns", namespace)
        ps = self.page_size
        for j in range(len(tokens) // ps):
            key = hash((key, tuple(tokens[j * ps:(j + 1) * ps])))
            keys.append(key)
        return keys

    def match(self, tokens, max_tokens, skip_pages=0, count_lookup=True,
              namespace=None):
        """-> (new_pages list, new_token_count) for the longest
        registered full-page prefix of ``tokens`` BEYOND the first
        ``skip_pages`` pages (already held by the caller), capped at
        ``max_tokens`` total. Takes ONE allocator reference per
        returned page (the caller's page table now holds them).

        Two call phases per request: admission (``count_lookup`` — one
        lookup per request) and first-chunk extension (skip = what
        admission matched, no second lookup — a same-step burst sibling
        may have registered more pages in between; a request counts as
        ONE hit across both phases)."""
        if count_lookup:
            self.lookups += 1
        pages = []
        cap_pages = max(0, int(max_tokens)) // self.page_size
        for key in self._chain_keys(tokens,
                                    namespace=namespace)[:cap_pages]:
            page = self._entries.get(key)
            if page is None:
                break
            self._entries.move_to_end(key)
            pages.append(page)
        new = pages[skip_pages:]
        for page in new:
            self.allocator.ref(page)
        if new:
            if count_lookup or skip_pages == 0:
                self.hits += 1
            self.hit_pages += len(new)
            self.tokens_saved += len(new) * self.page_size
        return new, len(new) * self.page_size

    def register(self, tokens, pages, namespace=None):
        """Record a prompt's full pages. ``pages[j]`` must hold tokens
        ``[j*ps, (j+1)*ps)``; entries already present are skipped (the
        existing shared page wins — the new duplicate stays owned by
        its sequence alone)."""
        for j, key in enumerate(self._chain_keys(tokens,
                                                 namespace=namespace)):
            if j >= len(pages):
                break
            if key in self._entries:
                self._entries.move_to_end(key)
                continue
            self.allocator.ref(pages[j])
            self._entries[key] = pages[j]
            while len(self._entries) > self.max_entries:
                _, evicted = self._entries.popitem(last=False)
                self.allocator.free(evicted)

    def unmatch(self, pages, counted_lookup=True):
        """Roll back one :meth:`match` whose admission failed: release
        the taken page references AND un-count the stats — a pool-full
        request retried every scheduler step would otherwise inflate
        hits/tokens_saved with savings that never happened."""
        for page in pages:
            self.allocator.free(page)
        if pages:
            self.hits -= 1
            self.hit_pages -= len(pages)
            self.tokens_saved -= len(pages) * self.page_size
        if counted_lookup:
            self.lookups -= 1

    def evict(self, n_needed):
        """Drop LRU entries (releasing the cache's page references)
        until the allocator can hand out ``n_needed`` pages or the
        cache is empty. Pages still referenced by live sequences just
        lose the cache's claim — they free when their sequences do."""
        while self._entries and not self.allocator.can_alloc(n_needed):
            _, page = self._entries.popitem(last=False)
            self.allocator.free(page)

    @property
    def hit_rate(self):
        return self.hits / self.lookups if self.lookups else 0.0

    def stats(self):
        return {"lookups": self.lookups, "hits": self.hits,
                "hit_rate": round(self.hit_rate, 4),
                "shared_pages": self.hit_pages,
                "tokens_saved": self.tokens_saved,
                "entries": len(self._entries)}

    def clear(self):
        for page in self._entries.values():
            self.allocator.free(page)
        self._entries.clear()


def plan_chunks(n_tokens, chunk_tokens, bucket_for, max_seq, start=0,
                max_chunk=None):
    """Chunked-prefill schedule: ``[(start, length), ...]`` covering
    ``[start, start + n_tokens)`` in pieces of at most ``chunk_tokens``.
    ``max_chunk`` (the largest prefill bucket) caps the chunk size
    regardless of config: a preemption-resume context longer than every
    bucket always chunks, whatever ``prefill_chunk_tokens`` says.

    A plan with a chunk whose PADDED bucket would pass ``max_seq``
    (``start + bucket > max_seq``) is merged back into one unchunked
    prefill when a bucket covers the whole span; otherwise the chunked
    plan stands. The merge was the slot layout's write safety (its
    ``dynamic_update_slice`` of the padded bucket would have been
    clamped DOWN over live positions); the paged write masks its pad
    (kv_cache.write_tokens) and does not need it. It stays because it
    decides which programs a long prompt near the cache's end runs
    (ROADMAP, debt (e))."""
    if max_chunk is not None:
        chunk_tokens = min(chunk_tokens or max_chunk, max_chunk)
    if not chunk_tokens or n_tokens <= chunk_tokens:
        return [(start, n_tokens)]
    chunks, pos, violated = [], 0, False
    while pos < n_tokens:
        ln = min(chunk_tokens, n_tokens - pos)
        violated = violated or start + pos + bucket_for(ln) > max_seq
        chunks.append((start + pos, ln))
        pos += ln
    if violated and n_tokens <= (max_chunk or n_tokens):
        return [(start, n_tokens)]
    return chunks
