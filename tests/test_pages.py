"""Host-side paged-KV bookkeeping (accelerate_tpu/serving/pages.py).

Pure-python/numpy contracts — no jax, no device: the refcounted free
list never leaks or double-frees, prefix-cache keying finds the longest
cached page-aligned prefix (and the partial-tail entry) by content, and
LRU eviction releases page references. The engine-level twins
(real arenas, real decode) live in tests/test_paged_serving.py.
"""

import numpy as np
import pytest

from accelerate_tpu.serving.pages import (
    PageAllocator,
    PagedTables,
    PrefixCache,
    PrefixEntry,
    _digest,
    _PrefixDigests,
)


class TestPageAllocator:
    def test_alloc_release_reuse_no_leak(self):
        alloc = PageAllocator(9, reserved=1)
        assert alloc.free_count == 8 and alloc.in_use == 0
        # 100 alloc/release cycles must neither leak nor grow the free list
        for _ in range(100):
            pages = [alloc.alloc() for _ in range(8)]
            assert None not in pages and alloc.alloc() is None  # exhausted
            assert alloc.in_use == 8
            for p in pages:
                assert alloc.release(p)
            assert alloc.free_count == 8 and alloc.in_use == 0

    def test_reserved_pages_never_handed_out(self):
        alloc = PageAllocator(4, reserved=2)
        got = {alloc.alloc() for _ in range(2)}
        assert got == {2, 3}

    def test_refcounts_shared_release(self):
        alloc = PageAllocator(4)
        p = alloc.alloc()
        alloc.retain(p)
        assert alloc.shared(p)
        assert not alloc.release(p)  # still referenced
        assert alloc.release(p)      # now free
        with pytest.raises(ValueError):
            alloc.release(p)
        with pytest.raises(ValueError):
            alloc.retain(p)


class TestPrefixCache:
    def _cache(self, num_pages=64, ps=4, **kw):
        alloc = PageAllocator(num_pages)
        return alloc, PrefixCache(alloc, page_size=ps, **kw)

    def _insert(self, alloc, cache, prompt):
        n = -(-prompt.size // cache.page_size)
        pages = [alloc.alloc() for _ in range(n)]
        cache.insert(prompt, pages)
        return pages

    def test_longest_aligned_prefix_wins(self):
        alloc, cache = self._cache()
        prompt = np.arange(10, dtype=np.int32)  # pages: [0:4) [4:8) [8:10)
        pages = self._insert(alloc, cache, prompt)
        # identical prompt, limited to size-1 (the engine always re-prefills
        # the last token for its logits): the 8-aligned entry must hit
        hit, entry = cache.lookup(prompt, limit=prompt.size - 1)
        assert hit == 8 and entry.pages == tuple(pages[:2])
        # longer prompt sharing the full 10 tokens hits the partial entry
        longer = np.concatenate([prompt, np.arange(50, 55, dtype=np.int32)])
        hit, entry = cache.lookup(longer)
        assert hit == 10 and entry.pages == tuple(pages)

    def test_content_mismatch_misses(self):
        alloc, cache = self._cache()
        self._insert(alloc, cache, np.arange(8, dtype=np.int32))
        other = np.arange(8, dtype=np.int32) + 1
        assert cache.lookup(other) == (0, None)
        assert cache.hit_ratio == 0.0

    def test_insert_retains_and_evict_releases(self):
        alloc, cache = self._cache()
        prompt = np.arange(9, dtype=np.int32)
        pages = self._insert(alloc, cache, prompt)
        # entries at 4, 8 and 9 tokens: page0 x3, page1 x2, page2 x1 refs
        assert alloc.refs[pages[0]] == 4  # 1 owner + 3 entries
        # the owner (slot) releases; cache refs keep pages alive
        for p in pages:
            alloc.release(p)
        assert alloc.in_use == 3
        cache.clear()
        assert alloc.in_use == 0 and not cache.entries

    def test_lru_eviction_order_and_cap(self):
        alloc, cache = self._cache(ps=4, max_entries=2)
        a = np.arange(4, dtype=np.int32)
        b = np.arange(4, dtype=np.int32) + 100
        self._insert(alloc, cache, a)
        self._insert(alloc, cache, b)
        assert len(cache.entries) == 2
        hit, e = cache.lookup(a)
        cache.record_hit(hit, e)  # COMMITTED hit touches a -> b becomes LRU
        self._insert(alloc, cache, np.arange(4, dtype=np.int32) + 200)
        assert len(cache.entries) == 2
        assert cache.lookup(a, limit=None)[0] == 4   # survived
        assert cache.lookup(b, limit=None)[0] == 0   # evicted

    def test_dtype_normalized_keys(self):
        alloc, cache = self._cache()
        self._insert(alloc, cache, np.arange(4, dtype=np.int64))
        assert cache.lookup(np.arange(4, dtype=np.int32))[0] == 4

    def test_hit_stats_count_committed_hits_only(self):
        """lookup() returning an entry does not move the hit gauges: the
        engine may shrink or decline the hit, and only record_hit() — with
        the final token count — counts."""
        alloc, cache = self._cache()
        prompt = np.arange(8, dtype=np.int32)
        self._insert(alloc, cache, prompt)
        hit, entry = cache.lookup(prompt)
        assert hit == 8 and entry is not None
        assert cache.hits == 0 and cache.hit_tokens == 0
        assert entry.hits == 0  # LRU recency is committed-hit based too
        cache.record_hit(0, entry)   # declined: still a miss in the gauges
        assert cache.hits == 0 and cache.hit_ratio == 0.0
        assert entry.hits == 0
        cache.record_hit(4, entry)   # committed after a shrink to 4 tokens
        assert cache.hits == 1 and cache.hit_tokens == 4
        assert entry.hits == 1


# -- the index before PR 45, kept as a plain oracle ---------------------------
# PrefixCache, GhostCache and _GhostShadow as they stood: the victim by a scan
# for the least ``last_used``, the candidate lengths by a set over every entry
# at every lookup, every candidate length digested from token 0. The classes in
# serving/pages.py do the same things at the cost of what they touch; the
# replay below holds them to these, event for event.


class _OracleShadow:
    def __init__(self, max_entries):
        self.max_entries, self.entries, self._clock, self.hits = int(max_entries), {}, 0, 0

    def _tick(self):
        self._clock += 1
        return self._clock

    def lookup(self, n, dig):
        for length in sorted({e[0] for e in self.entries.values()}, reverse=True):
            if length > n:
                continue
            e = self.entries.get(dig(length))
            if e is not None and e[0] == length:
                self.hits += 1
                e[1] = self._tick()
                return length
        return 0

    def insert(self, keyed_lengths):
        for length, key in keyed_lengths:
            e = self.entries.get(key)
            if e is not None:
                e[1] = self._tick()
                continue
            self.entries[key] = [length, self._tick()]
        while len(self.entries) > self.max_entries:
            del self.entries[min(self.entries, key=lambda k: self.entries[k][1])]


class _OracleGhost:
    def __init__(self, base_entries, multiples=(2, 4, 10), max_distances=4096):
        self.multiples = tuple(sorted({int(m) for m in multiples}))
        self.shadows = {m: _OracleShadow(m * int(base_entries)) for m in self.multiples}
        self.lookups = self.reuses = 0
        self._evicted, self._evicted_cap = {}, max(self.multiples) * int(base_entries)
        self._distances, self._max_distances = [], int(max_distances)

    def observe_lookup(self, prompt, limit=None):
        self.lookups += 1
        n = int(prompt.size if limit is None else min(prompt.size, limit))
        for shadow in self.shadows.values():
            shadow.lookup(n, lambda length: _digest(prompt[:length]))

    def observe_insert(self, keyed_lengths):
        for _, key in keyed_lengths:
            at = self._evicted.pop(key, None)
            if at is not None:
                self.reuses += 1
                self._distances.append(self.lookups - at)
                if len(self._distances) > self._max_distances:
                    del self._distances[: self._max_distances // 2]
        for shadow in self.shadows.values():
            shadow.insert(keyed_lengths)

    def observe_evict(self, key):
        self._evicted[key] = self.lookups
        while len(self._evicted) > self._evicted_cap:
            del self._evicted[next(iter(self._evicted))]

    def hit_ratio(self, multiple):
        return self.shadows[int(multiple)].hits / self.lookups if self.lookups else 0.0


class _OracleCache:
    def __init__(self, allocator, page_size, max_entries=512, ghost_multiples=(2, 4, 10), on_evict=None):
        self.allocator, self.page_size, self.max_entries = allocator, int(page_size), int(max_entries)
        self.entries, self._clock, self.on_evict = {}, 0, on_evict
        self.lookups = self.hits = self.hit_tokens = self.evictions = 0
        self.ghost = _OracleGhost(self.max_entries, ghost_multiples)

    def _tick(self):
        self._clock += 1
        return self._clock

    def lookup(self, prompt, limit=None):
        self.lookups += 1
        self.ghost.observe_lookup(prompt, limit)
        return self.peek(prompt, limit)

    def peek(self, prompt, limit=None):
        n = int(prompt.size if limit is None else min(prompt.size, limit))
        for length in sorted({e.token_len for e in self.entries.values()}, reverse=True):
            if length > n:
                continue
            entry = self.entries.get(_digest(prompt[:length]))
            if entry is not None and entry.token_len == length:
                return length, entry
        return 0, None

    def record_hit(self, tokens, entry=None):
        if tokens > 0:
            self.hits += 1
            self.hit_tokens += int(tokens)
            if entry is not None:
                entry.hits += 1
                entry.last_used = self._tick()

    def insert(self, prompt, pages, tenant="default"):
        ps = self.page_size
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        n = int(prompt.size)
        lengths = list(range(ps, n + 1, ps)) + ([n] if n % ps else [])
        keyed = [(length, _digest(prompt[:length])) for length in lengths]
        created = 0
        for length, key in keyed:
            hit = self.entries.get(key)
            if hit is not None:
                hit.last_used = self._tick()
                continue
            entry = PrefixEntry(key=key, token_len=length, pages=tuple(int(p) for p in pages[:-(-length // ps)]),
                                last_used=self._tick(), tokens=prompt[:length].copy(), tenant=str(tenant or "default"))
            for p in entry.pages:
                self.allocator.retain(p)
            self.entries[key] = entry
            created += 1
        self.ghost.observe_insert(keyed)
        while len(self.entries) > self.max_entries and self.evict_lru():
            pass
        return created

    def evict_lru(self):
        if not self.entries:
            return False
        self.evictions += 1
        key = min(self.entries, key=lambda k: self.entries[k].last_used)
        entry = self.entries.pop(key)
        if self.on_evict is not None:
            self.on_evict(entry)
        for p in entry.pages:
            self.allocator.release(p)
        self.ghost.observe_evict(key)
        return True


class _Replay:
    """One cache behind the steps an engine takes with it: look a prompt up,
    commit to the hit whole, shrunk by a page or not at all, map the hit's
    pages, grow the rest (evicting under page pressure), publish the prompt,
    and hold the slot's pages until a later step lets them go."""

    def __init__(self, cache_cls, page_size, num_pages, max_entries):
        self.alloc = PageAllocator(num_pages)
        self.victims = []
        self.cache = cache_cls(self.alloc, page_size, max_entries=max_entries,
                               on_evict=lambda e: self.victims.append((e.key, e.token_len, e.last_used)))
        self.live = []  # the pages of the slots still running, oldest first

    def _page(self):
        page = self.alloc.alloc()
        while page is None and self.cache.evict_lru():
            page = self.alloc.alloc()
        return page

    def admit(self, prompt, commit, tenant):
        ps = self.cache.page_size
        hit, entry = self.cache.lookup(prompt, limit=prompt.size - 1)
        found = (hit, entry and (entry.key, entry.token_len, entry.pages, entry.hits, entry.last_used))
        took = {"taken": hit, "shrunk": max(0, hit - ps), "declined": 0}[commit]
        self.cache.record_hit(took, entry if took else None)
        pages = list(entry.pages[: -(-took // ps)]) if took else []
        for p in pages:
            self.alloc.retain(p)
        while len(pages) < -(-prompt.size // ps):
            page = self._page()
            if page is None:  # every page pinned by a running slot: the admission is put off
                break
            pages.append(page)
        else:
            self.live.append(pages)
            return found, self.cache.insert(prompt, pages, tenant=tenant)
        for p in pages:
            self.alloc.release(p)
        return found, None

    def finish(self, index):
        for p in self.live.pop(index % len(self.live)):
            self.alloc.release(p)

    def state(self):
        c, g = self.cache, self.cache.ghost
        by_age = sorted(c.entries.values(), key=lambda e: e.last_used)
        return {
            "entries": [(e.key, e.token_len, e.pages, e.hits, e.last_used, e.tokens.tolist(), e.tenant) for e in by_age],
            "refs": list(self.alloc.refs), "free": list(self.alloc._free), "victims": list(self.victims),
            "counts": (c.lookups, c.hits, c.hit_tokens, c.evictions),
            "ghost": ([g.hit_ratio(m) for m in g.multiples], g.reuses, list(g._distances), list(g._evicted.items()),
                      [sorted(s.entries.items(), key=lambda kv: kv[1][1]) for s in g.shadows.values()]),
        }


def _trace(seed, page_size, steps=160):
    """Seeded steps over a few documents that sessions re-ask: prompts that
    share a document's prefix, end on and off a page boundary, and differ in
    their last tokens."""
    rng = np.random.RandomState(seed)
    docs = [rng.randint(3, 200, (int(rng.randint(2, 7)) * page_size + int(rng.randint(0, 3)),)).astype(np.int32)
            for _ in range(6)]
    out = []
    for _ in range(steps):
        kind = rng.choice(["admit", "admit", "admit", "finish", "finish", "evict", "peek"])
        doc = docs[int(rng.randint(len(docs)))]
        tail = rng.randint(3, 200, (int(rng.choice([0, 1, page_size - 1, page_size, page_size + 3])),)).astype(np.int32)
        prompt = np.concatenate([doc[: int(rng.randint(page_size, doc.size + 1))], tail])
        out.append((kind, prompt, str(rng.choice(["taken", "taken", "shrunk", "declined"])),
                    str(rng.choice(["default", "t1"])), int(rng.randint(1 << 16))))
    return out


REPLAYS = [  # page size, pages, entries: the entries run out first, then the pages
    (8, 96, 24), (8, 40, 400), (16, 96, 24), (16, 40, 400)]


@pytest.mark.parametrize("seed", [0, 1, 2])
@pytest.mark.parametrize("page_size,num_pages,max_entries", REPLAYS)
def test_replay_equals_the_index_before_pr45_event_for_event(page_size, num_pages, max_entries, seed):
    """Equal hits and entries, equal victims in equal order, equal refcounts
    and free lists, equal gauges and ghost state after every step; and the
    dict's order is the recency."""
    new, old = (_Replay(cls, page_size, num_pages, max_entries) for cls in (PrefixCache, _OracleCache))
    for kind, prompt, commit, tenant, pick in _trace(seed, page_size):
        if kind == "admit":
            assert new.admit(prompt, commit, tenant) == old.admit(prompt, commit, tenant)
        elif kind == "finish" and new.live:
            new.finish(pick), old.finish(pick)
        elif kind == "evict":
            assert new.cache.evict_lru() == old.cache.evict_lru()
        elif kind == "peek":
            (hit, entry), (hit0, entry0) = new.cache.peek(prompt), old.cache.peek(prompt)
            assert (hit, entry and entry.key) == (hit0, entry0 and entry0.key)
        assert new.state() == old.state()
        assert list(new.cache.entries) == [e[0] for e in new.state()["entries"]]  # least recently used first
        for shadow in new.cache.ghost.shadows.values():
            assert [e[1] for e in shadow.entries.values()] == sorted(e[1] for e in shadow.entries.values())
    c = new.cache
    assert c.evictions > 0 and c.hits > 0 and c.ghost.reuses > 0, "the trace exercises what it is there for"
    assert len(new.victims) == c.evictions
    assert c.evict_scanned == c.evictions  # one entry looked at an eviction


@pytest.mark.parametrize("page_size,num_pages,max_entries", REPLAYS)
def test_a_call_reads_its_prompt_once(page_size, num_pages, max_entries):
    """The cost in counts. A lookup's pass and an insert's read the prompt
    once each, so the cache's own ``digested_tokens`` rise by at most 2 n an
    admission of n tokens; the shadows digest for themselves only at lengths
    the lookup's table lacks (entries the cache has dropped and they keep),
    in one more pass of at most n - 1; an eviction looks at one entry."""
    run = _Replay(PrefixCache, page_size, num_pages, max_entries)
    c, g = run.cache, run.cache.ghost
    ghost_passes = 0
    for kind, prompt, commit, tenant, pick in _trace(7, page_size, steps=240):
        if kind == "finish" and run.live:
            run.finish(pick)
        if kind != "admit":
            continue
        n = prompt.size
        # the candidate lengths, from the entries themselves: the counted index has to agree
        held = {e.token_len for e in c.entries.values() if e.token_len < n}
        only_shadows = {e[0] for s in g.shadows.values() for e in s.entries.values() if e[0] < n} - held
        before = (c.digests, c.digested_tokens, g.digests, g.digested_tokens, c.entries_probed)
        _, created = run.admit(prompt, commit, tenant)
        digests, tokens, ghost_digests, ghost_tokens, probed = (
            b - a for a, b in zip(before, (c.digests, c.digested_tokens, g.digests, g.digested_tokens, c.entries_probed)))
        inserted = 0 if created is None else n
        assert tokens == max(held, default=0) + inserted <= 2 * n
        assert digests == len(held) + (created is not None) * len(range(page_size, n + 1, page_size)) + bool(
            inserted and n % page_size)
        assert probed <= len(held)
        assert (ghost_digests, ghost_tokens) == (len(only_shadows), max(only_shadows, default=0))
        ghost_passes += bool(only_shadows)
    assert ghost_passes and c.evictions and c.evict_scanned == c.evictions


@pytest.mark.parametrize("page_size", [8, 16])
def test_streamed_digests_are_the_keys_peers_exchange(page_size):
    """One hasher fed the prompt in ascending order gives ``_digest`` of every
    prefix, page-aligned or not, whatever the caller's integer width."""
    prompt = np.random.RandomState(page_size).randint(0, 1 << 20, (7 * page_size + 5,))
    digests = _PrefixDigests(prompt)
    aligned = list(range(page_size, prompt.size + 1, page_size))
    assert digests.extend(aligned) == (len(aligned), aligned[-1])
    partial = [1, page_size - 1, page_size + 1, 3 * page_size + 2, prompt.size]
    assert digests.extend(aligned + partial) == (len(partial), prompt.size)  # one more pass, its own, in ascending order
    assert digests.extend(aligned + partial) == (0, 0)
    for length in aligned + partial:
        assert digests.keys[length] == _digest(prompt[:length]) == _digest(prompt.astype(np.int32)[:length])


class TestPagedTables:
    def test_reset_restores_parking(self):
        t = PagedTables(2, 4, parking=0)
        t.rows[1, :2] = [5, 6]
        t.alloc_count[1] = 2
        assert t.slot_pages(1) == [5, 6]
        t.reset_slot(1)
        assert t.slot_pages(1) == [] and (t.rows[1] == 0).all()
