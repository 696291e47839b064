"""Paged KV arena: fixed-size pages, refcounted free list and a
copy-on-write prefix cache.

A dense ``num_slots x max_cache_len`` block would reserve ``max_cache_len``
of KV per slot no matter how long the request actually is, and every
request would pay a full prefill even when thousands share a templated
system prompt. The serving engine's storage layer is **pages**:

- K/V leaves become ``[num_pages, KVH, page_size, D]`` physical pages (a
  leading layer axis under ``scan_layers``); a per-slot **page table**
  ``[num_slots, pages_per_slot] int32`` maps each slot's position range
  ``[c*page_size, (c+1)*page_size)`` to a physical page. Page 0 is the
  reserved **parking page**: unallocated table entries point at it, and
  inactive slots' fused-step writes land there.
- the **free list + refcounts** live host-side (:class:`PageAllocator`);
  admission/growth/eviction are pure data changes (table-entry scatters),
  so a live engine never recompiles.
- the **prefix cache** (:class:`PrefixCache`) keys page-aligned prompt
  prefixes by token hash. A request whose prompt prefix is cached maps the
  shared pages into its table (refcount++) and prefills only the tail —
  near-zero TTFT for templated traffic. Shared pages are **copy-on-write**:
  the engine forks (copies) a page before the first divergent write, so a
  mutation by one slot can never perturb another slot's tokens.

Everything above the device helpers is plain-python/numpy bookkeeping and
imports **without jax or flax** (locked by tests/test_imports.py): a
router/scheduler tier can reason about page budgets on machines with no
accelerator stack. The device helpers (arena init, page forks, page
gathers and installs) import jax lazily at call time.
"""

from __future__ import annotations

import bisect
import hashlib
import re
from collections import OrderedDict
from dataclasses import dataclass, field
from typing import Optional

import numpy as np


# -- quantized-arena host helpers (jax-free, like everything above the
# device section: a router/admission tier sizes KV budgets on machines
# with no accelerator stack — locked by tests/test_imports.py) ------------

KV_CACHE_DTYPES = ("bf16", "int8", "int4")


def kv_cache_bits(kv_dtype) -> int:
    """Storage bits per K/V value for a ``kv_cache_dtype`` knob value
    (None/"bf16" -> 16). The host twin of
    ``utils.quantization.kv_cache_bits`` (which lives jax-side)."""
    if kv_dtype in (None, "bf16"):
        return 16
    if kv_dtype == "int8":
        return 8
    if kv_dtype == "int4":
        return 4
    raise ValueError(
        f"kv_cache_dtype must be one of {KV_CACHE_DTYPES}, got {kv_dtype!r}"
    )


def kv_payload_width(head_dim: int, kv_dtype) -> int:
    """Trailing payload dim of a K/V cache leaf: head_dim, or head_dim/2
    when int4 packs two values per byte."""
    if kv_cache_bits(kv_dtype) == 4:
        if head_dim % 2:
            raise ValueError(f"int4 KV needs an even head_dim, got {head_dim}")
        return head_dim // 2
    return head_dim


def kv_token_bytes(num_kv_heads: int, head_dim: int, kv_dtype,
                   cache_itemsize: int = 2, num_layers: int = 1) -> int:
    """HBM bytes one cached token costs across K and V (payload + the
    fp32 scale the quantized arena carries per (token, kv head)) — the
    capacity-planning number behind ``arena_hbm_bytes_per_slot`` and the
    ≥2x-slots math. ``cache_itemsize`` is the unquantized cache dtype's
    byte width (bf16 -> 2)."""
    bits = kv_cache_bits(kv_dtype)
    if bits == 16:
        per_value = num_kv_heads * head_dim * cache_itemsize
        return 2 * num_layers * per_value
    payload = num_kv_heads * kv_payload_width(head_dim, kv_dtype)
    scale = num_kv_heads * 4  # one fp32 per (token, kv head)
    return 2 * num_layers * (payload + scale)


def _digest(tokens: np.ndarray) -> bytes:
    """Stable content key for a token prefix (dtype-normalized so the same
    ids hash equally regardless of the caller's integer width)."""
    return hashlib.blake2b(
        np.ascontiguousarray(tokens, np.int32).tobytes(), digest_size=16
    ).digest()


class _PrefixDigests:
    """``_digest(prompt[:length])`` at many lengths of one prompt, for one
    reading of its bytes a pass: ``blake2b`` streams, so one hasher fed the
    prompt up to each length in ascending order gives at every stop the
    digest of the prefix so far. The keys are those of :func:`_digest`, byte
    for byte (peers exchange them: ``ServingEngine.kv_directory``,
    serving/tiers.py). ``keys`` maps a length to its key."""

    __slots__ = ("_bytes", "keys")

    def __init__(self, prompt: np.ndarray):
        self._bytes = memoryview(np.ascontiguousarray(prompt, np.int32).reshape(-1)).cast("B")
        self.keys: dict = {}

    def extend(self, lengths) -> tuple:
        """Digest those of ``lengths`` that ``keys`` lacks, in one ascending
        pass from the prompt's first token. Returns ``(digests made, tokens
        read)``: the work, for the caller's counters."""
        keys = self.keys
        missing = sorted({length for length in lengths if length not in keys})
        hasher, at, data = hashlib.blake2b(digest_size=16), 0, self._bytes
        for length in missing:
            hasher.update(data[4 * at: 4 * length])
            at = length
            keys[length] = hasher.digest()
        return len(missing), at


class _LengthIndex:
    """The distinct ``token_len`` of an index's entries, counted as entries
    come and go: the candidate lengths of a lookup without a visit to the
    entries. The sorted list is built again only after a length appeared or
    disappeared."""

    __slots__ = ("_count", "_ascending")

    def __init__(self):
        self._count: dict = {}  # token_len -> entries of that length
        self._ascending: Optional[list] = []

    def add(self, length: int):
        held = self._count.get(length, 0)
        self._count[length] = held + 1
        if not held:
            self._ascending = None

    def discard(self, length: int):
        left = self._count[length] - 1
        if left:
            self._count[length] = left
        else:
            del self._count[length]
            self._ascending = None

    def upto(self, n: int) -> list:
        """The lengths ``<= n``, ascending."""
        if self._ascending is None:
            self._ascending = sorted(self._count)
        return self._ascending[: bisect.bisect_right(self._ascending, n)]


class PageAllocator:
    """Refcounted free list over ``num_pages`` physical pages.

    Page ids ``< reserved`` are never handed out (page 0 is the parking
    page). A page is *free* iff its refcount is 0; ``alloc`` pops from the
    free list and sets refcount 1, ``retain`` adds a reference (prefix-cache
    sharing), ``release`` drops one and returns the page to the free list at
    zero. The free list is LIFO so recently-hot pages are reused first.
    """

    def __init__(self, num_pages: int, reserved: int = 1):
        if num_pages <= reserved:
            raise ValueError(
                f"num_pages ({num_pages}) must exceed reserved ({reserved})"
            )
        self.num_pages = int(num_pages)
        self.reserved = int(reserved)
        self.refs = [0] * num_pages
        self._free = list(range(num_pages - 1, reserved - 1, -1))  # pop() -> lowest id

    @property
    def free_count(self) -> int:
        return len(self._free)

    @property
    def in_use(self) -> int:
        return self.num_pages - self.reserved - len(self._free)

    def alloc(self) -> Optional[int]:
        """One fresh page with refcount 1, or None when exhausted."""
        if not self._free:
            return None
        page = self._free.pop()
        self.refs[page] = 1
        return page

    def retain(self, page: int):
        if self.refs[page] < 1:
            raise ValueError(f"retain of free page {page}")
        self.refs[page] += 1

    def release(self, page: int) -> bool:
        """Drop one reference; True when the page returned to the free list."""
        if self.refs[page] < 1:
            raise ValueError(f"release of free page {page}")
        self.refs[page] -= 1
        if self.refs[page] == 0:
            self._free.append(page)
            return True
        return False

    def shared(self, page: int) -> bool:
        return self.refs[page] > 1


@dataclass
class PrefixEntry:
    key: bytes
    token_len: int
    pages: tuple  # page ids covering [0, token_len)
    hits: int = 0
    last_used: int = 0
    # the entry's own token prefix + owning tenant: what the demote-on-
    # evict hook (serving/tiers.py) needs to rebuild the handoff blob
    # and attribute tier byte-seconds. None on entries inserted by
    # callers that predate tiering — those just can't demote.
    tokens: Optional[np.ndarray] = None
    tenant: str = "default"


class _GhostShadow:
    """Key-level LRU twin of a :class:`PrefixCache` at a scaled
    ``max_entries`` — entries are ``key -> [token_len, last_used]``, no
    pages, no allocator. Lookup/insert/evict follow the real cache's
    semantics exactly (longest-first probe, recency on committed hits and
    insert-touch, evict min ``last_used`` past capacity), so its hit count
    equals a brute-force ``PrefixCache(max_entries=N*base)`` replaying the
    same trace — the oracle tests/test_loadgen.py asserts against.

    What an operation costs is what it touches, at any capacity, as in the
    real cache: ``entries`` is in order of ``last_used`` (every touch takes a
    new, larger tick and moves the entry to the end, so an eviction pops the
    first key), and ``lengths`` counts the entries by ``token_len``, so a
    lookup takes its candidate lengths without a visit to the entries. (A
    scan for the minimum was three quarters of an admission's host time with
    the cache full, PERF.md, PR 37; the set of lengths over up to 65,000
    entries a lookup, PR 45.)"""

    __slots__ = ("max_entries", "entries", "lengths", "_clock", "hits")

    def __init__(self, max_entries: int):
        self.max_entries = int(max_entries)
        self.entries: OrderedDict = OrderedDict()  # key bytes -> [token_len, last_used], least recent first
        self.lengths = _LengthIndex()
        self._clock = 0
        self.hits = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def lookup(self, lengths: list, keys: dict) -> int:
        """Probe like ``PrefixCache.peek`` (the longest of ``lengths``, this
        shadow's own up to the prompt's limit, whose key in ``keys`` is an
        entry of that length), self-committing the hit: the simulation has
        no engine to decline it."""
        for length in reversed(lengths):
            key = keys[length]
            e = self.entries.get(key)
            if e is not None and e[0] == length:
                self.hits += 1
                self._touch(key, e)
                return length
        return 0

    def _touch(self, key, e):
        e[1] = self._tick()
        self.entries.move_to_end(key)  # the newest tick goes last

    def insert(self, keyed_lengths):
        for length, key in keyed_lengths:
            e = self.entries.get(key)
            if e is not None:
                self._touch(key, e)
                continue
            self.entries[key] = [length, self._tick()]
            self.lengths.add(length)
        while len(self.entries) > self.max_entries:
            _, (length, _) = self.entries.popitem(last=False)  # least last_used: see the class
            self.lengths.discard(length)


class GhostCache:
    """Ghost-cache economics telemetry for a :class:`PrefixCache`: what
    would larger capacities recover?

    Two instruments, both keys-only (no pages, no KV bytes — the whole
    point is measuring the value of storage that does NOT exist yet):

    - **capacity shadows**: one :class:`_GhostShadow` LRU simulation per
      multiple of the real cache's ``max_entries`` (default 2x/4x/10x),
      fed the same lookup/insert stream. ``hit_ratio(m)`` is the hit
      ratio the cache WOULD have at ``m x`` capacity — compare against
      ``serving/prefix_hit_ratio``; the gap is the reuse an entry-LRU
      host/disk tier (ROADMAP item 2) would serve.
    - **reuse-after-evict distances**: every key the real cache evicts is
      remembered (bounded, eviction-ordered); when a later ``insert``
      re-registers an evicted key — a re-prefill of KV the cache already
      held, the exact waste a tier absorbs — the distance in lookups
      since eviction is recorded.

    Shadows only model capacity-driven (``max_entries``) eviction: a
    simulated larger cache is assumed to keep its entries' KV in a tier,
    so the real arena's page pressure does not apply to it.
    """

    def __init__(self, base_entries: int, multiples=(2, 4, 10),
                 max_distances: int = 4096):
        self.multiples = tuple(sorted({int(m) for m in multiples}))
        if not self.multiples or self.multiples[0] < 1:
            raise ValueError(f"bad ghost multiples {multiples!r}")
        self.shadows = {
            m: _GhostShadow(m * int(base_entries)) for m in self.multiples
        }
        self.lookups = 0
        self.reuses = 0
        # what the shadows themselves cost an admission: the digests
        # observe_lookup had to compute itself, at lengths only a shadow
        # holds, and the tokens that pass read (lifetime; ghost_probes of
        # the serving/prefix_lookup span)
        self.digests = 0
        self.digested_tokens = 0
        self._evicted: dict = {}  # key -> lookup count at eviction
        self._evicted_cap = max(self.multiples) * int(base_entries)
        self._distances: list = []
        self._max_distances = int(max_distances)

    def observe_lookup(self, prompt: np.ndarray, limit: Optional[int] = None,
                       digests: Optional[_PrefixDigests] = None):
        """Replay one lookup through every shadow. ``digests``: the table the
        real cache's lookup made of this prompt; the lengths it holds are not
        digested again, those only a shadow holds (entries the real cache has
        dropped) take one pass of their own."""
        self.lookups += 1
        n = int(prompt.size if limit is None else min(prompt.size, limit))
        if digests is None:
            digests = _PrefixDigests(prompt)
        wanted = [shadow.lengths.upto(n) for shadow in self.shadows.values()]
        made, tokens = digests.extend(length for lengths in wanted for length in lengths)
        self.digests += made
        self.digested_tokens += tokens
        for shadow, lengths in zip(self.shadows.values(), wanted):
            shadow.lookup(lengths, digests.keys)

    def observe_insert(self, keyed_lengths):
        """``keyed_lengths``: the ``(length, key)`` pairs the real
        insert computed — shared so the prompt hashes exactly once."""
        for _, key in keyed_lengths:
            at = self._evicted.pop(key, None)
            if at is not None:
                self.reuses += 1
                self._distances.append(self.lookups - at)
                if len(self._distances) > self._max_distances:
                    del self._distances[: self._max_distances // 2]
        for shadow in self.shadows.values():
            shadow.insert(keyed_lengths)

    def observe_evict(self, key: bytes):
        self._evicted[key] = self.lookups
        while len(self._evicted) > self._evicted_cap:
            del self._evicted[next(iter(self._evicted))]

    def hit_ratio(self, multiple: int) -> float:
        shadow = self.shadows[int(multiple)]
        return shadow.hits / self.lookups if self.lookups else 0.0

    def reuse_distance_quantile(self, q: float) -> float:
        if not self._distances:
            return 0.0
        xs = sorted(self._distances)
        idx = min(len(xs) - 1, max(0, int(round(q * (len(xs) - 1)))))
        return float(xs[idx])

    def gauges(self) -> dict:
        """``serving/ghost_*`` gauge fragment merged into
        ``ServingEngine.metrics()`` (and so into rollup -> Prometheus
        exposition -> fleet merge; the 2x/4x/10x ratios average across
        replicas, reuse distances take the fleet-worst)."""
        out = {}
        for m in self.multiples:
            out[f"serving/ghost_hit_ratio_{m}x"] = self.hit_ratio(m)
        out["serving/ghost_reuses"] = self.reuses
        if self._distances:
            out["serving/ghost_reuse_distance_p50"] = (
                self.reuse_distance_quantile(0.5)
            )
            out["serving/ghost_reuse_distance_p99"] = (
                self.reuse_distance_quantile(0.99)
            )
        return out


class PrefixCache:
    """Prompt-prefix -> shared-pages map, keyed by token-content hash.

    Insertion registers every page-aligned prefix of a finished prompt
    (plus the full, possibly partial-page prompt itself) as an entry; each
    entry holds one allocator reference per covered page. Lookup walks the
    cached lengths longest-first and returns the deepest entry whose token
    hash matches the new prompt — O(distinct lengths) hash probes, no
    token-by-token trie. Eviction is LRU at entry granularity; a page's
    storage is reclaimed only when every referencing entry AND every
    mapped slot has released it (the allocator's refcount).

    **What an operation costs is what it touches, not what the cache holds**
    (PERF.md, PR 45): ``entries`` is in order of ``last_used`` (every touch
    takes its tick and moves the entry to the end), so an eviction pops the
    first key and releases the victim's pages; the candidate lengths are
    counted as entries come and go (:class:`_LengthIndex`), so a lookup
    takes them without a visit to the entries; and a call reads its prompt
    once (:class:`_PrefixDigests`): a lookup one pass up to its longest
    candidate length and a dict probe a length until the hit, an insert one
    pass over the prompt, a dict probe a length and one retain a page a new
    entry covers.
    """

    def __init__(self, allocator: PageAllocator, page_size: int,
                 max_entries: int = 512, ghost_multiples=(2, 4, 10),
                 ghost_base_entries: Optional[int] = None,
                 on_evict=None):
        self.allocator = allocator
        self.page_size = int(page_size)
        self.max_entries = int(max_entries)
        self.entries: OrderedDict = OrderedDict()  # key bytes -> PrefixEntry, least recently used first
        self._lengths = _LengthIndex()
        self._clock = 0
        self.lookups = 0
        self.hits = 0
        self.hit_tokens = 0
        # the work done, counted where it is done (lifetime; the engine's
        # serving/prefix_lookup, prefix_insert and page_grow spans take the
        # differences): digests computed by peek and insert and the tokens
        # their passes read, a pass its tokens once (the ghost shadows count
        # their own), entries a lookup probed, entries evicted, and entries
        # evict_lru looked at to find them (one an eviction)
        self.digests = 0
        self.digested_tokens = 0
        self.entries_probed = 0
        self.evictions = 0
        self.evict_scanned = 0
        # demote-on-evict hook: called with the victim PrefixEntry
        # BEFORE its page refs are released (the pages are still intact
        # on device, so the hook can gather them into a lower tier)
        self.on_evict = on_evict
        # ghost-cache economics telemetry (keys only — a few dict ops per
        # lookup/insert; pass ghost_multiples=None/() to disable).
        # ghost_base_entries overrides the shadows' 1x base: with a
        # host/disk tier attached, the base is the TOTAL (HBM+host+disk)
        # entry capacity so the 2x/4x/10x ratios keep answering "would a
        # bigger cache help?" about capacity beyond what now exists,
        # instead of re-measuring the tier just built.
        self.ghost = (
            GhostCache(
                int(ghost_base_entries) if ghost_base_entries
                else self.max_entries,
                ghost_multiples,
            )
            if ghost_multiples else None
        )

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def _touch(self, key: bytes, entry: PrefixEntry):
        entry.last_used = self._tick()
        self.entries.move_to_end(key)  # the newest tick goes last

    def lookup(self, prompt: np.ndarray, limit: Optional[int] = None):
        """Longest cached prefix of ``prompt`` with ``token_len <= limit``.
        Returns ``(hit_len, entry)`` or ``(0, None)``. The caller maps
        ``entry.pages[: ceil(hit_len / page_size)]`` into its slot table
        (retaining each) and prefills only ``prompt[hit_len:]`` — then
        reports what it actually used via :meth:`record_hit` (the engine
        may shrink or discard a hit whose tail plan would not fit the slot
        or would cost more prefill dispatches than a cold admission, and
        the hit-ratio gauges must reflect the final decision)."""
        self.lookups += 1
        digests = _PrefixDigests(prompt)
        found = self._probe(prompt, limit, digests)
        if self.ghost is not None:
            self.ghost.observe_lookup(prompt, limit, digests)
        return found

    def peek(self, prompt: np.ndarray, limit: Optional[int] = None):
        """:meth:`lookup` without side effects: the hit/lookup gauges and
        LRU recency stay untouched. The KV-handoff export path (a replica
        shipping cached pages to a peer) and router introspection probe
        with this — a probe is not serving traffic and must not skew the
        hit-ratio gauges or LRU-protect an entry it never admitted."""
        return self._probe(prompt, limit, _PrefixDigests(prompt))

    def _probe(self, prompt: np.ndarray, limit: Optional[int], digests: _PrefixDigests):
        """The cached lengths up to the limit digested in one ascending pass
        into ``digests``, then probed longest first."""
        n = int(prompt.size if limit is None else min(prompt.size, limit))
        lengths = self._lengths.upto(n)
        made, tokens = digests.extend(lengths)
        self.digests += made
        self.digested_tokens += tokens
        for at, length in enumerate(reversed(lengths), 1):
            entry = self.entries.get(digests.keys[length])
            if entry is not None and entry.token_len == length:
                self.entries_probed += at
                return length, entry
        self.entries_probed += len(lengths)
        return 0, None

    def record_hit(self, tokens: int, entry: Optional[PrefixEntry] = None):
        """Count a lookup hit that the caller actually committed to, with
        the (possibly shrunk) number of prefix tokens served. LRU recency
        moves here too: an entry whose hits are always declined must not
        stay LRU-protected, pinning its pages over genuinely useful ones."""
        if tokens > 0:
            self.hits += 1
            self.hit_tokens += int(tokens)
            if entry is not None:
                entry.hits += 1
                self._touch(entry.key, entry)

    def insert(self, prompt: np.ndarray, pages, tenant: str = "default") -> int:
        """Register ``prompt`` (whose KV now lives in ``pages``, position
        order) at every page-aligned prefix length plus its full length.
        Each new entry retains its covered pages. Returns the number of
        entries created."""
        ps = self.page_size
        prompt = np.array(prompt, np.int32).reshape(-1)  # one private copy, which the new entries slice
        n = int(prompt.size)
        lengths = list(range(ps, n + 1, ps))
        if n % ps:
            lengths.append(n)  # partial-page tail: the COW-fork case
        digests = _PrefixDigests(prompt)
        made, tokens = digests.extend(lengths)
        self.digests += made
        self.digested_tokens += tokens
        keyed = [(length, digests.keys[length]) for length in lengths]
        covered = tuple(int(p) for p in pages[: -(-n // ps)])
        tenant, retain = str(tenant or "default"), self.allocator.retain
        created = 0
        for length, key in keyed:
            hit = self.entries.get(key)
            if hit is not None:
                self._touch(key, hit)
                continue
            entry = PrefixEntry(
                key=key, token_len=length, pages=covered[: -(-length // ps)],
                last_used=self._tick(), tokens=prompt[:length], tenant=tenant,
            )
            for p in entry.pages:
                retain(p)
            self.entries[key] = entry
            self._lengths.add(length)
            created += 1
        if self.ghost is not None:
            self.ghost.observe_insert(keyed)
        while len(self.entries) > self.max_entries and self.evict_lru():
            pass
        return created

    def evict_lru(self) -> bool:
        """Drop the least-recently-used entry (releasing its page refs);
        False when the cache is empty. Called by the engine when the
        allocator cannot satisfy an admission or a decode-time page grow.
        With a demote hook attached, the victim's KV is offered to the
        lower tiers first — eviction demotes instead of dropping."""
        if not self.entries:
            return False
        self.evictions += 1
        self.evict_scanned += 1
        key, entry = self.entries.popitem(last=False)  # least last_used: see the class
        self._lengths.discard(entry.token_len)
        if self.on_evict is not None:
            # pages are still retained here: the hook may gather them
            try:
                self.on_evict(entry)
            except Exception:
                # demotion is an optimization; a failing tier must never
                # turn an eviction into an engine error
                pass
        for p in entry.pages:
            self.allocator.release(p)
        if self.ghost is not None:
            self.ghost.observe_evict(key)
        return True

    def clear(self):
        while self.evict_lru():
            pass

    @property
    def hit_ratio(self) -> float:
        return self.hits / self.lookups if self.lookups else 0.0


class PagedTables:
    """Host mirror of the device page tables: one np row per slot plus the
    allocated-entry count. Entries beyond ``alloc_count`` are parking-page
    padding (gathered but masked, never written by an active slot)."""

    def __init__(self, num_slots: int, pages_per_slot: int, parking: int = 0, aside: int = 0):
        self.num_slots = int(num_slots)
        self.pages_per_slot = int(pages_per_slot)
        self.parking = int(parking)
        self.rows = np.full((num_slots, pages_per_slot), parking, np.int32)
        self.alloc_count = [0] * num_slots
        # entries before ``released`` were given back behind a window: a
        # slot's pages are rows[released:alloc_count] (0 for a full kind)
        self.released = [0] * num_slots
        # a closing kind (CacheKind.closes): the row's last ``aside`` columns
        # hold the pages the open window's summaries are pooled into, once
        # the slot has them (``aside_held``); ``closed`` counts its windows
        self.aside = int(aside)
        self.aside_held = [False] * num_slots
        self.closed = [0] * num_slots

    def reset_slot(self, slot: int):
        self.rows[slot] = self.parking
        self.alloc_count[slot] = 0
        self.released[slot] = 0
        self.aside_held[slot] = False
        self.closed[slot] = 0

    def slot_pages(self, slot: int) -> list:
        pages = self.rows[slot, self.released[slot]: self.alloc_count[slot]]
        if self.aside_held[slot]:
            pages = np.concatenate([pages, self.rows[slot, self.pages_per_slot - self.aside:]])
        return [int(p) for p in pages]


class CacheKind:
    """One kind of state a model's layers keep in the serving cache, and
    how it is held. An attention kind is **paged**: its layers share a pool
    of physical pages (``allocator``), a page table a slot (``tables`` on
    the host, ``device_tables`` its device twin) and, for a window kind,
    the rule by which pages fall behind the window. A model of one kind has
    one, named "full", with the pages, tables and counts of before.

    ``window``: None for full attention, whose pages live as long as the
    slot; for a window layer the positions a query sees, its own included.
    ``layers`` and ``token_bytes`` (keys and values of one layer, at the
    widths the pages store) say what a page of this kind costs.

    A recurrent state ("state": a state-space mixer's, models/ssm.py) is
    **of a fixed size a slot and not paged**: ``num_pages`` 0, no pool, no
    table, ``token_bytes`` 0 and ``slot_bytes`` (all its layers' leaves of
    one slot), whatever the context's length.

    ``closes`` ``(window, chunk)``: a **closing window with pooled
    summaries** (EVA attention, ops/eva.py; a page is a chunk). The table is
    in *entry order*, ``[summaries of closed windows][tokens of the open
    window]``: a context of ``L`` positions holds :meth:`entries` ``(L //
    window) * (window // chunk) + L % window`` entries, not ``L``. While a
    window is open the device pools each filled page into one entry of the
    ``window / chunk / page`` pages the slot holds aside (the table's last
    columns); when it closes, :meth:`close_windows` puts those pages where
    the window's first token pages stood, gives its ``window / page`` token
    pages back to the pool and takes fresh pages aside. Everything that
    reckons a slot's pages from its length asks :meth:`entries`."""

    def __init__(self, name: str, window: Optional[int], num_pages: int,
                 num_slots: int, pages_per_slot: int, page_size: int,
                 layers: int, token_bytes: int, slot_bytes: int = 0,
                 closes: Optional[tuple] = None):
        self.name, self.window = name, window
        self.num_pages, self.page_size = int(num_pages), int(page_size)
        self.layers, self.token_bytes = int(layers), int(token_bytes)
        self.num_slots, self.slot_bytes = int(num_slots), int(slot_bytes)
        self.closes = None if closes is None else (int(closes[0]), int(closes[1]))
        self.allocator = self.tables = None
        aside = 0
        if self.closes is not None:
            w, c = self.closes
            aside = w // c // self.page_size
            # the pages of the most entries any context of the slot holds, and those aside
            most = max(self.entries(p) for p in range(pages_per_slot * self.page_size))
            need = most // self.page_size + 1 + aside
            if c != self.page_size or need > pages_per_slot:
                raise ValueError(
                    f"cache kind {name!r}: a page ({self.page_size}) must be a chunk ({c}), and "
                    f"a slot's table ({pages_per_slot} pages) must hold its most entries and "
                    f"the {aside} pages aside ({need}): max_cache_len at least two windows")
        if self.paged:
            self.allocator = PageAllocator(self.num_pages, reserved=1)
            self.tables = PagedTables(num_slots, pages_per_slot, parking=0, aside=aside)
        self.device_tables = None  # the engine puts the device twin here

    @property
    def paged(self) -> bool:
        return self.num_pages > 0

    @property
    def page_bytes(self) -> int:
        """Arena bytes one page of this kind takes over all its layers."""
        return self.layers * self.page_size * self.token_bytes

    def entries(self, length: int) -> int:
        """Entries a slot holds at a context of ``length`` positions, which
        is also the entry position ``length`` takes: ``length`` itself but
        for a closing kind, whose closed windows stand as one entry a chunk."""
        if self.closes is None:
            return length
        w, c = self.closes
        return (length // w) * (w // c) + length % w

    def close_windows(self, slot: int, next_pos: int) -> int:
        """Close the windows that lie wholly before ``next_pos``, the slot's
        next write (host bookkeeping; the caller uploads the row when this
        returns pages): the window's token pages go back to the pool, the
        pages of its summaries take their first columns, and the pages the
        next window's summaries are pooled into come out of those just given
        back. Returns the pages the slot holds fewer. A slot closes one
        window at a time: no dispatch's rows straddle a close."""
        if self.closes is None:
            return 0
        th, (w, _) = self.tables, self.closes
        released = 0
        while th.closed[slot] < next_pos // w:
            n = th.closed[slot]
            assert th.aside_held[slot] and next_pos // w == n + 1, "one window closes at a time"
            first, tokens, aside = n * th.aside, w // self.page_size, th.aside
            row = th.rows[slot]
            assert th.alloc_count[slot] == first + tokens, "the window's pages are all written"
            for idx in range(first, first + tokens):
                self.allocator.release(int(row[idx]))
            row[first:first + aside] = row[th.pages_per_slot - aside:]
            row[first + aside:first + tokens] = th.parking
            row[th.pages_per_slot - aside:] = [self.allocator.alloc() for _ in range(aside)]
            th.alloc_count[slot] = first + aside
            th.closed[slot] = n + 1
            released += tokens - aside
        return released

    def first_live_entry(self, next_pos: int) -> int:
        """The first table entry a slot still needs when its next query
        sits at ``next_pos``: every page before it lies wholly behind
        ``next_pos - window + 1``, the earliest position any later query
        sees. 0 for a full kind."""
        if self.window is None:
            return 0
        return max(0, next_pos - self.window + 1) // self.page_size

    def release_behind(self, slot: int, next_pos: int) -> int:
        """Give back the slot's pages that lie wholly behind the window of
        a query at ``next_pos`` (host bookkeeping only: no kernel reads a
        table entry before the window's first page, so the device table
        keeps its stale entries). Returns the pages released."""
        th = self.tables
        first = min(self.first_live_entry(next_pos), th.alloc_count[slot])
        n = 0
        for idx in range(th.released[slot], first):
            self.allocator.release(int(th.rows[slot][idx]))
            th.rows[slot][idx] = th.parking
            n += 1
        th.released[slot] = max(th.released[slot], first)
        return n

    def walked_tokens(self, pos: int) -> int:
        """Tokens the paged decode kernel walks in one layer of this kind
        for a slot whose last write lands at ``pos``: whole pages from the
        window's first (or the slot's first) through that one."""
        ps = self.page_size
        return (self.entries(pos) // ps + 1 - self.first_live_entry(pos)) * ps


# ---------------------------------------------------------------------------
# device helpers (lazy jax: the bookkeeping above must import accelerator-free)
# ---------------------------------------------------------------------------

# A cache leaf is found by its name, never by its rank: paged K/V leaves
# are ``cached_key`` / ``cached_value`` [num_pages, KVH, page_size, D] (+ a
# leading layer axis under a scanned stack; the two widths may differ), a
# quantized arena's ``*_scale`` leaves [num_pages, KVH, page_size, 1] move
# with their payloads through every generic tree op below (page gathers,
# installs, CoW forks), and everything else (``cache_index``, an encoder's
# memory) is not paged.
# A latent kind's one leaf is ``cached_latent`` [num_pages, 1, page_size,
# lanes]: one entry a token for all heads, the unit axis where the others
# have their kv heads, so that its page axis is theirs; it has no value twin.
PAGED_LEAF_NAMES = frozenset(
    ("cached_key", "cached_value", "cached_key_scale", "cached_value_scale", "cached_latent"))
# ... and so is a slot's recurrent state: ``ssm_state`` [slots, N, D] and
# ``conv_state`` [slots, K - 1, D] (models/ssm.py; the mixer with heads keeps
# [slots, D / 128, N, 128] and K - 1 float32 rows of D + 2 G N: 4 MB and
# 120 KB a layer at the published widths, against 320 KB and 30 KB), shaped by the
# number of slots and not by a pool. Under a scanned stack ``ssm_state`` has
# the rank of a page leaf or one more; every page operation below finds its
# leaves by name and leaves these alone.
STATE_LEAF_NAMES = frozenset(("ssm_state", "conv_state"))


def leaf_name(path) -> Optional[str]:
    """The last key of a tree path (a dict key or an attribute name), or of
    its ``jax.tree_util.keystr`` (the KV wire format's leaf paths)."""
    if isinstance(path, str):
        keys = re.findall(r"\w+", path)
        return keys[-1] if keys else None
    last = path[-1] if path else None
    return getattr(last, "key", getattr(last, "name", None))


def is_paged_leaf(path) -> bool:
    return leaf_name(path) in PAGED_LEAF_NAMES


def is_state_leaf(path) -> bool:
    return leaf_name(path) in STATE_LEAF_NAMES


def _page_axis(leaf) -> int:
    """A paged leaf's page axis: the fourth from the end."""
    return leaf.ndim - 4


def map_paged(fn, arena, *rest, other=lambda leaf, *_: leaf):
    """``fn(leaf, *rest_leaves)`` on the paged leaves, ``other`` on the rest."""
    import jax

    return jax.tree_util.tree_map_with_path(
        lambda path, leaf, *r: fn(leaf, *r) if is_paged_leaf(path) else other(leaf, *r),
        arena, *rest)


def paged_leaves(arena) -> list:
    """The paged leaves in the arena's flatten order."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(arena)
    return [leaf for path, leaf in flat if is_paged_leaf(path)]


def init_paged_arena(definition, params, num_slots: int, pages_per_slot: int,
                     placer, kinds=None):
    """All-zeros paged cache arena shaped by ``jax.eval_shape`` over the
    paged decode apply (no compile, no device compute, correct for any
    cache layout the family uses).
    ``kinds``: the cache kinds' names where the model states several (it
    then takes a page table a kind)."""
    import jax
    import jax.numpy as jnp

    def shape_fn(p):
        table = jnp.zeros((num_slots, pages_per_slot), jnp.int32)
        _, mutated = definition.apply(
            {"params": placer(p)},
            jnp.zeros((num_slots, 1), jnp.int32),
            positions=jnp.zeros((num_slots, 1), jnp.int32),
            use_cache=True,
            decode=True,
            cache_positions=jnp.zeros((num_slots,), jnp.int32),
            page_table={k: table for k in kinds} if kinds else table,
            mutable=["cache"],
        )
        return mutated["cache"]

    shapes = jax.eval_shape(shape_fn, params)
    return jax.tree_util.tree_map(lambda s: jnp.zeros(s.shape, s.dtype), shapes)


def arena_nbytes(arena) -> int:
    """Bytes of every leaf of the arena: pages, scales and a slot's state."""
    import jax

    return sum(int(l.nbytes) for l in jax.tree_util.tree_leaves(arena))


def state_nbytes(arena) -> int:
    """Bytes of the leaves that are a state a slot and not pages."""
    import jax

    flat, _ = jax.tree_util.tree_flatten_with_path(arena)
    return sum(int(leaf.nbytes) for path, leaf in flat if is_state_leaf(path))


def fork_page(arena, src, dst):
    """Copy physical page ``src`` -> ``dst`` across every K/V leaf (all
    layers) — the copy-on-write fork. Traced ``src``/``dst``: one compiled
    program forks any page."""
    import jax

    def copy(leaf):
        axis = _page_axis(leaf)
        page = jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=axis)
        return jax.lax.dynamic_update_slice_in_dim(leaf, page, dst, axis=axis)

    return map_paged(copy, arena)


def gather_page(arena, src):
    """Size-1 page slice of every K/V leaf at page ``src``, arena
    flatten order — the demote-on-evict read, and the exact mirror of
    :func:`install_page`'s write. Traced ``src``: one compiled program
    gathers any page, so a warmed engine demotes evicted prefixes into
    the host tier with zero recompiles (a gather by a per-call id *list*
    would compile per distinct page count)."""
    import jax

    return [jax.lax.dynamic_slice_in_dim(leaf, src, 1, axis=_page_axis(leaf))
            for leaf in paged_leaves(arena)]


def install_page(arena, page_tree, dst):
    """Write one physical page's worth of K/V (``page_tree``: the arena's
    pytree with every K/V leaf replaced by a size-1 page slice; non-K/V
    leaves are ignored) into page ``dst`` — the KV-handoff import write.
    Traced ``dst``: one compiled program installs any page, so a warmed
    engine imports handed-off pages with zero recompiles."""
    import jax

    def put(leaf, page):
        return jax.lax.dynamic_update_slice_in_dim(
            leaf, page.astype(leaf.dtype), dst, axis=_page_axis(leaf)
        )

    return map_paged(put, arena, page_tree)


def set_table_row(tables, slot, row):
    """Replace one slot's device page-table row (admission)."""
    return tables.at[slot].set(row)


def set_table_entry(tables, slot, idx, page):
    """Point one table entry at a physical page (growth / fork)."""
    return tables.at[slot, idx].set(page)
