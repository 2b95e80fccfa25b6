"""Paged-KV cache manager + continuous-batching decode engine.

The serving-side half of the paged-KV stack (the model math lives in
`models/llama.build_llama_paged_decode`, the attention kernel in
`ops/pallas/paged_attention`).  Reference capability: the Paddle inference
stack's `block_multihead_attention` + fused blockwise KV cache; the TPU
shape follows Ragged Paged Attention (arxiv 2604.15464) + vLLM-style
continuous batching:

  * `PagePool` — fixed-size REFCOUNTED page allocator over the shared KV
    page pool: `alloc` hands out pages at refcount 1, `share` lets the
    same physical page appear in many page tables (prefix cache), `free`
    decrements and only returns a page to the free list at refcount 0.
    Double frees, foreign pages, and duplicate ids inside one `free()`
    batch raise a typed `PageDoubleFreeError` BEFORE any state mutates.
  * `PrefixCache` — automatic prefix caching: a block-hash index (SHA-256
    of each page_size-aligned token block, chained on the parent block's
    hash, radix-style) mapping prompt prefixes to cached KV pages.
    Finished/preempted requests retire their pages INTO the cache instead
    of freeing them; later admissions attach the longest cached prefix
    read-only and prefill only the suffix.  A partially filled cached
    page is copied before anyone writes into it (copy-on-write).
  * `ServingEngine` — a fixed set of decode SLOTS stepped by ONE jitted
    executable; between steps, finished requests retire (EOS / token
    budget), their pages go to the prefix cache, and queued requests are
    admitted into the freed slots (prefill + first-token sample), so new
    traffic joins a RUNNING batch instead of waiting for the whole batch
    to drain — the throughput win of continuous batching over the
    static-batch `llama_generate_fused` baseline.  Long prompts prefill
    in fixed `prefill_chunk`-token chunks interleaved with decode
    horizons (chunked prefill), so time-to-first-token for queued short
    requests is bounded instead of head-of-line blocked.  With
    `speculative=K`, a host-side prompt-lookup n-gram index drafts up to
    K continuation tokens per greedy slot and one `verify_step` dispatch
    scores all K+1 positions — the engine accepts the longest matching
    draft prefix plus a bonus token (lossless under greedy sampling by
    construction), multiplying useful tokens per forward pass on
    repetitive/extractive traffic.

Pages are allocated LAZILY: a request holds ceil(len/page_size) pages at
every moment, growing one page at a time as decode crosses page
boundaries.  If the pool is momentarily empty, the engine walks the
serving degradation ladder (below) before stalling the slot for a step.

Self-healing (the serving degradation ladder: admit -> queue -> reject ->
evict cache -> preempt):

  * a bounded admission queue rejects overflow with a typed
    `AdmissionRejected` (backpressure) instead of growing unboundedly;
  * per-request deadlines retire overdue work (slot or queue) with
    `Request.timed_out` set, returning its pages;
  * pool exhaustion first EVICTS unreferenced prefix-cache pages (LRU,
    leaf-first along the hash chain) — cached pages are a performance
    opportunity, never a reason to refuse work;
  * when no slot can make progress even after eviction (the former
    hard-deadlock RuntimeError), the engine PREEMPTS a victim — the
    youngest / lowest-progress slot — returning its pages (via the cache,
    so the re-prefill itself can hit) and requeueing it at the queue
    head; re-admission re-prefills prompt + already-emitted tokens, so
    greedy outputs stay step-exact vs a never-preempted run;
  * injected page-pool pressure (`serve.pool_pressure` /
    `pagepool.alloc` fault points, resilience/faults.py) exercises all of
    the above deterministically on CPU.

Greedy outputs are bit-exact with the prefix cache on vs off (including
across preemption + re-prefill) — `tests/test_prefix_cache.py` asserts
token-for-token equality on every parity scenario.

Double-buffered async host loop (`overlap=True`, ROADMAP item 5): the
engine pipelines host scheduling against device execution at depth 1 —
dispatch N's sampled token / cache-length / budget / done state stays ON
DEVICE (`models/llama.make_paged_decode_horizon`) and feeds dispatch N+1
directly, then dispatch N's emitted tokens drain through ONE batched
fetch while N+1 runs.  EOS / budget / deadline / preemption decisions act
on the drained step with a BOUNDED LAG of one dispatch; budget-predicted
retirements hand their slot to the next admission before their final
tokens even land (`_detach_predicted`), so the lag costs no lane
idleness on budget-bound traffic.  `quiesce()` drains the pipeline to an
exact host-visible step boundary — `snapshot()`, `adopt`-driven routers,
`cancel()`, deadline sweeps of in-flight work, speculative verify
dispatches, and the degradation ladder all quiesce first, so every
existing exactness guarantee (greedy bit-exactness across the prefix
cache / chunked prefill / speculative decoding / preemption /
snapshot-restore / fleet-failover matrix) holds with overlap on.  On the
XLA CPU backend, buffer DONATION pins each dispatch to synchronous
execution (PERF.md §14's caveat, root-caused), so overlap mode trades
the in-place page update for async dispatch there; TPU keeps donation —
its dispatch is async regardless.

Async streaming (the ROADMAP item-4 front-end seed): `submit(...,
on_token=cb)` fires `cb(tok)` for every emitted token in order — at the
sync boundary in a synchronous engine, at the drain in an overlapped one
— and `Request.stream()` iterates tokens as they drain, driving the
engine until retirement; streamed tokens are exactly the final
`Request.generated` record.

Observability: `ServingEngine(..., telemetry=True)` threads a
`paddle_tpu.observability.Telemetry` through the step loop — request-
lifecycle traces (Chrome/Perfetto-exportable), latency histograms
(TTFT/TPOT/queue/per-phase host timing), and a crash flight recorder that
auto-dumps on stalls, recompile-budget failures, preemption storms, and
injected faults.  Telemetry off (default) is a no-op fast path: one flag
check per hook site, zero per-token work, outputs bit-identical either
way.  All timestamps are host clock reads at EXISTING sync boundaries —
telemetry adds no device round-trips (graftlint SYNC001 stays clean) and
no jitted code (sanitize(0) variant counts unchanged).

Profiler trace: independent of telemetry, every host phase of `step()` is a
`jax.profiler.TraceAnnotation` (`ServingEngine._span`): `serve.step` and,
tiling it, `serve.sched` (with the admission's `serve.prefill_dense` /
`serve.prefill_chunk` / `serve.first_token_sync` inside), `serve.provision`,
`serve.{decode,overlap,verify}_{dispatch,sync,record}` and
`serve.overlap_join_sync`, with `step` / `rid` / `tokens` / `padded` /
`pages` / `slots` / `k` as stats, and `launches` / `uploads` on every span
under which the engine launched an executable (one and one under a model
call's span: `stats()["step_launches"]` / `["step_uploads"]` are their
sums).  They cost nothing until somebody opens a profiler
session and then lie on the device events' clock, so an idle gap of the
device can be laid under the host phase that caused it
(benchmark/host_spans.py).  The kernels carry `kernel_metadata` labels and
the decode horizon's executable is `jit_decode_horizon`, for the same trace.
"""
from __future__ import annotations

import contextlib
import hashlib
import heapq
import json
import math
import time
import weakref
from collections import deque
from dataclasses import dataclass, field

import numpy as np

from ..analysis.sanitize import (RecompileBudgetError, instrument,
                                 jit_cache_size)
from ..observability.telemetry import ENGINE_PHASES, Telemetry
from ..resilience.faults import InjectedFault, fault_point

__all__ = ["PagePool", "PrefixCache", "Request", "ServingEngine",
           "serve_requests", "PoolCapacityError", "AdmissionRejected",
           "EngineStalledError", "PageDoubleFreeError", "KVHandoffError"]


class PoolCapacityError(ValueError):
    """The request can NEVER fit the configured pool / page-table geometry
    (a sizing error, distinct from malformed input)."""


class AdmissionRejected(RuntimeError):
    """The bounded admission queue is full — backpressure; retry later."""


class EngineStalledError(RuntimeError):
    """run() made no progress for max_stall_steps consecutive steps (only
    reachable under a never-clearing injected pool fault)."""


class PageDoubleFreeError(RuntimeError):
    """free()/share() saw a page holding no reference (double free or
    foreign page), or the same page id twice within one free() batch."""


class KVHandoffError(RuntimeError):
    """An ``export_kv`` packet cannot splice into this engine: mismatched
    page geometry, KV dtype, or tensor-parallel degree.  The caller's
    fallback is re-prefill (``adopt``), which walks the normal admission
    ladder and requantizes/reshards for THIS engine — greedy outputs stay
    bit-exact either way."""


class PagePool:
    """Fixed-size refcounted page allocator (the BlockManager analog):
    page ids 0..num_pages-1, LIFO free list for locality.  `alloc` returns
    pages at refcount 1; `share` lets a page appear in another page table
    (+1); `free` decrements and recycles at 0.  All misuse — double free,
    foreign page, duplicate ids in one batch — raises the typed
    `PageDoubleFreeError` before any state mutates, so fragmentation bugs
    surface immediately and never tear the pool."""

    def __init__(self, num_pages: int, page_size: int):
        if num_pages <= 0 or page_size <= 0:
            raise ValueError("num_pages and page_size must be positive")
        self.num_pages = int(num_pages)
        self.page_size = int(page_size)
        self._free = list(range(self.num_pages - 1, -1, -1))
        self._refs: dict[int, int] = {}
        # called with a page whose references drop to ONE: the prefix
        # cache over this pool installs it to learn when a page it holds
        # has become evictable
        self._on_sole_ref = None

    @property
    def num_free(self) -> int:
        return len(self._free)

    @property
    def num_allocated(self) -> int:
        """Pages holding at least one reference."""
        return len(self._refs)

    @property
    def num_referenced(self) -> int:
        """Total references across all page tables + the prefix cache
        (>= num_allocated; the excess is prefix sharing)."""
        return sum(self._refs.values())

    @property
    def _allocated(self):
        # backwards-compatible container view (tests use `p in _allocated`)
        return self._refs.keys()

    def refcount(self, page: int) -> int:
        return self._refs.get(int(page), 0)

    def alloc(self, n: int):
        """Pop n pages at refcount 1; raises RuntimeError when the pool
        cannot satisfy the request (callers check `num_free` first for
        graceful stalling).  Consults the `pagepool.alloc` fault point: a
        'trigger' spec forces the exhausted path, a 'raise' spec injects
        InjectedFault."""
        if n < 0:
            raise ValueError("alloc(n): n must be >= 0")
        injected = fault_point("pagepool.alloc", n=n, free=len(self._free))
        if n > len(self._free) or injected is not None:
            raise RuntimeError(
                f"PagePool exhausted{' (injected)' if injected else ''}: "
                f"requested {n} pages, {len(self._free)} "
                f"free of {self.num_pages}")
        pages = [self._free.pop() for _ in range(n)]
        for p in pages:
            self._refs[p] = 1
        return pages

    def share(self, pages):
        """+1 reference on each page (it appears in one more page table /
        the prefix cache).  Sharing an unallocated page is typed misuse."""
        pages = [int(p) for p in pages]
        for p in pages:
            if p not in self._refs:
                raise PageDoubleFreeError(
                    f"PagePool.share: page {p} is not allocated")
        for p in pages:
            self._refs[p] += 1
        return pages

    def free(self, pages):
        """-1 reference on each page; a page returns to the free list when
        its last reference drops.  The WHOLE batch is validated before any
        decrement (duplicate ids in one batch, double frees, and foreign
        pages raise `PageDoubleFreeError` with the pool untouched)."""
        pages = [int(p) for p in pages]
        seen = set()
        for p in pages:
            if p in seen:
                raise PageDoubleFreeError(
                    f"PagePool.free: page {p} appears more than once in one "
                    f"free() batch (each reference must be freed by its own "
                    f"holder)")
            seen.add(p)
            if p not in self._refs:
                raise PageDoubleFreeError(
                    f"PagePool.free: page {p} is not allocated "
                    "(double free or foreign page)")
        for p in pages:
            left = self._refs[p] - 1
            if left:
                self._refs[p] = left
                if left == 1 and self._on_sole_ref is not None:
                    self._on_sole_ref(p)
            else:
                del self._refs[p]
                self._free.append(p)


def _pages_up_to(n: int, page_size: int) -> int:
    """ceil(m / page_size) summed over m = 1..n: the pages a context
    growing token by token to ``n`` had to read, over all its steps."""
    q, r = divmod(n, page_size)
    return page_size * q * (q + 1) // 2 + r * (q + 1)


_ROOT = b"\x00root"                   # parent digest of block 0


def _chain_digest(parent: bytes, block) -> bytes:
    """One link of the chained block hash: ``sha256(parent + tokens)``.
    THE block-hash implementation — :class:`PrefixCache` indexing and the
    fleet's prefix-affinity router both route through here, so a
    router-side chain computed from a prompt is bit-identical to the
    cache-side chain the serving replica indexed."""
    return hashlib.sha256(
        parent + np.ascontiguousarray(block, np.int32).tobytes()).digest()


def prefix_chain_hashes(tokens, page_size: int) -> list[bytes]:
    """Chained SHA-256 block-hash digests of every full ``page_size``-
    aligned block of ``tokens``, in chain order (digest i identifies the
    WHOLE prefix through block i, exactly as :class:`PrefixCache` indexes
    it).  The trailing partial block is not hashed — partial tails are
    keyed by exact content, not by chain digest.

    This is the public seam between the cache and the fleet router
    (serving/routing.py): both sides MUST produce identical chains, so
    the affinity lookup finds the replica that actually holds the KV.
    Note :meth:`PrefixCache.lookup` caps its match at ``len(tokens) - 1``
    (one suffix token must remain to prefill); a router mirroring the
    attach behavior passes ``tokens[:-1]``."""
    tokens = np.asarray(tokens, np.int32).reshape(-1)
    ps = int(page_size)
    parent = _ROOT
    out: list[bytes] = []
    for i in range(len(tokens) // ps):
        parent = _chain_digest(parent, tokens[i * ps:(i + 1) * ps])
        out.append(parent)
    return out


class _CacheEntry:
    __slots__ = ("key", "parent", "page", "tokens", "tick", "children",
                 "queued")

    def __init__(self, key, parent, page, tokens=None):
        self.key = key                # chained SHA-256 digest (None: partial)
        self.parent = parent          # parent block's digest (or _ROOT)
        self.page = page              # physical page id (cache holds 1 ref)
        self.tokens = tokens          # None for full blocks; bytes for the
        self.tick = 0                 #   partial tail block's token content
        self.children = 0             # cached entries chained under this one
        self.queued = None            # tick of its live eviction-heap item


class PrefixCache:
    """Automatic prefix cache: a chained block-hash index over PagePool
    pages (the vLLM automatic-prefix-caching / RadixAttention analog).

    Every page_size-aligned token block hashes as
    ``sha256(parent_digest + block_tokens)`` — chaining makes the digest
    identify the whole prefix, so a dict lookup per block walks the radix
    path without storing a tree.  Entries hold ONE pool reference each;
    `lookup` returns matched pages WITHOUT taking references (callers
    attach via `PagePool.share`).  A retired sequence's trailing partial
    block is indexed too (by parent + exact token content): attaching it
    saves up to page_size-1 more prefill tokens, and because the attaching
    request will WRITE into that page's empty tail, the engine copies it
    first (copy-on-write).

    Eviction is LRU over entries that are pure cache (pool refcount 1)
    and leaves of the hash chain (no cached children) — evicting an inner
    block would strand its descendants unreachable while they still hold
    pages.  Those entries are kept in a min-heap by last use, so a victim
    costs O(log entries), not a pass over the whole cache: every
    evictable entry has one item at its current tick (pushed when it is
    touched, when its last child goes, and when the pool tells the cache
    a page of its dropped to one reference), and an item that went stale
    — a newer tick, a new child or holder — is dropped when it surfaces."""

    def __init__(self, pool: PagePool, page_size: int):
        self.pool = pool
        self.page_size = int(page_size)
        self._full: dict[bytes, _CacheEntry] = {}
        # partial-tail entries indexed by parent digest, so lookup touches
        # only the tails chained under the matched prefix — never the
        # whole cache (admission is the serving hot path)
        self._partial: dict[bytes, dict[bytes, _CacheEntry]] = {}
        self._tick = 0
        self.insertions = 0
        self.evictions = 0
        self.evict_visits = 0         # heap items eviction examined
        self._by_page: dict[int, _CacheEntry] = {}   # one entry a page
        self._heap: list = []         # (tick, entry): the eviction index
        pool._on_sole_ref = self._sole_ref
        # optional ``notify(kind, digests)`` listener (kind "insert" |
        # "evict", digests = full-block chain digests): the fleet router
        # keeps its per-replica cached-chain summary current through this
        # hook.  Partial tails are content-keyed, not chain-keyed, so
        # they never notify — the summary tracks full blocks only.
        self.notify = None

    def __len__(self) -> int:
        return len(self._by_page)

    def pages(self):
        """Every page the cache holds a reference on (one per entry)."""
        return iter(self._by_page)

    def _touch(self, e: _CacheEntry):
        self._tick += 1
        e.tick = self._tick
        self._enqueue(e)

    def _digest(self, parent: bytes, block) -> bytes:
        return _chain_digest(parent, block)

    def chain_digests(self):
        """Every FULL-block chain digest currently indexed (the router-
        summary seed for a replica whose cache was built before the
        listener attached — e.g. a snapshot-restored engine)."""
        return self._full.keys()

    # -- lookup / attach ---------------------------------------------------
    def lookup(self, tokens):
        """Longest cached prefix of `tokens` -> (full_pages, partial).

        full_pages: page ids of the matched full blocks, in order.
        partial: None, or (page_id, m) — a cached partially filled page
        whose first m tokens extend the match (the attaching engine MUST
        copy-on-write it before prefilling into its tail).

        The match is capped at len(tokens)-1 so at least one suffix token
        remains to prefill — its logits feed the first sample.  No
        references are taken; callers `share()` what they attach."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        ps = self.page_size
        limit = len(tokens) - 1
        parent = _ROOT
        pages = []
        n = 0
        while (n + 1) * ps <= limit:
            key = self._digest(parent, tokens[n * ps:(n + 1) * ps])
            e = self._full.get(key)
            if e is None:
                break
            self._touch(e)
            pages.append(e.page)
            parent = key
            n += 1
        partial = None
        rem = tokens[n * ps:limit]
        if len(rem):
            best_m, best_e = 0, None
            for e in self._partial.get(parent, {}).values():
                et = np.frombuffer(e.tokens, np.int32)
                L = min(len(et), len(rem))
                m = 0
                while m < L and et[m] == rem[m]:
                    m += 1
                if m > best_m:
                    best_m, best_e = m, e
            if best_e is not None:
                self._touch(best_e)
                partial = (best_e.page, best_m)
        return pages, partial

    # -- insertion ---------------------------------------------------------
    def register(self, tokens, pages, with_partial: bool = False):
        """Index this sequence's blocks: every full block always, plus the
        trailing partial block when `with_partial` (retire path — the page
        will receive no more writes).  The cache takes its OWN pool
        reference on each newly inserted page; blocks whose digest is
        already cached are left as-is (first writer wins, the caller's
        duplicate copy stays private)."""
        tokens = np.asarray(tokens, np.int32).reshape(-1)
        ps = self.page_size
        parent = _ROOT
        n_full = len(tokens) // ps
        inserted: list[bytes] = []
        for i in range(n_full):
            key = self._digest(parent, tokens[i * ps:(i + 1) * ps])
            e = self._full.get(key)
            if e is None:
                self.pool.share([pages[i]])
                e = _CacheEntry(key, parent, int(pages[i]))
                self._full[key] = e
                self._by_page[e.page] = e
                if parent in self._full:
                    self._full[parent].children += 1
                self.insertions += 1
                inserted.append(key)
            self._touch(e)
            parent = key
        if inserted and self.notify is not None:
            self.notify("insert", inserted)
        if with_partial:
            tail = np.ascontiguousarray(tokens[n_full * ps:], np.int32)
            if len(tail) and n_full < len(pages):
                tb = tail.tobytes()
                tails = self._partial.setdefault(parent, {})
                if tb not in tails:
                    self.pool.share([pages[n_full]])
                    e = _CacheEntry(None, parent, int(pages[n_full]),
                                    tokens=tb)
                    tails[tb] = e
                    self._by_page[e.page] = e
                    if parent in self._full:
                        self._full[parent].children += 1
                    self.insertions += 1
                    self._touch(e)

    # -- eviction ----------------------------------------------------------
    def _can_evict(self, e: _CacheEntry) -> bool:
        return (e.tokens is not None or e.children == 0) \
            and self.pool.refcount(e.page) == 1

    def _enqueue(self, e: _CacheEntry):
        """Give an evictable entry its heap item at its current tick, unless
        it has one."""
        if e.queued != e.tick and self._can_evict(e):
            e.queued = e.tick
            heapq.heappush(self._heap, (e.tick, e))
            if len(self._heap) > 2 * len(self._by_page) + 64:
                self._reindex()         # mostly stale items: start afresh

    def _sole_ref(self, page: int):
        e = self._by_page.get(page)
        if e is not None:
            self._enqueue(e)

    def _entries(self) -> list:
        return list(self._full.values()) + [
            e for d in self._partial.values() for e in d.values()]

    def _reindex(self):
        """Rebuild what the entries determine — children counts, the page
        map, the eviction heap — from the entries alone (a restore writes
        the entries directly; a heap gone mostly stale is compacted the
        same way)."""
        entries = self._entries()
        for e in entries:
            e.children = 0
        for e in entries:
            if e.parent in self._full:
                self._full[e.parent].children += 1
        self._by_page = {e.page: e for e in entries}
        self._heap = []
        for e in entries:
            e.queued = e.tick if self._can_evict(e) else None
            if e.queued is not None:
                self._heap.append((e.tick, e))
        heapq.heapify(self._heap)

    def check_index(self):
        """The eviction index agrees with the entries: one page an entry,
        and every evictable entry holds an item at its current tick."""
        entries = self._entries()
        assert self._by_page == {e.page: e for e in entries}, \
            "prefix cache: page map out of step with its entries"
        live = {id(e) for t, e in self._heap if t == e.tick == e.queued}
        stray = [e.page for e in entries
                 if self._can_evict(e) and id(e) not in live]
        assert not stray, f"evictable pages missing from the heap: {stray}"

    def evict(self, n_pages: int) -> int:
        """Drop up to n_pages LRU cache-only leaf entries, returning their
        pages to the free list; returns how many pages were freed.  Walks
        chains back-to-front: evicting a leaf can make its parent a leaf,
        which then competes by its own tick within the same call."""
        victims = []
        while len(victims) < n_pages and self._heap:
            tick, e = heapq.heappop(self._heap)
            self.evict_visits += 1
            if tick != e.tick or e.queued != tick:
                continue                # superseded by a newer item
            e.queued = None
            if self._can_evict(e):
                self._drop(e)
                victims.append(e.page)
        self.pool.free(victims)
        self.evictions += len(victims)
        return len(victims)

    def _drop(self, e: _CacheEntry):
        """Unindex e; its page reference is the caller's to free."""
        if e.tokens is None:
            del self._full[e.key]
            if self.notify is not None:
                self.notify("evict", [e.key])
        else:
            tails = self._partial[e.parent]
            del tails[e.tokens]
            if not tails:
                del self._partial[e.parent]
        del self._by_page[e.page]
        parent = self._full.get(e.parent)
        if parent is not None:
            parent.children -= 1
            if not parent.children:
                self._enqueue(parent)


class _NgramDraft:
    """Prompt-lookup n-gram draft proposer (self-speculative decoding —
    no draft model, no extra weights): a suffix-match index over this
    request's prompt + emitted tokens.  Each (min_n..max_n)-gram maps to
    the start of its most recent continuation; `propose(k)` returns up to
    k tokens that followed the LONGEST matching suffix n-gram the last
    time it occurred.  When the match runs off the end of the sequence,
    the continuation extrapolates periodically at the match lag — exact
    for cyclic output and free to be wrong otherwise (a rejected draft
    costs nothing extra: the verify dispatch is padded to a static K
    anyway).  Index updates are O(max_n) per emitted token."""

    __slots__ = ("toks", "min_n", "max_n", "_idx")

    def __init__(self, tokens, min_n: int = 1, max_n: int = 3):
        self.min_n, self.max_n = int(min_n), int(max_n)
        self._idx = [dict() for _ in range(self.max_n - self.min_n + 1)]
        self.toks: list[int] = []
        for t in np.asarray(tokens, np.int32).reshape(-1):
            self.append(int(t))

    def append(self, tok: int):
        self.toks.append(int(tok))
        # index the n-grams ending at the PREVIOUS position: deferring the
        # insert by one token means (a) every indexed occurrence has at
        # least one continuation token, and (b) the current suffix can
        # never match itself
        e = len(self.toks) - 1            # continuation start
        if e <= 0:
            return
        for j in range(len(self._idx)):
            n = self.min_n + j
            if e >= n:
                self._idx[j][tuple(self.toks[e - n:e])] = e

    def propose(self, k: int) -> list:
        """Up to k draft tokens continuing the longest-matching suffix
        n-gram's most recent earlier occurrence; [] when nothing matches."""
        if k <= 0:
            return []
        T = len(self.toks)
        for j in range(len(self._idx) - 1, -1, -1):   # longest n first
            n = self.min_n + j
            if T < n:
                continue
            pos = self._idx[j].get(tuple(self.toks[-n:]))
            if pos is None:
                continue
            out = []
            for i in range(k):
                src = pos + i
                # past the end: the sequence "continues" with the lag-
                # periodic extension (out already holds those predictions)
                out.append(self.toks[src] if src < T else out[src - T])
            return out
        return []


@dataclass
class Request:
    """One serving request: prompt + generation budget + sampling params."""
    rid: int
    prompt: np.ndarray                 # int32 [T]
    max_new_tokens: int = 32
    temperature: float = 0.0
    top_p: float = 1.0
    eos_token_id: int | None = None
    deadline: float | None = None      # absolute engine-clock cutoff
                                       #   (time.perf_counter unless a
                                       #   telemetry clock is injected)
    # filled by the engine
    generated: list = field(default_factory=list)
    submit_time: float = 0.0
    admit_time: float = 0.0            # FIRST admission into a slot (0.0
                                       #   until admitted; preserved across
                                       #   preemption re-admissions so
                                       #   queue_time keeps its meaning)
    first_token_time: float = 0.0      # TTFT = first_token_time - submit_time
    finish_time: float = 0.0
    timed_out: bool = False            # retired overdue (possibly partial)
    preemptions: int = 0               # times evicted + requeued mid-flight
    cached_prefix_tokens: int = 0      # prefix-cache tokens attached (total
                                       #   across re-prefills)
    draft_proposed: int = 0            # speculative draft tokens proposed
    draft_accepted: int = 0            #   ... greedy-verified AND emitted
                                       #   (an EOS/budget freeze mid-run
                                       #   discards the tail uncounted)
    slot: int = -1                     # the engine slot it rides, or last
                                       #   rode (-1: never admitted)
    trace_id: int | None = None        # fleet-wide stitching id: one per
                                       #   END-TO-END request, shared by the
                                       #   frontend/router/replica trace
                                       #   records across migrations and
                                       #   snapshot restores (observability
                                       #   .distributed.TraceStitcher)
    # async-streaming front end (not serialized; a restored Request
    # streams through a fresh subscription)
    on_token: object | None = field(default=None, repr=False, compare=False)
    _engine: object | None = field(default=None, repr=False, compare=False)

    @property
    def draft_accept_rate(self) -> float:
        """Fraction of this request's proposed draft tokens the verify
        step accepted (0.0 when nothing was ever proposed)."""
        return self.draft_accepted / self.draft_proposed \
            if self.draft_proposed else 0.0

    @property
    def retire_time(self) -> float:
        """When the request left the engine (finish, deadline, or queued
        timeout) — an alias of finish_time that can't drift from it."""
        return self.finish_time

    @property
    def queue_time(self) -> float:
        """Seconds waiting for FIRST admission (0.0 until admitted).
        first_token_time alone never distinguished this wait from prefill:
        ttft == queue_time + prefill_time."""
        return self.admit_time - self.submit_time if self.admit_time else 0.0

    @property
    def ttft(self) -> float:
        """Time to first token, seconds (0.0 until the first token)."""
        return self.first_token_time - self.submit_time \
            if self.first_token_time else 0.0

    @property
    def prefill_time(self) -> float:
        """First-admission prefill latency: ttft minus the queue wait."""
        if not (self.first_token_time and self.admit_time):
            return 0.0
        return self.first_token_time - self.admit_time

    @property
    def tpot(self) -> float:
        """Mean seconds per output token AFTER the first (time-per-output-
        token; 0.0 until retired with >= 2 generated tokens)."""
        n = len(self.generated) - 1
        if n <= 0 or not self.first_token_time or not self.finish_time:
            return 0.0
        return (self.finish_time - self.first_token_time) / n

    @property
    def output_ids(self) -> np.ndarray:
        return np.concatenate([self.prompt,
                               np.asarray(self.generated, np.int32)])

    def stream(self, max_stall_steps: int = 1000,
               cancel_on_close: bool = True):
        """Iterate this request's tokens in emission order, DRIVING the
        owning engine between yields until the request retires (the
        single-threaded analog of an async token stream; fed from the
        overlap drain when the engine is double-buffered).  The streamed
        sequence is exactly the final ``generated`` record — a token is
        yielded once it is host-visible, never re-ordered, never skipped.
        Safe to call after retirement (yields the recorded tokens and
        returns).  Raises :class:`EngineStalledError` after
        ``max_stall_steps`` consecutive no-progress engine steps (only
        reachable under a never-clearing injected fault window).

        A consumer that exits EARLY — ``break``, generator ``close()``,
        or the generator being garbage-collected — CANCELS the request
        (``cancel_on_close=False`` opts out): a disconnected client must
        free its pages mid-decode, not keep a slot decoding to nobody.
        Normal exhaustion retires the request first, so completion never
        cancels anything."""
        i = 0
        stalled = 0
        try:
            while True:
                while i < len(self.generated):
                    yield self.generated[i]
                    i += 1
                if self.finish_time:
                    return
                eng = self._engine() if self._engine is not None else None
                if eng is None:
                    raise RuntimeError(
                        "Request.stream: the owning engine is gone and the "
                        "request never retired")
                # consecutive ENGINE no-progress steps, same as run(): a
                # step that progressed other requests resets the counter
                # even if this request yielded nothing yet
                stalled = 0 if eng.step() else stalled + 1
                if stalled >= max_stall_steps:
                    raise EngineStalledError(
                        f"Request.stream: no engine progress for {stalled} "
                        f"consecutive steps waiting on rid={self.rid}")
        finally:
            if cancel_on_close and not self.finish_time:
                eng = self._engine() if self._engine is not None else None
                if eng is not None:
                    eng.cancel(self.rid)


class _Slot:
    __slots__ = ("req", "pages", "pending", "pending_dev", "stalled",
                 "admit_seq", "prefill_pos", "ctx", "resuming", "chunk_step",
                 "draft", "spec_k")

    def __init__(self, req, pages, pending, admit_seq=0):
        self.req = req
        self.pages = pages             # list of physical page ids, in order
        self.pending = pending         # last sampled token, not yet in cache
        self.pending_dev = None        # overlap mode: the admission-sampled
                                       #   first token, still ON DEVICE and
                                       #   unrecorded (drained later); while
                                       #   a lane rides the device carry,
                                       #   both pending fields are None
        self.stalled = False
        self.admit_seq = admit_seq     # monotonically increasing admit order
        self.prefill_pos = None        # tokens prefilled so far; None once
        self.ctx = None                #   decoding (chunked-prefill state)
        self.resuming = False          # re-admission after preemption
        self.chunk_step = -1           # engine step of the last chunk run
                                       #   (one chunk per slot per step)
        self.draft = None              # _NgramDraft (speculative mode only)
        self.spec_k = 0                # adaptive per-slot draft length


class _LaneRec:
    """One lane of an in-flight decode dispatch: which slot it was
    dispatched for, whether the drain must also record the slot's
    admission-deferred first token, and — for budget-predicted
    retirements whose slot was already handed to a successor — the
    detached retirement state (`retiring` + the cache length the
    predecessor had when it was detached)."""
    __slots__ = ("s", "slot", "take_first", "retiring", "base_len")

    def __init__(self, s, slot, take_first):
        self.s = s
        self.slot = slot
        self.take_first = take_first
        self.retiring = False
        self.base_len = 0


class _Inflight:
    """One double-buffered decode dispatch in flight: the un-fetched
    device outputs (``out`` plus the carried token/length/budget/done
    state the NEXT dispatch consumes directly), the lane records the
    drain will replay, and ``srcs`` — slot identity per lane at dispatch
    time, so the next dispatch only carries lanes whose slot is unchanged
    (a retired/preempted/re-admitted lane falls back to host state).
    In overlap mode the dispatch itself runs on the engine's one-worker
    thread and ``fut`` holds its pending result; ``ServingEngine._resolve``
    fills the output fields (and optionally rebinds the engine's page
    buffers) when someone needs them."""
    __slots__ = ("fut", "out", "toks", "lengths", "rem", "done", "K",
                 "greedy", "lanes", "srcs", "overlapped")

    def __init__(self, K, greedy, lanes, srcs, overlapped):
        self.fut = None
        self.out = None
        self.toks = None
        self.lengths = None
        self.rem = None
        self.done = None
        self.K = K
        self.greedy = greedy
        self.lanes = lanes
        self.srcs = srcs
        self.overlapped = overlapped


# ---------------------------------------------------------------------------
# The model calls of one engine step.  Each is ONE executable fed by ONE
# packed int32 upload: a field that is an argument of its own is an upload
# of its own (~0.3 ms of the device waiting, on the chip), and what the host
# computes with `jax.numpy` before a call — a key split, a zero fill, a
# merge — is an executable of its own (~0.5 ms).  The layouts live HERE (the
# horizon's beside it, `models/llama.pack_decode_state`); the engine, and
# the compile rehearsals that lower what the engine lowers
# (`perf/chip_fit.py`), use these and nothing else.
# ---------------------------------------------------------------------------
def pack_prefill(ids, true_len, slot, temperature, top_p, page_row):
    """A dense prefill's host state: ``true_len | slot | temperature |
    top_p | page_row [P] | ids [T_bucket]`` (the two floats as the int32
    words of their bits: `jax.lax.bitcast_convert_type` gives them back)."""
    return np.concatenate([
        np.asarray([true_len, slot], np.int32),
        np.asarray([temperature, top_p], np.float32).view(np.int32),
        np.asarray(page_row, np.int32), np.asarray(ids, np.int32).ravel()])


def pack_chunk(ids, pos, c, slot, table):
    """A prefill chunk's host state: ``pos | c | slot | table [P_slice] |
    ids [C_bucket]``.  Two of its widths vary, so the chunk executable
    takes ``C=`` as a static argument."""
    return np.concatenate([
        np.asarray([pos, c, slot], np.int32), np.asarray(table, np.int32),
        np.asarray(ids, np.int32).ravel()])


def make_step_calls(family, max_pages_per_seq):
    """``(decode_horizon, prefill_sample, prefill_chunk, sample_logits)``
    over a `PagedFamily`'s fns: what `ServingEngine` jits (the first three
    with argument 1, the cache, donated; the horizon and the dense prefill
    once for ``greedy=True`` and once for ``False``).  Every fn that draws
    takes the engine's key, splits it inside (`split_call_key`: one split
    a call, greedy or not) and returns the next key; the cache is the LAST
    output and the key the one before it."""
    import jax
    import jax.numpy as jnp
    from ..models.llama import (_sample_per_request, split_call_key,
                                make_paged_decode_horizon)
    P = int(max_pages_per_seq)

    def sample_one(logits, sub, temp_top_p):
        return _sample_per_request(logits[None], sub, temp_top_p[:1],
                                   temp_top_p[1:])[0]

    # prefill + first-token sample fused into ONE dispatch per admission
    # (a separate sample call would double the per-admission dispatches)
    def prefill_sample(params, cache, key, ints, *, greedy):  # graftlint: jit
        logits, cache = family.prefill(params, ints[4 + P:][None], ints[0],
                                       ints[4:4 + P], ints[1], cache)
        key, sub = split_call_key(key)
        if greedy:
            tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        else:
            tok = sample_one(logits, sub, jax.lax.bitcast_convert_type(
                ints[2:4], jnp.float32))
        return tok, key, cache

    def prefill_chunk(params, cache, ints, *, C, **last):  # graftlint: jit
        # ``last=`` only from an engine whose family takes it
        # (`PagedFamily.chunk_takes_last`): static, like C
        n = ints.shape[0]
        return family.prefill_chunk(params, ints[n - C:][None], ints[0],
                                    ints[1], ints[3:n - C], ints[2], cache,
                                    **last)

    # single-logits NUCLEUS sampler for the final chunk of a chunked /
    # suffix prefill and a verify's sampled lanes (the chunk executable
    # itself is sampling-agnostic so one executable serves every request)
    def sample_logits(logits, key, temp_top_p):       # graftlint: jit
        key, sub = split_call_key(key)
        return sample_one(logits, sub, temp_top_p), key

    return (make_paged_decode_horizon(family.decode_step,
                                      sample_fn=_sample_per_request),
            prefill_sample, prefill_chunk, sample_logits)


# every live engine, for the tests' refcount-invariant leak guard
# (tests/conftest.py checks each one after every test)
_LIVE_ENGINES: "weakref.WeakSet[ServingEngine]" = weakref.WeakSet()


class ServingEngine:
    """Continuous-batching decode engine over the paged KV cache.

    params: the (embed, block, head) pytrees `build_functional_llama` /
    `functional_params_from_layer` produce.  One jitted decode executable
    covers the whole run; prefill executables are cached per prompt-length
    bucket (per chunk size once `prefill_chunk` is set).

    Which model it serves is the CONFIGURATION's to say:
    ``config.paged_family(...)`` returns the family's paged fns over one
    cache pytree (`models/paged_family.py`), and the engine never asks for
    a model's name.  Three families are served.  ``llama`` (`LlamaConfig`:
    the Llama-shaped dense decoders; a cache of K/V pages) has every
    feature below.  ``nemotron_h`` (`models/nemotron_h.NemotronHConfig`:
    Mamba-2 + attention + LatentMoE layers, sparse experts inside the
    paged fns) keeps, beside the K/V pages of its attention layers, a
    convolution tail and an SSM state a SLOT and Mamba layer in the same
    cache: a slot's state starts from zero when a run starts at position 0
    (admission, slot reuse, the re-prefill after a preemption), is carried
    from prefill chunk to prefill chunk, and is left alone by a decode step
    the slot is not live in.  For such a ``recurrent`` family the engine
    runs WITHOUT a prefix cache (a cached prefix is pages without the state
    that belongs to them) and refuses, with an error that names what is
    missing: ``speculative``, ``quantize``, ``kv_dtype``, ``mesh``,
    ``snapshot("full_kv")``, ``export_kv`` and ``import_kv``
    (``snapshot("compact")`` / ``adopt`` re-prefill and work).  Its fns
    count on the device, inside the carried cache, what ``stats()`` reports
    as ``moe_*``, ``ssm_*`` and ``decode_state_bytes_moved``, and log every
    consumed token's expert selections by slot and position;
    ``recurrent_state(rid)`` reads a slot's state and its log (``moe_sel``:
    what a rollout pool replays the routing of, and what the benchmark
    routes its reference by).  ``mla_moe`` (`models/mla_moe.MlaMoeConfig`:
    multi-head latent attention + sigmoid-routed SwiGLU experts with shared
    experts) has ONE page store, of compressed rows (``page_leaves =
    ("latent",)``: every layer's state is pages, so the prefix cache,
    copy-on-write, preemption and the page transfers work as for K/V
    pages); it refuses ``speculative``, ``quantize``, ``kv_dtype`` and
    ``mesh`` by name, counts ``latent_*`` and ``moe_*`` on the device and
    keeps the same selection log (and the greedy token's log-probability)
    a slot for ``recurrent_state(rid)``.

    `prefix_cache=True` (default) turns on automatic prefix caching:
    retired requests park their KV pages in a block-hash index, and later
    prompts sharing a page-aligned prefix attach those pages read-only and
    prefill only the suffix.  `prefill_chunk=N` bounds any single prefill
    dispatch to N tokens, interleaving long-prompt prefill with decode
    horizons (chunked prefill).  `speculative=K` turns on lossless
    self-speculative decoding: a host-side n-gram index over each
    request's prompt + emitted tokens drafts up to K continuation tokens
    (prompt-lookup — no draft model), one `verify_step` dispatch scores
    all K+1 positions, and the engine accepts the longest draft prefix
    whose argmax matches, emitting up to K+1 tokens per forward pass.
    All three knobs preserve greedy outputs bit-exactly vs the plain
    engine.  `overlap=True` double-buffers the host loop: step N+1 is
    scheduled and dispatched while step N's decode is still in flight,
    with the sampled-token/length/budget/done state carried ON DEVICE
    between dispatches and emitted tokens drained one batched fetch
    behind (bounded-lag retirement; `quiesce()` forces an exact
    boundary) — greedy outputs stay bit-exact vs `overlap=False` across
    the whole feature matrix.  `telemetry=True` (or a configured
    `observability.Telemetry`) records request-lifecycle traces, latency
    histograms, and the crash flight recorder — also without touching
    outputs.

    `kv_dtype="int8"|"fp8"` stores KV pages quantized with per-(page,
    head, token-row) absmax scales held in the pool (~4x more pages per
    byte at int8 — PagePool capacity is the admission bottleneck, so this
    is a direct concurrent-user win); `quantize=8` snaps the serving
    weights onto the per-channel int8 grid (serving/quant.py).  Both keep
    the engine deterministic and bit-exact against ITSELF across every
    feature above; parity vs the f32 engine is exact-match-rate gated
    (`serving.quant.parity_report`, tests/test_quant.py), not
    bit-equality — quantization is lossy by definition."""

    def __init__(self, params, config, num_slots: int = 4,
                 page_size: int = 16, num_pages: int | None = None,
                 max_pages_per_seq: int | None = None, dtype=None,
                 attention_impl: str = "auto", interpret: bool = False,
                 prompt_bucket: int = 32, decode_horizon: int = 8,
                 seed: int = 0, max_queue: int | None = None,
                 prefix_cache: bool = True, prefill_chunk: int | None = None,
                 speculative: int | None = None, spec_max_ngram: int = 3,
                 overlap: bool = False,
                 telemetry: "Telemetry | bool | None" = None,
                 name: str = "engine", kv_dtype: str | None = None,
                 quantize=None, mesh=None, mp_axis: str = "mp",
                 quantized_allreduce: bool = False):
        import jax
        import jax.numpy as jnp
        self._jax, self._jnp = jax, jnp
        # quantized serving plane (ROADMAP item 2): kv_dtype stores KV
        # pages int8/fp8 with per-(page, head, row) absmax scales held in
        # the pool's device arrays; quantize=<bits|True|"int8"> snaps the
        # serving weights onto the per-channel int grid at construction
        # (serving/quant.py).  Both knobs keep the engine deterministic
        # and self-bit-exact across the whole feature matrix — parity vs
        # the f32 engine is gated by serving.quant.parity_report instead
        # of bit-equality (quantization is lossy by definition).
        self.kv_dtype = None if kv_dtype is None else str(kv_dtype)
        # tensor-parallel serving (ROADMAP item 1): mesh=<Mesh binding
        # mp_axis> shards Q/KV heads, KV pages, and the MLP weight columns/
        # rows over mp — the whole horizon runs under shard_map with ONE
        # AllReduce per transformer layer (f32 psum, or the EQuARX int8
        # grid with quantized_allreduce=True; distributed/quant_collectives).
        # The dispatch/drain loop below is mesh-oblivious: every scalar the
        # host touches is replicated.
        self.mesh = mesh
        self.mp_axis = str(mp_axis)
        self.tp = 1 if mesh is None else int(mesh.shape[mp_axis])
        self.quantized_allreduce = bool(quantized_allreduce and self.tp > 1)
        # THE seam between the engine and the model it serves: the
        # configuration names its family's paged fns
        # (models/paged_family.py); nothing below asks which model this is,
        # only what the family can do (`recurrent`, `verify_step`, ...)
        self.num_slots = int(num_slots)
        self.page_size = int(page_size)
        cap_pages = math.ceil(config.max_position_embeddings / page_size)
        self.max_pages_per_seq = int(max_pages_per_seq or cap_pages)
        if num_pages is None:
            num_pages = self.num_slots * self.max_pages_per_seq
        family = config.paged_family(
            page_size=page_size, num_pages=num_pages,
            num_slots=self.num_slots,
            max_pages_per_seq=self.max_pages_per_seq, dtype=dtype,
            attention_impl=attention_impl, interpret=interpret,
            kv_dtype=self.kv_dtype, mesh=mesh, mp_axis=self.mp_axis,
            quantized_allreduce=self.quantized_allreduce)
        self.family = family
        if quantize and not family.int8_weights:
            raise NotImplementedError(
                f"quantize: the {family.name} family's leaves have no int8 "
                f"grid in serving/quant.py (written for the Llama-shaped "
                f"tree)")
        if speculative and family.verify_step is None:
            raise NotImplementedError(
                f"speculative: the {family.name} family has no verify step "
                f"(over recurrent state, scoring drafted tokens needs a "
                f"state checkpoint a drafted token, to rewind to on a "
                f"rejection; over latent pages, the drafted rows' scores)")
        if quantize:
            bits = 8 if quantize is True or quantize == "int8" \
                else int(quantize)
            from ..serving.quant import quantize_params
            params = quantize_params(params, bits=bits)
            self.quantize_bits = bits
        else:
            self.quantize_bits = None
        # replica identity: rides the serve.crash / serve.wedge fault-point
        # ctx so a fleet drill can target one replica (match={"engine": ...})
        self.name = str(name)
        # per-model-fn compile-cache miss counters (analysis.sanitize
        # instrumentation; stats()["jit_cache_misses"]) + the underlying
        # jitted fns for jit_variants() accounting
        self.jit_cache_misses: dict[str, int] = {}
        self._jit_fns: dict[str, list] = {}
        self.config = config
        self.params = params
        self.pool = PagePool(num_pages, page_size)
        # a cached prefix is K/V pages alone: a family whose slots also hold
        # recurrent state may never attach one without the state that
        # belongs to it, and nothing snapshots that state at page
        # boundaries yet — so such a family runs with NO prefix cache
        self.cache = PrefixCache(self.pool, page_size) \
            if prefix_cache and not family.recurrent else None
        self.prefill_chunk = None if prefill_chunk is None \
            else max(1, int(prefill_chunk))
        self.prompt_bucket = int(prompt_bucket)
        self.decode_horizon = max(1, int(decode_horizon))
        # speculative=K: lossless self-speculative decoding — n-gram drafts
        # verified K+1 positions at a time (greedy slots only; 0/None off)
        self.speculative = 0 if not speculative else int(speculative)
        self.spec_max_ngram = max(1, int(spec_max_ngram))
        # overlap=True: double-buffered async host loop (pipeline depth 1).
        # Buffer donation pins a dispatch to SYNCHRONOUS execution on the
        # XLA CPU backend (the PERF.md §14 "dispatch blocks" caveat,
        # root-caused) — but dropping donation would copy the whole page
        # pool every step.  Overlap mode therefore issues its decode
        # dispatches from a ONE-WORKER thread: donation (and the in-place
        # page update) is kept on every backend, the worker chains each
        # dispatch on the previous one's future, and the main thread only
        # blocks at the drain — true async on CPU, a no-op wrapper on a
        # backend whose dispatch is already async.
        self.overlap = bool(overlap)
        self._inflight: _Inflight | None = None
        self._executor = None
        if self.overlap:
            from concurrent.futures import ThreadPoolExecutor
            self._executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix=f"{self.name}-dispatch")
            weakref.finalize(self, self._executor.shutdown, wait=False)
        # telemetry=True -> default Telemetry(); None/False -> OFF, and off
        # is a no-op fast path: every hook site below is one `is not None`
        # flag check, zero per-token Python work (observability/telemetry.py)
        self.telemetry: Telemetry | None = \
            Telemetry() if telemetry is True else (telemetry or None)
        # ONE clock domain: request timestamps (submit/admit/first-token/
        # retire/deadlines) share the telemetry clock when one is attached,
        # so an injected fake clock drives EVERY timestamp deterministically
        # (default Telemetry clock is time.perf_counter — no behavior change)
        self._clock = self.telemetry.clock if self.telemetry is not None \
            else time.perf_counter

        # ONE pytree holds everything the paged executables keep on the
        # device between calls.  The leaves the family names
        # (`family.page_leaves`) are its page stores: ``["k"]`` / ``["v"]``,
        # each a raw [L, Hkv, NP+1, ps, D] array (f32/bf16) or a
        # {"q": data, "s": scales} dict (kv_dtype set), or ONE store of
        # latent rows ``["latent"]`` [L, 1, NP+1, ps, W]; a family adds its
        # per-slot state, logs and counters as further leaves.  The
        # engine only hands the cache on: every paged executable takes it
        # DONATED, carries it whole through its layer loop (rows written in
        # place, the layer indexed inside the attention kernel — no
        # executable copies, slices or relays out the pool) and returns it
        # as its last output, which `_call_paged` rebinds.  What the engine
        # itself knows of the layout is the page axis, axis 2 of every leaf
        # of the named page stores (`_copy_page`; snapshot/restore through
        # gather/scatter_kv_pages)
        self._cache = family.init_cache()
        # where `_upload` places a packed host row: the default device, or
        # under TP replicated over the mesh like every scalar the host
        # touches (the key and the overlap carry's fillers with it)
        self._host_sharding = None
        if self.tp > 1:
            # commit params + pages onto the mesh with the same specs the
            # shard_map region expects, so every jitted fn compiles ONE
            # variant against stably-placed operands (no silent resharding,
            # no per-call device_put of the weights)
            from jax.sharding import NamedSharding, PartitionSpec
            param_specs, page_spec = family.mesh_specs(self.mp_axis)
            self.params = params = jax.tree_util.tree_map(
                lambda s, x: jax.device_put(x, NamedSharding(mesh, s)),
                param_specs, params,
                is_leaf=lambda s: isinstance(s, PartitionSpec))
            pg = NamedSharding(mesh, page_spec)
            self._cache = jax.tree_util.tree_map(
                lambda a: jax.device_put(a, pg), self._cache)
            self._host_sharding = NamedSharding(mesh, PartitionSpec())
        self._page_bytes = None        # lazy page_bytes cache

        # decode HORIZON: K decode+sample steps fused into one fori_loop
        # dispatch (admission/retirement happen between horizons).  A
        # per-token python loop pays one host dispatch per token; K
        # amortizes it K-fold (speeds: not measured on this code).  The
        # loop body lives
        # with the model math (models/llama.make_paged_decode_horizon);
        # it returns the sampled-token/length/budget/done carry as DEVICE
        # values so the overlapped engine feeds dispatch N+1 straight from
        # dispatch N's outputs — the synchronous engine passes no carry,
        # and the math is bit-identical either way.  The horizon, the dense
        # prefill (+ fused first-token sample), the chunk and the sampler
        # each take their per-call host state PACKED (`make_step_calls`)
        (self._horizon_fn, self._prefill_fn, prefill_chunk_fn,
         self._sample_fn) = make_step_calls(family, self.max_pages_per_seq)

        # copy-on-write page copy (src | dst are a traced pair: ONE
        # executable covers every copy).  tree_map keeps it generic over
        # the page-store layout: a raw array copies its page rows, a
        # quantized {"q","s"} store copies data AND scales — the page axis
        # is axis 2 of every leaf of the stores the family names
        # (`page_leaves`: K and V, or one latent store) by construction
        # (whatever else the cache holds belongs to slots, not to pages).
        def _copy_page(cache, src_dst):               # graftlint: jit
            src, dst = src_dst[0], src_dst[1]

            def cp(a):
                return a.at[:, :, dst].set(a[:, :, src])
            return {**cache, **{name: jax.tree_util.tree_map(cp, cache[name])
                                for name in family.page_leaves}}

        self._horizon_jit = {}         # (K, greedy) -> jitted horizon
        self._prefill_jit = {}         # (T_bucket, greedy) -> jitted prefill
        # one wrapper: jax.jit caches per (C_pad, packed length) — the
        # static chunk width tells the ids from the page-table slice
        self._chunk_jit = self._jit("prefill_chunk", prefill_chunk_fn,
                                    donate_argnums=(1,),
                                    static_argnames=("C", "last"))
        self._sample_jit = None        # lazily jitted nucleus sampler
        self._copy_jit = self._jit("page_copy", _copy_page,
                                   donate_argnums=(0,))
        # one wrapper: drafts pad to the STATIC K+1 query width, so the
        # verify executable compiles once per engine K (jax.jit caches by
        # shape) even when slots draft fewer tokens or none at all
        self._verify_jit = None if family.verify_step is None else \
            self._jit("verify_step", family.verify_step, donate_argnums=(4,))

        # host-side slot state
        S, P = self.num_slots, self.max_pages_per_seq
        self._slots: list[_Slot | None] = [None] * S
        self._page_tables = np.zeros((S, P), np.int32)
        self._lengths = np.zeros((S,), np.int32)
        self._temps = np.zeros((S,), np.float32)
        self._top_ps = np.ones((S,), np.float32)
        # ... and `temps | top_ps` as the horizon's device row: uploaded
        # again only after an admission changed a value (None = stale)
        self._sampling_dev = None
        self._queue: deque[Request] = deque()
        self._finished: dict[int, Request] = {}
        self._next_rid = 0
        # the PRNG key is DEVICE state like the cache: every executable
        # that draws splits it inside and returns the next key, which the
        # engine rebinds with the cache (no split executable of its own)
        self._key = jax.device_put(jax.random.PRNGKey(seed),
                                   self._host_sharding)
        # overlap: what a dispatch carries when no previous dispatch's
        # outputs are there to carry — made once, here, never by step()
        self._no_carry = self._zero_tok = None
        if self.overlap:
            put = lambda a: jax.device_put(a, self._host_sharding)
            self._no_carry = (put(np.zeros((S,), np.int32)),) * 3 \
                + (put(np.zeros((S,), bool)),)
            self._zero_tok = put(np.int32(0))
        self.max_queue = None if max_queue is None else int(max_queue)
        self._admit_seq = 0
        self._pressure = False         # this-step injected pool pressure
        self._step_seq = 0             # step() invocations (chunk pacing)
        self.steps_run = 0
        self.tokens_generated = 0
        self.preemptions = 0           # victim evictions (self-healing)
        self.timeouts = 0              # deadline retirements
        self.rejections = 0            # AdmissionRejected count
        self.cache_hits = 0            # admissions that attached a prefix
        self.cache_hit_tokens = 0      # prefill tokens skipped via the cache
        self.prefill_tokens = 0        # un-cached prompt tokens of the
                                       #   requests ADMITTED (counted at
                                       #   admission; their chunks may
                                       #   still be to run)
        self.prefill_calls = 0         # dense prefills + prefill chunks run
        self.prefill_tokens_dispatched = 0  # prompt tokens handed to a
                                       #   prefill executable, counted at
                                       #   each dense / chunk call ...
        self.prefill_tokens_padded = 0  # ... and the padded rows that call
                                       #   computed (T_bucket / C_bucket)
        self.prefill_kv_pages_written = 0  # ... and the pages their K/V
                                       #   rows lie on: what a prefill's
                                       #   page-run writer issues an
                                       #   update for (a head, side and
                                       #   layer alike), where the row
                                       #   form issued one a token
        self.decode_kv_tokens_attended = 0  # KV positions the horizon's
                                       #   live decode steps had to read
                                       #   (host ints at the drain) ...
        self.decode_kv_pages_attended = 0  # ... and the pages that hold
                                       #   them: what the ragged kernel's
                                       #   page loop walked
        self.cache_evictions = 0       # cached pages evicted under pressure
        self.cache_evict_visits = 0    # eviction-heap items examined for them
        self.cow_copies = 0            # copy-on-write page copies
        self.verify_steps = 0          # speculative verify dispatches
        self.draft_tokens_proposed = 0  # draft tokens sent to verify
        self.draft_tokens_accepted = 0  # ... whose argmax matched
        self.overlap_steps = 0         # dispatches issued double-buffered
                                       #   (a previous step still in flight)
        self.fused_sample_steps = 0    # steady-state dispatches that emitted
                                       #   TOKENS on-device (fused greedy
                                       #   argmax / in-horizon sampling) —
                                       #   steps_run minus this = dispatches
                                       #   that returned logits for host-
                                       #   side sampling (sampled verify
                                       #   lanes)
        self.quiesces = 0              # pipeline drains forced by a
                                       #   host-exactness point (snapshot/
                                       #   cancel/deadline/ladder/verify)
        self.kv_exports = 0            # export_kv packets produced
        self.kv_imports = 0            # import_kv packets spliced in
        self.kv_pages_exported = 0     # pages shipped in those packets
        self.kv_pages_imported = 0
        self.step_launches = 0         # executables step() launched ...
        self.step_uploads = 0          # ... and host->device arrays it
                                       #   made: one launch a model call
                                       #   (dense prefill, chunk, horizon)
                                       #   and one packed upload (two when
                                       #   the sampling row changed) is
                                       #   the whole of a steady step
        _LIVE_ENGINES.add(self)

    # -- submission --------------------------------------------------------
    def submit(self, prompt, max_new_tokens: int = 32, temperature: float = 0.0,
               top_p: float = 1.0, eos_token_id: int | None = None,
               timeout: float | None = None, on_token=None,
               trace_id: int | None = None) -> int:
        """Queue one request.  Raises `PoolCapacityError` for requests that
        can NEVER fit the pool geometry, `AdmissionRejected` when the bounded
        queue is full (backpressure), plain ValueError for malformed input.
        `timeout` (seconds from now) retires the request — wherever it is —
        once overdue, with `Request.timed_out` set.  `on_token` is the
        streaming hook: called as ``on_token(tok)`` for every emitted token
        in emission order, at the step's host-sync boundary (or the overlap
        drain — bounded lag, same order); `Request.stream()` is the
        pull-style equivalent.  `trace_id` (optional) is the fleet-wide
        stitching id the frontend/router minted for this end-to-end
        request (observability.distributed)."""
        now = self._clock()
        return self._enqueue(
            prompt, [], max_new_tokens, temperature, top_p, eos_token_id,
            None if timeout is None else now + float(timeout), now,
            on_token=on_token, trace_id=trace_id)

    def adopt(self, prompt, generated=(), max_new_tokens: int = 32,
              temperature: float = 0.0, top_p: float = 1.0,
              eos_token_id: int | None = None,
              deadline: float | None = None,
              trace_id: int | None = None) -> int:
        """Adopt a request MID-FLIGHT: queue `prompt` with `generated`
        tokens already emitted elsewhere (a crashed replica, a snapshot),
        to be continued from exactly that point.  Admission takes the
        preemption-resume path — re-prefill of prompt + generated[:-1]
        with generated[-1] as the pending token — so greedy continuation
        is bit-exact vs the engine that emitted those tokens.  Same
        validation + backpressure as :meth:`submit`; `deadline` is an
        absolute engine-clock cutoff (the migrating router's clock domain
        must match — in-process fleets share one clock)."""
        generated = [int(t) for t in generated]
        if max_new_tokens >= 1 and len(generated) >= max_new_tokens:
            raise ValueError(
                f"adopt: {len(generated)} tokens already emitted >= "
                f"max_new_tokens={max_new_tokens} — the request is complete, "
                f"nothing to continue (report it finished instead)")
        if eos_token_id is not None and eos_token_id in generated:
            raise ValueError(
                "adopt: generated already contains eos_token_id — the "
                "request is complete, nothing to continue")
        return self._enqueue(prompt, generated, max_new_tokens, temperature,
                             top_p, eos_token_id, deadline, self._clock(),
                             trace_id=trace_id)

    def _enqueue(self, prompt, generated, max_new_tokens, temperature,
                 top_p, eos_token_id, deadline, now, on_token=None,
                 trace_id=None) -> int:
        """Shared admission-queue entry for submit (fresh request, relative
        timeout already resolved to an absolute deadline) and adopt
        (mid-flight resume): validation, capacity check, backpressure, and
        Request construction live HERE, once."""
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        if len(prompt) < 1:
            raise ValueError("prompt must hold at least one token")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        total = len(prompt) + int(max_new_tokens)
        if total > self.config.max_position_embeddings:
            raise ValueError(
                f"prompt ({len(prompt)}) + max_new_tokens ({max_new_tokens}) "
                f"exceeds the model context "
                f"{self.config.max_position_embeddings}")
        # the cache holds total-1 tokens (the final sampled token is never
        # written); it must fit this request's page-table row
        need = math.ceil((total - 1) / self.page_size)
        if need > self.max_pages_per_seq:
            raise PoolCapacityError(
                f"request needs {need} pages > "
                f"max_pages_per_seq={self.max_pages_per_seq} "
                f"(prompt {len(prompt)} + max_new_tokens {max_new_tokens})")
        if need > self.pool.num_pages:
            raise PoolCapacityError(
                f"request needs {need} pages but the pool only has "
                f"{self.pool.num_pages} ({self.pool.num_free} free) — raise "
                f"num_pages")
        if self.max_queue is not None and len(self._queue) >= self.max_queue:
            self.rejections += 1
            if self.telemetry is not None:
                self.telemetry.rejected(len(self._queue), self.max_queue)
            raise AdmissionRejected(
                f"admission queue full ({len(self._queue)}/{self.max_queue} "
                f"waiting, {self.num_active} active) — backpressure, retry "
                f"later")
        rid = self._next_rid
        self._next_rid += 1
        req = Request(rid=rid, prompt=prompt,
                      max_new_tokens=int(max_new_tokens),
                      temperature=float(temperature), top_p=float(top_p),
                      eos_token_id=eos_token_id, submit_time=now,
                      deadline=deadline, generated=list(generated),
                      on_token=on_token, _engine=weakref.ref(self),
                      trace_id=None if trace_id is None else int(trace_id))
        self._queue.append(req)
        if self.telemetry is not None:
            self.telemetry.submitted(req, queue_depth=len(self._queue))
        return rid

    def lookup(self, rid: int) -> Request | None:
        """The Request for `rid` wherever it lives (slot, queue, finished);
        None for an unknown rid.  The returned object is live — a router
        streams tokens by watching its `generated` list grow."""
        r = self._finished.get(rid)
        if r is not None:
            return r
        for slot in self._slots:
            if slot is not None and slot.req.rid == rid:
                return slot.req
        for r in self._queue:
            if r.rid == rid:
                return r
        if self._inflight is not None:
            # budget-predicted retirement detached from the slot table but
            # not yet drained — still live, still streamable
            for lane in self._inflight.lanes:
                if lane.retiring and lane.slot.req.rid == rid:
                    return lane.slot.req
        return None

    def cancel(self, rid: int) -> bool:
        """Drop a request wherever it lives, recording no result: a queued
        request leaves the queue, a running slot releases (its written KV
        parks in the prefix cache first — the blocks are valid and future
        admissions may hit them), a finished record is forgotten.  Routers
        use this to prune snapshot-restored requests they already resolved
        elsewhere, so a revived replica does not decode zombies.  Returns
        True when the rid was found."""
        # quiesce only when the rid is actually riding the pipeline (slot
        # or in-flight lane): the common router case — pruning an
        # already-finished or queued zombie — must not stall a healthy
        # in-flight dispatch.  The drain may retire the rid itself; the
        # finished-dict pop below still resolves it.
        if any(sl is not None and sl.req.rid == rid for sl in self._slots) \
                or (self._inflight is not None
                    and any(ln.slot.req.rid == rid
                            for ln in self._inflight.lanes)):
            self.quiesce()     # cancellation acts on exact host state
        live = False
        for s, slot in enumerate(self._slots):
            if slot is not None and slot.req.rid == rid:
                self._register_slot(s, with_partial=True)
                self._release_slot(s)
                live = True
                break
        if not live:
            for r in self._queue:
                if r.rid == rid:
                    self._queue.remove(r)
                    live = True
                    break
        if live and self.telemetry is not None:
            # terminate the trace record (same ghost fix the router tracer
            # got in the stitching PR: Tracer._live is unbounded, and a
            # frontend with many disconnects would grow it forever); the
            # cancelled request stays attributable — its record moves to
            # the completed ring with a terminal `retired(cancelled)`.
            # LIVE paths only: an already-finished rid's record terminated
            # at retirement, and re-recording would mint a ghost duplicate
            self.telemetry.cancelled(rid)
        return live or self._finished.pop(rid, None) is not None

    # -- internals ---------------------------------------------------------
    @contextlib.contextmanager
    def _span(self, name, **attrs):
        """One host phase of the step loop.  ALWAYS a
        ``jax.profiler.TraceAnnotation("serve.<name>", **attrs)``: with a
        profiler session open the span is in the trace's host plane, on
        this thread's line, on the clock the device events are on, nested
        by containment (`serve.step` > `serve.sched` > `serve.prefill_dense`
        ...); with none open entering and leaving it costs about two
        microseconds and records nothing.  There is no switch: tracing is
        on when somebody is tracing.  With telemetry attached, a phase of
        `ENGINE_PHASES` also feeds its histogram and the tracer's engine
        track (an exception in the body skips that, as it always did).
        Yields a dict: what the body puts in it becomes further stats of
        the annotation (a record span's ``tokens``); a span under which
        the engine launched executables or made host->device arrays gets
        ``launches`` and ``uploads`` the same way (a dispatch or prefill
        span reads 1 and 1, or 2 where the sampling row had changed)."""
        tel = self.telemetry
        late = {}
        launched, uploaded = self.step_launches, self.step_uploads
        with self._jax.profiler.TraceAnnotation("serve." + name,
                                                **attrs) as ann:
            if tel is None or name not in ENGINE_PHASES:
                yield late
            else:
                t0 = tel.sched_begin() if name == "sched" else tel.clock()
                yield late
                if name == "sched":
                    tel.sched_done(t0, tel.clock())
                elif name in ("prefill_dense", "prefill_chunk"):
                    tel.prefill_dispatch(attrs["rid"], pos=attrs["pos"],
                                         tokens=attrs["tokens"], t0=t0,
                                         kind=name)
                elif name == "overlap_join_sync":
                    tel.join_wait(t0, tel.clock())
                else:
                    tel.phase(name, t0, tel.clock(), **attrs)
            if self.step_launches != launched:
                late["launches"] = self.step_launches - launched
                late["uploads"] = self.step_uploads - uploaded
            if late:
                ann.set_metadata(**late)

    def _fetch_first_token(self, slot, tok) -> int:
        """The one host fetch of a prefill's fused first token (the wait
        is the prefill's device time)."""
        with self._span("first_token_sync", rid=slot.req.rid):
            return int(np.asarray(tok))  # graftlint: disable=SYNC001

    def _jit(self, name, fn, **jit_kw):
        """jax.jit + recompile instrumentation: every compile-cache miss of
        the returned callable lands in `self.jit_cache_misses[name]` and is
        reported to any active `analysis.sanitize()` scope (the recompile
        budget).  All engine executables route through here so steady-state
        variant counts are observable per model fn — and, with telemetry
        attached, every miss's wall cost lands in `engine.compile_s` + a
        flight `compile` event (compile accounting)."""
        jf = self._jax.jit(fn, **jit_kw)
        # bounded by the (name, bucket) grid the budget gate polices,
        # not per-request  # graftlint: disable=LEAK001
        self._jit_fns.setdefault(name, []).append(jf)
        return instrument(jf, name=name, counters=self.jit_cache_misses,
                          on_miss=self._on_compile)

    def _on_compile(self, name, n, dur_s):
        """sanitize-instrumentation miss hook (host-only; telemetry off is
        one None check)."""
        tel = self.telemetry
        if tel is not None:
            tel.compiled(name, n, dur_s)

    def _call_paged(self, fn, *args, keyed=False, **static):
        """Call a cache-donating executable (its last output is the new
        cache; where it draws, `keyed`, the one before it is the next
        key).  A sanitize() budget raise fires only AFTER
        the underlying call ran — its donated inputs are gone — so rebind
        the cache (and the key the call consumed) from the executed call's
        outputs before
        propagating: lengths were never advanced for the raising step and
        K/V above lengths is never attended (the rewind invariant), so the
        engine stays fully usable."""
        try:
            out = fn(*args, **static)
        except RecompileBudgetError as e:
            if e.result is not None:
                self._cache = e.result[-1]
                if keyed:
                    self._key = e.result[-2]
            if self.telemetry is not None:
                # the postmortem the recompile sanitizer never had: the
                # last N engine events leading up to the budget failure
                self.telemetry.fault_dump("recompile_budget",
                                          error=str(e)[:200])
            raise
        return out

    def _upload(self, host):
        """ONE host->device array of `step()`, replicated over the mesh
        under TP.  It may ALIAS the numpy memory on the CPU backend: hand
        it a fresh buffer (a packed row is one), never a view of a host
        mirror that changes while the call is in flight."""
        self.step_uploads += 1
        return self._jax.device_put(host, self._host_sharding)

    @property
    def _pages_k(self):
        """The K side of a K/V page store (read-only view of the cache; a
        family with another kind of page store has no such leaf)."""
        return self._cache["k"]

    @property
    def _pages_v(self):
        return self._cache["v"]

    def _page_stores(self) -> dict:
        """{name: store} of the page stores the family names."""
        return {name: self._cache[name] for name in self.family.page_leaves}

    def jit_variants(self) -> dict:
        """{model fn name: number of compiled executables} — the bounded,
        documented variant counts PERF.md §12 records (None-valued entries
        mean the jax build exposes no cache introspection)."""
        out = {}
        for name, fns in self._jit_fns.items():
            sizes = [jit_cache_size(f) for f in fns]
            out[name] = None if any(s is None for s in sizes) else sum(sizes)
        return out

    def decode_horizon_compiled(self):
        """The greedy decode-horizon executable at this engine's
        steady-state shapes, compiled from shapes alone (nothing runs, no
        buffer is donated) through the very jit the engine dispatches: the
        object whose ``as_text()`` shows whether the Pallas kernel
        (``tpu_custom_call``) and, under TP, the per-layer all-reduce are
        in the program, and whose ``memory_analysis()`` says what it needs
        on each device."""
        jax, jnp = self._jax, self._jnp
        S, P = self.num_slots, self.max_pages_per_seq

        def like(a):
            return jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=a.sharding)

        def host(shape, dtype):
            return jax.ShapeDtypeStruct(shape, dtype)

        shaped = lambda tree: jax.tree_util.tree_map(like, tree)
        carry = None if not self.overlap else \
            shaped(self._no_carry + ((self._zero_tok,) * S,))
        return self._horizon_exec(self.decode_horizon, True).lower(
            shaped(self.params), shaped(self._cache), shaped(self._key),
            host(((6 + P) * S,), jnp.int32), host((2 * S,), jnp.float32),
            carry).compile()

    def _avail(self) -> int:
        """Free pages as THIS step sees them: zero while an injected
        `serve.pool_pressure` window is active (exhaustion drills)."""
        return 0 if self._pressure else self.pool.num_free

    def _evict(self, n: int) -> int:
        """Degradation-ladder rung between stall and preempt: reclaim up to
        n pages from the prefix cache (LRU leaf-first)."""
        if self.cache is None or n <= 0:
            return 0
        with self._span("evict", requested=n) as late:
            freed = late["freed"] = self._evict_cache(n)
        if self.telemetry is not None:
            # recorded even at freed == 0: walking this rung is what the
            # flight-recorder ladder drills assert (admit -> evict ->
            # preempt), whether or not the cache had anything to give back
            self.telemetry.evicted(requested=n, freed=freed)
        return freed

    def _evict_cache(self, n: int) -> int:
        visits = self.cache.evict_visits
        freed = self.cache.evict(n)
        self.cache_evictions += freed
        self.cache_evict_visits += self.cache.evict_visits - visits
        return freed

    def _register_pages(self, slot, valid: int, with_partial: bool):
        """Index a slot's written-so-far KV (first `valid` tokens) into the
        prefix cache — host-list hashing only, no device access."""
        if self.cache is None or valid <= 0:
            return
        seq = np.concatenate(
            [slot.req.prompt,
             np.asarray(slot.req.generated, np.int32)])[:valid]
        self.cache.register(seq, slot.pages, with_partial=with_partial)

    def _register_slot(self, s: int, with_partial: bool):
        """Index the slot's written-so-far KV into the prefix cache (full
        blocks always; the trailing partial block too on retire/preempt,
        since nothing will write into it anymore)."""
        self._register_pages(self._slots[s], int(self._lengths[s]),
                             with_partial)

    def _release_slot(self, s: int):
        slot = self._slots[s]
        self.pool.free(slot.pages)
        self._slots[s] = None
        self._page_tables[s] = 0
        self._lengths[s] = 0
        return slot

    def _finish(self, s: int):
        # retire INTO the cache: the pages this request wrote stay indexed
        # (refcount 1, cache-held) until LRU eviction needs them back
        self._register_slot(s, with_partial=True)
        slot = self._release_slot(s)
        slot.req.finish_time = self._clock()
        self._finished[slot.req.rid] = slot.req
        if self.telemetry is not None:
            self.telemetry.retired(slot.req)

    def _preempt(self, s: int):
        """Victim preemption: park the slot's written KV in the prefix
        cache, return its page references, and requeue the request at the
        queue head; re-admission re-prefills prompt + already-emitted
        tokens — and that re-prefill can hit the very blocks parked here,
        so a preemption usually costs one chunk of suffix prefill, not a
        full re-prefill.  Greedy decoding resumes step-exact either way."""
        self._register_slot(s, with_partial=True)
        slot = self._release_slot(s)
        slot.req.preemptions += 1
        self.preemptions += 1
        if self.telemetry is not None:
            # storm detection lives in the telemetry (N preemptions within
            # a step window auto-dumps the flight recorder once per storm)
            self.telemetry.preempted(slot.req, step=self._step_seq)
        self._queue.appendleft(slot.req)

    def _pick_victim(self) -> int:
        """Youngest / lowest-progress victim: fewest emitted tokens, ties
        broken toward the most recent admission (least invested work)."""
        return min((s for s, sl in enumerate(self._slots) if sl is not None),
                   key=lambda s: (len(self._slots[s].req.generated),
                                  -self._slots[s].admit_seq))

    def _retire_overdue(self):
        """Deadline enforcement: retire overdue requests wherever they live
        (running slot or admission queue), marking them timed_out.  An
        overdue request currently riding the in-flight dispatch forces a
        quiesce first — the deadline acts on the drained step (bounded
        lag), never on a half-visible one."""
        now = self._clock()
        if self._inflight is not None:
            live = [sl.req for sl in self._slots if sl is not None]
            live += [ln.slot.req for ln in self._inflight.lanes
                     if ln.retiring]
            if any(r.deadline is not None and now > r.deadline
                   for r in live):
                self.quiesce()
        for s, slot in enumerate(self._slots):
            if slot is not None and slot.req.deadline is not None \
                    and now > slot.req.deadline:
                slot.req.timed_out = True
                self.timeouts += 1
                self._finish(s)
        if any(r.deadline is not None and now > r.deadline
               for r in self._queue):
            keep: deque[Request] = deque()
            for req in self._queue:
                if req.deadline is not None and now > req.deadline:
                    req.timed_out = True
                    req.finish_time = now
                    self.timeouts += 1
                    self._finished[req.rid] = req
                    if self.telemetry is not None:
                        self.telemetry.retired(req)
                else:
                    keep.append(req)
            self._queue = keep

    def _emit_token(self, slot, tok: int) -> bool:     # graftlint: hot
        """Append one sampled token (a PYTHON int — callers fetch at the
        annotated batched sync/drain boundaries and `.tolist()` rows, so
        no per-token device round-trip happens here), fire the streaming
        callback, and return True when the request just finished
        (EOS/budget).  Finishing bookkeeping stays with the caller — the
        slot may be attached (sync path) or detached (overlap drain of a
        pre-retired lane)."""
        req = slot.req
        req.generated.append(tok)
        if slot.draft is not None:
            slot.draft.append(tok)
        if req.first_token_time == 0.0:
            req.first_token_time = self._clock()
            if self.telemetry is not None:
                # once per request, inside the first-token branch — the
                # per-token fast path stays telemetry-free
                self.telemetry.first_token(req)
        if req.on_token is not None:
            req.on_token(tok)
        self.tokens_generated += 1
        return (req.eos_token_id is not None and tok == req.eos_token_id) \
            or len(req.generated) >= req.max_new_tokens

    def _record_token(self, s: int, tok: int) -> bool:  # graftlint: hot
        """Append a sampled token (already a host int); returns True when
        the request finished (and retires it in place)."""
        slot = self._slots[s]
        done = self._emit_token(slot, tok)
        if done:
            self._finish(s)
        else:
            slot.pending = tok
        return done

    def _finish_detached(self, slot, valid: int):
        """Retire a slot already DETACHED from the slot table (a budget-
        predicted retirement handed its lane to a successor while its
        final tokens were still in flight): park the written KV in the
        prefix cache, return the page references, record the result."""
        self._register_pages(slot, valid, with_partial=True)
        self.pool.free(slot.pages)
        slot.req.finish_time = self._clock()
        self._finished[slot.req.rid] = slot.req
        if self.telemetry is not None:
            self.telemetry.retired(slot.req)

    def _cow(self, s: int, idx: int, src: int | None = None):
        """Copy-on-write: give slot s its own copy of the (shared) page at
        table index idx before anything writes into it.  `src` overrides
        the copy source (admission attaches a cached partial page without
        ever putting the shared id in the table)."""
        self._join_dispatch()      # the copy chains on concrete pages
        slot = self._slots[s]
        dst = slot.pages[idx]
        if src is None:
            src = dst
            dst = self.pool.alloc(1)[0]
        self.step_launches += 1
        self._cache = self._call_paged(
            self._copy_jit, self._cache,
            self._upload(np.asarray([src, dst], np.int32)))
        if slot.pages[idx] != dst:
            self.pool.free([slot.pages[idx]])
            slot.pages[idx] = dst
        self._page_tables[s, idx] = dst
        self.cow_copies += 1
        if self.telemetry is not None:
            self.telemetry.cow_copy(slot.req.rid, src=int(src), dst=int(dst))

    def _admit(self):                                 # graftlint: hot
        while self._queue:
            free_slots = [i for i, sl in enumerate(self._slots) if sl is None]
            if not free_slots:
                return
            req = self._queue[0]
            # resume path (preempted request): the cache must hold prompt +
            # all emitted tokens except the last, which becomes the pending
            # token — exactly the state the victim was evicted in
            resuming = len(req.generated) > 0
            ctx = req.prompt if not resuming else np.concatenate(
                # host list -> np ids, no device sync  # graftlint: disable=SYNC001
                [req.prompt, np.asarray(req.generated[:-1], np.int32)])
            T = len(ctx)
            total_pages = max(1, math.ceil(T / self.page_size))
            # longest cached prefix: whole pages attach read-only; a cached
            # partial tail page attaches via copy-on-write
            shared, partial = ([], None)
            if self.cache is not None:
                shared, partial = self.cache.lookup(ctx)
            n_shared = len(shared)
            # pin the matched pages (take our references now) so the
            # eviction below can never free them out from under us
            pin = list(shared) + ([partial[0]] if partial is not None else [])
            if pin:
                self.pool.share(pin)
            need = total_pages - n_shared   # pages this request must OWN
            if need > self._avail():
                # ladder: evict unreferenced cached pages before giving up
                self._evict(need - self._avail())
            if need > self._avail():
                if pin:
                    self.pool.free(pin)
                return                 # wait for retirements to free pages
            try:
                own = self.pool.alloc(need)
            except BaseException as exc:
                if pin:                # injected pagepool.alloc fault —
                    self.pool.free(pin)  # roll back so no reference leaks
                if self.telemetry is not None \
                        and isinstance(exc, InjectedFault):
                    self.telemetry.fault_dump("injected_fault",
                                              point="pagepool.alloc",
                                              error=str(exc)[:200])
                raise
            self._queue.popleft()
            s = free_slots[0]
            pages = shared + own
            matched = n_shared * self.page_size
            slot = _Slot(req, pages, 0, admit_seq=self._admit_seq)
            slot.resuming = resuming
            req.slot = s
            self._admit_seq += 1
            self._slots[s] = slot
            if self.speculative and req.temperature <= 0.0:
                # n-gram index over prompt + EVERY emitted token (ctx drops
                # the pending one; a preemption victim's index rebuilds
                # here from its full history)
                slot.spec_k = self.speculative
                slot.draft = _NgramDraft(
                    req.prompt if not resuming else np.concatenate(
                        # host list -> np ids, no device sync  # graftlint: disable=SYNC001
                        [req.prompt, np.asarray(req.generated, np.int32)]),
                    max_n=self.spec_max_ngram)
            row = np.zeros((self.max_pages_per_seq,), np.int32)
            row[:len(pages)] = pages
            self._page_tables[s] = row
            if partial is not None:
                # copy-on-write: the suffix prefill writes into this page's
                # tail, and the cache (and possibly other requests) still
                # reference it — copy first, then drop the pinned reference
                src, m = partial
                self._cow(s, n_shared, src=src)
                self.pool.free([src])
                matched += m
            self._set_sampling(s, req)
            if matched:
                self.cache_hits += 1
                self.cache_hit_tokens += matched
                req.cached_prefix_tokens += matched
            self.prefill_tokens += T - matched
            # admission timestamp at the host boundary we already stand on
            # (no device sync): first admission only, so queue_time keeps
            # meaning "wait for a slot" across preemption re-admissions
            admit_now = self._clock()
            first_admit = req.admit_time == 0.0
            if first_admit:
                req.admit_time = admit_now
            if self.telemetry is not None:
                self.telemetry.admitted(
                    req, slot=s, t=admit_now, resuming=resuming,
                    first=first_admit, cached_tokens=matched,
                    prefill_tokens=T - matched)
            chunked = self.prefill_chunk is not None \
                and (T - matched) > self.prefill_chunk
            if matched == 0 and not chunked:
                # whole-prompt dense prefill + fused first sample — the
                # pre-cache fast path, kept byte-identical so cache-off
                # numerics never shift
                self._lengths[s] = T
                # bucketed prompt pad -> one prefill executable per bucket
                # (clamped to the rope-table length: the bucket round-up may
                # overshoot the model context even though the prompt fits)
                Tb = max(self.prompt_bucket,
                         math.ceil(T / self.prompt_bucket) * self.prompt_bucket)
                Tb = min(Tb, self.config.max_position_embeddings)
                ids = np.zeros((Tb,), np.int32)
                ids[:T] = ctx
                greedy = req.temperature <= 0.0
                pf = self._prefill_jit.get((Tb, greedy))
                if pf is None:
                    fn = self._prefill_fn
                    # stays a lambda (module `jit__lambda`): the benchmark's
                    # model.prefill_dev_tok_s matches that name and, now
                    # that the horizon is `jit_decode_horizon`, reads the
                    # dense prefill alone by it.  Rename together with the
                    # matcher in a `benchmark` PR (PERF.md section 7).
                    pf = self._jit(
                        "prefill",
                        (lambda *a: fn(*a, greedy=True)) if greedy
                        else (lambda *a: fn(*a, greedy=False)),
                        donate_argnums=(1,))
                    # keyed by (T bucket, greedy): bounded by the
                    # bucket ladder  # graftlint: disable=LEAK001
                    self._prefill_jit[(Tb, greedy)] = pf
                self._join_dispatch()   # prefill chains on concrete pages
                kv_pages = self._count_prefill(0, T, Tb)
                try:
                    # the span closes BEFORE the bookkeeping below samples
                    # the first token, so the request record keeps ladder
                    # order: admitted -> prefill_dense -> first_token
                    with self._span("prefill_dense", rid=req.rid, pos=0,
                                    tokens=T, padded=Tb, pages=kv_pages,
                                    family=self.family.name,
                                    attention=self.family.attention_path,
                                    **({"cross_decoder": 1}
                                       if self.family.chunk_takes_last
                                       else {})):
                        self.step_launches += 1
                        tok, self._key, self._cache = self._call_paged(
                            pf, self.params, self._cache, self._key,
                            self._upload(pack_prefill(
                                ids, T, s, req.temperature, req.top_p, row)),
                            keyed=True)
                except RecompileBudgetError as e:
                    # the prefill DID run (pages already rebound by
                    # _call_paged) — finish the admission bookkeeping with
                    # the sampled token the raise carries, so the slot is
                    # left exactly as the success path leaves it and a
                    # later run() continues bit-exactly
                    if e.result is None:
                        raise
                    self._finish_admission(s, e.result[0], ctx, pages,
                                           resuming)
                    raise
                self._finish_admission(s, tok, ctx, pages, resuming)
            else:
                # suffix / chunked prefill: only the un-cached tokens run,
                # at most prefill_chunk per engine step
                slot.ctx = ctx
                slot.prefill_pos = matched
                self._lengths[s] = matched
                self._prefill_advance(s)

    def _set_sampling(self, s: int, req):
        """Slot s samples as `req` asks; the horizon's device row goes
        stale only where a value really changed (all-greedy traffic never
        uploads it twice)."""
        t, p = np.float32(req.temperature), np.float32(req.top_p)
        if self._temps[s] != t or self._top_ps[s] != p:
            self._temps[s], self._top_ps[s] = t, p
            self._sampling_dev = None

    def _count_prefill(self, pos: int, c: int, padded: int) -> int:
        """The dispatch-time counters of ONE prefill call: ``c`` real
        tokens from position ``pos`` in ``padded`` computed rows.  Returns
        the pages their K/V rows lie on (``pos`` may sit inside a page
        after a prefix-cache hit; the last is part full)."""
        self.prefill_calls += 1
        self.prefill_tokens_dispatched += c
        self.prefill_tokens_padded += padded
        pages = (pos + c - 1) // self.page_size - pos // self.page_size + 1
        self.prefill_kv_pages_written += pages
        return pages

    def _finish_admission(self, s, tok, ctx, pages,
                          resuming):                  # graftlint: hot
        """Post-dense-prefill bookkeeping, shared by the success path and
        the RecompileBudgetError recovery path of _admit (the executed
        call's outputs ride the exception)."""
        slot = self._slots[s]
        if self.cache is not None:
            self.cache.register(ctx, pages)
        if resuming:
            # the re-prefill rebuilt the cache; the last emitted token is
            # still the pending one (a python int) — discard the
            # redundant sample
            slot.pending = slot.req.generated[-1]
        elif self.overlap:
            # on-device token carry: the fused prefill+sample's first
            # token never round-trips — the next decode dispatch consumes
            # it directly and the drain records it (bounded lag).  The
            # per-admission host sync the synchronous path pays below is
            # structurally GONE here.
            slot.pending = None
            slot.pending_dev = tok
        else:
            # the ONE per-admission sync: the fused prefill+sample's
            # first token
            self._record_token(s, self._fetch_first_token(slot, tok))

    def _prefill_advance(self, s: int):               # graftlint: hot
        """Run ONE prefill chunk for slot s (suffix prefill after a cache
        hit is the single- or few-chunk case).  On the final chunk: index
        the prompt's full blocks into the cache and sample the first
        token."""
        self._join_dispatch()      # the chunk chains on concrete pages
        slot = self._slots[s]
        req = slot.req
        pos = slot.prefill_pos
        T = len(slot.ctx)
        c = T - pos
        if self.prefill_chunk is not None:
            c = min(c, self.prefill_chunk)
        # bucket the chunk pad (a short suffix must not pay a full-chunk
        # executable) and slice the page table to the pages this chunk can
        # actually see (the family's granularity, 4 pages where the
        # attention's cost follows the table's width — the plain forms
        # gather the whole table — and the whole table where the kernel
        # walks live pages only and a width is just one more executable)
        Cb = max(self.prompt_bucket,
                 math.ceil(c / self.prompt_bucket) * self.prompt_bucket)
        if self.prefill_chunk is not None:
            Cb = min(Cb, max(self.prompt_bucket, self.prefill_chunk))
        Cb = min(Cb, self.config.max_position_embeddings)
        ctx_pages = math.ceil((pos + c) / self.page_size)
        granule = self.family.chunk_table_granule or self.max_pages_per_seq
        Pb = min(self.max_pages_per_seq,
                 math.ceil(ctx_pages / granule) * granule)
        ids = np.zeros((Cb,), np.int32)
        ids[:c] = slot.ctx[pos:pos + c]
        kv_pages = self._count_prefill(pos, c, Cb)
        # a family whose chunk executable depends on it is told (a static
        # argument) whether this chunk is its prompt's last
        takes_last, is_last = self.family.chunk_takes_last, pos + c >= T
        with self._span("prefill_chunk", rid=req.rid, pos=pos, tokens=c,
                        padded=Cb, pages=kv_pages, family=self.family.name,
                        attention=self.family.attention_path,
                        state_carried=1 if self.family.recurrent and pos else 0,
                        **({"cross_decoder": 1 if is_last else 0}
                           if takes_last else {})):
            self.step_launches += 1
            logits, tok_g, self._cache = self._call_paged(
                self._chunk_jit, self.params, self._cache,
                # packed = copied: the row slice is a VIEW of the mutable
                # host table — an async in-flight chunk must not see later
                # host-side table growth (a CPU upload can alias)
                self._upload(pack_chunk(ids, pos, c, s,
                                        self._page_tables[s, :Pb])), C=Cb,
                **({"last": is_last} if takes_last else {}))
        slot.chunk_step = self._step_seq
        pos += c
        slot.prefill_pos = pos
        self._lengths[s] = pos
        if pos < T:
            return
        # prefill complete -> decoding
        slot.prefill_pos = None
        ctx, slot.ctx = slot.ctx, None
        if self.cache is not None:
            self.cache.register(ctx, slot.pages)
        if slot.resuming:
            # the re-prefill rebuilt the cache; the last emitted token is
            # still the pending one (a python int) — no fresh sample needed
            slot.pending = req.generated[-1]
        elif req.temperature <= 0.0:
            # fused greedy sampling: the chunk dispatch already emitted the
            # argmax token — no separate sample executable ever compiles
            # for the greedy final-chunk path
            if self.overlap:
                # on-device carry: no final-chunk host sync — the next
                # decode dispatch consumes the device scalar directly
                slot.pending = None
                slot.pending_dev = tok_g
            else:
                # the ONE final-chunk sync: the fused first token
                self._record_token(s, self._fetch_first_token(slot, tok_g))
        else:
            try:
                tok = self._sample(logits, req)
            except RecompileBudgetError as e:
                # the sampler DID run — record the token it produced so
                # the completed-prefill transition above stays consistent
                # and a later run() decodes from the right first token
                if e.result is None:
                    raise
                self._record_token(s, int(np.asarray(e.result[0])))  # graftlint: disable=SYNC001
                raise
            if self.overlap:
                slot.pending = None
                slot.pending_dev = tok
            else:
                # the ONE final-chunk sync: the sampled first token
                self._record_token(s, self._fetch_first_token(slot, tok))

    def _sample(self, logits, req):
        """One NUCLEUS draw from single-position logits (the sampled final
        chunk of a chunked/suffix prefill and the sampled lanes of a
        speculative verify share the jitted sampler): one launch and one
        upload, the key split inside and rebound here.  Greedy lanes never
        reach here — their argmax is FUSED into the chunk/verify/decode
        dispatch itself (tokens, not logits, leave the device), so no
        greedy sampler exists.  A `RecompileBudgetError` carries the
        executed call's ``(token, next key)``; the key is rebound before
        it propagates, so the resumed engine stays on the seeded stream."""
        sf = self._sample_jit
        if sf is None:                 # its module: `jit_sample_logits`
            sf = self._sample_jit = self._jit("sample", self._sample_fn)
        self.step_launches += 1
        try:
            tok, self._key = sf(logits, self._key, self._upload(np.asarray(
                [req.temperature, req.top_p], np.float32)))
        except RecompileBudgetError as e:
            if e.result is not None:
                self._key = e.result[1]
            raise
        return tok

    def _remaining(self, s: int) -> int:
        slot = self._slots[s]
        n = slot.req.max_new_tokens - len(slot.req.generated)
        # an admission-deferred first token (overlap mode) is spoken for
        # but not yet in `generated` — it counts against the budget
        return n - 1 if slot.pending_dev is not None else n

    def _provision(self, steps):
        """Lazy page growth for up to `steps` decode steps ahead: every
        DECODING slot gets pages covering write positions < lengths +
        min(steps, remaining); mid-prefill slots are skipped (their pages
        were provisioned at admission).  `steps` is an int (uniform
        horizon) or a {slot: tokens} dict of per-slot needs (the verify
        path: 1 + draft length; slots absent from the dict are draftless
        ride-along lanes writing a single token).  When the pool runs
        short the prefix cache is evicted first (degradation ladder); a
        slot that still cannot be covered stalls this horizon.  A shared
        page about to receive a write is copied first (copy-on-write —
        belt and braces: admission already copies the only shareable
        written page).  Returns the list of runnable slot indices."""
        with self._span("provision"):
            per_slot = steps if isinstance(steps, dict) else None
            run = []
            for s, slot in enumerate(self._slots):
                if slot is None or slot.prefill_pos is not None:
                    continue
                want = per_slot.get(s, 1) if per_slot is not None else steps
                slot.stalled = False
                w0 = int(self._lengths[s]) // self.page_size
                if w0 < len(slot.pages) \
                        and self.pool.refcount(slot.pages[w0]) > 1:
                    if self._avail() < 1:
                        self._evict(1)
                    if self._avail() < 1:
                        slot.stalled = True
                        continue
                    self._cow(s, w0)
                m = min(want, self._remaining(s))
                need = math.ceil((int(self._lengths[s]) + m)
                                 / self.page_size)
                grow = need - len(slot.pages)
                if grow > 0:
                    if grow > self._avail():
                        self._evict(grow - self._avail())
                    if grow > self._avail():
                        slot.stalled = True
                        continue
                    pages = self.pool.alloc(grow)
                    start = len(slot.pages)
                    slot.pages.extend(pages)
                    self._page_tables[s, start:start + grow] = pages
                run.append(s)
            return run

    # -- speculative decoding ----------------------------------------------
    def _propose_drafts(self) -> dict:
        """{slot -> draft tokens} for every decoding greedy slot whose
        n-gram index has a match this step.  Draft length is clamped to
        the slot's ADAPTIVE spec_k (shrunk while drafts keep missing,
        regrown on full acceptance) and to remaining-1 so an accepted run
        plus the bonus token can never overrun the request's budget — the
        page math then stays within the pages `submit` promised."""
        drafts = {}
        for s, slot in enumerate(self._slots):
            if slot is None or slot.prefill_pos is not None \
                    or slot.draft is None:
                continue
            k = min(slot.spec_k, self.speculative, self._remaining(s) - 1)
            if k <= 0:
                continue
            d = slot.draft.propose(k)
            if d:
                drafts[s] = d
        return drafts

    def _verify(self, run, drafts):                   # graftlint: hot
        """One speculative verify dispatch over the runnable slots: score
        pending + draft tokens at K+1 positions, accept the longest draft
        prefix whose argmax matches (lossless under greedy sampling), emit
        accepted tokens + the bonus token, and REWIND `lengths` past
        rejected positions — the stale K/V scattered for rejected drafts
        sits above the rewound length, is never attended (every attention
        path masks by lengths), and is overwritten by the next write at
        that position.  EOS/budget freezes mid-run exactly as in the
        decode horizon (`_record_token` stops the emit loop); sampled
        (temperature > 0) slots ride the same dispatch as single-token
        lanes drawn from the position-0 logits."""
        Q = self.speculative + 1
        S = self.num_slots
        toks = np.zeros((S, Q), np.int32)
        n_q = np.zeros((S,), np.int32)
        for s in run:
            slot = self._slots[s]
            d = drafts.get(s, ())
            toks[s, 0] = slot.pending
            if d:
                toks[s, 1:1 + len(d)] = d
            n_q[s] = 1 + len(d)
        with self._span("verify_dispatch", slots=len(run),
                        k=self.speculative):
            # no benchmark cell speculates: the verify step keeps an upload
            # a field (the sync below comes before the host touches its
            # mirrors again), and launches the one executable
            self.step_launches += 1
            logits0, gtoks, self._cache = self._call_paged(
                self._verify_jit,
                self.params, self._upload(toks),
                self._upload(self._lengths),
                self._upload(self._page_tables), self._cache,
                self._upload(n_q))
        with self._span("verify_sync"):
            # the ONE per-verify-dispatch sync: every slot's K+1 argmaxes
            # land in one transfer (acceptance is host logic by design)
            gtoks = np.asarray(gtoks)  # graftlint: disable=SYNC001
        with self._span("verify_record") as late:
            late["tokens"] = self._verify_record(run, drafts, logits0, gtoks)

    def _verify_record(self, run, drafts, logits0, gtoks):  # graftlint: hot
        """The host half of a verify dispatch: acceptance, emission and
        the length rewind (see `_verify`).  Returns the tokens emitted."""
        self.steps_run += 1
        self.verify_steps += 1
        if all(self._slots[s].req.temperature <= 0.0 for s in run):
            # every participating lane consumed the dispatch's own fused
            # argmax row — a token-emitting step; one sampled ride-along
            # lane makes it a logit-path dispatch instead
            self.fused_sample_steps += 1
        tel = self.telemetry
        if tel is not None:
            for s in run:
                tel.request_event(self._slots[s].req.rid, "verify_dispatch",
                                  drafted=len(drafts.get(s, ())))
        total = 0
        lens = self._lengths.tolist()    # host mirror -> python ints
        for s in run:
            slot = self._slots[s]
            req = slot.req
            d = list(drafts.get(s, ()))
            nd = len(d)
            old = lens[s]
            if req.temperature > 0.0:
                try:
                    tok = self._sample(logits0[s], req)
                except RecompileBudgetError as e:
                    # same recovery as the final-chunk sampler: the call
                    # ran and consumed a PRNG key — record its token so
                    # the resumed engine stays on the seeded key stream
                    # instead of re-sampling this position with a later key
                    if e.result is None:
                        raise
                    self._lengths[s] = old + 1
                    self._record_token(s, int(np.asarray(e.result[0])))  # graftlint: disable=SYNC001
                    raise
                # per sampled ride-along lane: one token fetch
                emitted = [int(np.asarray(tok))]  # graftlint: disable=SYNC001
                acc = 0
            else:
                g = gtoks[s].tolist()        # host row -> python ints
                acc = 0
                while acc < nd and g[acc] == d[acc]:
                    acc += 1
                emitted = d[:acc] + [g[acc]]
            if nd:
                if acc == nd:          # fully accepted: regrow toward K
                    slot.spec_k = min(self.speculative, slot.spec_k + 1)
                elif acc == 0:         # whiffed: back off (floor 1 — the
                    slot.spec_k = max(1, slot.spec_k // 2)  # lane is padded
                                       # to static K either way)
            n_emitted = 0
            for i, tok in enumerate(emitted, 1):
                # advance/rewind: cache now validly holds the pending token
                # plus i-1 accepted drafts past the old length
                self._lengths[s] = old + i
                n_emitted = i
                if self._record_token(s, tok):
                    break
            total += n_emitted
            if nd:
                # credit only drafts that actually LANDED: an EOS/budget
                # freeze mid-run discards the tail of an accepted run, and
                # the reported acceptance rate must reflect useful tokens
                # (spec_k adaptation above still keys off model-level acc)
                used = min(acc, n_emitted)
                self.draft_tokens_proposed += nd
                self.draft_tokens_accepted += used
                req.draft_proposed += nd
                req.draft_accepted += used
        return total

    def _horizon_exec(self, K: int, greedy: bool):
        fn = self._horizon_jit.get((K, greedy))
        if fn is None:
            horizon = self._horizon_fn

            # a NAMED function: a profiler trace lists the executable as
            # `jit_decode_horizon(<fingerprint>)` on the device's
            # "XLA Modules" line, which is how the benchmark finds it
            def decode_horizon(*a):
                return horizon(*a, K=K, greedy=greedy)

            fn = self._jit("decode_step", decode_horizon,
                           donate_argnums=(1,))
            # keyed by (K, greedy): bounded by the horizon ladder
            # graftlint: disable=LEAK001
            self._horizon_jit[(K, greedy)] = fn
        return fn

    # -- double-buffered host loop (overlap=True; ROADMAP item 5) ----------
    @property
    def inflight_depth(self) -> int:
        """Decode dispatches in flight and not yet drained (0 or 1 — the
        pipeline is double-buffered, not arbitrarily deep)."""
        return 0 if self._inflight is None else 1

    def quiesce(self) -> bool:
        """Drain the pipeline to an EXACT host-visible step boundary:
        fetch and record any in-flight dispatch's tokens (retiring what
        finished) and flush any admission-deferred first tokens back to
        host ints.  After quiesce(), `Request.generated`, slot pendings,
        the length mirror, and the page accounting are precisely what a
        synchronous engine would hold — `snapshot()`, `cancel()`,
        deadline sweeps of in-flight work, speculative verify, and the
        degradation ladder all call this first.  Returns True when
        anything was actually in flight.  No-op (and free) on a
        synchronous engine."""
        rec, self._inflight = self._inflight, None
        flushed = False
        if rec is not None:
            self._drain(rec)
            self.quiesces += 1
            flushed = True
        for s, slot in enumerate(self._slots):
            if slot is not None and slot.pending_dev is not None:
                # materialized long ago (the dispatch that would consume
                # it never went out) — this fetch waits on nothing new
                tok0 = int(np.asarray(slot.pending_dev))
                slot.pending_dev = None
                if self._emit_token(slot, tok0):
                    self._finish(s)
                else:
                    slot.pending = tok0
                flushed = True
        return flushed

    def _flush_exhausted(self):
        """Record admission-deferred first tokens that already EXHAUST
        their request's budget (max_new_tokens == 1): such a lane must
        never enter a decode dispatch, so its token is fetched here —
        rare, and the fetch waits only on the admission prefill."""
        for s, slot in enumerate(self._slots):
            if slot is not None and slot.pending_dev is not None \
                    and slot.prefill_pos is None and self._remaining(s) <= 0:
                tok0 = int(np.asarray(slot.pending_dev))
                slot.pending_dev = None
                self._emit_token(slot, tok0)
                self._finish(s)      # budget-exhausted by construction

    def _detach_predicted(self):
        """Budget-predicted retirement: a lane whose IN-FLIGHT dispatch is
        guaranteed to finish its request — remaining budget <= the
        dispatched horizon; an EOS could only finish it sooner — hands
        its slot to the admission queue NOW instead of idling a full
        dispatch.  The predecessor's pages stay referenced by the lane
        record until the drain registers + frees them; the successor's
        prefill writes disjoint fresh pages, so the in-flight dispatch
        (which holds its own device copy of the page table) is
        untouched."""
        rec = self._inflight
        if rec is None:
            return
        for lane in rec.lanes:
            s, slot = lane.s, lane.slot
            if lane.retiring or self._slots[s] is not slot \
                    or slot.prefill_pos is not None:
                continue
            if self._remaining(s) <= rec.K:
                lane.retiring = True
                lane.base_len = int(self._lengths[s])
                self._slots[s] = None
                self._page_tables[s] = 0
                self._lengths[s] = 0

    def _dispatch_decode(self, run, K: int, greedy: bool):  # graftlint: hot
        """Issue one decode-horizon dispatch over the runnable lanes and
        return its `_Inflight` record WITHOUT fetching anything.  Lanes
        whose slot also rode the previous (possibly still in-flight)
        dispatch take their token/length/budget/done inputs from that
        dispatch's DEVICE outputs (the on-device carry); freshly admitted
        lanes merge in host values — and an admission-deferred first
        token joins as a device scalar, so it never round-trips either.

        Synchronous engines call the executable inline (donation makes
        that blocking on CPU — unchanged behavior).  Overlap engines
        submit the call to the one-worker thread, chaining on the
        previous dispatch's future INSIDE the worker, so the main thread
        returns immediately and the engine's page binding lives in the
        future until someone `_join_dispatch()`s or drains."""
        from ..models.llama import pack_decode_state
        S = self.num_slots
        prev = self._inflight
        active = np.zeros((S,), np.int32)
        active[run] = 1
        toks = np.zeros((S,), np.int32)
        remaining = np.ones((S,), np.int32)
        eos_ids = np.full((S,), -1, np.int32)
        # where a lane's entry state comes from (`pack_decode_state`)
        carried = np.zeros((S,), np.int32)
        # ... and, for code 2, the first token an admission left on the
        # device (overlap engines; a filler scalar elsewhere)
        firsts = [self._zero_tok] * S
        lanes = []
        for s in run:
            slot = self._slots[s]
            remaining[s] = self._remaining(s)
            if slot.req.eos_token_id is not None:
                eos_ids[s] = slot.req.eos_token_id
            take_first = False
            if prev is not None and prev.srcs.get(s) is slot:
                carried[s] = 1
            elif slot.pending_dev is not None:
                carried[s] = 2
                firsts[s] = slot.pending_dev
                take_first = True
            else:
                toks[s] = slot.pending
            lanes.append(_LaneRec(s, slot, take_first))
        # ONE upload: the packed buffer is a copy, so the dispatch may
        # execute after the host has already mutated its mirrors
        # (admissions, drains, detaches) — an upload can ALIAS numpy memory
        # on the CPU backend.  The sampling row is uploaded again only
        # after an admission changed it.
        ints = self._upload(pack_decode_state(
            toks, self._lengths, remaining, eos_ids, active, carried,
            self._page_tables))
        if self._sampling_dev is None:
            self._sampling_dev = self._upload(
                np.concatenate([self._temps, self._top_ps]))
        sampling = self._sampling_dev
        fn = self._horizon_exec(K, greedy)
        self.step_launches += 1        # counted where it is issued

        def call(cache, key, *prev_state):
            """The dispatch's ONE launch; `prev_state` (overlap engines
            only) is the previous dispatch's (toks, lengths, rem, done)
            device outputs.  Runs on the dispatching thread."""
            carry = (prev_state + (tuple(firsts),),) if prev_state else ()
            return self._call_paged(fn, self.params, cache, key, ints,
                                    sampling, *carry, keyed=True)

        # carry sources are EXACTLY the dispatched lanes: only they got
        # real inputs merged in (a slot skipped by _provision this step
        # has default-filler rows in this dispatch — toks 0, remaining 1 —
        # and the horizon clobbers an inactive lane's token carry with the
        # eos filler), so a skipped lane must fall back to its host state,
        # which the previous drain left exact
        srcs = {lane.s: lane.slot for lane in lanes}
        rec = _Inflight(K, greedy, lanes, srcs, self.overlap)
        if not self.overlap:
            # synchronous: no dispatch is ever in flight, nothing to carry
            res = call(self._cache, self._key)
            rec.out, rec.toks, rec.lengths, rec.rem, rec.done = res[:5]
            self._key, self._cache = res[-2:]
        elif prev is not None and prev.fut is not None:
            # chain INSIDE the worker: the previous dispatch's outputs
            # (pages, key + carry) flow worker-to-worker, never through
            # the main thread
            pfut = prev.fut

            def work_chained():
                pres = pfut.result()
                return call(pres[-1], pres[-2], *pres[1:5])

            rec.fut = self._executor.submit(work_chained)
        else:
            # pipeline empty (or already joined by an admission): the
            # page binding, the key and any carry state are concrete arrays
            cache0, key0 = self._cache, self._key
            pstate = self._no_carry if prev is None \
                else (prev.toks, prev.lengths, prev.rem, prev.done)
            rec.fut = self._executor.submit(
                lambda: call(cache0, key0, *pstate))
        self.steps_run += 1
        # horizon dispatches always emit tokens on-device (fused greedy
        # argmax or in-loop sampling) — logits never leave the device
        self.fused_sample_steps += 1
        if prev is not None:
            self.overlap_steps += 1
        tel = self.telemetry
        if tel is not None:
            for s in run:
                tel.request_event(self._slots[s].req.rid, "decode_dispatch",
                                  k=K)
        return rec

    def _resolve(self, rec, rebind: bool):
        """Materialize an overlap dispatch's outputs from its future (and
        rebind the engine page buffers to them when `rec` is still the
        NEWEST dispatch — a superseded record's pages were already donated
        onward).  Re-raises the worker's exception (RecompileBudgetError:
        the worker's `_call_paged` already rebound the pages from the
        executed call, and the dispatch's tokens are discarded exactly as
        on the synchronous path)."""
        if rec.fut is None:
            return
        fut, rec.fut = rec.fut, None
        res = fut.result()
        rec.out, rec.toks, rec.lengths, rec.rem, rec.done = res[:5]
        if rebind:
            self._key, self._cache = res[-2:]

    def _join_dispatch(self):
        """Block until the pending async dispatch's output binding is
        concrete (overlap mode), so a page-consuming executable — an
        admission prefill, a chunk, a COW copy — can chain on real
        arrays.  The drain of its TOKENS still happens later; joining is
        about the page buffers, not the step results."""
        rec = self._inflight
        if rec is None or rec.fut is None:
            return
        try:
            with self._span("overlap_join_sync"):
                self._resolve(rec, rebind=True)
        except RecompileBudgetError:
            # the dispatch is discarded (its tokens were never recorded;
            # lengths never advanced — the rewind invariant); the worker
            # already rebound the page buffers, so the engine stays usable
            self._inflight = None
            raise

    def _drain(self, rec, rebind: bool = True):       # graftlint: hot
        """Fetch one dispatch's emitted tokens (ONE batched device sync)
        and replay the engine's freeze logic on the host: record tokens
        until each lane's EOS/budget stop — exactly mirroring the
        device-side freeze, so the host length mirror is reconstructed
        without fetching `lengths` at all — then retire what finished.
        Lanes whose slot was already retired by an earlier drain (an
        unpredicted EOS that rode one extra dispatch frozen) are
        skipped: their rows hold frozen `eos_ids` filler by
        construction.  `rebind=False` marks a record superseded by a
        newer dispatch (its page outputs were donated onward and must
        not re-bind)."""
        pre = "overlap" if rec.overlapped else "decode"
        with self._span(f"{pre}_sync"):
            self._resolve(rec, rebind=rebind)
            # the ONE per-step sync: every lane's K tokens in one batched
            # fetch
            out = np.asarray(rec.out)  # graftlint: disable=SYNC001
        with self._span(f"{pre}_record") as late:
            late["tokens"] = self._replay(rec, out)

    def _replay(self, rec, out) -> int:               # graftlint: hot
        """The host half of `_drain`: record each lane's fetched tokens
        until its EOS/budget stop, advance the length mirror, retire what
        finished.  Returns the tokens emitted."""
        total = attended = pages = 0
        ps = self.page_size
        lens = self._lengths.tolist()     # host mirror -> python ints
        for lane in rec.lanes:
            s, slot = lane.s, lane.slot
            if not lane.retiring and self._slots[s] is not slot:
                continue           # retired by an earlier drain
            if slot.req.finish_time:
                continue
            base = lane.base_len if lane.retiring else lens[s]
            row = out[s].tolist()  # host ints, no per-token conversion
            done = False
            if lane.take_first and slot.pending_dev is not None:
                # the admission-deferred first token: materialized when
                # its dispatch ran — this fetch waits on nothing new
                tok0 = int(np.asarray(slot.pending_dev))  # graftlint: disable=SYNC001
                slot.pending_dev = None
                done = self._emit_token(slot, tok0)
                total += 1
            emitted = 0
            if not done:
                for tok in row:
                    emitted += 1
                    done = self._emit_token(slot, tok)
                    if done:
                        break
            total += emitted
            # KV positions this lane's live decode steps had to read: step
            # j of `emitted` attends base + j (its own fresh row included)
            attended += emitted * base + emitted * (emitted + 1) // 2
            # ... and the pages holding them, ceil((base + j) / ps) summed
            # over j = 1..emitted in closed form
            pages += (_pages_up_to(base + emitted, ps)
                      - _pages_up_to(base, ps))
            if done:
                if lane.retiring:
                    self._finish_detached(slot, base + emitted)
                else:
                    self._lengths[s] = base + emitted
                    self._finish(s)
            else:
                # still live: the lane's last emitted token is the next
                # pending one; the device carry holds the same state
                self._lengths[s] = base + emitted
                slot.pending = row[emitted - 1]
        self.decode_kv_tokens_attended += attended
        self.decode_kv_pages_attended += pages
        return total

    # -- the serving loop --------------------------------------------------
    @property
    def num_active(self) -> int:
        return sum(1 for sl in self._slots if sl is not None)

    @property
    def page_bytes(self) -> int:
        """Bytes ONE pool page costs on device (every page store the family
        names, K + V or the latent rows, across all layers; per-page scales
        included when ``kv_dtype`` is set) — the unit the
        telemetry memory observatory multiplies page counts by, so
        capacity wins from quantized pages are visible in BYTES, not just
        page counts (`mem.pool_allocated_bytes` / `mem.pool_capacity_bytes`
        gauges, fleet snapshots).  Pure geometry — computed once and
        cached (the telemetry memory sampler reads it every step).  Under
        tensor parallelism this is the PER-CHIP cost: the KV-head axis is
        sharded over mp, so each chip holds 1/tp of every page."""
        pb = self._page_bytes
        if pb is None:
            # the page axis is axis 2 of every leaf of the page stores
            leaves = self._jax.tree_util.tree_leaves(self._page_stores())
            pb = self._page_bytes = sum(
                a.dtype.itemsize * math.prod(a.shape) // a.shape[2]
                for a in leaves) // self.tp
        return pb

    def step(self) -> bool:                           # graftlint: hot
        """One engine step: retire overdue requests, admit queued requests
        into free slots (attaching cached prefixes), advance each
        mid-prefill slot by one chunk, provision pages for the decode
        horizon, run the jitted K-step decode, record sampled tokens,
        retire finished requests into the prefix cache.  Returns True when
        any slot made progress.

        When nobody can progress — the former hard-deadlock RuntimeError —
        the engine walks the degradation ladder: evict unreferenced cached
        pages, then preempt a victim (pages parked in the cache, request
        requeued for re-prefill); under a fully injected pool-pressure
        window it parks and reports no progress.

        The step and each of its host phases is a `_span`: inside an open
        ``jax.profiler`` trace they are ``serve.step`` and its children
        ``serve.<phase>`` on this thread's line of the host plane.  With
        telemetry on, the step's host wall time lands in the
        ``engine.step_host_s`` histogram, a per-step summary lands in the
        flight recorder, and an active injected pool-pressure window
        auto-dumps the recorder (postmortem for fault drills)."""
        with self._span("step", step=self._step_seq + 1):
            tel = self.telemetry
            if tel is None:
                return self._step_impl()
            t0 = tel.clock()
            pre_tok = self.tokens_generated
            progressed = self._step_impl()
            tel.step_done(self, t0, progressed,
                          self.tokens_generated - pre_tok)
            return progressed

    def _step_impl(self) -> bool:                     # graftlint: hot
        self._step_seq += 1
        # serve.wedge: the engine "hangs" — the step returns without doing
        # ANY work (no admissions, no dispatch), the deterministic stand-in
        # for a replica that stopped responding.  A fleet watchdog sees
        # consecutive no-progress steps and declares the replica wedged.
        if fault_point("serve.wedge", engine=self.name,
                       step=self._step_seq) is not None:
            if self.telemetry is not None:
                self.telemetry.flight.record("fault", point="serve.wedge",
                                             step=self._step_seq)
            return False
        self._pressure = fault_point("serve.pool_pressure",
                                     step=self.steps_run) is not None
        pre_tokens = self.tokens_generated
        pre_finished = len(self._finished)
        # host scheduling phase: deadline sweep + admissions.  Admission
        # prefill dispatches run inside this span as its children and
        # record their own; telemetry's sched_done subtracts them so the
        # utilization buckets stay disjoint
        with self._span("sched", queued=len(self._queue),
                        active=self.num_active):
            # overlap: hand budget-predicted retiring lanes to the
            # admission queue before admitting, so a retirement costs zero
            # lane idleness
            self._detach_predicted()
            self._retire_overdue()
            pre_admit_seq = self._admit_seq
            self._admit()
            if self.overlap:
                self._flush_exhausted()
            # serve.crash phase="sched": die mid-step AFTER admissions
            # mutated slot/pool state but BEFORE any token was produced
            # this step — the raising InjectedFault models the process
            # dying; host state is consistent (a step boundary for page
            # accounting) but every in-flight request is stranded until a
            # fleet migrates it.
            fault_point("serve.crash", engine=self.name,
                        step=self._step_seq, phase="sched")
        # chunked prefill: each mid-prefill slot advances ONE chunk per
        # step, interleaved with the decode horizon below — a long prompt
        # never head-of-line blocks the running decodes or short arrivals.
        # A slot admitted THIS step already ran its first chunk inside
        # _admit (chunk_step guard), so the per-step prefill bound holds
        # on the admission step too.
        prefilled = False
        for s, slot in enumerate(self._slots):
            if slot is not None and slot.prefill_pos is not None \
                    and slot.chunk_step != self._step_seq:
                self._prefill_advance(s)
                prefilled = True
        if prefilled:
            self._admit()              # a 1-token request may have retired
        # speculative decoding: when any slot has a draft, ONE verify
        # dispatch scores K+1 positions per slot (slots without drafts ride
        # along as plain single-token lanes — mixed batches are the normal
        # case).  Draftless steps and pool-tight steps fall through to the
        # decode horizon below, so the degradation ladder is untouched.
        if self.speculative:
            drafts = self._propose_drafts()
            if drafts:
                # verify acceptance is HOST logic by design — the pipeline
                # drains first so every pending token is an exact host int
                # (overlap engines speculate on draftful steps at sync
                # pacing and double-buffer the draftless ones; drafts are
                # re-proposed on the drained state)
                if self._inflight is not None or any(
                        sl is not None and sl.pending_dev is not None
                        for sl in self._slots):
                    self.quiesce()
                    drafts = self._propose_drafts()
            if drafts:
                # per-slot need: 1 + draft length covers every K/V write
                # (padding lanes hit the trash page); draftless ride-along
                # lanes need a single token — no K+1 over-provisioning
                # that would evict cache / stall them under pool pressure
                run = self._provision(
                    {s: 1 + len(d) for s, d in drafts.items()})
                if run:
                    self._verify(run, drafts)
                    # serve.crash phase="record": die after this step's
                    # tokens were recorded but before anyone outside the
                    # engine observed them (mid-speculation intersection)
                    fault_point("serve.crash", engine=self.name,
                                step=self._step_seq, phase="record")
                    return True
        K = self.decode_horizon
        prev = self._inflight
        if prev is not None:
            # host lengths lag the in-flight dispatch by up to K tokens:
            # provision carried lanes for BOTH the in-flight writes and
            # this dispatch's (min(2K, remaining) is exact worst case);
            # fresh lanes provision the usual K
            want = {}
            for s, sl in enumerate(self._slots):
                if sl is not None and sl.prefill_pos is None:
                    want[s] = 2 * K if prev.srcs.get(s) is sl else K
            run = self._provision(want) if want else []
        else:
            run = self._provision(K)
        if not run and self._inflight is not None:
            # the pool cannot cover anyone while a step is in flight —
            # drain it (its retirements may free pages) and let the
            # degradation ladder act on exact state
            self.quiesce()
            run = self._provision(K)
        if not run and K > 1:
            # the pool cannot cover a full horizon for anyone — fall back to
            # single-step pacing so retirements can still free pages
            K = 1
            run = self._provision(1)
        # self-healing: cache eviction happens inside _provision/_admit;
        # when even that freed nothing usable, evict ONE victim per
        # no-progress step.  Freed pages go to the stalled SURVIVORS (no
        # re-admission here — the victim at the queue head would
        # immediately steal its own pages back and livelock).  One
        # eviction always suffices for a real deadlock: a stalled slot's
        # single-step growth need is <= 1 page and any victim frees >= 1
        # OWNED page (its suffix/COW page at minimum — cache-shared pages
        # may stay parked), so a survivor runs; when it doesn't (an
        # injected pool-pressure window hides every page), per-step
        # budgeting bounds the wasted re-prefills to one victim per
        # stalled step.
        # an admission THIS step ran its first prefill chunk inside _admit
        # (chunk_step guard) — that is progress, not a stall: without this,
        # a lone chunked-prefill admission with no decodable neighbor would
        # be preempted on its own admission step and thrash admit -> chunk
        # -> preempt until the prefix cache converged the re-prefills
        admitted = self._admit_seq != pre_admit_seq
        if not run and not prefilled and not admitted \
                and self.num_active > 0:
            self._preempt(self._pick_victim())
            K = 1
            run = self._provision(1)
        if not run:
            # pure-prefill step, pool-pressure window, or nothing to do
            # (any in-flight work was already drained above, so tokens /
            # retirements it produced still count as progress)
            return prefilled or admitted \
                or self.tokens_generated > pre_tokens \
                or len(self._finished) > pre_finished
        greedy = all(self._temps[s] <= 0.0 for s in run)
        try:
            with self._span("overlap_dispatch" if self.overlap
                            else "decode_dispatch", slots=len(run), k=K,
                            family=self.family.name,
                            attention=self.family.attention_path):
                rec = self._dispatch_decode(run, K, greedy)
            prev, self._inflight = self._inflight, rec
            if prev is not None:
                # drain step N-1's tokens WHILE step N runs: the fetch
                # waits only for N-1, and all host record/retire work
                # overlaps N
                self._drain(prev, rebind=False)
            if not self.overlap:
                # synchronous pacing: drain the dispatch we just issued
                self._inflight = None
                self._drain(rec)
        except RecompileBudgetError:
            # the raising dispatch's tokens are DISCARDED (lengths were
            # never advanced; K/V above lengths is never attended — the
            # rewind invariant), exactly as a synchronous engine discards
            # them; anything still drainable is drained so the pipeline
            # is empty when the error propagates
            try:
                self.quiesce()
            except RecompileBudgetError:
                pass           # the same failed dispatch, re-surfaced
            raise
        # serve.crash phase="record": die after this horizon's tokens were
        # recorded (and finished requests retired) but before any caller
        # observed them — a router that re-prefills from what it last
        # STREAMED must regenerate these tokens bit-identically (greedy)
        fault_point("serve.crash", engine=self.name, step=self._step_seq,
                    phase="record")
        return True

    def run(self, max_steps: int | None = None,
            max_stall_steps: int = 1000):
        """Drive until every submitted request finished; returns
        {rid: Request} (each with .generated / .output_ids filled).

        Consecutive no-progress steps (possible only while an injected
        pool-pressure window hides every page) are bounded by
        `max_stall_steps`; exceeding it raises `EngineStalledError` — the
        pool-sizing deadlock itself is resolved by cache eviction +
        preemption and can no longer raise."""
        steps = 0
        stalled = 0
        while self._queue or self.num_active or self._inflight is not None:
            progressed = self.step()
            stalled = 0 if progressed else stalled + 1
            if stalled >= max_stall_steps:
                if self.telemetry is not None:
                    # the flight recorder's reason for existing: dump the
                    # recent-event window BEFORE the engine dies
                    self.telemetry.fault_dump(
                        "engine_stalled", stalled_steps=stalled,
                        active=self.num_active, queued=len(self._queue),
                        free_pages=self.pool.num_free,
                        num_pages=self.pool.num_pages)
                raise EngineStalledError(
                    f"no engine progress for {stalled} consecutive steps "
                    f"({self.num_active} active, {len(self._queue)} queued, "
                    f"{self.pool.num_free} pages free of "
                    f"{self.pool.num_pages}) — a fault window that never "
                    f"clears?")
            steps += 1
            if max_steps is not None and steps >= max_steps:
                break
        return dict(self._finished)

    # -- snapshot / restore ------------------------------------------------
    # The engine's own durability (ROADMAP item 4): everything a process
    # restart would otherwise kill — in-flight Requests with emitted tokens,
    # the seeded RNG key stream, deadlines, slot table, page tables, PagePool
    # refcounts, prefix-cache index, adaptive spec state — serializes into a
    # versioned state dict and comes back bit-exactly.  Two modes:
    #
    #   * "full_kv": the referenced KV pages ride along raw — restore is a
    #     scatter back into the pool and decode CONTINUES without any
    #     re-prefill (fast restore; requires identical pool geometry);
    #   * "compact": token prefixes only — restore requeues every in-flight
    #     request through the preemption-resume path (re-prefill of prompt +
    #     emitted), so the snapshot is cheap and the restored pool may have
    #     a different size/geometry entirely.
    #
    # Greedy outputs are bit-exact across snapshot/restore in BOTH modes
    # (tests/test_fleet.py) — full_kv by construction, compact by the PR 2/3
    # preemption + re-prefill guarantee.  Snapshots are taken BETWEEN steps
    # (any step boundary is a consistent point for page accounting).

    SNAPSHOT_VERSION = 1

    def _req_state(self, r: Request) -> dict:
        eos = r.eos_token_id
        return {
            "rid": int(r.rid), "prompt": np.asarray(r.prompt).tolist(),
            "max_new_tokens": int(r.max_new_tokens),
            "temperature": float(r.temperature), "top_p": float(r.top_p),
            "eos_token_id": None if eos is None else int(eos),
            "deadline": None if r.deadline is None else float(r.deadline),
            "generated": [int(t) for t in r.generated],
            "submit_time": float(r.submit_time),
            "admit_time": float(r.admit_time),
            "first_token_time": float(r.first_token_time),
            "finish_time": float(r.finish_time),
            "timed_out": bool(r.timed_out),
            "preemptions": int(r.preemptions),
            "cached_prefix_tokens": int(r.cached_prefix_tokens),
            "draft_proposed": int(r.draft_proposed),
            "draft_accepted": int(r.draft_accepted),
            "trace_id": None if r.trace_id is None else int(r.trace_id),
        }

    @staticmethod
    def _req_from_state(d: dict) -> Request:
        return Request(
            rid=int(d["rid"]),
            prompt=np.asarray(d["prompt"], np.int32),
            max_new_tokens=int(d["max_new_tokens"]),
            temperature=float(d["temperature"]), top_p=float(d["top_p"]),
            eos_token_id=d["eos_token_id"], deadline=d["deadline"],
            generated=[int(t) for t in d["generated"]],
            submit_time=d["submit_time"], admit_time=d["admit_time"],
            first_token_time=d["first_token_time"],
            finish_time=d["finish_time"], timed_out=bool(d["timed_out"]),
            preemptions=int(d["preemptions"]),
            cached_prefix_tokens=int(d["cached_prefix_tokens"]),
            draft_proposed=int(d["draft_proposed"]),
            draft_accepted=int(d["draft_accepted"]),
            # .get: pre-ISSUE-12 snapshots carry no trace_id (version
            # unchanged — absent simply means "not stitched")
            trace_id=d.get("trace_id"))

    _COUNTER_ATTRS = ("steps_run", "tokens_generated", "preemptions",
                      "timeouts", "rejections", "cache_hits",
                      "cache_hit_tokens", "prefill_tokens", "prefill_calls",
                      "prefill_tokens_dispatched", "prefill_tokens_padded",
                      "prefill_kv_pages_written",
                      "decode_kv_tokens_attended",
                      "decode_kv_pages_attended",
                      "cache_evictions", "cache_evict_visits",
                      "cow_copies", "verify_steps",
                      "draft_tokens_proposed", "draft_tokens_accepted",
                      "overlap_steps", "quiesces", "fused_sample_steps",
                      "kv_exports", "kv_imports", "kv_pages_exported",
                      "kv_pages_imported", "step_launches", "step_uploads")

    def snapshot(self, mode: str = "full_kv",
                 include_finished: bool = True) -> dict:
        """Serialize the complete engine state at a step boundary.

        Returns a flat state dict ready for the crash-consistent
        ``distributed.checkpoint.save_state_dict`` writer (see
        ``serving.EngineSnapshotManager``): ``meta`` is one JSON string of
        host state, ``rng`` the engine PRNG key, and in ``full_kv`` mode
        ``kv_pages``/``kv_k``/``kv_v`` carry the referenced KV pages raw.
        ``include_finished`` keeps already-retired requests in the snapshot
        so a restored engine's ``run()`` still returns them."""
        if mode not in ("full_kv", "compact"):
            raise ValueError(f"unknown snapshot mode {mode!r}")
        if mode == "full_kv":
            self._refuse_recurrent('snapshot("full_kv")')
        # a snapshot is an EXACT state: drain the double-buffered pipeline
        # (in-flight tokens recorded, deferred first tokens flushed) so
        # the serialized pendings/lengths/pool are host-true
        self.quiesce()
        requests: dict[str, dict] = {}

        def _ref(r: Request) -> int:
            requests.setdefault(str(r.rid), self._req_state(r))
            return int(r.rid)

        slots = []
        for s, slot in enumerate(self._slots):
            if slot is None:
                slots.append(None)
                continue
            slots.append({
                "rid": _ref(slot.req),
                "pages": [int(p) for p in slot.pages],
                "pending": int(slot.pending),
                "admit_seq": int(slot.admit_seq),
                "prefill_pos": None if slot.prefill_pos is None
                else int(slot.prefill_pos),
                "ctx": None if slot.ctx is None
                else np.asarray(slot.ctx).tolist(),
                "resuming": bool(slot.resuming),
                "chunk_step": int(slot.chunk_step),
                "spec_k": int(slot.spec_k),
                "length": int(self._lengths[s]),
            })
        meta = {
            "version": self.SNAPSHOT_VERSION,
            "mode": mode,
            "geometry": {
                "num_slots": self.num_slots, "page_size": self.page_size,
                "num_pages": self.pool.num_pages,
                "max_pages_per_seq": self.max_pages_per_seq,
                "prefix_cache": self.cache is not None,
                # a full-KV snapshot's raw pages only scatter back into a
                # pool of the SAME kv_dtype (the stored bytes are that
                # dtype's codes + scales); any mismatch falls back to the
                # re-prefill path, which requantizes for the new store
                "kv_dtype": self.kv_dtype,
            },
            "requests": requests,
            "slots": slots,
            "queue": [_ref(r) for r in self._queue],
            "finished": [_ref(r) for r in self._finished.values()]
            if include_finished else [],
            "next_rid": int(self._next_rid),
            "admit_seq": int(self._admit_seq),
            "step_seq": int(self._step_seq),
            "counters": {k: int(getattr(self, k))
                         for k in self._COUNTER_ATTRS},
            "pool": {"free": [int(p) for p in self.pool._free],
                     "refs": [[int(p), int(c)]
                              for p, c in sorted(self.pool._refs.items())]},
        }
        state: dict = {"rng": np.asarray(self._key)}
        if mode == "full_kv":
            if self.cache is not None:
                c = self.cache
                meta["cache"] = {
                    "tick": int(c._tick), "insertions": int(c.insertions),
                    "evictions": int(c.evictions),
                    "full": [[e.key.hex(), e.parent.hex(), int(e.page),
                              int(e.tick)] for e in c._full.values()],
                    "partial": [[e.parent.hex(),
                                 np.frombuffer(e.tokens, np.int32).tolist(),
                                 int(e.page), int(e.tick)]
                                for d in c._partial.values()
                                for e in d.values()],
                }
            else:
                meta["cache"] = None
            ids = sorted(self.pool._refs)
            state["kv_pages"] = np.asarray(ids, np.int32)
            state.update(self._gather_pages(ids))
        state["meta"] = json.dumps(meta)
        return state

    def _refuse_recurrent(self, what: str):
        """Transfers that move K/V PAGES alone leave a recurrent family's
        slots without the state that belongs to those pages."""
        if self.family.recurrent:
            raise NotImplementedError(
                f"{what}: the {self.family.name} family keeps recurrent "
                f"state a slot beside its KV pages, and a transfer of that "
                f"state (a snapshot of it at the page boundary the pages "
                f"end on) is missing — use the re-prefill path "
                f'(snapshot("compact"), adopt)')

    def recurrent_state(self, rid: int):
        """{name: host array} of what the family keeps in the slot request
        ``rid`` rides or LAST rode (its recurrent state; ``moe_sel
        [layers, positions, k]``, the experts each consumed position
        selected) — after every token the engine fed for it, and only until
        another request is admitted to that slot; None for a family without
        such state or a request never admitted."""
        r = self.lookup(rid)
        if self.family.slot_state is None or r is None or r.slot < 0:
            return None
        self.quiesce()
        return self.family.slot_state(self._cache, r.slot)

    def _gather_pages(self, ids) -> dict:
        """Pull pages `ids` to the host as named planes — the read half of
        the full-KV transfer primitive snapshot() and export_kv() share.
        The page axis is axis 2 of [L, Hkv, NP+1, ps, D] (the
        models/llama.gather_kv_pages contract); only pages holding a
        reference carry information (free pages are dead state, the trash
        page is garbage by contract).  Gather ON DEVICE first so the host
        transfer (both callers stand at a quiesced sync point) is
        proportional to live context, not pool capacity.  A quantized
        store ships data pages AND their per-row scales together — a
        splice that lost the scales would write back garbage magnitudes."""
        from ..models.llama import gather_kv_pages
        idx = self._jnp.asarray(np.asarray(ids, np.int32))
        planes = {}
        for name, store in self._page_stores().items():
            got = gather_kv_pages(store, idx)
            if isinstance(got, dict):           # a quantized store's planes
                planes.update({f"kv_{name}_{part}": np.asarray(a)
                               for part, a in got.items()})
            else:
                planes[f"kv_{name}"] = np.asarray(got)
        return planes

    def _scatter_pages(self, ids, planes: dict):
        """Splice host planes (a `_gather_pages` result, same page order)
        into this engine's store at page ids `ids` — the write half of the
        transfer primitive `_restore_full` and `import_kv` share."""
        from ..models.llama import scatter_kv_pages
        idx = self._jnp.asarray(np.asarray(ids, np.int32))
        stores = {}
        for name, store in self._page_stores().items():
            mine = {part: planes[f"kv_{name}_{part}"] for part in store} \
                if isinstance(store, dict) else planes[f"kv_{name}"]
            stores[name] = scatter_kv_pages(store, idx, mine)
        self._cache = {**self._cache, **stores}

    # -- KV handoff (disaggregated prefill/decode) -------------------------
    KV_HANDOFF_VERSION = 1

    def handoff_ready(self, rid: int) -> bool:
        """True when `rid` rides a slot whose prefill is COMPLETE (dense,
        or every chunk executed) — the state a prefill-role replica hands
        to a decode replica.  First token is already banked (TTFT charged
        to the prefill engine); mid-chunked-prefill slots keep prefilling
        here.  Cheap host predicate — no quiesce, no device access."""
        for slot in self._slots:
            if slot is not None and slot.req.rid == rid:
                return (slot.prefill_pos is None and slot.ctx is None
                        and len(slot.req.generated) > 0)
        return False

    def export_kv(self, rids) -> dict:
        """Serialize the in-flight state of `rids` (slot-resident requests)
        plus exactly the KV pages their page tables reference, as one
        handoff packet for :meth:`import_kv` on another engine — the
        full-KV gather :meth:`snapshot` uses, scoped to a request subset.

        READ-ONLY on this engine: the caller decides when (whether) to
        `cancel` the source requests — cancelling parks their written KV
        in this engine's prefix cache, so a fallback re-prefill can still
        hit.  Raises KeyError for a rid not currently riding a slot
        (queued, finished, or unknown — nothing to hand off)."""
        self._refuse_recurrent("export_kv")
        # exact host state: drain the double-buffered pipeline first (the
        # drain itself may RETIRE a rid — the KeyError below reports it)
        self.quiesce()
        by_rid = {slot.req.rid: (s, slot)
                  for s, slot in enumerate(self._slots) if slot is not None}
        entries = []
        for rid in rids:
            if rid not in by_rid:
                raise KeyError(
                    f"export_kv: rid {rid} holds no slot (queued, finished "
                    "or unknown) — nothing to hand off")
            s, slot = by_rid[rid]
            entries.append({
                "req": self._req_state(slot.req),
                "pages": [int(p) for p in slot.pages],
                "pending": int(slot.pending),
                "prefill_pos": None if slot.prefill_pos is None
                else int(slot.prefill_pos),
                "ctx": None if slot.ctx is None
                else np.asarray(slot.ctx).tolist(),
                "resuming": bool(slot.resuming),
                "chunk_step": int(slot.chunk_step),
                "length": int(self._lengths[s]),
            })
        ids = sorted({p for e in entries for p in e["pages"]})
        planes = self._gather_pages(ids)
        packet = {
            "version": self.KV_HANDOFF_VERSION,
            "page_size": self.page_size,
            "kv_dtype": self.kv_dtype,
            "tp": self.tp,
            "kv_pages": [int(p) for p in ids],
            "planes": planes,
            "requests": entries,
            "bytes": int(sum(np.asarray(v).nbytes for v in planes.values())),
        }
        self.kv_exports += 1
        self.kv_pages_exported += len(ids)
        return packet

    def import_kv(self, packet: dict) -> dict:
        """Splice an :meth:`export_kv` packet into this RUNNING engine:
        allocate fresh pages, scatter the shipped planes into them, remap
        every request's page table onto the new ids, and seat the requests
        in free slots to continue decoding from exactly where the source
        engine stood — zero re-prefill, greedy bit-exact.

        Raises :class:`KVHandoffError` when the packet can NEVER splice
        here (page geometry / kv_dtype / tensor-parallel degree mismatch:
        head-sharded planes land rank-local only at EQUAL mp degree — the
        caller's fallback is re-prefill via `adopt`), and
        :class:`AdmissionRejected` for transient pressure (no free slot /
        no free pages even after the cache-eviction rung) — the ladder
        order of :meth:`_admit` is preserved.  Returns {source rid: rid
        minted here}."""
        self._refuse_recurrent("import_kv")
        if packet.get("version") != self.KV_HANDOFF_VERSION:
            raise KVHandoffError(
                f"kv handoff version {packet.get('version')!r} != "
                f"{self.KV_HANDOFF_VERSION}")
        if packet["page_size"] != self.page_size:
            raise KVHandoffError(
                f"page_size {packet['page_size']} != {self.page_size}: "
                "shipped pages cannot re-block without a device pass")
        if packet["kv_dtype"] != self.kv_dtype:
            raise KVHandoffError(
                f"kv_dtype {packet['kv_dtype']!r} != {self.kv_dtype!r}: "
                "stored codes/scales are the source dtype's — re-prefill "
                "requantizes for this store")
        if packet["tp"] != self.tp:
            raise KVHandoffError(
                f"mp degree {packet['tp']} != {self.tp}: head-sharded "
                "planes are rank-local only at equal mp degree — "
                "re-prefill (adopt) reshards for this submesh")
        entries = packet["requests"]
        if any(len(e["pages"]) > self.max_pages_per_seq for e in entries):
            raise KVHandoffError(
                "request page table exceeds this engine's "
                f"max_pages_per_seq={self.max_pages_per_seq}")
        # splice at an exact step boundary of THIS engine
        self.quiesce()
        free_slots = [i for i, sl in enumerate(self._slots) if sl is None]
        if len(entries) > len(free_slots):
            raise AdmissionRejected(
                f"import_kv: {len(entries)} requests > {len(free_slots)} "
                "free slots")
        old_ids = [int(p) for p in packet["kv_pages"]]
        n = len(old_ids)
        if n > self._avail():
            # ladder: evict unreferenced cached pages before giving up
            self._evict(n - self._avail())
        if n > self._avail():
            raise AdmissionRejected(
                f"import_kv: need {n} pages, {self._avail()} free after "
                "eviction")
        new_ids = self.pool.alloc(n)
        remap = dict(zip(old_ids, new_ids))
        self._scatter_pages(new_ids, packet["planes"])
        # extra references for pages shared by several shipped tables
        # (handed-off requests that shared a cached prefix on the source)
        nrefs: dict[int, int] = {}
        for e in entries:
            for p in e["pages"]:
                nrefs[p] = nrefs.get(p, 0) + 1
        extra = [remap[p] for p, c in nrefs.items() for _ in range(c - 1)]
        if extra:
            self.pool.share(extra)
        mapping: dict[int, int] = {}
        now = self._clock()
        for e, s in zip(entries, free_slots):
            d = dict(e["req"])
            src_rid = int(d["rid"])
            d["rid"] = self._next_rid
            self._next_rid += 1
            req = self._req_from_state(d)
            mapping[src_rid] = req.rid
            pages = [remap[p] for p in e["pages"]]
            slot = _Slot(req, pages, int(e["pending"]),
                         admit_seq=self._admit_seq)
            self._admit_seq += 1
            slot.prefill_pos = e["prefill_pos"]
            slot.ctx = None if e["ctx"] is None \
                else np.asarray(e["ctx"], np.int32)
            slot.resuming = bool(e["resuming"])
            slot.chunk_step = int(e["chunk_step"])
            if self.speculative and req.temperature <= 0.0:
                # drafting is THIS engine's capability (verify executables
                # compile per engine K): rebuild the pure-function n-gram
                # index from the shipped token stream
                slot.spec_k = self.speculative
                slot.draft = _NgramDraft(
                    np.concatenate([req.prompt,
                                    np.asarray(req.generated, np.int32)]),
                    max_n=self.spec_max_ngram)
            self._slots[s] = slot
            row = np.zeros((self.max_pages_per_seq,), np.int32)
            row[:len(pages)] = pages
            self._page_tables[s] = row
            self._lengths[s] = int(e["length"])
            self._set_sampling(s, req)
            if self.telemetry is not None:
                # stitched-trace continuity (the restore convention): the
                # handed-off request opens a track on THIS engine's tracer
                # whose first event carries handoff=True — the attribution
                # gap classifier turns the inter-engine gap into a
                # `kv_transfer` segment
                attrs = {"handoff": True}
                if req.trace_id is not None:
                    attrs["trace_id"] = req.trace_id
                self.telemetry.request_event(req.rid, "submitted", t=now,
                                             **attrs)
        self.kv_imports += 1
        self.kv_pages_imported += n
        return mapping

    def restore(self, state: dict) -> str:
        """Load a :meth:`snapshot` state dict into this FRESH engine
        (construct with the same params/config first; raises if this engine
        already ran work).  Returns the restore path taken:

          * ``"full_kv"`` — pool geometry matched a full-KV snapshot: KV
            pages scattered back, slots/page tables/cache rebuilt in place,
            decode continues with zero re-prefill;
          * ``"reprefill"`` — compact snapshot, OR a full-KV snapshot whose
            geometry no longer fits (e.g. restored into a smaller pool):
            every in-flight request requeues through the preemption-resume
            path and re-prefills prompt + emitted tokens, walking the
            normal admission ladder of THIS engine's pool.

        Greedy outputs are bit-exact vs the uninterrupted engine either
        way."""
        meta = state["meta"]
        if isinstance(meta, (bytes, np.ndarray)):
            meta = bytes(meta).decode()
        if isinstance(meta, str):
            meta = json.loads(meta)
        if meta.get("version") != self.SNAPSHOT_VERSION:
            raise ValueError(
                f"engine snapshot version {meta.get('version')!r} != "
                f"{self.SNAPSHOT_VERSION}")
        if self.num_active or self._queue or self._finished or self.steps_run:
            raise RuntimeError(
                "ServingEngine.restore: target engine already holds state — "
                "restore into a freshly constructed engine")
        self._key = self._jax.device_put(np.asarray(state["rng"]),
                                         self._host_sharding)
        reqs = {int(r): self._req_from_state(d)
                for r, d in meta["requests"].items()}
        for rid in meta["finished"]:
            self._finished[rid] = reqs[rid]
        self._next_rid = max(int(meta["next_rid"]), self._next_rid)
        for k, v in meta["counters"].items():
            setattr(self, k, int(v))
        self._admit_seq = int(meta["admit_seq"])
        g = meta["geometry"]
        fast = (meta["mode"] == "full_kv"
                and not self.family.recurrent     # pages without state
                and g["num_slots"] == self.num_slots
                and g["page_size"] == self.page_size
                and g["num_pages"] == self.pool.num_pages
                and g["max_pages_per_seq"] == self.max_pages_per_seq
                and bool(g["prefix_cache"]) == (self.cache is not None)
                # .get: pre-quant snapshots carry no kv_dtype (== f32/bf16
                # raw pages, the None default)
                and g.get("kv_dtype") == self.kv_dtype)
        if fast:
            self._restore_full(meta, state, reqs)
            applied = "full_kv"
        else:
            self._restore_reprefill(meta, reqs)
            applied = "reprefill"
        if self.telemetry is not None:
            # stitched-trace continuity: a restored in-flight request gets
            # a trace record (carrying its trace_id) on THIS engine's
            # tracer, so a failover revival appears as its own track in
            # the stitched Perfetto view.  Counters stay untouched — the
            # request was submitted elsewhere; this engine carries it on.
            now = self._clock()
            live = [sl.req for sl in self._slots if sl is not None]
            live.extend(self._queue)
            for r in live:
                attrs = {"restored": True}
                if r.trace_id is not None:
                    attrs["trace_id"] = r.trace_id
                self.telemetry.request_event(r.rid, "submitted", t=now,
                                             **attrs)
        return applied

    def _restore_full(self, meta, state, reqs):
        self._step_seq = int(meta["step_seq"])
        pool = self.pool
        pool._free = [int(p) for p in meta["pool"]["free"]]
        pool._refs = {int(p): int(c) for p, c in meta["pool"]["refs"]}
        ids = np.asarray(state["kv_pages"], np.int32)
        if len(ids):
            self._scatter_pages(ids, state)
        for s, sd in enumerate(meta["slots"]):
            if sd is None:
                continue
            req = reqs[sd["rid"]]
            slot = _Slot(req, [int(p) for p in sd["pages"]],
                         int(sd["pending"]), admit_seq=int(sd["admit_seq"]))
            slot.prefill_pos = sd["prefill_pos"]
            slot.ctx = None if sd["ctx"] is None \
                else np.asarray(sd["ctx"], np.int32)
            slot.resuming = bool(sd["resuming"])
            slot.chunk_step = int(sd["chunk_step"])
            slot.spec_k = int(sd["spec_k"])
            if self.speculative and req.temperature <= 0.0:
                # the n-gram index is a pure function of the token stream —
                # rebuild instead of serializing (identical by construction:
                # admission + per-token appends == one pass over the stream)
                slot.draft = _NgramDraft(
                    np.concatenate([req.prompt,
                                    np.asarray(req.generated, np.int32)]),
                    max_n=self.spec_max_ngram)
            self._slots[s] = slot
            row = np.zeros((self.max_pages_per_seq,), np.int32)
            row[:len(slot.pages)] = slot.pages
            self._page_tables[s] = row
            self._lengths[s] = int(sd["length"])
            self._set_sampling(s, req)
        for rid in meta["queue"]:
            self._queue.append(reqs[rid])
        if self.cache is not None and meta.get("cache"):
            c = self.cache
            cm = meta["cache"]
            c._tick = int(cm["tick"])
            c.insertions = int(cm["insertions"])
            c.evictions = int(cm["evictions"])
            for key_hex, parent_hex, page, tick in cm["full"]:
                e = _CacheEntry(bytes.fromhex(key_hex),
                                bytes.fromhex(parent_hex), int(page))
                e.tick = int(tick)
                c._full[e.key] = e
            for parent_hex, toks, page, tick in cm["partial"]:
                parent = bytes.fromhex(parent_hex)
                tb = np.asarray(toks, np.int32).tobytes()
                e = _CacheEntry(None, parent, int(page), tokens=tb)
                e.tick = int(tick)
                c._partial.setdefault(parent, {})[tb] = e
            c._reindex()

    def _restore_reprefill(self, meta, reqs):
        """Compact-mode (or geometry-mismatch) restore: requeue every
        in-flight request through the preemption-resume machinery, slots
        first in admission order (they were running; they get slots back
        first), then the parked queue in its order.  The prefix cache
        starts empty — its pages' CONTENT did not ride a compact snapshot —
        and refills as re-prefills register blocks."""
        inflight = sorted((sd for sd in meta["slots"] if sd is not None),
                          key=lambda sd: sd["admit_seq"])
        for sd in inflight:
            self._queue.append(reqs[sd["rid"]])
        for rid in meta["queue"]:
            self._queue.append(reqs[rid])

    # -- accounting / invariants -------------------------------------------
    def stats(self) -> dict:
        """Engine observability: one dict of monotonically increasing
        counters (the benchmark's drivers and dashboards diff it).
        `decode_steps` and `verify_steps` are DISJOINT dispatch counts
        (plain horizon vs speculative verify); their sum is the total
        number of engine dispatches (`steps_run`)."""
        prop = self.draft_tokens_proposed
        acc = self.draft_tokens_accepted
        return {
            "tokens_generated": self.tokens_generated,
            "decode_steps": self.steps_run - self.verify_steps,
            "verify_steps": self.verify_steps,
            # steady-state dispatches whose tokens were consumed from the
            # dispatch itself (fused greedy argmax / in-horizon sampling)
            # vs `steps_run` total: the remainder returned logits for
            # host-side sampling (sampled verify ride-along lanes)
            "fused_sample_steps": self.fused_sample_steps,
            "draft_tokens_proposed": prop,
            "draft_tokens_accepted": acc,
            "draft_accept_rate": round(acc / prop, 4) if prop else 0.0,
            # un-cached prompt tokens of admitted requests (admission
            # time) / tokens and padded rows of the prefill calls made
            # (dispatch time) / KV positions the decode horizon read
            "prefill_tokens_executed": self.prefill_tokens,
            "prefill_calls": self.prefill_calls,
            "prefill_tokens_dispatched": self.prefill_tokens_dispatched,
            "prefill_tokens_padded": self.prefill_tokens_padded,
            # a dispatched token is a K/V row written; rows / pages is the
            # factor of scatter updates the page-run writer saves
            "prefill_kv_rows_written": self.prefill_tokens_dispatched,
            "prefill_kv_pages_written": self.prefill_kv_pages_written,
            "decode_kv_tokens_attended": self.decode_kv_tokens_attended,
            "decode_kv_pages_attended": self.decode_kv_pages_attended,
            "cached_prefix_tokens": self.cache_hit_tokens,
            "cache_hits": self.cache_hits,
            "cache_evictions": self.cache_evictions,
            # heap items eviction examined: over `cache_evictions` the cost
            # of picking a victim (1 where no item went stale)
            "cache_evict_visits": self.cache_evict_visits,
            "cow_copies": self.cow_copies,
            "preemptions": self.preemptions,
            "timeouts": self.timeouts,
            "rejections": self.rejections,
            # double-buffered host loop (overlap=True): dispatches that
            # went out while the previous step was still in flight, and
            # forced pipeline drains (exactness points)
            "overlap_steps": self.overlap_steps,
            "quiesces": self.quiesces,
            # disaggregated prefill/decode: export_kv/import_kv traffic
            # through this engine (pages = post-dedup shipped page count)
            "kv_exports": self.kv_exports,
            "kv_imports": self.kv_imports,
            "kv_pages_exported": self.kv_pages_exported,
            "kv_pages_imported": self.kv_pages_imported,
            # executables `step()` launched and host->device arrays it
            # made: in a steady window launches over the model calls
            # (`prefill_calls` + `decode_steps` + `verify_steps`) reads 1
            # — a COW copy and a sampled first token after a chunk are
            # launches of their own — and a call makes one upload, two
            # where an admission changed the horizon's sampling row (a
            # verify keeps four)
            "step_launches": self.step_launches,
            "step_uploads": self.step_uploads,
            # tensor-parallel serving: mesh degree over mp (1 = single
            # chip) and whether the per-layer AllReduce rides the EQuARX
            # int8 grid (distributed/quant_collectives)
            "tp_degree": self.tp,
            "quantized_allreduce": self.quantized_allreduce,
            # per-model-fn compile-cache misses (analysis.sanitize
            # instrumentation) — a warmed steady state must hold these
            # flat (tests/test_recompile_budget.py)
            "jit_cache_misses": dict(self.jit_cache_misses),
            # what the family's fns counted on the device, inside the
            # carried cache (nothing for a cache of K/V pages alone): one
            # small fetch, here and nowhere else
            **self._family_counters(),
        }

    def _family_counters(self) -> dict:
        if "ctr" in self._cache:       # counted on the device, in the cache
            self._join_dispatch()      # ... which must be concrete
        return self.family.counters(self._cache)

    def stats_snapshot(self):
        """Immutable flattened :class:`EngineStats` snapshot of `stats()`
        (nested dicts dotted).  Two snapshots diff exactly:
        ``later.delta(earlier)`` is the per-window activity — the
        registry-backed replacement for hand-diffing the stats() dict."""
        from ..observability.metrics import EngineStats
        return EngineStats.capture(self.stats(), clock=self._clock)

    def release_cache(self) -> int:
        """Drop every evictable cached page back to the free list (tests,
        shutdown, or a host that wants its HBM back); returns pages
        freed.  Pages attached to live requests are untouched."""
        if self.cache is None:
            return 0
        return self._evict_cache(self.pool.num_pages)

    def check_invariants(self):
        """Page-refcount accounting must exactly equal what the live page
        tables + prefix cache reference — called by the tests' leak guard
        after every test, and valid at ANY step boundary."""
        expect: dict[int, int] = {}
        for slot in self._slots:
            if slot is None:
                continue
            for p in slot.pages:
                expect[p] = expect.get(p, 0) + 1
        if self._inflight is not None:
            # budget-predicted retirements detached from the slot table
            # hold their pages through the lane record until drained
            for lane in self._inflight.lanes:
                if lane.retiring:
                    for p in lane.slot.pages:
                        expect[p] = expect.get(p, 0) + 1
        if self.cache is not None:
            self.cache.check_index()
            for p in self.cache.pages():
                expect[p] = expect.get(p, 0) + 1
        assert expect == self.pool._refs, (
            f"page refcount drift: tables+cache say {expect}, "
            f"pool says {self.pool._refs}")
        assert self.pool.num_free + self.pool.num_allocated \
            == self.pool.num_pages, "free + allocated != pool size"
        free = self.pool._free
        assert len(set(free)) == len(free), "duplicate page on the free list"
        assert not (set(free) & set(self.pool._refs)), \
            "page simultaneously free and referenced"


def serve_requests(params, config, prompts, **kw):
    """One-shot convenience: submit every (prompt, request-kwargs) pair and
    run to completion.  `prompts` is a list of token arrays or
    (token_array, {request kwargs}) tuples; engine kwargs ride **kw."""
    req_kw_keys = ("max_new_tokens", "temperature", "top_p", "eos_token_id",
                   "timeout")
    default_req = {k: kw.pop(k) for k in req_kw_keys if k in kw}
    eng = ServingEngine(params, config, **kw)
    rids = []
    for p in prompts:
        if isinstance(p, tuple):
            p, rkw = p
            merged = dict(default_req)
            merged.update(rkw)
        else:
            merged = dict(default_req)
        rids.append(eng.submit(p, **merged))
    done = eng.run()
    return [done[r] for r in rids], eng
