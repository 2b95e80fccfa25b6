"""Prefix-cache + chunked-prefill suite (ISSUE 3 tentpole).

Three layers:

  * `PagePool` refcounting — share/free semantics, the typed
    `PageDoubleFreeError` (double free, foreign page, duplicate ids in one
    batch — the pool must stay untouched when it raises), and the
    `num_referenced` invariants.
  * `PrefixCache` unit behavior — chained block-hash lookup, partial-tail
    matching, LRU leaf-first eviction that never strands a chain, and the
    eviction heap held to the full scan it replaced (random traffic, and
    an engine restored from a snapshot).
  * Engine PARITY — the acceptance bar: greedy outputs bit-exact with the
    prefix cache on vs off (and vs `llama_generate`) across staggered
    arrivals, GQA configs, page-boundary prefix lengths (exact multiple of
    page_size and ±1), preemption of a cache-hit request (whose re-prefill
    itself hits the cache), eviction under injected pool pressure, and
    chunked prefill.  Every scenario also passes the conftest refcount
    leak guard (`ServingEngine.check_invariants`).
"""
import numpy as np
import pytest
import jax

from paddle_tpu.models.llama import (LlamaConfig, llama_config_tiny,
                                     build_functional_llama, llama_generate)
from paddle_tpu.inference.paged import (PagePool, PrefixCache, ServingEngine,
                                        PageDoubleFreeError)
from paddle_tpu.resilience import inject

rng = np.random.default_rng(23)


# ---------------------------------------------------------------------------
# PagePool refcounting
# ---------------------------------------------------------------------------
class TestPagePoolRefcounts:
    def test_share_free_lifecycle(self):
        pool = PagePool(8, 16)
        a = pool.alloc(2)
        assert pool.num_allocated == 2 and pool.num_referenced == 2
        pool.share(a)                      # a second page table attaches
        assert pool.num_allocated == 2 and pool.num_referenced == 4
        assert all(pool.refcount(p) == 2 for p in a)
        pool.free(a)                       # first holder detaches
        assert pool.num_free == 6          # still referenced -> not free
        assert pool.num_allocated == 2 and pool.num_referenced == 2
        pool.free(a)                       # last holder detaches
        assert pool.num_free == 8 and pool.num_allocated == 0
        assert pool.num_referenced == 0

    def test_double_free_is_typed(self):
        pool = PagePool(4, 8)
        a = pool.alloc(1)
        pool.free(a)
        with pytest.raises(PageDoubleFreeError, match="not allocated"):
            pool.free(a)

    def test_share_unallocated_is_typed(self):
        pool = PagePool(4, 8)
        with pytest.raises(PageDoubleFreeError, match="not allocated"):
            pool.share([2])

    def test_duplicate_ids_in_one_free_batch_raise_untorn(self):
        """ISSUE satellite: duplicates inside ONE free() batch raise the
        typed error even while the refcount could absorb both decrements —
        and the pool must be byte-identical to before the call."""
        pool = PagePool(8, 16)
        a = pool.alloc(3)
        pool.share([a[0]])                 # refcount 2: two decrements WOULD fit
        before = (dict(pool._refs), list(pool._free))
        with pytest.raises(PageDoubleFreeError, match="more than once"):
            pool.free([a[0], a[1], a[0]])
        assert (dict(pool._refs), list(pool._free)) == before
        # foreign page mid-batch also leaves the pool untouched
        with pytest.raises(PageDoubleFreeError, match="not allocated"):
            pool.free([a[1], 7])
        assert (dict(pool._refs), list(pool._free)) == before

    def test_shared_page_survives_one_holder(self):
        pool = PagePool(4, 8)
        a = pool.alloc(1)
        pool.share(a)
        pool.free(a)
        b = pool.alloc(3)                  # the shared page is NOT recycled
        assert a[0] not in b
        assert pool.refcount(a[0]) == 1


# ---------------------------------------------------------------------------
# PrefixCache unit behavior
# ---------------------------------------------------------------------------
class TestPrefixCacheIndex:
    def _pool_cache(self, n=16, ps=4):
        pool = PagePool(n, ps)
        return pool, PrefixCache(pool, ps)

    def test_chained_lookup_longest_prefix(self):
        pool, cache = self._pool_cache()
        toks = np.arange(1, 13, dtype=np.int32)      # 3 full blocks of 4
        pages = pool.alloc(3)
        cache.register(toks, pages)
        # full match capped at len-1: asking for the exact sequence may
        # only attach 2 blocks (a suffix token must remain)
        full, partial = cache.lookup(toks)
        assert full == pages[:2] and partial is None
        # one extra token -> all 3 blocks match
        full, _ = cache.lookup(np.concatenate([toks, [99]]))
        assert full == pages
        # diverging block 2 -> only block 1 matches (chained hash, not
        # per-block content)
        div = toks.copy()
        div[5] = 77
        full, _ = cache.lookup(np.concatenate([div, [99]]))
        assert full == pages[:1]
        # a cached page holds one cache reference each
        assert all(pool.refcount(p) == 2 for p in pages)
        pool.free(pages)                   # original holder leaves
        assert all(pool.refcount(p) == 1 for p in pages)

    def test_partial_tail_match(self):
        pool, cache = self._pool_cache()
        toks = np.arange(1, 11, dtype=np.int32)      # 2 full blocks + 2 tail
        pages = pool.alloc(3)
        cache.register(toks, pages, with_partial=True)
        ext = np.concatenate([toks, [50, 51]])       # extends past the tail
        full, partial = cache.lookup(ext)
        assert full == pages[:2]
        assert partial == (pages[2], 2)
        # prefix of the tail also matches (first token only)
        semi = np.concatenate([toks[:9], [60, 61]])
        full, partial = cache.lookup(semi)
        assert full == pages[:2] and partial == (pages[2], 1)

    def test_eviction_is_lru_leaf_first_and_skips_referenced(self):
        pool, cache = self._pool_cache(n=8, ps=4)
        a = np.arange(1, 9, dtype=np.int32)          # chain of 2 blocks
        pa = pool.alloc(2)
        cache.register(a, pa)
        b = np.concatenate([a[:4], [90, 91, 92, 93]]).astype(np.int32)
        pb = pool.alloc(2)
        cache.register(b, pb)                        # shares chain root
        pool.free(pa)
        pool.free(pb)                                # cache-only now
        # root has two children -> only the two leaves are evictable;
        # the LRU leaf is a's block 2 (registered first)
        assert cache.evict(1) == 1
        full, _ = cache.lookup(np.concatenate([a, [99]]))
        assert full == [pa[0]]                       # a's leaf gone, root kept
        full, _ = cache.lookup(np.concatenate([b, [99]]))
        assert full == [pa[0], pb[1]]                # b's chain intact
        # evicting everything walks chains back-to-front
        assert cache.evict(10) == 2
        assert len(cache) == 0 and pool.num_free == 8

    def test_lookup_prompt_shorter_than_one_block(self):
        """ISSUE 4 satellite: a prompt shorter than page_size can never
        match a full block (the match cap at len-1 leaves < page_size
        tokens), but CAN match a cached partial tail."""
        pool, cache = self._pool_cache(ps=4)
        toks = np.array([5, 6, 7], np.int32)         # < one block
        pages = pool.alloc(1)
        cache.register(toks, pages, with_partial=True)
        # shorter-than-block lookups: no full blocks, partial tail only
        full, partial = cache.lookup(np.array([5, 6], np.int32))
        assert full == [] and partial == (pages[0], 1)
        full, partial = cache.lookup(np.array([5, 6, 7, 8], np.int32))
        assert full == [] and partial == (pages[0], 3)
        # a 1-token prompt has a 0-token matchable prefix: nothing matches
        full, partial = cache.lookup(np.array([5], np.int32))
        assert full == [] and partial is None
        # divergent first token: no match at all
        full, partial = cache.lookup(np.array([9, 6], np.int32))
        assert full == [] and partial is None

    def test_lookup_prompt_exactly_one_block(self):
        """ISSUE 4 satellite: a prompt of exactly page_size tokens still
        only matches page_size-1 of them (one suffix token must remain to
        prefill); one token MORE matches the full block."""
        pool, cache = self._pool_cache(ps=4)
        toks = np.arange(1, 5, dtype=np.int32)       # exactly one block
        pages = pool.alloc(1)
        cache.register(toks, pages, with_partial=True)
        # register indexed the full block (no partial: the tail is empty)
        full, partial = cache.lookup(toks)
        assert full == []                            # cap at len-1 = 3
        assert partial is None                       # no partial entries
        full, partial = cache.lookup(np.arange(1, 6, dtype=np.int32))
        assert full == pages and partial is None     # one extra -> full hit

    def test_referenced_entries_never_evict(self):
        pool, cache = self._pool_cache()
        toks = np.arange(1, 9, dtype=np.int32)
        pages = pool.alloc(2)
        cache.register(toks, pages)                  # rc 2: holder + cache
        assert cache.evict(5) == 0                   # nothing evictable
        pool.free(pages)
        assert cache.evict(5) == 2


class _ScanCache(PrefixCache):
    """The oracle: eviction as a full pass over every entry for each page,
    taking the lowest tick among cache-only leaves."""

    def evict(self, n_pages):
        freed = 0
        while freed < n_pages:
            cand = None
            for e in self._scan():
                if cand is None or e.tick < cand.tick:
                    cand = e
            if cand is None:
                break
            self._drop(cand)
            self.pool.free([cand.page])
            freed += 1
        self.evictions += freed
        return freed

    def _scan(self):
        for d in self._partial.values():
            for e in d.values():
                if self.pool.refcount(e.page) == 1:
                    yield e
        for e in self._full.values():
            if e.children == 0 and self.pool.refcount(e.page) == 1:
                yield e


def _record_victims(cache):
    """Every page `cache` evicts, in order, and its notify calls."""
    victims, notes = [], []
    drop = cache._drop
    cache._drop = lambda e: (victims.append(e.page), drop(e))
    cache.notify = lambda kind, digests: notes.append((kind, list(digests)))
    return victims, notes


class _Side:
    """A pool, a cache over it and the page tables of live sequences."""

    def __init__(self, cache_cls, n_pages, ps):
        self.pool = PagePool(n_pages, ps)
        self.cache = cache_cls(self.pool, ps)
        self.victims, self.notes = _record_victims(self.cache)
        self.seqs = {}                     # sid -> [tokens, pages]

    def state(self):
        return (self.victims, self.notes, list(self.pool._free),
                dict(self.pool._refs), len(self.cache),
                self.cache.evictions)

    def check(self):
        expect = {}
        for _, pages in self.seqs.values():
            for p in pages:
                expect[p] = expect.get(p, 0) + 1
        for p in self.cache.pages():
            expect[p] = expect.get(p, 0) + 1
        assert expect == self.pool._refs

    def make_room(self, n):
        """Evict what n more pages lack; False if the pool still lacks."""
        if n > self.pool.num_free:
            self.cache.evict(n - self.pool.num_free)
        return n <= self.pool.num_free

    def admit(self, sid, tokens):
        ps = self.pool.page_size
        shared, partial = self.cache.lookup(tokens)
        pin = list(shared) + ([partial[0]] if partial else [])
        self.pool.share(pin)
        need = -(-len(tokens) // ps) - len(shared)
        if not self.make_room(need):
            self.pool.free(pin)
            return
        own = self.pool.alloc(need)
        if partial:                        # copied into its own page
            self.pool.free([partial[0]])
        self.seqs[sid] = [tokens, list(shared) + own]

    def grow(self, sid, more):
        tokens, pages = self.seqs[sid]
        tokens = np.concatenate([tokens, more]).astype(np.int32)
        grow = -(-len(tokens) // self.pool.page_size) - len(pages)
        if grow > 0 and not self.make_room(grow):
            return
        if grow > 0:
            pages += self.pool.alloc(grow)
        self.seqs[sid][0] = tokens

    def copy_on_write(self, sid, idx):
        pages = self.seqs[sid][1]
        if self.pool.refcount(pages[idx]) > 1 and self.make_room(1):
            new = self.pool.alloc(1)[0]
            self.pool.free([pages[idx]])
            pages[idx] = new

    def retire(self, sid, with_partial):
        tokens, pages = self.seqs.pop(sid)
        self.cache.register(tokens, pages, with_partial=with_partial)
        self.pool.free(pages)


def _random_traffic(seed, steps=600, n_pages=40, ps=4):
    """One op sequence from `seed`, applied to the heap cache and to the
    scan oracle in lockstep; both sides compared after every op."""
    r = np.random.default_rng(seed)
    docs = [r.integers(1, 9, int(r.integers(0, 15))).astype(np.int32)
            for _ in range(4)]
    sides = [_Side(PrefixCache, n_pages, ps), _Side(_ScanCache, n_pages, ps)]
    next_sid = 0
    for _ in range(steps):
        op = r.choice(["admit", "admit", "grow", "register", "retire",
                       "retire", "cow", "lookup", "evict", "release"],
                      p=[.18, .05, .17, .08, .17, .08, .07, .1, .09, .01])
        live = sorted(sides[0].seqs)
        sid = live[int(r.integers(len(live)))] if live else None
        if op == "admit":
            doc = docs[int(r.integers(len(docs)))]
            toks = np.concatenate([doc, r.integers(1, 9, int(r.integers(
                1, 8)))]).astype(np.int32)
            for s in sides:
                s.admit(next_sid, toks)
            next_sid += 1
        elif op == "grow" and sid is not None:
            more = r.integers(1, 9, int(r.integers(1, 6)))
            for s in sides:
                s.grow(sid, more)
        elif op == "register" and sid is not None:
            for s in sides:               # a finished prefill: full blocks
                s.cache.register(*s.seqs[sid])
        elif op == "retire" and sid is not None:
            with_partial = bool(r.integers(2))
            for s in sides:
                s.retire(sid, with_partial)
        elif op == "cow" and sid is not None:
            idx = int(r.integers(len(sides[0].seqs[sid][1])))
            for s in sides:
                s.copy_on_write(sid, idx)
        elif op == "lookup":
            doc = docs[int(r.integers(len(docs)))]
            for s in sides:
                s.cache.lookup(np.concatenate([doc, [1, 2]]))
        elif op == "evict":
            k = int(r.integers(1, 6))
            assert len({s.cache.evict(k) for s in sides}) == 1
        elif op == "release":
            assert len({s.cache.evict(n_pages) for s in sides}) == 1
        assert sides[0].state() == sides[1].state()
        for s in sides:
            s.check()
        sides[0].cache.check_index()
    for sid in sorted(sides[0].seqs):
        for s in sides:
            s.retire(sid, True)
    assert len({s.cache.evict(n_pages) for s in sides}) == 1
    assert sides[0].state() == sides[1].state()
    assert sides[0].pool.num_free == n_pages
    return sides[0]


class TestEvictionHeapMatchesTheScan:
    @pytest.mark.parametrize("seed", [0, 1, 2, 3, 4, 5])
    def test_random_traffic_evicts_as_the_full_scan(self, seed):
        """Register (with and without tails, over shared chains), lookup,
        attach, free, copy-on-write, evict(k) and a full release: the same
        victims in the same order, the same notify calls, the same free
        list and refcounts as the full scan, after every operation."""
        side = _random_traffic(seed)
        full = sum(len(d) for kind, d in side.notes if kind == "evict")
        # full blocks and partial tails (which notify nothing) among them
        assert len(side.victims) >= 40
        assert 0 < full < len(side.victims)

    def test_a_victim_costs_a_few_heap_items(self):
        side = _random_traffic(7)
        c = side.cache
        assert c.evict_visits <= 4 * c.evictions

    def test_index_follows_refcount_and_children(self):
        """An entry enters the heap when its page drops to the cache's one
        reference and when its last child goes; a new holder or a new
        touch leaves a stale item that eviction skips."""
        pool = PagePool(8, 4)
        cache = PrefixCache(pool, 4)
        a = np.arange(1, 9, dtype=np.int32)
        pa = pool.alloc(2)
        cache.register(a, pa)
        assert cache._heap == []                     # held by the sequence
        pool.free(pa)                                # cache-only now
        cache.check_index()
        assert [e.page for _, e in cache._heap] == [pa[1]]   # the leaf
        pool.share([pa[1]])                          # a holder attaches
        assert cache.evict(1) == 0 and cache.evict_visits == 1
        pool.free([pa[1]])
        cache.lookup(np.concatenate([a, [9]]))       # touches both
        cache.check_index()
        assert cache.evict(2) == 2
        assert pool.num_free == 8 and len(cache) == 0


# ---------------------------------------------------------------------------
# Engine parity: greedy outputs bit-exact, cache on vs off
# ---------------------------------------------------------------------------
def _params(cfg, seed=0):
    ep, bp, hp, *_ = build_functional_llama(cfg, key=jax.random.PRNGKey(seed))
    return ep, bp, hp


def _mk(cfg, params, **kw):
    base = dict(num_slots=2, page_size=8, num_pages=48, max_pages_per_seq=10,
                attention_impl="ref", prompt_bucket=8, decode_horizon=3)
    base.update(kw)
    return ServingEngine(params, cfg, **base)


def _run_both(cfg, params, prompts, max_new=6, stagger_after=None, **kw):
    """Run the SAME prompt list through a cache-on and a cache-off engine;
    assert greedy outputs are bit-exact between them AND vs llama_generate;
    return the cache-on engine for counter assertions."""
    outs = {}
    engines = {}
    for cache_on in (True, False):
        ekw = dict(kw)
        if not cache_on:
            ekw.update(prefix_cache=False, prefill_chunk=None)
        eng = _mk(cfg, params, **ekw)
        rids = [eng.submit(p, max_new_tokens=max_new)
                for p in (prompts if stagger_after is None
                          else prompts[:stagger_after])]
        if stagger_after is not None:
            eng.step()                     # first wave mid-flight
            rids += [eng.submit(p, max_new_tokens=max_new)
                     for p in prompts[stagger_after:]]
        done = eng.run()
        outs[cache_on] = [done[r].output_ids for r in rids]
        engines[cache_on] = eng
    for got_on, got_off, p in zip(outs[True], outs[False], prompts):
        np.testing.assert_array_equal(got_on, got_off)
        ref = np.asarray(llama_generate(params, cfg, p[None],
                                        max_new_tokens=max_new))[0]
        np.testing.assert_array_equal(got_on, ref)
    for eng in engines.values():
        eng.check_invariants()
    return engines[True]


class TestPrefixCacheParity:
    def test_shared_prefix_staggered_arrivals(self):
        """Shared 16-token system prompt, 5 requests, second wave submitted
        mid-run: every greedy output bit-exact, and the later arrivals hit
        the earlier arrivals' cached blocks."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=96)
        params = _params(cfg, seed=1)
        system = rng.integers(1, 64, (16,)).astype(np.int32)
        prompts = [np.concatenate([system,
                                   rng.integers(1, 64, (t,)).astype(np.int32)])
                   for t in (5, 9, 3, 12, 7)]
        eng = _run_both(cfg, params, prompts, stagger_after=2)
        assert eng.cache_hits >= 3         # every later arrival attached
        assert eng.cache_hit_tokens >= 3 * 16
        assert eng.prefill_tokens < sum(len(p) for p in prompts)

    def test_gqa_config_parity(self):
        cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=96,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, max_position_embeddings=96)
        params = _params(cfg, seed=2)
        system = rng.integers(1, 64, (12,)).astype(np.int32)
        prompts = [np.concatenate([system,
                                   rng.integers(1, 64, (t,)).astype(np.int32)])
                   for t in (4, 11, 6)]
        eng = _run_both(cfg, params, prompts, page_size=4)
        assert eng.cache_hits >= 2

    @pytest.mark.slow   # 3-length sweep x 2 engines: heavy compiles
    def test_page_boundary_prefix_lengths(self):
        """Shared prefixes landing at an exact page multiple and ±1: the
        boundary decides between pure full-block attach and a partial-tail
        attach that must copy-on-write.  Second-wave prompts share exactly
        `pre_len` tokens with the first (mid-block divergence only ever
        matches whole blocks — the chained hash sees the block, not the
        byte)."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=96)
        params = _params(cfg, seed=3)
        ps = 8
        for pre_len in (2 * ps - 1, 2 * ps, 2 * ps + 1):
            base = rng.integers(1, 64, (pre_len,)).astype(np.int32)
            tail_a = rng.integers(1, 64, (5,)).astype(np.int32)
            tail_b = rng.integers(1, 64, (6,)).astype(np.int32)
            prompts = [np.concatenate([base, tail_a]),
                       np.concatenate([base, tail_b])]
            eng = _run_both(cfg, params, prompts, page_size=ps)
            assert eng.cache_hit_tokens >= (pre_len // ps) * ps

    # tier-1 keeps ONE boundary case — the copy-on-write trigger (25 = 3
    # pages + 1); the page-exact and page-minus-one cases ride the slow
    # lane (heavy-compile sweep, ROADMAP 870 s tier-1 budget)
    @pytest.mark.parametrize("t1_len", [
        pytest.param(23, marks=pytest.mark.slow),
        pytest.param(24, marks=pytest.mark.slow),
        25,
    ])
    def test_multi_turn_partial_tail_cow(self, t1_len):
        """Multi-turn follow-up: turn 2's prompt embeds turn 1's full
        conversation, so it attaches turn 1's retired full blocks AND its
        partially filled tail page — which must be copied before the
        suffix prefill writes into it (copy-on-write).  `t1_len` places
        the retired turn-1 content at a page boundary and ±1."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=128)
        params = _params(cfg, seed=30)
        ps = 8
        # retired turn-1 content = prompt + max_new - 1 tokens: place IT
        # at the boundary case
        p1 = rng.integers(1, 64, (t1_len - 5,)).astype(np.int32)
        ref1 = np.asarray(llama_generate(params, cfg, p1[None],
                                         max_new_tokens=6))[0]
        p2 = np.concatenate([ref1,
                             rng.integers(1, 64, (7,)).astype(np.int32)])
        outs = {}
        for cache_on in (True, False):
            kw = {} if cache_on else dict(prefix_cache=False)
            eng = _mk(cfg, params, page_size=ps, num_pages=64,
                      max_pages_per_seq=12, **kw)
            r1 = eng.submit(p1, max_new_tokens=6)
            eng.run()
            r2 = eng.submit(p2, max_new_tokens=6)
            outs[cache_on] = eng.run()[r2].output_ids
            if cache_on:
                # all t1_len turn-1 tokens were written to its pages
                assert eng.cache_hit_tokens >= t1_len
                if t1_len % ps:
                    assert eng.cow_copies >= 1
            eng.check_invariants()
        np.testing.assert_array_equal(outs[True], outs[False])
        ref2 = np.asarray(llama_generate(params, cfg, p2[None],
                                         max_new_tokens=6))[0]
        np.testing.assert_array_equal(outs[True], ref2)

    def test_exact_full_prompt_reuse(self):
        """Identical prompt twice: the repeat may attach everything except
        one suffix token (whose logits seed the first sample)."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=96)
        params = _params(cfg, seed=4)
        p = rng.integers(1, 64, (24,)).astype(np.int32)
        eng = _run_both(cfg, params, [p, p.copy()])
        assert eng.cache_hit_tokens >= 16  # 2 full pages + partial tail

    def test_preemption_of_cache_hit_request(self):
        """Tight pool forces preemption while the cache is live: the victim
        re-prefills THROUGH the cache (its own parked blocks) and greedy
        outputs stay step-exact vs llama_generate and the cache-off
        engine (which preempts too, re-prefilling from token zero)."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=96)
        params = _params(cfg, seed=5)
        # the PR 2 deadlock geometry: two 8-token prompts each eventually
        # needing 4 pages, pool of 5 -> both slots stall mid-generation
        # with nothing retirable, forcing a preemption
        prompts = [rng.integers(1, 64, (8,)).astype(np.int32)
                   for _ in range(2)]
        eng = _run_both(cfg, params, prompts, max_new=8, page_size=4,
                        num_pages=5, max_pages_per_seq=4, decode_horizon=1)
        assert eng.preemptions >= 1
        # the resumed victim's re-prefill itself hit the cache (its own
        # blocks, parked there by the preemption)
        assert eng.cache_hits >= 1
        assert eng.cache_hit_tokens >= 4

    def test_eviction_under_injected_pool_pressure(self):
        """`serve.pool_pressure` windows + a pool small enough that cached
        pages must be reclaimed: the ladder goes evict-cache -> preempt,
        every request completes bit-exact, and no page leaks."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=96)
        params = _params(cfg, seed=6)
        system = rng.integers(1, 64, (8,)).astype(np.int32)
        prompts = [np.concatenate([system,
                                   rng.integers(1, 64, (t,)).astype(np.int32)])
                   for t in (3, 6, 4)]
        refs = [np.asarray(llama_generate(params, cfg, p[None],
                                          max_new_tokens=6))[0]
                for p in prompts]
        for seed in range(3):
            eng = _mk(cfg, params, page_size=4, num_pages=8,
                      max_pages_per_seq=6, decode_horizon=2)
            with inject({"serve.pool_pressure": dict(
                    action="trigger", prob=0.35, count=4)}, seed=seed):
                rids = [eng.submit(p, max_new_tokens=6) for p in prompts]
                done = eng.run()
            for rid, ref in zip(rids, refs):
                np.testing.assert_array_equal(done[rid].output_ids, ref)
            # the tight pool forced cached pages back out at least once
            assert eng.cache_evictions >= 1
            eng.check_invariants()
            eng.release_cache()
            assert eng.pool.num_free == eng.pool.num_pages

    def test_chunked_prefill_parity_and_ttft_interleave(self):
        """A long prompt with prefill_chunk set prefills across several
        engine steps while a short queued request decodes; outputs stay
        bit-exact and the short request finishes BEFORE the long one's
        prefill would have allowed under whole-prompt admission."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=128)
        params = _params(cfg, seed=7)
        p_long = rng.integers(1, 64, (56,)).astype(np.int32)
        p_short = rng.integers(1, 64, (4,)).astype(np.int32)
        eng = _run_both(cfg, params, [p_long, p_short], max_new=5,
                        num_pages=64, max_pages_per_seq=12, prefill_chunk=8)
        # 56 tokens / 8-token chunks -> several interleaved steps
        assert eng.steps_run >= 3

    def test_restored_engine_evicts_as_the_original(self):
        """A full-KV snapshot taken mid-run and restored into a fresh
        engine: the restore rebuilds the eviction heap, so under the same
        further traffic the two engines evict the same pages in the same
        order (and emit the same tokens)."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=96)
        params = _params(cfg, seed=10)
        r = np.random.default_rng(43)
        system = r.integers(1, 64, (6,)).astype(np.int32)
        first, later = ([np.concatenate([system, r.integers(
            1, 64, (int(n),))]).astype(np.int32) for n in r.integers(
                3, 7, k)] for k in (4, 6))
        geo = dict(page_size=4, num_pages=14, max_pages_per_seq=5)
        eng = _mk(cfg, params, **geo)
        for p in first:
            eng.submit(p, max_new_tokens=6)
        for _ in range(3):
            eng.step()
        assert eng.num_active == 2 and len(eng.cache)
        fresh = _mk(cfg, params, **geo)
        assert fresh.restore(eng.snapshot("full_kv")) == "full_kv"
        runs = []
        for e in (eng, fresh):
            victims, notes = _record_victims(e.cache)
            rids = [e.submit(p, max_new_tokens=6) for p in later]
            done = e.run()
            e.check_invariants()
            runs.append((victims, notes, list(e.pool._free),
                         [done[i].output_ids.tolist() for i in rids],
                         e.stats()["cache_evict_visits"]))
        assert runs[0][0], "the traffic evicted nothing after the snapshot"
        assert runs[0] == runs[1]
        assert eng.cache_evict_visits <= 4 * eng.cache_evictions

    def test_cache_off_engine_has_no_cache_state(self):
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=96)
        params = _params(cfg, seed=8)
        eng = _mk(cfg, params, prefix_cache=False)
        p = rng.integers(1, 64, (10,)).astype(np.int32)
        rid = eng.submit(p, max_new_tokens=4)
        eng.run()
        assert eng.cache is None and eng.cache_hits == 0
        assert eng.release_cache() == 0
        assert eng.pool.num_free == eng.pool.num_pages

    def test_sampled_mode_still_reproducible_with_cache(self):
        """Sampling parity across seeds is not part of the bit-exact bar,
        but a seeded engine must stay self-reproducible with the cache on
        (same seed -> same stream, hits and all)."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=96)
        params = _params(cfg, seed=9)
        sysm = rng.integers(1, 64, (16,)).astype(np.int32)
        p1 = np.concatenate([sysm, rng.integers(1, 64, (5,)).astype(np.int32)])
        p2 = np.concatenate([sysm, rng.integers(1, 64, (7,)).astype(np.int32)])

        def go(seed):
            eng = _mk(cfg, params, seed=seed)
            rids = [eng.submit(p, max_new_tokens=6, temperature=1.0,
                               top_p=0.9) for p in (p1, p2)]
            done = eng.run()
            eng.check_invariants()
            return [done[r].output_ids for r in rids]

        a, b = go(3), go(3)
        for x, y in zip(a, b):
            np.testing.assert_array_equal(x, y)
