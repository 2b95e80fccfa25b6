"""Paged-KV serving stack tests (ISSUE r6 tentpole): ragged paged-attention
kernel parity vs dense decode attention, page-pool invariants, and
end-to-end continuous batching matching `llama_generate`'s per-request
greedy outputs under staggered arrivals."""
import math
import re

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models.llama import (LlamaConfig, llama_config_tiny,
                                     build_functional_llama,
                                     build_llama_paged_decode,
                                     llama_generate)
from paddle_tpu.inference.paged import PagePool, ServingEngine
from paddle_tpu.ops.pallas.paged_attention import (
    ragged_paged_attention_decode, paged_attention_decode_ref,
    paged_gather_kv)

rng = np.random.default_rng(11)


def _dense_decode_attention(q, k_pages, v_pages, page_table, lengths):
    """Independent dense reference: gather each slot's pages, up-repeat KV
    heads, masked softmax over the valid prefix — the same math the dense
    decode path (`build_llama_decode._block_step`) runs per step."""
    k = np.asarray(paged_gather_kv(k_pages, page_table), np.float32)
    v = np.asarray(paged_gather_kv(v_pages, page_table), np.float32)
    qn = np.asarray(q, np.float32)
    S, Hq, D = qn.shape
    Hkv = k.shape[2]
    rep = Hq // Hkv
    out = np.zeros_like(qn)
    for s in range(S):
        L = int(lengths[s])
        if L == 0:
            continue
        for h in range(Hq):
            kv_h = h // rep
            sc = k[s, :L, kv_h] @ qn[s, h] / math.sqrt(D)
            p = np.exp(sc - sc.max())
            p /= p.sum()
            out[s, h] = p @ v[s, :L, kv_h]
    return out


def _rand_pages(Hkv, NP, ps, D, dtype=np.float32):
    k = rng.standard_normal((Hkv, NP, ps, D)).astype(dtype)
    v = rng.standard_normal((Hkv, NP, ps, D)).astype(dtype)
    return jnp.asarray(k), jnp.asarray(v)


class TestRaggedPagedAttentionKernel:
    @pytest.mark.parametrize("hq,hkv", [(4, 4), (8, 2), (8, 1)])
    def test_parity_vs_dense_ragged_lengths(self, hq, hkv):
        S, D, ps, NP, P = 5, 64, 16, 23, 4
        q = jnp.asarray(rng.standard_normal((S, hq, D)).astype(np.float32))
        kp, vp = _rand_pages(hkv, NP, ps, D)
        pt = jnp.asarray(
            rng.permutation(NP - 1)[: S * P].reshape(S, P).astype(np.int32))
        # ragged mix: empty slot, sub-page, exact page boundary, multi-page,
        # full table
        lens = jnp.asarray(np.array([0, 7, ps, ps + 3, P * ps], np.int32))
        out = ragged_paged_attention_decode(q, kp, vp, pt, lens,
                                            interpret=True)
        ref = _dense_decode_attention(q, kp, vp, pt, lens)
        np.testing.assert_allclose(np.asarray(out), ref, rtol=2e-5, atol=2e-5)
        # the jnp fallback implements the same semantics
        fb = paged_attention_decode_ref(q, kp, vp, pt, lens)
        np.testing.assert_allclose(np.asarray(fb), ref, rtol=2e-5, atol=2e-5)

    def test_parity_bf16(self):
        """Acceptance bound: bf16 inputs, f32 accumulation, rtol/atol <=
        2e-4 vs the dense reference computed from the same bf16 values in
        f32 (out_dtype=f32 reads the un-downcast accumulator)."""
        S, Hq, Hkv, D, ps, NP, P = 4, 8, 2, 64, 32, 17, 3
        q = jnp.asarray(rng.standard_normal((S, Hq, D)), jnp.bfloat16)
        kp, vp = _rand_pages(Hkv, NP, ps, D)
        kp, vp = kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16)
        pt = jnp.asarray(
            rng.permutation(NP - 1)[: S * P].reshape(S, P).astype(np.int32))
        lens = jnp.asarray(np.array([1, ps - 1, ps * 2, ps * 3], np.int32))
        out = np.asarray(ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True, out_dtype=jnp.float32))
        ref = _dense_decode_attention(q.astype(jnp.float32),
                                      kp.astype(jnp.float32),
                                      vp.astype(jnp.float32), pt, lens)
        np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)
        # the bf16-output form only adds the final downcast
        out16 = np.asarray(ragged_paged_attention_decode(
            q, kp, vp, pt, lens, interpret=True), np.float32)
        np.testing.assert_allclose(out16, ref, rtol=2e-2, atol=4e-3)

    def test_page_indirection_is_real(self):
        """Shuffled vs identity page tables over identical logical content
        must agree — the kernel must read through the table, not assume
        contiguity."""
        S, Hq, Hkv, D, ps, NP, P = 2, 2, 2, 32, 8, 9, 3
        kp, vp = _rand_pages(Hkv, NP, ps, D)
        q = jnp.asarray(rng.standard_normal((S, Hq, D)).astype(np.float32))
        perm = rng.permutation(NP - 1)[: S * P].reshape(S, P).astype(np.int32)
        ident = np.arange(S * P, dtype=np.int32).reshape(S, P)
        # build shuffled pools holding the same logical tokens
        kp2 = np.asarray(kp).copy()
        vp2 = np.asarray(vp).copy()
        for s in range(S):
            for i in range(P):
                kp2[:, perm[s, i]] = np.asarray(kp)[:, ident[s, i]]
                vp2[:, perm[s, i]] = np.asarray(vp)[:, ident[s, i]]
        lens = jnp.asarray(np.array([ps * 2 + 3, ps * 3], np.int32))
        a = ragged_paged_attention_decode(q, kp, vp, jnp.asarray(ident), lens,
                                          interpret=True)
        b = ragged_paged_attention_decode(q, jnp.asarray(kp2), jnp.asarray(vp2),
                                          jnp.asarray(perm), lens,
                                          interpret=True)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=1e-6, atol=1e-6)

    def test_zero_length_slot_outputs_zeros(self):
        S, Hq, Hkv, D, ps, NP, P = 3, 4, 2, 32, 8, 5, 2
        q = jnp.asarray(rng.standard_normal((S, Hq, D)).astype(np.float32))
        kp, vp = _rand_pages(Hkv, NP, ps, D)
        pt = jnp.zeros((S, P), jnp.int32)
        lens = jnp.asarray(np.array([0, 3, 0], np.int32))
        out = np.asarray(ragged_paged_attention_decode(q, kp, vp, pt, lens,
                                                       interpret=True))
        assert np.all(out[0] == 0.0) and np.all(out[2] == 0.0)
        assert np.isfinite(out).all() and np.abs(out[1]).sum() > 0


class TestPagePool:
    def test_alloc_free_roundtrip(self):
        pool = PagePool(8, 16)
        a = pool.alloc(3)
        b = pool.alloc(2)
        assert len(set(a) | set(b)) == 5          # all distinct
        assert pool.num_free == 3 and pool.num_allocated == 5
        pool.free(a)
        assert pool.num_free == 6
        c = pool.alloc(6)
        assert pool.num_free == 0
        assert set(c) | set(b) == set(range(8))   # full reuse, no leak

    def test_double_free_and_foreign_free_raise(self):
        pool = PagePool(4, 8)
        a = pool.alloc(2)
        pool.free(a)
        with pytest.raises(RuntimeError, match="not allocated"):
            pool.free(a)
        with pytest.raises(RuntimeError, match="not allocated"):
            pool.free([3 if 3 not in pool._allocated else 0])

    def test_exhaustion_raises(self):
        pool = PagePool(2, 8)
        pool.alloc(2)
        with pytest.raises(RuntimeError, match="exhausted"):
            pool.alloc(1)

    def test_fragmentation_interleave(self):
        """Interleaved alloc/free across 'requests' keeps the partition
        invariant: allocated + free == all pages, no duplicates ever."""
        pool = PagePool(16, 8)
        held = []
        r = np.random.default_rng(0)
        for _ in range(200):
            want = int(r.integers(1, 4))
            if held and (pool.num_free < want or r.random() < 0.4):
                pool.free(held.pop(r.integers(len(held))))
            else:
                held.append(pool.alloc(want))
            flat = [p for h in held for p in h]
            assert len(flat) == len(set(flat)) == pool.num_allocated
            assert pool.num_free + pool.num_allocated == 16


def _params(cfg, seed=0):
    ep, bp, hp, *_ = build_functional_llama(cfg, key=jax.random.PRNGKey(seed))
    return ep, bp, hp


class TestPagedDecodePath:
    def test_paged_prefill_decode_matches_dense_path(self):
        """build_llama_paged_decode (prefill + N paged decode steps) agrees
        with build_llama_decode's dense-cache logits, spanning pages."""
        from paddle_tpu.models.llama import build_llama_decode
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=32)
        params = _params(cfg)
        ps, NP = 4, 16
        fam = build_llama_paged_decode(
            cfg, page_size=ps, num_pages=NP, attention_impl="ref")
        _, dense_prefill, dense_step = build_llama_decode(cfg, max_seq=32)
        ids = rng.integers(1, 64, (1, 6)).astype(np.int32)

        row = np.zeros((8,), np.int32)
        row[:4] = [3, 7, 1, 5]                     # non-contiguous pages
        logits, cache = jax.jit(fam.prefill)(
            params, jnp.asarray(ids), jnp.asarray(6, jnp.int32),
            jnp.asarray(row), jnp.int32(0), fam.init_cache())
        dl, dcache = dense_prefill(params, jnp.asarray(ids))
        np.testing.assert_allclose(np.asarray(logits), np.asarray(dl[0]),
                                   rtol=2e-4, atol=2e-4)
        # 5 greedy decode steps crossing the page-size-4 boundary at pos 8
        tables = jnp.asarray(np.tile(row, (1, 1)))
        toks = jnp.argmax(logits)[None].astype(jnp.int32)
        lengths = jnp.asarray([6], jnp.int32)
        dtok = jnp.argmax(dl[0])[None].astype(jnp.int32)
        step_j = jax.jit(fam.decode_step)
        for _ in range(5):
            logits, cache = step_j(params, toks, lengths, tables, cache,
                                   jnp.ones((1,), bool))
            dl, dcache = dense_step(params, dtok, dcache)
            np.testing.assert_allclose(np.asarray(logits[0]),
                                       np.asarray(dl[0]),
                                       rtol=2e-4, atol=2e-4)
            toks = jnp.argmax(logits, -1).astype(jnp.int32)
            dtok = jnp.argmax(dl, -1).astype(jnp.int32)
            assert int(toks[0]) == int(dtok[0])
            lengths = lengths + 1


class TestServingEngine:
    def _mk(self, cfg, params, **kw):
        base = dict(num_slots=2, page_size=8, num_pages=24,
                    max_pages_per_seq=8, attention_impl="ref",
                    prompt_bucket=8, decode_horizon=3)
        base.update(kw)
        return ServingEngine(params, cfg, **base)

    def test_continuous_batching_staggered_greedy_parity(self):
        """More requests than slots, submitted in two waves mid-run: every
        request's greedy output must equal llama_generate's."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
        params = _params(cfg, seed=1)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (5, 11, 3, 8)]
        eng = self._mk(cfg, params)
        rids = [eng.submit(p, max_new_tokens=6) for p in prompts[:2]]
        eng.step()                                 # first wave in flight
        rids += [eng.submit(p, max_new_tokens=6) for p in prompts[2:]]
        done = eng.run()
        for rid, p in zip(rids, prompts):
            ref = np.asarray(llama_generate(params, cfg, p[None],
                                            max_new_tokens=6))[0]
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        # every page returned
        # retired pages park in the prefix cache; releasing it must
        # return EVERY page (any leak fails here)
        eng.release_cache()
        assert eng.pool.num_free == eng.pool.num_pages

    def test_gqa_engine_parity(self):
        cfg = LlamaConfig(vocab_size=64, hidden_size=32, intermediate_size=96,
                          num_hidden_layers=2, num_attention_heads=4,
                          num_key_value_heads=2, max_position_embeddings=64)
        params = _params(cfg, seed=2)
        p = rng.integers(1, 64, (7,)).astype(np.int32)
        eng = self._mk(cfg, params, page_size=4)
        rid = eng.submit(p, max_new_tokens=8)
        got = eng.run()[rid].output_ids
        ref = np.asarray(llama_generate(params, cfg, p[None],
                                        max_new_tokens=8))[0]
        np.testing.assert_array_equal(got, ref)

    def test_eos_retirement_frees_pages_and_truncates(self):
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
        params = _params(cfg, seed=3)
        p = rng.integers(1, 64, (5,)).astype(np.int32)
        full = np.asarray(llama_generate(params, cfg, p[None],
                                         max_new_tokens=8))[0]
        eos = int(full[len(p) + 2])                # third greedy token
        eng = self._mk(cfg, params)
        rid = eng.submit(p, max_new_tokens=8, eos_token_id=eos)
        out = eng.run()[rid].output_ids
        ref = np.asarray(llama_generate(params, cfg, p[None], max_new_tokens=8,
                                        eos_token_id=eos))[0]
        # the engine returns the variable-length output; llama_generate
        # eos-pads to fixed shape — prefix must agree, tail must be padding
        np.testing.assert_array_equal(out, ref[:len(out)])
        assert out[-1] == eos and (ref[len(out):] == eos).all()
        # retired pages park in the prefix cache; releasing it must
        # return EVERY page (any leak fails here)
        eng.release_cache()
        assert eng.pool.num_free == eng.pool.num_pages

    def test_tight_pool_stall_recovers(self):
        """A pool too small for both requests' full horizons forces stalls;
        outputs must still be exact and all pages returned."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
        params = _params(cfg, seed=4)
        pa = rng.integers(1, 64, (8,)).astype(np.int32)
        pb = rng.integers(1, 64, (4,)).astype(np.int32)
        # worst case needs ceil((8+8-1)/4) + ceil((4+6-1)/4) = 4+3=7 pages;
        # give 6 so growth must contend
        eng = self._mk(cfg, params, page_size=4, num_pages=6,
                       max_pages_per_seq=4, decode_horizon=2)
        ra = eng.submit(pa, max_new_tokens=8)
        rb = eng.submit(pb, max_new_tokens=6)
        done = eng.run()
        for rid, p, n in ((ra, pa, 8), (rb, pb, 6)):
            ref = np.asarray(llama_generate(params, cfg, p[None],
                                            max_new_tokens=n))[0]
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        # retired pages park in the prefix cache; releasing it must
        # return EVERY page (any leak fails here)
        eng.release_cache()
        assert eng.pool.num_free == eng.pool.num_pages

    def test_former_deadlock_self_heals_via_preemption(self):
        """Two requests each needing 4 pages eventually, pool of 5: both
        admit (2+2), the lone free page goes to slot 0, then both slots
        stall mid-generation with nothing retirable.  This used to raise a
        hard 'ServingEngine deadlock' RuntimeError, dropping both requests;
        the self-healing engine now preempts the lowest-progress victim
        (pages back to the pool, request requeued for re-prefill) and BOTH
        requests complete with greedy outputs exactly matching the
        never-preempted llama_generate reference."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
        params = _params(cfg, seed=5)
        eng = self._mk(cfg, params, num_slots=2, page_size=4, num_pages=5,
                       max_pages_per_seq=4, decode_horizon=1)
        pa = rng.integers(1, 64, (8,)).astype(np.int32)
        pb = rng.integers(1, 64, (8,)).astype(np.int32)
        ra = eng.submit(pa, max_new_tokens=8)
        rb = eng.submit(pb, max_new_tokens=8)
        done = eng.run()
        assert eng.preemptions >= 1
        # per-request accounting must agree with the engine-level counter
        assert done[ra].preemptions + done[rb].preemptions \
            == eng.preemptions
        for rid, p in ((ra, pa), (rb, pb)):
            ref = np.asarray(llama_generate(params, cfg, p[None],
                                            max_new_tokens=8))[0]
            np.testing.assert_array_equal(done[rid].output_ids, ref)
        # retired pages park in the prefix cache; releasing it must
        # return EVERY page (any leak fails here)
        eng.release_cache()
        assert eng.pool.num_free == eng.pool.num_pages

    def test_submit_validation(self):
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=32)
        params = _params(cfg, seed=6)
        eng = self._mk(cfg, params, page_size=4, max_pages_per_seq=4)
        with pytest.raises(ValueError, match="exceeds the model context"):
            eng.submit(np.zeros((30,), np.int32), max_new_tokens=8)
        with pytest.raises(ValueError, match="max_pages_per_seq"):
            eng.submit(np.zeros((10,), np.int32), max_new_tokens=12)
        with pytest.raises(ValueError, match="max_new_tokens"):
            eng.submit(np.zeros((4,), np.int32), max_new_tokens=0)

    def test_seeded_sampling_reproducible(self):
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=64)
        params = _params(cfg, seed=7)
        p = rng.integers(1, 64, (6,)).astype(np.int32)

        def go(seed):
            eng = self._mk(cfg, params, seed=seed)
            rid = eng.submit(p, max_new_tokens=8, temperature=1.0, top_p=0.9)
            return eng.run()[rid].output_ids

        np.testing.assert_array_equal(go(5), go(5))
        assert not np.array_equal(go(5), go(6))


# ---------------------------------------------------------------------------
# scatter_kv_rows (PR 28): the row write indexes (layer, head, page, offset)
# with a window of D only — same values into the same rows as the window
# over (Hkv, D) it replaces, so the store must come out bit-identical
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("store", ["data", "scales"])
@pytest.mark.parametrize("tok", [(6,), (40,), (4, 5)],
                         ids=["decode", "prefill", "verify"])
def test_scatter_kv_rows_equals_the_per_layer_window_scatter(tok, store):
    from paddle_tpu.models.llama import scatter_kv_rows
    L, Hkv, NP, ps, D = 3, 2, 7, 8, 16
    TRASH = NP
    lr = np.random.default_rng(5)
    w = (D,) if store == "data" else ()
    pool = jnp.asarray(lr.standard_normal((L, Hkv, NP + 1, ps) + w)
                       .astype(np.float32))
    rows = jnp.asarray(lr.standard_normal(tok + (Hkv,) + w)
                       .astype(np.float32))
    # distinct live (page, off) targets, and a third of the lanes routed to
    # the TRASH page with DUPLICATE targets there (idle slots, padding rows)
    n = int(np.prod(tok))
    flat = lr.permutation(NP * ps)[:n]
    page, off = flat // ps, flat % ps
    trash = np.zeros(n, bool)
    trash[lr.permutation(n)[:max(3, n // 3)]] = True
    page = np.where(trash, TRASH, page)
    off = np.where(trash, np.arange(n) % 2, off)
    assert trash.sum() > len(set(off[trash]))        # duplicates exist
    page = jnp.asarray(page.reshape(tok).astype(np.int32))
    off = jnp.asarray(off.reshape(tok).astype(np.int32))

    def old(pool, li):
        # what `_scatter` did before: the scan's per-layer slice, a window
        # over (Hkv, D), stacked back
        layer = pool[li].at[:, page, off].set(jnp.moveaxis(rows, len(tok), 0))
        return pool.at[li].set(layer)

    for li in range(L):
        got = jax.jit(lambda li: scatter_kv_rows(pool, li, rows, page, off))(
            jnp.int32(li))
        np.testing.assert_array_equal(np.asarray(got),
                                      np.asarray(old(pool, li)))
        # only layer li changed, and only the addressed rows of it
        changed = np.asarray(got != pool)
        assert not np.delete(changed, li, axis=0).any()
        assert changed[li].any()


# ---------------------------------------------------------------------------
# scatter_kv_run (PR 32): a prefill's CONTIGUOUS run of rows reaches the pool
# a page an update.  Every real page must come out bit-equal to what the row
# form (`scatter_kv_rows` fed the parent's page/off expressions) leaves, so
# each bit-exactness contract above it holds untouched.
# ---------------------------------------------------------------------------
# id: (page size, rows C, start, real length)
_RUN_CASES = {
    "start_0_all_real": (64, 128, 0, 128),
    "start_0_length_100": (64, 128, 0, 100),
    "start_on_page_64": (64, 128, 64, 128),
    "start_inside_page_37": (64, 128, 37, 128),
    "length_ends_inside_a_page": (64, 128, 37, 70),
    "one_real_row": (64, 128, 37, 1),
    "whole_pages_of_padding": (64, 256, 64, 30),
    "page_16_rows_40_start_5": (16, 40, 5, 33),
    "page_16_rows_40_start_0": (16, 40, 0, 40),
}


@pytest.mark.parametrize("hkv", [8, 2], ids=["hkv8", "tp4_rank_hkv2"])
@pytest.mark.parametrize("store", ["plain", "int8_codes", "scales"])
@pytest.mark.parametrize("case", list(_RUN_CASES))
def test_scatter_kv_run_leaves_the_pool_the_row_form_leaves(case, store,
                                                            hkv):
    from paddle_tpu.models.llama import scatter_kv_rows, scatter_kv_run
    ps, C, start, length = _RUN_CASES[case]
    L, NP, D = 2, 11, 8
    TRASH = NP
    lr = np.random.default_rng(32)
    tail = () if store == "scales" else (D,)
    draw = (lambda shape: lr.integers(-127, 128, shape).astype(np.int8)) \
        if store == "int8_codes" else \
        (lambda shape: lr.standard_normal(shape).astype(np.float32))
    # a pool that already HOLDS something: the rows before `start`, past
    # `length` and on untouched pages must keep it
    pool = jnp.asarray(draw((L, hkv, NP + 1, ps) + tail))
    rows = jnp.asarray(draw((C, hkv) + tail))
    # the tightest table the engine could hand over: the padded rows'
    # positions run past its end
    n_pages = -(-(start + length) // ps)
    page_row = jnp.asarray(lr.permutation(NP)[:n_pages].astype(np.int32))

    def row_form(pool, li, start, length):
        # the parent's `prefill` / `prefill_chunk`, word for word
        i_idx = jnp.arange(C)
        pos = start + i_idx
        page = jnp.where(i_idx < length, page_row[pos // ps], TRASH)
        return scatter_kv_rows(pool, li, rows, page, pos % ps)

    run_form = jax.jit(lambda pool, li, start, length: scatter_kv_run(
        pool, li, rows, start, length, page_row))
    row_form = jax.jit(row_form)
    for li in range(L):
        at = (pool, jnp.int32(li), jnp.int32(start), jnp.int32(length))
        got, want = np.asarray(run_form(*at)), np.asarray(row_form(*at))
        np.testing.assert_array_equal(got[:, :, :NP], want[:, :, :NP])
        # pages of padding are the TRASH page written back as it was
        np.testing.assert_array_equal(got[:, :, TRASH],
                                      np.asarray(pool)[:, :, TRASH])
        changed = got != np.asarray(pool)
        assert not np.delete(changed, li, axis=0).any()
        assert changed[li].any()


def test_scatter_kv_run_issues_a_page_an_update():
    """The point of it: the scatter's update window is (ps, D) and its
    updates number (pages touched) x Hkv, not C x Hkv."""
    from paddle_tpu.models.llama import scatter_kv_run
    ps, C, hkv, D = 64, 512, 8, 16
    pool = jnp.zeros((2, hkv, 40, ps, D))
    rows = jnp.zeros((C, hkv, D))
    table = jnp.arange(32, dtype=jnp.int32)
    text = jax.jit(lambda li, start, n: scatter_kv_run(
        pool, li, rows, start, n, table)).lower(
            jnp.int32(0), jnp.int32(0), jnp.int32(C)).as_text()
    (dims,) = set(re.findall(r"update_window_dims = \[([\d, ]*)\]", text))
    assert dims.replace(" ", "") == "2,3", text[-3000:]
    assert f"tensor<{hkv}x{C // ps + 1}x{ps}x{D}xf32>" in text


def test_prefill_kv_counters_say_how_many_updates_a_page_saves():
    """`prefill_kv_rows_written` / `prefill_kv_pages_written`: host
    arithmetic from the run's start, its real length and the page size —
    the rows a prefill wrote and the REAL pages they lie on (per layer,
    side and head alike).  A 37-token dense prefill, then a 130-token
    prompt whose first 37 tokens hit the prefix cache (two whole pages of
    16 and five rows of a third, copied on write): 93 rows from position
    37, on pages 2..8."""
    cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4, seq=256)
    params = _params(cfg, seed=3)
    eng = ServingEngine(params, cfg, num_slots=2, page_size=16,
                        num_pages=40, max_pages_per_seq=12,
                        attention_impl="ref", prompt_bucket=16)
    lr = np.random.default_rng(37)
    first = lr.integers(1, 64, (37,)).astype(np.int32)
    r1 = eng.submit(first, max_new_tokens=3)
    done = eng.run()
    st = eng.stats()
    assert (st["prefill_kv_rows_written"],
            st["prefill_kv_pages_written"]) == (37, 3)
    # the 38th token differs from what the first request generated there,
    # so the cached part-full page matches in five rows and no more
    nxt = (int(done[r1].generated[0]) % 62) + 1
    assert nxt != int(done[r1].generated[0])
    second = np.concatenate([first, [nxt],
                             lr.integers(1, 64, (92,))]).astype(np.int32)
    r2 = eng.submit(second, max_new_tokens=2)
    done = eng.run()
    assert done[r2].cached_prefix_tokens == 37
    st = eng.stats()
    assert st["prefill_kv_rows_written"] == 37 + 93
    assert st["prefill_kv_pages_written"] == 3 + 7
    ref = np.asarray(llama_generate(params, cfg, second[None],
                                    max_new_tokens=2))[0]
    np.testing.assert_array_equal(done[r2].output_ids, ref)
