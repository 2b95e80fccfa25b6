"""Lossless self-speculative decoding suite (ISSUE 4 tentpole).

Three layers:

  * `_NgramDraft` unit behavior — longest-suffix-first prompt-lookup
    matching, most-recent-occurrence selection, periodic extrapolation
    past the end of the sequence, no self-matching.
  * `verify_step` model-fn parity — the K+1-position verify dispatch must
    reproduce the sequential `decode_step` tokens/logits exactly (the
    acceptance test is only sound if scoring a token in a batch of drafts
    equals scoring it alone).
  * Engine PARITY — the acceptance bar: greedy outputs with
    `speculative=K` (K in {2, 4, 8}) bit-exact vs the speculation-off
    engine AND vs `llama_generate` across: all-rejected drafts,
    all-accepted runs (echo-biased model), EOS inside an accepted run,
    budget freeze mid-run (horizon AND speculative), preemption +
    re-prefill mid-speculation, prefix cache on and off, and mixed
    speculating/non-speculating batches.  Every scenario also passes the
    conftest refcount leak guard (`ServingEngine.check_invariants`).
"""
import zlib

import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.models.llama import (LlamaConfig, llama_config_tiny,
                                     build_functional_llama,
                                     build_llama_paged_decode,
                                     llama_generate)
from paddle_tpu.inference.paged import ServingEngine, _NgramDraft

rng = np.random.default_rng(41)


# ---------------------------------------------------------------------------
# _NgramDraft unit behavior
# ---------------------------------------------------------------------------
class TestNgramDraft:
    def test_longest_suffix_first_and_most_recent(self):
        d = _NgramDraft([1, 2, 3, 9, 1, 2, 3, 1, 2])
        # suffix (3, 1, 2) never recurs; (1, 2) does — most recent earlier
        # occurrence is at index 4..5, continuation [3, 1, 2, ...]
        assert d.propose(3) == [3, 1, 2]

    def test_periodic_extrapolation_past_end(self):
        # period-3 sequence: the match runs off the end and must extend
        # with its own lag-periodic prediction, not truncate
        d = _NgramDraft([7, 8, 9, 7, 8, 9, 7, 8])
        assert d.propose(6) == [9, 7, 8, 9, 7, 8]
        # period-1 (the echo-model shape): full k from a 1-token tail
        assert _NgramDraft([5, 5, 5]).propose(4) == [5, 5, 5, 5]

    def test_no_match_and_no_self_match(self):
        assert _NgramDraft([1, 2, 3, 4]).propose(4) == []
        # a sequence whose suffix occurs ONLY as the suffix itself must
        # not match itself (zero-length continuation is not a draft)
        assert _NgramDraft([9, 1, 2]).propose(4) == []
        assert _NgramDraft([3]).propose(4) == []

    def test_incremental_append_equals_rebuild(self):
        toks = list(rng.integers(0, 4, 60))
        inc = _NgramDraft(toks[:30])
        for t in toks[30:]:
            inc.append(t)
        rebuilt = _NgramDraft(toks)
        for k in (1, 3, 8):
            assert inc.propose(k) == rebuilt.propose(k)

    def test_propose_zero_or_negative_is_empty(self):
        d = _NgramDraft([5, 5, 5])
        assert d.propose(0) == [] and d.propose(-1) == []


# ---------------------------------------------------------------------------
# verify_step model-fn parity vs sequential decode_step
# ---------------------------------------------------------------------------
def _params(cfg, seed=0):
    ep, bp, hp, *_ = build_functional_llama(cfg, key=jax.random.PRNGKey(seed))
    return ep, bp, hp


def _echo_params(cfg, seed=0):
    """Echo-biased params: block weights down-scaled so the residual
    stream stays embedding-dominated, LM head tied to the embedding
    transpose — greedy decode settles into repetition, the deterministic
    stand-in for high-overlap (extractive/template) traffic."""
    ep, bp, hp, *_ = build_functional_llama(cfg, key=jax.random.PRNGKey(seed))
    bp = {k: (v * 0.05 if k.startswith("w") else v) for k, v in bp.items()}
    hp = dict(hp, lm=(ep["tok"].T * 4.0).astype(hp["lm"].dtype))
    return ep, bp, hp


class TestVerifyStepParity:
    def test_verify_matches_sequential_decode(self):
        """Drafting the TRUE greedy continuation: every verify position's
        argmax must equal the sequential decode tokens, and the position-0
        logits must equal the single-token decode logits."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=64)
        params = _params(cfg, seed=3)
        ps, NP, P = 4, 16, 8
        fam = build_llama_paged_decode(cfg, page_size=ps, num_pages=NP,
                                       attention_impl="ref")
        ids = rng.integers(1, 64, (1, 6)).astype(np.int32)
        row = np.zeros((P,), np.int32)
        row[:4] = [3, 7, 1, 5]
        logits, cache = fam.prefill(
            params, jnp.asarray(ids), jnp.asarray(6, jnp.int32),
            jnp.asarray(row), 0, fam.init_cache())
        pending = int(jnp.argmax(logits))
        tables = jnp.asarray(row[None])
        # sequential greedy reference (fresh copies of the pages)
        seq_toks, seq_logits = [], []
        seq_cache = cache
        tok, lengths = pending, 6
        for _ in range(4):
            lg, seq_cache = fam.decode_step(
                params, jnp.asarray([tok], jnp.int32),
                jnp.asarray([lengths], jnp.int32), tables, seq_cache,
                jnp.ones((1,), bool))
            seq_logits.append(np.asarray(lg[0]))
            tok = int(jnp.argmax(lg[0]))
            seq_toks.append(tok)
            lengths += 1
        # verify the first 3 true tokens as drafts (pending + 3 = 4 queries)
        toks = np.zeros((1, 4), np.int32)
        toks[0, 0] = pending
        toks[0, 1:] = seq_toks[:3]
        logits0, greedy, _ = fam.verify_step(
            params, jnp.asarray(toks), jnp.asarray([6], jnp.int32),
            tables, cache, jnp.asarray([4], jnp.int32))
        assert [int(t) for t in np.asarray(greedy)[0]] == seq_toks
        np.testing.assert_allclose(np.asarray(logits0[0]), seq_logits[0],
                                   rtol=1e-5, atol=1e-5)

    def test_position0_logits_independent_of_later_drafts(self):
        """Causality: a WRONG draft at position j must not change any
        logits at positions < j (the accepted prefix stays lossless)."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=64)
        params = _params(cfg, seed=4)
        ps, NP, P = 4, 16, 8
        fam = build_llama_paged_decode(cfg, page_size=ps, num_pages=NP,
                                       attention_impl="ref")
        ids = rng.integers(1, 64, (1, 5)).astype(np.int32)
        row = np.zeros((P,), np.int32)
        row[:4] = [2, 9, 4, 6]
        logits, cache = fam.prefill(
            params, jnp.asarray(ids), jnp.asarray(5, jnp.int32),
            jnp.asarray(row), 0, fam.init_cache())
        pending = int(jnp.argmax(logits))
        tables = jnp.asarray(row[None])
        out = {}
        for name, draft in (("good", [10, 11, 12]), ("bad", [50, 51, 52])):
            toks = np.zeros((1, 4), np.int32)
            toks[0, 0] = pending
            toks[0, 1:] = draft
            lg0, greedy, _ = fam.verify_step(
                params, jnp.asarray(toks), jnp.asarray([5], jnp.int32),
                tables, cache, jnp.asarray([4], jnp.int32))
            out[name] = (np.asarray(lg0[0]), int(np.asarray(greedy)[0, 0]))
        np.testing.assert_array_equal(out["good"][0], out["bad"][0])
        assert out["good"][1] == out["bad"][1]


# ---------------------------------------------------------------------------
# Engine parity: the acceptance bar
# ---------------------------------------------------------------------------
def _mk(cfg, params, **kw):
    base = dict(num_slots=2, page_size=8, num_pages=48, max_pages_per_seq=10,
                attention_impl="ref", prompt_bucket=8, decode_horizon=3)
    base.update(kw)
    return ServingEngine(params, cfg, **base)


def _run_spec_vs_plain(cfg, params, prompts, max_new=8, eos=None, **kw):
    """Run the SAME prompts through speculative and plain engines; assert
    greedy outputs bit-exact between them AND vs llama_generate; return
    the speculative engine for counter assertions."""
    outs, engines = {}, {}
    for spec in (kw.pop("speculative", 4), None):
        eng = _mk(cfg, params, speculative=spec, **kw)
        rids = [eng.submit(p, max_new_tokens=max_new, eos_token_id=eos)
                for p in prompts]
        done = eng.run()
        outs[spec] = [done[r].output_ids for r in rids]
        engines[spec] = eng
        eng.check_invariants()
    (spec_on,) = [k for k in outs if k]
    for got_on, got_off, p in zip(outs[spec_on], outs[None], prompts):
        np.testing.assert_array_equal(got_on, got_off)
        ref = np.asarray(llama_generate(params, cfg, p[None],
                                        max_new_tokens=max_new,
                                        eos_token_id=eos))[0]
        # llama_generate pads the tail with eos after finishing; the
        # engine stops — compare the engine's tokens against the prefix
        np.testing.assert_array_equal(got_on, ref[:len(got_on)])
        if eos is not None and len(got_on) < len(ref):
            assert got_on[-1] == eos or len(got_on) - len(p) == max_new
            assert np.all(ref[len(got_on):] == eos)
    return engines[spec_on]


class TestSpecDecodeEngineParity:
    @pytest.fixture
    def rng(self, request):
        """A generator of the test's own, seeded from its name: what a test
        draws does not depend on which tests ran before it."""
        return np.random.default_rng(zlib.crc32(request.node.name.encode()))

    @pytest.mark.parametrize("K", [2, 4, 8])
    def test_random_traffic_parity_any_K(self, rng, K):
        """Random prompts (mixed accepted/rejected drafts): bit-exact at
        every K, prefix cache ON (the default).  A draft exists at every
        K by construction: the proposer matches the n-gram that ENDS in
        the newest emitted token, so two prompts close on a span they
        opened with and the third holds every id of the vocabulary once —
        whatever the model emits first has occurred before."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=96)
        params = _params(cfg, seed=1)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (9, 5)]
        for p in prompts:
            p[-2:] = p[:2]
        prompts.append(rng.permutation(64).astype(np.int32))
        eng = _run_spec_vs_plain(cfg, params, prompts, speculative=K)
        assert eng.verify_steps > 0

    def test_parity_prefix_cache_off(self, rng):
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=96)
        params = _params(cfg, seed=2)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (7, 10)]
        _run_spec_vs_plain(cfg, params, prompts, speculative=4,
                           prefix_cache=False)

    def test_all_accepted_echo_model(self, rng):
        """Echo-biased model: greedy output settles into repetition, so
        drafts accept nearly always — the maximal-rewind-free path — and
        outputs stay bit-exact."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=128)
        params = _echo_params(cfg, seed=5)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (6, 11)]
        eng = _run_spec_vs_plain(cfg, params, prompts, max_new=16,
                                 speculative=4, num_pages=64,
                                 max_pages_per_seq=12)
        st = eng.stats()
        assert st["draft_tokens_accepted"] >= st["draft_tokens_proposed"] // 2
        assert st["draft_tokens_accepted"] > 0

    def test_all_rejected_drafts(self, rng):
        """Prompts with embedded repetition fire the n-gram proposer, but
        a plain random model's continuation diverges — drafts keep being
        rejected (exercising the rewind path every step) and outputs stay
        bit-exact; the adaptive spec_k backs off to its floor."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=96)
        params = _params(cfg, seed=7)
        pat = rng.integers(1, 64, (4,)).astype(np.int32)
        prompts = [np.concatenate([pat, pat, pat]).astype(np.int32),
                   np.tile(rng.integers(1, 64, (3,)), 4).astype(np.int32)]
        eng = _run_spec_vs_plain(cfg, params, prompts, speculative=4)
        st = eng.stats()
        assert st["draft_tokens_proposed"] > 0
        assert st["draft_tokens_accepted"] < st["draft_tokens_proposed"]
        for slot_req in eng._finished.values():
            assert 0.0 <= slot_req.draft_accept_rate <= 1.0

    def test_eos_inside_accepted_run(self, rng):
        """EOS token emitted INSIDE an accepted speculative run: the
        request freezes at the EOS, later accepted tokens are discarded,
        and the output equals llama_generate's with the same eos."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=128)
        params = _echo_params(cfg, seed=7)
        p = rng.integers(1, 64, (9,)).astype(np.int32)
        # pick the eos a few tokens into the reference continuation so it
        # lands mid-run once speculation is warmed up
        ref = np.asarray(llama_generate(params, cfg, p[None],
                                        max_new_tokens=16))[0]
        eos = int(ref[len(p) + 4])
        eng = _run_spec_vs_plain(cfg, params, [p], max_new=16, eos=eos,
                                 speculative=4, num_pages=64,
                                 max_pages_per_seq=12)
        done = list(eng._finished.values())[0]
        assert done.generated[-1] == eos
        assert len(done.generated) < 16          # EOS actually fired early

    def test_budget_freeze_mid_speculative_run(self, rng):
        """max_new_tokens reached mid-accepted-run: exactly the budget is
        emitted, token-for-token vs llama_generate."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=128)
        params = _echo_params(cfg, seed=8)
        p = rng.integers(1, 64, (7,)).astype(np.int32)
        for max_new in (3, 5):
            eng = _run_spec_vs_plain(cfg, params, [p], max_new=max_new,
                                     speculative=8, num_pages=64,
                                     max_pages_per_seq=12)
            done = list(eng._finished.values())[0]
            assert len(done.generated) == max_new

    def test_budget_freeze_mid_horizon(self, rng):
        """ISSUE satellite: the NON-speculative decode-horizon budget
        edge — a slot whose max_new_tokens lands mid-horizon freezes at
        exactly the budget, token-for-token vs llama_generate."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=96)
        params = _params(cfg, seed=9)
        p = rng.integers(1, 64, (8,)).astype(np.int32)
        for max_new in (3, 5, 7):                # all inside horizon=8
            eng = _mk(cfg, params, decode_horizon=8)
            r = eng.submit(p, max_new_tokens=max_new)
            done = eng.run()
            assert len(done[r].generated) == max_new
            ref = np.asarray(llama_generate(params, cfg, p[None],
                                            max_new_tokens=max_new))[0]
            np.testing.assert_array_equal(done[r].output_ids, ref)
            eng.check_invariants()

    def test_preemption_mid_speculation(self, rng):
        """Tight pool forces a preemption while slots are speculating: the
        victim re-prefills (hitting its own parked blocks) and greedy
        outputs stay step-exact vs the spec-off engine and
        llama_generate."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=96)
        params = _echo_params(cfg, seed=10)
        prompts = [rng.integers(1, 64, (8,)).astype(np.int32)
                   for _ in range(2)]
        eng = _run_spec_vs_plain(cfg, params, prompts, max_new=8,
                                 speculative=4, page_size=4, num_pages=5,
                                 max_pages_per_seq=4, decode_horizon=1)
        assert eng.preemptions >= 1
        assert eng.verify_steps >= 1

    def test_mixed_speculating_and_sampled_batch(self, rng):
        """A sampled (temperature > 0) request shares the batch with
        greedy speculating slots: greedy outputs stay bit-exact vs
        llama_generate, the sampled slot rides the verify dispatch as a
        single-token lane, and the whole engine stays seed-reproducible."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=128)
        params = _echo_params(cfg, seed=11)
        pg = rng.integers(1, 64, (10,)).astype(np.int32)
        psamp = rng.integers(1, 64, (6,)).astype(np.int32)

        def go(seed):
            eng = _mk(cfg, params, speculative=4, num_pages=64,
                      max_pages_per_seq=12, seed=seed)
            rg = eng.submit(pg, max_new_tokens=12)
            rs = eng.submit(psamp, max_new_tokens=12, temperature=1.0,
                            top_p=0.9)
            done = eng.run()
            eng.check_invariants()
            return done[rg].output_ids, done[rs].output_ids, eng

        g1, s1, eng = go(3)
        g2, s2, _ = go(3)
        np.testing.assert_array_equal(g1, g2)
        np.testing.assert_array_equal(s1, s2)    # seed-reproducible
        ref = np.asarray(llama_generate(params, cfg, pg[None],
                                        max_new_tokens=12))[0]
        np.testing.assert_array_equal(g1, ref)
        st = eng.stats()
        assert st["verify_steps"] > 0            # speculation was active
        # the sampled request never proposed drafts
        assert eng._finished[1].draft_proposed == 0

    def test_staggered_arrivals_with_speculation(self, rng):
        """Second wave submitted mid-run (continuous batching) with
        speculation on: parity holds across admissions into a running
        speculative batch."""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=96)
        params = _params(cfg, seed=12)
        prompts = [rng.integers(1, 64, (t,)).astype(np.int32)
                   for t in (5, 9, 4, 11)]
        outs = {}
        for spec in (4, None):
            eng = _mk(cfg, params, speculative=spec)
            rids = [eng.submit(p, max_new_tokens=6) for p in prompts[:2]]
            eng.step()
            rids += [eng.submit(p, max_new_tokens=6) for p in prompts[2:]]
            done = eng.run()
            outs[spec] = [done[r].output_ids for r in rids]
            eng.check_invariants()
        for a, b, p in zip(outs[4], outs[None], prompts):
            np.testing.assert_array_equal(a, b)
            ref = np.asarray(llama_generate(params, cfg, p[None],
                                            max_new_tokens=6))[0]
            np.testing.assert_array_equal(a, ref)

    def test_stats_counters_consistent(self, rng):
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=96)
        params = _echo_params(cfg, seed=13)
        eng = _mk(cfg, params, speculative=4, num_pages=64,
                  max_pages_per_seq=12)
        r = eng.submit(rng.integers(1, 64, (8,)).astype(np.int32),
                       max_new_tokens=12)
        done = eng.run()
        st = eng.stats()
        assert st["tokens_generated"] == 12 == len(done[r].generated)
        assert 0.0 <= st["draft_accept_rate"] <= 1.0
        assert st["draft_tokens_accepted"] <= st["draft_tokens_proposed"]
        # disjoint dispatch counts: plain horizons + verifies = all steps
        assert st["verify_steps"] + st["decode_steps"] == eng.steps_run
        assert st["verify_steps"] > 0
        # all-greedy traffic: EVERY steady-state dispatch emitted tokens
        # on-device (fused argmax) — none returned logits for host sampling
        assert st["fused_sample_steps"] == eng.steps_run > 0
        req = done[r]
        assert req.draft_accepted == st["draft_tokens_accepted"]
        assert req.draft_proposed == st["draft_tokens_proposed"]


# ---------------------------------------------------------------------------
# Impl-uniform losslessness (ISSUE 16): verify, decode, AND chunked prefill
# must score through the ONE ragged attention callable — no jnp-reference
# fallback special to the verify path
# ---------------------------------------------------------------------------
class TestImplUniformAttention:
    def test_verify_decode_chunk_share_one_attention_callable(self):
        """Monkeypatch the unified ragged ref with a recorder BEFORE
        building the paged fns (the builder binds it at build time): one
        chunked prefill, one decode step, and one verify dispatch must all
        route through that single recorded callable, with segment widths
        Qmax = chunk, 1, and K+1 — there is no per-path attention
        implementation left to drift."""
        import paddle_tpu.ops.pallas.paged_attention as pa
        calls = []
        real = pa.ragged_paged_attention_ref

        def recorder(q, *a, **kw):
            calls.append(q.shape[1])          # Qmax of this dispatch
            return real(q, *a, **kw)

        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=64)
        params = _params(cfg, seed=5)
        ps, NP, P = 4, 16, 8
        orig = pa.ragged_paged_attention_ref
        pa.ragged_paged_attention_ref = recorder
        try:
            fam = build_llama_paged_decode(cfg, page_size=ps, num_pages=NP,
                                           attention_impl="ref")
            row = np.zeros((P,), np.int32)
            row[:4] = [3, 7, 1, 5]
            ids = rng.integers(1, 64, (1, 8)).astype(np.int32)
            # chunked prefill: the whole prompt as one chunk (Qmax = 8)
            logits, tok_g, cache = fam.prefill_chunk(
                params, jnp.asarray(ids), jnp.asarray(0, jnp.int32),
                jnp.asarray(8, jnp.int32), jnp.asarray(row), 0,
                fam.init_cache())
            assert int(tok_g) == int(jnp.argmax(logits))
            chunk_widths = set(calls)
            assert chunk_widths == {8}, calls
            calls.clear()
            # decode: Qmax = 1
            tables = jnp.asarray(row[None])
            _lg, cache = fam.decode_step(
                params, jnp.asarray([int(tok_g)], jnp.int32),
                jnp.asarray([8], jnp.int32), tables, cache,
                jnp.ones((1,), bool))
            assert set(calls) == {1}, calls
            calls.clear()
            # speculative verify: Qmax = K+1 = 4
            toks = np.zeros((1, 4), np.int32)
            toks[0, 0] = int(tok_g)
            toks[0, 1:] = [1, 2, 3]
            fam.verify_step(params, jnp.asarray(toks),
                            jnp.asarray([9], jnp.int32), tables, cache,
                            jnp.asarray([4], jnp.int32))
            assert set(calls) == {4}, calls
        finally:
            pa.ragged_paged_attention_ref = orig

    @pytest.mark.slow   # 3s engine compile; counter consistency stays tier-1
    def test_sampled_lane_keeps_logit_path_counter(self):
        """A sampled (temperature > 0) ride-along lane makes its verify
        dispatches logit-path: fused_sample_steps stays strictly below
        steps_run, while decode/verify disjointness is untouched.
        (Drafting is greedy-only, so the speculation is driven by a
        greedy echo-traffic request sharing the batch.)"""
        cfg = llama_config_tiny(vocab=64, hidden=32, layers=2, heads=4,
                                seq=96)
        params = _echo_params(cfg, seed=17)
        eng = _mk(cfg, params, speculative=3, num_pages=64,
                  max_pages_per_seq=12)
        eng.submit(np.tile(np.array([5, 9, 2], np.int32), 4),
                   max_new_tokens=12)                       # greedy, drafts
        eng.submit(rng.integers(1, 64, (8,)).astype(np.int32),
                   max_new_tokens=12, temperature=0.8, top_p=0.9)
        eng.run()
        st = eng.stats()
        assert st["verify_steps"] + st["decode_steps"] == eng.steps_run
        assert st["verify_steps"] > 0
        # horizon dispatches are always token-emitting; a verify carrying
        # the sampled lane is logit-path, one after it retires is fused
        assert st["decode_steps"] <= st["fused_sample_steps"]
        assert st["fused_sample_steps"] < eng.steps_run
