"""The SambaY family (`models/sambay.py`: Mamba-1 + window and full
differential attention + Gated Memory Units, ONE K/V page store that several
layers read) through `ServingEngine`, every request held to
`benchmark/reference_sambay.py`'s full forward pass (every token through
every layer, no cache): dense and chunked prefill with window, state and tail
carried, decode past a wrap of the window's ring, preemption, slot reuse; the
paired form of differential attention against four plain calls; the scan
against the one-token update; what the configuration and the engine
refuse.  The model fns alone: `tests/test_sambay.py`."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_sambay as ref
from paddle_tpu.inference.paged import ServingEngine
from paddle_tpu.models.sambay import (build_functional_sambay,
                                      sambay_config_tiny)

TOY_LIMIT = 1e-4            # float32 end to end: a rounding's worth


@pytest.fixture(scope="module")
def model():
    cfg = sambay_config_tiny()
    params = jax.jit(lambda k: build_functional_sambay(
        cfg, k, jnp.float32))(jax.random.PRNGKey(5))
    keys = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return cfg, params, keys


def engine(model, **kw):
    cfg, params, _ = model
    kw = {"num_slots": 3, "page_size": 8, "max_pages_per_seq": 16,
          "dtype": jnp.float32, "attention_impl": "ref", "prompt_bucket": 32,
          "prefill_chunk": 32, "decode_horizon": 4, **kw}
    return ServingEngine(params, cfg, **kw)


def prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def hold_to_reference(model, eng, prompt, rid, state=True):
    """The request's tokens under the reference's logits; with ``state``
    (the request still holds its slot) its SSM states and the window's rows,
    read out of the ring by position, too."""
    cfg, params, keys = model
    req = eng.lookup(rid)
    want = ref.check_generation(params, keys, prompt, req.generated)
    assert max(want["gaps"]) < TOY_LIMIT, (rid, want["gaps"])
    if state:
        got = eng.recurrent_state(rid)
        assert max(ref.relative_errors(list(got["ssm"]), want["states"])) \
            < TOY_LIMIT
        rows = want["window_positions"] % cfg.sliding_window
        for j, (k, v) in enumerate(want["window"]):
            assert max(ref.relative_errors(
                [got["window_k"][j][rows], got["window_v"][j][rows]],
                [k, v])) < TOY_LIMIT, j


def run_keeping_states(eng, rids, check):
    """Step until every request finished; ``check(rid)`` right after a
    request finishes, while its slot still holds its state."""
    seen = set()
    while not all(eng.lookup(r).finish_time for r in rids):
        eng.step()
        for r in rids:
            if r not in seen and eng.lookup(r).finish_time:
                seen.add(r)
                check(r)


@pytest.mark.parametrize("chunk,lens", [
    (None, [27, 70, 45, 9]), (32, [27, 70, 45, 9, 33, 96])],
    ids=["dense", "chunked"])
def test_more_requests_than_slots_every_one_trails_the_reference(model, chunk,
                                                                 lens):
    """Prompts shorter and longer than the window (16) and than three chunks,
    24 decoded tokens each (past a wrap of the ring), more requests than
    slots: a reused slot's stale ring and state must not leak."""
    cfg = model[0]
    ps = prompts(cfg, lens)
    eng = engine(model, prefill_chunk=chunk)
    rids = [eng.submit(p, max_new_tokens=24) for p in ps]
    by_rid = dict(zip(rids, ps))
    run_keeping_states(eng, rids, lambda r: hold_to_reference(
        model, eng, by_rid[r], r))
    eng.check_invariants()
    st = eng.stats()
    # one token a prompt entered the second half of the model
    assert st["prefill_tokens_cross_decoder"] == len(ps)
    assert st["prefill_tokens_self_decoder"] == sum(lens)
    assert st["shared_kv_rows_written"] == sum(lens) + st["ssm_live_slot_steps"]
    assert st["shared_kv_bytes_per_token"] == 2 * 4 * 8 * 4
    assert st["shared_kv_tokens_attended_decode"] > 0
    assert 0 < st["window_tokens_attended_decode"] \
        <= 2 * cfg.sliding_window * st["ssm_live_slot_steps"]


def test_a_preempted_request_is_prefilled_again_and_trails_the_reference(
        model):
    cfg = model[0]
    ps = prompts(cfg, [19, 21], seed=2)
    eng = engine(model, num_slots=2, num_pages=9, max_pages_per_seq=8)
    rids = [eng.submit(p, max_new_tokens=30) for p in ps]
    done = eng.run()
    eng.check_invariants()
    assert eng.stats()["preemptions"] >= 1
    for p, rid in zip(ps, rids):
        assert len(done[rid].generated) == 30
        hold_to_reference(model, eng, p, rid, state=False)


def test_a_reused_slot_gives_the_tokens_of_a_fresh_engine(model):
    """One slot, a long request and then a short one: what the long one left
    in the ring, the state and the tail is nobody's."""
    cfg = model[0]
    long_, short = prompts(cfg, [90, 11], seed=4)
    eng = engine(model, num_slots=1)
    eng.submit(long_, max_new_tokens=20)
    rid = eng.submit(short, max_new_tokens=20)
    done = eng.run()
    fresh = engine(model, num_slots=1)
    again = fresh.submit(short, max_new_tokens=20)
    assert fresh.run()[again].generated == done[rid].generated
    hold_to_reference(model, eng, short, rid)


@pytest.fixture(scope="module")
def baseline(model):
    """(prompts, the tokens the plain synchronous engine makes of them)."""
    ps = prompts(model[0], [14, 40, 7, 26, 18], seed=3)
    eng = engine(model)
    rids = [eng.submit(p, max_new_tokens=8) for p in ps]
    done = eng.run()
    return ps, [done[r].generated for r in rids]


@pytest.mark.parametrize("kw", [{"overlap": True}, {"decode_horizon": 1},
                                {"attention_impl": "pallas",
                                 "interpret": True}],
                         ids=["overlap", "horizon_1", "kernel"])
def test_the_same_tokens_whatever_the_loop(model, baseline, kw):
    ps, want = baseline
    eng = engine(model, **kw)
    rids = [eng.submit(p, max_new_tokens=8) for p in ps]
    done = eng.run()
    eng.check_invariants()
    assert [done[r].generated for r in rids] == want


def test_the_cache_holds_one_store_a_ring_and_a_state(model):
    cfg = model[0]
    eng = engine(model)
    cache = eng._cache
    assert eng.family.page_leaves == ("k", "v") and eng.family.recurrent
    assert eng.cache is None                        # no prefix cache
    pairs, wide = cfg.num_key_value_heads // 2, 2 * cfg.head_dim
    assert cache["k"].shape == (1, pairs, 3 * 16 + 1, 8, wide)
    # exactly `sliding_window` rows a slot and window layer, whatever the
    # context (and one page for dead slots' writes)
    assert cache["win_k"].shape == (2, pairs, 3 * cfg.sliding_window // 8 + 1,
                                    8, wide)
    assert len(cache["ssm"]) == 3 and all(
        s.shape == (3, cfg.mamba_d_state, cfg.d_inner)
        and s.dtype == jnp.float32 for s in cache["ssm"])
    rid = eng.submit(prompts(cfg, [10])[0], max_new_tokens=4)
    eng.run()
    got = eng.recurrent_state(rid)
    assert got["ssm"].shape == (3, cfg.d_inner, cfg.mamba_d_state)
    assert got["window_k"].shape == (2, cfg.sliding_window,
                                     cfg.num_key_value_heads, cfg.head_dim)


def test_a_window_that_is_not_whole_pages_is_refused(model):
    with pytest.raises(ValueError, match="whole pages"):
        engine(model, page_size=12)


@pytest.mark.parametrize("what", ["speculative", "quantize", "kv_dtype",
                                  "mesh"])
def test_the_engine_refuses_by_name_what_the_family_lacks(model, what):
    from paddle_tpu.distributed.topology import build_mesh
    kw = {"speculative": 2, "quantize": 8, "kv_dtype": "int8",
          "mesh": build_mesh({"mp": 2}, devices=jax.devices()[:2])}[what]
    with pytest.raises(NotImplementedError, match=what):
        engine(model, **{what: kw})


def test_page_only_transfers_are_refused_and_a_compact_snapshot_restores(
        model):
    cfg = model[0]
    ps = prompts(cfg, [13, 22], seed=6)
    eng = engine(model)
    rids = [eng.submit(p, max_new_tokens=12) for p in ps]
    eng.step()
    eng.step()
    with pytest.raises(NotImplementedError, match="full_kv"):
        eng.snapshot("full_kv")
    with pytest.raises(NotImplementedError, match="export_kv"):
        eng.export_kv([rids[0]])
    with pytest.raises(NotImplementedError, match="import_kv"):
        eng.import_kv({"version": 1})
    state = eng.snapshot("compact")
    other = engine(model)
    assert other.restore(state) == "reprefill"
    got, want = other.run(), eng.run()
    assert [got[r].generated for r in rids] == [want[r].generated for r in rids]


def test_spans_say_which_prefill_entered_the_second_half(model, tmp_path):
    from benchmark import host_spans
    cfg = model[0]
    eng = engine(model)
    eng.submit(prompts(cfg, [70])[0], max_new_tokens=5)
    eng.submit(prompts(cfg, [6])[0], max_new_tokens=5)
    eng.run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    eng.submit(prompts(cfg, [70], seed=9)[0], max_new_tokens=5)
    eng.submit(prompts(cfg, [6], seed=9)[0], max_new_tokens=5)
    eng.run()
    jax.profiler.stop_trace()
    spans = host_spans.engine_line(host_spans.load(str(tmp_path)))
    chunks = sorted((s for s in spans if s[0] == "serve.prefill_chunk"),
                    key=lambda s: s[1])
    assert [int(s[3]["cross_decoder"]) for s in chunks] == [0, 0, 1]
    assert [int(s[3]["state_carried"]) for s in chunks] == [0, 1, 1]
    dense = [s for s in spans if s[0] == "serve.prefill_dense"]
    assert [int(s[3]["cross_decoder"]) for s in dense] == [1]
    assert {s[3]["family"] for s in chunks + dense} == {"sambay"}
    assert any(s[0] == "serve.decode_dispatch"
               and s[3]["family"] == "sambay" for s in spans)


def test_another_family_s_spans_and_chunk_call_are_as_they_were():
    """`chunk_takes_last` is this family's: the others' chunk executable
    takes no ``last`` and their spans no ``cross_decoder``."""
    from paddle_tpu.models.llama import (build_functional_llama,
                                         llama_config_tiny)
    cfg = llama_config_tiny()
    params = build_functional_llama(cfg)[:3]
    eng = ServingEngine(params, cfg, num_slots=2, page_size=4,
                        max_pages_per_seq=16, prefill_chunk=8,
                        prompt_bucket=8, attention_impl="ref")
    assert not eng.family.chunk_takes_last
    rid = eng.submit(np.arange(1, 20, dtype=np.int32), max_new_tokens=3)
    assert len(eng.run()[rid].generated) == 3
