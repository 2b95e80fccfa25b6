"""A family with recurrent state through `ServingEngine`: admission, slot
reuse, chunked prefill with the state carried, preemption (re-prefill), the
decode horizon and the double-buffered loop, every request held to
`benchmark/reference_nemotron_h.py`'s full forward pass; what the engine
refuses for such a family; and the Llama path through the same seam."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_nemotron_h as ref
from paddle_tpu.inference.paged import ServingEngine
from paddle_tpu.models.nemotron_h import (build_functional_nemotron_h,
                                          nemotron_h_config_tiny)

TOY_LOGIT_LIMIT = 1e-4      # float32 end to end: a rounding's worth
TOY_STATE_LIMIT = 1e-5


@pytest.fixture(scope="module")
def model():
    cfg = nemotron_h_config_tiny(experts_held=(4, 8))
    params = jax.jit(lambda k: build_functional_nemotron_h(
        cfg, k, jnp.float32))(jax.random.PRNGKey(5))
    keys = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    keys["expert_offset"] = 4
    return cfg, params, keys


def engine(model, **kw):
    cfg, params, _ = model
    kw = {"num_slots": 3, "page_size": 4, "max_pages_per_seq": 16,
          "dtype": jnp.float32, "attention_impl": "ref", "prompt_bucket": 8,
          "prefill_chunk": 16, "decode_horizon": 4, **kw}
    return ServingEngine(params, cfg, **kw)


def prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def hold_to_reference(model, eng, prompt, rid, state=True):
    cfg, params, keys = model
    req = eng.lookup(rid)
    got = eng.recurrent_state(rid) if state else None
    # a slot that another request took since holds that request's log: the
    # reference then goes by its own selections (float32: the same ones)
    sels = got["moe_sel"] if state else np.zeros((2, 0, 4), np.int32)
    want = ref.check_generation(params, keys, prompt, req.generated, sels)
    assert max(want["gaps"]) < TOY_LOGIT_LIMIT, (rid, want["gaps"])
    if state:
        assert want["strays"] == 0 and want["pairs"] > 0
        assert max(ref.state_errors(list(got["ssm"]), want["states"])) \
            < TOY_STATE_LIMIT


def test_more_requests_than_slots_every_one_trails_the_reference(model):
    """Dense and chunked prefill (the state carried over three chunks),
    slots reused by later requests, a horizon that several requests leave
    at different steps."""
    cfg = model[0]
    eng = engine(model)
    assert eng.cache is None                  # no prefix cache: pages alone
    lens = [5, 37, 16, 9, 21, 33, 12]
    outs = [6, 9, 5, 12, 7, 4, 10]
    ps = prompts(cfg, lens)
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, outs)]
    done = eng.run()
    eng.check_invariants()
    for p, rid, n in zip(ps, rids, outs):
        assert len(done[rid].generated) == n
        # the last three requests still hold their slots' state
        hold_to_reference(model, eng, p, rid, state=rid in rids[-3:])
    st = eng.stats()
    assert st["moe_rows_dropped"] == 0
    assert st["ssm_slot_resets"] == len(lens)
    assert st["cache_hits"] == 0 and st["cached_prefix_tokens"] == 0
    assert st["moe_pairs_held"] > 0
    assert 0 < st["moe_experts_touched_decode"] \
        <= 8 * st["moe_expert_layer_calls_decode"]
    assert st["ssm_state_bytes"] > 0 and st["decode_state_bytes_moved"] > 0
    assert st["moe.load_max_over_mean"] >= 1.0
    assert {done[r].slot for r in rids} == {0, 1, 2}


def test_a_reused_slot_gives_the_tokens_of_a_fresh_engine(model):
    cfg = model[0]
    ps = prompts(cfg, [23, 11, 30, 19], seed=1)
    eng = engine(model, num_slots=1)
    rids = [eng.submit(p, max_new_tokens=9) for p in ps]
    done = eng.run()
    for p, rid in zip(ps, rids):
        fresh = engine(model, num_slots=1)
        r = fresh.submit(p, max_new_tokens=9)
        assert fresh.run()[r].generated == done[rid].generated


def test_a_preempted_request_recomputes_its_state(model):
    """A pool too small for both requests' growth: one is evicted, its
    slot's state rebuilt by the re-prefill of prompt + emitted tokens."""
    cfg = model[0]
    ps = prompts(cfg, [9, 10], seed=2)
    eng = engine(model, num_slots=2, num_pages=9, max_pages_per_seq=8)
    rids = [eng.submit(p, max_new_tokens=20) for p in ps]
    done = eng.run()
    eng.check_invariants()
    st = eng.stats()
    assert st["preemptions"] >= 1
    assert st["ssm_slot_resets"] == 2 + st["preemptions"]
    assert sum(done[r].preemptions for r in rids) == st["preemptions"]
    for p, rid in zip(ps, rids):
        assert len(done[rid].generated) == 20
        hold_to_reference(model, eng, p, rid, state=False)
    roomy = engine(model, num_slots=2)
    again = [roomy.submit(p, max_new_tokens=20) for p in ps]
    out = roomy.run()
    assert roomy.stats()["preemptions"] == 0
    assert [out[r].generated for r in again] \
        == [done[r].generated for r in rids]


@pytest.mark.parametrize("kw", [{"overlap": True}, {"decode_horizon": 1},
                                {"prefill_chunk": None}])
def test_the_same_tokens_whatever_the_loop(model, kw):
    cfg = model[0]
    ps = prompts(cfg, [14, 40, 7, 26, 18], seed=3)
    want_eng = engine(model)
    want = [want_eng.submit(p, max_new_tokens=8) for p in ps]
    want_done = want_eng.run()
    eng = engine(model, **kw)
    rids = [eng.submit(p, max_new_tokens=8) for p in ps]
    done = eng.run()
    eng.check_invariants()
    assert [done[r].generated for r in rids] \
        == [want_done[r].generated for r in want]
    assert eng.stats()["moe_rows_dropped"] == 0


def test_sampled_requests_ride_the_same_engine(model):
    cfg = model[0]
    eng = engine(model)
    ps = prompts(cfg, [12, 20], seed=4)
    rids = [eng.submit(ps[0], max_new_tokens=6, temperature=0.8, top_p=0.9),
            eng.submit(ps[1], max_new_tokens=6)]
    done = eng.run()
    assert all(len(done[r].generated) == 6 for r in rids)
    hold_to_reference(model, eng, ps[1], rids[1])


def test_compact_snapshot_restores_by_re_prefill(model):
    cfg = model[0]
    ps = prompts(cfg, [13, 22], seed=6)
    eng = engine(model)
    rids = [eng.submit(p, max_new_tokens=12) for p in ps]
    eng.step()
    eng.step()
    state = eng.snapshot("compact")
    other = engine(model)
    assert other.restore(state) == "reprefill"
    got = other.run()
    want = eng.run()
    assert [got[r].generated for r in rids] == [want[r].generated for r in rids]


@pytest.mark.parametrize("kw, what", [
    ({"speculative": 2}, "speculative"), ({"quantize": 8}, "quantize"),
    ({"kv_dtype": "int8"}, "kv_dtype"), ({"mesh": "two devices"}, "mesh")])
def test_the_constructor_refuses_and_names_what_is_missing(model, kw, what):
    if "mesh" in kw:
        kw = {"mesh": jax.sharding.Mesh(np.array(jax.devices()[:2]), ("mp",))}
    with pytest.raises(NotImplementedError, match=what):
        engine(model, **kw)


def test_page_only_transfers_are_refused(model):
    eng = engine(model)
    rid = eng.submit(prompts(model[0], [10])[0], max_new_tokens=8)
    eng.step()
    with pytest.raises(NotImplementedError, match="full_kv"):
        eng.snapshot("full_kv")
    with pytest.raises(NotImplementedError, match="export_kv"):
        eng.export_kv([rid])
    with pytest.raises(NotImplementedError, match="import_kv"):
        eng.import_kv({"version": 1})
    eng.run()


def test_spans_carry_the_family_and_whether_the_state_was_carried(model,
                                                                  tmp_path):
    from benchmark import host_spans
    eng = engine(model)
    warm = eng.submit(prompts(model[0], [40])[0], max_new_tokens=5)
    eng.run()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    rid = eng.submit(prompts(model[0], [40], seed=9)[0], max_new_tokens=5)
    short = eng.submit(prompts(model[0], [6], seed=9)[0], max_new_tokens=5)
    eng.run()
    jax.profiler.stop_trace()
    spans = host_spans.engine_line(host_spans.load(str(tmp_path)))
    chunks = sorted((s for s in spans if s[0] == "serve.prefill_chunk"),
                    key=lambda s: s[1])
    assert [int(s[3]["state_carried"]) for s in chunks] == [0, 1, 1]
    assert {s[3]["family"] for s in chunks} == {"nemotron_h"}
    assert any(s[0] == "serve.prefill_dense"
               and s[3]["family"] == "nemotron_h" for s in spans)
    assert any(s[0] == "serve.decode_dispatch"
               and s[3]["family"] == "nemotron_h" for s in spans)
    assert warm != rid != short


def test_the_llama_path_through_the_same_seam():
    """Greedy outputs of the Llama family equal a plain loop over the fns
    `build_llama_paged_decode` returns (the family the engine builds its
    executables from), and the page accounting holds."""
    from paddle_tpu.models.llama import (build_functional_llama,
                                         build_llama_paged_decode,
                                         llama_config_tiny)
    cfg = llama_config_tiny()
    params = build_functional_llama(cfg, key=jax.random.PRNGKey(0))[:3]
    eng = ServingEngine(params, cfg, num_slots=2, page_size=8,
                        max_pages_per_seq=8, attention_impl="ref",
                        prompt_bucket=8, decode_horizon=4)
    assert eng.family.name == "llama" and not eng.family.recurrent
    assert eng.recurrent_state(0) is None
    ps = prompts(cfg, [11, 19, 6], seed=7)
    rids = [eng.submit(p, max_new_tokens=7) for p in ps]
    done = eng.run()
    eng.check_invariants()
    assert "moe_rows_dropped" not in eng.stats()
    fam = build_llama_paged_decode(cfg, page_size=8, num_pages=16,
                                   attention_impl="ref")
    for p, rid in zip(ps, rids):
        row = jnp.arange(8, dtype=jnp.int32)
        ids = np.zeros((1, 24), np.int32)
        ids[0, :len(p)] = p
        logits, cache = fam.prefill(params, jnp.asarray(ids),
                                    jnp.int32(len(p)), row, 0,
                                    fam.init_cache())
        toks = [int(jnp.argmax(logits))]
        for i in range(6):
            logits, cache = fam.decode_step(
                params, jnp.asarray([toks[-1]], jnp.int32),
                jnp.asarray([len(p) + i], jnp.int32), row[None], cache,
                jnp.asarray([True]))
            toks.append(int(jnp.argmax(logits[0])))
        assert toks == done[rid].generated
