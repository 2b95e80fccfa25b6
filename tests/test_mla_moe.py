"""The latent-attention + sparse-expert family (`models/mla_moe.py`) and its
one page store through `ServingEngine`, every request held to
`benchmark/reference_mla_moe.py`'s full forward pass (the EXPANDED form, no
cache): dense and chunked prefill, decode through the latent pages,
preemption, the prefix cache; the absorbed products against the expanded
ones; the expert shares; the latent kernel against its plain form; what the
configuration and the engine refuse."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_mla_moe as ref
from paddle_tpu.incubate.distributed.models.moe import dropless
from paddle_tpu.inference.paged import ServingEngine
from paddle_tpu.models import mla_moe
from paddle_tpu.models.mla_moe import (MlaMoeConfig, build_functional_mla_moe,
                                       mla_moe_config_tiny)
from paddle_tpu.ops.pallas.paged_attention import (mla_paged_attention,
                                                   mla_paged_attention_ref)

TOY_LIMIT = 1e-4            # float32 end to end: a rounding's worth


def keys_of(cfg):
    keys = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    keys["expert_offset"] = cfg.held()[0]
    return keys


@pytest.fixture(scope="module")
def model():
    cfg = mla_moe_config_tiny(experts_held=(4, 8))
    params = jax.jit(lambda k: build_functional_mla_moe(
        cfg, k, jnp.float32))(jax.random.PRNGKey(5))
    return cfg, params, keys_of(cfg)


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


def hold_to_reference(model, eng, prompt, rid, logs=True, given_prefix=None):
    """The request's tokens under the reference's logits; with ``logs`` (the
    request still holds its slot) its selection log and its greedy
    log-probabilities too.  ``given_prefix``: the selections of a cached
    prefix, which another slot logged."""
    cfg, params, keys = model
    req = eng.lookup(rid)
    consumed = len(prompt) + len(req.generated) - 1
    if logs:
        got = eng.recurrent_state(rid)
        sels, logp = got["moe_sel"], got["logp"]
        if given_prefix is not None:
            n = given_prefix.shape[1]
            sels = np.concatenate([given_prefix, sels[:, n:]], axis=1)
    else:
        # float32: the reference's own selections are the engine's
        sels = np.zeros((2, 0, cfg.num_experts_per_tok), np.int32)
        logp = np.zeros((consumed,), np.float32)
    want = ref.check_generation(params, keys, prompt, req.generated, sels,
                                logp)
    assert max(want["gaps"]) < TOY_LIMIT, (rid, want["gaps"])
    if logs:
        assert want["strays"] == 0 and want["pairs"] > 0
        assert np.abs(want["logp_err"]).max() < TOY_LIMIT, want["logp_err"]
    return eng.recurrent_state(rid) if logs else None


@pytest.mark.parametrize("chunk", [None, 16], ids=["dense", "chunked"])
def test_more_requests_than_slots_every_one_trails_the_reference(model,
                                                                 chunk):
    """Dense prefill, or prefill over up to three chunks with the prefix read
    back from the latent pages; slots reused; decode through the latent
    cache; a horizon that requests leave at different steps."""
    cfg = model[0]
    eng = engine(model, prefill_chunk=chunk, prefix_cache=False)
    lens, outs = [5, 37, 16, 9, 21, 33, 12], [6, 9, 5, 12, 7, 4, 10]
    ps = prompts(cfg, lens)
    rids = [eng.submit(p, max_new_tokens=n) for p, n in zip(ps, outs)]
    done = eng.run()
    eng.check_invariants()
    # the last request each slot served still holds its logs
    last = {done[r].slot: r for r in sorted(
        rids, key=lambda r: done[r].admit_time)}
    assert sorted(last) == [0, 1, 2]
    for p, rid, n in zip(ps, rids, outs):
        assert len(done[rid].generated) == n
        hold_to_reference(model, eng, p, rid, logs=rid in last.values())
    st = eng.stats()
    assert st["moe_rows_dropped"] == 0 and st["moe_pairs_held"] > 0
    assert 0 < st["moe_experts_touched_decode"] \
        <= 8 * st["moe_expert_layer_calls_decode"]
    assert st["moe_experts_held"] == 8
    # every consumed token wrote one row a layer, and read what stood there
    consumed = sum(t + n - 1 for t, n in zip(lens, outs))
    assert st["latent_rows_written"] == consumed
    assert st["latent_tokens_attended_decode"] \
        == st["decode_kv_tokens_attended"] \
        == sum(sum(range(t + 1, t + n)) for t, n in zip(lens, outs))
    assert st["latent_pairs_attended_prefill"] \
        == sum(t * (t + 1) // 2 for t in lens)
    assert st["latent_bytes_per_token"] == 3 * (32 + 8) * 4
    assert eng.page_bytes == 3 * 4 * 128 * 4       # rows stored lane-wide


def test_a_preempted_request_is_prefilled_again_and_trails_the_reference(
        model):
    cfg = model[0]
    ps = prompts(cfg, [9, 10], seed=2)
    eng = engine(model, num_slots=2, num_pages=9, max_pages_per_seq=8)
    rids = [eng.submit(p, max_new_tokens=20) for p in ps]
    done = eng.run()
    eng.check_invariants()
    assert eng.stats()["preemptions"] >= 1
    for p, rid in zip(ps, rids):
        assert len(done[rid].generated) == 20
        hold_to_reference(model, eng, p, rid, logs=False)
    roomy = engine(model, num_slots=2)
    again = [roomy.submit(p, max_new_tokens=20) for p in ps]
    out = roomy.run()
    assert [out[r].generated for r in again] \
        == [done[r].generated for r in rids]


def test_a_prefix_hit_attaches_latent_pages_and_trails_the_reference(model):
    """Every layer's state is pages, so a cached prefix is complete: the
    second prompt prefills its suffix alone over the first one's pages."""
    cfg = model[0]
    first, tail = prompts(cfg, [37, 14], seed=3)
    second = np.concatenate([first[:24], tail])
    eng = engine(model, num_slots=2)
    assert eng.cache is not None
    r1 = eng.submit(first, max_new_tokens=6)
    eng.run()
    log1 = hold_to_reference(model, eng, first, r1)
    before = eng.stats()
    r2 = eng.submit(second, max_new_tokens=8)
    eng.run()
    eng.check_invariants()
    st = eng.stats()
    assert st["cache_hits"] - before["cache_hits"] == 1
    assert st["cached_prefix_tokens"] - before["cached_prefix_tokens"] == 24
    assert st["latent_rows_written"] - before["latent_rows_written"] \
        == len(second) - 24 + 7
    hold_to_reference(model, eng, second, r2,
                      given_prefix=log1["moe_sel"][:, :24])
    cold = engine(model, num_slots=2, prefix_cache=False)
    r = cold.submit(second, max_new_tokens=8)
    assert cold.run()[r].generated == eng.lookup(r2).generated


def test_the_kernel_engine_gives_the_plain_engines_tokens(model):
    cfg = model[0]
    ps = prompts(cfg, [19, 33, 7], seed=4)
    outs = []
    for kw in ({}, {"attention_impl": "pallas", "interpret": True}):
        eng = engine(model, **kw)
        rids = [eng.submit(p, max_new_tokens=6) for p in ps]
        done = eng.run()
        outs.append([done[r].generated for r in rids])
    assert outs[0] == outs[1]


def test_the_absorbed_products_equal_the_expanded_attention(model):
    """`mla_project`'s absorbed query over the rows [c | r], and the value
    side's W_kvb after the sum, against the reference's per-head K and V."""
    cfg, params, keys = model
    lp = {leaf: per[1] for leaf, per in params[1]["attn"].items()}
    x = jax.random.normal(jax.random.PRNGKey(0), (21, cfg.hidden_size))
    nh, dn, dv, dl = cfg.num_attention_heads, cfg.qk_nope_head_dim, \
        cfg.v_head_dim, cfg.kv_lora_rank
    with jax.default_matmul_precision("highest"):
        q, row = mla_moe.mla_project(
            cfg, lp, mla_moe._rms(x, lp["norm"], cfg.rms_norm_eps),
            jnp.arange(21))
        s = jnp.einsum("qhd,kd->hqk", q, row) / np.sqrt(
            dn + cfg.qk_rope_head_dim)
        s = jnp.where(jnp.tril(jnp.ones((21, 21), bool))[None], s, -jnp.inf)
        o_lat = jnp.einsum("hqk,kl->qhl", jax.nn.softmax(s, -1), row[:, :dl])
        w_uv = lp["w_kvb"].reshape(dl, nh, dn + dv)[:, :, dn:]
        got = x + jnp.einsum("qhl,lhv->qhv", o_lat, w_uv).reshape(
            21, -1) @ lp["wo"]
        want = ref._attention(
            x, {k: v for k, v in lp.items() if k != "post_norm"}, heads=nh,
            dn=dn, dr=cfg.qk_rope_head_dim, dv=dv, dl=dl,
            eps=cfg.rms_norm_eps, theta=cfg.rope_theta, drop_rope_key=False,
            drop_kv_norm=False, scale=1 / np.sqrt(dn + cfg.qk_rope_head_dim),
            ct=jnp.float32)
    np.testing.assert_allclose(got, want, atol=2e-5)


def test_the_four_shares_add_up_to_the_uncut_layer_of_the_reference():
    """Four ranks of 4 experts each, the shared experts counted once, give
    the reference's layer over all 16 experts."""
    cfg = mla_moe_config_tiny()
    params = build_functional_mla_moe(cfg, jax.random.PRNGKey(2))
    lp = {leaf: per[0] for leaf, per in params[1]["moe"].items()}
    post = params[1]["attn"]["post_norm"][1]
    x = jax.random.normal(jax.random.PRNGKey(1), (19, cfg.hidden_size))
    valid = jnp.ones((19,), bool)
    g = mla_moe._rms(x, post, cfg.rms_norm_eps)
    with jax.default_matmul_precision("highest"):
        want, _ = ref.moe_layer(x, lp, post, keys_of(cfg))
        total, shared = 0, None
        for rank in range(4):
            c = dataclasses.replace(cfg, experts_held=(4 * rank, 4))
            mine = {**lp, **{k: lp[k][4 * rank:4 * rank + 4]
                             for k in ("we_gate", "we_up", "we_down")}}
            out, rows, beyond, _ = mla_moe.moe_layer(c, mine, g, valid)
            shared = mla_moe._swiglu(g, lp["ws_gate"], lp["ws_up"],
                                     lp["ws_down"])
            total = total + out - shared
            assert int(beyond) == 0 and int(rows.sum()) > 0
    np.testing.assert_allclose(x + total + shared, want, atol=2e-5)


@pytest.mark.parametrize("queries,group", [(1, None), (1, 1), (5, 2),
                                           (24, 4)],
                         ids=["decode", "decode_g1", "few", "segment"])
def test_the_latent_kernel_equals_its_plain_form(queries, group):
    """Ragged lengths, a slot with nothing, dead table columns holding
    ids past the pool (never read), the layer traced."""
    rng = np.random.default_rng(queries)
    slots, heads, dl, dr, ps, pages, table = 5, 4, 128, 128, 8, 20, 7
    q = jnp.asarray(rng.normal(size=(slots, queries, heads, dl + dr)),
                    jnp.float32)
    pool = jnp.asarray(rng.normal(size=(3, 1, pages, ps, dl + dr)),
                       jnp.float32)
    tab = rng.integers(0, pages, (slots, table)).astype(np.int32)
    kv_len = rng.integers(1, table * ps + 1, (slots,)).astype(np.int32)
    kv_len[0] = 0
    q_len = np.minimum(rng.integers(0, queries + 1, (slots,)),
                       kv_len).astype(np.int32)
    q_len[1] = min(queries, kv_len[1])
    q_start = kv_len - q_len
    dead = tab.copy()
    for s in range(slots):
        dead[s, -(-int(kv_len[s]) // ps):] = 10 ** 6
    args = [jnp.asarray(a) for a in (q_start, q_len, kv_len)]
    kw = dict(dv=dl, sm_scale=0.09)
    for layer in (0, 2):
        want = mla_paged_attention_ref(q, pool, jnp.asarray(tab), *args,
                                       layer=layer, **kw)
        got = jax.jit(lambda ly: mla_paged_attention(
            q, pool, jnp.asarray(dead), *args, layer=ly, role="decode",
            interpret=True, _group=group, **kw))(jnp.int32(layer))
        np.testing.assert_allclose(got, want, atol=2e-5)
        assert not np.asarray(got[0]).any()        # a slot with no query
        for s in range(slots):
            assert not np.asarray(got[s, q_len[s]:]).any()


def test_the_latent_kernel_carries_its_label():
    q = jnp.zeros((2, 1, 4, 128))
    pool = jnp.zeros((1, 1, 4, 8, 128))
    z = jnp.zeros((2,), jnp.int32)
    text = jax.jit(lambda: mla_paged_attention(
        q, pool, jnp.zeros((2, 2), jnp.int32), z, z, z, dv=64, sm_scale=1.0,
        layer=jnp.int32(0), role="decode", interpret=True)).lower().as_text(
            debug_info=True)
    assert "mla_paged_attention" in text


@pytest.mark.parametrize("key,value", [
    ("q_lora_rank", 1536), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("n_group", 8), ("topk_group", 4), ("scoring_func", "softmax"),
    ("topk_method", "greedy"), ("num_nextn_predict_layers", 1),
    ("hidden_act", "gelu"), ("attention_bias", True),
    ("tie_word_embeddings", True), ("moe_layer_freq", 2),
    ("num_key_value_heads", 2), ("experts_held", (12, 8)),
    ("first_k_dense_replace", 9)])
def test_validate_refuses_what_the_path_lacks(key, value):
    cfg = mla_moe_config_tiny(**{key: value})
    with pytest.raises(ValueError, match=key.split("_")[0]):
        cfg.validate()
    with pytest.raises(ValueError):
        build_functional_mla_moe(cfg)


def test_the_published_keys_are_the_defaults():
    cfg = MlaMoeConfig()
    cfg.validate()
    assert (cfg.kv_lora_rank, cfg.qk_rope_head_dim, cfg.latent_row,
            cfg.n_routed_experts, cfg.num_experts_per_tok,
            cfg.routed_scaling_factor) == (512, 64, 576, 64, 6, 2.446)


@pytest.mark.parametrize("what", ["speculative", "quantize", "kv_dtype",
                                  "mesh"])
def test_the_engine_refuses_by_name_what_the_family_lacks(model, what):
    from paddle_tpu.distributed.topology import build_mesh
    kw = {"speculative": 2, "quantize": 8, "kv_dtype": "int8",
          "mesh": build_mesh({"mp": 2}, devices=jax.devices()[:2])}[what]
    with pytest.raises(NotImplementedError, match=what):
        engine(model, **{what: kw})


def test_snapshot_and_page_transfers_walk_the_latent_store(model):
    """`full_kv` snapshots, export / import and copy-on-write name the
    family's page leaves, not K and V."""
    cfg = model[0]
    ps = prompts(cfg, [21, 13], seed=6)
    eng = engine(model, num_slots=2)
    rids = [eng.submit(p, max_new_tokens=12) for p in ps]
    for _ in range(4):
        eng.step()
    state = eng.snapshot("full_kv")
    assert "kv_latent" in state and "kv_k" not in state
    fresh = engine(model, num_slots=2)
    assert fresh.restore(state) == "full_kv"
    got, want = fresh.run(), eng.run()
    fresh.check_invariants()
    assert [got[r].generated for r in rids] \
        == [want[r].generated for r in rids]


def test_the_swiglu_expert_takes_the_kernel_at_serving_shapes_only():
    """`grouped_swiglu` as the train step calls it is three `ragged_dot`s;
    with the caller's platform test passed, few rows a group and lane-aligned
    widths take `grouped_matmul` (the same numbers), many rows do not."""
    rng = np.random.default_rng(0)
    held, h, f = 4, 128, 256
    mats = [jnp.asarray(rng.normal(size=s) / 12, jnp.float32)
            for s in ((held, h, f), (held, h, f), (held, f, h))]
    rows = jnp.asarray([3, 0, 40, 9], jnp.int32)
    xs = jnp.asarray(rng.normal(size=(64, h)), jnp.float32)
    calls = lambda fn, x: str(jax.make_jaxpr(fn)(x)).count("pallas_call")
    plain = lambda x: dropless.grouped_swiglu(x, *mats, rows)
    served = lambda x: dropless.grouped_swiglu(x, *mats, rows, kernel=True,
                                               interpret=True, role="decode")
    # the gate's and the up's jitted calls agree in shapes: one function
    assert calls(plain, xs) == 0 and calls(served, xs) == 2
    n = int(rows.sum())
    np.testing.assert_allclose(served(xs)[:n], plain(xs)[:n], atol=1e-4)
    many = jnp.zeros((held * 512, h), jnp.float32)
    assert calls(lambda x: dropless.grouped_swiglu(
        x, *mats, rows, kernel=True, interpret=True), many) == 0


@pytest.mark.parametrize("family", ["llama", "nemotron_h", "mla_moe"])
def test_every_family_names_its_page_stores(family):
    """`PagedFamily.page_leaves`: what the engine's page copies, byte
    counts and transfers walk — K and V for the two K/V families (as it
    always was), one latent store here; every named leaf's page axis is
    axis 2."""
    from paddle_tpu.models.llama import llama_config_tiny
    from paddle_tpu.models.nemotron_h import nemotron_h_config_tiny
    cfg, leaves = {
        "llama": (llama_config_tiny(), ("k", "v")),
        "nemotron_h": (nemotron_h_config_tiny(), ("k", "v")),
        "mla_moe": (mla_moe_config_tiny(), ("latent",))}[family]
    fam = cfg.paged_family(page_size=4, num_pages=6, num_slots=2,
                           attention_impl="ref")
    assert fam.name == family and fam.page_leaves == leaves
    assert fam.int8_weights == (family == "llama")
    cache = jax.eval_shape(fam.init_cache)
    for name in leaves:
        assert cache[name].shape[2:4] == (7, 4), (name, cache[name].shape)


def test_one_chunk_executable_a_padded_length_whatever_the_context(model):
    """The family hands every chunk the whole page table (its kernel walks
    live pages only), so prompts of many lengths share the chunk executables
    of their padded chunk lengths: 8 and 16 here, where a table slice rounded
    to 4 pages would make one for every 4 pages of context."""
    cfg = model[0]
    eng = engine(model, num_slots=2, prefix_cache=False)
    for p in prompts(cfg, [17, 25, 33, 41, 49, 57], seed=7):
        eng.submit(p, max_new_tokens=2)
    eng.run()
    assert eng.family.chunk_table_granule == 0
    assert eng.jit_variants()["prefill_chunk"] == 2
