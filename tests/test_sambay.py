"""The SambaY family's model fns (`models/sambay.py`: Mamba-1 + window and
full differential attention + Gated Memory Units, ONE K/V page store that
several layers read) and the two forms of Mamba-1's recurrence
(`ops/ssm.py`): a chunk that is not its prompt's last, a dead slot, the
paired form of differential attention against four plain calls, the scan
against the one-token update, what the configuration refuses.  The engine's
side is `tests/test_sambay_serving.py`."""
import dataclasses
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmark import reference_sambay as ref
from paddle_tpu.models import sambay
from paddle_tpu.models.sambay import (SambaYConfig, build_functional_sambay,
                                      sambay_config_tiny)
from paddle_tpu.ops.ssm import selective_scan, selective_update

TOY_LIMIT = 1e-4            # float32 end to end: a rounding's worth


@pytest.fixture(scope="module")
def model():
    cfg = sambay_config_tiny()
    params = jax.jit(lambda k: build_functional_sambay(
        cfg, k, jnp.float32))(jax.random.PRNGKey(5))
    keys = {f.name: getattr(cfg, f.name) for f in dataclasses.fields(cfg)}
    return cfg, params, keys


def prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(1, cfg.vocab_size, (n,)).astype(np.int32)
            for n in lens]


def test_every_kind_of_layer_is_there(model):
    cfg = model[0]
    kinds = [k for k, _ in sambay.layer_kinds(cfg)]
    assert kinds == ["mamba", "window", "mamba", "window", "mamba", "full",
                     "gmu", "cross"]
    big = [k for k, _ in sambay.layer_kinds(SambaYConfig())]
    assert [big.count(k) for k in sambay.KINDS] == [9, 8, 1, 7, 7]
    assert big[16] == "mamba" and big[17] == "full" and big[31] == "cross"
    assert [k for k, _ in ref.layer_kinds(32)] == big


def test_a_chunk_that_is_not_the_last_returns_no_logits(model):
    """The chunk executable of a chunk that is not its prompt's last holds
    the first half of the layers only: no logits, no token, no attention
    over the store — and the last chunk's logits are the full forward's."""
    cfg, params, keys = model
    fam = cfg.paged_family(page_size=8, num_pages=32, num_slots=2,
                           max_pages_per_seq=16, attention_impl="ref")
    (p,) = prompts(cfg, [40], seed=3)
    cache = fam.init_cache()
    row = jnp.arange(16, dtype=jnp.int32)
    i32 = lambda v: jnp.asarray(v, jnp.int32)
    chunk = jax.jit(fam.prefill_chunk, static_argnames="last")
    logits, tok, cache = chunk(
        params, jnp.asarray(p[None, :32]), i32(0), i32(32), row, i32(1),
        cache, last=False)
    assert not np.asarray(logits).any() and int(tok) == 0
    ids = np.zeros((1, 8), np.int32)
    ids[0, :8] = p[32:]
    logits, tok, cache = chunk(
        params, jnp.asarray(ids), i32(32), i32(8), row, i32(1), cache,
        last=True)
    want = ref.logits_at(params, keys, ref.forward(params, keys, p)["hidden"],
                         [len(p) - 1])[0]
    np.testing.assert_allclose(np.asarray(logits), want, atol=TOY_LIMIT)
    got = fam.counters(cache)
    assert got["prefill_tokens_self_decoder"] == 40
    assert got["prefill_tokens_cross_decoder"] == 1
    text = chunk.lower(params, jnp.asarray(ids), i32(32), i32(8), row,
                       i32(1), cache, last=False).as_text()
    assert "gmu" not in text and "ssm.selective_scan" in text


def test_a_dead_slot_keeps_its_state_window_and_pages(model):
    cfg, params, _ = model
    fam = cfg.paged_family(page_size=8, num_pages=32, num_slots=2,
                           max_pages_per_seq=16, attention_impl="ref")
    cache = jax.tree_util.tree_map(
        lambda a: jax.random.normal(jax.random.PRNGKey(a.ndim), a.shape)
        .astype(a.dtype) if a.dtype != jnp.int32 else a, fam.init_cache())
    tables = jnp.arange(32, dtype=jnp.int32).reshape(2, 16)
    _, after = jax.jit(fam.decode_step)(
        params, jnp.asarray([5, 7], jnp.int32), jnp.asarray([20, 9], jnp.int32),
        tables, cache, jnp.asarray([True, False]))
    before, after = jax.device_get((cache, after))
    wp = cfg.sliding_window // 8
    for j in range(3):
        np.testing.assert_array_equal(after["ssm"][j][1], before["ssm"][j][1])
        assert (after["ssm"][j][0] != before["ssm"][j][0]).any()
    np.testing.assert_array_equal(after["conv"][:, 1], before["conv"][:, 1])
    np.testing.assert_array_equal(after["win_k"][:, :, wp:2 * wp],
                                  before["win_k"][:, :, wp:2 * wp])
    np.testing.assert_array_equal(after["k"][:, :, 16:32],
                                  before["k"][:, :, 16:32])
    assert (after["k"][:, :, 2] != before["k"][:, :, 2]).any()


def test_the_paired_form_equals_four_plain_attention_calls():
    """Differential attention as the Differential Transformer's flash form
    has it — softmax(q1 k1) v_even, softmax(q1 k1) v_odd, softmax(q2 k2)
    v_even, softmax(q2 k2) v_odd, four calls of plain GQA attention —
    against the paired form over 2 hd-wide values."""
    rng = np.random.default_rng(0)
    Q, Kn, nh, nkv, hd = 6, 11, 8, 4, 16
    q = jnp.asarray(rng.normal(size=(Q, nh, hd)), jnp.float32)
    k = jnp.asarray(rng.normal(size=(Kn, nkv, hd)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(Kn, nkv, hd)), jnp.float32)
    mask = jnp.asarray(np.arange(Kn)[None] <= np.arange(Q)[:, None] + 5)
    a1, a2 = sambay.diff_attention_pairs(q, k, v, mask, 1 / math.sqrt(hd))

    def plain(qs, ks, vs):          # [Q, 4, hd] x [Kn, 2, hd] -> [Q, 4, hd]
        ks, vs = jnp.repeat(ks, 2, 1), jnp.repeat(vs, 2, 1)
        s = jnp.einsum("qhd,khd->hqk", qs, ks) / math.sqrt(hd)
        p = jax.nn.softmax(jnp.where(mask[None], s, -jnp.inf), -1)
        return jnp.einsum("hqk,khd->qhd", p, vs)

    q1, q2, k1, k2 = q[:, 0::2], q[:, 1::2], k[:, 0::2], k[:, 1::2]
    v_even, v_odd = v[:, 0::2], v[:, 1::2]
    want1 = jnp.concatenate([plain(q1, k1, v_even), plain(q1, k1, v_odd)], -1)
    want2 = jnp.concatenate([plain(q2, k2, v_even), plain(q2, k2, v_odd)], -1)
    np.testing.assert_allclose(a1, want1, atol=1e-5)
    np.testing.assert_allclose(a2, want2, atol=1e-5)


@pytest.mark.parametrize("t", [1, 8, 21])
def test_the_scan_over_a_run_equals_the_update_a_token_at_a_time(t):
    rng = np.random.default_rng(t)
    d, n = 24, 8
    f = lambda *s: jnp.asarray(rng.normal(size=s), jnp.float32)
    u, b, c, s0 = f(t, d), f(t, n), f(t, n), f(n, d)
    dt = jax.nn.softplus(f(t, d)).at[t // 2].set(0.0)    # one padding token
    a = -jnp.exp(f(d, n))
    y, s = selective_scan(u, dt, a, b, c, s0)
    step, ys, before = s0[None], [], s0
    for i in range(t):
        yi, step = selective_update(step, u[i:i + 1], dt[i:i + 1], a,
                                    b[i:i + 1], c[i:i + 1])
        ys.append(yi[0])
        if i == t // 2:                 # dt 0: the state stays as it was
            np.testing.assert_array_equal(step[0], before)
        before = step[0]
    np.testing.assert_allclose(y, jnp.stack(ys), rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(s, step[0], rtol=1e-5, atol=1e-6)
    assert y.dtype == s.dtype == jnp.float32


@pytest.mark.parametrize("key,value", [
    ("num_hidden_layers", 10), ("num_hidden_layers", 4), ("mb_per_layer", 1),
    ("embd_pdrop", 0.1), ("resid_pdrop", 0.1), ("tie_word_embeddings", False),
    ("mlp_bias", True), ("lm_head_bias", True), ("hidden_act", "gelu"),
    ("num_key_value_heads", 3), ("sliding_window", 0)])
def test_validate_refuses_what_the_path_lacks(key, value):
    cfg = sambay_config_tiny(**{key: value})
    with pytest.raises(ValueError):
        cfg.validate()


def test_the_published_keys_are_the_defaults():
    cfg = SambaYConfig()
    cfg.validate()
    assert (cfg.hidden_size, cfg.num_attention_heads, cfg.head_dim,
            cfg.num_key_value_heads, cfg.intermediate_size, cfg.vocab_size,
            cfg.sliding_window, cfg.d_inner, cfg.mamba_d_state, cfg.dt_rank,
            cfg.num_hidden_layers) == (2560, 40, 64, 20, 10240, 200064, 512,
                                       5120, 16, 160, 32)
    assert abs(sambay.lambda_init(17) - (0.8 - 0.6 * math.exp(-5.1))) < 1e-12


def test_the_kernel_calls_carry_their_kind():
    """The ragged kernel takes the KIND of layer it serves as a second label
    (its metadata is compiled text: `tests/test_chip_compile.py` reads it
    there); None leaves the call as it was."""
    import inspect
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    assert inspect.signature(ragged_paged_attention).parameters[
        "kind"].default is None
    q = jnp.zeros((2, 1, 4, 128))
    pool = jnp.zeros((1, 2, 4, 8, 128))
    z = jnp.ones((2,), jnp.int32)
    text = jax.jit(lambda: ragged_paged_attention(
        q, pool, pool, jnp.zeros((2, 2), jnp.int32), z, z, z,
        layer=jnp.int32(0), role="decode", kind="cross",
        interpret=True)).lower().as_text(debug_info=True)
    assert "ragged_paged_attention" in text
