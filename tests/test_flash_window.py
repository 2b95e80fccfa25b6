"""Flash attention with a sliding window against a masked ``jax.numpy``
attention: forward and all three gradients, kernels interpreted on the CPU,
block sizes cut to 128 so that the band spans several blocks and whole
blocks are skipped (and their clamped index maps exercised)."""
import importlib
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest


fa = importlib.import_module("paddle_tpu.ops.pallas.flash_attention")


def masked_attention(q, k, v, window):
    """Plain f32 attention, [B, S, H, D], GQA by repeating K/V; query i sees
    keys max(0, i - window + 1) .. i (window None: all keys up to i)."""
    rep = q.shape[2] // k.shape[2]
    k, v = jnp.repeat(k, rep, 2), jnp.repeat(v, rep, 2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / math.sqrt(q.shape[-1])
    i = jnp.arange(q.shape[1])[:, None]
    j = jnp.arange(k.shape[1])[None, :]
    keep = j <= i
    if window is not None:
        keep &= (i - j) < window
    p = jax.nn.softmax(jnp.where(keep, s, -jnp.inf), -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def qkv(seq, hq=8, hkv=1, d=64, seed=0):
    ks = jax.random.split(jax.random.PRNGKey(seed), 4)
    shape = lambda h: (1, seq, h, d)
    return (jax.random.normal(ks[0], shape(hq), jnp.float32),
            jax.random.normal(ks[1], shape(hkv), jnp.float32),
            jax.random.normal(ks[2], shape(hkv), jnp.float32),
            jax.random.normal(ks[3], shape(hq), jnp.float32))


@pytest.fixture
def small_blocks(monkeypatch):
    monkeypatch.setattr(fa, "_block_sizes", lambda s_q, s_k, d: (128, 128))
    fa._make_op.cache_clear()
    yield
    fa._make_op.cache_clear()


# sequence lengths that are (512 = 4 x 128, 2 x 256) and are not (384 = 1.5
# x 256, 512 over 200) multiples of the window; 1 = only the diagonal; a
# window past the sequence = plain causal
@pytest.mark.parametrize("seq,window", [(512, 128), (512, 256), (384, 256),
                                        (512, 200), (256, 1), (256, 1000)])
def test_window_forward_and_gradients_match_masked_attention(small_blocks,
                                                             seq, window):
    q, k, v, g = qkv(seq)

    def loss(fn):
        return lambda q, k, v: (fn(q, k, v) * g).sum()

    kernel = lambda q, k, v: fa.flash_attention(q, k, v, causal=True,
                                                interpret=True, window=window)
    plain = lambda q, k, v: masked_attention(q, k, v, window)
    np.testing.assert_allclose(kernel(q, k, v), plain(q, k, v),
                               atol=2e-5, rtol=2e-5)
    got = jax.grad(loss(kernel), argnums=(0, 1, 2))(q, k, v)
    want = jax.grad(loss(plain), argnums=(0, 1, 2))(q, k, v)
    for a, b, name in zip(got, want, "qkv"):
        np.testing.assert_allclose(a, b, atol=1e-4, rtol=1e-4,
                                   err_msg=f"d{name}")
    np.testing.assert_allclose(
        fa.flash_attention_ref(q, k, v, causal=True, window=window),
        plain(q, k, v), atol=2e-5, rtol=2e-5)


def test_window_none_is_the_causal_kernel_and_a_window_changes_the_result(
        small_blocks):
    q, k, v, _ = qkv(512)
    causal = fa.flash_attention(q, k, v, causal=True, interpret=True)
    none = fa.flash_attention(q, k, v, causal=True, interpret=True,
                              window=None)
    np.testing.assert_array_equal(causal, none)
    np.testing.assert_allclose(causal, masked_attention(q, k, v, None),
                               atol=2e-5, rtol=2e-5)
    windowed = fa.flash_attention(q, k, v, causal=True, interpret=True,
                                  window=128)
    # the first 128 queries see the same keys either way, later ones do not
    np.testing.assert_allclose(windowed[:, :128], causal[:, :128],
                               atol=2e-5, rtol=2e-5)
    assert float(jnp.abs(windowed[:, 128:] - causal[:, 128:]).max()) > 1e-2
    # the causal jaxpr holds no trace of the window's arithmetic: the same
    # kernels as before this option existed
    text = str(jax.make_jaxpr(lambda q, k, v: fa.flash_attention(
        q, k, v, causal=True, interpret=True))(q, k, v))
    assert '"window"' not in text and "window" not in text


def test_window_labels_and_refusals(small_blocks):
    q, k, v, _ = qkv(256)
    assert fa._label("fwd", None) == {"kernel": "flash_attention",
                                      "pass": "fwd"}
    assert fa._label("dkv", 2048)["window"] == 2048
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, causal=False, window=128)
    with pytest.raises(ValueError):
        fa.flash_attention(q, k, v, causal=True, window=0)


@pytest.mark.parametrize("window,bq,bk,s", [(2048, 1024, 1024, 8192),
                                            (200, 128, 128, 512),
                                            (1, 128, 256, 512),
                                            (128, 256, 128, 1024)])
def test_blocks_that_run_are_exactly_those_that_touch_the_band(window, bq, bk,
                                                               s):
    """The skip predicate and the clamped index maps against the band
    itself, block by block, at the cell's sizes and at awkward ones."""
    nq, nk = s // bq, s // bk
    qi, kj = np.arange(s)[:, None], np.arange(s)[None, :]
    band = (kj <= qi) & (qi - kj < window)
    for i in range(nq):
        for j in range(nk):
            touches = band[i * bq:(i + 1) * bq, j * bk:(j + 1) * bk].any()
            assert bool(fa._block_runs(i, j, bq, bk, 0, window)) == touches
            cj = int(fa._clamp_k_block(i, j, bq, bk, nk, 0, window))
            ci = int(fa._clamp_q_block(i, j, bq, bk, nq, 0, window))
            if touches:
                assert (ci, cj) == (i, j)
            else:       # a skipped step names a block of its row that runs
                assert band[i * bq:(i + 1) * bq, cj * bk:(cj + 1) * bk].any()
                assert band[ci * bq:(ci + 1) * bq, j * bk:(j + 1) * bk].any()
