"""Pallas kernel tests — run in interpreter mode on the CPU mesh (the kernels
themselves are TPU-targeted; interpret=True validates the math)."""
import functools
import math
import numpy as np
import pytest
import jax
import jax.numpy as jnp

from paddle_tpu.ops.pallas.flash_attention import flash_attention

rng = np.random.default_rng(7)


def _ref_sdpa(q, k, v, causal):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        s = jnp.where(jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq), s, -jnp.inf)
    return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(s, -1), v)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("shape", [(1, 128, 1, 64), (2, 256, 2, 64)])
def test_flash_attention_forward(causal, shape):
    B, S, H, D = shape
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
               for _ in range(3))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    ref = _ref_sdpa(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_grads(causal):
    B, S, H, D = 1, 256, 2, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
               for _ in range(3))

    def loss_fa(q, k, v):
        return (flash_attention(q, k, v, causal=causal, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_sdpa(q, k, v, causal) ** 2).sum()

    g = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    gr = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g, gr):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=1e-3, atol=1e-4)


def test_flash_attention_cross_lengths():
    # decoder cross-attention: s_q != s_k
    B, H, D = 1, 2, 64
    q = jnp.asarray(rng.standard_normal((B, 128, H, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, 256, H, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, 256, H, D)).astype(np.float32))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    ref = _ref_sdpa(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref), rtol=2e-5, atol=2e-5)


def test_unsupported_shape_returns_none():
    q = jnp.zeros((1, 197, 1, 64))  # short untileable S: XLA path wins
    assert flash_attention(q, q, q) is None
    q = jnp.zeros((1, 128, 1, 300))  # head_dim > 256
    assert flash_attention(q, q, q) is None


def test_sdpa_dispatch_uses_registry():
    """When the pallas kernel is registered, F.scaled_dot_product_attention
    routes through it; on CPU (unregistered) the default runs — either way
    the answer matches the reference."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    B, S, H, D = 1, 128, 2, 32
    q = rng.standard_normal((B, S, H, D)).astype(np.float32)
    out = F.scaled_dot_product_attention(paddle.to_tensor(q), paddle.to_tensor(q),
                                         paddle.to_tensor(q), is_causal=True)
    ref = _ref_sdpa(jnp.asarray(q), jnp.asarray(q), jnp.asarray(q), True)
    np.testing.assert_allclose(out.numpy(), np.asarray(ref), rtol=1e-4, atol=1e-5)


# ---------------------------------------------------------------------------
# GQA (native KV-head indexing, VERDICT r2 item #5)
# ---------------------------------------------------------------------------
def _ref_sdpa_gqa(q, k, v, causal):
    rep = q.shape[2] // k.shape[2]
    if rep > 1:
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    return _ref_sdpa(q, k, v, causal)


@pytest.mark.parametrize("causal", [False, True])
@pytest.mark.parametrize("hq,hkv", [(4, 2), (4, 1)])
def test_flash_attention_gqa_forward(causal, hq, hkv):
    B, S, D = 2, 128, 64
    q = jnp.asarray(rng.standard_normal((B, S, hq, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, hkv, D)).astype(np.float32))
    out = flash_attention(q, k, v, causal=causal, interpret=True)
    assert out is not None, "GQA shape must be kernel-supported"
    ref = _ref_sdpa_gqa(q, k, v, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_gqa_grads(causal):
    B, S, hq, hkv, D = 1, 128, 4, 2, 64
    q = jnp.asarray(rng.standard_normal((B, S, hq, D)).astype(np.float32))
    k = jnp.asarray(rng.standard_normal((B, S, hkv, D)).astype(np.float32))
    v = jnp.asarray(rng.standard_normal((B, S, hkv, D)).astype(np.float32))

    def loss_fa(q, k, v):
        return (flash_attention(q, k, v, causal=causal, interpret=True) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_sdpa_gqa(q, k, v, causal) ** 2).sum()

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        assert a.shape == b.shape  # dk/dv stay at the KV head count
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Fused rms_norm kernel
# ---------------------------------------------------------------------------
def test_fused_rms_norm_forward_and_grads():
    from paddle_tpu.ops.pallas.fused import rms_norm
    N, H = 32, 256
    eps = 1e-5
    x = jnp.asarray(rng.standard_normal((N, H)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((H,)).astype(np.float32))

    def ref(x, w):
        ms = jnp.mean(jnp.square(x.astype(jnp.float32)), -1, keepdims=True)
        return (x * jax.lax.rsqrt(ms + eps)) * w

    out = rms_norm(x, w, eps=eps, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, w)),
                               rtol=2e-5, atol=2e-5)

    g_k = jax.grad(lambda x, w: (rms_norm(x, w, eps=eps, interpret=True) ** 2).sum(),
                   argnums=(0, 1))(x, w)
    g_r = jax.grad(lambda x, w: (ref(x, w) ** 2).sum(), argnums=(0, 1))(x, w)
    for a, b in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


def test_fused_rms_norm_untileable_returns_none():
    from paddle_tpu.ops.pallas.fused import rms_norm
    assert rms_norm(jnp.zeros((4, 100)), jnp.zeros((100,)), interpret=True) is None


# ---------------------------------------------------------------------------
# Fused AdamW kernel
# ---------------------------------------------------------------------------
def test_fused_adamw_matches_reference():
    from paddle_tpu.ops.pallas.fused import adamw_update, adamw_update_ref
    n = 4 * 4096
    p = jnp.asarray(rng.standard_normal(n).astype(np.float32)).reshape(16, 1024)
    g = jnp.asarray(rng.standard_normal(n).astype(np.float32)).reshape(16, 1024)
    m = jnp.zeros((16, 1024), jnp.float32)
    v = jnp.zeros((16, 1024), jnp.float32)
    lr, b1, b2, eps, wd, t = 1e-3, 0.9, 0.999, 1e-8, 0.01, 1

    res = adamw_update(p, g, m, v, lr=lr, beta1=b1, beta2=b2, eps=eps,
                       weight_decay=wd, step=t, interpret=True)
    assert res is not None
    np_, nm, nv = res

    p_ref, m_ref, v_ref = adamw_update_ref(
        p, g, m, v, lr=lr, beta1=b1, beta2=b2, eps=eps, weight_decay=wd,
        step=t)
    np.testing.assert_allclose(np.asarray(np_), np.asarray(p_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nm), np.asarray(m_ref), rtol=1e-6, atol=1e-6)
    np.testing.assert_allclose(np.asarray(nv), np.asarray(v_ref), rtol=1e-6, atol=1e-6)


def test_fused_adamw_bf16_param_fp32_state():
    from paddle_tpu.ops.pallas.fused import adamw_update
    p = jnp.asarray(rng.standard_normal(8192).astype(np.float32)).astype(jnp.bfloat16)
    g = jnp.asarray(rng.standard_normal(8192).astype(np.float32)).astype(jnp.bfloat16)
    m = jnp.zeros((8192,), jnp.float32)
    v = jnp.zeros((8192,), jnp.float32)
    res = adamw_update(p, g, m, v, lr=1e-3, step=3, interpret=True)
    assert res is not None
    np_, nm, nv = res
    assert np_.dtype == jnp.bfloat16 and nm.dtype == jnp.float32
    assert bool(jnp.all(jnp.isfinite(nm)))


# ---------------------------------------------------------------------------
# Varlen / segment-ids (VERDICT r2 item #5 remainder)
# ---------------------------------------------------------------------------
def _ref_sdpa_segments(q, k, v, seg, causal):
    d = q.shape[-1]
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k) / np.sqrt(d)
    mask = seg[:, None, :, None] == seg[:, None, None, :]
    if causal:
        sq = s.shape[-2]
        mask = mask & jnp.tril(jnp.ones((sq, sq), bool))[None, None]
    s = jnp.where(mask, s, -jnp.inf)
    p = jax.nn.softmax(s, -1)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_segment_ids_forward(causal):
    B, S, H, D = 2, 256, 2, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
               for _ in range(3))
    # two packed sequences per row: [0]*100 + [1]*156 (crosses block bounds)
    seg = jnp.asarray(np.concatenate([np.zeros(100), np.ones(156)])[None]
                      .repeat(B, 0).astype(np.int32))
    out = flash_attention(q, k, v, causal=causal, interpret=True,
                          segment_ids=seg)
    assert out is not None
    ref = _ref_sdpa_segments(q, k, v, seg, causal)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow   # 6s/pair grad compiles; forward segment-id parity stays tier-1
@pytest.mark.parametrize("causal", [False, True])
def test_flash_attention_segment_ids_grads(causal):
    B, S, H, D = 1, 128, 2, 64
    q, k, v = (jnp.asarray(rng.standard_normal((B, S, H, D)).astype(np.float32))
               for _ in range(3))
    seg = jnp.asarray(np.concatenate([np.zeros(48), np.ones(80)])[None]
                      .astype(np.int32))

    def loss_fa(q, k, v):
        return (flash_attention(q, k, v, causal=causal, interpret=True,
                                segment_ids=seg) ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_sdpa_segments(q, k, v, seg, causal) ** 2).sum()

    g_fa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    g_ref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(g_fa, g_ref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=3e-4, atol=3e-4)


def test_flash_attn_unpadded_matches_per_sequence():
    """Packed varlen == attending each sequence separately."""
    import paddle_tpu as paddle
    import paddle_tpu.nn.functional as F
    H, D = 2, 32
    lens = [5, 9, 4]
    total = sum(lens)
    qkv = rng.standard_normal((3, total, H, D)).astype(np.float32)
    cu = np.cumsum([0] + lens).astype(np.int32)
    out, _ = F.flash_attn_unpadded(
        paddle.to_tensor(qkv[0]), paddle.to_tensor(qkv[1]),
        paddle.to_tensor(qkv[2]), paddle.to_tensor(cu), paddle.to_tensor(cu),
        max(lens), max(lens), causal=True)
    out = np.asarray(out.numpy())
    for i in range(len(lens)):
        lo, hi = cu[i], cu[i + 1]
        ref = _ref_sdpa(jnp.asarray(qkv[0][None, lo:hi]),
                        jnp.asarray(qkv[1][None, lo:hi]),
                        jnp.asarray(qkv[2][None, lo:hi]), True)
        np.testing.assert_allclose(out[lo:hi], np.asarray(ref)[0],
                                   rtol=2e-4, atol=2e-5)


# ---------------------------------------------------------------------------
# Fused softmax / layer_norm kernels (SURVEY §2.1 north star completion)
# ---------------------------------------------------------------------------
def test_fused_softmax_forward_and_grads():
    from paddle_tpu.ops.pallas.fused import softmax as psoftmax
    x = jnp.asarray(rng.standard_normal((16, 256)).astype(np.float32))
    out = psoftmax(x, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(jax.nn.softmax(x, -1)),
                               rtol=1e-5, atol=1e-6)
    g_k = jax.grad(lambda v: (psoftmax(v, interpret=True) ** 2).sum())(x)
    g_r = jax.grad(lambda v: (jax.nn.softmax(v, -1) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g_k), np.asarray(g_r),
                               rtol=1e-4, atol=1e-5)


def test_fused_softmax_untileable_returns_none():
    from paddle_tpu.ops.pallas.fused import softmax as psoftmax
    assert psoftmax(jnp.zeros((4, 100)), interpret=True) is None
    assert psoftmax(jnp.zeros((128,)), interpret=True) is None


def test_fused_layer_norm_forward_and_grads():
    from paddle_tpu.ops.pallas.fused import layer_norm as pln
    N, H = 16, 128
    eps = 1e-5
    x = jnp.asarray(rng.standard_normal((N, H)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((H,)).astype(np.float32))
    b = jnp.asarray(rng.standard_normal((H,)).astype(np.float32))

    def ref(x, w, b):
        mu = jnp.mean(x, -1, keepdims=True)
        var = jnp.var(x, -1, keepdims=True)
        return (x - mu) * jax.lax.rsqrt(var + eps) * w + b

    out = pln(x, w, b, eps=eps, interpret=True)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref(x, w, b)),
                               rtol=1e-5, atol=1e-5)
    g_k = jax.grad(lambda x, w, b: (pln(x, w, b, eps=eps, interpret=True) ** 2).sum(),
                   argnums=(0, 1, 2))(x, w, b)
    g_r = jax.grad(lambda x, w, b: (ref(x, w, b) ** 2).sum(),
                   argnums=(0, 1, 2))(x, w, b)
    for a, bb in zip(g_k, g_r):
        np.testing.assert_allclose(np.asarray(a), np.asarray(bb),
                                   rtol=2e-4, atol=2e-4)


def test_fused_linear_cross_entropy_matches_dense():
    """Round-4 chunked-CE head op: values and grads match the materialized
    log_softmax head (incubate.nn.functional.fused_linear_cross_entropy)."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.incubate.nn.functional import \
        fused_linear_cross_entropy_impl

    rng = np.random.default_rng(5)
    T, H, V = 48, 16, 64
    x = jnp.asarray(rng.normal(0, 1, (T, H)).astype(np.float32))
    W = jnp.asarray(rng.normal(0, 0.2, (H, V)).astype(np.float32))
    lab = jnp.asarray(rng.integers(0, V, (T,)).astype(np.int32))

    def dense(x, W):
        logp = jax.nn.log_softmax((x @ W).astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, lab[:, None], -1))

    def chunked(x, W):
        return jnp.mean(fused_linear_cross_entropy_impl(x, W, lab, n_chunks=8))

    np.testing.assert_allclose(np.asarray(chunked(x, W)),
                               np.asarray(dense(x, W)), rtol=1e-5)
    gd = jax.grad(dense, argnums=(0, 1))(x, W)
    gc = jax.grad(chunked, argnums=(0, 1))(x, W)
    for a, b in zip(gc, gd):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=2e-4,
                                   atol=1e-6)
    # non-divisible vocab falls back to a single chunk, still correct
    def chunked7(x, W):
        return jnp.mean(fused_linear_cross_entropy_impl(x, W, lab, n_chunks=7))
    np.testing.assert_allclose(np.asarray(chunked7(x, W)),
                               np.asarray(dense(x, W)), rtol=1e-5)


def test_llama_head_chunks_matches_default():
    """build_functional_llama(head_chunks=N) is numerically the default head."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.models.llama import llama_config_tiny, \
        build_functional_llama

    cfg = llama_config_tiny(vocab=96, hidden=32, layers=2, heads=4, seq=16)
    key = jax.random.PRNGKey(0)
    ep, bp, hp, ea, ba, hl = build_functional_llama(cfg, key=key)
    ep2, bp2, hp2, _, _, hl_c = build_functional_llama(cfg, key=key,
                                                       head_chunks=4)
    rng = np.random.default_rng(1)
    ids = jnp.asarray(rng.integers(0, 96, (2, 16)).astype(np.int32))
    batch = (ids, ids)

    def loss(hl_fn, ep, bp, hp):
        x = ea(ep, batch)[0]
        for i in range(cfg.num_hidden_layers):
            x = ba(jax.tree_util.tree_map(lambda v: v[i], bp), x)
        return hl_fn(hp, x[None], batch)

    l0 = loss(hl, ep, bp, hp)
    l1 = loss(hl_c, ep2, bp2, hp2)
    np.testing.assert_allclose(np.asarray(l0), np.asarray(l1), rtol=2e-5)
    g0 = jax.grad(lambda p: loss(hl, ep, bp, p))(hp)
    g1 = jax.grad(lambda p: loss(hl_c, ep2, bp2, p))(hp2)
    for k in g0:
        np.testing.assert_allclose(np.asarray(g0[k]), np.asarray(g1[k]),
                                   rtol=5e-4, atol=1e-6)


def test_pallas_adamw_now_optin(monkeypatch):
    """Round-4: the fused Pallas AdamW measured slower than XLA's chain and
    is gated behind FLAGS_use_pallas_adamw (default off)."""
    import paddle_tpu as paddle
    from paddle_tpu.core import dispatch
    from paddle_tpu.core.dispatch import get_kernel
    from paddle_tpu.ops import pallas
    from paddle_tpu.ops.pallas import register_all
    # the forced registration is this test's alone: a later test of the same
    # process (tests/test_chip_smoke.py) must find the CPU's registry
    monkeypatch.setattr(dispatch, "_KERNELS",
                        {k: dict(v) for k, v in dispatch._KERNELS.items()})
    monkeypatch.setattr(pallas, "_registered", [pallas._registered[0]])
    register_all(force=True)
    import jax.numpy as jnp
    k = get_kernel("adamw_fused")
    if k is None:
        pytest.skip("pallas kernels not registered")
    p = jnp.ones((8, 128), jnp.float32)
    args = (p, p * 0.01, p * 0, p * 0)
    kw = dict(lr=1e-3, beta1=0.9, beta2=0.999, eps=1e-8, weight_decay=0.0,
              bias1=0.1, bias2=0.001)
    assert paddle.get_flags(["use_pallas_adamw"])["use_pallas_adamw"] is False
    assert k(*args, **kw) is None       # gated off by default
    paddle.set_flags({"use_pallas_adamw": True})
    try:
        res = k(*args, **kw)
        assert res is None or len(res) == 3   # kernel may decline shapes
    finally:
        paddle.set_flags({"use_pallas_adamw": False})


# ---------------------------------------------------------------------------
# In-kernel attention dropout (round 5)
# ---------------------------------------------------------------------------
# (the dropout variant's Mosaic lowering is guarded by the v5e compile in
# tests/test_chip_compile.py — pltpu PRNG has no interpret-mode lowering, so
# its numerics can only be checked on a chip)


def test_flash_attention_dropout_rate0_matches_plain():
    """rate=0 must be bit-identical to the plain kernel (shared cache key
    would otherwise hide a plumbing bug)."""
    B, S, H, D = 1, 128, 2, 64
    lr = np.random.default_rng(2)
    q, k, v = (jnp.asarray(lr.normal(0, 1, (B, S, H, D)).astype(np.float32))
               for _ in range(3))
    o0 = flash_attention(q, k, v, causal=False, interpret=True)
    od = flash_attention(q, k, v, causal=False, interpret=True,
                         dropout_rate=0.0, dropout_seed=3)
    np.testing.assert_array_equal(np.asarray(o0), np.asarray(od))


@pytest.mark.slow   # 8s/pair odd-length compiles; tile-pad coverage stays via kernel parity sweeps
@pytest.mark.parametrize("S", [453, 390])
def test_flash_attention_pad_to_tile(S):
    """Long untileable sequence lengths pad to the next 128-multiple with a
    pad segment — output and grads match the exact XLA reference on the
    real rows.  (Short untileable S like ViT's 197 deliberately stays on
    the XLA path: measured slower through the padded kernel.)"""
    B, H, D = 2, 2, 64
    lr = np.random.default_rng(3)
    q, k, v = (jnp.asarray(lr.normal(0, 1, (B, S, H, D)).astype(np.float32))
               for _ in range(3))
    out = flash_attention(q, k, v, causal=False, interpret=True)
    assert out is not None, "pad-to-tile path did not engage"
    ref = _ref_sdpa(q, k, v, False)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)

    def loss_fa(q, k, v):
        return (flash_attention(q, k, v, causal=False, interpret=True)
                ** 2).sum()

    def loss_ref(q, k, v):
        return (_ref_sdpa(q, k, v, False) ** 2).sum()

    gfa = jax.grad(loss_fa, argnums=(0, 1, 2))(q, k, v)
    gref = jax.grad(loss_ref, argnums=(0, 1, 2))(q, k, v)
    for a, b in zip(gfa, gref):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# Ragged paged-attention decode kernel vs its jnp reference (graftlint
# PAR001: every ops/pallas kernel module registers a parity test HERE; the
# serving-level sweeps live in test_paged_serving.py)
# ---------------------------------------------------------------------------
def test_paged_attention_decode_parity_vs_ref():
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention_decode, paged_attention_decode_ref)
    S, Hq, Hkv, D, ps, NP, P = 4, 8, 2, 64, 16, 13, 3
    q = jnp.asarray(rng.standard_normal((S, Hq, D)).astype(np.float32))
    kp = jnp.asarray(rng.standard_normal((Hkv, NP, ps, D)).astype(np.float32))
    vp = jnp.asarray(rng.standard_normal((Hkv, NP, ps, D)).astype(np.float32))
    pt = jnp.asarray(rng.permutation(NP - 1)[: S * P].reshape(S, P)
                     .astype(np.int32))
    # ragged mix: empty, sub-page, page-boundary, full-table lengths
    lens = jnp.asarray(np.array([0, 5, ps, P * ps], np.int32))
    out = ragged_paged_attention_decode(q, kp, vp, pt, lens, interpret=True)
    ref = paged_attention_decode_ref(q, kp, vp, pt, lens)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.parametrize("kv_dtype", ["int8", "fp8"])
def test_paged_attention_decode_quantized_parity_vs_ref(kv_dtype):
    """ISSUE 15 fused-dequant path (PAR001 pairing): the quantized kernel
    (int8/fp8 pages + per-row scales, dequant fused in VMEM) must agree
    with the scale-aware jnp ref — and the scale-aware ref must agree
    BIT-EXACTLY with manual dequantization fed to the plain ref, pinning
    that both use the one sanctioned dequant expression."""
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention_decode, paged_attention_decode_ref)
    from paddle_tpu.serving.quant import kv_spec, quantize_kv
    S, Hq, Hkv, D, ps, NP, P = 4, 8, 2, 64, 16, 13, 3
    storage, qmax = kv_spec(kv_dtype)
    q = jnp.asarray(rng.standard_normal((S, Hq, D)).astype(np.float32))
    kf = jnp.asarray(rng.standard_normal((Hkv, NP, ps, D))
                     .astype(np.float32))
    vf = jnp.asarray(rng.standard_normal((Hkv, NP, ps, D))
                     .astype(np.float32))
    kq, ks = quantize_kv(kf, qmax=qmax, dtype=storage)
    vq, vs = quantize_kv(vf, qmax=qmax, dtype=storage)
    pt = jnp.asarray(rng.permutation(NP - 1)[: S * P].reshape(S, P)
                     .astype(np.int32))
    lens = jnp.asarray(np.array([0, 5, ps, P * ps], np.int32))
    out = ragged_paged_attention_decode(q, kq, vq, pt, lens, interpret=True,
                                        k_scales=ks, v_scales=vs)
    ref = paged_attention_decode_ref(q, kq, vq, pt, lens,
                                     k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # length-0 slot produces exact zeros on both paths
    assert not np.asarray(out[0]).any() and not np.asarray(ref[0]).any()
    # the scale-aware ref == manual dequant + plain ref, bit-for-bit
    kd = kq.astype(jnp.float32) * ks[..., None]
    vd = vq.astype(jnp.float32) * vs[..., None]
    ref2 = paged_attention_decode_ref(q, kd, vd, pt, lens)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ref2))
    # scales-without-partner is a usage error, not silent garbage
    with pytest.raises(ValueError):
        ragged_paged_attention_decode(q, kq, vq, pt, lens, interpret=True,
                                      k_scales=ks)

# ---------------------------------------------------------------------------
# UNIFIED ragged paged-attention kernel (ISSUE 16): decode / speculative
# verify / chunked prefill are all ragged (q_start, q_len, kv_len) segments
# of ONE kernel — these sweeps pin kernel-vs-ref parity across the segment
# shapes the serving engine actually dispatches
# ---------------------------------------------------------------------------
def _mk_ragged(S, Hq, Hkv, D, ps, NP, P, dtype=np.float32, seed_off=0):
    lr = np.random.default_rng(11 + seed_off)
    q = jnp.asarray(lr.standard_normal((S, 8, Hq, D)).astype(dtype))
    kp = jnp.asarray(lr.standard_normal((Hkv, NP, ps, D)).astype(dtype))
    vp = jnp.asarray(lr.standard_normal((Hkv, NP, ps, D)).astype(dtype))
    # random (possibly shared) physical pages — parity only needs valid ids
    pt = jnp.asarray(lr.integers(0, NP, (S, P)).astype(np.int32))
    return q, kp, vp, pt


def test_ragged_paged_attention_parity_vs_ref():
    """One batch mixing every serving segment shape: q_len=1 (decode),
    q_len=K+1 (verify), q_len=chunk (chunked prefill, full Qmax), and an
    inactive q_len=0 slot — with a verify segment STRADDLING a page
    boundary (queries at positions 14..18, ps=16)."""
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention, ragged_paged_attention_ref)
    S, Hq, Hkv, D, ps, NP, P = 4, 8, 2, 64, 16, 13, 3
    q, kp, vp, pt = _mk_ragged(S, Hq, Hkv, D, ps, NP, P)
    q_start = jnp.asarray(np.array([7, 14, 16, 0], np.int32))
    q_len = jnp.asarray(np.array([1, 5, 8, 0], np.int32))
    kv_len = jnp.asarray(np.array([8, 19, 24, 0], np.int32))
    out = ragged_paged_attention(q, kp, vp, pt, q_start, q_len, kv_len,
                                 interpret=True)
    ref = ragged_paged_attention_ref(q, kp, vp, pt, q_start, q_len, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    # padding query rows (>= q_len) and the inactive slot are exact zeros
    # on BOTH paths — garbage here would poison the residual stream
    assert not np.asarray(out[0, 1:]).any() and not np.asarray(ref[0, 1:]).any()
    assert not np.asarray(out[1, 5:]).any() and not np.asarray(ref[1, 5:]).any()
    assert not np.asarray(out[3]).any() and not np.asarray(ref[3]).any()


@pytest.mark.parametrize(
    "hq,hkv",
    [pytest.param(4, 4, marks=pytest.mark.slow),   # MHA 1x: the mixed-widths
     (8, 2),                                       #   parity sweep covers it
     pytest.param(16, 2, marks=pytest.mark.slow)])  # 8x: same grouping math
def test_ragged_paged_attention_gqa_ratios(hq, hkv):
    """GQA head ratios 1x/4x/8x: the kernel fetches K/V once per kv head
    and flattens the query-head group into the scratch rows."""
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention, ragged_paged_attention_ref)
    S, D, ps, NP, P = 3, 32, 8, 11, 4
    q, kp, vp, pt = _mk_ragged(S, hq, hkv, D, ps, NP, P, seed_off=hq)
    q_start = jnp.asarray(np.array([0, 6, 20], np.int32))
    q_len = jnp.asarray(np.array([4, 1, 8], np.int32))
    kv_len = jnp.asarray(np.array([4, 7, 28], np.int32))
    out = ragged_paged_attention(q, kp, vp, pt, q_start, q_len, kv_len,
                                 interpret=True)
    ref = ragged_paged_attention_ref(q, kp, vp, pt, q_start, q_len, kv_len)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


@pytest.mark.slow   # interpret-mode bf16 compile; f32 + quant parity stay tier-1
def test_ragged_paged_attention_bf16():
    """bf16 inputs, f32 accumulation: read the un-downcast result via
    out_dtype=f32 and bound kernel-vs-ref drift at 2e-4 (the same
    acceptance bound as the decode-shaped bf16 parity test)."""
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention, ragged_paged_attention_ref)
    S, Hq, Hkv, D, ps, NP, P = 3, 8, 2, 64, 16, 13, 3
    lr = np.random.default_rng(23)
    q = jnp.asarray(lr.standard_normal((S, 8, Hq, D)), jnp.bfloat16)
    kp = jnp.asarray(lr.standard_normal((Hkv, NP, ps, D)), jnp.bfloat16)
    vp = jnp.asarray(lr.standard_normal((Hkv, NP, ps, D)), jnp.bfloat16)
    pt = jnp.asarray(lr.permutation(NP - 1)[: S * P].reshape(S, P)
                     .astype(np.int32))
    q_start = jnp.asarray(np.array([3, 12, 16], np.int32))
    q_len = jnp.asarray(np.array([1, 5, 8], np.int32))
    kv_len = jnp.asarray(np.array([4, 17, 24], np.int32))
    out = ragged_paged_attention(q, kp, vp, pt, q_start, q_len, kv_len,
                                 interpret=True, out_dtype=jnp.float32)
    ref = ragged_paged_attention_ref(q, kp, vp, pt, q_start, q_len, kv_len,
                                     out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize(
    "kv_dtype",
    ["int8",
     pytest.param("fp8", marks=pytest.mark.slow)])  # same codepath, 2nd dtype
def test_ragged_paged_attention_quantized_parity(kv_dtype):
    """Fused dequant on EVERY path (the ISSUE 16 extension of the ISSUE 15
    decode-only fusion): int8/fp8 pages + per-row scales through the
    ragged kernel across decode/verify/chunk segment shapes, and the
    scale-aware ref must equal manual-dequant + plain ref BIT-EXACTLY
    (both route through the one sanctioned dequant expression)."""
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention, ragged_paged_attention_ref)
    from paddle_tpu.serving.quant import kv_spec, quantize_kv
    S, Hq, Hkv, D, ps, NP, P = 4, 8, 2, 64, 16, 13, 3
    storage, qmax = kv_spec(kv_dtype)
    q, kf, vf, pt = _mk_ragged(S, Hq, Hkv, D, ps, NP, P, seed_off=3)
    kq, ks = quantize_kv(kf, qmax=qmax, dtype=storage)
    vq, vs = quantize_kv(vf, qmax=qmax, dtype=storage)
    q_start = jnp.asarray(np.array([7, 14, 16, 0], np.int32))
    q_len = jnp.asarray(np.array([1, 5, 8, 0], np.int32))
    kv_len = jnp.asarray(np.array([8, 19, 24, 0], np.int32))
    out = ragged_paged_attention(q, kq, vq, pt, q_start, q_len, kv_len,
                                 interpret=True, k_scales=ks, v_scales=vs)
    ref = ragged_paged_attention_ref(q, kq, vq, pt, q_start, q_len, kv_len,
                                     k_scales=ks, v_scales=vs)
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)
    assert not np.asarray(out[3]).any() and not np.asarray(ref[3]).any()
    kd = kq.astype(jnp.float32) * ks[..., None]
    vd = vq.astype(jnp.float32) * vs[..., None]
    ref2 = ragged_paged_attention_ref(q, kd, vd, pt, q_start, q_len, kv_len)
    np.testing.assert_array_equal(np.asarray(ref), np.asarray(ref2))
    with pytest.raises(ValueError):
        ragged_paged_attention(q, kq, vq, pt, q_start, q_len, kv_len,
                               interpret=True, k_scales=ks)


def test_ragged_decode_wrappers_delegate():
    """The decode-shaped API is a PURE q_len=1 delegation to the unified
    ragged pair — wrapper output must equal hand-built segment descriptors
    fed to the ragged fns, bit-for-bit (no second decode implementation)."""
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention, ragged_paged_attention_ref,
        ragged_paged_attention_decode, paged_attention_decode_ref)
    S, Hq, Hkv, D, ps, NP, P = 4, 8, 2, 64, 16, 13, 3
    q, kp, vp, pt = _mk_ragged(S, Hq, Hkv, D, ps, NP, P, seed_off=5)
    qd = q[:, 0]
    lens = jnp.asarray(np.array([0, 5, ps, P * ps], np.int32))
    q_start = jnp.maximum(lens - 1, 0)
    q_len = (lens > 0).astype(jnp.int32)
    wrap = ragged_paged_attention_decode(qd, kp, vp, pt, lens,
                                         interpret=True)
    direct = ragged_paged_attention(qd[:, None], kp, vp, pt, q_start,
                                    q_len, lens, interpret=True)[:, 0]
    np.testing.assert_array_equal(np.asarray(wrap), np.asarray(direct))
    wrap_r = paged_attention_decode_ref(qd, kp, vp, pt, lens)
    direct_r = ragged_paged_attention_ref(qd[:, None], kp, vp, pt, q_start,
                                          q_len, lens)[:, 0]
    np.testing.assert_array_equal(np.asarray(wrap_r), np.asarray(direct_r))


# ---------------------------------------------------------------------------
# The WHOLE-POOL form (PR 28): `layer=` hands the kernel (and the ref) the
# 5-D [L, Hkv, NP, ps, D] pool and the layer index rides scalar prefetch —
# it must equal the 4-D call on `pool[layer]` BIT for bit, on every segment
# shape the engine dispatches, plain and quantized, kernel and ref alike
# ---------------------------------------------------------------------------
_POOL_SEGMENTS = {
    # (S, Qmax, q_start, q_len, kv_len)
    "decode": (4, 1, [7, 0, 16, 47], [1, 0, 1, 1], [8, 0, 17, 48]),
    "chunk": (1, 8, [16], [8], [24]),
    "verify": (4, 5, [7, 14, 16, 0], [1, 5, 3, 0], [8, 19, 19, 0]),
}


@pytest.mark.parametrize("impl", ["kernel", "ref"])
@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("shape", list(_POOL_SEGMENTS))
def test_ragged_paged_attention_pool_layer_form_is_bit_equal(shape, quant,
                                                             impl):
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention, ragged_paged_attention_ref)
    from paddle_tpu.serving.quant import kv_spec, quantize_kv
    L, Hq, Hkv, D, ps, NP, P = 3, 8, 2, 64, 16, 13, 3
    S, qmax, q_start, q_len, kv_len = _POOL_SEGMENTS[shape]
    lr = np.random.default_rng(31)
    q = jnp.asarray(lr.standard_normal((S, qmax, Hq, D)).astype(np.float32))
    kp = jnp.asarray(lr.standard_normal((L, Hkv, NP, ps, D))
                     .astype(np.float32))
    vp = jnp.asarray(lr.standard_normal((L, Hkv, NP, ps, D))
                     .astype(np.float32))
    pt = jnp.asarray(lr.integers(0, NP, (S, P)).astype(np.int32))
    seg = [jnp.asarray(np.array(a, np.int32))
           for a in (q_start, q_len, kv_len)]
    ks = vs = None
    if quant:
        storage, qm = kv_spec("int8")
        kp, ks = quantize_kv(kp, qmax=qm, dtype=storage)
        vp, vs = quantize_kv(vp, qmax=qm, dtype=storage)
    fn = functools.partial(ragged_paged_attention, interpret=True) \
        if impl == "kernel" else ragged_paged_attention_ref

    # the layer arrives TRACED in both, as the model's layer loop hands it
    # over: one program indexes it inside the call, the other slices first
    @jax.jit
    def pool_form(li):
        return fn(q, kp, vp, pt, *seg, layer=li,
                  **(dict(k_scales=ks, v_scales=vs) if quant else {}))

    @jax.jit
    def layer_form(li):
        return fn(q, kp[li], vp[li], pt, *seg,
                  **(dict(k_scales=ks[li], v_scales=vs[li]) if quant else {}))

    for li in range(L):
        pool = pool_form(jnp.int32(li))
        np.testing.assert_array_equal(np.asarray(pool),
                                      np.asarray(layer_form(jnp.int32(li))))
    assert np.asarray(pool).any()


# ---------------------------------------------------------------------------
# The re-blocked kernel (PR 30): a grid step is one (slot, block of Hb kv
# heads) and loops over the slot's LIVE pages, which it copies out of HBM
# itself into two buffers in turn; Hb comes from the shapes.
# One sweep holds the kernel to the ref over role x head layout x pool form
# with every kv_len edge in ONE batch; one sweep holds every blocking
# BIT-equal to the one-head step
# ---------------------------------------------------------------------------
_RB = dict(D=64, ps=16, NP=23, P=9, L=2)
_RB_KV_LEN = [0, 1, 2 * 16, 9 * 16, 37]        # empty, one token, a page
_RB_ROLES = {"decode": 1, "verify": 5, "chunk": 128}   # multiple, full, ragged
_RB_HEADS = {"mha8x8": (8, 8), "gqa8x2_tp_local": (8, 2), "gqa16x4": (16, 4)}


def _reblocked_case(qmax, hq, hkv, pooled, dtype, quant=False, seed=41):
    from paddle_tpu.serving.quant import kv_spec, quantize_kv
    D, ps, NP, P, L = (_RB[k] for k in ("D", "ps", "NP", "P", "L"))
    lr = np.random.default_rng(seed)
    kv_len = np.array(_RB_KV_LEN, np.int32)
    q_len = np.minimum(qmax, kv_len)
    S = len(kv_len)
    q = jnp.asarray(lr.standard_normal((S, qmax, hq, D)), dtype)
    pool = (L, hkv, NP, ps, D) if pooled else (hkv, NP, ps, D)
    kp = jnp.asarray(lr.standard_normal(pool), dtype)
    vp = jnp.asarray(lr.standard_normal(pool), dtype)
    # live columns name real pages; DEAD ones an id past the pool's end —
    # the kernel never reads a dead entry, whatever it holds
    pt = lr.integers(0, NP, (S, P)).astype(np.int32)
    pt[np.arange(P)[None, :] * ps >= kv_len[:, None]] = 10 ** 6
    kw = {"layer": jnp.int32(L - 1)} if pooled else {}
    if quant:
        storage, qm = kv_spec("int8")
        kp, kw["k_scales"] = quantize_kv(kp.astype(jnp.float32), qmax=qm,
                                         dtype=storage)
        vp, kw["v_scales"] = quantize_kv(vp.astype(jnp.float32), qmax=qm,
                                         dtype=storage)
    args = (q, kp, vp, jnp.asarray(pt), jnp.asarray(kv_len - q_len),
            jnp.asarray(q_len), jnp.asarray(kv_len))
    return args, kw


def _ref_with_dead_entries_in_range(args, kw):
    """the ref GATHERS the whole table, so hand it the dead entries as page
    0: its mask drops them"""
    from paddle_tpu.ops.pallas.paged_attention import (
        ragged_paged_attention_ref)
    pt = jnp.where(args[3] < _RB["NP"], args[3], 0)
    return ragged_paged_attention_ref(*args[:3], pt, *args[4:],
                                      out_dtype=jnp.float32, **kw)


@pytest.mark.parametrize("pooled", [False, True], ids=["4d", "5d_layer"])
@pytest.mark.parametrize("heads", list(_RB_HEADS))
@pytest.mark.parametrize("role", list(_RB_ROLES))
def test_ragged_reblocked_parity(role, heads, pooled):
    """bf16 pages and queries, f32 accumulation read un-downcast: the
    present bf16→f32 bound (2e-4), whatever Hb the chooser picked."""
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    args, kw = _reblocked_case(_RB_ROLES[role], *_RB_HEADS[heads], pooled,
                               jnp.bfloat16)
    out = np.asarray(ragged_paged_attention(
        *args, interpret=True, out_dtype=jnp.float32, role=role, **kw))
    np.testing.assert_allclose(
        out, np.asarray(_ref_with_dead_entries_in_range(args, kw)),
        rtol=2e-4, atol=2e-4)
    q_len = np.asarray(args[5])
    assert not out[0].any()                        # the q_len = 0 slot
    for s, n in enumerate(q_len):
        assert not out[s, n:].any() and (n == 0 or out[s, :n].any())


def test_ragged_reblocked_parity_quantized():
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    args, kw = _reblocked_case(5, 8, 2, True, jnp.float32, quant=True)
    out = ragged_paged_attention(*args, interpret=True,
                                 out_dtype=jnp.float32, **kw)
    np.testing.assert_allclose(
        np.asarray(out), np.asarray(_ref_with_dead_entries_in_range(args, kw)),
        rtol=2e-5, atol=2e-5)


@functools.lru_cache(maxsize=None)
def _one_head_step(quant):
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    args, kw = _reblocked_case(5, 8, 4, True, jnp.float32, quant=quant)
    return np.asarray(ragged_paged_attention(*args, interpret=True,
                                             _heads=1, **kw))


@pytest.mark.parametrize("quant", [False, True], ids=["plain", "int8"])
@pytest.mark.parametrize("heads", [2, 4])
def test_ragged_blockings_are_bit_equal(heads, quant):
    """Every blocking `_choose_heads` can return for 4 kv heads gives the
    SAME bits as the one-head step: a query row's updates come in page
    order whatever a step carries — with slots of 0, 1, 2, 3 and 9 live
    pages in the batch, so the two page buffers run dry, start cold and
    are handed from step to step at either parity.  The int8 body is held
    to the last bit but one: interpret mode makes each blocking its own
    XLA:CPU program, and with the two scale products in it the compiler
    contracts a multiply and an add differently in ~3 % of the outputs
    (6e-8 absolute)."""
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    args, kw = _reblocked_case(5, 8, 4, True, jnp.float32, quant=quant)
    out = np.asarray(ragged_paged_attention(
        *args, interpret=True, _heads=heads, **kw))
    if quant:
        np.testing.assert_allclose(out, _one_head_step(quant), rtol=5e-7,
                                   atol=1.2e-7)
    else:
        np.testing.assert_array_equal(out, _one_head_step(quant))
    assert out.any()


@pytest.mark.parametrize("heads", [1, 2, 4, 8])
def test_ragged_blockings_are_bit_equal_at_the_decode_shape(heads):
    """... and at the decode shape with 8 kv heads, GQA 4: the cell's head
    layout, every divisor against the chooser's own pick."""
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    args, kw = _reblocked_case(1, 32, 8, True, jnp.float32, seed=43)
    chosen = ragged_paged_attention(*args, interpret=True, **kw)
    forced = ragged_paged_attention(*args, interpret=True, _heads=heads,
                                    **kw)
    np.testing.assert_array_equal(np.asarray(forced), np.asarray(chosen))
    assert np.asarray(forced).any()


def test_choose_heads_from_shapes():
    """The chooser at the cell's shapes (Mistral-7B: 8 kv heads of 128,
    pages of 64, bf16): every head at the decode, verify and 512-query
    chunk rows, fewer as the rows grow; a TP=4 rank's two heads; the heads
    always divide and the count always fits the budget (or is one)."""
    from paddle_tpu.ops.pallas.paged_attention import _choose_heads
    cell = dict(d=128, page_size=64, q_bytes=2, out_bytes=2, kv_bytes=2,
                quant=False)
    assert _choose_heads(8, 8, **cell) == 8
    assert _choose_heads(24, 8, **cell) == 8
    assert _choose_heads(2048, 8, **cell) == 8
    assert _choose_heads(4096, 8, **cell) == 2
    assert _choose_heads(1 << 20, 8, **cell) == 1
    assert _choose_heads(8, 2, **cell) == 2
    for rows in (8, 24, 512, 1024, 2048, 8192, 65536):
        for hkv in (1, 2, 8, 32):
            for quant in (False, True):
                assert hkv % _choose_heads(rows, hkv,
                                           **{**cell, "quant": quant}) == 0


# ---------------------------------------------------------------------------
# The products' operands (PR 40): 2-byte queries over 2-byte pages of one
# dtype go to the MXU as they are stored — scores one bf16 x bf16 product,
# values p as TWO bf16 pieces, a product each against the V tile — f32
# accumulation; every other pairing of dtypes runs the f32 products it ran
# before, bit for bit.  The rule is the operands' dtypes at trace time.
# ---------------------------------------------------------------------------
def _kernel_eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _kernel_eqns(sub)


def _traced_kernel(q_dtype, page_dtype, qmax, quant=False):
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    S, hq, hkv, D, ps, NP, P = 2, 8, 2, 128, 64, 9, 4
    q = jnp.zeros((S, qmax, hq, D), q_dtype)
    pages = jnp.zeros((hkv, NP, ps, D), page_dtype)
    kw = dict(k_scales=jnp.ones((hkv, NP, ps)),
              v_scales=jnp.ones((hkv, NP, ps))) if quant else {}
    seg = jnp.ones((S,), jnp.int32)
    jaxpr = jax.make_jaxpr(lambda q, k, v: ragged_paged_attention(
        q, k, v, jnp.zeros((S, P), jnp.int32), seg, seg, seg, **kw))(
            q, pages, pages)
    (call,) = [e for e in jaxpr.jaxpr.eqns if e.primitive.name == "pallas_call"]
    return str(jaxpr), list(_kernel_eqns(call.params["jaxpr"])), (ps, D)


@pytest.mark.parametrize("qmax", [1, 5, 128], ids=["decode", "verify",
                                                   "chunk"])
def test_ragged_kernel_feeds_the_mxu_its_pages_as_stored(qmax):
    """bf16 queries over bf16 pages: no [ps, D] tile is widened to f32 and
    no query block either; an update's `dot_general`s — the scores, then
    the two pieces of p against the V tile — take bf16 operands and give an
    f32 result."""
    _, eqns, tile = _traced_kernel(jnp.bfloat16, jnp.bfloat16, qmax)
    rows = -(-qmax * 4 // 8) * 8
    widened = [e for e in eqns if e.primitive.name == "convert_element_type"
               and e.outvars[0].aval.dtype == jnp.float32
               and e.invars[0].aval.shape in (tile, (rows, tile[1]))]
    assert not widened
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 3 * 2          # (scores, p_hi, p_lo) x 2 kv heads
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.bfloat16] * 2
        assert e.outvars[0].aval.dtype == jnp.float32
    assert [e.invars[0].aval.shape for e in dots] \
        == [(rows, tile[1]), (rows, tile[0]), (rows, tile[0])] * 2
    # the two value products read ONE V tile
    assert dots[1].invars[1] is dots[2].invars[1]


# sha256 of `str(make_jaxpr(...))`, recorded at the parent `aa86b38` (jax
# 0.9.0): the text holds no path and no address.  A later PR that changes
# the kernel's f32 path on purpose records them again.
_PARENT_JAXPR = {
    ("float32", "float32", False): "8cdb0bde321bd50a",
    ("float32", "bfloat16", False): "d3e6c85521400c8f",
    ("bfloat16", "float32", False): "678aab9bcc57c204",
    ("float32", "int8", True): "a160bb9130933e57",
}


@pytest.mark.parametrize("q_dtype,page_dtype,quant", list(_PARENT_JAXPR),
                         ids=["f32", "q_wider_than_pages",
                              "pages_wider_than_q", "int8_pages"])
def test_ragged_kernel_on_other_dtypes_is_the_parents_program(q_dtype,
                                                              page_dtype,
                                                              quant):
    """f32 pages (the parity sweeps, the bit equalities across blockings),
    a query wider than its pages, and the int8 body trace to the program
    they traced to before PR 40 — the whole jaxpr, text for text — so their
    results are the parent's bits: every product on f32 operands."""
    import hashlib
    text, eqns, _ = _traced_kernel(jnp.dtype(q_dtype), jnp.dtype(page_dtype),
                                   5, quant=quant)
    dots = [e for e in eqns if e.primitive.name == "dot_general"]
    assert len(dots) == 2 * 2                  # (scores, values) x 2 kv heads
    for e in dots:
        assert [v.aval.dtype for v in e.invars] == [jnp.float32] * 2
    assert hashlib.sha256(text.encode()).hexdigest()[:16] \
        == _PARENT_JAXPR[q_dtype, page_dtype, quant]


@pytest.mark.parametrize("role", list(_RB_ROLES))
def test_ragged_bf16_products_equal_the_f32_products_to_f32_rounding(role):
    """The SAME bf16 values through both paths of the one kernel: as bf16
    (native products, p in two bf16 pieces) and widened to f32 outside
    (the f32 products).  A bf16 x bf16 product is exact in f32, so the
    scores differ by the order of an f32 sum only; p = p_hi + p_lo holds
    to 2^-17 relative, so an output differs by at most 2^-17 of the largest
    |v| it averages — 64 x closer than ONE bf16 piece of p (2^-9) would
    come, which this bound refuses."""
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    args, kw = _reblocked_case(_RB_ROLES[role], 16, 4, True, jnp.bfloat16,
                               seed=53)
    wide = tuple(a.astype(jnp.float32) for a in args[:3]) + args[3:]
    native = np.asarray(ragged_paged_attention(
        *args, interpret=True, out_dtype=jnp.float32, role=role, **kw))
    f32 = np.asarray(ragged_paged_attention(
        *wide, interpret=True, out_dtype=jnp.float32, role=role, **kw))
    bound = 2.0 ** -17 * float(np.abs(np.asarray(wide[2])).max())
    assert np.abs(native - f32).max() <= bound + 2e-6   # + the sums' order
    assert native.any()


@pytest.mark.parametrize("qmax", [1, 5], ids=["decode", "verify"])
def test_ragged_kernel_as_differential_attention(qmax):
    """`models/sambay.py`'s use of the kernel: rows `[k_2p | k_2p+1]`, `[v_2p
    | v_2p+1]` 128 wide, queries `[q | 0]` / `[0 | q]`, the output `a1 - lam
    a2` a DIFFERENCE of two softmax sums (lam 0.8) — against the plain
    `diff_attention_pairs` in f32 on the same bf16 values.  Each sum holds
    to 2^-17 of the largest |v|, the difference to (1 + lam) times that."""
    from paddle_tpu.models.sambay import diff_attention_pairs
    from paddle_tpu.ops.pallas.paged_attention import ragged_paged_attention
    S, nh, nkv, hd, ps, P, lam = 3, 8, 4, 64, 16, 5, 0.8
    npair, wide = nkv // 2, 2 * hd
    lr = np.random.default_rng(59)
    kv_len = np.array([P * ps, 37, 16], np.int32)
    bf = lambda *shape: jnp.asarray(lr.standard_normal(shape), jnp.bfloat16)
    q, k, v = bf(S, qmax, nh, hd), bf(S, P * ps, nkv, hd), bf(S, P * ps, nkv, hd)
    # the store: a slot's pages in order, rows 2 hd wide a K/V pair
    pages = lambda x: x.reshape(S * P, ps, npair, wide).transpose(2, 0, 1, 3)
    table = jnp.arange(S * P, dtype=jnp.int32).reshape(S, P)
    qp = q.reshape(S, qmax, nh // 2, 2, hd)
    zero = jnp.zeros_like(qp[:, :, :, 0])
    qz = jnp.stack([jnp.concatenate([qp[:, :, :, 0], zero], -1),
                    jnp.concatenate([zero, qp[:, :, :, 1]], -1)], 3)
    o = ragged_paged_attention(
        qz.reshape(S, qmax, nh, wide), pages(k), pages(v), table,
        jnp.asarray(kv_len - qmax), jnp.full((S,), qmax, jnp.int32),
        jnp.asarray(kv_len), sm_scale=1 / math.sqrt(hd), interpret=True,
        out_dtype=jnp.float32, role="decode", kind="cross")
    o = np.asarray(o).reshape(S, qmax, nh // 2, 2, wide)
    got = o[:, :, :, 0] - lam * o[:, :, :, 1]
    f32 = lambda x: x.astype(jnp.float32)
    for s in range(S):
        n = int(kv_len[s])
        mask = jnp.asarray(np.arange(n)[None]
                           <= n - qmax + np.arange(qmax)[:, None])
        a1, a2 = diff_attention_pairs(f32(q[s]), f32(k[s, :n]), f32(v[s, :n]),
                                      mask, 1 / math.sqrt(hd))
        bound = (1 + lam) * 2.0 ** -17 * float(np.abs(f32(v[s, :n])).max())
        assert np.abs(got[s] - np.asarray(a1 - lam * a2)).max() \
            <= bound + 4e-6
    assert got.any()


def test_grouped_matmul_against_ragged_dot():
    """`ops/pallas/grouped_matmul.py` beside its reference on one uneven
    load with empty groups (tests/test_grouped_matmul.py has the layouts,
    the rule and the expert layer on both paths)."""
    from paddle_tpu.ops.pallas.grouped_matmul import (grouped_matmul,
                                                      grouped_matmul_ref)
    lr = np.random.default_rng(34)
    xs = jnp.asarray(lr.standard_normal((128, 256)), jnp.bfloat16)
    w = jnp.asarray(lr.standard_normal((8, 256, 384)) / 16, jnp.bfloat16)
    rows = jnp.asarray([0, 41, 0, 3, 0, 0, 60, 1], jnp.int32)
    out = grouped_matmul(xs, w, rows, tm=32, tn=128, interpret=True,
                         out_dtype=jnp.float32)
    ref = grouped_matmul_ref(xs, w, rows, out_dtype=jnp.float32)
    np.testing.assert_allclose(np.asarray(out)[:105], np.asarray(ref)[:105],
                               rtol=2e-4, atol=2e-4)
