"""Fused Pallas TPU kernels: rms_norm (fwd+bwd) and the AdamW update.

SURVEY §2.1 kernel north star (the reference fuses these in CUDA:
paddle/phi/kernels/fusion/ fused_rms_norm, gpu/adamw_kernel.cu). XLA fuses
elementwise chains on its own; these kernels exist to (a) pin the fusion
(one VMEM round trip per row regardless of surrounding graph) and (b) keep
the fp32 statistics/moments math inside the kernel while params stream
through in bf16.

rms_norm: rows [N, H]; forward saves inv_rms for a cheap backward.
adamw: one kernel updates (p, m, v) in fp32 math with decoupled weight
decay, reading the bias-corrected step size from SMEM scalars.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["rms_norm", "adamw_update", "softmax", "layer_norm",
           "rms_norm_ref", "layer_norm_ref", "softmax_ref",
           "adamw_update_ref"]

# jnp references (graftlint PAR001: the kernel module itself exports the
# fallback/oracle implementations its parity tests pair against).  The
# norm refs are shared with the functional API — one source of truth.
from ...nn.functional.norm import layer_norm_ref, rms_norm_ref  # noqa: F401,E402


def softmax_ref(x, axis=-1):
    """jnp reference for the fused `softmax` kernel (last-axis case)."""
    return jax.nn.softmax(x, axis=axis)


def adamw_update_ref(p, g, m, v, *, lr, beta1=0.9, beta2=0.999, eps=1e-8,
                     weight_decay=0.01, step=None, bias1=None, bias2=None):
    """jnp reference for `adamw_update` (same signature sans `interpret`):
    fp32 moment math, decoupled weight decay, params back in p.dtype."""
    if bias1 is None:
        bias1 = 1.0 - beta1 ** step
        bias2 = 1.0 - beta2 ** step
    gf = g.astype(jnp.float32)
    nm = beta1 * m.astype(jnp.float32) + (1.0 - beta1) * gf
    nv = beta2 * v.astype(jnp.float32) + (1.0 - beta2) * gf * gf
    mh = nm / bias1
    vh = nv / bias2
    pf = p.astype(jnp.float32)
    np_ = pf - lr * (mh / (jnp.sqrt(vh) + eps) + weight_decay * pf)
    return np_.astype(p.dtype), nm, nv


# ---------------------------------------------------------------------------
# RMSNorm
# ---------------------------------------------------------------------------
def _rms_fwd_kernel(x_ref, w_ref, o_ref, inv_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)          # [bn, H]
    ms = jnp.mean(x * x, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(ms + eps)
    o_ref[...] = (x * inv * w_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    inv_ref[...] = inv


def _rms_bwd_kernel(x_ref, w_ref, inv_ref, g_ref, dx_ref, dw_ref, dw_scr, *,
                    eps, num_blocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    inv = inv_ref[...]                           # [bn, 1]
    xhat = x * inv
    gw = g * w
    # dx = inv * (gw - xhat * mean(gw * xhat))
    m = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (inv * (gw - xhat * m)).astype(dx_ref.dtype)
    dw_scr[...] += jnp.sum(g * xhat, axis=0, keepdims=True)

    @pl.when(i == num_blocks - 1)
    def _fin():
        dw_ref[...] = dw_scr[...].astype(dw_ref.dtype)


def _rms_block_rows(n, h):
    # a (rows, h) tile of 256K elements is what the row-wise backward
    # kernels fit into scoped VMEM with their f32 temporaries and double
    # buffers (256 rows at h=1024, 64 at h=4096; a fixed 256 rows at h=4096
    # asks Mosaic for 18 MB against its 16 MB limit)
    cap = max(8, (256 * 1024) // h)
    cap = 1 << (cap.bit_length() - 1)
    bn = max(8, min(cap, n))
    while n % bn != 0:
        bn //= 2
    return max(bn, 1)


@functools.lru_cache(maxsize=8)
def _make_rms(eps: float, interpret: bool):
    @jax.custom_vjp
    def op(x, w):
        o, _ = fwd(x, w)
        return o

    def fwd(x, w):
        n, h = x.shape
        bn = _rms_block_rows(n, h)
        o, inv = pl.pallas_call(
            functools.partial(_rms_fwd_kernel, eps=eps),
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((1, h), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, h), x.dtype),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)],
            interpret=interpret,
            metadata={"kernel": "rms_norm", "pass": "fwd"},
        )(x, w.reshape(1, h))
        return o, (x, w, inv)

    def bwd(res, g):
        x, w, inv = res
        n, h = x.shape
        bn = _rms_block_rows(n, h)
        dx, dw = pl.pallas_call(
            functools.partial(_rms_bwd_kernel, eps=eps, num_blocks=n // bn),
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((1, h), lambda i: (0, 0)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                      pl.BlockSpec((bn, h), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                       pl.BlockSpec((1, h), lambda i: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, h), x.dtype),
                       jax.ShapeDtypeStruct((1, h), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((1, h), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
            metadata={"kernel": "rms_norm", "pass": "bwd"},
        )(x, w.reshape(1, h), inv, g)
        return dx, dw.reshape(w.shape).astype(w.dtype)

    op.defvjp(fwd, bwd)
    return op


def rms_norm(x, weight, eps=1e-6, interpret=False):
    """Fused RMSNorm over the last dim; x [..., H]. Returns None when the
    shape doesn't tile (dispatch falls back to the jnp impl)."""
    h = x.shape[-1]
    if h % 128 != 0:
        return None
    lead = x.shape[:-1]
    n = 1
    for s in lead:
        n *= s
    if n % 8 != 0:
        return None
    out = _make_rms(float(eps), bool(interpret))(x.reshape(n, h), weight)
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# AdamW fused update
# ---------------------------------------------------------------------------
def _adamw_kernel(p_ref, g_ref, m_ref, v_ref, sc_ref,
                  np_ref, nm_ref, nv_ref):
    # sc: [lr, beta1, beta2, eps, wd, bias1, bias2] in SMEM
    lr = sc_ref[0]
    b1 = sc_ref[1]
    b2 = sc_ref[2]
    eps = sc_ref[3]
    wd = sc_ref[4]
    c1 = sc_ref[5]
    c2 = sc_ref[6]
    p = p_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    m = m_ref[...]
    v = v_ref[...]
    m_new = b1 * m + (1.0 - b1) * g
    v_new = b2 * v + (1.0 - b2) * g * g
    mhat = m_new / c1
    vhat = v_new / c2
    upd = mhat / (jnp.sqrt(vhat) + eps) + wd * p
    np_ref[...] = (p - lr * upd).astype(np_ref.dtype)
    nm_ref[...] = m_new
    nv_ref[...] = v_new


@functools.lru_cache(maxsize=4)
def _make_adamw(interpret: bool):
    def call(p, g, m, v, scalars):
        n, h = p.shape
        bn = _rms_block_rows(n, h)
        return pl.pallas_call(
            _adamw_kernel,
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec(memory_space=pltpu.SMEM)],
            out_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                       pl.BlockSpec((bn, h), lambda i: (i, 0)),
                       pl.BlockSpec((bn, h), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, h), p.dtype),
                       jax.ShapeDtypeStruct((n, h), jnp.float32),
                       jax.ShapeDtypeStruct((n, h), jnp.float32)],
            interpret=interpret,
        )(p, g, m, v, scalars)
    return call


_LANE = 1024


def adamw_update(p, g, m, v, *, lr, beta1=0.9, beta2=0.999, eps=1e-8,
                 weight_decay=0.01, step=None, bias1=None, bias2=None,
                 interpret=False):
    """Fused AdamW step on one flat tensor. m/v are fp32; p any float dtype.
    bias1/bias2 = 1-beta^t correction terms (traced scalars ok). Returns
    (p', m', v') or None when the size doesn't tile."""
    total = p.size
    if total % _LANE != 0 or total < 8 * _LANE:
        return None
    if bias1 is None:
        bias1 = 1.0 - beta1 ** step
        bias2 = 1.0 - beta2 ** step
    shape = p.shape
    rows = total // _LANE
    scalars = jnp.stack([
        jnp.asarray(lr, jnp.float32), jnp.asarray(beta1, jnp.float32),
        jnp.asarray(beta2, jnp.float32), jnp.asarray(eps, jnp.float32),
        jnp.asarray(weight_decay, jnp.float32),
        jnp.asarray(bias1, jnp.float32), jnp.asarray(bias2, jnp.float32)])
    p2 = p.reshape(rows, _LANE)
    g2 = g.reshape(rows, _LANE)
    m2 = m.reshape(rows, _LANE).astype(jnp.float32)
    v2 = v.reshape(rows, _LANE).astype(jnp.float32)
    np_, nm, nv = _make_adamw(bool(interpret))(p2, g2, m2, v2, scalars)
    return np_.reshape(shape), nm.reshape(shape), nv.reshape(shape)


# ---------------------------------------------------------------------------
# Softmax (last axis)
# ---------------------------------------------------------------------------
def _softmax_fwd_kernel(x_ref, o_ref):
    x = x_ref[...].astype(jnp.float32)
    m = jnp.max(x, axis=-1, keepdims=True)
    e = jnp.exp(x - m)
    o_ref[...] = (e / jnp.sum(e, axis=-1, keepdims=True)).astype(o_ref.dtype)


def _softmax_bwd_kernel(o_ref, g_ref, dx_ref):
    o = o_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    s = jnp.sum(g * o, axis=-1, keepdims=True)
    dx_ref[...] = (o * (g - s)).astype(dx_ref.dtype)


@functools.lru_cache(maxsize=4)
def _make_softmax(interpret: bool):
    @jax.custom_vjp
    def op(x):
        return fwd(x)[0]

    def fwd(x):
        n, h = x.shape
        bn = _rms_block_rows(n, h)
        o = pl.pallas_call(
            _softmax_fwd_kernel,
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bn, h), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, h), x.dtype),
            interpret=interpret,
        )(x)
        return o, o

    def bwd(o, g):
        n, h = o.shape
        bn = _rms_block_rows(n, h)
        dx = pl.pallas_call(
            _softmax_bwd_kernel,
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((bn, h), lambda i: (i, 0))],
            out_specs=pl.BlockSpec((bn, h), lambda i: (i, 0)),
            out_shape=jax.ShapeDtypeStruct((n, h), o.dtype),
            interpret=interpret,
        )(o, g)
        return (dx,)

    op.defvjp(fwd, bwd)
    return op


def softmax(x, interpret=False):
    """Fused last-axis softmax over rows; None when untileable."""
    h = x.shape[-1]
    lead = 1
    for s in x.shape[:-1]:
        lead *= s
    if h % 128 != 0 or lead % 8 != 0 or x.ndim < 2:
        return None
    out = _make_softmax(bool(interpret))(x.reshape(lead, h))
    return out.reshape(x.shape)


# ---------------------------------------------------------------------------
# LayerNorm (last axis, affine)
# ---------------------------------------------------------------------------
def _ln_fwd_kernel(x_ref, w_ref, b_ref, o_ref, mu_ref, inv_ref, *, eps):
    x = x_ref[...].astype(jnp.float32)
    mu = jnp.mean(x, axis=-1, keepdims=True)
    xc = x - mu
    var = jnp.mean(xc * xc, axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + eps)
    o_ref[...] = (xc * inv * w_ref[...].astype(jnp.float32)
                  + b_ref[...].astype(jnp.float32)).astype(o_ref.dtype)
    mu_ref[...] = mu
    inv_ref[...] = inv


def _ln_bwd_kernel(x_ref, w_ref, mu_ref, inv_ref, g_ref,
                   dx_ref, dw_ref, db_ref, dw_scr, db_scr, *, num_blocks):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        dw_scr[...] = jnp.zeros_like(dw_scr)
        db_scr[...] = jnp.zeros_like(db_scr)

    x = x_ref[...].astype(jnp.float32)
    w = w_ref[...].astype(jnp.float32)
    g = g_ref[...].astype(jnp.float32)
    mu = mu_ref[...]
    inv = inv_ref[...]
    xhat = (x - mu) * inv
    gw = g * w
    m1 = jnp.mean(gw, axis=-1, keepdims=True)
    m2 = jnp.mean(gw * xhat, axis=-1, keepdims=True)
    dx_ref[...] = (inv * (gw - m1 - xhat * m2)).astype(dx_ref.dtype)
    dw_scr[...] += jnp.sum(g * xhat, axis=0, keepdims=True)
    db_scr[...] += jnp.sum(g, axis=0, keepdims=True)

    @pl.when(i == num_blocks - 1)
    def _fin():
        dw_ref[...] = dw_scr[...]
        db_ref[...] = db_scr[...]


@functools.lru_cache(maxsize=8)
def _make_layer_norm(eps: float, interpret: bool):
    @jax.custom_vjp
    def op(x, w, b):
        o, _ = fwd(x, w, b)
        return o

    def fwd(x, w, b):
        n, h = x.shape
        bn = _rms_block_rows(n, h)
        o, mu, inv = pl.pallas_call(
            functools.partial(_ln_fwd_kernel, eps=eps),
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((1, h), lambda i: (0, 0)),
                      pl.BlockSpec((1, h), lambda i: (0, 0))],
            out_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                       pl.BlockSpec((bn, 1), lambda i: (i, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, h), x.dtype),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32),
                       jax.ShapeDtypeStruct((n, 1), jnp.float32)],
            interpret=interpret,
        )(x, w.reshape(1, h), b.reshape(1, h))
        return o, (x, w, b, mu, inv)

    def bwd(res, g):
        x, w, b, mu, inv = res
        n, h = x.shape
        bn = _rms_block_rows(n, h)
        dx, dw, db = pl.pallas_call(
            functools.partial(_ln_bwd_kernel, num_blocks=n // bn),
            grid=(n // bn,),
            in_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                      pl.BlockSpec((1, h), lambda i: (0, 0)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                      pl.BlockSpec((bn, 1), lambda i: (i, 0)),
                      pl.BlockSpec((bn, h), lambda i: (i, 0))],
            out_specs=[pl.BlockSpec((bn, h), lambda i: (i, 0)),
                       pl.BlockSpec((1, h), lambda i: (0, 0)),
                       pl.BlockSpec((1, h), lambda i: (0, 0))],
            out_shape=[jax.ShapeDtypeStruct((n, h), x.dtype),
                       jax.ShapeDtypeStruct((1, h), jnp.float32),
                       jax.ShapeDtypeStruct((1, h), jnp.float32)],
            scratch_shapes=[pltpu.VMEM((1, h), jnp.float32),
                            pltpu.VMEM((1, h), jnp.float32)],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",)),
            interpret=interpret,
        )(x, w.reshape(1, h), mu, inv, g)
        return dx, dw.reshape(w.shape).astype(w.dtype), \
            db.reshape(b.shape).astype(b.dtype)

    op.defvjp(fwd, bwd)
    return op


def layer_norm(x, weight, bias, eps=1e-5, interpret=False):
    """Fused affine LayerNorm over the last dim; None when untileable."""
    h = x.shape[-1]
    lead = 1
    for s in x.shape[:-1]:
        lead *= s
    if h % 128 != 0 or lead % 8 != 0 or x.ndim < 2:
        return None
    out = _make_layer_norm(float(eps), bool(interpret))(
        x.reshape(lead, h), weight, bias)
    return out.reshape(x.shape)
