"""Pallas TPU flash attention (forward + backward).

The TPU-native replacement for the reference's CUDA FA2 kernel
(paddle/phi/kernels/gpu/flash_attn_kernel.cu + third_party flashattn):
online-softmax tiling so the S×S score matrix never hits HBM.

Layout: [B, S, H, D] at the API (reference flash_attention.py convention);
kernels run per (batch*head) over [BH, S, D] with q-block × k-block tiling.

Forward: FlashAttention-2 style — one pass over K/V blocks per Q block with a
running max/denominator in VMEM scratch; emits O and the per-row logsumexp L.
Backward: two kernels (dKdV accumulating over Q blocks; dQ accumulating over
K blocks) using the saved L and D = rowsum(dO ∘ O).

Grid iteration puts the reduction dim last ("arbitrary" semantics) so output
blocks are revisited with live scratch.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

__all__ = ["flash_attention", "flash_attention_fwd_kernel_call"]

NEG_INF = -1e30


def _block_sizes(s_q, s_k, d):
    """v5e-measured defaults (round-4 sweep on the 271M llama train step):
    k-blocks of 1024 beat 512 at every config (+6% at S=2048); q-blocks of
    512 win at S<=4k, 1024 at S>=8k (+5% at S=8192).  128-multiple
    fallbacks keep odd shapes tileable."""
    bq_pref = 1024 if s_q >= 8192 else 512
    bq = next((b for b in (bq_pref, 512, 256, 128) if s_q % b == 0 and b <= s_q),
              s_q)
    bk = next((b for b in (1024, 512, 256, 128) if s_k % b == 0 and b <= s_k),
              s_k)
    return bq, bk


# ---------------------------------------------------------------------------
# The band: causal, and with a sliding ``window`` W query i sees keys
# i-W+1 .. i (positions bottom-right aligned by ``offset`` = s_k - s_q).
# A (q-block i, k-block j) pair RUNS iff some pair in it lies in the band;
# every other block is skipped, not masked.  With a window the index maps
# also clamp the block a skipped step names to the nearest block that runs,
# so that a skipped step fetches nothing new (Pallas re-fetches a block
# only when its index changes).  window=None leaves the causal kernels as
# they were, trace for trace.
# ---------------------------------------------------------------------------
def _block_runs(i, j, block_q, block_k, offset, window):
    run = (j * block_k) <= (i * block_q + block_q - 1 + offset)
    if window is not None:
        run &= (i * block_q + offset - (j * block_k + block_k - 1)) < window
    return run


def _band_mask(s, i, j, block_q, block_k, offset, window):
    q_ids = i * block_q + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 0)
    k_ids = j * block_k + jax.lax.broadcasted_iota(
        jnp.int32, (block_q, block_k), 1)
    keep = q_ids + offset >= k_ids
    if window is not None:
        keep &= (q_ids + offset - k_ids) < window
    return jnp.where(keep, s, NEG_INF)


def _clamp_k_block(i, j, block_q, block_k, num_k_blocks, offset, window):
    """The k-block step (i, j) names: j itself inside q-block i's band, the
    band's nearest end outside it."""
    if window is None:
        return j
    lo = jnp.maximum(i * block_q + offset - (window - 1), 0) // block_k
    hi = jnp.minimum((i * block_q + block_q - 1 + offset) // block_k,
                     num_k_blocks - 1)
    return jnp.clip(j, lo, hi)


def _clamp_q_block(i, j, block_q, block_k, num_q_blocks, offset, window):
    """The q-block step (j, i) of the dK/dV grid names."""
    if window is None:
        return i
    lo = jnp.maximum(j * block_k - offset, 0) // block_q
    hi = jnp.minimum(
        jnp.maximum(j * block_k + block_k - 1 - offset + window - 1, 0)
        // block_q, num_q_blocks - 1)
    return jnp.clip(i, lo, hi)


def _label(pass_, window):
    """kernel_metadata: what a device trace finds the kernel by."""
    label = {"kernel": "flash_attention", "pass": pass_}
    if window is not None:
        label["window"] = int(window)
    return label


# ---------------------------------------------------------------------------
# Forward
# ---------------------------------------------------------------------------
def _dropout_mask(seed_f32, qh, i, j, n_i, n_j, shape, rate):
    """Regenerable keep-mask scale for block (qh, i, j): seeds the per-core
    PRNG deterministically so the backward kernels rebuild the identical
    mask without it ever hitting HBM (the same trick the reference's CUDA
    FA uses with its philox offset).  The TPU PRNG takes at most two seed
    words, so the block coordinates mix into one int32 (unique per block:
    i < n_i, j < n_j are grid sizes)."""
    mix = (qh * n_i + i) * n_j + j
    pltpu.prng_seed(jnp.int32(seed_f32), mix)
    bits = pltpu.bitcast(pltpu.prng_random_bits(shape), jnp.uint32)
    thresh = jnp.uint32(int(rate * 4294967296.0))
    keep = bits >= thresh                       # P(keep) = 1 - rate
    return keep.astype(jnp.float32) / (1.0 - rate)


def _fwd_kernel(q_ref, k_ref, v_ref, *rest, causal, sm_scale, block_q,
                block_k, num_k_blocks, offset, has_segments=False,
                dropout_rate=0.0, num_q_blocks=1, window=None):
    rest = list(rest)
    qseg_ref = kseg_ref = seed_ref = None
    if has_segments:
        qseg_ref, kseg_ref = rest.pop(0), rest.pop(0)
    if dropout_rate > 0.0:
        seed_ref = rest.pop(0)
    o_ref, lse_ref, m_scr, l_scr, acc_scr = rest
    j = pl.program_id(2)  # k-block index (innermost, reduction)
    i = pl.program_id(1)  # q-block index

    @pl.when(j == 0)
    def _init():
        m_scr[:] = jnp.full_like(m_scr, NEG_INF)
        l_scr[:] = jnp.zeros_like(l_scr)
        acc_scr[:] = jnp.zeros_like(acc_scr)

    # causal: process only blocks with k_start <= q_end (and, with a
    # window, k_end >= q_start - window + 1)
    run = True
    if causal:
        run = _block_runs(i, j, block_q, block_k, offset, window)

    @pl.when(run)
    def _body():
        q = q_ref[0]                      # [block_q, d]
        k = k_ref[0]                      # [block_k, d]
        v = v_ref[0]                      # [block_k, d]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * sm_scale  # [bq, bk]
        if causal:
            s = _band_mask(s, i, j, block_q, block_k, offset, window)
        if has_segments:
            qs = qseg_ref[0, :, 0]        # [block_q] (f32 segment ids)
            ks = kseg_ref[0, :, 0]        # [block_k]
            s = jnp.where(qs[:, None] == ks[None, :], s, NEG_INF)
        m_prev = m_scr[:]                 # [bq, 1]
        m_cur = jnp.max(s, axis=1, keepdims=True)
        m_new = jnp.maximum(m_prev, m_cur)
        p = jnp.exp(s - m_new)            # [bq, bk]
        alpha = jnp.exp(m_prev - m_new)   # [bq, 1]
        # the softmax DENOMINATOR uses the un-dropped p (dropout applies to
        # the normalized probabilities); only the V accumulation is masked
        l_new = alpha * l_scr[:] + jnp.sum(p, axis=1, keepdims=True)
        pd = p
        if dropout_rate > 0.0:
            b = pl.program_id(0)
            pd = p * _dropout_mask(seed_ref[0], b, i, j, num_q_blocks,
                                   num_k_blocks, (block_q, block_k),
                                   dropout_rate)
        acc_scr[:] = acc_scr[:] * alpha + jax.lax.dot_general(
            pd.astype(v.dtype), v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[:] = m_new
        l_scr[:] = l_new

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        l = l_scr[:]
        inv = jnp.where(l > 0.0, 1.0 / jnp.where(l > 0.0, l, 1.0), 0.0)
        o_ref[0] = (acc_scr[:] * inv).astype(o_ref.dtype)
        lse_ref[0] = m_scr[:] + jnp.log(jnp.maximum(l, 1e-30))


def _pack_lse(lse3, interpret=False):
    """[bh, s, 1] (tile-padded 1 -> 128 lanes in HBM: 128x memory) ->
    compact [bh, s] via a repack kernel.  A plain squeeze does NOT work:
    XLA lowers it as a bitcast that keeps the padded layout alive — with 24
    saved lse residuals that measured 6 GB of pure padding (the r5 ViT
    OOM).  The grid walks s in fixed-size row CHUNKS (ADVICE r5 #4): a
    full-row block holds ~512·s transient bytes of lane padding in VMEM,
    which overflowed it at s >= ~16k even though the attention kernels
    themselves tile fine there."""
    bh, s, _ = lse3.shape
    chunk = next(b for b in (1024, 512, 256, 128) if s % b == 0)

    def kern(x_ref, o_ref):
        o_ref[0] = x_ref[0][:, 0].reshape(chunk // 128, 128)

    out = pl.pallas_call(
        kern, grid=(bh, s // chunk),
        in_specs=[pl.BlockSpec((1, chunk, 1), lambda b, i: (b, i, 0))],
        out_specs=pl.BlockSpec((1, chunk // 128, 128), lambda b, i: (b, i, 0)),
        out_shape=_sds((bh, s // 128, 128), lse3.dtype, _vma_of(lse3)),
        interpret=interpret,
        metadata={"kernel": "flash_attention", "pass": "lse_pack"},
    )(lse3)
    return out.reshape(bh, s)


def _vma_of(*arrs):
    """Union of manual-axes (shard_map vma) of the inputs: pallas_call
    out_shapes must declare it when the kernel runs inside shard_map."""
    out = frozenset()
    for a in arrs:
        out |= getattr(getattr(a, "aval", None), "vma", frozenset()) or frozenset()
    return out


def _sds(shape, dtype, vma):
    if vma:
        return jax.ShapeDtypeStruct(shape, dtype, vma=vma)
    return jax.ShapeDtypeStruct(shape, dtype)


def flash_attention_fwd_kernel_call(q, k, v, causal, sm_scale, interpret=False,
                                    n_q_heads=None, n_kv_heads=None,
                                    segment_ids=None, dropout_rate=0.0,
                                    dropout_seed=None, window=None):
    """q: [B*Hq, S, D], k/v: [B*Hkv, S, D] -> (o [B*Hq, Sq, D], lse).

    GQA (n_kv_heads < n_q_heads) is handled in the BlockSpec index maps: the
    kernel reads KV blocks of head h // rep directly from HBM — no
    materialized jnp.repeat of K/V (reference flash_attn_kernel.cu GQA path).
    """
    bh, s_q, d = q.shape
    s_k = k.shape[1]
    hq = n_q_heads or 1
    hkv = n_kv_heads or hq
    rep = hq // hkv
    block_q, block_k = _block_sizes(s_q, s_k, d)
    grid = (bh, s_q // block_q, s_k // block_k)

    def kv_idx(b, i, j):
        return ((b // hq) * hkv + (b % hq) // rep,
                _clamp_k_block(i, j, block_q, block_k, s_k // block_k,
                               s_k - s_q, window), 0)

    has_seg = segment_ids is not None
    kernel = functools.partial(
        _fwd_kernel, causal=causal, sm_scale=sm_scale, block_q=block_q,
        block_k=block_k, num_k_blocks=s_k // block_k, offset=s_k - s_q,
        has_segments=has_seg, dropout_rate=dropout_rate,
        num_q_blocks=s_q // block_q, window=window)

    in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_idx),
        pl.BlockSpec((1, block_k, d), kv_idx),
    ]
    args = [q, k, v]
    if has_seg:
        # segment ids per batch row [B, S] (f32), broadcast over heads.
        # The [B, S, 1] kernel view tile-pads 1 -> 128 lanes, but only as a
        # TRANSIENT around this call (the caller holds compact [B, S]) —
        # TPU Pallas requires the last two block dims (8, 128)-aligned, so
        # a 2-D (1, block) spec is not lowerable.
        seg3 = segment_ids[:, :, None]
        in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b // hq, i, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, i, j: (b // hq, j, 0)),
        ]
        args += [seg3, seg3]
    if dropout_rate > 0.0:
        in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)]
        args += [jnp.asarray(dropout_seed, jnp.float32).reshape(1)]
    o, lse3 = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=[
            pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b, i, 0)),
        ],
        out_shape=[
            _sds((bh, s_q, d), q.dtype, _vma_of(q, k, v)),
            _sds((bh, s_q, 1), jnp.float32, _vma_of(q, k, v)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata=_label("fwd", window),
    )(*args)
    # COMPACT 2-D lse for the caller: the [bh, s, 1] kernel output tile-pads
    # its last dim 1 -> 128 in HBM (measured 128x, 256 MB per ViT layer);
    # _pack_lse forces a real re-layout (a squeeze is just a bitcast) so
    # saved residuals cost s_q * 4 bytes per row, not 512
    return o, _pack_lse(lse3, interpret=interpret)


# ---------------------------------------------------------------------------
# Backward
# ---------------------------------------------------------------------------
def _col_from_packed(ref, i, block_q, scr):
    """Load this q-block's per-row stats from a COMPACT [s//128, 128] packed
    row into a [block_q, 1] VMEM column.  The slice-store loop is the
    relayout Mosaic can lower (a lanes->sublanes reshape is not); keeping
    lse/delta packed end-to-end means the backward never materializes the
    128x tile-padded [bh, s, 1] HBM tensors (the r5 ViT OOM came back via
    scheduler-hoisted unpack kernels)."""
    nch = block_q // 128
    chunk = ref[0, pl.ds(i * nch, nch)]            # [bq//128, 128]
    for t in range(nch):
        scr[t * 128:(t + 1) * 128, 0] = chunk[t]
    return scr[:]


def _bwd_dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                    causal, sm_scale, block_q, block_k, num_q_blocks,
                    rep_heads, offset, has_segments=False, dropout_rate=0.0,
                    hq=1, hkv=1, num_k_blocks=1, window=None):
    rest = list(rest)
    qseg_ref = kseg_ref = seed_ref = None
    if has_segments:
        qseg_ref, kseg_ref = rest.pop(0), rest.pop(0)
    if dropout_rate > 0.0:
        seed_ref = rest.pop(0)
    dk_ref, dv_ref, dk_scr, dv_scr, lse_scr, delta_scr = rest
    # grid (bh_kv, j, rr, i): rr walks the rep q-heads sharing this kv head
    # (GQA — dk/dv accumulate over them), i walks q blocks
    j = pl.program_id(1)  # k-block
    rr = pl.program_id(2)  # q-head within the kv group (reduction)
    i = pl.program_id(3)  # q-block (reduction)

    @pl.when((i == 0) & (rr == 0))
    def _init():
        dk_scr[:] = jnp.zeros_like(dk_scr)
        dv_scr[:] = jnp.zeros_like(dv_scr)

    run = True
    if causal:
        run = _block_runs(i, j, block_q, block_k, offset, window)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = _col_from_packed(lse_ref, i, block_q, lse_scr)    # [bq, 1]
        delta = _col_from_packed(delta_ref, i, block_q, delta_scr)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _band_mask(s, i, j, block_q, block_k, offset, window)
        if has_segments:
            s = jnp.where(qseg_ref[0, :, 0][:, None]
                          == kseg_ref[0, :, 0][None, :], s, NEG_INF)
        p = jnp.exp(s - lse)                            # [bq, bk]
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            # rebuild the forward's mask for THIS q-head block: the fwd grid
            # b was the global q-row index
            b = pl.program_id(0)
            qh = (b // hkv) * hq + (b % hkv) * rep_heads + rr
            m = _dropout_mask(seed_ref[0], qh, i, j, num_q_blocks,
                              num_k_blocks, (block_q, block_k),
                              dropout_rate)
            pd = p * m
            dp = dp * m
        else:
            pd = p
        # dv += (masked p)^T do
        dv_scr[:] += jax.lax.dot_general(
            pd, do, (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        # ds = p * (masked dp - delta) * scale  (delta = rowsum(do∘o) holds
        # with dropout too: o already contains the mask)
        ds = p * (dp - delta) * sm_scale
        # dk += ds^T q
        dk_scr[:] += jax.lax.dot_general(
            ds, q.astype(jnp.float32), (((0,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when((i == num_q_blocks - 1) & (rr == rep_heads - 1))
    def _finalize():
        dk_ref[0] = dk_scr[:].astype(dk_ref.dtype)
        dv_ref[0] = dv_scr[:].astype(dv_ref.dtype)


def _bwd_dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref, *rest,
                   causal, sm_scale, block_q, block_k, num_k_blocks, offset,
                   has_segments=False, dropout_rate=0.0, num_q_blocks=1,
                   window=None):
    rest = list(rest)
    qseg_ref = kseg_ref = seed_ref = None
    if has_segments:
        qseg_ref, kseg_ref = rest.pop(0), rest.pop(0)
    if dropout_rate > 0.0:
        seed_ref = rest.pop(0)
    dq_ref, dq_scr, lse_scr, delta_scr = rest
    j = pl.program_id(2)  # k-block (reduction)
    i = pl.program_id(1)  # q-block

    @pl.when(j == 0)
    def _init():
        dq_scr[:] = jnp.zeros_like(dq_scr)

    run = True
    if causal:
        run = _block_runs(i, j, block_q, block_k, offset, window)

    @pl.when(run)
    def _body():
        q = q_ref[0]
        k = k_ref[0]
        v = v_ref[0]
        do = do_ref[0].astype(jnp.float32)
        lse = _col_from_packed(lse_ref, i, block_q, lse_scr)
        delta = _col_from_packed(delta_ref, i, block_q, delta_scr)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * sm_scale
        if causal:
            s = _band_mask(s, i, j, block_q, block_k, offset, window)
        if has_segments:
            s = jnp.where(qseg_ref[0, :, 0][:, None]
                          == kseg_ref[0, :, 0][None, :], s, NEG_INF)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(do, v.astype(jnp.float32),
                                 (((1,), (1,)), ((), ())),
                                 preferred_element_type=jnp.float32)
        if dropout_rate > 0.0:
            b = pl.program_id(0)          # this grid's b IS the q-row index
            dp = dp * _dropout_mask(seed_ref[0], b, i, j, num_q_blocks,
                                    num_k_blocks, (block_q, block_k),
                                    dropout_rate)
        ds = p * (dp - delta) * sm_scale
        dq_scr[:] += jax.lax.dot_general(
            ds, k.astype(jnp.float32), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(j == num_k_blocks - 1)
    def _finalize():
        dq_ref[0] = dq_scr[:].astype(dq_ref.dtype)


def _bwd_call(res, g, causal, sm_scale, interpret, n_q_heads=None,
              n_kv_heads=None, segment_ids=None, delta=None,
              dropout_rate=0.0, dropout_seed=None, window=None):
    q, k, v, o, lse = res
    do = g
    bh, s_q, d = q.shape
    bh_kv, s_k, _ = k.shape
    hq = n_q_heads or 1
    hkv = n_kv_heads or hq
    rep = hq // hkv
    block_q, block_k = _block_sizes(s_q, s_k, d)
    if delta is None:   # ring callers precompute it once across hops
        delta = jnp.sum(do.astype(jnp.float32) * o.astype(jnp.float32),
                        axis=-1)                 # [bh, s_q] compact 2-D
    # lse/delta stay PACKED [bh, s//128, 128] end to end: the kernels read
    # full packed rows and relayout per q-block in VMEM (_col_from_packed)
    if lse.ndim == 3 and lse.shape[-1] == 1:
        lse = _pack_lse(lse, interpret)
    if delta.ndim == 3 and delta.shape[-1] == 1:
        delta = _pack_lse(delta, interpret)
    nch_q = s_q // 128
    lse_p = lse.reshape(bh, nch_q, 128)
    delta_p = delta.reshape(bh, nch_q, 128)
    has_seg = segment_ids is not None
    seed_arr = (jnp.asarray(dropout_seed, jnp.float32).reshape(1)
                if dropout_rate > 0.0 else None)

    def q_idx_dkv(b, j, rr, i):
        # b indexes B*Hkv; the q head is the rr-th member of its kv group
        return ((b // hkv) * hq + (b % hkv) * rep + rr,
                _clamp_q_block(i, j, block_q, block_k, s_q // block_q,
                               s_k - s_q, window), 0)

    def kv_idx_dkv(b, j, rr, i):
        return (b, j, 0)

    def stats_idx_dkv(b, j, rr, i):
        # full packed row of the rr-th q head in this kv group
        return ((b // hkv) * hq + (b % hkv) * rep + rr, 0, 0)

    dkv_in_specs = [
        pl.BlockSpec((1, block_q, d), q_idx_dkv),
        pl.BlockSpec((1, block_k, d), kv_idx_dkv),
        pl.BlockSpec((1, block_k, d), kv_idx_dkv),
        pl.BlockSpec((1, block_q, d), q_idx_dkv),
        pl.BlockSpec((1, nch_q, 128), stats_idx_dkv),
        pl.BlockSpec((1, nch_q, 128), stats_idx_dkv),
    ]
    dkv_args = [q, k, v, do, lse_p, delta_p]
    if has_seg:
        seg3 = segment_ids[:, :, None]
        dkv_in_specs += [
            pl.BlockSpec((1, block_q, 1),
                         lambda b, j, rr, i: (b // hkv, i, 0)),
            pl.BlockSpec((1, block_k, 1),
                         lambda b, j, rr, i: (b // hkv, j, 0)),
        ]
        dkv_args += [seg3, seg3]
    if dropout_rate > 0.0:
        dkv_in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)]
        dkv_args += [seed_arr]

    dkv = pl.pallas_call(
        functools.partial(_bwd_dkv_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k,
                          num_q_blocks=s_q // block_q, rep_heads=rep,
                          offset=s_k - s_q, has_segments=has_seg,
                          dropout_rate=dropout_rate, hq=hq, hkv=hkv,
                          num_k_blocks=s_k // block_k, window=window),
        grid=(bh_kv, s_k // block_k, rep, s_q // block_q),
        in_specs=dkv_in_specs,
        out_specs=[
            pl.BlockSpec((1, block_k, d), kv_idx_dkv),
            pl.BlockSpec((1, block_k, d), kv_idx_dkv),
        ],
        out_shape=[
            _sds((bh_kv, s_k, d), k.dtype, _vma_of(q, k, v, do)),
            _sds((bh_kv, s_k, d), v.dtype, _vma_of(q, k, v, do)),
        ],
        scratch_shapes=[
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_k, d), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
            pltpu.VMEM((block_q, 1), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary",
                                 "arbitrary")),
        interpret=interpret,
        metadata=_label("dkv", window),
    )(*dkv_args)
    dk, dv = dkv

    def kv_idx_dq(b, i, j):
        return ((b // hq) * hkv + (b % hq) // rep,
                _clamp_k_block(i, j, block_q, block_k, s_k // block_k,
                               s_k - s_q, window), 0)

    dq_in_specs = [
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, block_k, d), kv_idx_dq),
        pl.BlockSpec((1, block_k, d), kv_idx_dq),
        pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        pl.BlockSpec((1, nch_q, 128), lambda b, i, j: (b, 0, 0)),
        pl.BlockSpec((1, nch_q, 128), lambda b, i, j: (b, 0, 0)),
    ]
    dq_args = [q, k, v, do, lse_p, delta_p]
    if has_seg:
        dq_in_specs += [
            pl.BlockSpec((1, block_q, 1), lambda b, i, j: (b // hq, i, 0)),
            pl.BlockSpec((1, block_k, 1), lambda b, i, j: (b // hq, j, 0)),
        ]
        dq_args += [segment_ids[:, :, None], segment_ids[:, :, None]]
    if dropout_rate > 0.0:
        dq_in_specs += [pl.BlockSpec(memory_space=pltpu.SMEM)]
        dq_args += [seed_arr]

    dq = pl.pallas_call(
        functools.partial(_bwd_dq_kernel, causal=causal, sm_scale=sm_scale,
                          block_q=block_q, block_k=block_k,
                          num_k_blocks=s_k // block_k, offset=s_k - s_q,
                          has_segments=has_seg, dropout_rate=dropout_rate,
                          num_q_blocks=s_q // block_q, window=window),
        grid=(bh, s_q // block_q, s_k // block_k),
        in_specs=dq_in_specs,
        out_specs=pl.BlockSpec((1, block_q, d), lambda b, i, j: (b, i, 0)),
        out_shape=_sds((bh, s_q, d), q.dtype, _vma_of(q, k, v, do)),
        scratch_shapes=[pltpu.VMEM((block_q, d), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32),
                        pltpu.VMEM((block_q, 1), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        metadata=_label("dq", window),
    )(*dq_args)
    return dq, dk, dv


# ---------------------------------------------------------------------------
# Public op: [B, S, H, D] layout with custom VJP
# ---------------------------------------------------------------------------
@functools.lru_cache(maxsize=16)
def _make_op(causal: bool, interpret: bool, has_segments: bool = False,
             dropout_rate: float = 0.0, window: int = None):
    """has_segments: op takes an extra arg seg [B, S] (f32 segment ids —
    intra-segment attention only, the varlen/flash_attn_unpadded mask;
    f32 so custom_vjp's cotangent contract stays uniform).

    dropout_rate > 0: op takes a trailing f32 scalar-array seed; the
    attention-probability dropout runs INSIDE the kernels (per-block
    regenerable PRNG — the S×S mask never exists in HBM), which is what
    keeps dropout-training configs (ERNIE/BERT pretrain) on the flash path
    instead of the materializing XLA fallback."""
    has_drop = dropout_rate > 0.0

    def _fwd(q, k, v, *rest):
        rest = list(rest)
        sids = rest.pop(0) if has_segments else None
        seed = rest.pop(0) if has_drop else None
        b, s_q, h, d = q.shape
        s_k = k.shape[1]
        hkv = k.shape[2]
        sm_scale = 1.0 / math.sqrt(d)
        qr = q.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
        kr = k.transpose(0, 2, 1, 3).reshape(b * hkv, s_k, d)
        vr = v.transpose(0, 2, 1, 3).reshape(b * hkv, s_k, d)
        o, lse = flash_attention_fwd_kernel_call(qr, kr, vr, causal, sm_scale,
                                                 interpret, n_q_heads=h,
                                                 n_kv_heads=hkv,
                                                 segment_ids=sids,
                                                 dropout_rate=dropout_rate,
                                                 dropout_seed=seed,
                                                 window=window)
        o4 = o.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
        # name the bwd residuals so a save_only_these_names("fa_res") remat
        # policy keeps them and the backward skips re-running the fwd kernel
        from jax.ad_checkpoint import checkpoint_name
        res = tuple(checkpoint_name(x, "fa_res") for x in (qr, kr, vr, o, lse))
        return o4, res + (sids, seed, (b, h, hkv, s_q, s_k, d))

    n_extra = (1 if has_segments else 0) + (1 if has_drop else 0)
    if n_extra == 2:
        @jax.custom_vjp
        def op(q, k, v, seg, seed):
            return _fwd(q, k, v, seg, seed)[0]

        def fwd(q, k, v, seg, seed):
            return _fwd(q, k, v, seg, seed)
    elif n_extra == 1:
        @jax.custom_vjp
        def op(q, k, v, extra):
            return _fwd(q, k, v, extra)[0]

        def fwd(q, k, v, extra):
            return _fwd(q, k, v, extra)
    else:
        @jax.custom_vjp
        def op(q, k, v):
            return _fwd(q, k, v)[0]

        def fwd(q, k, v):
            return _fwd(q, k, v)

    def bwd(res, g):
        qr, kr, vr, o, lse, sids, seed, (b, h, hkv, s_q, s_k, d) = res
        sm_scale = 1.0 / math.sqrt(d)
        do = g.transpose(0, 2, 1, 3).reshape(b * h, s_q, d)
        dq, dk, dv = _bwd_call((qr, kr, vr, o, lse), do, causal, sm_scale,
                               interpret, n_q_heads=h, n_kv_heads=hkv,
                               segment_ids=sids, dropout_rate=dropout_rate,
                               dropout_seed=seed, window=window)
        dq4 = dq.reshape(b, h, s_q, d).transpose(0, 2, 1, 3)
        dk4 = dk.reshape(b, hkv, s_k, d).transpose(0, 2, 1, 3)
        dv4 = dv.reshape(b, hkv, s_k, d).transpose(0, 2, 1, 3)
        extras = ()
        if has_segments:
            extras += (jnp.zeros_like(sids),)
        if has_drop:
            extras += (jnp.zeros_like(seed),)
        return (dq4, dk4, dv4) + extras

    op.defvjp(fwd, bwd)
    return op


def _supported(q_shape, k_shape, causal=False):
    b, s_q, h, d = q_shape
    s_k = k_shape[1]
    hkv = k_shape[2]
    if h % hkv != 0:
        return False
    if d > 256 or d % 8 != 0:
        return False
    if causal and s_q > s_k:
        # bottom-right-aligned causal leaves rows [0, s_q - s_k) with zero
        # valid keys; their softmax is ill-defined and the XLA fallback's
        # uniform-weight convention differs from FA's zero-output — defer to
        # the fallback for this shape.
        return False
    for s in (s_q, s_k):
        if s % 128 != 0:
            return False
    return True


def _pad_to_tile(q, k, v, segment_ids):
    """Pad an untileable sequence length up to the next 128-multiple and
    mask the tail via the kernel's segment ids (padding gets a segment of
    its own, so real tokens never attend it).  This is what keeps e.g.
    ViT's S=197 attention on the flash path instead of the
    [B,H,S,S]-materializing XLA fallback (round-5 ViT profile: the
    materialized probs were both the memory AND the throughput ceiling)."""
    b, s, h, d = q.shape
    pad = (-s) % 128
    qp = jnp.pad(q, [(0, 0), (0, pad), (0, 0), (0, 0)])
    kp = jnp.pad(k, [(0, 0), (0, pad), (0, 0), (0, 0)])
    vp = jnp.pad(v, [(0, 0), (0, pad), (0, 0), (0, 0)])
    if segment_ids is None:
        seg = jnp.zeros((b, s), jnp.float32)
    else:
        seg = segment_ids.astype(jnp.float32)
    # the pad segment id must differ from every real id (real ids are
    # small non-negative ints in practice; -1 stays distinct)
    segp = jnp.pad(seg, [(0, 0), (0, pad)], constant_values=-1.0)
    return qp, kp, vp, segp, s


def flash_attention_ref(q, k, v, causal=False, window=None):
    """jnp reference with identical semantics to the kernel's core path
    ([B, S, H, D] layout, GQA via up-materialized K/V, fp32 softmax) — the
    parity tests' oracle and the off-TPU dispatch fallback.  Materializes
    the [B, H, S, S] score tensor; use the kernel for real workloads.
    ``window`` (causal only): query i sees keys i-window+1 .. i."""
    d = q.shape[-1]
    if k.shape[2] != q.shape[2]:  # GQA: up-materialize only in the fallback
        rep = q.shape[2] // k.shape[2]
        k = jnp.repeat(k, rep, axis=2)
        v = jnp.repeat(v, rep, axis=2)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k).astype(jnp.float32) \
        / math.sqrt(d)
    if causal:
        sq, sk = s.shape[-2], s.shape[-1]
        mask = jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq)
        if window is not None:
            mask &= ~jnp.tril(jnp.ones((sq, sk), bool), k=sk - sq - window)
        s = jnp.where(mask, s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v)


def flash_attention(q, k, v, causal=False, interpret=False, segment_ids=None,
                    dropout_rate=0.0, dropout_seed=None, window=None):
    """[B, S, H, D] flash attention; falls back unsupported shapes to the
    caller (returns None so the dispatch default runs).

    segment_ids: optional int [B, S] — attention stays within equal-id
    spans (the varlen/flash_attn_unpadded mask; reference
    flash_attn_kernel.cu varlen entries). Requires s_q == s_k.

    dropout_rate/dropout_seed: in-kernel attention-probability dropout
    (per-block regenerable PRNG; the mask never exists in HBM).  seed may
    be a traced scalar — it does not bake into the executable.

    window: optional static int, with causal=True — query i sees keys
    i-window+1 .. i; blocks wholly outside that band are skipped.  None
    is the causal kernel as it always was.
    """
    if window is not None:
        if not causal or int(window) < 1:
            raise ValueError("a sliding window needs causal=True and "
                             f"window >= 1 (got causal={causal}, "
                             f"window={window})")
        window = int(window)
    drop = float(dropout_rate or 0.0)
    if drop >= 1.0:
        # torch/paddle semantics: dropout_p == 1 zeroes the output (the
        # kernel's uint32 threshold would wrap and emit inf instead).
        # Checked BEFORE pad-to-tile so the zeros match the caller's shape.
        return jnp.zeros_like(q)
    unpad_to = None
    if not _supported(q.shape, k.shape, causal):
        s_q, s_k = q.shape[1], k.shape[1]
        # pad-to-tile engages only for LONG untileable sequences: at short S
        # the padded kernel's small tiles starve the MXU and lose to XLA's
        # fused-softmax path (measured r5: ViT S=197->256 B=64, FA-pad 197
        # img/s vs XLA 243) while the memory it saves is modest; at S >= 384
        # the S^2 materialization cost dominates and FA wins
        tileable = (s_q == s_k and s_q % 128 != 0 and s_q >= 384
                    and _supported(q.shape[:1] + (128,) + q.shape[2:],
                                   k.shape[:1] + (128,) + k.shape[2:],
                                   causal))
        if not tileable:
            return None
        q, k, v, segment_ids, unpad_to = _pad_to_tile(q, k, v, segment_ids)
    extras = ()
    has_seg = segment_ids is not None
    if has_seg:
        if q.shape[1] != k.shape[1]:
            return None
        extras += (segment_ids.astype(jnp.float32),)
    if drop > 0.0:
        if dropout_seed is None:
            # fresh mask per call (the reference CUDA FA draws a philox seed
            # when none is fixed) — a constant default would repeat the
            # identical mask every step and layer
            from ...core.random import split_key
            dropout_seed = jax.random.randint(split_key(), (), 0, 1 << 23)
        extras += (jnp.asarray(dropout_seed, jnp.float32),)
    out = _make_op(bool(causal), bool(interpret), has_seg, drop, window)(
        q, k, v, *extras)
    if unpad_to is not None:
        out = out[:, :unpad_to]
    return out
