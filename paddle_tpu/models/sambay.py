"""SambaY (``model_type: phi4flash``: Phi-4-mini-flash-reasoning, the
decoder-hybrid-decoder of arXiv:2507.06607) for the serving engine: FIVE
kinds of layer, a mixer and a gated MLP each,

    x = x + Mixer_i(LN(x));   x = x + W_down(silu(g) * u),  [g | u] = LN(x) W_gate_up

then ``LN_f`` and the TIED head.  With L layers (32) and ``h = L / 2``:

  mamba   i even, i <= h.  Mamba-1: [u | z] = v W_in; u = silu(conv4(u) + b);
          [r | B | C] = u W_x; dt = softplus(r W_dt + b_dt); A = -exp(A_log);
          s_t = exp(dt_t A) s_{t-1} + dt_t u_t (x) B_t; y_t = s_t C_t + D u_t
          (`ops/ssm.py`: `selective_scan` for a run, `selective_update` for
          decode); out = (y * silu(z)) W_out.  Layer h also hands on its
          MEMORY m_t = y_t (after the D u skip, before the gate).
  window  i odd, i < h.  Differential attention over the last
          ``sliding_window`` keys (the query's own counted), K/V of its own.
  full    i = h + 1.  Differential attention over every key; its K/V are
          THE page store of the model.
  gmu     i even, i > h.  out = (silu(v W_1) * m_t) W_2: no state, the memory
          of the SAME token from layer h.
  cross   i odd, i > h + 1.  Differential attention of its own queries over
          layer h + 1's K/V, read from its pages: no W_k, no W_v, no state.

Differential attention (no positional term of any kind: the Mamba layers
carry order): heads come in pairs, ``q1_j = q_2j, q2_j = q_2j+1``; K/V heads
too, ``k1_p = k_2p, k2_p = k_2p+1, V_p = [v_2p | v_2p+1]``; pair j reads
``p = j // (pairs of q / pairs of kv)``;
``o_j = (1 - lam0) RMSNorm(P1_j V_p - lam P2_j V_p)`` with two softmaxes
``P1 = softmax(q1 k1 / sqrt(hd))``, ``P2`` likewise, ``lam = exp(lq1 . lk1)
- exp(lq2 . lk2) + lam0``, ``lam0 = 0.8 - 0.6 exp(-0.3 i)`` by LAYER index.
The pairing packs: a row ``[k_2p | k_2p+1]`` is 2 hd = 128 wide, a query
``[q_2j | 0]`` scores it as q1 . k1 and ``[0 | q_2j+1]`` as q2 . k2, and the
value under either is V_p — so the ragged paged-attention kernel serves it
as GQA with 2 hd-wide heads, four queries a K/V head, and nothing new.

The cache of the paged fns (`build_sambay_paged`) holds THREE kinds of state
in one pytree:

  ``k`` / ``v [1, kv pairs, NP+1, ps, 2 hd]``   ONE page store, layer h + 1's,
          which that layer writes and it and every cross layer read;
  ``win_k`` / ``win_v [window layers, kv pairs, slots * W / ps + 1, ps, 2 hd]``
          a RING of exactly ``sliding_window`` W tokens a slot and window
          layer, laid out as W / ps pages so that the same kernel reads it:
          position p lies at row p mod W, which is the row of p - W, the
          newest key outside p's window.  A decode step writes, then attends
          min(p + 1, W) rows — every row is in the window, whatever their
          order.  It never grows with the context;
  ``conv [mamba layers, slots, K-1, d_inner]`` and ``ssm``, a tuple of
          ``[slots, N, d_inner]`` float32 leaves, one a Mamba layer (its own
          buffer: `models/nemotron_h.py` says why), the state ``[N, D]`` for
          the lanes' sake (`ops/ssm.py`);

and ``ctr``, the counters the fns accumulate on the device.

A run of a prompt's tokens (dense prefill, a prefill chunk) goes through
layers 0 .. h and the K/V layer's two K/V projections only; that layer's
queries, W_o and MLP and layers h + 2 .. L - 1 run for the prompt's LAST
token alone (it needs m of that token and its own queries over the pages;
nothing reads another token's) — the architecture's linear-time prefill, and
the logits are the full forward's.  A chunk that is not its prompt's last
returns no logits (``last=False``, a static argument: that executable holds
half the layers and no attention over the store).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..ops.ssm import selective_scan, selective_update
from ..profiler import device_span
from .llama import scatter_kv_rows, scatter_kv_run
from .paged_family import PagedFamily

__all__ = ["SambaYConfig", "sambay_config_tiny", "build_functional_sambay",
           "build_sambay_paged", "layer_kinds", "lambda_init",
           "diff_attention_pairs"]

KINDS = ("mamba", "window", "full", "gmu", "cross")
CARRY = 1 << 20                 # a counter's low word carries over at this
WINDOW_BLOCK = 128              # queries a block of a run's window attention


@dataclasses.dataclass
class SambaYConfig:
    """The public ``config.json`` keys of a phi4flash model, and the sizes
    its config does not carry (``mamba_*``, ``time_step_*``: the family's
    Mamba-1 defaults)."""
    vocab_size: int = 200064
    hidden_size: int = 2560
    intermediate_size: int = 10240
    num_hidden_layers: int = 32
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    mb_per_layer: int = 2
    layer_norm_eps: float = 1e-5
    max_position_embeddings: int = 262144
    hidden_act: str = "silu"
    embd_pdrop: float = 0.0
    resid_pdrop: float = 0.0
    tie_word_embeddings: bool = True
    mlp_bias: bool = False
    lm_head_bias: bool = False
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: Optional[int] = None       # None: ceil(hidden_size / 16)
    time_step_min: float = 0.001
    time_step_max: float = 0.1

    @property
    def head_dim(self):
        return self.hidden_size // self.num_attention_heads

    @property
    def d_inner(self):
        return self.mamba_expand * self.hidden_size

    @property
    def dt_rank(self):
        return self.mamba_dt_rank or -(-self.hidden_size // 16)

    def validate(self):
        if self.num_hidden_layers % 4 or self.num_hidden_layers < 8:
            raise ValueError(
                f"num_hidden_layers {self.num_hidden_layers}: the layer "
                f"pattern (Mamba every second layer, the K/V layer after "
                f"the first half) needs a multiple of 4, at least 8")
        if self.mb_per_layer != 2:
            raise ValueError(f"mb_per_layer {self.mb_per_layer}: only a "
                             f"Mamba layer every SECOND layer is built")
        if self.embd_pdrop or self.resid_pdrop:
            raise ValueError("dropout is not built (a serving path)")
        if not self.tie_word_embeddings or self.mlp_bias \
                or self.lm_head_bias or self.hidden_act != "silu":
            raise ValueError("phi4flash as built here has a tied head, no "
                             "bias in the MLP or the head, and silu")
        if self.hidden_size % self.num_attention_heads \
                or self.num_attention_heads % 2 \
                or self.num_key_value_heads % 2 \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("differential attention pairs the query heads "
                             "and the K/V heads: both counts must be even, "
                             "and the K/V heads must divide the query heads")
        if self.sliding_window < 1:
            raise ValueError("sliding_window must be a positive key count")

    def paged_family(self, **build_kw) -> PagedFamily:
        """The seam `inference.paged.ServingEngine` builds its fns through."""
        return build_sambay_paged(self, **build_kw)


def sambay_config_tiny(**kw):
    """The CPU tests' size: every kind of layer present, nothing at scale."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                num_hidden_layers=8, num_attention_heads=8,
                num_key_value_heads=4, sliding_window=16,
                max_position_embeddings=512, mamba_d_state=8)
    base.update(kw)
    return SambaYConfig(**base)


def layer_kinds(config):
    """[(kind, index within its kind)] of the layers."""
    half = config.num_hidden_layers // 2
    seen, out = {}, []
    for i in range(config.num_hidden_layers):
        if i % 2 == 0:
            kind = "mamba" if i <= half else "gmu"
        else:
            kind = "window" if i < half else \
                "full" if i == half + 1 else "cross"
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def kind_counts(config):
    return {kind: sum(1 for k, _ in layer_kinds(config) if k == kind)
            for kind in KINDS}


def lambda_init(layer):
    """lam0 of the attention layer at index ``layer`` (0-based, counted over
    ALL layers)."""
    return 0.8 - 0.6 * math.exp(-0.3 * layer)


def build_functional_sambay(config: SambaYConfig, key=None, dtype=None):
    """(embed, blocks, head) from a seed: matrices normal / sqrt(fan_in)
    (0.02 for the embedding, which is also the head), biases normal x 0.02,
    LayerNorm weights ones; a Mamba layer's ``dt`` bias the inverse softplus
    of a log-uniform step in [time_step_min, time_step_max], ``A_log`` the
    log of 1 .. N along the state index, ``D`` ones, the convolution's bias
    normal x 0.1; the lambda vectors normal x 0.1, the sub-norm ones.
    ``blocks[kind][leaf]`` is a tuple, one array a layer of that kind
    (``"mlp"``: every layer); a layer's leaf is its own buffer
    (`models/nemotron_h.py` says why).  Jit it."""
    c = config
    c.validate()
    d = jnp.dtype(dtype) if dtype is not None else jnp.float32
    key = key if key is not None else jax.random.PRNGKey(0)
    f32 = jnp.float32
    H, F, hd = c.hidden_size, c.intermediate_size, c.head_dim
    q_dim, kv_dim = c.num_attention_heads * hd, c.num_key_value_heads * hd
    d_in, N, K, R = c.d_inner, c.mamba_d_state, c.mamba_d_conv, c.dt_rank

    def init(k, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, f32) * scale).astype(d)

    def bias(k, n, scale=0.02):
        return jax.random.normal(k, (n,), f32) * scale

    def norm(k):
        return {"ln_w": jnp.ones((H,), f32), "ln_b": bias(k, H)}

    def mamba_layer(k):
        ks = jax.random.split(k, 8)
        step = jnp.exp(jax.random.uniform(ks[0], (d_in,), f32) * (
            math.log(c.time_step_max) - math.log(c.time_step_min))
            + math.log(c.time_step_min))
        return {**norm(ks[1]),
                "w_in": init(ks[2], (H, 2 * d_in)),
                "conv_w": init(ks[3], (K, d_in), 1.0 / math.sqrt(K)),
                "conv_b": bias(ks[4], d_in, 0.1),
                "w_x": init(ks[5], (d_in, R + 2 * N)),
                "w_dt": init(ks[6], (R, d_in)),
                "dt_bias": step + jnp.log(-jnp.expm1(-step)),
                "A_log": jnp.broadcast_to(
                    jnp.log(jnp.arange(1, N + 1, dtype=f32)), (d_in, N)),
                "D": jnp.ones((d_in,), f32),
                "w_out": init(ks[7], (d_in, H))}

    def attn_layer(k, own_kv=True):
        ks = jax.random.split(k, 12)
        lp = {**norm(ks[0]),
              "wq": init(ks[1], (H, q_dim)), "bq": bias(ks[2], q_dim),
              "wo": init(ks[3], (q_dim, H)), "bo": bias(ks[4], H),
              "lq1": bias(ks[5], hd, 0.1), "lk1": bias(ks[6], hd, 0.1),
              "lq2": bias(ks[7], hd, 0.1), "lk2": bias(ks[8], hd, 0.1),
              "sub_w": jnp.ones((2 * hd,), f32)}
        if own_kv:
            kk = jax.random.split(ks[9], 4)
            lp.update(wk=init(kk[0], (H, kv_dim)), bk=bias(kk[1], kv_dim),
                      wv=init(kk[2], (H, kv_dim)), bv=bias(kk[3], kv_dim))
        return lp

    def gmu_layer(k):
        ks = jax.random.split(k, 3)
        return {**norm(ks[0]), "w1": init(ks[1], (H, d_in)),
                "w2": init(ks[2], (d_in, H))}

    def mlp_layer(k):
        ks = jax.random.split(k, 3)
        return {**norm(ks[0]), "w_gate_up": init(ks[1], (H, 2 * F)),
                "w_down": init(ks[2], (F, H))}

    make = {"mamba": mamba_layer, "window": attn_layer, "full": attn_layer,
            "gmu": gmu_layer,
            "cross": lambda k: attn_layer(k, own_kv=False)}
    k_layers, k_mlp, k_embed, k_head = jax.random.split(key, 4)
    kinds = layer_kinds(c)
    layers = [make[kind](k) for (kind, _), k in zip(
        kinds, jax.random.split(k_layers, c.num_hidden_layers))]
    blocks = {}
    for kind in KINDS:
        mine = [lp for (kd, _), lp in zip(kinds, layers) if kd == kind]
        blocks[kind] = {leaf: tuple(lp[leaf] for lp in mine)
                        for leaf in mine[0]}
    mlps = [mlp_layer(k) for k in jax.random.split(k_mlp,
                                                   c.num_hidden_layers)]
    blocks["mlp"] = {leaf: tuple(lp[leaf] for lp in mlps) for leaf in mlps[0]}
    embed = {"tok": init(k_embed, (c.vocab_size, H), 0.02)}
    head = {"ln_w": jnp.ones((H,), f32), "ln_b": bias(k_head, H)}
    return embed, blocks, head


def _ln(x, w, b, eps):
    x = x.astype(jnp.float32)
    mu = x.mean(-1, keepdims=True)
    var = jnp.square(x - mu).mean(-1, keepdims=True)
    return (x - mu) * jax.lax.rsqrt(var + eps) * w + b


def diff_attention_pairs(q, k, v, mask, sm_scale):
    """The two softmaxes of differential attention in the PAIRED form, in
    plain ``jax.numpy``: q [Q, heads, hd], k / v [Kn, kv heads, hd], mask
    bool [Q, Kn] -> (a1, a2), each float32 [Q, heads / 2, 2 hd]:
    ``a1_j = softmax(q_2j . k_2p) [v_2p | v_2p+1]``, ``a2_j = softmax(q_2j+1
    . k_2p+1) [v_2p | v_2p+1]``, p the K/V pair that query pair j reads.  A
    row whose mask is empty comes back as the keys' mean (garbage the caller
    masks: a padding query)."""
    Q, nh, hd = q.shape
    Kn, nkv = k.shape[:2]
    npair, g = nkv // 2, nh // nkv
    f32 = jnp.float32
    s = jnp.einsum("qpgwd,kpwd->pgwqk", q.reshape(Q, npair, g, 2, hd),
                   k.reshape(Kn, npair, 2, hd),
                   preferred_element_type=f32) * sm_scale
    p = jax.nn.softmax(jnp.where(mask[None, None, None], s, -1e30), axis=-1)
    a = jnp.einsum("pgwqk,kpe->qpgwe", p.astype(v.dtype),
                   v.reshape(Kn, npair, 2 * hd), preferred_element_type=f32)
    a = a.reshape(Q, nh // 2, 2, 2 * hd)
    return a[:, :, 0], a[:, :, 1]


def build_sambay_paged(config: SambaYConfig, page_size: int = 16,
                       num_pages: int = 64, num_slots: int = 4,
                       max_pages_per_seq: Optional[int] = None, dtype=None,
                       attention_impl: str = "auto", interpret: bool = False,
                       kv_dtype=None, mesh=None, mp_axis: str = "mp",
                       quantized_allreduce: bool = False) -> PagedFamily:
    """The paged fns of `models/paged_family.PagedFamily` for this family
    (the module's docstring has the cache and the prefill that stops at the
    K/V layer).  A run of tokens belongs to ONE slot: the Mamba layers take
    the slot's convolution tail and state as they stand (zeros when the run
    starts at position 0, which is also how a slot is reset on admission and
    recomputed after a preemption), the window layers the slot's ring (rows
    of positions before the run's start; what an earlier sequence left is
    masked by position), and leave them after the run's last real token.  A
    decode step is one token for every slot; an inactive slot's state, ring
    and pages stay as they were.

    ``kv_dtype`` and ``mesh`` are refused: the quantized page store and the
    tensor-parallel region are written for a K/V pool a layer.
    """
    from ..ops.pallas.paged_attention import (ragged_paged_attention,
                                              ragged_paged_attention_ref)
    c = config
    c.validate()
    if kv_dtype is not None:
        raise NotImplementedError(
            "kv_dtype: a quantized store for this family's cache is missing "
            "(int8 pages for the one shared store beside bfloat16 rings and "
            "float32 recurrent state; ROADMAP B5)")
    if mesh is not None:
        raise NotImplementedError(
            "mesh: sharding specs for leaves grouped by layer kind are "
            "missing (ROADMAP B1)")
    W = c.sliding_window
    if W % page_size:
        raise ValueError(f"sliding_window {W} is not whole pages of "
                         f"{page_size}: the window's ring is laid out in "
                         f"pages")
    d = jnp.dtype(dtype if dtype is not None else jnp.float32)
    f32 = jnp.float32
    HI = jax.lax.Precision.HIGHEST
    kinds = layer_kinds(c)
    n = kind_counts(c)
    hd, F = c.head_dim, c.intermediate_size
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    npair, wide = nkv // 2, 2 * hd
    d_in, N, K, R = c.d_inner, c.mamba_d_state, c.mamba_d_conv, c.dt_rank
    eps = c.layer_norm_eps
    half = c.num_hidden_layers // 2
    wp = W // page_size                       # pages of a slot's ring
    TRASH, WIN_TRASH = num_pages, num_slots * wp
    sm_scale = 1.0 / math.sqrt(hd)
    readers = 1 + n["cross"]                  # layers that read the store
    if attention_impl == "auto":
        use_kernel = any(dev.platform == "tpu" for dev in jax.devices())
    else:
        use_kernel = attention_impl == "pallas"
    # a slot's ring as the kernel's page table: its wp pages, in order
    ring_tables = (np.arange(num_slots)[:, None] * wp
                   + np.arange(wp)[None]).astype(np.int32)

    def init_cache():
        ctr = ("shared_kv_attended", "window_attended", "live_slot_steps",
               "prefill_self", "prefill_cross", "shared_kv_rows")
        return {
            "k": jnp.zeros((1, npair, num_pages + 1, page_size, wide), d),
            "v": jnp.zeros((1, npair, num_pages + 1, page_size, wide), d),
            "win_k": jnp.zeros((n["window"], npair, num_slots * wp + 1,
                                page_size, wide), d),
            "win_v": jnp.zeros((n["window"], npair, num_slots * wp + 1,
                                page_size, wide), d),
            "conv": jnp.zeros((n["mamba"], num_slots, K - 1, d_in), d),
            "ssm": tuple(jnp.zeros((num_slots, N, d_in), f32)
                         for _ in range(n["mamba"])),
            # every counter a (high, low) pair, low below `CARRY`: an int32
            # alone wraps within minutes of decode at 64 slots x 8 readers
            "ctr": {name: jnp.zeros((2,), jnp.int32) for name in ctr}}

    def _count(ctr, name, inc):
        """ctr[name] (high, low) + inc (0 <= inc < 2^30), with the carry."""
        low = ctr[name][1] + inc.astype(jnp.int32)
        return {**ctr, name: jnp.stack([ctr[name][0] + low // CARRY,
                                        low % CARRY])}

    def _layer_params(bp, kind, j):
        return {leaf: per_layer[j] for leaf, per_layer in bp[kind].items()}

    def _attend(q, k_pages, v_pages, li, tables, q_start, q_len, kv_len,
                role, kind):
        """q [S, Q, nh, hd] -> (a1, a2) float32 [S, Q, nh / 2, 2 hd]: the
        two softmaxes' sums over V_p, through the ragged kernel (or its
        plain form) as GQA over 2 hd-wide rows — an even head's query
        ``[q | 0]``, an odd head's ``[0 | q]``."""
        S, Q = q.shape[:2]
        q = q.reshape(S, Q, nh // 2, 2, hd)
        zero = jnp.zeros_like(q[:, :, :, 0])
        qz = jnp.stack([jnp.concatenate([q[:, :, :, 0], zero], -1),
                        jnp.concatenate([zero, q[:, :, :, 1]], -1)], 3)
        qz = qz.reshape(S, Q, nh, wide)
        if use_kernel:
            o = ragged_paged_attention(
                qz, k_pages, v_pages, tables, q_start, q_len, kv_len,
                sm_scale=sm_scale, interpret=interpret, out_dtype=f32,
                role=role, kind=kind, layer=li)
        else:
            o = ragged_paged_attention_ref(
                qz, k_pages, v_pages, tables, q_start, q_len, kv_len,
                sm_scale=sm_scale, out_dtype=f32, layer=li)
        o = o.reshape(S, Q, nh // 2, 2, wide)
        return o[:, :, :, 0], o[:, :, :, 1]

    def _combine(lp, a1, a2, layer):
        """(a1, a2) [*tok, nh / 2, 2 hd] -> the attention mixer's output."""
        lam0 = lambda_init(layer)
        lam = jnp.exp(jnp.sum(lp["lq1"] * lp["lk1"])) \
            - jnp.exp(jnp.sum(lp["lq2"] * lp["lk2"])) + lam0
        o = a1 - lam * a2
        o = o * jax.lax.rsqrt(jnp.mean(o * o, -1, keepdims=True) + eps) \
            * lp["sub_w"] * (1.0 - lam0)
        o = o.reshape(o.shape[:-2] + (nh * hd,)).astype(d)
        return o @ lp["wo"] + lp["bo"].astype(d)

    def _q(lp, v):
        return (v @ lp["wq"] + lp["bq"].astype(d)).reshape(
            v.shape[:-1] + (nh, hd))

    def _kv(lp, v):
        """-> K, V rows of a token as the stores hold them [*tok, pairs,
        2 hd]: ``[k_2p | k_2p+1]``, ``[v_2p | v_2p+1]``."""
        shape = v.shape[:-1] + (npair, wide)
        return (v @ lp["wk"] + lp["bk"].astype(d)).reshape(shape), \
            (v @ lp["wv"] + lp["bv"].astype(d)).reshape(shape)

    def _mlp(bp, i, x):
        lp = _layer_params(bp, "mlp", i)
        with device_span("block.mlp"):
            gu = _ln(x, lp["ln_w"], lp["ln_b"], eps).astype(d) \
                @ lp["w_gate_up"]
            return x + (jax.nn.silu(gu[..., :F]) * gu[..., F:]) @ lp["w_down"]

    def _mamba_in(lp, x):
        """-> (u before the convolution, z), float32: what the recurrence
        takes is not rounded to the serving type on its way."""
        uz = jnp.matmul(_ln(x, lp["ln_w"], lp["ln_b"], eps).astype(d),
                        lp["w_in"], preferred_element_type=f32)
        return uz[..., :d_in], uz[..., d_in:]

    def _mamba_dt_bc(lp, u, live):
        """u [*tok, d_in] float32 after the convolution -> (dt (0 where not
        ``live``), B, C), float32 at the highest matmul precision (two small
        products; a bfloat16 pass would round the recurrence's operands)."""
        rbc = jnp.matmul(u, lp["w_x"].astype(f32), precision=HI)
        dt = jax.nn.softplus(
            jnp.matmul(rbc[..., :R], lp["w_dt"].astype(f32), precision=HI)
            + lp["dt_bias"])
        return jnp.where(live[..., None], dt, 0.0), rbc[..., R:R + N], \
            rbc[..., R + N:]

    def _mamba_out(lp, y, u, z):
        """-> (the mixer's output, the memory m = y + D u, float32)."""
        m = y + lp["D"] * u
        return (m * jax.nn.silu(z)).astype(d) @ lp["w_out"], m

    def _gmu(lp, x, m):
        g = _ln(x, lp["ln_w"], lp["ln_b"], eps).astype(d) @ lp["w1"]
        return (jax.nn.silu(g.astype(f32)) * m).astype(d) @ lp["w2"]

    @device_span("head")
    def _head(ep, hp, h_last):
        return jnp.einsum(
            "...h,vh->...v",
            _ln(h_last, hp["ln_w"], hp["ln_b"], eps).astype(d), ep["tok"],
            preferred_element_type=f32)

    def _ring(store, j, slot):
        """The slot's ring of window layer ``j`` [W, pairs, 2 hd] (row r
        holds the newest position p <= the last written with p mod W = r)."""
        pages = jax.lax.dynamic_slice(
            store, (j, 0, slot * wp, 0, 0),
            (1, npair, wp, page_size, wide))[0]
        return pages.reshape(npair, W, wide).swapaxes(0, 1)

    def _set_ring(store, j, slot, ring):
        pages = ring.swapaxes(0, 1).reshape(1, npair, wp, page_size, wide)
        return jax.lax.dynamic_update_slice(store, pages.astype(store.dtype),
                                            (j, 0, slot * wp, 0, 0))

    def _window_run(lp, layer, q, k, v, ring_k, ring_v, start, length):
        """The window layer over a run of C tokens at positions start ..:
        q [C, nh, hd], k / v [C, pairs, 2 hd], the slot's rings -> (the
        mixer's output [C, H], the rings after the run's last real token).
        Keys are the W positions before the run (out of the ring, in order)
        and the run's own; a block of queries sees the W + block keys that
        end with its last query."""
        C = q.shape[0]
        # row i of `before` holds position start - W + i
        before_k = jnp.roll(ring_k, -(start % W), axis=0)
        before_v = jnp.roll(ring_v, -(start % W), axis=0)
        keys = jnp.concatenate([before_k, k.astype(ring_k.dtype)])
        vals = jnp.concatenate([before_v, v.astype(ring_v.dtype)])
        # the W positions that end with the last real one, back on the ring
        first = start + length - W                # position of row 0
        new_k = jnp.roll(jax.lax.dynamic_slice_in_dim(keys, length, W),
                         first % W, axis=0)
        new_v = jnp.roll(jax.lax.dynamic_slice_in_dim(vals, length, W),
                         first % W, axis=0)
        blk = WINDOW_BLOCK if C % WINDOW_BLOCK == 0 else C
        nblk, span = C // blk, W + blk
        # query c of a block (local) sees local keys c < x <= c + W: the W
        # positions that end with its own; a position before 0 is nobody's
        cq = jnp.arange(blk)[:, None]
        xk = jnp.arange(span)[None]
        band = (xk > cq) & (xk <= cq + W)

        def block(i):
            lo = i * blk
            mask = band & (start - W + lo + xk >= 0)
            return diff_attention_pairs(
                jax.lax.dynamic_slice_in_dim(q, lo, blk),
                jax.lax.dynamic_slice_in_dim(keys, lo, span)
                .reshape(span, nkv, hd),
                jax.lax.dynamic_slice_in_dim(vals, lo, span)
                .reshape(span, nkv, hd), mask, sm_scale)

        a1, a2 = jax.lax.map(block, jnp.arange(nblk))
        out = _combine(lp, a1.reshape(C, nh // 2, wide),
                       a2.reshape(C, nh // 2, wide), layer)
        return out, new_k, new_v

    def _kv_layer_out(params, x, v_in, cache, tables, q_start, q_len, kv_len,
                      role):
        """The K/V layer (h + 1) AFTER its K/V rows are in the store: x
        [S, H] + its attention over the store (one query a row, ``v_in`` the
        rows' LayerNorm output), then its MLP."""
        _, bp, _ = params
        lp = _layer_params(bp, "full", 0)
        with device_span("attn.shared_kv"):
            a1, a2 = _attend(_q(lp, v_in)[:, None], cache["k"], cache["v"],
                             0, tables, q_start, q_len, kv_len, role, "full")
            x = x + _combine(lp, a1[:, 0], a2[:, 0], half + 1)
        return _mlp(bp, half + 1, x)

    def _upper(params, x, m, cache, tables, q_start, q_len, kv_len, role):
        """Layers h + 2 .. L - 1 over x [S, H] with the memory m [S, d_in]:
        GMU and cross attention over the page store, one query a row."""
        _, bp, _ = params
        for i in range(half + 2, c.num_hidden_layers):
            kind, j = kinds[i]
            lp = _layer_params(bp, kind, j)
            if kind == "gmu":
                with device_span("gmu"):
                    x = x + _gmu(lp, x, m)
            else:
                with device_span("attn.shared_kv"):
                    u = _ln(x, lp["ln_w"], lp["ln_b"], eps).astype(d)
                    a1, a2 = _attend(_q(lp, u)[:, None], cache["k"],
                                     cache["v"], 0, tables, q_start, q_len,
                                     kv_len, role, "cross")
                    x = x + _combine(lp, a1[:, 0], a2[:, 0], i)
            x = _mlp(bp, i, x)
        return x

    def _run(params, ids, start, length, page_row, slot, cache, last):
        """A run of C tokens of the sequence riding ``slot``, at positions
        start .. start + C - 1, the first ``length`` real -> (logits of the
        last real token — zeros unless ``last`` —, cache)."""
        ep, bp, hp = params
        C = ids.shape[1]
        x = ep["tok"][ids[0]].astype(d)
        start = start.astype(jnp.int32)
        length = length.astype(jnp.int32)
        real = jnp.arange(C) < length
        fresh = start == 0
        cache = dict(cache)
        ssm = list(cache["ssm"])
        ctr = _count(cache["ctr"], "prefill_self", length)
        ctr = _count(ctr, "shared_kv_rows", length)
        m = None
        for i in range(half + 1):
            kind, j = kinds[i]
            lp = _layer_params(bp, kind, j)
            if kind == "mamba":
                with device_span("mamba.proj"):
                    u, z = _mamba_in(lp, x)
                    tail = jnp.where(fresh, 0, cache["conv"][j, slot])
                    window = jnp.concatenate([tail.astype(f32), u])
                    # the last K-1 REAL inputs: the next run's (or decode's)
                    # tail
                    cache["conv"] = cache["conv"].at[j, slot].set(
                        jax.lax.dynamic_slice_in_dim(window, length, K - 1)
                        .astype(d))
                    u = jax.nn.silu(
                        sum(window[t:t + C] * lp["conv_w"][t].astype(f32)
                            for t in range(K)) + lp["conv_b"])
                    dt, b, cc = _mamba_dt_bc(lp, u, real)
                    s0 = jnp.where(fresh, 0, ssm[j][slot])
                    y, s = selective_scan(u, dt, -jnp.exp(lp["A_log"]), b, cc,
                                          s0)
                    ssm[j] = ssm[j].at[slot].set(s.astype(ssm[j].dtype))
                    out, m = _mamba_out(lp, y, u, z)
                    x = x + out
            else:
                with device_span("attn.window"):
                    v_in = _ln(x, lp["ln_w"], lp["ln_b"], eps).astype(d)
                    k, v = _kv(lp, v_in)
                    out, new_k, new_v = _window_run(
                        lp, i, _q(lp, v_in), k, v,
                        _ring(cache["win_k"], j, slot),
                        _ring(cache["win_v"], j, slot), start, length)
                    cache["win_k"] = _set_ring(cache["win_k"], j, slot, new_k)
                    cache["win_v"] = _set_ring(cache["win_v"], j, slot, new_v)
                    x = x + out
            x = _mlp(bp, i, x)
        cache["ssm"] = tuple(ssm)
        # the K/V layer: every token's K and V go into the store; its
        # queries, W_o and MLP are the last token's alone, like every layer
        # after it (nothing reads another token's)
        lp = _layer_params(bp, "full", 0)
        with device_span("attn.shared_kv"):
            v_in = _ln(x, lp["ln_w"], lp["ln_b"], eps).astype(d)
            k, v = _kv(lp, v_in)
            cache["k"] = scatter_kv_run(cache["k"], 0, k, start, length,
                                        page_row)
            cache["v"] = scatter_kv_run(cache["v"], 0, v, start, length,
                                        page_row)
        if not last:       # static  # graftlint: disable=TRACE001
            cache["ctr"] = ctr
            return jnp.zeros((c.vocab_size,), f32), cache
        cache["ctr"] = _count(ctr, "prefill_cross", jnp.int32(1))
        at = length - 1
        x = jax.lax.dynamic_slice_in_dim(x, at, 1)
        m = jax.lax.dynamic_slice_in_dim(m, at, 1)
        end = (start + length)[None]
        seg = (page_row[None], end - 1, jnp.ones((1,), jnp.int32), end,
               "chunk")
        x = _kv_layer_out(params, x, jax.lax.dynamic_slice_in_dim(v_in, at, 1),
                          cache, *seg)
        x = _upper(params, x, m, cache, *seg)
        return _head(ep, hp, x[0]), cache

    def prefill(params, ids, true_len, page_row, slot, cache):  # graftlint: jit
        return _run(params, ids, jnp.zeros((), jnp.int32), true_len,
                    page_row, slot, cache, True)

    def prefill_chunk(params, ids, start, chunk_len, page_row, slot, cache,
                      last=True):                     # graftlint: jit
        logits, cache = _run(params, ids, start, chunk_len, page_row, slot,
                             cache, last)
        with device_span("head"):
            tok = jnp.argmax(logits).astype(jnp.int32)
        return logits, tok, cache

    def decode_step(params, toks, lengths, page_tables, cache,
                    active):                          # graftlint: jit
        ep, bp, hp = params
        S = toks.shape[0]
        x = ep["tok"][toks].astype(d)                 # [S, H]
        pos = jnp.where(active, lengths, 0)
        page = jnp.where(active, jnp.take_along_axis(
            page_tables, (pos // page_size)[:, None], 1)[:, 0], TRASH)
        off = pos % page_size
        eff_len = jnp.where(active, lengths + 1, 0)
        n_q = active.astype(jnp.int32)
        # the ring: position pos lies at row pos mod W; written first, every
        # one of its min(pos + 1, W) rows is then inside the window
        row = pos % W
        win_page = jnp.where(active, jnp.arange(S) * wp + row // page_size,
                             WIN_TRASH)
        win_off = row % page_size
        win_len = jnp.where(active, jnp.minimum(pos + 1, W), 0)
        cache = dict(cache)
        ssm = list(cache["ssm"])
        live = active.sum(dtype=jnp.int32)
        ctr = _count(cache["ctr"], "live_slot_steps", live)
        ctr = _count(ctr, "shared_kv_rows", live)
        ctr = _count(ctr, "shared_kv_attended", eff_len.sum() * readers)
        cache["ctr"] = _count(ctr, "window_attended",
                              win_len.sum() * n["window"])
        m = None
        for i in range(half + 1):
            kind, j = kinds[i]
            lp = _layer_params(bp, kind, j)
            if kind == "mamba":
                with device_span("mamba.proj"):
                    u, z = _mamba_in(lp, x)
                    tail = cache["conv"][j]               # [S, K-1, d_in]
                    window = jnp.concatenate(
                        [tail.astype(f32), u[:, None]], axis=1)
                    cache["conv"] = cache["conv"].at[j].set(jnp.where(
                        active[:, None, None], window[:, 1:].astype(d), tail))
                    u = jax.nn.silu(
                        (window * lp["conv_w"].astype(f32)[None]).sum(1)
                        + lp["conv_b"])
                    dt, b, cc = _mamba_dt_bc(lp, u, active)
                    y, ssm[j] = selective_update(
                        ssm[j], u, dt, -jnp.exp(lp["A_log"]), b, cc)
                    out, m = _mamba_out(lp, y, u, z)
                    x = x + out
            else:
                with device_span("attn.window"):
                    v_in = _ln(x, lp["ln_w"], lp["ln_b"], eps).astype(d)
                    k, v = _kv(lp, v_in)
                    cache["win_k"] = scatter_kv_rows(cache["win_k"], j, k,
                                                     win_page, win_off)
                    cache["win_v"] = scatter_kv_rows(cache["win_v"], j, v,
                                                     win_page, win_off)
                    a1, a2 = _attend(
                        _q(lp, v_in)[:, None], cache["win_k"],
                        cache["win_v"], j, ring_tables,
                        jnp.maximum(win_len - 1, 0), n_q, win_len, "decode",
                        "window")
                    x = x + _combine(lp, a1[:, 0], a2[:, 0], i)
            x = _mlp(bp, i, x)
        cache["ssm"] = tuple(ssm)
        lp = _layer_params(bp, "full", 0)
        with device_span("attn.shared_kv"):
            v_in = _ln(x, lp["ln_w"], lp["ln_b"], eps).astype(d)
            k, v = _kv(lp, v_in)
            cache["k"] = scatter_kv_rows(cache["k"], 0, k, page, off)
            cache["v"] = scatter_kv_rows(cache["v"], 0, v, page, off)
        seg = (page_tables, pos, n_q, eff_len, "decode")
        x = _kv_layer_out(params, x, v_in, cache, *seg)
        x = _upper(params, x, m, cache, *seg)
        return _head(ep, hp, x), cache

    state_bytes = num_slots * n["mamba"] * (
        N * d_in * 4 + (K - 1) * d_in * d.itemsize)
    window_bytes = num_slots * n["window"] * 2 * W * nkv * hd * d.itemsize

    def counters(cache):
        """The device-side counters as host numbers (one small fetch)."""
        got = {name: int(pair[0]) * CARRY + int(pair[1])
               for name, pair in jax.device_get(cache["ctr"]).items()}
        return {
            "shared_kv_tokens_attended_decode": got["shared_kv_attended"],
            "window_tokens_attended_decode": got["window_attended"],
            "ssm_live_slot_steps": got["live_slot_steps"],
            "prefill_tokens_self_decoder": got["prefill_self"],
            "prefill_tokens_cross_decoder": got["prefill_cross"],
            "shared_kv_rows_written": got["shared_kv_rows"],
            "shared_kv_bytes_per_token": 2 * nkv * hd * d.itemsize,
            "shared_kv_reading_layers": readers,
            "window_state_bytes": window_bytes,
            "ssm_state_bytes": state_bytes,
        }

    def slot_state(cache, slot):
        """What the cache holds of the slot beside its pages: ``ssm [mamba
        layers, d_inner, N]``, ``conv``, and the rings ``window_k`` /
        ``window_v [window layers, W, kv heads, hd]`` — position p at row
        p mod W (rows of positions the slot's sequence never reached are an
        earlier sequence's)."""
        ring = lambda store: np.asarray(
            store[:, :, slot * wp:(slot + 1) * wp]).reshape(
                n["window"], npair, W, wide).swapaxes(1, 2).reshape(
                n["window"], W, nkv, hd)
        return {"ssm": np.stack([np.asarray(s[slot]).T
                                 for s in cache["ssm"]]),
                "conv": np.asarray(cache["conv"][:, slot]),
                "window_k": ring(cache["win_k"]),
                "window_v": ring(cache["win_v"])}

    return PagedFamily(name="sambay", init_cache=init_cache, prefill=prefill,
                       prefill_chunk=prefill_chunk, decode_step=decode_step,
                       page_leaves=("k", "v"), verify_step=None,
                       recurrent=True, counters=counters,
                       chunk_table_granule=0, chunk_takes_last=True,
                       attention_path="paged_kv_shared",
                       slot_state=slot_state)
