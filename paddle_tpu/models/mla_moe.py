"""Latent-attention + sparse-expert decoders (``model_type: deepseek_v3`` as
Kimi-VL-A3B's language model and DeepSeek-V3 publish it) for the serving
engine: multi-head latent attention (MLA) in every layer, a dense SwiGLU MLP
in the first ``first_k_dense_replace`` layers and a sigmoid-routed
mixture of SwiGLU experts with shared experts in the rest; then the final
norm and the untied head.  Per token row x [H] at position p:

  h = RMSNorm(x; norm)
  q = h W_q -> [heads, dn + dr];   q = [q_nope | RoPE(q_rope, p)]
  a = h W_kva -> [dl + dr];  c = RMSNorm(a[:dl]; kv_norm);  r = RoPE(a[dl:], p)
      ONE rotary key r, shared by every head.  The page store keeps the row
      [c | r] (``kv_lora_rank + qk_rope_head_dim`` values) a token and layer.
  expanded:  kv = c W_kvb -> [heads, dn + dv];  k_i = [kv_i[:dn] | r],
             v_i = kv_i[dn:];  o_i = softmax_causal(q_i . k_j / sqrt(dn+dr)) v
  absorbed:  q'_i = q_nope_i W_kvb_k,i^T -> [dl];
             s_ij = (q'_i . c_j + q_rope_i . r_j) / sqrt(dn + dr);
             o'_i = sum_j P_ij c_j -> [dl];  o_i = o'_i W_kvb_v,i -> [dv]
  x = x + concat_i(o_i) W_o;   g = RMSNorm(x; post_norm)
  dense layer:   x = x + W_down (silu(W_gate g) * W_up g)
  expert layer:  s = sigmoid(g W_r) in float32; sel = top-k of s + b;
                 w_e = routed_scaling_factor * s_e / sum_{sel} s;
                 x = x + sum_{e in sel, held} w_e SwiGLU_e(g) + SwiGLU_shared(g)
                 (the shared experts are one MLP of width n_shared x F)

The two forms of the attention are the same function (the products
associate differently).  The paged fns here take the ABSORBED one on every
path — decode, a prefill chunk and dense prefill alike score the latent rows
through `ops/pallas/paged_attention.mla_paged_attention` (on the CPU its
plain form), a run of queries cut into segments of ``SEGMENT`` — so K and V
per head never exist and a prefix costs nothing to re-expand; a long prefill
pays (2 dl + dr) / (dn + dr + dv) = 3.4 x the expanded form's attention
FLOPs for it (the expanded chunk path through flash attention is ROADMAP B3).

An expert-parallel rank's share, as `models/afmoe.py` and
`models/nemotron_h.py` have it: ``experts_held=(offset, count)`` — the router
keeps all ``n_routed_experts`` outputs and the published top-k, this rank
holds ``count`` experts and adds their part; attention, router, shared
experts and the dense MLP are whole.  A sliced vocabulary is a smaller
``vocab_size``.

Weights are grouped by kind, ``blocks["attn" | "dense" | "moe"][leaf]`` a
tuple with one array a layer of that kind (a layer's leaf is its OWN buffer:
the TPU compiler copies a static slice of a stacked operand out before a
kernel may read it), matrices as [in, out].

The cache of the paged fns (`build_mla_moe_paged`) is ONE page store,
``latent [L, 1, NP+1, ps, W]`` — the row [c | r] padded with zeros to W, a
whole number of 128-lane tiles (the TPU lays the minor dimension out in
tiles of 128 and a copy out of the pool moves whole tiles: 576 values are
stored as 640) — and beside it, by SLOT: ``sel [Le, slots, k, ctx]``, the
experts every consumed token selected in each expert layer, ``logp [slots,
ctx]``, the log-probability of the greedy token after each consumed
position, and ``ctr``, the counters the fns accumulate on the device.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..incubate.distributed.models.moe.dropless import (
    dropless_expert_forward, grouped_swiglu, sigmoid_topk_route)
from ..profiler import device_span
from .llama import scatter_kv_rows, scatter_kv_run
from .paged_family import PagedFamily, log_selections_run

__all__ = ["MlaMoeConfig", "mla_moe_config_tiny", "build_functional_mla_moe",
           "build_mla_moe_paged", "mla_project", "moe_layer", "SEGMENT"]

DECODE, PREFILL = 0, 1          # the two halves of every per-phase counter
CARRY = 1 << 20                 # a counter's low word carries over at this
SEGMENT = 64                    # queries a segment of a run (x heads = rows)
_LANES = 128


@dataclasses.dataclass
class MlaMoeConfig:
    """The public ``config.json`` keys of the decoder, and one that says how
    it is held here (``experts_held``)."""
    vocab_size: int = 163840
    hidden_size: int = 2048
    intermediate_size: int = 11264
    moe_intermediate_size: int = 1408
    num_hidden_layers: int = 27
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    n_shared_experts: int = 2
    n_routed_experts: int = 64
    routed_scaling_factor: float = 2.446
    kv_lora_rank: int = 512
    q_lora_rank: Optional[int] = None
    qk_rope_head_dim: int = 64
    v_head_dim: int = 128
    qk_nope_head_dim: int = 128
    topk_method: str = "noaux_tc"
    n_group: int = 1
    topk_group: int = 1
    num_experts_per_tok: int = 6
    moe_layer_freq: int = 1
    first_k_dense_replace: int = 1
    norm_topk_prob: bool = True
    scoring_func: str = "sigmoid"
    hidden_act: str = "silu"
    rms_norm_eps: float = 1e-5
    rope_theta: float = 800000.0
    rope_scaling: Optional[dict] = None
    attention_bias: bool = False
    tie_word_embeddings: bool = False
    max_position_embeddings: int = 131072
    num_nextn_predict_layers: int = 0
    # (offset, count) of the routed experts held here; None: all of them
    experts_held: Optional[Tuple[int, int]] = None

    def held(self):
        return self.experts_held or (0, self.n_routed_experts)

    @property
    def latent_row(self):
        """Values a token and layer keeps: the latent and the rotary key."""
        return self.kv_lora_rank + self.qk_rope_head_dim

    def validate(self):
        """Refuse what the path lacks, by name: nothing is ignored."""
        lacks = {
            "q_lora_rank": self.q_lora_rank is not None,
            "rope_scaling": self.rope_scaling is not None,
            "n_group": self.n_group != 1,
            "topk_group": self.topk_group != 1,
            "scoring_func": self.scoring_func != "sigmoid",
            "topk_method": self.topk_method != "noaux_tc",
            "num_nextn_predict_layers": self.num_nextn_predict_layers != 0,
            "hidden_act": self.hidden_act != "silu",
            "attention_bias": bool(self.attention_bias),
            "tie_word_embeddings": bool(self.tie_word_embeddings),
            "moe_layer_freq": self.moe_layer_freq != 1,
            "num_key_value_heads":
                self.num_key_value_heads != self.num_attention_heads,
            "qk_rope_head_dim": self.qk_rope_head_dim % 2 != 0,
        }
        for key, lacking in lacks.items():
            if lacking:
                raise ValueError(
                    f"{key}={getattr(self, key)!r}: the mla_moe path has a "
                    f"full-rank query projection, unscaled rotary positions, "
                    f"sigmoid scores with a selection bias over ONE group, "
                    f"silu, no attention bias, an untied head, an expert "
                    f"layer after every dense one and no drafting head")
        if not 0 <= self.first_k_dense_replace <= self.num_hidden_layers:
            raise ValueError(
                f"first_k_dense_replace={self.first_k_dense_replace} outside "
                f"the {self.num_hidden_layers} layers")
        offset, count = self.held()
        if not (0 <= offset and count >= 1
                and offset + count <= self.n_routed_experts):
            raise ValueError(f"experts_held {(offset, count)} outside the "
                             f"{self.n_routed_experts} experts")

    def paged_family(self, **build_kw) -> PagedFamily:
        """The seam `inference.paged.ServingEngine` builds its fns through."""
        return build_mla_moe_paged(self, **build_kw)


def mla_moe_config_tiny(**kw):
    """The CPU tests' size: every mechanism present, nothing at scale."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=96,
                moe_intermediate_size=24, num_hidden_layers=3,
                num_attention_heads=4, num_key_value_heads=4,
                n_shared_experts=2, n_routed_experts=16,
                routed_scaling_factor=2.446, kv_lora_rank=32,
                qk_rope_head_dim=8, v_head_dim=16, qk_nope_head_dim=16,
                num_experts_per_tok=3, first_k_dense_replace=1,
                max_position_embeddings=512)
    base.update(kw)
    return MlaMoeConfig(**base)


def build_functional_mla_moe(config: MlaMoeConfig, key=None, dtype=None):
    """(embed, blocks, head) from a seed: matrices normal / sqrt(fan_in)
    (0.02 for the embedding and the head), the router and its correction
    bias float32 (the bias normal x 0.01: enough to change selections, not
    to set them), ``kv_norm`` uniform in [0.5, 1.5] (a latent whose norm
    weight is 1 under unit-variance inputs would hide a dropped norm), the
    other norms ones.  Jit it (every leaf is drawn in float32 and cast in
    one fusion)."""
    c = config
    c.validate()
    d = jnp.dtype(dtype) if dtype is not None else jnp.float32
    key = key if key is not None else jax.random.PRNGKey(0)
    f32 = jnp.float32
    H, nh = c.hidden_size, c.num_attention_heads
    dn, dr, dv, dl = c.qk_nope_head_dim, c.qk_rope_head_dim, c.v_head_dim, \
        c.kv_lora_rank
    F, Fs = c.moe_intermediate_size, \
        c.n_shared_experts * c.moe_intermediate_size
    _, held = c.held()

    def init(k, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, f32) * scale).astype(d)

    def attn_layer(k):
        ks = jax.random.split(k, 5)
        return {"norm": jnp.ones((H,), f32),
                "wq": init(ks[0], (H, nh * (dn + dr))),
                "w_kva": init(ks[1], (H, dl + dr)),
                "kv_norm": jax.random.uniform(ks[2], (dl,), f32, 0.5, 1.5),
                "w_kvb": init(ks[3], (dl, nh * (dn + dv))),
                "wo": init(ks[4], (nh * dv, H)),
                "post_norm": jnp.ones((H,), f32)}

    def dense_layer(k):
        ks = jax.random.split(k, 3)
        return {"w_gate": init(ks[0], (H, c.intermediate_size)),
                "w_up": init(ks[1], (H, c.intermediate_size)),
                "w_down": init(ks[2], (c.intermediate_size, H))}

    def moe_layer_(k):
        ks = jax.random.split(k, 8)
        return {"router": jax.random.normal(
                    ks[0], (H, c.n_routed_experts), f32) / math.sqrt(H),
                "router_bias": 0.01 * jax.random.normal(
                    ks[1], (c.n_routed_experts,), f32),
                "we_gate": init(ks[2], (held, H, F)),
                "we_up": init(ks[3], (held, H, F)),
                "we_down": init(ks[4], (held, F, H)),
                "ws_gate": init(ks[5], (H, Fs)),
                "ws_up": init(ks[6], (H, Fs)),
                "ws_down": init(ks[7], (Fs, H))}

    k_attn, k_mlp, k_embed, k_head = jax.random.split(key, 4)
    L, nd = c.num_hidden_layers, c.first_k_dense_replace
    mlp_keys = jax.random.split(k_mlp, L)
    stack = lambda layers: {leaf: tuple(lp[leaf] for lp in layers)
                            for leaf in (layers[0] if layers else {})}
    blocks = {
        "attn": stack([attn_layer(k) for k in jax.random.split(k_attn, L)]),
        "dense": stack([dense_layer(k) for k in mlp_keys[:nd]]),
        "moe": stack([moe_layer_(k) for k in mlp_keys[nd:]])}
    embed = {"tok": init(k_embed, (c.vocab_size, H), 0.02)}
    head = {"ln_f": jnp.ones((H,), f32),
            "lm": init(k_head, (H, c.vocab_size), 0.02)}
    return embed, blocks, head


def _rms(x, w, eps):
    from ..nn.functional.norm import rms_norm_ref
    return rms_norm_ref(x, w, eps)


def _rope(x, pos, theta):
    """x [T, ..., dr] at positions pos [T]: the half-split convention (the
    pair of column i is column i + dr/2), angles in float32."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos.astype(jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1).astype(x.dtype)


def mla_project(config: MlaMoeConfig, lp, h, pos):
    """h [T, H] (the layer's normed input) at positions pos [T] -> (q [T,
    heads, dl + dr]: the query of the ABSORBED form, ``[q_nope W_kvb_k^T |
    RoPE(q_rope)]``; row [T, dl + dr]: what the page store keeps of the
    token, ``[c | r]``)."""
    c = config
    nh, dn, dr, dl = c.num_attention_heads, c.qk_nope_head_dim, \
        c.qk_rope_head_dim, c.kv_lora_rank
    q = (h @ lp["wq"]).reshape(-1, nh, dn + dr)
    w_uk = lp["w_kvb"].reshape(dl, nh, dn + c.v_head_dim)[:, :, :dn]
    q_lat = jnp.einsum("thn,lhn->thl", q[..., :dn], w_uk)
    a = h @ lp["w_kva"]
    row = jnp.concatenate([_rms(a[:, :dl], lp["kv_norm"], c.rms_norm_eps),
                           _rope(a[:, dl:], pos, c.rope_theta)], axis=-1)
    q = jnp.concatenate([q_lat, _rope(q[..., dn:], pos, c.rope_theta)], -1)
    return q, row


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def moe_layer(config: MlaMoeConfig, lp, g, valid, expert=grouped_swiglu):
    """One expert layer's share: g [T, H] (already normed), ``lp`` the
    layer's leaves, valid bool [T] (a token that is padding, or a dead
    slot's, selects no expert), ``expert`` the grouped product of the held
    experts -> (out [T, H]: the held experts' part plus the shared experts;
    rows int32 [held]; beyond int32, the held pairs past the grouped
    product's row bound; sel int32 [T, k] over ALL experts,
    ``n_routed_experts`` where the token is not valid)."""
    c = config
    offset, _ = c.held()
    with device_span("moe.layer"):
        sel, w = sigmoid_topk_route(
            g.astype(jnp.float32), lp["router"], lp["router_bias"],
            c.num_experts_per_tok, c.routed_scaling_factor, c.norm_topk_prob,
            precision=jax.lax.Precision.HIGHEST)
        sel = jnp.where(valid[:, None], sel, c.n_routed_experts)
        part, rows, beyond = dropless_expert_forward(
            g, sel, w, (lp["we_gate"], lp["we_up"], lp["we_down"]), offset,
            c.n_routed_experts, expert=expert)
        with device_span("moe.shared"):
            shared = _swiglu(g, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
        return part + shared, rows, beyond, sel


def build_mla_moe_paged(config: MlaMoeConfig, page_size: int = 16,
                        num_pages: int = 64, num_slots: int = 4,
                        max_pages_per_seq: Optional[int] = None,
                        dtype=None, attention_impl: str = "auto",
                        interpret: bool = False, kv_dtype=None, mesh=None,
                        mp_axis: str = "mp",
                        quantized_allreduce: bool = False) -> PagedFamily:
    """The paged fns of `models/paged_family.PagedFamily` for this family.

    Every layer's state is latent pages, so nothing here belongs to a slot
    but the logs: a run of tokens (dense prefill, a prefill chunk) writes
    its rows a page an update and attends, cut into segments of `SEGMENT`
    queries, over the sequence's pages — a cached prefix's among them; a
    decode step writes one row a live slot and attends with one query.
    Padding tokens and inactive slots are routed to no expert.  The
    residual stream is carried in float32 through the layers (a [tokens, H]
    array: no weight and no stored row is wider for it) and the logits come
    out of the head's product in float32; every matmul operand, and every
    stored row, is in ``dtype``.

    ``kv_dtype`` and ``mesh`` are refused: int8 latent rows and per-rank
    latent shards are not built (ROADMAP B3).
    """
    from ..ops.pallas.paged_attention import (mla_paged_attention,
                                              mla_paged_attention_ref)
    c = config
    c.validate()
    if kv_dtype is not None:
        raise NotImplementedError(
            "kv_dtype: a quantized store of latent rows is missing (the "
            "rotary key and the latent want scales of their own; ROADMAP "
            "B3)")
    if mesh is not None:
        raise NotImplementedError(
            "mesh: every head reads the whole latent row, so tensor "
            "parallelism shards heads over a REPLICATED store; those specs "
            "and the experts' exchange are missing (ROADMAP B3, B1)")
    d = jnp.dtype(dtype if dtype is not None else jnp.float32)
    f32 = jnp.float32
    L, nd = c.num_hidden_layers, c.first_k_dense_replace
    nh, dn, dv, dl = c.num_attention_heads, c.qk_nope_head_dim, \
        c.v_head_dim, c.kv_lora_rank
    eps = c.rms_norm_eps
    width = -(-c.latent_row // _LANES) * _LANES        # the stored row
    sm_scale = 1.0 / math.sqrt(dn + c.qk_rope_head_dim)
    _, held = c.held()
    top_k = c.num_experts_per_tok
    log_k = -(-top_k // 8) * 8
    n_moe = L - nd
    ctx = (max_pages_per_seq or num_pages) * page_size
    TRASH = num_pages
    if attention_impl == "auto":
        use_kernel = any(dev.platform == "tpu" for dev in jax.devices())
    else:
        use_kernel = attention_impl == "pallas"
    attention_path = "absorbed_" + ("kernel" if use_kernel else "plain")

    def init_cache():
        return {
            "latent": jnp.zeros((L, 1, num_pages + 1, page_size, width), d),
            # positions minor, k rounded up to whole sublane tiles: see
            # `models/nemotron_h.py`'s log
            "sel": jnp.zeros((max(n_moe, 1), num_slots, log_k, ctx),
                             jnp.int32),
            "logp": jnp.zeros((num_slots, ctx), f32),
            # every counter a (high, low) pair, low below `CARRY`
            "ctr": {"moe_pairs": jnp.zeros((2, 2), jnp.int32),
                    "moe_touched": jnp.zeros((2, 2), jnp.int32),
                    "moe_calls": jnp.zeros((2, 2), jnp.int32),
                    "moe_dropped": jnp.zeros((), jnp.int32),
                    "latent_attended": jnp.zeros((2, 2), jnp.int32),
                    "latent_written": jnp.zeros((2, 1), jnp.int32)}}

    def _count(ctr, name, inc):
        """ctr[name] (high, low) + inc >= 0, with the carries."""
        high, low = ctr[name][0], ctr[name][1] + inc
        return {**ctr, name: jnp.stack([high + low // CARRY, low % CARRY])}

    def _at(phase, v):
        return jnp.zeros((2,), v.dtype).at[phase].set(v)

    @functools.partial(jax.jit, static_argnames=("role",))
    def _attend(q, latent, li, tables, q_start, q_len, kv_len, role):
        """q [S, Q, nh, dl + dr] -> the latent-space output [S, Q, nh, dl].
        Jitted: the layers of one executable share one lowered call."""
        q = jnp.pad(q, ((0, 0),) * 3 + ((0, width - q.shape[-1]),))
        kw = dict(dv=dl, sm_scale=sm_scale, layer=li)
        if use_kernel:    # static  # graftlint: disable=TRACE001
            return mla_paged_attention(q, latent, tables, q_start, q_len,
                                       kv_len, role=role,
                                       interpret=interpret, **kw)
        return mla_paged_attention_ref(q, latent, tables, q_start, q_len,
                                       kv_len, **kw)

    def _attn_out(lp, o_lat):
        """The latent-space output [T, nh, dl] -> the mixer's [T, H]."""
        w_uv = lp["w_kvb"].reshape(dl, nh, dn + dv)[:, :, dn:]
        o = jnp.einsum("thl,lhv->thv", o_lat, w_uv)
        return o.reshape(-1, nh * dv) @ lp["wo"]

    def _stored(row):
        """[T, dl + dr] -> [T, 1, W]: the row as the store keeps it."""
        return jnp.pad(row, ((0, 0), (0, width - row.shape[-1])))[:, None]

    def _mlp(bp, li, g, valid, ctr, phase):
        """The layer's MLP over the normed g [T, H] -> (out, ctr, sel [T,
        log_k] or None for a dense layer)."""
        if li < nd:    # a python int  # graftlint: disable=TRACE001
            lp = {leaf: per[li] for leaf, per in bp["dense"].items()}
            return _swiglu(g, lp["w_gate"], lp["w_up"], lp["w_down"]), ctr, \
                None
        lp = {leaf: per[li - nd] for leaf, per in bp["moe"].items()}
        out, rows, beyond, sel = moe_layer(
            c, lp, g, valid, expert=functools.partial(
                grouped_swiglu, kernel=use_kernel, interpret=interpret,
                role=("decode", "prefill")[phase]))
        ctr = _count(ctr, "moe_pairs", _at(phase, rows.sum()))
        ctr = _count(ctr, "moe_touched",
                     _at(phase, (rows > 0).sum(dtype=jnp.int32)))
        ctr = _count(ctr, "moe_calls", _at(phase, jnp.int32(1)))
        ctr = {**ctr, "moe_dropped": ctr["moe_dropped"] + beyond}
        return out, ctr, jnp.pad(sel, ((0, 0), (0, log_k - top_k)))

    def _mlp_span(li):
        """The region of layer ``li``'s MLP half: a dense MLP or the expert
        layer's glue (what `moe.route` ... `moe.shared` do not own)."""
        # a python int  # graftlint: disable=TRACE001
        return "block.mlp" if li < nd else "moe.layer"

    def _normed(x, w):
        """The float32 residual stream, normed, in the compute dtype."""
        return _rms(x, w, eps).astype(d)

    @device_span("head")
    def _head(hp, h_last):
        """-> (logits float32, the greedy token's log-probability)."""
        logits = jnp.dot(_normed(h_last, hp["ln_f"]), hp["lm"],
                         preferred_element_type=f32)
        top = logits.max(-1)
        return logits, -jnp.log(jnp.exp(logits - top[..., None]).sum(-1))

    def _run(params, ids, start, length, page_row, slot, cache):
        """A run of C tokens of the sequence riding ``slot``, at positions
        start .. start + C - 1, the first ``length`` real -> (logits of the
        last real token, cache)."""
        ep, bp, hp = params
        C = ids.shape[1]
        x = ep["tok"][ids[0]].astype(f32)
        start, length = start.astype(jnp.int32), length.astype(jnp.int32)
        pos = start + jnp.arange(C, dtype=jnp.int32)
        real = jnp.arange(C) < length
        seg = SEGMENT if C % SEGMENT == 0 else C
        nseg = C // seg
        seg_off = jnp.arange(nseg, dtype=jnp.int32) * seg
        seg_start = start + seg_off
        seg_len = jnp.clip(length - seg_off, 0, seg)
        tables = jnp.broadcast_to(page_row[None], (nseg,) + page_row.shape)
        cache = dict(cache)
        ctr = _count(cache["ctr"], "latent_written", length)
        # the (query, key) pairs the run's real queries see
        ctr = _count(ctr, "latent_attended", _at(
            PREFILL, length * start + length * (length + 1) // 2))
        for li in range(L):
            lp = {leaf: per[li] for leaf, per in bp["attn"].items()}
            with device_span("mla.project"):
                q, row = mla_project(c, lp, _normed(x, lp["norm"]), pos)
                cache["latent"] = scatter_kv_run(cache["latent"], li,
                                                 _stored(row), start, length,
                                                 page_row)
                o = _attend(q.reshape(nseg, seg, nh, -1), cache["latent"],
                            li, tables, seg_start, seg_len,
                            seg_start + seg_len, role="chunk")
                x = x + _attn_out(lp, o.reshape(C, nh, dl)).astype(f32)
            with device_span(_mlp_span(li)):
                out, ctr, sel = _mlp(bp, li, _normed(x, lp["post_norm"]),
                                     real, ctr, PREFILL)
                if sel is not None:
                    cache["sel"] = log_selections_run(cache["sel"], li - nd,
                                                      slot, sel, start)
                x = x + out.astype(f32)
        cache["ctr"] = ctr
        h_last = jax.lax.dynamic_index_in_dim(x, length - 1, 0,
                                              keepdims=False)
        logits, logp = _head(hp, h_last)
        cache["logp"] = cache["logp"].at[slot, start + length - 1].set(
            logp, mode="drop")
        return logits, cache

    def prefill(params, ids, true_len, page_row, slot, cache):  # graftlint: jit
        return _run(params, ids, jnp.zeros((), jnp.int32), true_len,
                    page_row, slot, cache)

    def prefill_chunk(params, ids, start, chunk_len, page_row, slot,
                      cache):                         # graftlint: jit
        logits, cache = _run(params, ids, start, chunk_len, page_row, slot,
                             cache)
        with device_span("head"):
            tok = jnp.argmax(logits).astype(jnp.int32)
        return logits, tok, cache

    def decode_step(params, toks, lengths, page_tables, cache,
                    active):                          # graftlint: jit
        ep, bp, hp = params
        x = ep["tok"][toks].astype(f32)               # [S, H]
        pos = jnp.where(active, lengths, 0).astype(jnp.int32)
        page = jnp.where(active, jnp.take_along_axis(
            page_tables, (pos // page_size)[:, None], 1)[:, 0], TRASH)
        off = pos % page_size
        eff_len = jnp.where(active, lengths + 1, 0).astype(jnp.int32)
        n_q = active.astype(jnp.int32)
        cache = dict(cache)
        ctr = _count(cache["ctr"], "latent_written", n_q.sum())
        ctr = _count(ctr, "latent_attended", _at(DECODE, eff_len.sum()))
        # a dead slot's log entries fall past the row's end: dropped
        log_pos = jnp.where(active, lengths, ctx)
        slots = jnp.arange(toks.shape[0])
        for li in range(L):
            lp = {leaf: per[li] for leaf, per in bp["attn"].items()}
            with device_span("mla.project"):
                q, row = mla_project(c, lp, _normed(x, lp["norm"]), pos)
                cache["latent"] = scatter_kv_rows(cache["latent"], li,
                                                  _stored(row), page, off)
                o = _attend(q[:, None], cache["latent"], li, page_tables,
                            pos, n_q, eff_len, role="decode")[:, 0]
                x = x + _attn_out(lp, o).astype(f32)
            with device_span(_mlp_span(li)):
                out, ctr, sel = _mlp(bp, li, _normed(x, lp["post_norm"]),
                                     active, ctr, DECODE)
                if sel is not None:
                    cache["sel"] = cache["sel"].at[
                        li - nd, slots, :, log_pos].set(sel, mode="drop")
                x = x + out.astype(f32)
        cache["ctr"] = ctr
        logits, logp = _head(hp, x)
        cache["logp"] = cache["logp"].at[slots, log_pos].set(logp,
                                                            mode="drop")
        return logits, cache

    def counters(cache):
        """The device-side counters as host numbers (one small fetch)."""
        got = jax.device_get(cache["ctr"])
        dropped = int(got.pop("moe_dropped"))
        got = {name: pair[0].astype(object) * CARRY + pair[1].astype(object)
               for name, pair in got.items()}
        return {
            "latent_tokens_attended_decode":
                int(got["latent_attended"][DECODE]),
            "latent_pairs_attended_prefill":
                int(got["latent_attended"][PREFILL]),
            "latent_rows_written": int(got["latent_written"][0]),
            # what a token REQUIRES the store to keep, and what it keeps
            "latent_bytes_per_token": L * c.latent_row * d.itemsize,
            "latent_bytes_per_token_stored": L * width * d.itemsize,
            "moe_pairs_held": int(got["moe_pairs"].sum()),
            "moe_experts_touched_decode": int(got["moe_touched"][DECODE]),
            "moe_experts_touched_prefill": int(got["moe_touched"][PREFILL]),
            "moe_expert_layer_calls_decode": int(got["moe_calls"][DECODE]),
            "moe_expert_layer_calls_prefill": int(got["moe_calls"][PREFILL]),
            "moe_experts_held": held,
            "moe_rows_dropped": dropped,
        }

    def slot_state(cache, slot):
        """``moe_sel [Le, positions, k]``: the selections of the positions
        the slot's sequence consumed, and ``logp [positions]``: the greedy
        token's log-probability after each (the caller knows how many
        positions; the rest is an earlier sequence's)."""
        return {"moe_sel": np.asarray(cache["sel"][:, slot, :top_k])
                .swapaxes(1, 2),
                "logp": np.asarray(cache["logp"][slot])}

    return PagedFamily(name="mla_moe", init_cache=init_cache,
                       prefill=prefill, prefill_chunk=prefill_chunk,
                       decode_step=decode_step, page_leaves=("latent",),
                       verify_step=None, recurrent=False, counters=counters,
                       slot_state=slot_state,
                       # the kernel walks live pages only: one chunk
                       # executable a padded length, whatever the context
                       chunk_table_granule=0,
                       attention_path=attention_path)
