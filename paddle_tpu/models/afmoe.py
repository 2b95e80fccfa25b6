"""AFMoE (arcee-ai Trinity, ``model_type: afmoe``) for the compiled train
step: the functional counterpart of `build_functional_llama` for a decoder
whose layers DIFFER.

Per layer (HF ``modeling_afmoe.py``; x is [T, H], RMSNorm everywhere):

  attention  h = norm_in(x); q, k = per-head RMSNorm of (h Wq), (h Wk) over
             the head dim; v = h Wv; g = h Wg.  A ``sliding_attention`` layer
             rotates q, k (rotate-half, theta) and query i sees keys
             i-window+1 .. i; a ``full_attention`` layer has NO rotary and is
             causal over all keys.  a = softmax(q k^T / sqrt(D)) v, gated:
             attn = (a * sigmoid(g)) Wo.
  block      x' = x + norm_post_attn(attn);
             y  = x' + norm_post_mlp(MLP(norm_pre_mlp(x'))).
  MLP        the first ``num_dense_layers`` layers: SwiGLU of
             ``intermediate_size``.  The others: Shared(u) + sum over the
             top-k routed experts of w_e Expert_e(u), all SwiGLUs of
             ``moe_intermediate_size``; sigmoid scores, a selection bias
             (a buffer: not trained by the loss), normalised and scaled
             weights (`incubate.distributed.models.moe.dropless`).
  embedding  E[ids] * sqrt(H) (``mup_enabled``); head = norm_f then W_lm.

An expert-parallel rank's share: ``experts_held=(offset, count)`` — the
router keeps all ``num_experts`` outputs and the published top-k, this rank
holds ``count`` experts' weights and adds their part of the sum; the shared
expert is whole.  A sliced vocabulary is a smaller ``vocab_size``.

Blocks are stacked BY KIND — ``{"dense": {...[n_dense, ...]}, "moe":
{...[n_moe, ...]}}`` — and no leaf is padded to another kind's shape;
`layer_params(blocks, i, num_dense_layers)` gives layer i's leaves and
``block_apply(lp, x, i)`` runs it (the layer index is static: it picks
window or full, dense or expert).  ``block_apply`` returns ``(x, routed)``: None for a dense layer,
else ``{"rows": int32 [count], the rows routed to each held expert — the
step's counters; "sel": int32 [T, k], the experts each token selected;
"held_pairs": how many of those pairs name a held expert (= rows.sum() when
no row is dropped); "load": int32 [num_experts], the tokens that selected
each of ALL the experts}``.

The selection bias is a BUFFER: the loss does not train it and the optimizer
leaves it alone (`is_buffer`); a step moves it by `balance_bias_update` from
the step's own ``load`` (`dropless.py`), so that the experts' loads stay even.

The norms' weights are float32 whatever ``dtype`` is: a bfloat16 1.0 cannot
move by less than 0.4 %, so an update of any usual learning rate would round
away (there is no float32 master copy of the weights).
"""
from __future__ import annotations

import dataclasses
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from ..incubate.distributed.models.moe.dropless import (dropless_expert_ffn,
                                                        expert_load,
                                                        sigmoid_topk_route)
from ..profiler import device_span

__all__ = ["AfmoeConfig", "afmoe_config_tiny", "build_functional_afmoe",
           "layer_params", "is_buffer"]

# leaves the loss does not train (the optimizer leaves them as they are)
BUFFERS = ("router_bias",)


def is_buffer(name: str) -> bool:
    """True for a flattened leaf name (``moe.router_bias``) that is a
    buffer, not a parameter."""
    return name.rsplit(".", 1)[-1] in BUFFERS


@dataclasses.dataclass
class AfmoeConfig:
    """The public ``config.json`` keys of an afmoe model."""
    vocab_size: int = 200192
    hidden_size: int = 2048
    intermediate_size: int = 6144
    moe_intermediate_size: int = 1024
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    num_experts: int = 128
    num_experts_per_tok: int = 8
    num_shared_experts: int = 1
    score_func: str = "sigmoid"
    route_norm: bool = True
    route_scale: float = 2.826
    load_balance_coeff: float = 0.001
    sliding_window: int = 2048
    layer_types: Optional[Tuple[str, ...]] = None
    global_attn_every_n_layers: int = 4
    mup_enabled: bool = True
    rope_theta: float = 10000.0
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 131072
    hidden_act: str = "silu"
    tie_word_embeddings: bool = False

    def kinds(self):
        """``layer_types``, or the published rule: every
        ``global_attn_every_n_layers``-th layer is full."""
        if self.layer_types is not None:
            kinds = tuple(self.layer_types)
        else:
            n = self.global_attn_every_n_layers
            kinds = tuple("full_attention" if (i + 1) % n == 0
                          else "sliding_attention"
                          for i in range(self.num_hidden_layers))
        if len(kinds) != self.num_hidden_layers or set(kinds) - {
                "sliding_attention", "full_attention"}:
            raise ValueError(f"layer_types {kinds} do not describe "
                             f"{self.num_hidden_layers} afmoe layers")
        return kinds

    def validate(self):
        if self.score_func != "sigmoid" or self.hidden_act != "silu" \
                or self.num_shared_experts != 1 or self.tie_word_embeddings:
            raise ValueError("afmoe as built here has sigmoid scores, silu, "
                             "one shared expert and an untied head")
        if not 0 <= self.num_dense_layers <= self.num_hidden_layers:
            raise ValueError("num_dense_layers outside the model")
        self.kinds()


def afmoe_config_tiny(**kw):
    """The CPU tests' size: every mechanism present, nothing at scale."""
    base = dict(vocab_size=256, hidden_size=64, intermediate_size=192,
                moe_intermediate_size=32, num_hidden_layers=5,
                num_dense_layers=1, num_attention_heads=8,
                num_key_value_heads=2, head_dim=8, num_experts=16,
                num_experts_per_tok=4, sliding_window=8,
                layer_types=("sliding_attention",) * 4 + ("full_attention",),
                max_position_embeddings=64)
    base.update(kw)
    return AfmoeConfig(**base)


def layer_params(blocks, i, num_dense_layers):
    """Layer i's leaves out of the two stacked groups."""
    group, j = ("dense", i) if i < num_dense_layers \
        else ("moe", i - num_dense_layers)
    return jax.tree_util.tree_map(lambda v: v[j], blocks[group])


def _rope(x, theta):
    """x [B, S, heads, D]: rotate-half rotary at positions 0..S-1, computed
    in float32."""
    s, d = x.shape[1], x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[None, :, None, :]
    x32 = x.astype(jnp.float32)
    x1, x2 = x32[..., : d // 2], x32[..., d // 2:]
    rot = jnp.concatenate([-x2, x1], -1)
    return (x32 * jnp.cos(ang) + rot * jnp.sin(ang)).astype(x.dtype)


def build_functional_afmoe(config: AfmoeConfig, key=None, dtype=None,
                           experts_held: Optional[Tuple[int, int]] = None,
                           head_chunks: int = 0, init_params: bool = True):
    """Returns (embed_params, block_params, head_params, embed_apply,
    block_apply, head_loss_apply), as `build_functional_llama` does.

    ``experts_held=(offset, count)``: the experts this rank holds (default:
    all).  batch = (input_ids [B, S], labels [B, S]); ``embed_apply`` returns
    [1, B, S, H] (one micro-batch), ``head_loss_apply(p, y, batch)`` takes
    that shape back.  Kernels come from the registry
    (``flash_attention_causal`` with its ``window``, ``rms_norm``); off the
    TPU the jnp fallbacks run.
    """
    c = config
    c.validate()
    d = jnp.dtype(dtype) if dtype is not None else jnp.float32
    key = key if key is not None else jax.random.PRNGKey(0)
    kinds = c.kinds()
    offset, held = experts_held if experts_held is not None \
        else (0, c.num_experts)
    if not (0 <= offset and held >= 1 and offset + held <= c.num_experts):
        raise ValueError(f"experts_held {(offset, held)} outside the "
                         f"{c.num_experts} experts")
    H, D = c.hidden_size, c.head_dim
    q_dim, kv_dim = c.num_attention_heads * D, c.num_key_value_heads * D
    n_dense = c.num_dense_layers
    n_moe = c.num_hidden_layers - n_dense
    I, M = c.intermediate_size, c.moe_intermediate_size

    def init(k, shape, scale=None):
        # fan-in is the second-to-last dim of a (stacked) [.., in, out] leaf
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(d)

    def attention_leaves(k, n):
        ks = jax.random.split(k, 5)
        ones = lambda *s: jnp.ones(s, jnp.float32)
        return {"ln_in": ones(n, H), "ln_post_attn": ones(n, H),
                "ln_pre_mlp": ones(n, H), "ln_post_mlp": ones(n, H),
                "ln_q": ones(n, D), "ln_k": ones(n, D),
                "wq": init(ks[0], (n, H, q_dim)),
                "wk": init(ks[1], (n, H, kv_dim)),
                "wv": init(ks[2], (n, H, kv_dim)),
                "wg": init(ks[3], (n, H, q_dim)),
                "wo": init(ks[4], (n, q_dim, H))}

    if not init_params:
        embed_params = block_params = head_params = None
    else:
        ks = jax.random.split(key, 16)
        embed_params = {"tok": init(ks[0], (c.vocab_size, H), 0.02)}
        block_params = {}
        if n_dense:
            block_params["dense"] = {
                **attention_leaves(ks[1], n_dense),
                "wgate": init(ks[2], (n_dense, H, I)),
                "wup": init(ks[3], (n_dense, H, I)),
                "wdown": init(ks[4], (n_dense, I, H))}
        if n_moe:
            block_params["moe"] = {
                **attention_leaves(ks[5], n_moe),
                "router": init(ks[6], (n_moe, H, c.num_experts)),
                # a buffer (the published model balances load by moving
                # it); small enough to change selections, not to set them
                "router_bias": (0.01 * jax.random.normal(
                    ks[7], (n_moe, c.num_experts), jnp.float32)),
                "ws_gate": init(ks[8], (n_moe, H, M)),
                "ws_up": init(ks[9], (n_moe, H, M)),
                "ws_down": init(ks[10], (n_moe, M, H)),
                "we_gate": init(ks[11], (n_moe, held, H, M)),
                "we_up": init(ks[12], (n_moe, held, H, M)),
                "we_down": init(ks[13], (n_moe, held, M, H))}
        head_params = {"ln_f": jnp.ones((H,), jnp.float32),
                       "lm": init(ks[14], (H, c.vocab_size), 0.02)}

    def rms(x, w):
        from ..core.dispatch import get_kernel
        from ..nn.functional.norm import rms_norm_ref
        impl = get_kernel("rms_norm")
        if impl is not None:
            return impl(x, w, epsilon=c.rms_norm_eps)
        return rms_norm_ref(x, w, c.rms_norm_eps)

    def attention(q, k, v, window):
        from ..core.dispatch import get_kernel
        from ..ops.pallas.flash_attention import flash_attention_ref
        impl = get_kernel("flash_attention_causal")
        if impl is not None:
            return impl(q, k, v, window=window)
        return flash_attention_ref(q, k, v, causal=True, window=window)

    def swiglu(u, wgate, wup, wdown):
        return (jax.nn.silu(u @ wgate) * (u @ wup)) @ wdown

    @device_span("embed")
    def embed_apply(p, batch):
        ids, _ = batch
        x = p["tok"][ids]
        if c.mup_enabled:
            x = x * jnp.asarray(math.sqrt(H), x.dtype)
        return x[None]

    def block_apply(lp, x, layer):
        """x [B, S, H] -> (y [B, S, H], routed: None or rows, sel, ...)."""
        B, S, _ = x.shape
        sliding = kinds[layer] == "sliding_attention"
        with device_span("block.attn"):
            h = rms(x, lp["ln_in"])
            q = rms((h @ lp["wq"]).reshape(B, S, -1, D), lp["ln_q"])
            k = rms((h @ lp["wk"]).reshape(B, S, -1, D), lp["ln_k"])
            v = (h @ lp["wv"]).reshape(B, S, -1, D)
            if sliding:
                q, k = _rope(q, c.rope_theta), _rope(k, c.rope_theta)
            a = attention(q, k, v, c.sliding_window if sliding else None)
            a = a.reshape(B, S, q_dim) * jax.nn.sigmoid(h @ lp["wg"])
            x = x + rms(a @ lp["wo"], lp["ln_post_attn"])
        if "router" not in lp:
            with device_span("block.mlp"):
                mlp = swiglu(rms(x, lp["ln_pre_mlp"]), lp["wgate"],
                             lp["wup"], lp["wdown"])
                return x + rms(mlp, lp["ln_post_mlp"]), None
        # the expert half: its norms, the residual and what no `moe.*`
        # function of `dropless.py` owns are the layer's glue
        with device_span("moe.layer"):
            u = rms(x, lp["ln_pre_mlp"])
            uf = u.reshape(B * S, H)
            sel, w = sigmoid_topk_route(
                uf, lp["router"], lp["router_bias"], c.num_experts_per_tok,
                c.route_scale, c.route_norm)
            part, rows = dropless_expert_ffn(
                uf, sel, w, lp["we_gate"], lp["we_up"], lp["we_down"],
                offset, c.num_experts)
            with device_span("moe.shared"):
                shared = swiglu(u, lp["ws_gate"], lp["ws_up"], lp["ws_down"])
            mlp = shared + part.reshape(B, S, H)
            load = expert_load(sel, c.num_experts)
            routed = {"rows": rows, "sel": sel, "load": load,
                      "held_pairs": load[offset:offset + held].sum()}
            return x + rms(mlp, lp["ln_post_mlp"]), routed

    @device_span("head_loss")
    def head_loss_apply(p, y, batch):
        """y [1, B, S, H] -> mean token NLL over the vocabulary held."""
        _, labels = batch
        h = rms(y, p["ln_f"]).reshape(-1, H)
        lab = labels.reshape(-1).astype(jnp.int32)
        if head_chunks:
            from ..incubate.nn.functional import \
                fused_linear_cross_entropy_impl
            return jnp.mean(fused_linear_cross_entropy_impl(
                h, p["lm"], lab, n_chunks=head_chunks))
        logp = jax.nn.log_softmax((h @ p["lm"]).astype(jnp.float32), -1)
        return -jnp.mean(jnp.take_along_axis(logp, lab[:, None], -1))

    return (embed_params, block_params, head_params, embed_apply, block_apply,
            head_loss_apply)
