"""LLaMA model family (LLaMA-2 7B/13B, TP+PP).

Two forms:
 * `LlamaForCausalLM` — eager Layer (dygraph parity; PaddleNLP-style config),
   using the framework attention dispatch (Pallas flash-attn override) and
   optional fleet TP layers when mp_degree > 1.
 * `build_functional_llama` — pure param-pytree + apply fns matching
   paddle_tpu.parallel.PipelineTrainStep's (embed, block, head) contract,
   used by the hybrid dp×pp×mp compiled train step, the benchmark's
   train driver, and __graft_entry__.dryrun_multichip.
"""
from __future__ import annotations

import functools
import math
from dataclasses import dataclass, field

import jax
import jax.numpy as jnp
import numpy as np

from ..core.tensor import Tensor, Parameter
from ..nn.layer import Layer
from ..nn import Linear, Embedding, RMSNorm, LayerList
from ..nn import functional as F
from ..tensor import manipulation as manip
from ..incubate.nn.functional import fused_rotary_position_embedding
from ..profiler import device_span

__all__ = ["LlamaConfig", "LlamaForCausalLM", "LlamaModel", "LlamaDecoderLayer",
           "build_functional_llama", "llama_microbatch_fns", "llama_block_specs",
           "llama_config_7b", "llama_config_tiny", "build_llama_decode",
           "build_llama_paged_decode", "make_paged_decode_horizon",
           "pack_decode_state", "split_call_key",
           "functional_params_from_layer", "llama_generate",
           "gather_kv_pages", "scatter_kv_pages", "scatter_kv_rows",
           "scatter_kv_run"]


@dataclass
class LlamaConfig:
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_hidden_layers: int = 32
    num_attention_heads: int = 32
    num_key_value_heads: int = 32
    max_position_embeddings: int = 4096
    rms_norm_eps: float = 1e-5
    rope_theta: float = 10000.0
    tie_word_embeddings: bool = False
    use_flash_attention: bool = True
    tensor_parallel_degree: int = 1
    dtype: str = "float32"
    # MoE variant (LLaMA-MoE / Mixtral-style): num_experts > 1 swaps the
    # dense MLP for a MoELayer of per-expert SwiGLU FFNs
    num_experts: int = 1
    moe_topk: int = 2
    moe_capacity_factor: float = 1.25
    moe_aux_loss_weight: float = 0.01

    def paged_family(self, **build_kw):
        """The seam `inference.paged.ServingEngine` builds its fns through
        (`models/paged_family.py`)."""
        return build_llama_paged_decode(self, **build_kw)


def llama_config_7b():
    return LlamaConfig()


def llama_config_tiny(vocab=1024, hidden=128, layers=2, heads=4, seq=128):
    return LlamaConfig(vocab_size=vocab, hidden_size=hidden,
                      intermediate_size=hidden * 3, num_hidden_layers=layers,
                      num_attention_heads=heads, num_key_value_heads=heads,
                      max_position_embeddings=seq)


def _rope_tables(seq_len, head_dim, theta, dtype=jnp.float32):
    inv = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    pos = jnp.arange(seq_len, dtype=jnp.float32)
    freqs = jnp.outer(pos, inv)
    emb = jnp.concatenate([freqs, freqs], axis=-1)
    return jnp.sin(emb).astype(dtype), jnp.cos(emb).astype(dtype)


def _apply_rope(x, sin, cos):
    # x: [B, S, H, D]; sin/cos: [S, D]
    half = x.shape[-1] // 2
    x1, x2 = x[..., :half], x[..., half:]
    rot = jnp.concatenate([-x2, x1], axis=-1)
    return x * cos[None, :, None, :] + rot * sin[None, :, None, :]


class LlamaAttention(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        self.num_heads = c.num_attention_heads
        self.num_kv = c.num_key_value_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        self.config = c
        tp = c.tensor_parallel_degree
        if tp > 1:
            from ..distributed.fleet.meta_parallel import (ColumnParallelLinear,
                                                           RowParallelLinear)
            self.q_proj = ColumnParallelLinear(c.hidden_size,
                                               self.num_heads * self.head_dim,
                                               has_bias=False, gather_output=False)
            self.k_proj = ColumnParallelLinear(c.hidden_size,
                                               self.num_kv * self.head_dim,
                                               has_bias=False, gather_output=False)
            self.v_proj = ColumnParallelLinear(c.hidden_size,
                                               self.num_kv * self.head_dim,
                                               has_bias=False, gather_output=False)
            self.o_proj = RowParallelLinear(self.num_heads * self.head_dim,
                                            c.hidden_size, has_bias=False,
                                            input_is_parallel=True)
        else:
            self.q_proj = Linear(c.hidden_size, self.num_heads * self.head_dim,
                                 bias_attr=False)
            self.k_proj = Linear(c.hidden_size, self.num_kv * self.head_dim,
                                 bias_attr=False)
            self.v_proj = Linear(c.hidden_size, self.num_kv * self.head_dim,
                                 bias_attr=False)
            self.o_proj = Linear(self.num_heads * self.head_dim, c.hidden_size,
                                 bias_attr=False)

    def forward(self, x, sin=None, cos=None):
        b, s, _ = x.shape
        q = manip.reshape(self.q_proj(x), [b, s, -1, self.head_dim])
        k = manip.reshape(self.k_proj(x), [b, s, -1, self.head_dim])
        v = manip.reshape(self.v_proj(x), [b, s, -1, self.head_dim])
        if sin is not None:
            from ..core.dispatch import op_call
            q = op_call("rope", lambda qq: _apply_rope(qq, sin, cos), q)
            k = op_call("rope", lambda kk: _apply_rope(kk, sin, cos), k)
        # GQA KV heads pass through un-repeated: the Pallas kernel indexes
        # them natively; the jnp fallback up-materializes internally
        out = F.scaled_dot_product_attention(q, k, v, is_causal=True,
                                             training=self.training)
        out = manip.reshape(out, [b, s, -1])
        return self.o_proj(out)


class LlamaMLP(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        c = config
        tp = c.tensor_parallel_degree
        if tp > 1:
            from ..distributed.fleet.meta_parallel import (ColumnParallelLinear,
                                                           RowParallelLinear)
            self.gate_proj = ColumnParallelLinear(c.hidden_size, c.intermediate_size,
                                                  has_bias=False, gather_output=False)
            self.up_proj = ColumnParallelLinear(c.hidden_size, c.intermediate_size,
                                                has_bias=False, gather_output=False)
            self.down_proj = RowParallelLinear(c.intermediate_size, c.hidden_size,
                                               has_bias=False, input_is_parallel=True)
        else:
            self.gate_proj = Linear(c.hidden_size, c.intermediate_size, bias_attr=False)
            self.up_proj = Linear(c.hidden_size, c.intermediate_size, bias_attr=False)
            self.down_proj = Linear(c.intermediate_size, c.hidden_size, bias_attr=False)

    def forward(self, x):
        return self.down_proj(F.swiglu(self.gate_proj(x), self.up_proj(x)))


class LlamaMoEBlock(Layer):
    """Mixtral/LLaMA-MoE-style sparse MLP: MoELayer over per-expert SwiGLU
    FFNs (expert-parallel-ready via incubate moe; dense eager here)."""

    def __init__(self, config: LlamaConfig):
        super().__init__()
        if config.tensor_parallel_degree > 1:
            raise NotImplementedError(
                "LlamaMoEBlock: tensor-parallel experts are not implemented "
                "— use expert parallelism (incubate moe ep_axis / moe_ffn "
                "over an 'ep' mesh axis) instead of mp for the MoE variant")
        from ..incubate.distributed.models.moe import MoELayer

        class _Expert(Layer):
            def __init__(self, c):
                super().__init__()
                self.gate_proj = Linear(c.hidden_size, c.intermediate_size,
                                        bias_attr=False)
                self.up_proj = Linear(c.hidden_size, c.intermediate_size,
                                      bias_attr=False)
                self.down_proj = Linear(c.intermediate_size, c.hidden_size,
                                        bias_attr=False)

            def forward(self, x):
                return self.down_proj(
                    F.swiglu(self.gate_proj(x), self.up_proj(x)))

        self.moe = MoELayer(
            d_model=config.hidden_size,
            experts=[_Expert(config) for _ in range(config.num_experts)],
            gate={"type": "gshard", "top_k": config.moe_topk},
            capacity_factor=config.moe_capacity_factor)

    def forward(self, x):
        return self.moe(x)

    def aux_loss(self):
        l = self.moe.gate.get_loss()
        return l


class LlamaDecoderLayer(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.input_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.self_attn = LlamaAttention(config)
        self.post_attention_layernorm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        self.mlp = LlamaMoEBlock(config) if config.num_experts > 1 \
            else LlamaMLP(config)

    def forward(self, x, sin=None, cos=None):
        x = x + self.self_attn(self.input_layernorm(x), sin, cos)
        x = x + self.mlp(self.post_attention_layernorm(x))
        return x


class LlamaModel(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        if config.tensor_parallel_degree > 1:
            from ..distributed.fleet.meta_parallel import VocabParallelEmbedding
            self.embed_tokens = VocabParallelEmbedding(config.vocab_size,
                                                       config.hidden_size)
        else:
            self.embed_tokens = Embedding(config.vocab_size, config.hidden_size)
        self.layers = LayerList([LlamaDecoderLayer(config)
                                 for _ in range(config.num_hidden_layers)])
        self.norm = RMSNorm(config.hidden_size, config.rms_norm_eps)
        head_dim = config.hidden_size // config.num_attention_heads
        sin, cos = _rope_tables(config.max_position_embeddings, head_dim,
                                config.rope_theta)
        self._sin, self._cos = sin, cos

    def forward(self, input_ids):
        x = self.embed_tokens(input_ids)
        s = x.shape[1]
        sin, cos = self._sin[:s], self._cos[:s]
        for layer in self.layers:
            x = layer(x, sin, cos)
        return self.norm(x)


class LlamaForCausalLM(Layer):
    def __init__(self, config: LlamaConfig):
        super().__init__()
        self.config = config
        self.llama = self.model = LlamaModel(config)
        self.lm_head = Linear(config.hidden_size, config.vocab_size, bias_attr=False)
        if config.tie_word_embeddings:
            self.lm_head.weight = self.model.embed_tokens.weight

    def generate(self, input_ids, max_new_tokens=32, temperature=0.0,
                 top_k=0, top_p=1.0, eos_token_id=None, seed=0):
        """Compiled KV-cache generation (PaddleNLP model.generate analog):
        exports this Layer's weights to the functional decode path once and
        decodes with a jitted per-token step."""
        if self.config.tensor_parallel_degree > 1:
            raise NotImplementedError("generate() needs full weights on this "
                                      "host (tensor_parallel_degree == 1)")
        if self.config.num_experts > 1:
            raise NotImplementedError(
                "generate() does not support the MoE variant — the functional "
                "decode path computes the dense FFN")
        # re-export per call: weights may have trained since the last one
        params = functional_params_from_layer(self)
        ids = input_ids._value if isinstance(input_ids, Tensor) \
            else jnp.asarray(input_ids)
        out = llama_generate(params, self.config, ids,
                             max_new_tokens=max_new_tokens,
                             temperature=temperature, top_k=top_k,
                             top_p=top_p, eos_token_id=eos_token_id,
                             seed=seed)
        return Tensor(out)

    def forward(self, input_ids, labels=None):
        h = self.model(input_ids)
        logits = self.lm_head(h)
        if labels is not None:
            loss = F.cross_entropy(
                manip.reshape(logits, [-1, self.config.vocab_size]),
                manip.reshape(labels, [-1]))
            if self.config.num_experts > 1:
                # collect per-layer MoE balance losses (Mixtral aux loss)
                for layer in self.model.layers:
                    aux = layer.mlp.aux_loss()
                    if aux is not None:
                        loss = loss + self.config.moe_aux_loss_weight * aux
            return loss, logits
        return logits


# ---------------------------------------------------------------------------
# Functional form (pipeline/benchmark path)
# ---------------------------------------------------------------------------
def llama_block_specs(mp_axis: str = "mp", moe: bool = False,
                      ep_axis: str = None):
    """Per-leaf PartitionSpec suffixes (excluding the leading layer dim) for
    Megatron-style tensor parallelism over `mp_axis`:

      wq/wk/wv, wgate/wup: column-parallel (output dim sharded over mp)
      wo, wdown:           row-parallel (input dim sharded, psum after)
      ln1/ln2:             replicated

    With moe=True the FFN leaves are the expert-stacked tensors; ep_axis
    shards their expert dim (expert parallelism — reference moe_layer.py).

    Reference: fleet/layers/mpu/mp_layers.py:336 (ColumnParallelLinear),
    :543 (RowParallelLinear) — here the sharded matmuls live inside the
    pipeline stage function (block_apply) as rank-local dots + lax.psum.
    """
    col = (None, mp_axis)
    row = (mp_axis, None)
    specs = {"ln1": (None,), "wq": col, "wk": col, "wv": col, "wo": row,
             "ln2": (None,)}
    if moe:
        exp = (ep_axis, None, None)
        specs.update({"gate_w": (None, None), "we_gate": exp, "we_up": exp,
                      "we_down": exp})
    else:
        specs.update({"wgate": col, "wup": col, "wdown": row})
    return specs


def llama_microbatch_fns(config: LlamaConfig, mp_axis: str = None, dtype=None,
                         ep_axis: str = None):
    """Per-microbatch (embed, block, head) adapters for the pipeline schedule
    step fns (Pipeline1F1BTrainStep et al.), without initializing a second
    parameter set: embed returns one [mbs, S, H] microbatch, head consumes a
    single microbatch activation."""
    _, _, _, ea1, ba1, hl1 = build_functional_llama(
        config, n_micro=1, mp_axis=mp_axis, ep_axis=ep_axis, dtype=dtype,
        init_params=False)
    embed_mb = lambda p, mb: ea1(p, mb)[0]
    head_mb = lambda p, y, mb: hl1(p, y[None], mb)
    return embed_mb, ba1, head_mb


def build_functional_llama(config: LlamaConfig, key=None, dtype=None,
                           n_micro: int = 1, mp_axis: str = None,
                           ep_axis: str = None, init_params: bool = True,
                           head_chunks: int = 0):
    """Returns (embed_params, block_params_stacked, head_params,
    embed_apply, block_apply, head_loss_apply).

    block_params leaves have leading dim num_hidden_layers (stackable over
    'pp'). batch = (input_ids[B,S], labels[B,S]); embed_apply splits B into
    n_micro microbatches.

    When mp_axis is set, block_apply is tensor-parallel over that mesh axis:
    it must then run inside shard_map with `mp_axis` in scope and with block
    weights sharded per `llama_block_specs(mp_axis)` (column-parallel QKV and
    gate/up, row-parallel wo/wdown followed by lax.psum over mp_axis).  The
    per-rank head counts are derived from the *local* weight shard shapes, so
    the same block_apply works sharded and unsharded.  Requires
    num_attention_heads % mp == 0 and num_key_value_heads % mp == 0.
    """
    c = config
    d = jnp.dtype(dtype) if dtype is not None else jnp.float32
    key = key if key is not None else jax.random.PRNGKey(0)
    head_dim = c.hidden_size // c.num_attention_heads
    ks = jax.random.split(key, 16)

    def init(k, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[0])
        return (jax.random.normal(k, shape, jnp.float32) * scale).astype(d)

    L = c.num_hidden_layers
    kv_dim = c.num_key_value_heads * head_dim
    moe = c.num_experts > 1
    E = c.num_experts
    if not init_params:
        embed_params = block_params = head_params = None
    else:
        embed_params = {"tok": init(ks[0], (c.vocab_size, c.hidden_size), 0.02)}
        block_params = {
            "ln1": jnp.ones((L, c.hidden_size), d),
            "wq": jnp.stack([init(jax.random.fold_in(ks[1], i),
                                  (c.hidden_size, c.hidden_size)) for i in range(L)]),
            "wk": jnp.stack([init(jax.random.fold_in(ks[2], i),
                                  (c.hidden_size, kv_dim)) for i in range(L)]),
            "wv": jnp.stack([init(jax.random.fold_in(ks[3], i),
                                  (c.hidden_size, kv_dim)) for i in range(L)]),
            "wo": jnp.stack([init(jax.random.fold_in(ks[4], i),
                                  (c.hidden_size, c.hidden_size)) for i in range(L)]),
            "ln2": jnp.ones((L, c.hidden_size), d),
        }
        if moe:
            # expert-stacked FFN (LLaMA-MoE / Mixtral; ep-shardable on dim 1)
            block_params.update({
                "gate_w": jnp.stack([init(jax.random.fold_in(ks[9], i),
                                          (c.hidden_size, E), 0.02)
                                     for i in range(L)]),
                "we_gate": jnp.stack([init(jax.random.fold_in(ks[5], i),
                                           (E, c.hidden_size,
                                            c.intermediate_size),
                                           1.0 / math.sqrt(c.hidden_size))
                                      for i in range(L)]),
                "we_up": jnp.stack([init(jax.random.fold_in(ks[6], i),
                                         (E, c.hidden_size,
                                          c.intermediate_size),
                                         1.0 / math.sqrt(c.hidden_size))
                                    for i in range(L)]),
                "we_down": jnp.stack([init(jax.random.fold_in(ks[7], i),
                                           (E, c.intermediate_size,
                                            c.hidden_size),
                                           1.0 / math.sqrt(c.intermediate_size))
                                      for i in range(L)]),
            })
        else:
            block_params.update({
                "wgate": jnp.stack([init(jax.random.fold_in(ks[5], i),
                                         (c.hidden_size, c.intermediate_size)) for i in range(L)]),
                "wup": jnp.stack([init(jax.random.fold_in(ks[6], i),
                                       (c.hidden_size, c.intermediate_size)) for i in range(L)]),
                "wdown": jnp.stack([init(jax.random.fold_in(ks[7], i),
                                         (c.intermediate_size, c.hidden_size)) for i in range(L)]),
            })
        head_params = {"ln_f": jnp.ones((c.hidden_size,), d),
                       "lm": init(ks[8], (c.hidden_size, c.vocab_size), 0.02)}

    sin_t, cos_t = _rope_tables(c.max_position_embeddings, head_dim, c.rope_theta, d)

    def rms(x, w, eps=c.rms_norm_eps):
        from ..core.dispatch import get_kernel
        from ..nn.functional.norm import rms_norm_ref
        impl = get_kernel("rms_norm")
        if impl is not None:
            return impl(x, w, epsilon=eps)
        return rms_norm_ref(x, w, eps)

    @device_span("embed")
    def embed_apply(p, batch):
        ids, labels = batch
        # [B, S] -> [n_micro, mbs, S, H]
        x = p["tok"][ids]
        B = x.shape[0]
        mbs = B // n_micro
        return x.reshape((n_micro, mbs) + x.shape[1:])

    def _mp_reduce(y):
        # row-parallel epilogue: sum partials across mp ranks, then restore
        # the manual-varying type (psum strips mp from the vma set, but the
        # residual stream it is added to is varying over mp)
        if mp_axis is None:
            return y
        y = jax.lax.psum(y, mp_axis)
        return jax.lax.pcast(y, (mp_axis,), to="varying")

    def block_apply(lp, x):
        # x: [mbs, S, H] (one microbatch); weight leaves may be mp-local
        # shards (llama_block_specs) — head counts derive from local shapes
        B, S, H = x.shape
        nh_l = lp["wq"].shape[-1] // head_dim
        nkv_l = lp["wk"].shape[-1] // head_dim
        with device_span("block.attn"):
            h = rms(x, lp["ln1"])
            q = (h @ lp["wq"]).reshape(B, S, nh_l, head_dim)
            k = (h @ lp["wk"]).reshape(B, S, nkv_l, head_dim)
            v = (h @ lp["wv"]).reshape(B, S, nkv_l, head_dim)
            sin, cos = sin_t[:S], cos_t[:S]
            q = _apply_rope(q, sin, cos)
            k = _apply_rope(k, sin, cos)
            from ..core.dispatch import get_kernel
            attn_impl = get_kernel("flash_attention_causal")
            # GQA: the Pallas kernel indexes KV heads natively; only the jnp
            # fallback up-materializes (reference flash_attn GQA path)
            o = attn_impl(q, k, v) if attn_impl is not None else None
            if o is None:
                if nh_l != nkv_l:
                    rep = nh_l // nkv_l
                    k = jnp.repeat(k, rep, axis=2)
                    v = jnp.repeat(v, rep, axis=2)
                logits = jnp.einsum("bqhd,bkhd->bhqk", q, k) \
                    / math.sqrt(head_dim)
                mask = jnp.tril(jnp.ones((S, S), bool))
                logits = jnp.where(mask, logits.astype(jnp.float32), -jnp.inf)
                w = jax.nn.softmax(logits, -1).astype(x.dtype)
                o = jnp.einsum("bhqk,bkhd->bqhd", w, v)
            o = _mp_reduce(o.reshape(B, S, nh_l * head_dim) @ lp["wo"])
            x = x + o
        with device_span("block.mlp"):
            h = rms(x, lp["ln2"])
            if moe:
                return x + _moe_ffn_block(lp, h, B, S)
            ff = jax.nn.silu(h @ lp["wgate"]) * (h @ lp["wup"])
            return x + _mp_reduce(ff @ lp["wdown"])

    def _moe_ffn_block(lp, h, B, S):
        """Sparse SwiGLU FFN over the expert-stacked leaves. Under shard_map
        with `ep_axis` in scope the expert dim of we_* is the LOCAL shard and
        dispatch/combine ride lax.all_to_all (reference MoEScatter/MoEGather);
        without ep_axis it is the dense single-mesh computation."""
        from ..incubate.distributed.models.moe.gate import (top_k_gating,
                                                            compute_capacity)
        from ..incubate.distributed.models.moe.moe_layer import (
            moe_dispatch, moe_combine, ep_all_to_all, ep_all_to_all_back)
        T = B * S
        xf = h.reshape(T, -1)
        E_total = lp["gate_w"].shape[-1]
        logits = (xf @ lp["gate_w"].astype(xf.dtype)).astype(jnp.float32)
        capacity = compute_capacity(T, E_total, c.moe_topk,
                                    c.moe_capacity_factor)
        # balance aux loss is intentionally not routed through the pipeline
        # loss (the per-stage schedules carry only the LM loss); use the
        # eager LlamaMoEBlock path when the aux term must train the gate
        combine, dispatch, _aux, _ = top_k_gating(
            logits, c.moe_topk, capacity, balance_loss_weight=0.0)
        disp = moe_dispatch(xf, dispatch)                 # [E_total, C, H]
        if ep_axis is not None:
            disp = ep_all_to_all(disp, ep_axis)           # [E_local, W*C, H]
        ff = jax.nn.silu(jnp.einsum("ebd,edh->ebh", disp,
                                    lp["we_gate"].astype(disp.dtype))) \
            * jnp.einsum("ebd,edh->ebh", disp, lp["we_up"].astype(disp.dtype))
        y = jnp.einsum("ebh,ehd->ebd", ff, lp["we_down"].astype(ff.dtype))
        if ep_axis is not None:
            y = ep_all_to_all_back(y, ep_axis)            # [E_total, C, H]
        out = moe_combine(y, combine)
        return out.reshape(B, S, -1).astype(h.dtype)

    @device_span("head_loss")
    def head_loss_apply(p, y, batch):
        # y: [n_micro, mbs, S, H]
        ids, labels = batch
        B = labels.shape[0]
        mbs = B // n_micro
        lab = labels.reshape(n_micro, mbs, -1)
        h = rms(y, p["ln_f"])
        if head_chunks:
            # vocab-chunked online-logsumexp head: the [*, V] logits tensor
            # never materializes (round-4 perf work; see
            # incubate.nn.functional.fused_linear_cross_entropy_impl)
            from ..incubate.nn.functional import \
                fused_linear_cross_entropy_impl
            nllv = fused_linear_cross_entropy_impl(
                h.reshape(-1, c.hidden_size), p["lm"], lab.reshape(-1),
                n_chunks=head_chunks)
            return jnp.mean(nllv)
        logits = h @ p["lm"]
        logp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(logp, lab[..., None].astype(jnp.int32), -1)
        return jnp.mean(nll)

    return embed_params, block_params, head_params, embed_apply, block_apply, head_loss_apply


# ---------------------------------------------------------------------------
# Serving decode path (KV cache)
# ---------------------------------------------------------------------------
def build_llama_decode(config: LlamaConfig, max_seq: int = None, dtype=None):
    """Compiled autoregressive serving path (reference: the fused decode
    attention masked_multihead_attention_kernel.cu + Predictor decode loop).

    Returns (init_cache, prefill, decode_step) over the same
    (embed_params, block_params, head_params) pytrees build_functional_llama
    produces:

      cache = init_cache(B)                      # {"k","v" [L,B,S,KV,D], "pos"}
      logits, cache = prefill(params, ids)       # prompt pass, fills cache
      logits, cache = decode_step(params, tok, cache)   # one token, O(S) attn

    All shapes static (max_seq bounds the cache); jit decode_step once and
    every generated token reuses the executable.
    """
    c = config
    d = jnp.dtype(dtype) if dtype is not None else jnp.float32
    S_max = max_seq or c.max_position_embeddings
    head_dim = c.hidden_size // c.num_attention_heads
    L = c.num_hidden_layers
    nkv = c.num_key_value_heads
    sin_t, cos_t = _rope_tables(S_max, head_dim, c.rope_theta, d)

    from ..nn.functional.norm import rms_norm_ref

    def init_cache(batch):
        return {
            "k": jnp.zeros((L, batch, S_max, nkv, head_dim), d),
            "v": jnp.zeros((L, batch, S_max, nkv, head_dim), d),
            "pos": jnp.zeros((), jnp.int32),
        }

    def _rope_at(pos, T):
        """Rope table slice [pos:pos+T] — static fast path for a host-int
        pos (the dense-prefill pos=0 case), dynamic_slice for a traced
        pos.  The isinstance dispatch is static under trace (a tracer is
        an ndarray, a python int is not — no tracer bool conversion), and
        the callers hoist it out of the layer scan: one slice per step,
        not one per layer."""
        if isinstance(pos, jnp.ndarray):
            return (jax.lax.dynamic_slice_in_dim(sin_t, pos, T, 0),
                    jax.lax.dynamic_slice_in_dim(cos_t, pos, T, 0))
        return sin_t[pos:pos + T], cos_t[pos:pos + T]

    def _block_step(lp, x, k_cache, v_cache, pos, n_valid, sin, cos):
        """One decoder block on x [B, T, H] with cache write at pos and
        attention over cache[:, :n_valid]; sin/cos are the caller's rope
        slice for [pos, pos+T). Returns (x_out, k_cache, v_cache)."""
        B, T, H = x.shape
        nh = c.num_attention_heads
        h = rms_norm_ref(x, lp["ln1"], c.rms_norm_eps)
        q = (h @ lp["wq"]).reshape(B, T, nh, head_dim)
        k = (h @ lp["wk"]).reshape(B, T, nkv, head_dim)
        v = (h @ lp["wv"]).reshape(B, T, nkv, head_dim)
        q = _apply_rope(q, sin, cos)
        k = _apply_rope(k, sin, cos)
        k_cache = jax.lax.dynamic_update_slice_in_dim(k_cache, k, pos, 1)
        v_cache = jax.lax.dynamic_update_slice_in_dim(v_cache, v, pos, 1)
        rep = nh // nkv
        kf = jnp.repeat(k_cache, rep, axis=2) if rep > 1 else k_cache
        vf = jnp.repeat(v_cache, rep, axis=2) if rep > 1 else v_cache
        s = jnp.einsum("bthd,bshd->bhts", q.astype(jnp.float32),
                       kf.astype(jnp.float32)) / math.sqrt(head_dim)
        q_pos = pos + jnp.arange(T)[None, :, None]          # [1, T, 1]
        k_pos = jnp.arange(S_max)[None, None, :]            # [1, 1, S]
        mask = (k_pos <= q_pos) & (k_pos < n_valid)
        s = jnp.where(mask[:, None, :, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1).astype(x.dtype)
        o = jnp.einsum("bhts,bshd->bthd", p, vf).reshape(B, T, nh * head_dim)
        x = x + o @ lp["wo"]
        h = rms_norm_ref(x, lp["ln2"], c.rms_norm_eps)
        ff = jax.nn.silu(h @ lp["wgate"]) * (h @ lp["wup"])
        return x + ff @ lp["wdown"], k_cache, v_cache

    def _head(hp, x_last):
        h = rms_norm_ref(x_last, hp["ln_f"], c.rms_norm_eps)
        return (h @ hp["lm"]).astype(jnp.float32)

    def prefill(params, ids):                         # graftlint: jit
        """ids [B, T_prompt] -> (logits [B, vocab] for the last token, cache)."""
        ep, bp, hp = params
        B, T = ids.shape
        cache = init_cache(B)
        x = ep["tok"][ids].astype(d)
        sin, cos = _rope_at(0, T)

        def body(carry, layer_in):
            xc, = carry
            lp, kc, vc = layer_in
            x_out, kc, vc = _block_step(lp, xc, kc, vc, 0, T, sin, cos)
            return (x_out,), (kc, vc)

        (x,), (ks, vs) = jax.lax.scan(
            body, (x,), (bp, cache["k"], cache["v"]))
        cache = {"k": ks, "v": vs, "pos": jnp.asarray(T, jnp.int32)}
        return _head(hp, x[:, -1]), cache

    def decode_step(params, tok, cache):              # graftlint: jit
        """tok [B] int32 -> (logits [B, vocab], cache advanced by one)."""
        ep, bp, hp = params
        B = tok.shape[0]
        pos = cache["pos"]
        x = ep["tok"][tok][:, None, :].astype(d)       # [B, 1, H]
        sin, cos = _rope_at(pos, 1)

        def body(carry, layer_in):
            xc, = carry
            lp, kc, vc = layer_in
            x_out, kc, vc = _block_step(lp, xc, kc, vc, pos, pos + 1,
                                        sin, cos)
            return (x_out,), (kc, vc)

        (x,), (ks, vs) = jax.lax.scan(
            body, (x,), (bp, cache["k"], cache["v"]))
        cache = {"k": ks, "v": vs, "pos": pos + 1}
        return _head(hp, x[:, -1]), cache

    return init_cache, prefill, decode_step


# ---------------------------------------------------------------------------
# Paged-KV serving decode path (ragged paged attention + page-pool cache)
# ---------------------------------------------------------------------------
def llama_paged_param_specs(mp_axis: str = "mp"):
    """Per-leaf PartitionSpec for the paged-decode ``(ep, bp, hp)`` params
    tree under tensor parallelism over ``mp_axis``: column-parallel wq/wk/wv
    and wgate/wup (output dim sharded = heads / FFN columns), ROW-parallel
    wdown (input dim sharded — its matmul produces the partial sums the
    layer's ONE AllReduce combines), and wo REPLICATED: it multiplies the
    all_gathered head outputs, so its matmul is bit-identical to the
    single-chip engine's (the gather is exact; see _gather_heads).  The
    leading dim of every bp leaf is the stacked layer axis (unsharded).
    Returned as a pytree matching (ep, bp, hp) for shard_map in_specs and
    NamedSharding placement alike."""
    from jax.sharding import PartitionSpec as P
    col = P(None, None, mp_axis)
    bp = {"ln1": P(), "wq": col, "wk": col, "wv": col, "wo": P(),
          "ln2": P(), "wgate": col, "wup": col,
          "wdown": P(None, mp_axis, None)}
    return ({"tok": P()}, bp, {"ln_f": P(), "lm": P()})


def llama_paged_page_spec(mp_axis: str = "mp"):
    """PartitionSpec for one side of the paged-KV store: shard the KV-head
    axis (dim 1 of the ``[L, Hkv, NP+1, ps, D]`` data pages and of the
    ``[L, Hkv, NP+1, ps]`` scale pages) over ``mp_axis``.  A single spec
    works as a pytree prefix for both the raw-array and the quantized
    ``{"q","s"}`` page stores — every leaf shards the same axis."""
    from jax.sharding import PartitionSpec as P
    return P(None, mp_axis)


def gather_kv_pages(store, idx):
    """Gather pages ``idx`` from one side of the paged-KV store (raw array
    or quantized ``{"q","s"}`` dict alike).  The page axis is AXIS 2 of the
    ``[L, Hkv, NP+1, ps, D]`` data planes and ``[L, Hkv, NP+1, ps]`` scale
    planes — this function is the one place that contract lives for
    transfers (snapshot, restore, and the disaggregated prefill->decode
    handoff all ride it).  The KV-head axis (dim 1) is what
    ``llama_paged_page_spec`` shards over ``mp``, and a page gather never
    touches it: at equal ``mp`` degree the gathered planes land rank-local
    on the destination submesh with no re-sharding.  Returns planes in
    ``idx`` order."""
    if isinstance(store, dict):
        return {k: v[:, :, idx] for k, v in store.items()}
    return store[:, :, idx]


def scatter_kv_pages(store, ids, planes):
    """Splice ``planes`` (a :func:`gather_kv_pages` result, same page
    order) into the store at page ids ``ids`` — the inverse transfer used
    by full-KV restore and by ``import_kv`` on a foreign engine.  A
    quantized store splices data AND scale planes together: int8/fp8 codes
    without their per-row scales are garbage magnitudes."""
    if isinstance(store, dict):
        return {k: store[k].at[:, :, ids].set(
                    jnp.asarray(planes[k], store[k].dtype))
                for k in store}
    return store.at[:, :, ids].set(jnp.asarray(planes, store.dtype))


def scatter_kv_rows(store, layer, rows, page, off):
    """Write per-token rows into one layer of a page store, in place:
    ``store [L, Hkv, NP+1, ps, D]`` data pages with ``rows [*tok, Hkv, D]``
    (or ``[L, Hkv, NP+1, ps]`` scale pages with ``rows [*tok, Hkv]``), token
    t landing at ``[layer, :, page[t], off[t]]``; ``layer`` may be traced.

    The scatter's INDICES run over (layer, head, page, offset) and its
    update window is D only (a scalar for scales).  A window over (Hkv, D)
    — ``store[layer].at[:, page, off].set(...)`` — makes the TPU's layout
    assignment put Hkv next to D in the pool, while the Mosaic attention
    kernel reads it row-major: a relayout of a layer per layer and copies
    of the whole pool around the layer loop (PERF.md section 6, PR 28)."""
    heads = jnp.arange(store.shape[1])
    return store.at[layer, heads, page[..., None], off[..., None]].set(
        rows.astype(store.dtype))


def scatter_kv_run(store, layer, rows, start, length, page_row):
    """Write a CONTIGUOUS run of token rows into one layer of a page store,
    in place, a page an update: ``rows [C, Hkv, D]`` (``[C, Hkv]`` for scale
    pages) are positions ``start .. start+C-1`` of the sequence whose page
    table is ``page_row [P]``, the first ``length`` of them real; ``layer``,
    ``start`` and ``length`` may be traced.

    What a prefill writes is whole pages but for the run's two ends, and a
    page ``[ps, D]`` is whole tiles of the pool where a row is a sixteenth
    of one.  So the scatter's INDICES run over (layer, head, page) and its
    update window is ``(ps, D)`` — the pool's two minor dimensions, the
    row-major layout the attention kernel reads — about ``(C / ps + 1) x
    Hkv`` updates where :func:`scatter_kv_rows` issues ``C x Hkv``.  The
    touched pages are gathered first and the rows outside the run keep what
    they held (``start`` may lie inside a page after a prefix-cache hit, the
    last real page is part full), so every real page comes out bit-equal to
    the row form's.  A page that holds only padding is the TRASH page (the
    store's last, :func:`build_llama_paged_decode`) written back unchanged;
    the row form throws the padded rows into it."""
    n_rows, hkv = rows.shape[:2]
    tail = rows.shape[2:]
    ps, trash = store.shape[3], store.shape[2] - 1
    n_pg = -(-n_rows // ps) + 1              # the run may start inside a page
    first, lead = start // ps, start % ps
    # the run laid over whole pages: slot s holds row s - lead
    row_of = jnp.arange(n_pg * ps) - lead
    real = ((row_of >= 0) & (row_of < length)).reshape(n_pg, ps)
    view = jax.lax.dynamic_update_slice_in_dim(
        jnp.zeros((n_pg * ps, hkv) + tail, store.dtype),
        rows.astype(store.dtype), lead, axis=0)
    view = jnp.moveaxis(view.reshape((n_pg, ps, hkv) + tail), 2, 0)
    ids = jnp.where(
        real.any(axis=1),
        page_row[jnp.minimum(first + jnp.arange(n_pg), page_row.shape[0] - 1)],
        trash)[None]
    heads = jnp.arange(hkv)[:, None]
    keep = store[layer, heads, ids]                   # [Hkv, n_pg, ps, (D)]
    mask = real.reshape((1, n_pg, ps) + (1,) * len(tail))
    return store.at[layer, heads, ids].set(jnp.where(mask, view, keep))


def build_llama_paged_decode(config: LlamaConfig, page_size: int = 16,
                             num_pages: int = 64, dtype=None,
                             attention_impl: str = "auto",
                             interpret: bool = False, kv_dtype=None,
                             mesh=None, mp_axis: str = "mp",
                             quantized_allreduce: bool = False,
                             num_slots=None, max_pages_per_seq=None):
    """Paged-KV decode path (the `block_multihead_attention` serving analog;
    Ragged Paged Attention arxiv 2604.15464): the KV cache lives in a pool of
    fixed-size pages shared by every in-flight request, so mixed-length
    sequences occupy memory (and attention FLOPs) proportional to their OWN
    length instead of the longest sequence in the batch.

    Returns the family's `models/paged_family.PagedFamily` — the ONE form
    every serving family's fns have, which `ServingEngine` builds through
    ``LlamaConfig.paged_family`` — with these fields (``num_slots``,
    ``max_pages_per_seq`` and the fns' ``slot`` are of no interest to a
    cache of K/V pages alone: unused):

      cache = init_cache()
          {"k","v": [L, Hkv, num_pages + 1, page_size, head_dim]} — the last
          page is the TRASH page inactive slots write into; the page pool
          (inference/paged.py PagePool) hands out ids < num_pages.

      logits, cache = prefill(params, ids, true_len, page_row, slot, cache)
          ids [1, T_pad] right-padded prompt, true_len the real length,
          page_row [P] this request's page table.  Dense causal attention
          over the prompt; post-RoPE K/V scatter into the request's pages;
          logits [vocab] for the LAST real token.

      logits, greedy_tok, cache = prefill_chunk(
              params, ids, start, chunk_len, page_row, slot, cache)
          CHUNKED / SUFFIX prefill for the prefix cache + chunked-prefill
          scheduler: ids [1, C_pad] right-padded chunk of the prompt, start
          the number of tokens ALREADY in this request's pages (a cached
          prefix and/or earlier chunks), chunk_len the real chunk length.
          The chunk's K/V scatter into the pages at absolute positions
          start..start+chunk_len-1 (RoPE at those positions), then the
          chunk attends as ONE ragged query segment of the unified kernel
          (causal across cache + chunk).  Returns logits [vocab] for the
          LAST real chunk token plus its fused greedy argmax token (int32
          scalar) — a greedy request's final chunk consumes the token
          directly (no separate sample dispatch); only sampled lanes read
          the logits.  `prefill_chunk(.., start=0, chunk_len=T)` is
          semantically identical to `prefill` (the engine keeps the dense
          path for the no-cache-hit whole-prompt case purely so its
          numerics stay byte-identical with the pre-cache engine).

      logits, cache = decode_step(params, toks, lengths, page_tables,
                                  cache, active)
          One token per slot: toks [S], lengths [S] (tokens already cached —
          the new token lands at position lengths[s]), page_tables [S, P],
          active [S] bool.  Inactive slots write to the trash page and
          produce garbage logits the engine discards.

      Decode, verify, AND chunked prefill all dispatch the ONE ragged
      paged-attention kernel (attention_impl "pallas"/"auto"-on-TPU) or
      its ONE jnp ref ("ref"/"auto"-off-TPU) — decode is the q_len = 1
      segment, verify q_len = K+1, a chunk q_len = chunk_len.  There is
      no per-path attention implementation anywhere in the paged family.

      logits0, greedy, cache = verify_step(params, toks, lengths,
                                           page_tables, cache, n_q)
          Speculative-decoding verify: toks [S, K+1] (pending token +
          draft tokens per slot), n_q [S] valid query counts — scores all
          K+1 positions in one dispatch so the engine can accept the
          longest draft prefix whose argmax matches (lossless under
          greedy sampling).  See the fn docstring for the rewind
          contract.

    All shapes static; jit once and every decode step of a whole serving
    run reuses the same executable regardless of which requests occupy
    which slots.

    The page pool stays IN PLACE through all four fns (jit them with
    the cache donated): ONE layer loop (`_layers`) carries both sides
    whole, the fresh rows go into layer ``li`` a ROW an update with a D-only
    window (`scatter_kv_rows`: decode and verify, per-slot positions) or a
    PAGE an update with a (ps, D) window (`scatter_kv_run`: both prefills,
    one contiguous run; PR 32), and the ragged kernel takes the whole pool
    plus ``li`` and picks the layer in its page DMA.  Scanning over the
    pool (xs/ys) or scattering a (Hkv, D) window made every executable
    slice, relay out and stack back a layer per layer and copy the whole
    pool around the loop — 41 % of a decode step on the v5e (PERF.md
    section 6, PR 28; tests/test_chip_compile.py holds the compiled
    programs to it).

    ``kv_dtype`` ("int8" / "fp8", ROADMAP item 2): the page store holds
    QUANTIZED K/V — each side becomes a ``{"q": [L, Hkv, NP+1, ps, D]
    storage-dtype, "s": [L, Hkv, NP+1, ps] f32}`` dict of data pages plus
    per-(page, head, token-row) absmax scales.  Every scatter path
    (prefill / prefill_chunk / decode_step / verify_step) quantizes
    through ``serving.quant.quantize_kv`` before writing, and every
    attention path dequantizes through the ONE ``dequantize_kv``
    expression — fused inside the unified ragged kernel on TPU (decode,
    verify, and chunked prefill alike), applied to the gathered rows in
    its jnp ref off-TPU.  Per-row scales make quantization
    write-order independent, so the engine's whole bit-exactness matrix
    (cache on/off, chunked, preemption re-prefill, COW, snapshot, spec
    decode) holds for the quantized engine against itself.  The dense
    ``prefill`` additionally fake-quants its LOCAL K/V before attending
    (quantize -> dequantize round trip), so its numerics equal a chunked
    prefill of the same prompt reading the rows back from the pages.

    ``mesh`` (ROADMAP item 1, TP serving): when a Mesh binding ``mp_axis``
    with size > 1 is given, the four jitted fns come back wrapped in
    ``shard_map`` over that axis — Q/KV heads and KV pages sharded over
    ``mp`` (specs: llama_paged_param_specs / llama_paged_page_spec), every
    scalar/logits input and output replicated.  Per layer the sharded body
    pays exactly ONE AllReduce (the row-parallel wdown partial reduction;
    f32 psum by default, the EQuARX int8 grid with
    ``quantized_allreduce=True`` — distributed/quant_collectives) plus one
    exact all_gather of the per-rank attention head outputs, after which wo
    applies replicated — so with f32 collectives every matmul is
    bit-identical to the single-chip engine and the only divergence source
    is the psum's fixed summation order.  Requires mp | num_key_value_heads
    (hence mp | num_attention_heads); MoE blocks are not supported under
    TP serving.
    """
    from ..ops.pallas.paged_attention import (ragged_paged_attention,
                                              ragged_paged_attention_ref)
    c = config
    d = jnp.dtype(dtype) if dtype is not None else jnp.float32
    head_dim = c.hidden_size // c.num_attention_heads
    L = c.num_hidden_layers
    nkv = c.num_key_value_heads
    nh = c.num_attention_heads
    TRASH = num_pages
    tp = 1 if mesh is None else int(mesh.shape[mp_axis])
    if tp > 1:
        if c.num_experts > 1:
            raise NotImplementedError(
                "tensor-parallel paged decode does not support MoE blocks")
        if nkv % tp or nh % tp:
            raise ValueError(
                f"mp={tp} must divide num_key_value_heads={nkv} (and "
                f"num_attention_heads={nh}) to head-shard paged decode")
        from ..distributed.quant_collectives import allreduce as _allreduce

    def _gather_heads(o):  # graftlint: spmd=mp
        """Head-sharded attention epilogue: each rank pushed its LOCAL
        heads through the one ragged dispatch; the tiled all_gather over
        the head axis (second-to-last) restores the full [..., nh, D] in
        global head order — NamedSharding hands rank r the contiguous head
        block r*nh_l..(r+1)*nh_l-1, which is exactly the r-th tile of the
        gather.  The gather moves bits unchanged, so the replicated wo
        matmul that follows is bit-identical to single-chip.  NOT an
        AllReduce: the layer's one psum stays the wdown reduction."""
        if tp == 1:
            return o
        return jax.lax.all_gather(o, mp_axis, axis=o.ndim - 2, tiled=True)

    def _mp_reduce(y):  # graftlint: spmd=mp
        """THE one AllReduce per transformer layer: sum the row-parallel
        wdown partials over mp — plain f32 psum by default (the bit-exact
        escape hatch), the EQuARX int8 per-chunk grid when the engine asks
        for quantized collectives."""
        if tp == 1:
            return y
        return _allreduce(y, mp_axis, quantized=quantized_allreduce)
    if kv_dtype is not None:
        from ..serving.quant import dequantize_kv, kv_spec, quantize_kv
        kv_storage, kv_qmax = kv_spec(kv_dtype)
    sin_t, cos_t = _rope_tables(c.max_position_embeddings, head_dim,
                                c.rope_theta, d)
    if attention_impl == "auto":
        use_kernel = any(dev.platform == "tpu" for dev in jax.devices())
    else:
        use_kernel = attention_impl == "pallas"

    from ..nn.functional.norm import rms_norm_ref

    def init_pages():
        shape = (L, nkv, num_pages + 1, page_size, head_dim)
        if kv_dtype is None:
            return {"k": jnp.zeros(shape, d), "v": jnp.zeros(shape, d)}
        sshape = (L, nkv, num_pages + 1, page_size)

        def side():
            return {"q": jnp.zeros(shape, kv_storage),
                    "s": jnp.zeros(sshape, jnp.float32)}
        return {"k": side(), "v": side()}

    def _scatter(store, li, vals, write):
        """Write per-token K or V rows ``vals [*tok, nkv, D]`` into layer
        ``li`` of the WHOLE page store, in place, through the caller's
        ``write(plane, li, rows) -> plane`` (``_rows_at`` or ``_run_at``
        below); returns the updated store plus the LOCAL view of what was
        written — ``vals`` itself on the f32/bf16 path, the dequantized
        round trip on a quantized store (so a caller attending over its own
        fresh rows sees exactly what any later gather of the pages will
        see)."""
        if kv_dtype is None:
            return write(store, li, vals), vals
        qv, sv = quantize_kv(vals, qmax=kv_qmax, dtype=kv_storage)
        new = {"q": write(store["q"], li, qv), "s": write(store["s"], li, sv)}
        # .astype(d): the jnp paths consume dequantized rows in the
        # COMPUTE dtype, exactly like the f32/bf16 store — activations
        # keep their dtype (no silent f32 promotion) and decode/chunk/
        # verify/dense all see the same rounded values on a bf16 engine
        return new, dequantize_kv(qv, sv).astype(d)

    def _rows_at(page, off):
        """The writer of decode and verify: token t's row lands at
        ``page[t], off[t]``, per-slot positions with no run to exploit."""
        return lambda plane, li, rows: scatter_kv_rows(plane, li, rows, page,
                                                       off)

    def _run_at(start, length, page_row):
        """The writer of both prefills: the tokens are ONE contiguous run of
        positions from ``start``, the first ``length`` real, so they reach
        the pool a page an update (:func:`scatter_kv_run`)."""
        return lambda plane, li, rows: scatter_kv_run(plane, li, rows, start,
                                                      length, page_row)

    def _attn(q, pk, pv, li, page_tables, q_start, q_len, kv_len, role):
        """THE attention dispatch: every paged path (decode, speculative
        verify, chunked prefill) routes its ragged query segments
        ``q [S, Qmax, nh, D]`` through the ONE ragged paged-attention
        kernel (or, off-TPU, its ONE jnp ref) — impl-uniformity is what
        makes speculative verify lossless by construction rather than by
        an assertion.  ``pk/pv`` are the WHOLE page stores and ``li`` the
        layer: the kernel indexes the layer itself.  On a quantized store
        the int8/fp8 pages and their per-row scales pass straight through;
        dequant fuses inside the kernel (and inside the ref's gather) for
        every path.  ``role`` ("decode" | "chunk" | "verify") is the
        kernel's trace label."""
        if kv_dtype is not None:
            kq, vq = pk["q"], pv["q"]
            scale_kw = dict(k_scales=pk["s"], v_scales=pv["s"])
        else:
            kq, vq = pk, pv
            scale_kw = {}
        if use_kernel:
            return ragged_paged_attention(q, kq, vq, page_tables, q_start,
                                          q_len, kv_len, interpret=interpret,
                                          role=role, layer=li, **scale_kw)
        return ragged_paged_attention_ref(q, kq, vq, page_tables, q_start,
                                          q_len, kv_len, layer=li, **scale_kw)

    def _rope_at(x, sin_p, cos_p):
        # x: [..., H, D]; sin_p/cos_p: [..., D] (per-row positions — the
        # leading dims are [S] for decode, [C] for chunks, [S, Q] for the
        # multi-token verify step)
        half = x.shape[-1] // 2
        x1, x2 = x[..., :half], x[..., half:]
        rot = jnp.concatenate([-x2, x1], axis=-1)
        return x * cos_p[..., None, :] + rot * sin_p[..., None, :]

    @device_span("head")
    def _head(hp, h_last):
        h = rms_norm_ref(h_last, hp["ln_f"], c.rms_norm_eps)
        return (h @ hp["lm"]).astype(jnp.float32)

    def _layers(bp, x, cache, sin, cos, write, attend):
        """THE layer loop of all four paged fns.  ``x [*tok, H]`` are the
        tokens' activations (``tok`` = [T] dense, [C] chunk, [S] decode,
        [S, Q] verify), ``sin/cos [*tok, D]`` their rotary rows, ``write``
        how their K/V rows reach the pool — chosen by what the caller knows
        of its tokens: one contiguous run (``_run_at``: both prefills) or
        per-slot positions (``_rows_at``: decode, verify).  Per layer: norm,
        q/k/v, RoPE, the K/V rows written into the pool, ``attend(q, k_loc,
        v_loc, pk, pv, li) -> o [*tok, nh_l, D]`` (the other thing the fns
        differ in), wo, MLP.  The page pool is the loop's CARRY, never its
        xs/ys: scanning over it slices a layer out and stacks it back, a
        layer of pool read and written twice per layer to change a few rows
        of it.
        Carried, indexed by ``li`` in the scatter and inside the kernel, it
        is updated in place.  Returns (x, cache)."""
        tok = x.shape[:-1]

        def body(carry, layer_in):
            xc, pk, pv = carry
            lp, li = layer_in
            # head counts from the LOCAL weight shards: under shard_map
            # each rank holds nh/tp q heads and nkv/tp kv heads
            nh_l = lp["wq"].shape[-1] // head_dim
            nkv_l = lp["wk"].shape[-1] // head_dim
            with device_span("attn.proj"):
                h = rms_norm_ref(xc, lp["ln1"], c.rms_norm_eps)
                q = (h @ lp["wq"]).reshape(*tok, nh_l, head_dim)
                k = (h @ lp["wk"]).reshape(*tok, nkv_l, head_dim)
                v = (h @ lp["wv"]).reshape(*tok, nkv_l, head_dim)
                q = _rope_at(q, sin, cos)
                k = _rope_at(k, sin, cos)
                pk, k_loc = _scatter(pk, li, k, write)
                pv, v_loc = _scatter(pv, li, v, write)
                o = _gather_heads(attend(q, k_loc, v_loc, pk, pv, li))
                xc = xc + o.reshape(*tok, nh * head_dim) @ lp["wo"]
            with device_span("block.mlp"):
                h = rms_norm_ref(xc, lp["ln2"], c.rms_norm_eps)
                ff = jax.nn.silu(h @ lp["wgate"]) * (h @ lp["wup"])
                return (xc + _mp_reduce(ff @ lp["wdown"]), pk, pv), None

        # the loop's own work — a layer's weights sliced out of the stacks —
        # is `block.scan`; what the body does takes the body's names
        with device_span("block.scan"):
            (x, pages_k, pages_v), _ = jax.lax.scan(
                body, (x, cache["k"], cache["v"]), (bp, jnp.arange(L)))
        return x, {"k": pages_k, "v": pages_v}

    def prefill(params, ids, true_len, page_row, slot, cache):  # graftlint: jit
        ep, bp, hp = params
        T = ids.shape[1]
        x = ep["tok"][ids[0]].astype(d)               # [T, H]
        t_idx = jnp.arange(T)
        mask = (t_idx[None, :] <= t_idx[:, None]) \
            & (t_idx < true_len)[None, :]

        def attend(q, k_loc, v_loc, pk, pv, li):
            # dense causal attention over the prompt's own fresh rows
            rep = q.shape[1] // k_loc.shape[1]
            kf = jnp.repeat(k_loc, rep, axis=1) if rep > 1 else k_loc
            vf = jnp.repeat(v_loc, rep, axis=1) if rep > 1 else v_loc
            s = jnp.einsum("qhd,khd->hqk", q.astype(jnp.float32),
                           kf.astype(jnp.float32)) / math.sqrt(head_dim)
            s = jnp.where(mask[None, :, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1).astype(d)
            return jnp.einsum("hqk,khd->qhd", p, vf)

        x, cache = _layers(bp, x, cache, sin_t[:T], cos_t[:T],
                           _run_at(0, true_len, page_row), attend)
        h_last = jax.lax.dynamic_index_in_dim(x, true_len - 1, 0,
                                              keepdims=False)
        return _head(hp, h_last), cache

    def prefill_chunk(params, ids, start, chunk_len, page_row, slot,
                      cache):                         # graftlint: jit
        ep, bp, hp = params
        C = ids.shape[1]
        x = ep["tok"][ids[0]].astype(d)               # [C, H]
        pos = start + jnp.arange(C)                   # absolute positions
        sin, cos = jnp.take(sin_t, pos, axis=0), jnp.take(cos_t, pos, axis=0)
        # the whole chunk is ONE ragged query segment of the unified
        # kernel: queries at absolute positions start..start+chunk_len-1
        # attend every page-table position <= their own (causal across the
        # cached prefix + earlier chunk tokens).  Positions past the
        # written region (or recycled-page garbage) can never be <= a
        # query position, so the segment mask alone keeps them out.
        start_r = jnp.reshape(start, (1,)).astype(jnp.int32)
        clen_r = jnp.reshape(chunk_len, (1,)).astype(jnp.int32)
        kvlen_r = start_r + clen_r
        page_tab = page_row[None]                     # [1, P]

        def attend(q, k_loc, v_loc, pk, pv, li):
            return _attn(q[None], pk, pv, li, page_tab,
                         start_r, clen_r, kvlen_r, "chunk")[0]

        x, cache = _layers(bp, x, cache, sin, cos,
                           _run_at(start, chunk_len, page_row), attend)
        h_last = jax.lax.dynamic_index_in_dim(x, chunk_len - 1, 0,
                                              keepdims=False)
        logits = _head(hp, h_last)
        # fused greedy sampling: the chunk dispatch also emits the argmax
        # token, so a greedy request's FINAL chunk needs no separate
        # sample executable — the engine consumes this token directly and
        # the logits feed only sampled-temperature lanes
        with device_span("head"):
            tok = jnp.argmax(logits).astype(jnp.int32)
        return logits, tok, cache

    def decode_step(params, toks, lengths, page_tables, cache,
                    active):                          # graftlint: jit
        ep, bp, hp = params
        x = ep["tok"][toks].astype(d)                 # [S, H]
        pos = jnp.where(active, lengths, 0)
        page = jnp.where(active, jnp.take_along_axis(
            page_tables, (pos // page_size)[:, None], 1)[:, 0], TRASH)
        off = pos % page_size
        eff_len = jnp.where(active, lengths + 1, 0)
        n_q = active.astype(jnp.int32)                # q_len: 1 live, 0 idle

        def attend(q, k_loc, v_loc, pk, pv, li):
            # decode is the q_len = 1 segment of the unified ragged kernel
            return _attn(q[:, None], pk, pv, li, page_tables,
                         pos, n_q, eff_len, "decode")[:, 0]

        x, cache = _layers(bp, x, cache, sin_t[pos], cos_t[pos],
                           _rows_at(page, off), attend)
        return _head(hp, x), cache

    def verify_step(params, toks, lengths, page_tables, cache,
                    n_q):                             # graftlint: jit
        """Multi-token speculative VERIFY (self-speculative decoding):
        score Q = K+1 query positions per slot in ONE dispatch.  Per slot,
        toks[s, 0] is the pending token (the last sampled token, not yet
        in the cache) and toks[s, 1:] its draft tokens; n_q[s] counts the
        VALID queries (1 + drafts; 0 marks an inactive slot — padding
        lanes write to the trash page and return garbage the engine
        ignores).  Every valid query's K/V scatters into the slot's pages
        at absolute positions lengths[s]..lengths[s]+n_q[s]-1 (RoPE at
        those positions), then each slot attends as one ragged segment of
        the UNIFIED paged-attention kernel — the very callable decode and
        chunked prefill dispatch, so verify-vs-decode losslessness is
        impl-uniform by construction.  Returns (logits0 [S, vocab] f32 —
        position-0 logits for sampled slots; greedy [S, Q] int32 — argmax
        per position, the engine's acceptance test; the cache).

        Rewind contract: K/V written for drafts the engine then REJECTS
        sits at positions >= the rewound `lengths` — every attention path
        masks by `lengths`, so stale entries are overwritten by later
        writes before any query can ever attend to them."""
        ep, bp, hp = params
        Q = toks.shape[1]
        x = ep["tok"][toks].astype(d)                 # [S, Q, H]
        q_idx = jnp.arange(Q)
        valid = q_idx[None, :] < n_q[:, None]         # [S, Q]
        pos = lengths[:, None] + q_idx[None, :]       # [S, Q] absolute
        # out-of-range indices on the padding lanes clip (jax gather
        # semantics) and are routed to TRASH by the `valid` mask anyway
        page = jnp.where(valid, jnp.take_along_axis(
            page_tables, pos // page_size, axis=1), TRASH)
        off = pos % page_size
        # each slot is one ragged segment of the unified kernel: n_q
        # queries starting at absolute position lengths[s], causal among
        # themselves and over the cached context — the SAME kernel (and
        # off-TPU the same ref) decode dispatches with q_len = 1
        kv_len = lengths + n_q

        def attend(q, k_loc, v_loc, pk, pv, li):
            return _attn(q, pk, pv, li, page_tables, lengths, n_q, kv_len,
                         "verify")

        x, cache = _layers(bp, x, cache, sin_t[pos], cos_t[pos],
                           _rows_at(page, off), attend)
        logits = _head(hp, x)                         # [S, Q, V] f32
        greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return logits[:, 0], greedy, cache

    if tp > 1:
        # TP serving region: the four paged fns run under shard_map over
        # mp — params/pages per the spec helpers, every scalar + logits
        # input/output replicated.  All replicated outputs are computed
        # identically on every rank (the last op touching the residual is
        # the psum), so check_vma=False only skips re-proving what the
        # per-layer collective structure already guarantees.
        from jax.sharding import PartitionSpec
        p_specs = llama_paged_param_specs(mp_axis)
        pg = llama_paged_page_spec(mp_axis)
        pg = {"k": pg, "v": pg}
        r = PartitionSpec()

        def _smap(fn, in_specs, out_specs):
            return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                                 out_specs=out_specs, check_vma=False)

        prefill = _smap(prefill, (p_specs, r, r, r, r, pg), (r, pg))
        prefill_chunk = _smap(prefill_chunk, (p_specs, r, r, r, r, r, pg),
                              (r, r, pg))
        decode_step = _smap(decode_step, (p_specs, r, r, r, pg, r), (r, pg))
        verify_step = _smap(verify_step, (p_specs, r, r, r, pg, r),
                            (r, r, pg))

    from .paged_family import PagedFamily
    return PagedFamily(
        name="llama", init_cache=init_pages, prefill=prefill,
        prefill_chunk=prefill_chunk, decode_step=decode_step,
        page_leaves=("k", "v"), int8_weights=True, verify_step=verify_step,
        mesh_specs=lambda axis: (llama_paged_param_specs(axis),
                                 llama_paged_page_spec(axis)))


def _sample_per_request(logits, key, temps, top_ps):
    """Per-request sampling for the serving engine: logits [S, V], temps /
    top_ps [S] -> token ids [S] int32.  temp <= 0 rows decode greedily; the
    rest draw from the per-row nucleus (`tensor/search._top_p_mask` — the
    same mask `top_p_sampling` applies)."""
    from ..tensor.search import _top_p_mask
    greedy = jnp.argmax(logits, axis=-1).astype(jnp.int32)
    scaled = logits / jnp.maximum(temps, 1e-6)[:, None]
    masked = _top_p_mask(scaled, top_ps)
    sampled = jax.random.categorical(key, masked, axis=-1).astype(jnp.int32)
    return jnp.where(temps <= 0.0, greedy, sampled)


def pack_decode_state(toks, lengths, remaining, eos_ids, active, carried,
                      page_tables):
    """The decode horizon's per-call host state as ONE fresh int32 buffer
    ``toks | lengths | remaining | eos_ids | active | carried |
    page_tables.ravel()`` (``[S]`` each, the table ``[S, P]``): one upload
    a dispatch, where a field that is an argument of its own is an upload
    of its own.  ``active`` is 0 / 1; ``carried`` says where a lane's entry state
    comes from: 0 the host's values in this buffer, 1 the previous
    dispatch's device outputs, 2 an admission's first token still on the
    device (`make_paged_decode_horizon`'s ``carry``).  The horizon takes the
    buffer apart again with static slices (`_unpack_decode_state`); being a
    copy, it never aliases a host mirror that changes while the call is in
    flight."""
    return np.concatenate(
        [np.asarray(a, np.int32).ravel()
         for a in (toks, lengths, remaining, eos_ids, active, carried,
                   page_tables)])


def _unpack_decode_state(ints, S):
    """`pack_decode_state`'s fields out of the traced buffer."""
    toks, lengths, remaining, eos_ids, active, carried = (
        ints[i * S:(i + 1) * S] for i in range(6))
    return (toks, lengths, remaining, eos_ids, active != 0, carried,
            ints[6 * S:].reshape(S, -1))


def split_call_key(key):
    """One engine-level split a model call, INSIDE its executable:
    ``(next key, this call's subkey)`` — rows 0 and 1 of
    ``jax.random.split(key)``, what ``key, sub = jax.random.split(key)``
    binds on the host, so the stream of subkeys is the sequential split's
    whether the split is an executable of its own or a few operations of
    the call it feeds."""
    pair = jax.random.split(key)
    return pair[0], pair[1]


def make_paged_decode_horizon(decode_step, sample_fn=None):
    """Build the K-step decode-horizon loop with ON-DEVICE token feedback
    (the serving engine's one decode executable; ROADMAP item 5) over a
    family's ``decode_step(params, toks, lengths, page_tables, cache, live)
    -> (logits, cache)`` (`models/paged_family.py`): the cache is ONE
    pytree, carried whole through the loop.

    K decode+sample steps fuse into one ``fori_loop`` dispatch.  What the
    host knows of the call arrives as ONE packed int32 buffer
    (`pack_decode_state`) and one float32 row ``temps | top_ps`` ``[2S]``
    (passed again as the same device array until an admission changes
    it); the entry ``done`` flags are made here, and the engine's PRNG key
    is split here (`split_call_key`) and the next key returned, so nothing
    but this executable is launched for a dispatch.

    The loop state that used to round-trip through the host between
    dispatches — the last sampled token per slot, the cache lengths, the
    remaining generation budget, and the per-slot done flags — is both
    ACCEPTED and RETURNED as device values.  A double-buffered engine
    passes ``carry = (toks, lengths, remaining, done, firsts)``: the
    previous dispatch's four outputs and ``firsts``, ``S`` int32 scalars
    holding admissions' first tokens that never left the device; a lane
    whose ``carried`` code is 1 enters with the previous dispatch's state
    (and its ``done``), a lane with code 2 with ``firsts[s]`` as its token,
    every other lane with the packed host values — the merge is part of
    this program too.  A synchronous engine passes ``carry=None`` (no
    previous dispatch is ever in flight) and gets a program without the
    merge; the math (and therefore greedy output) is bit-identical either
    way.

    Per-slot freeze semantics inside the loop (mirrors
    ``llama_generate_fused``'s masking, so greedy outputs are step-exact
    at any K): a slot freezes once it emits ``eos_ids[s]`` (where >= 0)
    or its ``remaining`` budget hits zero; frozen slots echo ``eos_ids``
    into ``out``, stop advancing ``lengths``/``remaining``, and carry
    their state through unchanged — including slots frozen at ENTRY via
    the carried ``done`` (a lane whose EOS the overlapped host has not yet
    drained) and inactive slots (``active`` 0), whose returned ``done`` is
    the entry value passed through so a momentarily stalled lane is never
    permanently frozen by one inactive dispatch.  A frozen slot is NOT
    live for ``decode_step``: a family's recurrent state stays as it was.

    ``sample_fn`` defaults to :func:`_sample_per_request` (only consulted
    when ``greedy=False``).

    Returns ``horizon(params, cache, key, ints, sampling, carry=None, *,
    K, greedy) -> (out [S, K], toks, lengths, remaining, done, next_key,
    cache)`` — the cache stays the LAST output and the key the one before
    it (the engine's ``_call_paged`` rebind convention)."""
    if sample_fn is None:
        sample_fn = _sample_per_request

    def horizon(params, cache, key, ints, sampling, carry=None, *, K,
                greedy):  # graftlint: jit
        S = sampling.shape[0] // 2
        temps, top_ps = sampling[:S], sampling[S:]
        (toks, lengths, remaining, eos_ids, active, carried,
         page_tables) = _unpack_decode_state(ints, S)
        if carry is None:
            done0 = jnp.zeros((S,), jnp.bool_)
        else:
            prev_toks, prev_lengths, prev_rem, prev_done, firsts = carry
            cm = carried == 1
            toks = jnp.where(cm, prev_toks, toks)
            lengths = jnp.where(cm, prev_lengths, lengths)
            remaining = jnp.where(cm, prev_rem, remaining)
            done0 = cm & prev_done
            toks = jnp.where(carried == 2, jnp.stack(firsts), toks)
        next_key, key = split_call_key(key)
        out = jnp.zeros((S, K), jnp.int32)

        def body(t, state):
            toks, lengths, rem, cache, done, key, out = state
            live = ~done
            logits, cache = decode_step(params, toks, lengths, page_tables,
                                        cache, live)
            with device_span("head"):
                if greedy:
                    # static fast path when every running request decodes
                    # greedily (the common serving default): skips the
                    # sort/cumsum of the nucleus mask — the same shortcut
                    # _sample_token takes for temperature == 0.0
                    tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
                else:
                    key, sub = jax.random.split(key)
                    tok = sample_fn(logits, sub, temps, top_ps)
            tok = jnp.where(done, eos_ids, tok)
            out = out.at[:, t].set(tok)
            lengths = lengths + live.astype(lengths.dtype)
            rem = rem - live.astype(rem.dtype)
            done = done | ((eos_ids >= 0) & (tok == eos_ids)) | (rem <= 0)
            return (tok, lengths, rem, cache, done, key, out)

        state = (toks, lengths, remaining, cache, ~active | done0, key, out)
        toks, lengths, rem, cache, done, key, out = jax.lax.fori_loop(
            0, K, body, state)
        # inactive lanes pass done0 through untouched: ~active folded into
        # the in-loop freeze must not leak into the carried done state
        done = jnp.where(active, done, done0)
        return out, toks, lengths, rem, done, next_key, cache

    return horizon


def functional_params_from_layer(model: "LlamaForCausalLM"):
    """Stack an eager LlamaForCausalLM's per-layer weights into the
    (embed, block, head) pytrees the functional/decode paths consume.
    Requires tensor_parallel_degree == 1 (full weights on this host) and
    the dense (non-MoE) variant."""
    if getattr(model.config, "num_experts", 1) > 1:
        raise NotImplementedError(
            "functional_params_from_layer: MoE experts do not map onto the "
            "dense wgate/wup/wdown leaves")
    m = model.model
    def val(p):
        return p._value
    bp = {
        "ln1": jnp.stack([val(l.input_layernorm.weight) for l in m.layers]),
        "wq": jnp.stack([val(l.self_attn.q_proj.weight) for l in m.layers]),
        "wk": jnp.stack([val(l.self_attn.k_proj.weight) for l in m.layers]),
        "wv": jnp.stack([val(l.self_attn.v_proj.weight) for l in m.layers]),
        "wo": jnp.stack([val(l.self_attn.o_proj.weight) for l in m.layers]),
        "ln2": jnp.stack([val(l.post_attention_layernorm.weight) for l in m.layers]),
        "wgate": jnp.stack([val(l.mlp.gate_proj.weight) for l in m.layers]),
        "wup": jnp.stack([val(l.mlp.up_proj.weight) for l in m.layers]),
        "wdown": jnp.stack([val(l.mlp.down_proj.weight) for l in m.layers]),
    }
    ep = {"tok": val(m.embed_tokens.weight)}
    hp = {"ln_f": val(m.norm.weight), "lm": val(model.lm_head.weight)}
    return ep, bp, hp


def _sample_token(logits, key, *, temperature=1.0, top_k=0, top_p=1.0):
    """logits [B, V] -> token ids [B] (greedy when temperature == 0).

    The sampling knobs are KEYWORD-ONLY statics (python `if`s below branch
    on them): callers bind them via functools.partial before jitting, so
    each (temperature, top_k, top_p) combination is its own executable —
    graftlint TRACE001 enforces that they can never arrive traced."""
    if temperature == 0.0:
        return jnp.argmax(logits, axis=-1).astype(jnp.int32)
    logits = logits / temperature
    if top_k and top_k > 0:
        kth = jnp.sort(logits, axis=-1)[:, -top_k][:, None]
        logits = jnp.where(logits < kth, -jnp.inf, logits)
    if top_p < 1.0:
        sorted_logits = jnp.sort(logits, axis=-1)[:, ::-1]
        probs = jax.nn.softmax(sorted_logits, axis=-1)
        cum = jnp.cumsum(probs, axis=-1)
        # smallest set with cumulative prob >= top_p; keep at least 1
        cutoff_idx = jnp.sum(cum < top_p, axis=-1)
        cutoff = jnp.take_along_axis(sorted_logits, cutoff_idx[:, None], -1)
        logits = jnp.where(logits < cutoff, -jnp.inf, logits)
    return jax.random.categorical(key, logits, axis=-1).astype(jnp.int32)


def llama_generate(params, config: LlamaConfig, input_ids, max_new_tokens=32,
                   temperature=0.0, top_k=0, top_p=1.0, eos_token_id=None,
                   seed=0, max_seq=None, dtype=None):
    """Compiled autoregressive generation over the KV-cache decode path
    (the PaddleNLP `model.generate` analog for the functional params).

    input_ids: int [B, T_prompt] (numpy/jax). Returns int32 of FIXED shape
    [B, T_prompt + max_new_tokens]; once a sequence emits eos_token_id its
    tail is padded with eos. Raises when the total length exceeds the cache
    (max_seq / max_position_embeddings). The jitted prefill/decode/sample
    executables are cached per (config, lengths, sampling knobs) so serving
    loops compile once.
    """
    c = config
    ids = jnp.asarray(input_ids, jnp.int32)
    B, T = ids.shape
    S_max = _resolve_cache_len(c, T, max_new_tokens, max_seq)
    prefill, decode, sample = _generate_executables(
        c, S_max, temperature, top_k, top_p, dtype=dtype)
    key = jax.random.PRNGKey(seed)

    logits, cache = prefill(params, ids)
    out = [ids]
    done = jnp.zeros((B,), bool)
    for i in range(max_new_tokens):
        key, sub = jax.random.split(key)
        tok = sample(logits, sub)
        if eos_token_id is not None:
            tok = jnp.where(done, eos_token_id, tok)
            done = done | (tok == eos_token_id)
        out.append(tok[:, None])
        if i == max_new_tokens - 1:
            break                        # the next logits would be discarded
        if eos_token_id is not None and bool(done.all()):
            # every sequence finished: pad the tail to the fixed shape
            pad = jnp.full((B, max_new_tokens - 1 - i), eos_token_id,
                           jnp.int32)
            out.append(pad)
            break
        logits, cache = decode(params, tok, cache)
    return jnp.concatenate(out, axis=1)


_GENERATE_CACHE = {}


def _resolve_cache_len(config, T, max_new_tokens, max_seq):
    """Shared llama_generate/_fused prologue: bucket the KV-cache length
    (multiple of 256, capped by the model context) so requests in the same
    bucket share an executable, and validate the fit."""
    if config.num_experts > 1:
        raise NotImplementedError(
            "llama generation: the MoE decode path is not implemented — "
            "build_llama_decode computes the dense FFN")
    required = T + max_new_tokens
    bucket = min(config.max_position_embeddings,
                 ((required + 255) // 256) * 256)
    S_max = max_seq or bucket
    if required > S_max:
        raise ValueError(
            f"prompt ({T}) + max_new_tokens ({max_new_tokens}) = {required} "
            f"exceeds the KV cache length {S_max}; raise max_seq / "
            "max_position_embeddings or generate fewer tokens")
    return S_max


def _cache_put(cache, key, val, cap=16):
    """FIFO-evict ONE entry at capacity; clearing all would thrash hot
    executables."""
    if len(cache) > cap:
        cache.pop(next(iter(cache)))
    cache[key] = val
    return val


def llama_generate_fused(params, config: LlamaConfig, input_ids,
                         max_new_tokens=32, temperature=0.0, top_k=0,
                         top_p=1.0, eos_token_id=None, seed=0, max_seq=None,
                         dtype=None):
    """Whole-generation-in-one-graph variant of llama_generate: prefill +
    a `lax.fori_loop` over decode steps (sampling inside the loop) compile
    into ONE executable, so serving pays a single dispatch per request
    instead of one per token.

    The per-token python loop pays one host dispatch per token; the fused
    loop pays one per request (speeds: not measured on this code).
    Trade-off vs llama_generate: always runs max_new_tokens
    steps (no early exit when every sequence hits EOS — EOS tails are
    masked to eos_token_id, same output contract)."""
    c = config
    ids = jnp.asarray(input_ids, jnp.int32)
    if max_new_tokens <= 0:
        # parity with llama_generate: the prompt comes back unchanged
        # (ADVICE r5 #3 — the fused loop's pre-loop sample would otherwise
        # clobber the last prompt token via the clamped update at column T)
        return ids
    B, T = ids.shape
    S_max = _resolve_cache_len(c, T, max_new_tokens, max_seq)
    fused = _generate_fused_executable(
        c, S_max, int(max_new_tokens), float(temperature), int(top_k),
        float(top_p), -1 if eos_token_id is None else int(eos_token_id),
        None if dtype is None else jnp.dtype(dtype).name)
    return fused(params, ids, jax.random.PRNGKey(seed))


_FUSED_CACHE = {}


def _generate_fused_executable(config, S_max, max_new, temperature, top_k,
                               top_p, eos_id, dtype_name):
    ckey = (tuple(sorted(config.__dict__.items())), S_max, max_new,
            temperature, top_k, top_p, eos_id, dtype_name)
    hit = _FUSED_CACHE.get(ckey)
    if hit is not None:
        return hit
    dtype = None if dtype_name is None else jnp.dtype(dtype_name)
    _, prefill, decode_step = build_llama_decode(config, max_seq=S_max,
                                                 dtype=dtype)
    sample = functools.partial(_sample_token, temperature=temperature,
                               top_k=top_k, top_p=top_p)

    def gen(params, ids, key):
        B, T = ids.shape
        logits, cache = prefill(params, ids)
        out = jnp.zeros((B, T + max_new), jnp.int32)
        out = jax.lax.dynamic_update_slice(out, ids, (0, 0))
        done = jnp.zeros((B,), bool)

        def emit(logits, out, done, key, t):
            key, sub = jax.random.split(key)
            tok = sample(logits, sub)
            if eos_id >= 0:
                tok = jnp.where(done, eos_id, tok)
                done = done | (tok == eos_id)
            out = jax.lax.dynamic_update_slice(out, tok[:, None], (0, T + t))
            return tok, out, done, key

        # decode-then-sample ordering: exactly max_new - 1 decode steps (the
        # logits after the LAST sampled token are never computed — the same
        # dead step llama_generate's loop breaks out of)
        tok, out, done, key = emit(logits, out, done, key, 0)

        def body(t, carry):
            tok, cache, out, done, key = carry
            logits, cache = decode_step(params, tok, cache)
            tok, out, done, key = emit(logits, out, done, key, t)
            return (tok, cache, out, done, key)

        tok, cache, out, done, key = jax.lax.fori_loop(
            1, max_new, body, (tok, cache, out, done, key))
        return out

    return _cache_put(_FUSED_CACHE, ckey, jax.jit(gen))


def _generate_executables(config, S_max, temperature, top_k, top_p,
                          dtype=None):
    """(prefill, decode, sample) jitted once per key — new closures per call
    would defeat jax.jit's cache entirely. `dtype` is the activation/KV-cache
    compute dtype (None = f32; serve bf16 params with dtype=bf16)."""
    ckey = (tuple(sorted(config.__dict__.items())), S_max,
            float(temperature), int(top_k), float(top_p),
            None if dtype is None else jnp.dtype(dtype).name)
    hit = _GENERATE_CACHE.get(ckey)
    if hit is not None:
        return hit
    _, prefill, decode_step = build_llama_decode(config, max_seq=S_max,
                                                 dtype=dtype)
    entry = (jax.jit(prefill), jax.jit(decode_step),
             jax.jit(functools.partial(_sample_token, temperature=temperature,
                                       top_k=top_k, top_p=top_p)))
    return _cache_put(_GENERATE_CACHE, ckey, entry)
