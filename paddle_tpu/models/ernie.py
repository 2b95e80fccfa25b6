"""ERNIE model family (ERNIE-3.0 base MLM pretrain, sharding stage-2).

Reference: PaddleNLP's ErnieModel (transformer encoder, learned positions,
token-type embeddings, post-LN) — the reference repo ships the framework it
trains on; the architecture here follows the public ERNIE-3.0-base config.
Built entirely from framework layers (nn.TransformerEncoder path) so it
exercises the encoder stack the way vision/ViT exercises it.
"""
from __future__ import annotations

from dataclasses import dataclass

import jax.numpy as jnp

from ..nn.layer import Layer
from ..nn import Linear, Embedding, LayerNorm, Dropout, LayerList
from ..nn import functional as F
from ..tensor import manipulation as manip

__all__ = ["ErnieConfig", "ErnieModel", "ErnieForMaskedLM",
           "ErnieForSequenceClassification", "ernie_config_base",
           "ernie_config_tiny"]


@dataclass
class ErnieConfig:
    vocab_size: int = 40000
    hidden_size: int = 768
    num_hidden_layers: int = 12
    num_attention_heads: int = 12
    intermediate_size: int = 3072
    hidden_act: str = "gelu"
    hidden_dropout_prob: float = 0.1
    attention_probs_dropout_prob: float = 0.1
    max_position_embeddings: int = 2048
    type_vocab_size: int = 4
    layer_norm_eps: float = 1e-12
    pad_token_id: int = 0


def ernie_config_base():
    return ErnieConfig()


def ernie_config_tiny(vocab=1000, hidden=64, layers=2, heads=4, seq=64):
    return ErnieConfig(vocab_size=vocab, hidden_size=hidden,
                       num_hidden_layers=layers, num_attention_heads=heads,
                       intermediate_size=hidden * 4,
                       max_position_embeddings=seq, hidden_dropout_prob=0.0,
                       attention_probs_dropout_prob=0.0)


class ErnieEmbeddings(Layer):
    def __init__(self, c: ErnieConfig):
        super().__init__()
        self.word_embeddings = Embedding(c.vocab_size, c.hidden_size)
        self.position_embeddings = Embedding(c.max_position_embeddings,
                                             c.hidden_size)
        self.token_type_embeddings = Embedding(c.type_vocab_size, c.hidden_size)
        self.layer_norm = LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.dropout = Dropout(c.hidden_dropout_prob)

    def forward(self, input_ids, token_type_ids=None, position_ids=None):
        import paddle_tpu as paddle
        B, S = input_ids.shape
        if position_ids is None:
            position_ids = paddle.to_tensor(
                jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S)))
        if token_type_ids is None:
            token_type_ids = paddle.to_tensor(
                jnp.zeros((B, S), jnp.int32))
        x = (self.word_embeddings(input_ids)
             + self.position_embeddings(position_ids)
             + self.token_type_embeddings(token_type_ids))
        return self.dropout(self.layer_norm(x))


class ErnieSelfAttention(Layer):
    def __init__(self, c: ErnieConfig):
        super().__init__()
        self.num_heads = c.num_attention_heads
        self.head_dim = c.hidden_size // c.num_attention_heads
        self.q = Linear(c.hidden_size, c.hidden_size)
        self.k = Linear(c.hidden_size, c.hidden_size)
        self.v = Linear(c.hidden_size, c.hidden_size)
        self.out = Linear(c.hidden_size, c.hidden_size)
        self.dropout_p = c.attention_probs_dropout_prob

    def forward(self, x, attn_mask=None):
        b, s, _ = x.shape
        q = manip.reshape(self.q(x), [b, s, self.num_heads, self.head_dim])
        k = manip.reshape(self.k(x), [b, s, self.num_heads, self.head_dim])
        v = manip.reshape(self.v(x), [b, s, self.num_heads, self.head_dim])
        o = F.scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=self.dropout_p,
            is_causal=False, training=self.training)
        return self.out(manip.reshape(o, [b, s, -1]))


class ErnieLayer(Layer):
    """Post-LN encoder block (BERT/ERNIE convention)."""

    def __init__(self, c: ErnieConfig):
        super().__init__()
        self.attention = ErnieSelfAttention(c)
        self.norm1 = LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.fc1 = Linear(c.hidden_size, c.intermediate_size)
        self.fc2 = Linear(c.intermediate_size, c.hidden_size)
        self.norm2 = LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.dropout = Dropout(c.hidden_dropout_prob)
        self.act = getattr(F, c.hidden_act)

    def forward(self, x, attn_mask=None):
        x = self.norm1(x + self.dropout(self.attention(x, attn_mask)))
        h = self.fc2(self.act(self.fc1(x)))
        return self.norm2(x + self.dropout(h))


class ErnieModel(Layer):
    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.config = config
        self.embeddings = ErnieEmbeddings(config)
        self.encoder = LayerList([ErnieLayer(config)
                                  for _ in range(config.num_hidden_layers)])
        self.pooler = Linear(config.hidden_size, config.hidden_size)

    def forward(self, input_ids, token_type_ids=None, position_ids=None,
                attention_mask=None):
        if attention_mask is not None and attention_mask.ndim == 2:
            # [B, S] padding mask -> additive [B, 1, 1, S]
            import paddle_tpu as paddle
            m = (1.0 - attention_mask.astype("float32")) * -1e4
            attention_mask = manip.reshape(m, [m.shape[0], 1, 1, m.shape[1]])
        x = self.embeddings(input_ids, token_type_ids, position_ids)
        for layer in self.encoder:
            x = layer(x, attention_mask)
        pooled = F.tanh(self.pooler(x[:, 0]))
        return x, pooled


class ErnieForMaskedLM(Layer):
    """MLM head (the BASELINE pretrain objective)."""

    def __init__(self, config: ErnieConfig):
        super().__init__()
        self.ernie = ErnieModel(config)
        self.config = config
        c = config
        self.transform = Linear(c.hidden_size, c.hidden_size)
        self.layer_norm = LayerNorm(c.hidden_size, epsilon=c.layer_norm_eps)
        self.decoder = Linear(c.hidden_size, c.vocab_size)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None,
                labels=None, ignore_index=-100, return_logits=False):
        """With labels, returns (loss, logits_or_None).

        The training loss runs through the vocab-chunked online-logsumexp
        head (the same chunked-CE design that broke the LLaMA perf plateau,
        PERF.md §3): the [B, S, V] logits tensor never materializes, and the
        second element of the return is **None** — a deliberate departure
        from the reference's (loss, prediction_scores) contract, because
        materializing 40k-vocab logits nobody reads is exactly the HBM
        traffic the head removes.  Callers that need the scores pass
        `return_logits=True` to get the dense head + dense CE (identical
        loss to f32 accumulation, reference-shaped return)."""
        seq, _ = self.ernie(input_ids, token_type_ids,
                            attention_mask=attention_mask)
        h = self.layer_norm(F.gelu(self.transform(seq)))
        if labels is not None:
            if return_logits:
                logits = self.decoder(h)
                loss = F.cross_entropy(
                    manip.reshape(logits, [-1, self.config.vocab_size]),
                    manip.reshape(labels, [-1]), ignore_index=ignore_index)
                return loss, logits
            from ..incubate.nn import functional as IF
            loss = IF.fused_linear_cross_entropy(
                h, self.decoder.weight, labels, n_chunks=8,
                bias=self.decoder.bias, ignore_index=ignore_index)
            return loss, None
        return self.decoder(h)


class ErnieForSequenceClassification(Layer):
    def __init__(self, config: ErnieConfig, num_classes=2):
        super().__init__()
        self.ernie = ErnieModel(config)
        self.dropout = Dropout(config.hidden_dropout_prob)
        self.classifier = Linear(config.hidden_size, num_classes)

    def forward(self, input_ids, token_type_ids=None, attention_mask=None):
        _, pooled = self.ernie(input_ids, token_type_ids,
                               attention_mask=attention_mask)
        return self.classifier(self.dropout(pooled))
