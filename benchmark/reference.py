"""The plain reference: a pre-norm decoder's forward pass in straightforward
``jax.numpy`` — RMSNorm, rotary embedding (rotate-half, the public
``modeling_mistral`` convention) at the config's theta, grouped-query causal
attention, SwiGLU, output head — in float32 under
``jax.default_matmul_precision("highest")`` (on a TPU an f32 matmul is
otherwise rounded to bf16).  No cache, no kernel, no batching.

It is independent of the code under test and reads only the WEIGHTS the
system was given: the ``(embed, blocks, head)`` trees whose leaves are
``tok [V,H]``; ``ln1 wq wk wv wo ln2 wgate wup wdown`` stacked over layers,
weights as ``[in, out]``; ``ln_f [H]`` and ``lm [H,V]``.  The weights are
read one layer at a time and widened to f32 inside the layer (an f32 copy of
the whole model does not fit beside the system on one chip).

Tolerances (set from chip runs of PR 25, see PERF.md section 6):

SERVE_LOGIT_DELTA — the serving engine is checked through its TOKENS: at every
  generated position the engine's greedy token must have a reference logit
  within this distance of the reference's maximum.  Random weights flip an
  arg-max on rounding, so tokens cannot be compared for equality; a token
  the bf16 engine prefers is one whose f32 logit is near the top.
TRAIN_LOSS_RTOL — the train step's loss on its first batch, from the initial
  parameters, against the reference's mean token NLL of the same batch.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np

# Chip readings (PR 25, one TPU v5 lite, 16 layers at Mistral-7B widths, bf16
# engine against this f32 reference): over 7 seeds x 32 positions the worst
# gap per seed was 0, 0.0042, 0.0051, 0.0074, 0.0076, 0.0135 and 0.0354 (mean
# gap 0.0002-0.0018), where |logit| reaches ~6 and the top-2 gap of these
# random-weight logits is ~0.1-0.4.  With the reference reading layers 0 and 1
# exchanged the same check read worst 7.54, mean 5.20.  0.15 is 4x the worst
# honest reading and 50x below the wrong model's; a lower precision than bf16
# activations, or a dropped term, lands above it.
SERVE_LOGIT_DELTA = 0.15
# Chip readings (PR 25, 4 layers, 2 x 2048 tokens, loss ~11.2): |step loss -
# reference| / reference was 2.3e-6 ... 3.9e-5 over 7 seeds, both signs (bf16
# activations against f32, averaged over 4,096 tokens).  With layers 0 and 1
# exchanged it read 1.24e-3 on one seed and 1.65e-4 on another: with random
# weights every model of this shape has nearly the same MEAN loss, so only a
# tight tolerance tells them apart, and not by much.  1e-4 is 2.5x the worst
# honest reading and below both wrong-model readings.
TRAIN_LOSS_RTOL = 1e-4


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _rope(x, theta):
    """x [T, heads, D]: rotate-half rotary embedding at positions 0..T-1."""
    t, _, d = x.shape
    inv = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = jnp.arange(t, dtype=jnp.float32)[:, None] * inv[None, :]
    ang = jnp.concatenate([ang, ang], -1)[:, None, :]
    x1, x2 = x[..., : d // 2], x[..., d // 2:]
    return x * jnp.cos(ang) + jnp.concatenate([-x2, x1], -1) * jnp.sin(ang)


def _layer(x, lp, *, heads, kv_heads, theta, eps):
    """One decoder block over one sequence x [T, H], float32."""
    lp = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32), lp)
    t, hidden = x.shape
    d = hidden // heads
    h = _rms(x, lp["ln1"], eps)
    q = _rope((h @ lp["wq"]).reshape(t, heads, d), theta)
    k = _rope((h @ lp["wk"]).reshape(t, kv_heads, d), theta)
    v = (h @ lp["wv"]).reshape(t, kv_heads, d)
    k = jnp.repeat(k, heads // kv_heads, axis=1)
    v = jnp.repeat(v, heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(d)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    x = x + o.reshape(t, hidden) @ lp["wo"]
    h = _rms(x, lp["ln2"], eps)
    return x + (jax.nn.silu(h @ lp["wgate"]) * (h @ lp["wup"])) @ lp["wdown"]


def _head(x, hp, eps):
    return _rms(x, hp["ln_f"].astype(jnp.float32), eps) \
        @ hp["lm"].astype(jnp.float32)


def _nll(x, hp, labels, eps):
    logp = jax.nn.log_softmax(_head(x, hp, eps), -1)
    return -jnp.take_along_axis(logp, labels[:, None], -1)[:, 0].sum()


_layer_jit = jax.jit(_layer, static_argnames=("heads", "kv_heads", "theta",
                                              "eps"))
_head_jit = jax.jit(_head, static_argnames="eps")
_nll_jit = jax.jit(_nll, static_argnames="eps")


def hidden_states(params, model, ids, layer_order=None):
    """ids int[T] -> the last block's output [T, H] (before the final norm).
    ``model`` holds num_attention_heads, num_key_value_heads, rope_theta,
    rms_norm_eps.  ``layer_order`` (default 0..L-1) reads the layers in
    another order: a deliberately WRONG model, to show the checks can fail."""
    embed, blocks, _ = params
    with jax.default_matmul_precision("highest"):
        x = embed["tok"][jnp.asarray(ids)].astype(jnp.float32)
        for i in layer_order or range(blocks["wq"].shape[0]):
            x = _layer_jit(x, jax.tree_util.tree_map(lambda a: a[i], blocks),
                      heads=model["num_attention_heads"],
                      kv_heads=model["num_key_value_heads"],
                      theta=float(model["rope_theta"]),
                      eps=float(model["rms_norm_eps"]))
    return x


def logits_at(params, model, ids, positions, layer_order=None):
    """Reference logits [len(positions), V] of the sequence ``ids``."""
    x = hidden_states(params, model, ids, layer_order)[jnp.asarray(positions)]
    with jax.default_matmul_precision("highest"):
        return np.asarray(_head_jit(x, params[2],
                                    eps=float(model["rms_norm_eps"])))


def generation_gaps(params, model, prompt, generated, pad_to=None,
                    layer_order=None):
    """For each generated token: reference maximum logit at its position
    minus the reference logit of the token the system chose (>= 0; 0 where
    the system chose the reference's arg-max).  The whole sequence is
    recomputed without a cache; ``pad_to`` pads it at the END (which a
    causal model cannot see) so that several prompts share one compile."""
    t, n = len(prompt), len(generated)
    ids = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(generated, np.int32)])
    if pad_to is not None:
        ids = np.concatenate([ids, np.zeros(pad_to - len(ids), np.int32)])
    logits = logits_at(params, model, ids, np.arange(t - 1, t - 1 + n),
                       layer_order)
    chosen = logits[np.arange(n), np.asarray(generated)]
    return (logits.max(-1) - chosen).tolist()


def mean_nll(params, model, inputs, labels, layer_order=None):
    """Mean over every position of every sequence of -log p(label), one
    sequence at a time: what a train step's head must return for the batch
    (inputs, labels), both int[B, S]."""
    total = 0.0
    for row, lab in zip(np.asarray(inputs), np.asarray(labels)):
        x = hidden_states(params, model, row, layer_order)
        with jax.default_matmul_precision("highest"):
            total += float(_nll_jit(x, params[2], jnp.asarray(lab, jnp.int32),
                                    eps=float(model["rms_norm_eps"])))
    return total / np.asarray(labels).size
