"""The plain reference of a Nemotron-H hybrid (``model_type: nemotron_h``):
Mamba-2, attention and LatentMoE layers in straightforward ``jax.numpy``,
float32 under ``jax.default_matmul_precision("highest")``.  No cache, no
kernel, no batching, no chunked scan; nothing is imported from the program.

Every layer is ``x = x + Mixer_i(RMSNorm_i(x))`` (eps ``layer_norm_epsilon``),
one mixer a layer, the kind given by ``hybrid_override_pattern``; then
``norm_f`` and the untied head.  x is [T, H].

  M  Mamba-2.  [z | xBC | dt] = u W_in; xBC = silu(causal_conv1d(xBC, w) + b)
     (kernel 4, a channel at a time); x, B, C = split(xBC) as [heads, P],
     [groups, N], [groups, N] (heads/groups heads share a group's B and C);
     dt = softplus(dt + dt_bias), A = -exp(A_log), a scalar a head;
     h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t, y_t = h_t C_t + D x_t:
     a SEQUENTIAL ``lax.scan`` over tokens; y = GroupRMSNorm(y * silu(z)) *
     w_norm (the gate before the norm, statistics over each group's
     channels); out = y W_out.
  *  attention.  GQA, no bias, causal over all keys, softmax(q k^T /
     sqrt(D)) v, W_o.  No rotary embedding.
  E  LatentMoE.  s = sigmoid(u W_r) (float32); select top-k of s + b;
     w = s[sel] / sum(s[sel]) * routed_scaling_factor; l = u W_1;
     r = sum_e w_e relu(l W_e^up)^2 W_e^down; out = r W_2 + Shared(u),
     Shared(u) = relu(u W_s^up)^2 W_s^down.  Every HELD expert is computed
     for every token and masked by the selection, a block of experts at a
     time (widened to float32 inside the block).

Departures from the published model, each also in the configuration file:
  - the chip's SHARE: the router scores all ``published.n_routed_experts``
    experts and takes its published top-k; the weights hold experts
    ``expert_offset .. expert_offset + n_routed_experts`` and only their
    part of r is computed (the absent experts' part is left out and that
    partial result goes on to the next layer); the vocabulary is the slice
    the head's weights hold;
  - the depth is the layers the pattern in the file lists;
  - no multi-token-prediction head (the base model's logits do not depend
    on it);
  - attention takes no positions (``rope_theta`` and
    ``partial_rotary_factor`` are unused: assumed);
  - weights are random from a seed.

It reads only the WEIGHTS the system was given, ``(embed, blocks, head)``:
``embed.tok [V, H]``; ``blocks[kind][leaf][j]`` the leaf of the kind's j-th
layer (a tuple of arrays or a stacked array alike), weights as [in, out]:
``mamba``: norm, w_in [H, d_in + conv + heads], conv_w [K, conv], conv_b,
dt_bias, A_log, D [heads], norm_w [d_in], w_out; ``attn``: norm, wq, wk, wv,
wo; ``moe``: norm, router [H, E_all], router_bias [E_all], w_lat_in,
w_lat_out, we_up [held, L, F], we_down [held, F, L], ws_up, ws_down;
``head``: ln_f, lm [H, V].

``fault`` hands the REFERENCE a deliberately wrong model, to show that the
checks can fail: ``{"layer_order": [...]}``, ``{"drop_d": True}``,
``{"drop_conv_bias": True}``, ``{"route_scale": 1.0}``, ``{"dtype":
"bfloat16"}`` (everything, the recurrent state too, in bfloat16 at the
default matmul precision: the nearest precision below the stated one).

``given`` routes the reference by the SYSTEM'S selections (`forward`): the
check of a system in a lower precision than this one compares arithmetic, not
the fall of near-ties.  Limits of the check (chip readings: see the
constants).
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Chip readings behind the limits (PR 33, one TPU v5 lite, the 11-layer cut at
# published widths, bf16 engine against this f32 reference ROUTED BY THE
# ENGINE'S SELECTIONS; 4 check prompts x 128 generated positions and x 5 Mamba
# layers a seed; `tools/wrong_model_nemotron_h.py`, three seeds an arm; honest:
# six seeds through the tool and nine benchmark runs at 128 positions, eight
# more at 32 in the same bands; PERF.md section 6 has the table).  Left to route itself, the
# reference differs from a bf16 engine by the fall of near-ties at the router
# (0.7 % of the selections: the scores around rank 22 of 512 lie ~0.02 apart
# and a bf16 hidden state moves them by ~0.005), each of which changes 1/22 of
# a layer's routed output for every later layer and token: the worst logit gap
# then read 0.10 ... 1.74 and the state error 0.05 ... 0.14 HONEST, and no
# limit could tell a bfloat16 reference from the engine.  Given the selections,
# what is left is arithmetic, and it is steady.
#
# SERVE_LOGIT_DELTA — the worst over the generated positions of (the
#   reference's maximum logit - the reference logit of the engine's token).
#   Honest 0.025 ... 0.043 (mean 0.0001 ... 0.0008).  Wrong models: the conv
#   bias dropped 0.63 ... 0.76, routed_scaling_factor 1 3.6 ... 4.8, the D x_t
#   term dropped 3.4 ... 4.6, two layers exchanged 7.4 ... 8.6.  0.15 is 3.5 x
#   the worst honest reading and 4.2 x below the nearest wrong model; it is
#   what holds the attention layer and the head, which no state follows.  (A
#   bfloat16 reference reads 0.029 ... 0.086: top-1 tokens seldom differ.)
SERVE_LOGIT_DELTA = 0.15
# SERVE_STATE_RTOL — the worst over the check's prompts and Mamba layers of
#   ||h_engine - h_reference|| / ||h_reference|| over the whole [heads, P, N]
#   state the slot is left with after its last consumed token.  Honest 0.0150
#   ... 0.0187 (0.0076 ... 0.0086 at the first Mamba layer, growing with
#   depth).  THIS REFERENCE IN BFLOAT16 (the nearest precision below the
#   stated one: everything, the state too, at the default matmul precision):
#   0.0310 ... 0.0538, refused on every seed.  Wrong models: 0.28 ... 1.6.
#   0.025 is 1.34 x the worst honest reading and 1.24 x below the lowest
#   bfloat16 one.
SERVE_STATE_RTOL = 0.025
# SERVE_STRAY_SHARE — the share of the engine's (token, expert) selections
#   outside the reference's own top-k on the same hidden state: what holds
#   the ROUTER, which a reference routed by the engine no longer holds through
#   the logits.  Honest 0.00725 ... 0.00777 (~440,000 selections a seed: the
#   near-ties, and steady).  The bfloat16 reference 0.0114 ... 0.0136 (refused
#   by this limit too); wrong models 0.08 ... 0.92.  0.0093 is 1.20 x the
#   worst honest reading and 1.23 x below the lowest bfloat16 one.
SERVE_STRAY_SHARE = 0.0093
# SERVE_STRAY_SHORT — how far below the reference's k-th best biased score
#   the worst stray selection scored: a near-tie is near.  Honest 0.0050 ...
#   0.0064 (a maximum over ~440,000: given room); the bfloat16 reference
#   0.0086 ... 0.0142; wrong models 0.09 ... 0.86.  0.03 is 4.7 x the worst
#   honest reading and 3 x below the nearest wrong model.
SERVE_STRAY_SHORT = 0.03
# SERVE_STATE_BF16_SHARE — an EXTRA on the engine's own array, no comparison:
#   the state must be KEPT in float32, as the configuration states.  The share
#   of its elements that a bfloat16 holds exactly (low 16 bits zero) reads
#   3.3e-5 ... 4.0e-5 honest and 1.0 with the engine keeping its state in
#   bfloat16.  That control's state error reads 0.0175 ... 0.0211 (mean
#   0.0137 ... 0.0161 against an honest 0.0118 ... 0.0127): a rounding of 2^-9
#   a store adds in quadrature to the 0.0076+ of the bf16 operands and stays
#   within a tenth of the honest band, at 32 decode steps and at 128 alike, so
#   NO comparison refuses it with room on both sides and the bits do.  What
#   the bits cannot see — a float32 state updated from bfloat16-rounded
#   operands — is open (PERF.md section 7).
SERVE_STATE_BF16_SHARE = 0.01

EXPERT_BLOCK = 16


def _rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def _relu2(x):
    return jnp.square(jnp.maximum(x, 0))


def _wide(lp, ct):
    return jax.tree_util.tree_map(lambda a: a.astype(ct), lp)


def _mamba(x, lp, true_len, *, heads, head_dim, groups, state, eps, drop_d,
           drop_conv_bias, ct):
    """One Mamba-2 layer over one sequence x [T, H] -> (x', h after token
    ``true_len - 1`` [heads, head_dim, state])."""
    lp = _wide(lp, ct)
    t = x.shape[0]
    d_in, gn = heads * head_dim, groups * state
    u = _rms(x, lp["norm"], eps)
    zxd = u @ lp["w_in"]
    z, xbc, dt = zxd[:, :d_in], zxd[:, d_in:2 * d_in + 2 * gn], \
        zxd[:, 2 * d_in + 2 * gn:]
    k = lp["conv_w"].shape[0]
    padded = jnp.concatenate([jnp.zeros((k - 1, xbc.shape[1]), ct), xbc])
    conv = sum(padded[i:i + t] * lp["conv_w"][i] for i in range(k))
    if not drop_conv_bias:
        conv = conv + lp["conv_b"]
    xbc = jax.nn.silu(conv)
    xs = xbc[:, :d_in].reshape(t, heads, head_dim)
    rep = heads // groups
    b = jnp.repeat(xbc[:, d_in:d_in + gn].reshape(t, groups, state), rep, 1)
    c = jnp.repeat(xbc[:, d_in + gn:].reshape(t, groups, state), rep, 1)
    dt = jax.nn.softplus(dt + lp["dt_bias"])
    # tokens past the real ones leave the state as it was
    dt = jnp.where((jnp.arange(t) < true_len)[:, None], dt, 0)
    a = -jnp.exp(lp["A_log"])

    def step(h, tok):
        x_t, b_t, c_t, dt_t = tok
        h = h * jnp.exp(dt_t * a)[:, None, None].astype(ct) \
            + (dt_t[:, None] * x_t)[:, :, None] * b_t[:, None, :]
        return h.astype(ct), jnp.einsum("hpn,hn->hp", h, c_t)

    h, y = jax.lax.scan(step, jnp.zeros((heads, head_dim, state), ct),
                        (xs, b, c, dt))
    if not drop_d:
        y = y + lp["D"][None, :, None] * xs
    y = y.reshape(t, d_in) * jax.nn.silu(z)
    y = _rms(y.reshape(t, groups, d_in // groups), 1.0, eps) \
        .reshape(t, d_in) * lp["norm_w"]
    return x + (y @ lp["w_out"]).astype(ct), h


def _attn(x, lp, *, heads, kv_heads, head_dim, eps, ct):
    lp = _wide(lp, ct)
    t = x.shape[0]
    u = _rms(x, lp["norm"], eps)
    q = (u @ lp["wq"]).reshape(t, heads, head_dim)
    k = jnp.repeat((u @ lp["wk"]).reshape(t, kv_heads, head_dim),
                   heads // kv_heads, axis=1)
    v = jnp.repeat((u @ lp["wv"]).reshape(t, kv_heads, head_dim),
                   heads // kv_heads, axis=1)
    s = jnp.einsum("qhd,khd->hqk", q, k) / math.sqrt(head_dim)
    s = jnp.where(jnp.tril(jnp.ones((t, t), bool)), s, -jnp.inf)
    o = jnp.einsum("hqk,khd->qhd", jax.nn.softmax(s, -1), v)
    return x + o.reshape(t, heads * head_dim) @ lp["wo"]


def _route(x, lp, given, n_given, *, top_k, scale, eps, ct):
    """-> (u, latent l, sel int32 [T, k], weights [T, k], own int32 [T, k],
    short f32 [T]).  ``own`` is this model's top-k; ``sel`` is ``given``
    [T, k] for the first ``n_given`` tokens (the system's selections: the
    scores and the weights stay this model's) and ``own`` after them;
    ``short[t]`` is how far the worst of the selected experts' biased
    scores lies below the k-th best (0 where the sets agree)."""
    u = _rms(x, lp["norm"].astype(ct), eps)
    s = jax.nn.sigmoid((u @ lp["router"].astype(ct)).astype(jnp.float32))
    biased = s + lp["router_bias"].astype(jnp.float32)
    best, own = jax.lax.top_k(biased, top_k)
    own = own.astype(jnp.int32)
    sel = jnp.where((jnp.arange(x.shape[0]) < n_given)[:, None],
                    jnp.clip(given, 0, s.shape[-1] - 1), own)
    short = best[:, -1] - jnp.take_along_axis(biased, sel, -1).min(-1)
    w = jnp.take_along_axis(s, sel, -1)
    w = w / w.sum(-1, keepdims=True) * scale
    return u, u @ lp["w_lat_in"].astype(ct), sel, w, own, short


def _expert_block(lat, we_up, we_down, mask):
    """The block's experts for EVERY token, masked by the selection:
    lat [T, L], we_up [B, L, F], we_down [B, F, L], mask [T, B] (the routing
    weight where the token selected the expert, else 0) -> [T, L]."""
    ct = lat.dtype
    act = _relu2(jnp.einsum("tl,elf->tef", lat, we_up.astype(ct)))
    y = jnp.einsum("tef,efl->tel", act, we_down.astype(ct))
    return jnp.einsum("tel,te->tl", y, mask.astype(ct))


def _moe_out(x, u, r, lp, ct):
    shared = _relu2(u @ lp["ws_up"].astype(ct)) @ lp["ws_down"].astype(ct)
    return x + r @ lp["w_lat_out"].astype(ct) + shared


_mamba_jit = jax.jit(_mamba, static_argnames=(
    "heads", "head_dim", "groups", "state", "eps", "drop_d",
    "drop_conv_bias", "ct"))
_attn_jit = jax.jit(_attn, static_argnames=("heads", "kv_heads", "head_dim",
                                            "eps", "ct"))
_route_jit = jax.jit(_route, static_argnames=("top_k", "scale", "eps", "ct"))
_block_jit = jax.jit(_expert_block)
_moe_out_jit = jax.jit(_moe_out, static_argnames="ct")


@functools.partial(jax.jit, static_argnames=("eps", "ct"))
def _head(x, hp, eps, ct):
    return (_rms(x, hp["ln_f"].astype(ct), eps) @ hp["lm"].astype(ct)) \
        .astype(jnp.float32)


def layer_kinds(model):
    """[(kind, index within its kind)] of the pattern's layers."""
    seen, out = {}, []
    for ch in model["hybrid_override_pattern"]:
        kind = {"M": "mamba", "*": "attn", "E": "moe"}[ch]
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def moe_layer(x, lp, model, fault=None, ct=jnp.float32, given=None):
    """One LatentMoE layer's share over x [T, H] -> (x', {"sel": the
    selections it routed by [T, k], "own": its own top-k, "short": [T]}).
    ``given`` int[n, k], n <= T: route the first n tokens by these."""
    fault = fault or {}
    eps = float(model["layer_norm_epsilon"])
    offset = int(model.get("expert_offset", 0))
    held = lp["we_up"].shape[0]
    top_k = int(model["num_experts_per_tok"])
    n_given = 0 if given is None else len(given)
    full = np.zeros((x.shape[0], top_k), np.int32)
    full[:n_given] = 0 if given is None else given
    u, lat, sel, w, own, short = _route_jit(
        x, lp, full, jnp.asarray(n_given, jnp.int32), top_k=top_k,
        scale=float(fault.get("route_scale", model["routed_scaling_factor"])),
        eps=eps, ct=ct)
    r = jnp.zeros_like(lat)
    for lo in range(0, held, EXPERT_BLOCK):
        ids = offset + jnp.arange(lo, min(lo + EXPERT_BLOCK, held))
        mask = jnp.where(sel[:, :, None] == ids[None, None, :],
                         w[:, :, None], 0.0).sum(1)
        r = r + _block_jit(lat, lp["we_up"][lo:lo + EXPERT_BLOCK],
                           lp["we_down"][lo:lo + EXPERT_BLOCK], mask)
    return _moe_out_jit(x, u, r, lp, ct=ct), {"sel": sel, "own": own,
                                              "short": short}


def forward(params, model, ids, true_len=None, fault=None, given=None):
    """ids int[T] -> {"hidden": the last layer's output [T, H] (before
    norm_f), "states": per Mamba layer h after token true_len - 1,
    "routes": per LatentMoE layer what `moe_layer` says of its routing}.
    ``model`` holds the configuration file's public keys.  ``given``: per
    LatentMoE layer int[n, k], the selections to route the first n tokens
    by (the system's: a near-tie at rank k falls either way at the system's
    precision, and a reference that went its own way there would hold the
    system to the fall of a coin and not to its arithmetic)."""
    fault = fault or {}
    ct = jnp.dtype(fault.get("dtype", "float32"))
    embed, blocks, _ = params
    ids = jnp.asarray(ids)
    true_len = len(ids) if true_len is None else true_len
    eps = float(model["layer_norm_epsilon"])
    kinds = layer_kinds(model)
    states, routes = [], []
    precision = "highest" if ct == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        x = embed["tok"][ids].astype(ct)
        for i in fault.get("layer_order") or range(len(kinds)):
            kind, j = kinds[i]
            lp = {name: leaf[j] for name, leaf in blocks[kind].items()}
            if kind == "mamba":
                x, h = _mamba_jit(
                    x, lp, jnp.asarray(true_len, jnp.int32),
                    heads=int(model["mamba_num_heads"]),
                    head_dim=int(model["mamba_head_dim"]),
                    groups=int(model["n_groups"]),
                    state=int(model["ssm_state_size"]), eps=eps,
                    drop_d=bool(fault.get("drop_d")),
                    drop_conv_bias=bool(fault.get("drop_conv_bias")), ct=ct)
                states.append(h)
            elif kind == "attn":
                x = _attn_jit(x, lp, heads=int(model["num_attention_heads"]),
                              kv_heads=int(model["num_key_value_heads"]),
                              head_dim=int(model["head_dim"]), eps=eps, ct=ct)
            else:
                x, route = moe_layer(
                    x, lp, model, fault, ct,
                    None if given is None else given[len(routes)])
                routes.append(route)
    return {"hidden": x, "states": states, "routes": routes}


def logits_at(params, model, hidden, positions, fault=None):
    ct = jnp.dtype((fault or {}).get("dtype", "float32"))
    precision = "highest" if ct == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        return np.asarray(_head(hidden[jnp.asarray(positions)], params[2],
                                eps=float(model["layer_norm_epsilon"]),
                                ct=ct))


def check_generation(params, model, prompt, generated, selections,
                     pad_to=None, fault=None):
    """What the reference says of one greedy generation, ROUTED BY THE
    SYSTEM'S SELECTIONS (``selections``: per LatentMoE layer int[>= consumed,
    k]): ``gaps`` — for each generated token the reference's maximum logit
    at its position minus the reference logit of the token the system chose
    (>= 0); ``states`` — per Mamba layer the state after the last token the
    system CONSUMED (prompt + generated[:-1]; the last token was sampled and
    never fed); ``pairs`` — the consumed tokens' (token, selected expert)
    pairs; ``strays`` — those of them outside the reference's own top-k;
    ``short`` — the furthest a selected expert's biased score lay below the
    reference's k-th best.  The whole sequence is recomputed; ``pad_to``
    pads it at the END (which a causal model cannot see, and which the
    state does not take in) so that prompts share one compile."""
    t, n = len(prompt), len(generated)
    ids = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(generated, np.int32)])
    consumed = t + n - 1
    if pad_to is not None:
        ids = np.concatenate([ids, np.zeros(pad_to - len(ids), np.int32)])
    given = [np.asarray(s)[:consumed] for s in selections]
    out = forward(params, model, ids, true_len=consumed, fault=fault,
                  given=given)
    logits = logits_at(params, model, out["hidden"],
                       np.arange(t - 1, t - 1 + n), fault)
    chosen = logits[np.arange(n), np.asarray(generated)]
    strays = sum(int((np.asarray(r["sel"])[:consumed, :, None] != np.asarray(
        r["own"])[:consumed, None, :]).all(-1).sum()) for r in out["routes"])
    return {"gaps": (logits.max(-1) - chosen).tolist(),
            "states": [np.asarray(h, np.float32) for h in out["states"]],
            "pairs": sum(g.size for g in given), "strays": strays,
            "short": max(float(np.asarray(r["short"])[:consumed].max())
                         for r in out["routes"])}


def bfloat16_share(state):
    """The share of a float32 state's elements that a bfloat16 holds exactly
    (their low 16 bits are zero): ~2^-16 of a state computed and kept in
    float32, all of one that was rounded to bfloat16 on its way (and 1.0 of
    a state that is not float32 at all)."""
    state = np.asarray(state)
    if state.dtype != np.float32:
        return 1.0
    bits = state.view(np.uint32)
    return float(((bits & 0xFFFF) == 0).mean())


def state_errors(got, want):
    """Per Mamba layer ||got - want|| / ||want|| over the whole state."""
    return [float(np.linalg.norm(np.asarray(g, np.float32) - w)
                  / max(np.linalg.norm(w), 1e-30))
            for g, w in zip(got, want)]
