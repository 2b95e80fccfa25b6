"""The plain reference of a latent-attention + sparse-expert decoder
(``model_type: deepseek_v3``, as Kimi-VL-A3B-Instruct's language model
publishes it): straightforward ``jax.numpy``, float32 under
``jax.default_matmul_precision("highest")``.  No cache, no pages, no kernel,
no absorbed products; nothing is imported from the program.

Every layer, x [T, H] at positions 0 .. T-1 (eps ``rms_norm_eps``):

  h = RMSNorm(x; norm)
  q = h W_q -> [heads, dn + dr];  q = [q_nope | RoPE(q_rope)]
  a = h W_kva -> [dl + dr];  c = RMSNorm(a[:dl]; kv_norm);  r = RoPE(a[dl:])
  kv = c W_kvb -> [heads, dn + dv];  k_i = [kv_i[:dn] | r] (ONE rotary key
       for every head);  v_i = kv_i[dn:]
  o_i = softmax_causal(q_i . k_j / sqrt(dn + dr)) v — the EXPANDED form only,
       per-head K and V from c, full causal attention, a block of queries at
       a time;  x = x + concat_i(o_i) W_o
  g = RMSNorm(x; post_norm)
  layers < first_k_dense_replace:  x = x + W_down (silu(W_gate g) * W_up g)
  the others:  s = sigmoid(g W_r); sel = top-k of s + b;
       w_e = routed_scaling_factor * s_e / sum_{sel} s;
       x = x + sum_{e in sel, held} w_e SwiGLU_e(g) + SwiGLU_shared(g):
       every HELD expert is computed for every token and masked by the
       selection, a block of experts at a time.
then the final RMSNorm and the head.

Departures from the published model, each also in the configuration file:
  - the chip's SHARE: the router scores all ``published.n_routed_experts``
    experts and takes its published top-k; the weights hold experts
    ``expert_offset .. expert_offset + n_routed_experts`` and only their part
    is computed (the absent experts' part is left out and that partial
    result goes on to the next layer); the vocabulary is the slice the
    head's weights hold; the depth is the file's ``num_hidden_layers``;
  - the rotary pairing is the half-split one (column i pairs with column
    i + dr/2) where the published checkpoint interleaves: the two differ by
    a fixed permutation of W_q's and W_kva's rotary columns, which random
    weights do not see;
  - ``e_score_correction_bias`` is a float32 buffer from the seed, in the
    selection only;
  - no vision tower: image positions are ordinary positions;
  - weights are random from a seed.

It reads only the WEIGHTS the system was given, ``(embed, blocks, head)``:
``embed.tok [V, H]``; ``blocks[kind][leaf][j]`` the leaf of the kind's j-th
layer, matrices as [in, out]: ``attn`` (every layer): norm, wq, w_kva,
kv_norm, w_kvb [dl, heads x (dn + dv)], wo, post_norm; ``dense``: w_gate,
w_up, w_down; ``moe``: router [H, E_all], router_bias [E_all], we_gate /
we_up [held, H, F], we_down [held, F, H], ws_gate, ws_up, ws_down;
``head``: ln_f, lm [H, V].

``fault`` hands the REFERENCE a deliberately wrong model, to show that the
checks can fail: ``{"dtype": "bfloat16"}`` (everything in bfloat16 at the
default matmul precision: the nearest precision below the stated one),
``{"drop_rope_key": True}`` (r without its rotation), ``{"drop_kv_norm":
True}``, ``{"softmax_scale": "nope"}`` (1 / sqrt(dn)), ``{"route_scale":
1.0}``, ``{"drop_shared": True}``, ``{"layer_order": [...]}``.

``given`` routes the reference by the SYSTEM'S selections: the check of a
system in a lower precision than this one compares arithmetic, not the fall
of near-ties at the router's rank k.
"""
import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

# Limits of the serving check, each beside its chip readings (PR 35, one TPU
# v5 lite, the 9-layer cut at published widths, a bfloat16 engine against
# this float32 reference ROUTED BY THE ENGINE'S SELECTIONS; 3 check prompts x
# 128 generated positions a seed; `tools/wrong_model_mla_moe.py`; PERF.md
# section 6 has the table).  "honest": the engine against the reference as it
# is; "bf16": against the reference recomputed in bfloat16 at the default
# matmul precision, the nearest precision below the stated one; "faults": the
# six planted structural faults.
#
# The worst, over the generated positions, of the reference's maximum logit
# minus the reference logit of the engine's token: 0 where both agree on the
# argmax, otherwise as far apart as the two best logits may lie for bfloat16
# arithmetic to exchange them.  Honest 0.031-0.047, bf16 0.031-0.063 (a
# maximum over 384 positions: it does not tell the two apart), faults
# 1.41-5.36.
SERVE_LOGIT_DELTA = 0.25
# The root mean square, over the generated positions, of the engine's
# log-probability of its greedy token minus the reference's of the same
# token: the whole forward pass's arithmetic in one number a position, steady
# because it is a mean.  THE limit that holds precision: honest 0.0119-0.0138
# over nine seeds, bf16 0.0158-0.0182 on every seed tried (1.32-1.37 x the
# honest reading of the SAME seed: the engine's error and a bfloat16
# recomputation's are about equal and add in squares), faults 0.41-2.77.
SERVE_LOGP_RMS = 0.015
# The share of the engine's selections outside the reference's own top-k on
# the same hidden states (honest 0.0102-0.0114, bf16 0.0119-0.0131: rank 6
# of 64 has too few near-ties for a precision to show; faults 0.19-0.72),
# and how far below the reference's k-th best biased score the worst of them
# lay (honest 0.008-0.011, bf16 0.012-0.013, faults 0.34-0.81).
SERVE_STRAY_SHARE = 0.02
SERVE_STRAY_SHORT = 0.03

EXPERT_BLOCK = 4
QUERY_BLOCK = 1024


def _rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, -1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def _rope(x, theta):
    """x [T, ..., dr] at positions 0 .. T-1, half-split pairs."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = jnp.arange(x.shape[0], dtype=jnp.float32)[:, None] * inv
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    sin, cos = jnp.sin(ang), jnp.cos(ang)
    x1 = x[..., :half].astype(jnp.float32)
    x2 = x[..., half:].astype(jnp.float32)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


def _swiglu(g, w_gate, w_up, w_down):
    return (jax.nn.silu(g @ w_gate) * (g @ w_up)) @ w_down


def _attention(x, lp, *, heads, dn, dr, dv, dl, eps, theta, drop_rope_key,
               drop_kv_norm, scale, ct):
    """x [T, H] -> x + the expanded attention's output."""
    lp = {k: v.astype(ct) for k, v in lp.items()}
    t = x.shape[0]
    h = _rms(x, lp["norm"], eps)
    q = (h @ lp["wq"]).reshape(t, heads, dn + dr)
    q = jnp.concatenate([q[..., :dn], _rope(q[..., dn:], theta)], -1)
    a = h @ lp["w_kva"]
    c = a[:, :dl] if drop_kv_norm else _rms(a[:, :dl], lp["kv_norm"], eps)
    r = a[:, dl:] if drop_rope_key else _rope(a[:, dl:], theta)
    kv = (c @ lp["w_kvb"]).reshape(t, heads, dn + dv)
    k = jnp.concatenate(
        [kv[..., :dn], jnp.broadcast_to(r[:, None], (t, heads, dr))], -1)
    v = kv[..., dn:]
    out = []
    for lo in range(0, t, QUERY_BLOCK):
        qb = q[lo:lo + QUERY_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k).astype(jnp.float32) * scale
        ok = jnp.arange(t)[None, :] <= (lo + jnp.arange(qb.shape[0]))[:, None]
        p = jax.nn.softmax(jnp.where(ok[None], s, -jnp.inf), -1).astype(ct)
        out.append(jnp.einsum("hqk,khd->qhd", p, v))
    o = jnp.concatenate(out).reshape(t, heads * dv)
    return x + o @ lp["wo"]


def _dense(x, lp, post_norm, *, eps, ct):
    lp = {k: v.astype(ct) for k, v in lp.items()}
    g = _rms(x, post_norm.astype(ct), eps)
    return x + _swiglu(g, lp["w_gate"], lp["w_up"], lp["w_down"])


def _route(x, post_norm, router, bias, given, n_given, *, top_k, scale, eps):
    """-> (g, the selections routed by [T, k], their weights, the
    reference's OWN top-k, how far below its k-th best biased score each
    token's worst routed selection lay)."""
    g = _rms(x, post_norm.astype(x.dtype), eps)
    s = jax.nn.sigmoid(g.astype(jnp.float32) @ router.astype(jnp.float32))
    biased = s + bias.astype(jnp.float32)
    kth, own = jax.lax.top_k(biased, top_k)
    use = jnp.arange(x.shape[0])[:, None] < n_given
    sel = jnp.where(use, given, own)
    w = jnp.take_along_axis(s, sel, 1)
    w = w / (w.sum(-1, keepdims=True) + 1e-20) * scale
    short = jnp.maximum(
        kth[:, -1] - jnp.take_along_axis(biased, sel, 1).min(-1), 0.0)
    return g, sel, w, own, short


def _expert_block(g, we_gate, we_up, we_down, mask):
    """sum over the block's experts of mask[t, e] * SwiGLU_e(g[t])."""
    ct = g.dtype
    y = jnp.einsum("th,ehf->tef", g, we_gate.astype(ct))
    u = jnp.einsum("th,ehf->tef", g, we_up.astype(ct))
    act = jax.nn.silu(y) * u * mask[..., None].astype(ct)
    return jnp.einsum("tef,efh->th", act, we_down.astype(ct))


def _moe_out(x, g, routed, lp, drop_shared):
    ct = x.dtype
    shared = 0 if drop_shared else _swiglu(
        g, lp["ws_gate"].astype(ct), lp["ws_up"].astype(ct),
        lp["ws_down"].astype(ct))
    return x + routed + shared


_attention_jit = jax.jit(_attention, static_argnames=(
    "heads", "dn", "dr", "dv", "dl", "eps", "theta", "drop_rope_key",
    "drop_kv_norm", "scale", "ct"))
_dense_jit = jax.jit(_dense, static_argnames=("eps", "ct"))
_route_jit = jax.jit(_route, static_argnames=("top_k", "scale", "eps"))
_block_jit = jax.jit(_expert_block)
_moe_out_jit = jax.jit(_moe_out, static_argnames=("drop_shared",))


def moe_layer(x, lp, post_norm, model, fault=None, given=None):
    """One expert layer's share over x [T, H] -> (x', {"sel": the
    selections it routed by [T, k], "own": its own top-k, "short": [T]}).
    ``given`` int[n, k], n <= T: route the first n tokens by these."""
    fault = fault or {}
    offset = int(model.get("expert_offset", 0))
    held = lp["we_gate"].shape[0]
    top_k = int(model["num_experts_per_tok"])
    n_given = 0 if given is None else len(given)
    full = np.zeros((x.shape[0], top_k), np.int32)
    full[:n_given] = 0 if given is None else given
    g, sel, w, own, short = _route_jit(
        x, post_norm, lp["router"], lp["router_bias"], full,
        jnp.asarray(n_given, jnp.int32), top_k=top_k,
        scale=float(fault.get("route_scale", model["routed_scaling_factor"])),
        eps=float(model["rms_norm_eps"]))
    routed = jnp.zeros_like(x)
    for lo in range(0, held, EXPERT_BLOCK):
        ids = offset + jnp.arange(lo, min(lo + EXPERT_BLOCK, held))
        mask = jnp.where(sel[:, :, None] == ids[None, None, :],
                         w[:, :, None], 0.0).sum(1)
        routed = routed + _block_jit(
            g, lp["we_gate"][lo:lo + EXPERT_BLOCK],
            lp["we_up"][lo:lo + EXPERT_BLOCK],
            lp["we_down"][lo:lo + EXPERT_BLOCK], mask)
    return _moe_out_jit(x, g, routed, lp, bool(fault.get("drop_shared"))), \
        {"sel": sel, "own": own, "short": short}


def forward(params, model, ids, fault=None, given=None):
    """ids int[T] -> {"hidden": the last layer's output [T, H] (before the
    final norm), "routes": per expert layer what `moe_layer` says of its
    routing}.  ``model`` holds the configuration file's public keys.
    ``given``: per expert layer int[n, k], the selections to route the first
    n tokens by."""
    fault = fault or {}
    ct = jnp.dtype(fault.get("dtype", "float32"))
    embed, blocks, _ = params
    dn, dr = int(model["qk_nope_head_dim"]), int(model["qk_rope_head_dim"])
    n_dense = int(model["first_k_dense_replace"])
    scale = 1.0 / math.sqrt(dn if fault.get("softmax_scale") == "nope"
                            else dn + dr)
    eps = float(model["rms_norm_eps"])
    routes = []
    precision = "highest" if ct == jnp.float32 else "default"
    with jax.default_matmul_precision(precision):
        x = embed["tok"][jnp.asarray(ids)].astype(ct)
        for i in fault.get("layer_order") or \
                range(int(model["num_hidden_layers"])):
            lp = {name: leaf[i] for name, leaf in blocks["attn"].items()}
            post_norm = lp.pop("post_norm")
            x = _attention_jit(
                x, lp, heads=int(model["num_attention_heads"]), dn=dn, dr=dr,
                dv=int(model["v_head_dim"]), dl=int(model["kv_lora_rank"]),
                eps=eps, theta=float(model["rope_theta"]),
                drop_rope_key=bool(fault.get("drop_rope_key")),
                drop_kv_norm=bool(fault.get("drop_kv_norm")), scale=scale,
                ct=ct)
            if i < n_dense:
                x = _dense_jit(x, {name: leaf[i] for name, leaf
                                   in blocks["dense"].items()}, post_norm,
                               eps=eps, ct=ct)
            else:
                j = i - n_dense
                x, route = moe_layer(
                    x, {name: leaf[j] for name, leaf
                        in blocks["moe"].items()}, post_norm, model, fault,
                    None if given is None else given[j])
                routes.append((j, route))
    # by the expert layer's own index, whatever order the layers ran in
    return {"hidden": x, "routes": [r for _, r in sorted(
        routes, key=lambda jr: jr[0])]}


def logits_at(params, model, hidden, positions, fault=None):
    ct = jnp.dtype((fault or {}).get("dtype", "float32"))
    precision = "highest" if ct == jnp.float32 else "default"
    hp = params[2]
    with jax.default_matmul_precision(precision):
        h = _rms(hidden[jnp.asarray(positions)].astype(ct),
                 hp["ln_f"].astype(ct), float(model["rms_norm_eps"]))
        return np.asarray((h @ hp["lm"].astype(ct)).astype(jnp.float32))


def check_generation(params, model, prompt, generated, selections, logp,
                     pad_to=None, fault=None):
    """What the reference says of one greedy generation, ROUTED BY THE
    SYSTEM'S SELECTIONS (``selections``: per expert layer int[>= consumed,
    k]): ``gaps`` — for each generated token the reference's maximum logit
    at its position minus the reference logit of the token the system chose
    (>= 0); ``logp_err`` — the system's log-probability of each generated
    token (``logp``: float[>= consumed], by consumed position) minus the
    reference's; ``pairs`` — the consumed tokens' (token, selected expert)
    pairs; ``strays`` — those of them outside the reference's own top-k;
    ``short`` — the furthest a selected expert's biased score lay below the
    reference's k-th best.  The whole sequence is recomputed (prompt +
    generated[:-1]: the last token was sampled and never fed); ``pad_to``
    pads it at the END, which a causal model cannot see, so that prompts
    share one compile."""
    t, n = len(prompt), len(generated)
    ids = np.concatenate([np.asarray(prompt, np.int32),
                          np.asarray(generated, np.int32)])
    consumed = t + n - 1
    if pad_to is not None:
        ids = np.concatenate([ids, np.zeros(pad_to - len(ids), np.int32)])
    given = [np.asarray(s)[:consumed] for s in selections]
    out = forward(params, model, ids, fault=fault, given=given)
    at = np.arange(t - 1, t - 1 + n)
    logits = logits_at(params, model, out["hidden"], at, fault)
    chosen = logits[np.arange(n), np.asarray(generated)]
    top = logits.max(-1)
    want_logp = chosen - top - np.log(np.exp(logits - top[:, None]).sum(-1))
    strays = sum(int((np.asarray(r["sel"])[:consumed, :, None] != np.asarray(
        r["own"])[:consumed, None, :]).all(-1).sum()) for r in out["routes"])
    return {"gaps": (top - chosen).tolist(),
            "logp_err": (np.asarray(logp, np.float32)[at]
                         - want_logp).tolist(),
            "pairs": sum(g.size for g in given), "strays": strays,
            "short": max([float(np.asarray(r["short"])[:consumed].max())
                          for r in out["routes"]] or [0.0])}
