"""Nemotron-H (``model_type: nemotron_h``: NVIDIA Nemotron-3) for the serving
engine: a hybrid decoder whose layers are of THREE kinds, one mixer a layer,
``x = x + Mixer_i(RMSNorm_i(x))``, the kind given by
``hybrid_override_pattern`` (``M`` Mamba-2, ``*`` attention, ``E``
LatentMoE); then ``norm_f`` and the untied head.

  M  [z | xBC | dt] = u W_in; xBC = silu(causal_conv1d(xBC) + b) (kernel 4);
     x, B, C = split(xBC); dt = softplus(dt + dt_bias), A = -exp(A_log);
     h_t = exp(dt_t A) h_{t-1} + dt_t x_t (x) B_t; y_t = h_t C_t + D x_t
     (`ops/ssm.py`: a chunked scan for a run of tokens, a one-token update
     for decode); y = GroupRMSNorm(y * silu(z)) * w_norm; out = y W_out.
  *  GQA, no bias, NO rotary embedding (the Mamba layers carry order),
     causal over all keys: the ragged paged-attention kernel over the page
     pool, for a run of tokens and for decode alike.
  E  s = sigmoid(u W_r) in float32; top-k of s + b; w = s[sel] / sum * scale;
     l = u W_1 (the latent); r = sum_e w_e relu(l W_e^up)^2 W_e^down over the
     HELD experts (`incubate/distributed/models/moe/dropless.py`: route,
     sort, grouped product, combine; nothing dropped); out = r W_2 +
     relu(u W_s^up)^2 W_s^down.

An expert-parallel rank's share, as `models/afmoe.py` has it:
``experts_held=(offset, count)`` — the router keeps all ``n_routed_experts``
outputs and the published top-k, this rank holds ``count`` experts and adds
their part of r; the latent projections and the shared expert are whole.  A
sliced vocabulary is a smaller ``vocab_size``.

Weights are grouped BY KIND (``blocks["mamba" | "attn" | "moe"][leaf]`` is a
tuple, one array a layer of that kind), none padded to another kind's shape.
A layer's leaf is its OWN buffer, not a slice of a stack: the layers are
unrolled with static indices, and the TPU compiler copies a static slice of
a stacked operand out before a kernel (a grouped product, a matmul) may read
it — 672 MB an expert matrix, every call (the described-v5e compile of
PR 33).  The multi-token-prediction head is not built (the base model's
logits do not depend on it).

The cache of the paged fns (`build_nemotron_h_paged`) holds two kinds of
state in ONE pytree: KV pages for the attention layers
(``k`` / ``v [La, Hkv, NP+1, ps, D]``, the page axis where Llama's is) and,
per Mamba layer and engine SLOT, a convolution tail ``conv [Lm, slots, K-1,
conv_dim]`` and an SSM state ``ssm`` (float32) that does not grow with the
context: a tuple of ``Lm`` arrays ``[slots, heads, P, N]``, a layer's state
its OWN buffer like a layer's weights — a decode step then reads it once
(`init_cache`); beside them ``sel [Le, slots, k, ctx]``, the
experts every consumed token of the slot's sequence selected in each
LatentMoE layer (what a rollout pool replays the routing of in training, and
what the benchmark routes its reference by), and ``ctr``, the counters the
fns accumulate on the device (`ServingEngine.stats` fetches them).
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
    dropless_expert_forward, grouped_relu2, row_bounds, row_tier,
    sigmoid_topk_route)
from ..ops.ssm import ssd_chunked_scan, ssm_decode_update
from ..profiler import device_span
from .llama import scatter_kv_rows, scatter_kv_run
from .paged_family import PagedFamily, log_selections_run

__all__ = ["NemotronHConfig", "nemotron_h_config_tiny",
           "build_functional_nemotron_h", "build_nemotron_h_paged",
           "latent_moe", "layer_kinds"]

KINDS = {"M": "mamba", "*": "attn", "E": "moe"}
# the region (`profiler.device_span`) a layer of each kind runs under; what
# an inner function owns (`ssm.*`, `moe.route` ...) takes its own name
SPANS = {"mamba": "mamba.proj", "attn": "attn.proj", "moe": "moe.layer"}
DECODE, PREFILL = 0, 1          # the two halves of every per-phase counter
CARRY = 1 << 20                 # a counter's low word carries over at this


@dataclasses.dataclass
class NemotronHConfig:
    """The public ``config.json`` keys of a nemotron_h model, and two that
    say how it is held here (``experts_held``, ``ssm_state_dtype``)."""
    vocab_size: int = 131072
    hidden_size: int = 4096
    num_hidden_layers: int = 88
    hybrid_override_pattern: str = ""
    num_attention_heads: int = 32
    num_key_value_heads: int = 2
    head_dim: int = 128
    mamba_num_heads: int = 128
    mamba_head_dim: int = 64
    n_groups: int = 8
    ssm_state_size: int = 128
    conv_kernel: int = 4
    chunk_size: int = 128
    expand: int = 2
    n_routed_experts: int = 512
    num_experts_per_tok: int = 22
    moe_intermediate_size: int = 2688
    moe_latent_size: int = 1024
    moe_shared_expert_intermediate_size: int = 5376
    n_shared_experts: int = 1
    routed_scaling_factor: float = 5.0
    norm_topk_prob: bool = True
    n_group: int = 1
    topk_group: int = 1
    layer_norm_epsilon: float = 1e-5
    max_position_embeddings: int = 262144
    time_step_min: float = 0.001
    time_step_max: float = 0.1
    time_step_floor: float = 1e-4
    mlp_hidden_act: str = "relu2"
    mamba_hidden_act: str = "silu"
    use_conv_bias: bool = True
    mamba_proj_bias: bool = False
    attention_bias: bool = False
    mlp_bias: bool = False
    tie_word_embeddings: bool = False
    # (offset, count) of the routed experts held here; None: all of them
    experts_held: Optional[Tuple[int, int]] = None
    # the dtype the SSM state is KEPT in between calls.  NOT a deployment
    # option: the model states float32, and "bfloat16" is the planted fault
    # of `benchmark/tools/wrong_model_nemotron_h.py` (a control that the
    # benchmark's check must refuse)
    ssm_state_dtype: str = "float32"

    @property
    def d_inner(self):
        return self.mamba_num_heads * self.mamba_head_dim

    @property
    def conv_dim(self):
        return self.d_inner + 2 * self.n_groups * self.ssm_state_size

    def held(self):
        return self.experts_held or (0, self.n_routed_experts)

    def validate(self):
        if len(self.hybrid_override_pattern) != self.num_hidden_layers \
                or set(self.hybrid_override_pattern) - set(KINDS):
            raise ValueError(
                f"hybrid_override_pattern {self.hybrid_override_pattern!r} "
                f"does not describe {self.num_hidden_layers} layers of "
                f"kinds {sorted(KINDS)}")
        if self.mlp_hidden_act != "relu2" or self.mamba_hidden_act != "silu" \
                or self.n_group != 1 or self.topk_group != 1 \
                or self.n_shared_experts != 1 or self.tie_word_embeddings \
                or self.mamba_proj_bias or self.attention_bias \
                or self.mlp_bias or not self.use_conv_bias \
                or not self.norm_topk_prob:
            raise ValueError(
                "nemotron_h as built here has relu^2 experts, silu in the "
                "Mamba layers, a convolution bias and no other, one shared "
                "expert, normalised top-k weights, no group-limited routing "
                "and an untied head")
        if self.expand * self.hidden_size != self.d_inner \
                or self.mamba_num_heads % self.n_groups \
                or self.num_attention_heads % self.num_key_value_heads:
            raise ValueError("mamba_num_heads x mamba_head_dim must be "
                             "expand x hidden_size, and the groups must "
                             "divide the heads")
        offset, count = self.held()
        if not (0 <= offset and count >= 1
                and offset + count <= self.n_routed_experts):
            raise ValueError(f"experts_held {(offset, count)} outside the "
                             f"{self.n_routed_experts} experts")

    def paged_family(self, **build_kw) -> PagedFamily:
        """The seam `inference.paged.ServingEngine` builds its fns through."""
        return build_nemotron_h_paged(self, **build_kw)


def nemotron_h_config_tiny(**kw):
    """The CPU tests' size: every mechanism present, nothing at scale."""
    base = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5,
                hybrid_override_pattern="MEM*E", num_attention_heads=8,
                num_key_value_heads=2, head_dim=8, mamba_num_heads=8,
                mamba_head_dim=16, n_groups=2, ssm_state_size=16,
                chunk_size=8, n_routed_experts=16, num_experts_per_tok=4,
                moe_intermediate_size=48, moe_latent_size=32,
                moe_shared_expert_intermediate_size=96,
                max_position_embeddings=512)
    base.update(kw)
    return NemotronHConfig(**base)


def layer_kinds(config):
    """[(kind, index within its kind)] of the pattern's layers."""
    seen, out = {}, []
    for ch in config.hybrid_override_pattern:
        kind = KINDS[ch]
        out.append((kind, seen.get(kind, 0)))
        seen[kind] = seen.get(kind, 0) + 1
    return out


def kind_counts(config):
    """{kind: the layers of that kind in the pattern}."""
    return {kind: sum(1 for k, _ in layer_kinds(config) if k == kind)
            for kind in KINDS.values()}


def build_functional_nemotron_h(config: NemotronHConfig, key=None,
                                dtype=None):
    """(embed, blocks, head) from a seed: matrices normal / sqrt(fan_in)
    (0.02 for the embedding and the head), the router and its correction
    bias float32, the Mamba layers' ``dt_bias`` the inverse softplus of a
    log-uniform step in [time_step_min, time_step_max], ``A_log`` the log of
    uniform [1, 16], ``D`` ones, the convolution's bias normal x 0.1, norms
    ones.  Jit it (every leaf is drawn in float32 and cast in one fusion)."""
    c = config
    c.validate()
    d = jnp.dtype(dtype) if dtype is not None else jnp.float32
    key = key if key is not None else jax.random.PRNGKey(0)
    f32 = jnp.float32
    n = kind_counts(c)
    H, D = c.hidden_size, c.head_dim
    q_dim, kv_dim = c.num_attention_heads * D, c.num_key_value_heads * D
    d_in, conv, nh = c.d_inner, c.conv_dim, c.mamba_num_heads
    L, F, Fs = c.moe_latent_size, c.moe_intermediate_size, \
        c.moe_shared_expert_intermediate_size
    _, held = c.held()

    def init(k, shape, scale=None):
        scale = scale if scale is not None else 1.0 / math.sqrt(shape[-2])
        return (jax.random.normal(k, shape, f32) * scale).astype(d)

    def mamba_layer(k):
        ks = jax.random.split(k, 6)
        step = jnp.exp(jax.random.uniform(ks[2], (nh,), f32) * (
            math.log(c.time_step_max) - math.log(c.time_step_min))
            + math.log(c.time_step_min))
        step = jnp.maximum(step, c.time_step_floor)
        return {
            "norm": jnp.ones((H,), f32),
            "w_in": init(ks[0], (H, d_in + conv + nh)),
            "conv_w": init(ks[1], (c.conv_kernel, conv),
                           1.0 / math.sqrt(c.conv_kernel)),
            "conv_b": init(ks[3], (conv,), 0.1),
            "dt_bias": step + jnp.log(-jnp.expm1(-step)),
            "A_log": jnp.log(jax.random.uniform(ks[4], (nh,), f32, 1.0,
                                                16.0)),
            "D": jnp.ones((nh,), f32),
            "norm_w": jnp.ones((d_in,), f32),
            "w_out": init(ks[5], (d_in, H))}

    def attn_layer(k):
        ks = jax.random.split(k, 4)
        return {"norm": jnp.ones((H,), f32),
                "wq": init(ks[0], (H, q_dim)), "wk": init(ks[1], (H, kv_dim)),
                "wv": init(ks[2], (H, kv_dim)), "wo": init(ks[3], (q_dim, H))}

    def moe_layer(k):
        ks = jax.random.split(k, 8)
        return {
            "norm": jnp.ones((H,), f32),
            "router": jax.random.normal(
                ks[0], (H, c.n_routed_experts), f32) / math.sqrt(H),
            # a buffer (the published model balances load by moving it);
            # small enough to change selections, not to set them
            "router_bias": 0.01 * jax.random.normal(
                ks[1], (c.n_routed_experts,), f32),
            "w_lat_in": init(ks[2], (H, L)),
            "w_lat_out": init(ks[3], (L, H)),
            "we_up": init(ks[4], (held, L, F)),
            "we_down": init(ks[5], (held, F, L)),
            "ws_up": init(ks[6], (H, Fs)),
            "ws_down": init(ks[7], (Fs, H))}

    make = {"mamba": mamba_layer, "attn": attn_layer, "moe": moe_layer}
    k_layers, k_embed, k_head = jax.random.split(key, 3)
    layers = [make[kind](k) for (kind, _), k in zip(
        layer_kinds(c), jax.random.split(k_layers, c.num_hidden_layers))]
    blocks = {}
    for kind in n:
        mine = [lp for (kd, _), lp in zip(layer_kinds(c), layers)
                if kd == kind]
        if mine:
            blocks[kind] = {leaf: tuple(lp[leaf] for lp in mine)
                            for leaf in mine[0]}
    embed = {"tok": init(k_embed, (c.vocab_size, H), 0.02)}
    head = {"ln_f": jnp.ones((H,), f32),
            "lm": init(k_head, (H, c.vocab_size), 0.02)}
    return embed, blocks, head


def _rms(x, w, eps):
    from ..nn.functional.norm import rms_norm_ref
    return rms_norm_ref(x, w, eps)


def _relu2(x):
    return jnp.square(jax.nn.relu(x))


def latent_moe(config: NemotronHConfig, lp, x, valid, expert=grouped_relu2):
    """One LatentMoE mixer's share: x [T, H], ``lp`` the layer's leaves,
    valid bool [T] (a token that is padding, or a dead slot's, selects no
    expert), ``expert`` the grouped product of the held experts
    (`grouped_relu2`, with or without its kernel) -> (out [T, H]: the held
    experts' part of r through W_2, plus the shared expert; rows int32
    [held]; beyond int32, the held pairs past the grouped product's row
    bound (`dropless_expert_forward`); sel int32 [T, k], the selection over
    ALL experts, ``n_routed_experts`` where the token is not valid)."""
    c = config
    offset, _ = c.held()
    with device_span("moe.layer"):
        u = _rms(x, lp["norm"], c.layer_norm_epsilon)
        sel, w = sigmoid_topk_route(
            u.astype(jnp.float32), lp["router"], lp["router_bias"],
            c.num_experts_per_tok, c.routed_scaling_factor, True,
            precision=jax.lax.Precision.HIGHEST)
        sel = jnp.where(valid[:, None], sel, c.n_routed_experts)
        part, rows, beyond = dropless_expert_forward(
            u @ lp["w_lat_in"], sel, w, (lp["we_up"], lp["we_down"]), offset,
            c.n_routed_experts, expert=expert)
        with device_span("moe.shared"):
            shared = _relu2(u @ lp["ws_up"]) @ lp["ws_down"]
        return part @ lp["w_lat_out"] + shared, rows, beyond, sel


def build_nemotron_h_paged(config: NemotronHConfig, page_size: int = 16,
                           num_pages: int = 64, num_slots: int = 4,
                           max_pages_per_seq: Optional[int] = None,
                           dtype=None, attention_impl: str = "auto",
                           interpret: bool = False, kv_dtype=None, mesh=None,
                           mp_axis: str = "mp",
                           quantized_allreduce: bool = False) -> PagedFamily:
    """The paged fns of `models/paged_family.PagedFamily` for this family.

    A run of tokens (dense prefill, a prefill chunk) belongs to ONE slot:
    the Mamba layers take the slot's convolution tail and SSM state as they
    stand — zeros when the run starts at position 0, which is also how a
    slot is RESET on admission and recomputed after a preemption — and
    leave them after the run's last real token; the attention layers write
    the run's K/V a page an update and attend through the ragged kernel,
    the run cut into segments of ``chunk_size`` queries.  A decode step is
    one token for every slot; an inactive slot's state stays as it was.
    Padding tokens and inactive slots are routed to no expert.  Every
    consumed token's selections are written to the slot's row of ``sel`` at
    the token's position (``max_pages_per_seq`` pages of positions a slot;
    None: the whole pool's).

    ``kv_dtype`` and ``mesh`` are refused: the quantized page store and the
    tensor-parallel region are written for K/V pages alone.
    """
    from ..ops.pallas.grouped_matmul import tiles, weight_visits
    from ..ops.pallas.paged_attention import (ragged_paged_attention,
                                              ragged_paged_attention_ref)
    c = config
    c.validate()
    if kv_dtype is not None:
        raise NotImplementedError(
            "kv_dtype: a quantized store for a hybrid cache is missing "
            "(int8 pages beside float32 recurrent state; ROADMAP B5)")
    if mesh is not None:
        raise NotImplementedError(
            "mesh: sharding specs for this family's leaves and the experts' "
            "exchange are missing (ROADMAP B1)")
    d = jnp.dtype(dtype if dtype is not None else jnp.float32)
    f32 = jnp.float32
    state_dt = jnp.dtype(c.ssm_state_dtype)
    kinds = layer_kinds(c)
    n = kind_counts(c)
    H, D = c.hidden_size, c.head_dim
    nh, nkv = c.num_attention_heads, c.num_key_value_heads
    mh, P, G, N = c.mamba_num_heads, c.mamba_head_dim, c.n_groups, \
        c.ssm_state_size
    d_in, conv_dim, K = c.d_inner, c.conv_dim, c.conv_kernel
    eps = c.layer_norm_epsilon
    _, held = c.held()
    top_k = c.num_experts_per_tok
    log_k = -(-top_k // 8) * 8
    ctx = (max_pages_per_seq or num_pages) * page_size
    TRASH = num_pages
    if attention_impl == "auto":
        use_kernel = any(dev.platform == "tpu" for dev in jax.devices())
    else:
        use_kernel = attention_impl == "pallas"

    def init_cache():
        pool = (max(n["attn"], 1), nkv, num_pages + 1, page_size, D)
        m = max(n["mamba"], 1)
        return {
            "k": jnp.zeros(pool, d), "v": jnp.zeros(pool, d),
            "conv": jnp.zeros((m, num_slots, K - 1, conv_dim), d),
            # one leaf a Mamba layer: over a WHOLE leaf the TPU compiler
            # fuses a decode step's update and its `y = h C` into one pass
            # that writes the new state in place; over a slice of a stacked
            # leaf it updated in place and then read the state again for y
            # (805 MB a layer and step where 537 do: PERF.md section 6,
            # PR 36; tests/test_chip_compile.py holds the one pass)
            "ssm": tuple(jnp.zeros((num_slots, mh, P, N), state_dt)
                         for _ in range(m)),
            # positions on the minor axis (a k there would be padded to 128
            # lanes) and k, rounded up to whole sublane tiles, on the next:
            # with a ragged k the TPU lays the leaf out slots-minor, the
            # updates want it k-minor, and every executable copies the
            # whole log in and out (the described-v5e compile of PR 33)
            "sel": jnp.zeros((max(n["moe"], 1), num_slots, log_k, ctx),
                             jnp.int32),
            # every counter a (high, low) pair, low below `CARRY`: an int32
            # alone wraps within hours of decode at 64 slots
            "ctr": {"moe_pairs": jnp.zeros((2, 2), jnp.int32),
                    "moe_touched": jnp.zeros((2, 2), jnp.int32),
                    "moe_visits": jnp.zeros((2, 2), jnp.int32),
                    "moe_calls": jnp.zeros((2, 2), jnp.int32),
                    "moe_ratio": jnp.zeros((2, 2), f32),
                    "moe_dropped": jnp.zeros((2,), jnp.int32),
                    "ssm_resets": jnp.zeros((2,), jnp.int32),
                    "live_slot_steps": jnp.zeros((2,), jnp.int32)}}

    def _count(ctr, name, inc):
        """ctr[name] (high, low) + inc, with the carry."""
        high, low = ctr[name][0], ctr[name][1] + inc
        over = (low >= CARRY).astype(low.dtype)
        return {**ctr, name: jnp.stack([high + over, low - over * CARRY])}

    def _attend(q, cache, li, page_tables, q_start, q_len, kv_len, role):
        fn = ragged_paged_attention if use_kernel \
            else ragged_paged_attention_ref
        kw = dict(interpret=interpret, role=role) if use_kernel else {}
        return fn(q, cache["k"], cache["v"], page_tables, q_start, q_len,
                  kv_len, layer=li, **kw)

    def _mamba_in(lp, x):
        """-> (z, xBC before the convolution, dt before softplus)."""
        zxd = _rms(x, lp["norm"], eps) @ lp["w_in"]
        return zxd[..., :d_in], zxd[..., d_in:d_in + conv_dim], \
            zxd[..., d_in + conv_dim:]

    def _mamba_out(lp, y, xs, z):
        """y, xs [*tok, mh, P], z [*tok, d_in] -> the mixer's output."""
        tok = z.shape[:-1]
        y = y.astype(f32) + lp["D"][:, None] * xs.astype(f32)
        y = y.reshape(*tok, d_in) * jax.nn.silu(z.astype(f32))
        y = y.reshape(*tok, G, d_in // G)
        y = y * jax.lax.rsqrt(jnp.mean(y * y, -1, keepdims=True) + eps)
        y = (y.reshape(*tok, d_in) * lp["norm_w"]).astype(d)
        return y @ lp["w_out"]

    def _split(xbc):
        tok = xbc.shape[:-1]
        return (xbc[..., :d_in].reshape(*tok, mh, P),
                xbc[..., d_in:d_in + G * N].reshape(*tok, G, N),
                xbc[..., d_in + G * N:].reshape(*tok, G, N))

    def _moe(lp, x, valid, ctr, phase):
        """x [T, H]; valid bool [T] (padding and dead slots select no
        expert) -> (the mixer's output, ctr, sel [T, log_k])."""
        out, rows, beyond, sel = latent_moe(
            c, lp, x, valid, expert=functools.partial(
                grouped_relu2, kernel=use_kernel, interpret=interpret,
                role=("decode", "prefill")[phase]))
        pairs = rows.sum()
        at = lambda v: jnp.zeros((2,), v.dtype).at[phase].set(v)
        ctr = _count(ctr, "moe_pairs", at(pairs))
        ctr = _count(ctr, "moe_touched", at((rows > 0).sum(dtype=jnp.int32)))
        # the (row tile, group) visits of the grouped-matmul kernel under
        # the row bound the layer took; 0 where `ragged_dot` ran
        def visits(bound):
            t = tiles(bound, *lp["we_up"].shape[1:], held, d.itemsize)
            if use_kernel and t:   # static  # graftlint: disable=TRACE001
                return weight_visits(rows, bound, t[0])
            return jnp.int32(0)

        bounds = row_bounds(x.shape[0], top_k, held, c.n_routed_experts)
        ctr = _count(ctr, "moe_visits", at(jnp.stack(
            [visits(b) for b in bounds])[row_tier(bounds, rows)]))
        ctr = _count(ctr, "moe_calls", at(jnp.int32(1)))
        ctr = _count(ctr, "moe_ratio", at(
            rows.max().astype(f32) * held / jnp.maximum(pairs, 1)))
        return out, _count(ctr, "moe_dropped", beyond), \
            jnp.pad(sel, ((0, 0), (0, log_k - top_k)))

    def _layer_params(bp, kind, j):
        return {leaf: per_layer[j] for leaf, per_layer in bp[kind].items()}

    @device_span("head")
    def _head(hp, h_last):
        return (_rms(h_last, hp["ln_f"], eps) @ hp["lm"]).astype(f32)

    def _run(params, ids, start, length, page_row, slot, cache):
        """A run of C tokens of the sequence riding ``slot``, at positions
        start .. start + C - 1, the first ``length`` real -> (logits of the
        last real token, cache)."""
        ep, bp, hp = params
        C = ids.shape[1]
        x = ep["tok"][ids[0]].astype(d)
        real = jnp.arange(C) < length
        fresh = start == 0
        # the attention's query segments: chunk_size queries each
        seg = c.chunk_size if C % c.chunk_size == 0 else C
        nseg = C // seg
        seg_off = jnp.arange(nseg, dtype=jnp.int32) * seg
        seg_start = start.astype(jnp.int32) + seg_off
        seg_len = jnp.clip(length.astype(jnp.int32) - seg_off, 0, seg)
        tables = jnp.broadcast_to(page_row[None], (nseg,) + page_row.shape)
        cache = dict(cache)
        ssm = list(cache["ssm"])
        ctr = _count(cache["ctr"], "ssm_resets", fresh.astype(jnp.int32))
        for kind, j in kinds:
            lp = _layer_params(bp, kind, j)
            with device_span(SPANS[kind]):
                if kind == "mamba":
                    z, xbc, dt = _mamba_in(lp, x)
                    tail = jnp.where(fresh, 0, cache["conv"][j, slot])
                    window = jnp.concatenate([tail.astype(d), xbc])
                    # the last K-1 REAL inputs: the next run's (or
                    # decode's) tail
                    cache["conv"] = cache["conv"].at[j, slot].set(
                        jax.lax.dynamic_slice_in_dim(window, length, K - 1))
                    xbc = jax.nn.silu(
                        sum(window[i:i + C] * lp["conv_w"][i]
                            for i in range(K)) + lp["conv_b"])
                    xs, b, cc = _split(xbc)
                    dt = jnp.where(real[:, None], jax.nn.softplus(
                        dt.astype(f32) + lp["dt_bias"]), 0.0)
                    h0 = jnp.where(fresh, 0, ssm[j][slot].astype(f32))
                    y, h = ssd_chunked_scan(xs, dt, -jnp.exp(lp["A_log"]), b,
                                            cc, h0, chunk=c.chunk_size)
                    ssm[j] = ssm[j].at[slot].set(h.astype(state_dt))
                    x = x + _mamba_out(lp, y, xs, z)
                elif kind == "attn":
                    u = _rms(x, lp["norm"], eps)
                    q = (u @ lp["wq"]).reshape(C, nh, D)
                    k = (u @ lp["wk"]).reshape(C, nkv, D)
                    v = (u @ lp["wv"]).reshape(C, nkv, D)
                    cache["k"] = scatter_kv_run(cache["k"], j, k, start,
                                                length, page_row)
                    cache["v"] = scatter_kv_run(cache["v"], j, v, start,
                                                length, page_row)
                    o = _attend(q.reshape(nseg, seg, nh, D), cache, j, tables,
                                seg_start, seg_len, seg_start + seg_len,
                                "chunk")
                    x = x + o.reshape(C, nh * D) @ lp["wo"]
                else:
                    out, ctr, sel = _moe(lp, x, real, ctr, PREFILL)
                    cache["sel"] = log_selections_run(cache["sel"], j, slot,
                                                      sel, start)
                    x = x + out
        cache["ssm"], cache["ctr"] = tuple(ssm), ctr
        h_last = jax.lax.dynamic_index_in_dim(x, length - 1, 0,
                                              keepdims=False)
        return _head(hp, h_last), cache

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
        x = ep["tok"][toks].astype(d)                 # [S, H]
        pos = jnp.where(active, lengths, 0)
        page = jnp.where(active, jnp.take_along_axis(
            page_tables, (pos // page_size)[:, None], 1)[:, 0], TRASH)
        off = pos % page_size
        eff_len = jnp.where(active, lengths + 1, 0)
        n_q = active.astype(jnp.int32)
        cache = dict(cache)
        ssm = list(cache["ssm"])
        ctr = _count(cache["ctr"], "live_slot_steps",
                     active.sum(dtype=jnp.int32))
        # a dead slot's selections fall past the row's end: dropped
        log_pos = jnp.where(active, lengths, ctx)
        slots = jnp.arange(toks.shape[0])
        for kind, j in kinds:
            lp = _layer_params(bp, kind, j)
            with device_span(SPANS[kind]):
                if kind == "mamba":
                    z, xbc, dt = _mamba_in(lp, x)
                    tail = cache["conv"][j]               # [S, K-1, conv]
                    window = jnp.concatenate([tail, xbc[:, None].astype(
                        tail.dtype)], axis=1)
                    cache["conv"] = cache["conv"].at[j].set(jnp.where(
                        active[:, None, None], window[:, 1:], tail))
                    xbc = jax.nn.silu(
                        (window.astype(d) * lp["conv_w"][None]).sum(1)
                        + lp["conv_b"])
                    xs, b, cc = _split(xbc)
                    dt = jnp.where(active[:, None], jax.nn.softplus(
                        dt.astype(f32) + lp["dt_bias"]), 0.0)
                    y, ssm[j] = ssm_decode_update(ssm[j], xs, dt,
                                                  -jnp.exp(lp["A_log"]), b, cc)
                    x = x + _mamba_out(lp, y, xs, z)
                elif kind == "attn":
                    u = _rms(x, lp["norm"], eps)
                    S = x.shape[0]
                    q = (u @ lp["wq"]).reshape(S, nh, D)
                    k = (u @ lp["wk"]).reshape(S, nkv, D)
                    v = (u @ lp["wv"]).reshape(S, nkv, D)
                    cache["k"] = scatter_kv_rows(cache["k"], j, k, page, off)
                    cache["v"] = scatter_kv_rows(cache["v"], j, v, page, off)
                    o = _attend(q[:, None], cache, j, page_tables, pos, n_q,
                                eff_len, "decode")[:, 0]
                    x = x + o.reshape(S, nh * D) @ lp["wo"]
                else:
                    out, ctr, sel = _moe(lp, x, active, ctr, DECODE)
                    cache["sel"] = cache["sel"].at[j, slots, :, log_pos].set(
                        sel, mode="drop")
                    x = x + out
        cache["ssm"], cache["ctr"] = tuple(ssm), ctr
        return _head(hp, x), cache

    state_bytes = num_slots * (
        n["mamba"] * mh * P * N * state_dt.itemsize
        + n["mamba"] * (K - 1) * conv_dim * d.itemsize)

    def counters(cache):
        """The device-side counters as host numbers (one small fetch)."""
        got = {name: pair[0].astype(object) * CARRY + pair[1].astype(object)
               for name, pair in jax.device_get(cache["ctr"]).items()}
        calls = got["moe_calls"]
        per_slot = state_bytes // num_slots
        return {
            "moe_pairs_held": int(got["moe_pairs"].sum()),
            "moe_experts_touched_decode": int(got["moe_touched"][DECODE]),
            "moe_experts_touched_prefill": int(got["moe_touched"][PREFILL]),
            "moe_gmm_weight_visits_decode": int(got["moe_visits"][DECODE]),
            "moe_gmm_weight_visits_prefill": int(got["moe_visits"][PREFILL]),
            "moe_expert_layer_calls_decode": int(calls[DECODE]),
            "moe_expert_layer_calls_prefill": int(calls[PREFILL]),
            "moe_experts_held": held,
            "moe_rows_dropped": int(got["moe_dropped"]),
            "moe.load_max_over_mean": float(
                got["moe_ratio"].sum() / max(int(calls.sum()), 1)),
            "moe.load_ratio_sum": float(got["moe_ratio"].sum()),
            "ssm_state_bytes": state_bytes,
            "ssm_slot_resets": int(got["ssm_resets"]),
            # a live slot's state is read once and written once a step
            "decode_state_bytes_moved":
                2 * per_slot * int(got["live_slot_steps"]),
        }

    def slot_state(cache, slot):
        """The slot's recurrent state, and ``moe_sel [Le, positions, k]``:
        the selections of the positions the slot's sequence consumed (the
        caller knows how many; the rest is an earlier sequence's)."""
        return {"ssm": np.stack([np.asarray(h[slot]) for h in cache["ssm"]]),
                "conv": np.asarray(cache["conv"][:, slot]),
                "moe_sel": np.asarray(cache["sel"][:, slot, :top_k])
                .swapaxes(1, 2)}

    return PagedFamily(name="nemotron_h", init_cache=init_cache,
                       prefill=prefill, prefill_chunk=prefill_chunk,
                       decode_step=decode_step, page_leaves=("k", "v"),
                       verify_step=None, recurrent=True, counters=counters,
                       slot_state=slot_state)
