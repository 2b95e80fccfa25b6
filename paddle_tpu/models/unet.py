"""Stable-Diffusion-style UNet (SD 1.5 UNet — conv + attention mixed
workload for the Pallas/conv kernels).

Compact latent-diffusion UNet following the SD 1.5 topology: sinusoidal
timestep embedding → MLP; down path of ResBlocks with self+cross attention
at the lower resolutions; middle ResBlock-attn-ResBlock; up path with skip
concatenation; GroupNorm(32)+SiLU throughout. Built from framework layers
only (Conv2D/GroupNorm/Linear/SDPA dispatch)."""
from __future__ import annotations

import math
from dataclasses import dataclass, field
from typing import List, Tuple

import jax.numpy as jnp

from ..nn.layer import Layer
from ..nn import Linear, Conv2D, GroupNorm, LayerNorm, LayerList
from ..nn import functional as F
from ..tensor import manipulation as manip

__all__ = ["UNetConfig", "UNet2DConditionModel", "unet_config_sd15",
           "unet_config_tiny", "timestep_embedding"]


@dataclass
class UNetConfig:
    in_channels: int = 4
    out_channels: int = 4
    block_channels: Tuple[int, ...] = (320, 640, 1280, 1280)
    layers_per_block: int = 2
    attn_levels: Tuple[int, ...] = (0, 1, 2)    # levels with attention
    num_heads: int = 8
    cross_attention_dim: int = 768
    norm_groups: int = 32
    time_embed_mult: int = 4


def unet_config_sd15():
    return UNetConfig()


def unet_config_tiny():
    return UNetConfig(in_channels=4, out_channels=4,
                      block_channels=(32, 64), layers_per_block=1,
                      attn_levels=(1,), num_heads=4, cross_attention_dim=32,
                      norm_groups=8)


def timestep_embedding(t, dim, max_period=10000.0):
    """Sinusoidal embedding [B] -> [B, dim] (SD convention)."""
    half = dim // 2
    freqs = jnp.exp(-math.log(max_period)
                    * jnp.arange(half, dtype=jnp.float32) / half)
    v = t._value if hasattr(t, "_value") else jnp.asarray(t)
    args = v.astype(jnp.float32)[:, None] * freqs[None]
    emb = jnp.concatenate([jnp.cos(args), jnp.sin(args)], axis=-1)
    import paddle_tpu as paddle
    return paddle.Tensor(emb)


class ResBlock(Layer):
    def __init__(self, c_in, c_out, t_dim, groups):
        super().__init__()
        self.norm1 = GroupNorm(min(groups, c_in), c_in)
        self.conv1 = Conv2D(c_in, c_out, 3, padding=1)
        self.time_proj = Linear(t_dim, c_out)
        self.norm2 = GroupNorm(min(groups, c_out), c_out)
        self.conv2 = Conv2D(c_out, c_out, 3, padding=1)
        self.skip = Conv2D(c_in, c_out, 1) if c_in != c_out else None

    def forward(self, x, temb):
        h = self.conv1(F.silu(self.norm1(x)))
        h = h + manip.reshape(self.time_proj(F.silu(temb)),
                              [temb.shape[0], -1, 1, 1])
        h = self.conv2(F.silu(self.norm2(h)))
        return h + (self.skip(x) if self.skip is not None else x)


class CrossAttention(Layer):
    def __init__(self, dim, ctx_dim, heads):
        super().__init__()
        self.heads = heads
        self.to_q = Linear(dim, dim, bias_attr=False)
        self.to_k = Linear(ctx_dim, dim, bias_attr=False)
        self.to_v = Linear(ctx_dim, dim, bias_attr=False)
        self.to_out = Linear(dim, dim)

    def forward(self, x, ctx=None):
        ctx = x if ctx is None else ctx
        b, n, _ = x.shape
        hd = x.shape[-1] // self.heads
        q = manip.reshape(self.to_q(x), [b, n, self.heads, hd])
        k = manip.reshape(self.to_k(ctx), [b, ctx.shape[1], self.heads, hd])
        v = manip.reshape(self.to_v(ctx), [b, ctx.shape[1], self.heads, hd])
        o = F.scaled_dot_product_attention(q, k, v, is_causal=False,
                                           training=self.training)
        return self.to_out(manip.reshape(o, [b, n, -1]))


class TransformerBlock(Layer):
    """Self-attn → cross-attn → geglu-ff over flattened spatial tokens."""

    def __init__(self, dim, ctx_dim, heads):
        super().__init__()
        self.norm1 = LayerNorm(dim)
        self.attn1 = CrossAttention(dim, dim, heads)
        self.norm2 = LayerNorm(dim)
        self.attn2 = CrossAttention(dim, ctx_dim, heads)
        self.norm3 = LayerNorm(dim)
        self.ff1 = Linear(dim, dim * 8)
        self.ff2 = Linear(dim * 4, dim)
        self.proj_in = Conv2D(dim, dim, 1)
        self.proj_out = Conv2D(dim, dim, 1)
        self.norm_in = GroupNorm(min(32, dim), dim)

    def forward(self, x, ctx):
        b, c, h, w = x.shape
        res = x
        t = self.proj_in(self.norm_in(x))
        t = manip.transpose(manip.reshape(t, [b, c, h * w]), [0, 2, 1])
        t = t + self.attn1(self.norm1(t))
        t = t + self.attn2(self.norm2(t), ctx)
        ff = self.ff1(self.norm3(t))
        gate = ff[:, :, ff.shape[-1] // 2:]
        ff = ff[:, :, : ff.shape[-1] // 2] * F.gelu(gate)
        t = t + self.ff2(ff)
        t = manip.reshape(manip.transpose(t, [0, 2, 1]), [b, c, h, w])
        return self.proj_out(t) + res


class UNet2DConditionModel(Layer):
    """The SD UNet: (latents [B,4,H,W], t [B], context [B,L,ctx]) -> eps."""

    def __init__(self, config: UNetConfig = None):
        super().__init__()
        c = config or unet_config_sd15()
        self.config = c
        ch = c.block_channels
        t_dim = ch[0] * c.time_embed_mult
        self.t_dim0 = ch[0]
        self.time_fc1 = Linear(ch[0], t_dim)
        self.time_fc2 = Linear(t_dim, t_dim)
        self.conv_in = Conv2D(c.in_channels, ch[0], 3, padding=1)

        self.down_res = LayerList()
        self.down_attn = LayerList()
        self.downsamplers = LayerList()
        cur = ch[0]
        self._skips_per_level = c.layers_per_block
        for lvl, cout in enumerate(ch):
            for i in range(c.layers_per_block):
                self.down_res.append(ResBlock(cur, cout, t_dim, c.norm_groups))
                self.down_attn.append(
                    TransformerBlock(cout, c.cross_attention_dim, c.num_heads)
                    if lvl in c.attn_levels else None)
                cur = cout
            if lvl < len(ch) - 1:
                self.downsamplers.append(Conv2D(cur, cur, 3, stride=2, padding=1))

        self.mid_res1 = ResBlock(cur, cur, t_dim, c.norm_groups)
        self.mid_attn = TransformerBlock(cur, c.cross_attention_dim, c.num_heads)
        self.mid_res2 = ResBlock(cur, cur, t_dim, c.norm_groups)

        self.up_res = LayerList()
        self.up_attn = LayerList()
        self.upsamplers = LayerList()
        skip_ch = []
        cc = ch[0]
        for lvl, cout in enumerate(ch):
            for _ in range(c.layers_per_block):
                skip_ch.append(cout)
        for lvl in reversed(range(len(ch))):
            cout = ch[lvl]
            for i in range(c.layers_per_block):
                s = skip_ch.pop()
                self.up_res.append(ResBlock(cur + s, cout, t_dim, c.norm_groups))
                self.up_attn.append(
                    TransformerBlock(cout, c.cross_attention_dim, c.num_heads)
                    if lvl in c.attn_levels else None)
                cur = cout
            if lvl > 0:
                self.upsamplers.append(Conv2D(cur, cur, 3, padding=1))

        self.norm_out = GroupNorm(min(c.norm_groups, cur), cur)
        self.conv_out = Conv2D(cur, c.out_channels, 3, padding=1)

    def forward(self, latents, timesteps, context):
        c = self.config
        # sinusoidal table is f32; follow the latents' compute dtype so the
        # time-projection adds don't promote the conv stream back to f32
        temb = timestep_embedding(timesteps, self.t_dim0).astype(latents.dtype)
        temb = self.time_fc2(F.silu(self.time_fc1(temb)))

        x = self.conv_in(latents)
        skips = []
        idx = 0
        ds = 0
        for lvl in range(len(c.block_channels)):
            for i in range(c.layers_per_block):
                x = self.down_res[idx](x, temb)
                if self.down_attn[idx] is not None:
                    x = self.down_attn[idx](x, context)
                skips.append(x)
                idx += 1
            if lvl < len(c.block_channels) - 1:
                x = self.downsamplers[ds](x)
                ds += 1

        x = self.mid_res1(x, temb)
        x = self.mid_attn(x, context)
        x = self.mid_res2(x, temb)

        idx = 0
        us = 0
        for lvl in reversed(range(len(c.block_channels))):
            for i in range(c.layers_per_block):
                skip = skips.pop()
                x = manip.concat([x, skip], axis=1)
                x = self.up_res[idx](x, temb)
                if self.up_attn[idx] is not None:
                    x = self.up_attn[idx](x, context)
                idx += 1
            if lvl > 0:
                x = F.interpolate(x, scale_factor=2, mode="nearest")
                x = self.upsamplers[us](x)
                us += 1

        return self.conv_out(F.silu(self.norm_out(x)))
