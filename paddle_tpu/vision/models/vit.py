"""Vision Transformer (ViT-L/16, data-parallel).

Reference ViT implementations live in PaddleClas; paddle.vision itself ships
the backbone zoo — we provide ViT here as one of the model families.
"""
from __future__ import annotations

import jax.numpy as jnp

from ...core.tensor import Parameter, Tensor
from ...nn.layer import Layer
from ...nn import (Linear, LayerNorm, Dropout, Conv2D, Sequential, GELU,
                   LayerList)
from ...nn import functional as F
from ...nn.initializer import TruncatedNormal, Constant
from ...tensor import manipulation as manip

__all__ = ["VisionTransformer", "vit_b_16", "vit_l_16"]


class PatchEmbed(Layer):
    def __init__(self, img_size=224, patch_size=16, in_chans=3, embed_dim=768):
        super().__init__()
        self.num_patches = (img_size // patch_size) ** 2
        self.proj = Conv2D(in_chans, embed_dim, patch_size, stride=patch_size)

    def forward(self, x):
        x = self.proj(x)  # [B, C, H/p, W/p]
        x = manip.flatten(x, 2)  # [B, C, N]
        return manip.transpose(x, [0, 2, 1])  # [B, N, C]


class MLP(Layer):
    def __init__(self, dim, hidden, drop=0.0):
        super().__init__()
        self.fc1 = Linear(dim, hidden)
        self.act = GELU()
        self.fc2 = Linear(hidden, dim)
        self.drop = Dropout(drop)

    def forward(self, x):
        return self.drop(self.fc2(self.drop(self.act(self.fc1(x)))))


class Attention(Layer):
    def __init__(self, dim, num_heads, attn_drop=0.0, proj_drop=0.0, qkv_bias=True):
        super().__init__()
        self.num_heads = num_heads
        self.head_dim = dim // num_heads
        self.qkv = Linear(dim, dim * 3, bias_attr=None if qkv_bias else False)
        self.proj = Linear(dim, dim)
        self.attn_drop = attn_drop
        self.proj_drop = Dropout(proj_drop)

    def forward(self, x):
        b, n, c = x.shape
        qkv = self.qkv(x)
        qkv = manip.reshape(qkv, [b, n, 3, self.num_heads, self.head_dim])
        q = qkv[:, :, 0]
        k = qkv[:, :, 1]
        v = qkv[:, :, 2]
        out = F.scaled_dot_product_attention(q, k, v, dropout_p=self.attn_drop,
                                             training=self.training)
        out = manip.reshape(out, [b, n, c])
        return self.proj_drop(self.proj(out))


class Block(Layer):
    def __init__(self, dim, num_heads, mlp_ratio=4.0, drop=0.0, attn_drop=0.0,
                 qkv_bias=True, epsilon=1e-6):
        super().__init__()
        self.norm1 = LayerNorm(dim, epsilon=epsilon)
        self.attn = Attention(dim, num_heads, attn_drop, drop, qkv_bias)
        self.norm2 = LayerNorm(dim, epsilon=epsilon)
        self.mlp = MLP(dim, int(dim * mlp_ratio), drop)

    def forward(self, x):
        x = x + self.attn(self.norm1(x))
        x = x + self.mlp(self.norm2(x))
        return x


class VisionTransformer(Layer):
    def __init__(self, img_size=224, patch_size=16, in_chans=3, num_classes=1000,
                 embed_dim=768, depth=12, num_heads=12, mlp_ratio=4.0,
                 qkv_bias=True, drop_rate=0.0, attn_drop_rate=0.0, epsilon=1e-6):
        super().__init__()
        self.patch_embed = PatchEmbed(img_size, patch_size, in_chans, embed_dim)
        n = self.patch_embed.num_patches
        self.cls_token = Parameter(jnp.zeros((1, 1, embed_dim), jnp.float32))
        self.pos_embed = Parameter(jnp.zeros((1, n + 1, embed_dim), jnp.float32))
        TruncatedNormal(std=0.02)(self.pos_embed)
        TruncatedNormal(std=0.02)(self.cls_token)
        self.pos_drop = Dropout(drop_rate)
        self.blocks = LayerList([
            Block(embed_dim, num_heads, mlp_ratio, drop_rate, attn_drop_rate,
                  qkv_bias, epsilon) for _ in range(depth)])
        self.norm = LayerNorm(embed_dim, epsilon=epsilon)
        self.head = Linear(embed_dim, num_classes) if num_classes > 0 else None

    def forward(self, x):
        x = self.patch_embed(x)
        b = x.shape[0]
        cls = manip.expand(self.cls_token, [b, 1, x.shape[2]])
        x = manip.concat([cls, x], axis=1)
        x = self.pos_drop(x + self.pos_embed)
        for blk in self.blocks:
            x = blk(x)
        x = self.norm(x)
        cls_out = x[:, 0]
        return self.head(cls_out) if self.head is not None else cls_out


def vit_b_16(**kwargs):
    return VisionTransformer(embed_dim=768, depth=12, num_heads=12, **kwargs)


def vit_l_16(**kwargs):
    return VisionTransformer(embed_dim=1024, depth=24, num_heads=16, **kwargs)
