"""Multi-head attention (reference: timm/layers/attention.py:1-293).

TPU-first design: tokens are (B, N, C). Where `flash_attention_supported`
holds (a TPU backend, N <= 1024, no mask or a key-padding mask, no attention
dropout, the float32 softmax policy, whole 128-lane head columns, one device)
the core is the Pallas kernel pair of timm_tpu/kernels/flash_attention.py:
`Attention` hands it the qkv product's output as it is and gives its output
to the output product as it is, and `scaled_dot_product_attention` packs
(B, H, N, D) operands onto that layout. Everything else takes the plain
einsum + softmax path (`_sdpa`) or, above N = 1024, XLA's
`jax.nn.dot_product_attention`. Selection is at trace time, by what the
call can see — the reference's SDPA-vs-manual switch at attention.py:123-129.
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Union

import jax
import jax.numpy as jnp
from flax import nnx

from ..utils import tracing
from .config import softmax_with_policy, use_fused_attn
from .drop import Dropout, dropout_rng_key
from .weight_init import trunc_normal_, zeros_

__all__ = ['Attention', 'AttentionRope', 'maybe_add_mask', 'apply_rot_embed_cat']


def maybe_add_mask(scores, attn_mask=None):
    if attn_mask is None:
        return scores
    if attn_mask.dtype == jnp.bool_:
        neg = jnp.finfo(scores.dtype).min
        return jnp.where(attn_mask, scores, neg)
    return scores + attn_mask


def apply_rot_embed_cat(x, emb, half: bool = False):
    """Apply concatenated (sin, cos) rotary embedding to (..., N, D) tokens.

    half=False: interleaved layout — sin/cos repeat per channel pair and the
    rotation swaps within each pair ([-x1, x0, -x3, x2, ...]).
    half=True: half layout (DINOv3 / LLaMA style) — sin/cos tile across the
    two halves and the rotation swaps halves ([-x[D/2:], x[:D/2]]).
    (reference pos_embed_sincos.py:281-297)
    """
    sin_emb, cos_emb = jnp.split(emb, 2, axis=-1)
    if half:
        xa, xb = jnp.split(x, 2, axis=-1)
        rot = jnp.concatenate([-xb, xa], axis=-1)
    else:
        x1, x2 = jnp.split(x.reshape(*x.shape[:-1], -1, 2), 2, axis=-1)
        x1 = x1[..., 0]
        x2 = x2[..., 0]
        rot = jnp.stack([-x2, x1], axis=-1).reshape(x.shape)
    return x * cos_emb + rot * sin_emb


def _sdpa(q, k, v, attn_mask=None, dropout_p: float = 0.0, key=None, scale: Optional[float] = None,
          softmax_dtype=None):
    """Scaled dot-product attention on (B, H, N, D) tensors.

    Softmax internals follow the compute-precision policy (config.py):
    default is the historical fp32 upcast, bit-identical to the pre-policy
    code; `softmax_dtype` overrides per call.
    """
    scale = scale if scale is not None else q.shape[-1] ** -0.5
    q = q * scale
    attn = jnp.einsum('bhqd,bhkd->bhqk', q, k)
    attn = maybe_add_mask(attn, attn_mask)
    attn = softmax_with_policy(attn, axis=-1, dtype=softmax_dtype).astype(q.dtype)
    if dropout_p > 0.0 and key is not None:
        keep = jax.random.bernoulli(key, 1.0 - dropout_p, attn.shape)
        attn = jnp.where(keep, attn / (1.0 - dropout_p), 0.0)
    return jnp.einsum('bhqk,bhkd->bhqd', attn, v)


def scaled_dot_product_attention(
        q, k, v,
        attn_mask=None,
        dropout_p: float = 0.0,
        dropout_key=None,
        scale: Optional[float] = None,
        fused: Optional[bool] = None,
        softmax_dtype=None,
):
    """Dispatcher over (B, H, N, D) q/k/v. `fused=None` → config default;
    `softmax_dtype=None` → config policy (fp32 upcast by default)."""
    fused = use_fused_attn() if fused is None else fused
    if fused and dropout_p == 0.0:
        from ..kernels import flash_attention_supported, flash_attention
        if q.ndim == 4 and q.shape == k.shape == v.shape and q.dtype == k.dtype == v.dtype:
            B, H, N, D = q.shape
            if flash_attention_supported(B, N, H, D, attn_mask, softmax_dtype=softmax_dtype, itemsize=q.dtype.itemsize):
                return flash_attention(q, k, v, mask=attn_mask, scale=scale)
        # What the kernel pair does not take at image-model lengths (additive or
        # per-query masks, a softmax policy, heads that do not fill 128 lanes,
        # a mesh over several devices) stays on the plain einsum + softmax graph.
        if q.shape[-2] <= 1024:
            return _sdpa(q, k, v, attn_mask, 0.0, None, scale, softmax_dtype)
        # XLA's fused path: expects (B, N, H, D)
        mask = attn_mask
        if mask is not None and mask.dtype != jnp.bool_:
            return _sdpa(q, k, v, attn_mask, 0.0, None, scale, softmax_dtype)
        out = jax.nn.dot_product_attention(
            q.transpose(0, 2, 1, 3), k.transpose(0, 2, 1, 3), v.transpose(0, 2, 1, 3),
            mask=mask, scale=scale,
        )
        return out.transpose(0, 2, 1, 3)
    return _sdpa(q, k, v, attn_mask, dropout_p, dropout_key, scale, softmax_dtype)


class Attention(nnx.Module):
    """Standard MHSA with optional qk-norm (reference attention.py:26-146)."""

    def __init__(
            self,
            dim: int,
            num_heads: int = 8,
            qkv_bias: bool = False,
            qk_norm: bool = False,
            proj_bias: bool = True,
            attn_drop: float = 0.0,
            proj_drop: float = 0.0,
            norm_layer: Optional[Callable] = None,
            scale_norm: bool = False,
            softmax_dtype=None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        assert dim % num_heads == 0, 'dim should be divisible by num_heads'
        if qk_norm or scale_norm:
            assert norm_layer is not None, 'norm_layer must be provided if qk_norm or scale_norm is True'
        self.num_heads = num_heads
        self.head_dim = dim // num_heads
        self.scale = self.head_dim ** -0.5
        self.attn_drop_rate = attn_drop
        self.softmax_dtype = softmax_dtype  # per-instance policy override

        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs,
        )
        self.qkv = linear(dim, dim * 3, use_bias=qkv_bias)
        self.q_norm = norm_layer(self.head_dim, rngs=rngs) if qk_norm else None
        self.k_norm = norm_layer(self.head_dim, rngs=rngs) if qk_norm else None
        self.attn_drop = Dropout(attn_drop, rngs=rngs)
        self.norm = norm_layer(dim, rngs=rngs) if scale_norm else None
        self.proj = linear(dim, dim, use_bias=proj_bias)
        self.proj_drop = Dropout(proj_drop, rngs=rngs)

    def _heads(self, qkv):
        """(B, N, 3 * H * D) -> q, k, v as (B, H, N, D), q / k normed: the plain path's operands."""
        from ..parallel import shard_activation
        B, N, _ = qkv.shape
        qkv = qkv.reshape(B, N, 3, self.num_heads, self.head_dim)
        qkv = qkv.transpose(2, 0, 3, 1, 4)  # (3, B, H, N, D)
        # heads over 'model' matches the column-parallel qkv kernel split, so
        # scores/softmax/values never leave the owning tp shard
        q, k, v = (shard_activation(t, 'heads') for t in (qkv[0], qkv[1], qkv[2]))
        if self.q_norm is not None:
            q = self.q_norm(q)
        if self.k_norm is not None:
            k = self.k_norm(k)
        return q, k, v

    def _packed(self, qkv):
        """The qkv product's output as the kernel pair takes it, (B, N, 3 * H * D): as it is, or with the
        q / k norms applied on its (B, N, H, D) views (they act on the last axis)."""
        if self.q_norm is None and self.k_norm is None:
            return qkv
        B, N, _ = qkv.shape
        q, k, v = (qkv.reshape(B, N, 3, self.num_heads, self.head_dim)[:, :, i] for i in range(3))
        q = q if self.q_norm is None else self.q_norm(q)
        k = k if self.k_norm is None else self.k_norm(k)
        return jnp.stack([q.astype(v.dtype), k.astype(v.dtype), v], axis=2).reshape(qkv.shape)

    def __call__(self, x, attn_mask=None):
        from ..kernels import flash_attention_supported, packed_attention
        from ..parallel import shard_activation
        B, N, C = x.shape
        dropout_p = 0.0 if self.attn_drop.deterministic else self.attn_drop_rate
        with tracing.scope('img.attn.qkv'):
            qkv = self.qkv(x)
            # the kernel pair (kernels/flash_attention.py) where the call allows it: no head transpose before or
            # after the core and no scores in HBM; the two counters say which core a traced call took
            packed = flash_attention_supported(B, N, self.num_heads, self.head_dim, attn_mask, dropout_p=dropout_p,
                                               softmax_dtype=self.softmax_dtype, itemsize=qkv.dtype.itemsize)
            if packed:
                tracing.count('attention.fused_calls')
                qkv = self._packed(qkv)
            else:
                tracing.count('attention.plain_calls')
                q, k, v = self._heads(qkv)
        with tracing.scope('img.attn.core'):
            if packed:
                x = packed_attention(qkv, self.num_heads, attn_mask, self.scale)
            else:
                dropout_key = dropout_rng_key(self.attn_drop) if dropout_p > 0.0 else None
                x = scaled_dot_product_attention(
                    q, k, v, attn_mask=attn_mask, dropout_p=dropout_p, dropout_key=dropout_key, scale=self.scale,
                    softmax_dtype=self.softmax_dtype,
                )
        with tracing.scope('img.attn.proj'):
            if not packed:
                x = x.transpose(0, 2, 1, 3).reshape(B, N, C)
            x = shard_activation(x, 'hidden')
            if self.norm is not None:
                x = self.norm(x)
            x = self.proj(x)
            x = self.proj_drop(x)
        return x


class AttentionRope(nnx.Module):
    """MHSA accepting a rotary position embedding, with fused or unfused qkv,
    qk/scale norms, and interleaved or half rotation layout
    (reference attention.py:148-290)."""

    def __init__(
            self,
            dim: int,
            num_heads: int = 8,
            dim_out: Optional[int] = None,
            qkv_bias: bool = True,
            qkv_fused: bool = True,
            num_prefix_tokens: int = 1,
            attn_drop: float = 0.0,
            proj_drop: float = 0.0,
            attn_head_dim: Optional[int] = None,
            norm_layer: Optional[Callable] = None,
            qk_norm: bool = False,
            scale_norm: bool = False,
            proj_bias: bool = True,
            rotate_half: bool = False,
            softmax_dtype=None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        dim_out = dim_out or dim
        head_dim = attn_head_dim
        if head_dim is None:
            assert dim % num_heads == 0, 'dim should be divisible by num_heads'
            head_dim = dim // num_heads
        if scale_norm or qk_norm:
            assert norm_layer is not None, 'norm_layer must be provided if qk_norm or scale_norm is True'
        self.num_heads = num_heads
        self.head_dim = head_dim
        self.attn_dim = head_dim * num_heads
        self.scale = head_dim ** -0.5
        self.num_prefix_tokens = num_prefix_tokens
        self.rotate_half = rotate_half
        self.attn_drop_rate = attn_drop
        self.softmax_dtype = softmax_dtype  # per-instance policy override

        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs,
        )
        if qkv_fused:
            self.qkv = linear(dim, self.attn_dim * 3, use_bias=qkv_bias)
            self.q_proj = self.k_proj = self.v_proj = None
        else:
            self.qkv = None
            self.q_proj = linear(dim, self.attn_dim, use_bias=qkv_bias)
            self.k_proj = linear(dim, self.attn_dim, use_bias=qkv_bias)
            self.v_proj = linear(dim, self.attn_dim, use_bias=qkv_bias)
        self.q_norm = norm_layer(head_dim, rngs=rngs) if qk_norm else None
        self.k_norm = norm_layer(head_dim, rngs=rngs) if qk_norm else None
        self.attn_drop = Dropout(attn_drop, rngs=rngs)
        self.norm = norm_layer(self.attn_dim, rngs=rngs) if scale_norm else None
        self.proj = linear(self.attn_dim, dim_out, use_bias=proj_bias)
        self.proj_drop = Dropout(proj_drop, rngs=rngs)

    def __call__(self, x, rope=None, attn_mask=None):
        from ..parallel import shard_activation
        B, N, C = x.shape
        if self.qkv is not None:
            qkv = self.qkv(x).reshape(B, N, 3, self.num_heads, self.head_dim)
            qkv = qkv.transpose(2, 0, 3, 1, 4)
            q, k, v = qkv[0], qkv[1], qkv[2]
        else:
            q = self.q_proj(x).reshape(B, N, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
            k = self.k_proj(x).reshape(B, N, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
            v = self.v_proj(x).reshape(B, N, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        q, k, v = (shard_activation(t, 'heads') for t in (q, k, v))
        if self.q_norm is not None:
            q = self.q_norm(q)
        if self.k_norm is not None:
            k = self.k_norm(k)
        if rope is not None:
            # don't rotate prefix (cls/reg) tokens — rope covers trailing tokens
            npt = self.num_prefix_tokens
            if npt > 0:
                q = jnp.concatenate(
                    [q[..., :npt, :], apply_rot_embed_cat(q[..., npt:, :], rope, half=self.rotate_half)], axis=-2)
                k = jnp.concatenate(
                    [k[..., :npt, :], apply_rot_embed_cat(k[..., npt:, :], rope, half=self.rotate_half)], axis=-2)
            else:
                q = apply_rot_embed_cat(q, rope, half=self.rotate_half)
                k = apply_rot_embed_cat(k, rope, half=self.rotate_half)
            q = q.astype(v.dtype)
            k = k.astype(v.dtype)
        dropout_p = 0.0 if self.attn_drop.deterministic else self.attn_drop_rate
        dropout_key = dropout_rng_key(self.attn_drop) if dropout_p > 0.0 else None
        x = scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=dropout_p, dropout_key=dropout_key, scale=self.scale,
            softmax_dtype=self.softmax_dtype,
        )
        x = shard_activation(x.transpose(0, 2, 1, 3).reshape(B, N, self.attn_dim), 'hidden')
        if self.norm is not None:
            x = self.norm(x)
        x = self.proj(x)
        x = self.proj_drop(x)
        return x
