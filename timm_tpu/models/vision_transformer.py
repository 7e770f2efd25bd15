"""Vision Transformer, TPU-native.

Re-designed from the reference's VisionTransformer
(reference: timm/models/vision_transformer.py:711-1302) for JAX/XLA:
NLC tokens, explicit RNG streams, trace-time pos-embed resampling for
dynamic image sizes, rematerialised blocks for grad checkpointing.

Model contract parity (reference vision_transformer.py):
  forward_features / forward_head / __call__, get_classifier / reset_classifier,
  group_matcher, set_grad_checkpointing, forward_intermediates,
  prune_intermediate_layers, no_weight_decay, set_input_size.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import (
    Attention, AttentionPoolLatent, DropPath, Dropout, LayerNorm, LayerScale,
    Mlp, PatchDropout, PatchEmbed, RmsNorm, SwiGLU, SwiGLUPacked, calculate_drop_path_rates,
    get_act_fn, get_norm_layer, global_pool_nlc, maybe_add_mask,
    resample_abs_pos_embed, scaled_dot_product_attention, trunc_normal_, zeros_,
)
from ..layers.drop import apply_drop_path
from ..utils import tracing
from ._builder import build_model_with_cfg
from ._features import feature_take_indices
from ._manipulate import (
    BlockStackError, checkpoint_seq, drop_path_scan_inputs, resolve_block_scan,
    scan_block_stack, warn_scan_fallback,
)
from ._registry import generate_default_cfgs, register_model

__all__ = ['VisionTransformer', 'Block', 'ResPostBlock']


class Block(nnx.Module):
    """Pre-norm transformer block (reference vision_transformer.py:128-216)."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = False,
            qk_norm: bool = False,
            scale_attn_norm: bool = False,
            scale_mlp_norm: bool = False,
            proj_bias: bool = True,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            init_values: Optional[float] = None,
            drop_path: float = 0.0,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            mlp_layer: Callable = Mlp,
            attn_layer: Optional[Callable] = None,
            depth: int = 0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        attn_layer = attn_layer or Attention
        self.norm1 = norm_layer(dim, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.attn = attn_layer(
            dim,
            num_heads=num_heads,
            qkv_bias=qkv_bias,
            qk_norm=qk_norm,
            scale_norm=scale_attn_norm,
            proj_bias=proj_bias,
            attn_drop=attn_drop,
            proj_drop=proj_drop,
            norm_layer=norm_layer,
            dtype=dtype,
            param_dtype=param_dtype,
            rngs=rngs,
        )
        self.ls1 = LayerScale(dim, init_values=init_values, param_dtype=param_dtype, rngs=rngs) if init_values else None
        self.drop_path1 = DropPath(drop_path, rngs=rngs)
        self.norm2 = norm_layer(dim, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.mlp = mlp_layer(
            dim,
            hidden_features=int(dim * mlp_ratio),
            act_layer=act_layer,
            norm_layer=norm_layer if scale_mlp_norm else None,
            drop=proj_drop,
            bias=proj_bias,
            dtype=dtype,
            param_dtype=param_dtype,
            rngs=rngs,
        )
        self.ls2 = LayerScale(dim, init_values=init_values, param_dtype=param_dtype, rngs=rngs) if init_values else None
        self.drop_path2 = DropPath(drop_path, rngs=rngs)

    def __call__(self, x, attn_mask=None, drop_path_override=None):
        with tracing.scope('img.block'):
            y = self.attn(self.norm1(x), attn_mask=attn_mask)
            if self.ls1 is not None:
                y = self.ls1(y)
            x = x + apply_drop_path(y, self.drop_path1, drop_path_override, 0)
            y = self.mlp(self.norm2(x))
            if self.ls2 is not None:
                y = self.ls2(y)
            x = x + apply_drop_path(y, self.drop_path2, drop_path_override, 1)
        return x


class ResPostBlock(nnx.Module):
    """Post-norm residual block (reference vision_transformer.py:217-291)."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = False,
            qk_norm: bool = False,
            scale_attn_norm: bool = False,
            scale_mlp_norm: bool = False,
            proj_bias: bool = True,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            init_values: Optional[float] = None,
            drop_path: float = 0.0,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            mlp_layer: Callable = Mlp,
            attn_layer: Optional[Callable] = None,
            depth: int = 0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.init_values = init_values
        attn_cls = attn_layer or Attention
        self.attn = attn_cls(
            dim, num_heads=num_heads, qkv_bias=qkv_bias, qk_norm=qk_norm,
            scale_norm=scale_attn_norm, proj_bias=proj_bias,
            attn_drop=attn_drop, proj_drop=proj_drop, norm_layer=norm_layer,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs,
        )
        self.norm1 = norm_layer(dim, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.drop_path1 = DropPath(drop_path, rngs=rngs)
        self.mlp = mlp_layer(
            dim, hidden_features=int(dim * mlp_ratio), act_layer=act_layer,
            norm_layer=norm_layer if scale_mlp_norm else None, drop=proj_drop,
            bias=proj_bias, dtype=dtype, param_dtype=param_dtype, rngs=rngs,
        )
        self.norm2 = norm_layer(dim, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.drop_path2 = DropPath(drop_path, rngs=rngs)
        # reference init: scale norm weights by init_values when provided
        if init_values is not None:
            self.norm1.scale[...] = self.norm1.scale[...] * init_values
            self.norm2.scale[...] = self.norm2.scale[...] * init_values

    def __call__(self, x, attn_mask=None, drop_path_override=None):
        with tracing.scope('img.block'):
            x = x + apply_drop_path(
                self.norm1(self.attn(x, attn_mask=attn_mask)), self.drop_path1, drop_path_override, 0)
            x = x + apply_drop_path(
                self.norm2(self.mlp(x)), self.drop_path2, drop_path_override, 1)
        return x


class ParallelScalingBlock(nnx.Module):
    """ViT-22B-style parallel block: one fused input projection computes the
    qkv AND the MLP hidden activations from a single norm, and the attention /
    MLP branch outputs are summed into the residual
    (reference vision_transformer.py:292-421).

    TPU note: the fused in_proj is exactly the layout the MXU wants — one
    (N, C) x (C, 3C+H) matmul per block instead of two smaller ones.
    """

    def __init__(
            self,
            dim: int,
            num_heads: int,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = False,
            qk_norm: bool = False,
            scale_attn_norm: bool = False,
            scale_mlp_norm: bool = False,
            proj_bias: bool = True,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            init_values: Optional[float] = None,
            drop_path: float = 0.0,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            mlp_layer: Optional[Callable] = None,  # unused, fused design
            attn_layer: Optional[Callable] = None,  # unused, fused design
            depth: int = 0,  # unused
            fuse_out_proj: bool = False,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        assert dim % num_heads == 0, 'dim should be divisible by num_heads'
        assert not scale_attn_norm and not scale_mlp_norm, 'Scale norms not supported'
        self.num_heads = num_heads
        self.head_dim = dim // num_heads
        self.scale = self.head_dim ** -0.5
        mlp_hidden_dim = int(mlp_ratio * dim)
        self.mlp_hidden_dim = mlp_hidden_dim

        linear = partial(nnx.Linear, dtype=dtype, param_dtype=param_dtype,
                         kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs)
        self.in_norm = norm_layer(dim, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.in_proj = linear(dim, mlp_hidden_dim + 3 * dim, use_bias=qkv_bias)
        # when in_proj has no bias, the MLP branch still gets its own bias
        self.mlp_bias = None if qkv_bias else nnx.Param(jnp.zeros((mlp_hidden_dim,), param_dtype))
        self.q_norm = norm_layer(self.head_dim, rngs=rngs) if qk_norm else None
        self.k_norm = norm_layer(self.head_dim, rngs=rngs) if qk_norm else None
        self.attn_drop_rate = attn_drop
        self.attn_drop = Dropout(attn_drop, rngs=rngs)
        self.mlp_drop = Dropout(proj_drop, rngs=rngs)
        self.mlp_act = get_act_fn(act_layer)
        if fuse_out_proj:
            self.out_proj = linear(dim + mlp_hidden_dim, dim, use_bias=proj_bias)
            self.attn_out_proj = None
            self.mlp_out_proj = None
        else:
            self.out_proj = None
            self.attn_out_proj = linear(dim, dim, use_bias=proj_bias)
            self.mlp_out_proj = linear(mlp_hidden_dim, dim, use_bias=proj_bias)
        self.ls = LayerScale(dim, init_values=init_values, param_dtype=param_dtype, rngs=rngs) \
            if init_values is not None else None
        self.drop_path = DropPath(drop_path, rngs=rngs)

    def __call__(self, x, attn_mask=None):
        B, N, C = x.shape
        y = self.in_proj(self.in_norm(x))
        x_mlp, qkv = jnp.split(y, [self.mlp_hidden_dim], axis=-1)
        if self.mlp_bias is not None:
            x_mlp = x_mlp + self.mlp_bias[...].astype(x_mlp.dtype)

        q, k, v = jnp.split(qkv.reshape(B, N, 3, self.num_heads, self.head_dim)
                            .transpose(2, 0, 3, 1, 4), 3, axis=0)
        q, k, v = q[0], k[0], v[0]
        if self.q_norm is not None:
            q = self.q_norm(q)
        if self.k_norm is not None:
            k = self.k_norm(k)
        from ..layers.drop import dropout_rng_key
        dropout_p = 0.0 if self.attn_drop.deterministic else self.attn_drop_rate
        dropout_key = dropout_rng_key(self.attn_drop) if dropout_p > 0.0 else None
        x_attn = scaled_dot_product_attention(
            q, k, v, attn_mask=attn_mask, dropout_p=dropout_p, dropout_key=dropout_key, scale=self.scale)
        x_attn = x_attn.transpose(0, 2, 1, 3).reshape(B, N, C)

        x_mlp = self.mlp_drop(self.mlp_act(x_mlp))
        if self.out_proj is not None:
            y = self.out_proj(jnp.concatenate([x_attn, x_mlp], axis=-1))
        else:
            y = self.attn_out_proj(x_attn) + self.mlp_out_proj(x_mlp)
        if self.ls is not None:
            y = self.ls(y)
        return x + self.drop_path(y)


class DiffParallelScalingBlock(nnx.Module):
    """Parallel fused block with differential attention
    (reference vision_transformer.py:424-595): two softmax attention maps from
    split half-dim heads are subtracted with a learned per-layer lambda, then
    RMS-normed per head before the fused output projection."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = False,
            qk_norm: bool = False,
            scale_attn_norm: bool = False,
            scale_mlp_norm: bool = False,
            proj_bias: bool = True,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            init_values: Optional[float] = None,
            drop_path: float = 0.0,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            mlp_layer: Optional[Callable] = None,  # unused
            attn_layer: Optional[Callable] = None,  # unused
            depth: int = 0,
            dual_lambda: bool = False,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        assert dim % num_heads == 0, 'dim should be divisible by num_heads'
        assert not scale_attn_norm and not scale_mlp_norm, 'Scale norms not supported'
        self.num_heads = num_heads
        self.head_dim = dim // num_heads // 2  # half head_dim for diff attention
        self.scale = self.head_dim ** -0.5
        mlp_hidden_dim = int(mlp_ratio * dim)
        self.mlp_hidden_dim = mlp_hidden_dim

        linear = partial(nnx.Linear, dtype=dtype, param_dtype=param_dtype,
                         kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs)
        self.in_norm = norm_layer(dim, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.in_proj = linear(dim, mlp_hidden_dim + 3 * dim, use_bias=qkv_bias)
        self.mlp_bias = None if qkv_bias else nnx.Param(jnp.zeros((mlp_hidden_dim,), param_dtype))
        self.q_norm = norm_layer(self.head_dim, rngs=rngs) if qk_norm else None
        self.k_norm = norm_layer(self.head_dim, rngs=rngs) if qk_norm else None
        self.attn_drop = Dropout(attn_drop, rngs=rngs)
        self.sub_norm = RmsNorm(2 * self.head_dim, eps=1e-5, rngs=rngs)
        self.dual_lambda = dual_lambda
        key = rngs.params()
        if dual_lambda:
            self.lambda_a = nnx.Param(jnp.zeros((), jnp.float32))
            self.lambda_b = nnx.Param(jnp.zeros((), jnp.float32))
            self.lambda_q1 = self.lambda_k1 = self.lambda_q2 = self.lambda_k2 = None
        else:
            ks = jax.random.split(key, 4)
            self.lambda_a = self.lambda_b = None
            self.lambda_q1 = nnx.Param(jax.random.normal(ks[0], (self.head_dim,), jnp.float32) * 0.1)
            self.lambda_k1 = nnx.Param(jax.random.normal(ks[1], (self.head_dim,), jnp.float32) * 0.1)
            self.lambda_q2 = nnx.Param(jax.random.normal(ks[2], (self.head_dim,), jnp.float32) * 0.1)
            self.lambda_k2 = nnx.Param(jax.random.normal(ks[3], (self.head_dim,), jnp.float32) * 0.1)
        self.mlp_drop = Dropout(proj_drop, rngs=rngs)
        self.mlp_act = get_act_fn(act_layer)
        self.out_proj = linear(dim + mlp_hidden_dim, dim, use_bias=proj_bias)
        self.ls = LayerScale(dim, init_values=init_values, param_dtype=param_dtype, rngs=rngs) \
            if init_values is not None else None
        self.drop_path = DropPath(drop_path, rngs=rngs)
        self.lambda_init = 0.8 - 0.6 * math.exp(-0.3 * depth)

    def _compute_lambda(self):
        if self.lambda_a is not None:
            l1 = jnp.exp(self.lambda_a[...])
            l2 = jnp.exp(self.lambda_b[...])
        else:
            l1 = jnp.exp(jnp.sum(self.lambda_q1[...] * self.lambda_k1[...]))
            l2 = jnp.exp(jnp.sum(self.lambda_q2[...] * self.lambda_k2[...]))
        return l1 - l2 + self.lambda_init

    def __call__(self, x, attn_mask=None):
        B, N, C = x.shape
        y = self.in_proj(self.in_norm(x))
        x_mlp, qkv = jnp.split(y, [self.mlp_hidden_dim], axis=-1)
        if self.mlp_bias is not None:
            x_mlp = x_mlp + self.mlp_bias[...].astype(x_mlp.dtype)
        q, k, v = jnp.split(qkv, 3, axis=-1)
        # 2x heads with half head_dim for q/k; v keeps full head width
        q = q.reshape(B, N, 2 * self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        k = k.reshape(B, N, 2 * self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        v = v.reshape(B, N, self.num_heads, 2 * self.head_dim).transpose(0, 2, 1, 3)
        if self.q_norm is not None:
            q = self.q_norm(q)
        if self.k_norm is not None:
            k = self.k_norm(k)
        lambda_full = self._compute_lambda().astype(q.dtype)

        attn = (q * self.scale) @ k.transpose(0, 1, 3, 2)
        attn = maybe_add_mask(attn, attn_mask)
        attn = jax.nn.softmax(attn, axis=-1)
        attn = self.attn_drop(attn)
        attn = attn.reshape(B, self.num_heads, 2, N, N)
        attn = attn[:, :, 0] - lambda_full * attn[:, :, 1]
        x_attn = attn @ v
        x_attn = self.sub_norm(x_attn)
        x_attn = x_attn * (1 - self.lambda_init)
        x_attn = x_attn.transpose(0, 2, 1, 3).reshape(B, N, C)

        x_mlp = self.mlp_drop(self.mlp_act(x_mlp))
        y = self.out_proj(jnp.concatenate([x_attn, x_mlp], axis=-1))
        if self.ls is not None:
            y = self.ls(y)
        return x + self.drop_path(y)


class _AttnBranch(nnx.Module):
    """norm → attn → layer-scale → drop-path branch of ParallelThingsBlock
    (keeps the reference's ``attns.N.{norm,attn,ls}`` state naming)."""

    def __init__(self, dim, attn_cls, norm_cls, init_values, drop_path, *,
                 param_dtype=jnp.float32, rngs: nnx.Rngs, **attn_kwargs):
        self.norm = norm_cls(dim, rngs=rngs)
        self.attn = attn_cls(dim, **attn_kwargs, rngs=rngs)
        self.ls = LayerScale(dim, init_values=init_values, param_dtype=param_dtype, rngs=rngs) \
            if init_values else None
        self.drop_path = DropPath(drop_path, rngs=rngs)

    def __call__(self, x, attn_mask=None):
        y = self.attn(self.norm(x), attn_mask=attn_mask)
        if self.ls is not None:
            y = self.ls(y)
        return self.drop_path(y)


class _FfnBranch(nnx.Module):
    """norm → mlp → layer-scale → drop-path branch of ParallelThingsBlock."""

    def __init__(self, dim, mlp_layer, norm_cls, init_values, drop_path, *,
                 param_dtype=jnp.float32, rngs: nnx.Rngs, **mlp_kwargs):
        self.norm = norm_cls(dim, rngs=rngs)
        self.mlp = mlp_layer(dim, **mlp_kwargs, rngs=rngs)
        self.ls = LayerScale(dim, init_values=init_values, param_dtype=param_dtype, rngs=rngs) \
            if init_values else None
        self.drop_path = DropPath(drop_path, rngs=rngs)

    def __call__(self, x):
        y = self.mlp(self.norm(x))
        if self.ls is not None:
            y = self.ls(y)
        return self.drop_path(y)


class ParallelThingsBlock(nnx.Module):
    """'Three things' parallel block: N parallel attentions then N parallel
    MLPs, each branch summed into the residual
    (reference vision_transformer.py:598-682)."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            num_parallel: int = 2,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = False,
            qk_norm: bool = False,
            scale_attn_norm: bool = False,
            scale_mlp_norm: bool = False,
            proj_bias: bool = True,
            init_values: Optional[float] = None,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            drop_path: float = 0.0,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            mlp_layer: Callable = Mlp,
            attn_layer: Optional[Callable] = None,
            depth: int = 0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        attn_cls = attn_layer or Attention
        self.num_parallel = num_parallel
        self.attns = nnx.List([
            _AttnBranch(
                dim, attn_cls, norm_layer, init_values, drop_path,
                num_heads=num_heads, qkv_bias=qkv_bias, qk_norm=qk_norm,
                scale_norm=scale_attn_norm, proj_bias=proj_bias, attn_drop=attn_drop,
                proj_drop=proj_drop, norm_layer=norm_layer,
                dtype=dtype, param_dtype=param_dtype, rngs=rngs,
            ) for _ in range(num_parallel)])
        self.ffns = nnx.List([
            _FfnBranch(
                dim, mlp_layer, norm_layer, init_values, drop_path,
                hidden_features=int(dim * mlp_ratio), act_layer=act_layer,
                norm_layer=norm_layer if scale_mlp_norm else None,
                bias=proj_bias, drop=proj_drop,
                dtype=dtype, param_dtype=param_dtype, rngs=rngs,
            ) for _ in range(num_parallel)])

    def __call__(self, x, attn_mask=None):
        x = x + sum(attn(x, attn_mask=attn_mask) for attn in self.attns)
        x = x + sum(ffn(x) for ffn in self.ffns)
        return x


class VisionTransformer(nnx.Module):
    """ViT with the reference's full model contract."""

    dynamic_img_size: bool

    def __init__(
            self,
            img_size: Union[int, Tuple[int, int]] = 224,
            patch_size: Union[int, Tuple[int, int]] = 16,
            in_chans: int = 3,
            num_classes: int = 1000,
            global_pool: str = 'token',
            embed_dim: int = 768,
            depth: int = 12,
            num_heads: int = 12,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = True,
            qk_norm: bool = False,
            scale_attn_norm: bool = False,
            scale_mlp_norm: bool = False,
            proj_bias: bool = True,
            init_values: Optional[float] = None,
            class_token: bool = True,
            pos_embed: str = 'learn',
            no_embed_class: bool = False,
            reg_tokens: int = 0,
            pre_norm: bool = False,
            final_norm: bool = True,
            fc_norm: Optional[bool] = None,
            dynamic_img_size: bool = False,
            dynamic_img_pad: bool = False,
            drop_rate: float = 0.0,
            pos_drop_rate: float = 0.0,
            patch_drop_rate: float = 0.0,
            proj_drop_rate: float = 0.0,
            attn_drop_rate: float = 0.0,
            drop_path_rate: float = 0.0,
            weight_init: str = '',
            fix_init: bool = False,
            embed_layer: Callable = PatchEmbed,
            embed_norm_layer: Optional[Union[str, Callable]] = None,
            norm_layer: Optional[Union[str, Callable]] = None,
            act_layer: Optional[Union[str, Callable]] = None,
            block_fn: Callable = Block,
            mlp_layer: Callable = Mlp,
            attn_layer: Optional[Union[str, Callable]] = None,
            pad_tokens_to: Optional[Union[int, str]] = None,
            block_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        assert global_pool in ('', 'avg', 'avgmax', 'max', 'token', 'map')
        assert class_token or global_pool != 'token'
        assert pos_embed in ('', 'none', 'learn')
        norm_layer = get_norm_layer(norm_layer) or LayerNorm
        act_layer = act_layer or 'gelu'

        # TPU tile alignment: pad the token sequence once at embed time so the
        # (B·H, N, N) attention matmuls and softmax land on lane/sublane tile
        # boundaries (PERF.md §2 item 1: N=197 wastes up to ~23% of MXU issue
        # on ~28% of ViT FLOPs). 'auto' rounds up to the next sublane multiple
        # (197 → 200); an int pads to exactly that count (e.g. 256 for a full
        # lane tile). Pad keys are excluded via a key-padding mask threaded
        # through every block, and the pad is stripped again before
        # forward_head, so outputs match the unpadded model to fp precision.
        # None (default) traces the exact pre-padding graph.
        if pad_tokens_to is not None and pad_tokens_to != 'auto':
            pad_tokens_to = int(pad_tokens_to)
            if pad_tokens_to == 0:
                pad_tokens_to = None
        if pad_tokens_to is not None and patch_drop_rate > 0:
            raise ValueError(
                'pad_tokens_to is incompatible with patch_drop_rate > 0: '
                'PatchDropout re-indexes the token sequence, invalidating the pad mask')
        self.pad_tokens_to = pad_tokens_to

        self.num_classes = num_classes
        self.global_pool = global_pool
        self.num_features = self.head_hidden_size = self.embed_dim = embed_dim
        self.num_prefix_tokens = 1 if class_token else 0
        self.num_prefix_tokens += reg_tokens
        self.num_reg_tokens = reg_tokens
        self.has_class_token = class_token
        self.no_embed_class = no_embed_class
        self.dynamic_img_size = dynamic_img_size
        self.grad_checkpointing = False
        self.depth = depth
        # scan-over-layers execution: one lax.scan over stacked per-layer
        # params instead of a Python loop over L traced block subgraphs —
        # O(1)-in-depth trace/compile. None → TIMM_TPU_BLOCK_SCAN env toggle.
        self.block_scan = resolve_block_scan(block_scan)

        embed_args = {}
        if dynamic_img_size:
            embed_args.update(dict(strict_img_size=False))
        if embed_norm_layer is not None:
            embed_args['norm_layer'] = get_norm_layer(embed_norm_layer)
        self.patch_embed = embed_layer(
            img_size=img_size,
            patch_size=patch_size,
            in_chans=in_chans,
            embed_dim=embed_dim,
            bias=not pre_norm,  # pre-norm (CLIP) ViTs have no patch-proj bias
            dynamic_img_pad=dynamic_img_pad,
            dtype=dtype,
            param_dtype=param_dtype,
            rngs=rngs,
            **embed_args,
        )
        num_patches = self.patch_embed.num_patches
        if hasattr(self.patch_embed, 'feat_ratio'):
            # hybrid embeds: backbone stride x patch size (reference vision_transformer.py:552)
            reduction = self.patch_embed.feat_ratio()
        elif hasattr(self.patch_embed, 'patch_size'):
            reduction = self.patch_embed.patch_size[0]
        else:
            reduction = 16

        self.cls_token = nnx.Param(
            jnp.zeros((1, 1, embed_dim), param_dtype)) if class_token else None
        self.reg_token = nnx.Param(
            trunc_normal_(std=0.02)(rngs.params(), (1, reg_tokens, embed_dim), param_dtype)) if reg_tokens else None

        embed_len = num_patches if no_embed_class else num_patches + self.num_prefix_tokens
        if not pos_embed or pos_embed == 'none':
            self.pos_embed = None
        else:
            self.pos_embed = nnx.Param(
                trunc_normal_(std=0.02)(rngs.params(), (1, embed_len, embed_dim), param_dtype))
        self.pos_drop = Dropout(pos_drop_rate, rngs=rngs)
        if patch_drop_rate > 0:
            self.patch_drop = PatchDropout(patch_drop_rate, num_prefix_tokens=self.num_prefix_tokens, rngs=rngs)
        else:
            self.patch_drop = None
        self.norm_pre = norm_layer(embed_dim, rngs=rngs) if pre_norm else None

        def _resolve_attn_layer(i: int):
            if attn_layer is None:
                return None
            if attn_layer == 'diff':
                from ..layers.diff_attention import DiffAttention
                return partial(DiffAttention, depth=i)  # depth-dependent lambda_init
            return attn_layer

        dpr = calculate_drop_path_rates(drop_path_rate, depth)
        self.blocks = nnx.List([
            block_fn(
                dim=embed_dim,
                num_heads=num_heads,
                mlp_ratio=mlp_ratio,
                qkv_bias=qkv_bias,
                qk_norm=qk_norm,
                scale_attn_norm=scale_attn_norm,
                scale_mlp_norm=scale_mlp_norm,
                proj_bias=proj_bias,
                init_values=init_values,
                proj_drop=proj_drop_rate,
                attn_drop=attn_drop_rate,
                drop_path=dpr[i],
                norm_layer=norm_layer,
                act_layer=act_layer,
                mlp_layer=mlp_layer,
                attn_layer=_resolve_attn_layer(i),
                depth=i,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
            for i in range(depth)
        ])
        self.feature_info = [
            dict(module=f'blocks.{i}', num_chs=embed_dim, reduction=reduction) for i in range(depth)]

        # feature norm (pre-pool) vs fc norm (post-pool)
        if fc_norm is None:
            fc_norm = global_pool == 'avg'
        self.norm = norm_layer(embed_dim, rngs=rngs) if final_norm and not fc_norm else None

        # head
        if global_pool == 'map':
            self.attn_pool = AttentionPoolLatent(
                self.embed_dim,
                num_heads=num_heads,
                mlp_ratio=mlp_ratio,
                norm_layer=norm_layer,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
        else:
            self.attn_pool = None
        self.fc_norm = norm_layer(embed_dim, rngs=rngs) if final_norm and fc_norm else None
        self.head_drop = Dropout(drop_rate, rngs=rngs)
        self.head = nnx.Linear(
            self.embed_dim, num_classes,
            kernel_init=trunc_normal_(std=0.02),
            bias_init=lambda key, shape, dtype=jnp.float32: jnp.zeros(shape, dtype),
            dtype=dtype, param_dtype=param_dtype, rngs=rngs,
        ) if num_classes > 0 else None

        self._dtype = dtype
        self._param_dtype = param_dtype

        if fix_init:
            self.fix_init_weight()

    def fix_init_weight(self):
        """Rescale block projections by depth (reference vision_transformer.py:~980)."""
        for layer_id, block in enumerate(self.blocks):
            scale = math.sqrt(2.0 * (layer_id + 1))
            block.attn.proj.kernel[...] = block.attn.proj.kernel[...] / scale
            block.mlp.fc2.kernel[...] = block.mlp.fc2.kernel[...] / scale

    # ---- contract methods -------------------------------------------------
    def no_weight_decay(self) -> set:
        return {'pos_embed', 'cls_token', 'reg_token', 'dist_token'}

    def group_matcher(self, coarse: bool = False) -> Dict:
        return dict(
            stem=r'^cls_token|pos_embed|patch_embed|reg_token',
            blocks=[(r'^blocks\.(\d+)', None), (r'^norm', (99999,))],
        )

    def set_grad_checkpointing(self, enable: bool = True):
        self.grad_checkpointing = enable

    def set_block_scan(self, enable: bool = True):
        """Toggle scan-over-layers execution of the block stack. When the
        stack is not scannable (heterogeneous blocks, active inner dropout),
        each forward transparently falls back to the Python loop (logged once)."""
        self.block_scan = enable

    def get_classifier(self):
        return self.head

    def reset_classifier(self, num_classes: int, global_pool: Optional[str] = None, *, rngs: Optional[nnx.Rngs] = None):
        self.num_classes = num_classes
        if global_pool is not None:
            assert global_pool in ('', 'avg', 'avgmax', 'max', 'token', 'map')
            if global_pool == 'map' and self.attn_pool is None:
                raise AssertionError("Cannot currently add attention pooling in reset_classifier().")
            if global_pool != 'map':
                self.attn_pool = None
            self.global_pool = global_pool
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        self.head = nnx.Linear(
            self.embed_dim, num_classes, kernel_init=trunc_normal_(std=0.02),
            dtype=self._dtype, param_dtype=self._param_dtype, rngs=rngs,
        ) if num_classes > 0 else None

    def set_input_size(self, img_size=None, patch_size=None):
        """Resample learned pos embed for a new static input size
        (reference vision_transformer.py:1013)."""
        if img_size is None:
            return
        prev_grid = self.patch_embed.grid_size
        self.patch_embed.set_input_size(img_size=img_size, patch_size=patch_size)
        new_grid = self.patch_embed.grid_size
        if self.pos_embed is not None and new_grid != prev_grid:
            # shape changes, so the Param must be replaced, not assigned into
            self.pos_embed = nnx.Param(resample_abs_pos_embed(
                self.pos_embed[...],
                new_size=new_grid,
                old_size=prev_grid,
                num_prefix_tokens=0 if self.no_embed_class else self.num_prefix_tokens,
            ))

    # ---- forward ----------------------------------------------------------
    def _resolve_pad_len(self, n: int, pad_tokens_to=None) -> int:
        """Padded sequence length for an n-token sequence (== n when the
        padding knob is off or n is already aligned)."""
        pad = pad_tokens_to if pad_tokens_to is not None else self.pad_tokens_to
        if not pad:
            return n
        if pad == 'auto':
            return -(-n // 8) * 8  # next sublane multiple: 197 → 200
        target = int(pad)
        if target < n:
            raise ValueError(f'pad_tokens_to={target} is smaller than the token count {n}')
        return target

    def _pos_embed(self, x, grid_size: Optional[Tuple[int, int]] = None, pad_tokens_to=None):
        """Prefix-token concat + position embedding, then (optionally) the
        tile-alignment pad. `pad_tokens_to` overrides the constructor knob for
        this call (0 disables). Returns (tokens, key_padding_mask, orig_len);
        the mask is None and orig_len == tokens.shape[1] when no pad was added.
        """
        B = x.shape[0]
        if self.pos_embed is None:
            pos_embed = None
        else:
            pos_embed = self.pos_embed[...].astype(x.dtype)
            if self.dynamic_img_size and grid_size is not None and grid_size != self.patch_embed.grid_size:
                pos_embed = resample_abs_pos_embed(
                    pos_embed,
                    new_size=grid_size,
                    old_size=self.patch_embed.grid_size,
                    num_prefix_tokens=0 if self.no_embed_class else self.num_prefix_tokens,
                )

        to_cat = []
        if self.cls_token is not None:
            to_cat.append(jnp.broadcast_to(self.cls_token[...].astype(x.dtype), (B, 1, x.shape[-1])))
        if self.reg_token is not None:
            to_cat.append(jnp.broadcast_to(self.reg_token[...].astype(x.dtype), (B, self.num_reg_tokens, x.shape[-1])))

        if self.no_embed_class:
            if pos_embed is not None:
                x = x + pos_embed
            if to_cat:
                x = jnp.concatenate(to_cat + [x], axis=1)
        else:
            if to_cat:
                x = jnp.concatenate(to_cat + [x], axis=1)
            if pos_embed is not None:
                x = x + pos_embed
        x = self.pos_drop(x)
        return self._pad_token_seq(x, pad_tokens_to)

    def _pad_token_seq(self, x, pad_tokens_to=None):
        """Apply the tile-alignment pad to (B, N, C) tokens.
        Returns (tokens, key_padding_mask, orig_len); mask is None when no
        pad was added."""
        B, n = x.shape[0], x.shape[1]
        n_pad = self._resolve_pad_len(n, pad_tokens_to)
        if n_pad == n:
            return x, None, n
        x = jnp.pad(x, ((0, 0), (0, n_pad - n), (0, 0)))
        # key-padding mask, True = real token, broadcast over heads/queries
        mask = jnp.broadcast_to((jnp.arange(n_pad) < n)[None, None, None, :], (B, 1, 1, n_pad))
        return x, mask, n

    def forward_features(self, x, attn_mask=None):
        grid_size = None
        if self.dynamic_img_size:
            grid_size = self.patch_embed.dynamic_feat_size(x.shape[1:3])
        x = self.patch_embed(x)
        with tracing.scope('img.patch_embed'):
            # an externally supplied attn_mask is sized for the UNPADDED sequence,
            # so the alignment pad is skipped for that call
            x, pad_mask, orig_len = self._pos_embed(
                x, grid_size=grid_size, pad_tokens_to=0 if attn_mask is not None else None)
            if pad_mask is not None:
                attn_mask = pad_mask
            if self.patch_drop is not None:
                x = self.patch_drop(x)
            if self.norm_pre is not None:
                x = self.norm_pre(x)
        x = self._forward_block_stack(x, attn_mask=attn_mask)
        with tracing.scope('img.head'):
            if self.norm is not None:
                x = self.norm(x)
            if x.shape[1] != orig_len:
                x = x[:, :orig_len]  # strip the alignment pad before the head
        return x

    def _forward_block_stack(self, x, attn_mask=None, collect=False, blocks=None):
        """Execute the block stack. With `block_scan` on and a homogeneous
        stack: one lax.scan over stacked per-layer params (O(1)-in-depth
        trace/compile; remat-inside-scan replaces checkpoint_seq when grad
        checkpointing is on; per-layer DropPath rates ride a scanned rate
        vector). Otherwise: the Python loop (checkpoint_seq when grad
        checkpointing and unmasked). `collect=True` additionally returns the
        list of per-layer outputs (forward_intermediates). Either path pins
        the residual stream to the tensor-parallel layout on 'model' meshes
        (scan does it on the carry inside scan_block_stack)."""
        from ..parallel import shard_activation
        blocks = self.blocks if blocks is None else blocks
        if self.block_scan:
            try:
                dp = drop_path_scan_inputs(blocks)

                def call(blk, xx, extra):
                    return blk(xx, attn_mask=attn_mask, drop_path_override=extra)

                out = scan_block_stack(
                    blocks, x, call, per_layer=dp,
                    remat=self.grad_checkpointing, collect=collect)
                if collect:
                    final, ys = out
                    return final, [ys[i] for i in range(ys.shape[0])]
                return out
            except BlockStackError as e:
                warn_scan_fallback(type(self).__name__, e)
        x = shard_activation(x, 'residual')
        if collect:
            outs = []
            for blk in blocks:
                x = shard_activation(blk(x, attn_mask=attn_mask), 'residual')
                outs.append(x)
            return x, outs
        if self.grad_checkpointing and attn_mask is None:
            return checkpoint_seq(blocks, x)
        for blk in blocks:
            x = shard_activation(blk(x, attn_mask=attn_mask), 'residual')
        return x

    def pool(self, x, pool_type: Optional[str] = None, mask=None):
        """`mask` (optional key-padding mask, True = valid) supports pooling a
        still-padded token sequence; the standard forward path strips the
        alignment pad before the head, so it passes None."""
        if self.attn_pool is not None:
            return self.attn_pool(x, attn_mask=mask)
        pool_type = self.global_pool if pool_type is None else pool_type
        return global_pool_nlc(x, pool_type=pool_type, num_prefix_tokens=self.num_prefix_tokens, mask=mask)

    def forward_head(self, x, pre_logits: bool = False):
        with tracing.scope('img.head'):
            x = self.pool(x)
            if self.fc_norm is not None:
                x = self.fc_norm(x)
            x = self.head_drop(x)
            if pre_logits or self.head is None:
                return x
            return self.head(x)

    def __call__(self, x, attn_mask=None):
        x = self.forward_features(x, attn_mask=attn_mask)
        x = self.forward_head(x)
        return x

    # ---- intermediates ----------------------------------------------------
    def forward_intermediates(
            self,
            x,
            indices: Optional[Union[int, List[int]]] = None,
            return_prefix_tokens: bool = False,
            norm: bool = False,
            stop_early: bool = False,
            output_fmt: str = 'NHWC',
            intermediates_only: bool = False,
            attn_mask=None,
    ):
        """Collect intermediate block outputs (reference vision_transformer.py:1077).

        With `block_scan` on, the full-depth path runs the scan with stacked
        per-layer outputs and gathers `indices` from them. `stop_early=True`
        slices the Python block list, which a stacked scan cannot represent —
        that path (like a pruned model, see `prune_intermediate_layers`) always
        uses the Python loop, so results never silently disagree with the
        sliced `self.blocks`.
        """
        assert output_fmt in ('NHWC', 'NLC'), 'Output format must be NHWC or NLC.'
        reshape = output_fmt == 'NHWC'
        take_indices, max_index = feature_take_indices(len(self.blocks), indices)

        B, H, W, _ = x.shape
        grid_size = self.patch_embed.dynamic_feat_size((H, W)) if self.dynamic_img_size \
            else self.patch_embed.grid_size
        x = self.patch_embed(x)
        # no alignment pad here: intermediates are reshaped to spatial grids
        x, _, _ = self._pos_embed(x, grid_size=grid_size if self.dynamic_img_size else None, pad_tokens_to=0)
        if self.patch_drop is not None:
            x = self.patch_drop(x)
        if self.norm_pre is not None:
            x = self.norm_pre(x)

        if stop_early:
            # scan runs the full stacked depth; early stop needs the loop
            intermediates = []
            for i, blk in enumerate(self.blocks[:max_index + 1]):
                x = blk(x, attn_mask=attn_mask)
                if i in take_indices:
                    intermediates.append(self.norm(x) if (norm and self.norm is not None) else x)
        else:
            x, outs = self._forward_block_stack(x, attn_mask=attn_mask, collect=True)
            intermediates = [
                self.norm(outs[i]) if (norm and self.norm is not None) else outs[i]
                for i in range(len(outs)) if i in take_indices]

        # split prefix tokens, reshape spatial
        prefix_tokens = None
        if self.num_prefix_tokens:
            prefix_tokens = [y[:, 0:self.num_prefix_tokens] for y in intermediates]
            intermediates = [y[:, self.num_prefix_tokens:] for y in intermediates]
        if reshape:
            intermediates = [
                y.reshape(B, grid_size[0], grid_size[1], -1) for y in intermediates]
        if return_prefix_tokens and prefix_tokens is not None:
            intermediates = list(zip(intermediates, prefix_tokens))

        if intermediates_only:
            return intermediates
        if self.norm is not None:
            x = self.norm(x)
        return x, intermediates

    def prune_intermediate_layers(
            self,
            indices: Union[int, List[int]] = 1,
            prune_norm: bool = False,
            prune_head: bool = True,
    ):
        """Safe under `block_scan`: the scan stacks whatever `self.blocks`
        currently holds at call time, so a pruned stack scans at its pruned
        depth (and a single remaining block falls back to the loop)."""
        take_indices, max_index = feature_take_indices(len(self.blocks), indices)
        self.blocks = nnx.List(list(self.blocks)[:max_index + 1])
        if prune_norm:
            self.norm = None
        if prune_head:
            self.fc_norm = None
            self.attn_pool = None
            self.reset_classifier(0, '')
        return take_indices


def checkpoint_filter_fn(state_dict: Dict, model) -> Dict:
    """Convert reference-timm torch checkpoints → this module's state layout."""
    from ._torch_convert import convert_torch_state_dict
    return convert_torch_state_dict(state_dict, model)


def _cfg(url: str = '', **kwargs) -> Dict[str, Any]:
    return {
        'url': url,
        'num_classes': 1000,
        'input_size': (3, 224, 224),
        'pool_size': None,
        'crop_pct': 0.9,
        'interpolation': 'bicubic',
        'fixed_input_size': True,
        'mean': (0.5, 0.5, 0.5),
        'std': (0.5, 0.5, 0.5),
        'first_conv': 'patch_embed.proj',
        'classifier': 'head',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'vit_tiny_patch16_224.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'vit_tiny_patch16_384.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_small_patch32_224.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'vit_small_patch16_224.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'vit_small_patch16_384.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_base_patch32_224.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'vit_base_patch16_224.augreg2_in21k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'vit_base_patch16_224.augreg_in1k': _cfg(hf_hub_id='timm/'),
    'vit_base_patch16_384.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_base_patch8_224.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'vit_large_patch16_224.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'vit_dlittle_patch16_reg1_gap_256.sbb_nadamuon_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_little_patch16_reg4_gap_256.sbb_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_medium_patch16_reg4_gap_256.sbb_in12k_ft_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_large_patch14_224.untrained': _cfg(url=''),
    'vit_huge_patch14_224.untrained': _cfg(url=''),
    'vit_so400m_patch14_siglip_224.untrained': _cfg(url=''),
    'vit_tiny_patch16_224.untrained': _cfg(url=''),
    # tiny test fixtures (reference vision_transformer.py:4802-4833)
    'vit_small_patch32_384.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_small_patch8_224.dino': _cfg(hf_hub_id='timm/', num_classes=0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_base_patch32_384.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_base_patch32_384.augreg_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_large_patch32_224.orig_in21k': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_large_patch32_384.orig_in21k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_large_patch16_384.augreg_in21k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_giant_patch14_224.untrained': _cfg(),
    'vit_gigantic_patch14_224.untrained': _cfg(),
    'vit_base_patch16_224_miil.in21k': _cfg(hf_hub_id='timm/', num_classes=11221, crop_pct=0.875, interpolation='bilinear', mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0)),
    'vit_base_patch16_224_miil.in21k_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.875, interpolation='bilinear', mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0)),
    'vit_medium_patch16_gap_240.sw_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 240, 240), crop_pct=0.95),
    'vit_medium_patch16_gap_256.sw_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_medium_patch16_gap_384.sw_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=0.95, crop_mode='squash'),
    'vit_betwixt_patch16_gap_256.untrained': _cfg(input_size=(3, 256, 256), crop_pct=0.95),
    'vit_base_patch16_gap_224.untrained': _cfg(),
    'vit_huge_patch14_gap_224.in1k_ijepa': _cfg(num_classes=0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_huge_patch14_gap_224.in22k_ijepa': _cfg(num_classes=0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_huge_patch16_gap_448.in1k_ijepa': _cfg(num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_giant_patch16_gap_224.in22k_ijepa': _cfg(num_classes=0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_xsmall_patch16_clip_224.tinyclip_yfcc15m': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_medium_patch32_clip_224.tinyclip_laion400m': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_medium_patch16_clip_224.tinyclip_yfcc15m': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_betwixt_patch32_clip_224.tinyclip_laion400m': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.openai_ft_in12k_in1k': _cfg(mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.laion2b_ft_in1k': _cfg(hf_hub_id='timm/', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.openai_ft_in1k': _cfg(hf_hub_id='timm/', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.laion2b': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.laion400m_e32': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.datacompxl': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.metaclip_400m': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_224.openai': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_256.datacompxl': _cfg(hf_hub_id='timm/', num_classes=512, input_size=(3, 256, 256), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_384.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_384.openai_ft_in12k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=0.95, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_448.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 448, 448), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.95, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.openai_ft_in12k_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.95, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.laion2b_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.openai_ft_in1k': _cfg(hf_hub_id='timm/', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.laion2b_ft_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.openai_ft_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.laion2b': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.laion400m_e32': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.datacompxl': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.dfn2b': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.metaclip_400m': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_224.openai': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_384.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_384.openai_ft_in12k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=0.95, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_384.laion2b_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_384.openai_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_plus_clip_240.laion400m_e32': _cfg(hf_hub_id='timm/', num_classes=640, input_size=(3, 240, 240), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0),
    'vit_large_patch14_clip_224.openai_ft_in12k_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.laion2b_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0),
    'vit_large_patch14_clip_224.openai_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.laion2b_ft_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, crop_pct=1.0),
    'vit_large_patch14_clip_224.openai_ft_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.laion2b': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0),
    'vit_large_patch14_clip_224.laion400m_e32': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.datacompxl': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.dfn2b_s39b': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.dfn2b': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.metaclip_400m': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.openai': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_224.apple_mclip2_dfndr2b': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_336.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash'),
    'vit_large_patch14_clip_336.openai_ft_in12k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_336.laion2b_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash'),
    'vit_large_patch14_clip_336.openai': _cfg(hf_hub_id='timm/', num_classes=768, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_224.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_224.laion2b_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_224.laion2b_ft_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_224.laion2b': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_224.dfn5b': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_224.metaclip2_worldwide': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_224.metaclip_altogether': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_336.laion2b_ft_in12k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_336.laion2b_ft_in1k': _cfg(input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_378.dfn5b': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 378, 378), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_378.metaclip2_worldwide': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 378, 378), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_giant_patch14_clip_224.laion2b': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_gigantic_patch14_clip_224.laion2b': _cfg(hf_hub_id='timm/', num_classes=1280, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_gigantic_patch14_clip_224.metaclip2_worldwide': _cfg(hf_hub_id='timm/', num_classes=1280, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_gigantic_patch14_clip_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=1280, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_gigantic_patch14_clip_378.metaclip2_worldwide': _cfg(hf_hub_id='timm/', num_classes=1280, input_size=(3, 378, 378), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_quickgelu_224.laion400m_e32': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_quickgelu_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_quickgelu_224.metaclip_400m': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_clip_quickgelu_224.openai': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_quickgelu_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_quickgelu_224.metaclip_400m': _cfg(hf_hub_id='timm/', num_classes=512, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch16_clip_quickgelu_224.openai': _cfg(hf_hub_id='timm/', num_classes=512, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_quickgelu_224.dfn2b': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_quickgelu_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_quickgelu_224.metaclip_400m': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_quickgelu_224.openai': _cfg(hf_hub_id='timm/', num_classes=768, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_large_patch14_clip_quickgelu_336.openai': _cfg(hf_hub_id='timm/', num_classes=768, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_quickgelu_224.dfn5b': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_quickgelu_224.metaclip2_worldwide': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_quickgelu_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=1024, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_huge_patch14_clip_quickgelu_378.dfn5b': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 378, 378), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_gigantic_patch14_clip_quickgelu_224.metaclip_2pt5b': _cfg(hf_hub_id='timm/', num_classes=1280, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'vit_base_patch32_plus_256.untrained': _cfg(input_size=(3, 256, 256), crop_pct=0.95),
    'vit_base_patch16_plus_240.untrained': _cfg(input_size=(3, 240, 240), crop_pct=0.95),
    'vit_base_patch16_rpn_224.sw_in1k': _cfg(hf_hub_id='timm/'),
    'vit_small_patch16_36x1_224.untrained': _cfg(),
    'vit_small_patch16_18x2_224.untrained': _cfg(),
    'vit_base_patch16_18x2_224.untrained': _cfg(),
    'eva_large_patch14_196.in22k_ft_in22k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 196, 196), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'eva_large_patch14_196.in22k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 196, 196), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'eva_large_patch14_336.in22k_ft_in22k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'eva_large_patch14_336.in22k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 336, 336), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'flexivit_small.1200ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_small.600ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_small.300ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_base.1200ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_base.600ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_base.300ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_base.1000ep_in21k': _cfg(hf_hub_id='timm/', num_classes=21843, input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_base.300ep_in21k': _cfg(hf_hub_id='timm/', num_classes=21843, input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_base.patch16_in21k': _cfg(hf_hub_id='timm/', num_classes=21843, input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_base.patch30_in21k': _cfg(hf_hub_id='timm/', num_classes=21843, input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_large.1200ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_large.600ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'flexivit_large.300ep_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 240, 240), crop_pct=0.95),
    'vit_base_patch16_xp_224.untrained': _cfg(),
    'vit_large_patch14_xp_224.untrained': _cfg(),
    'vit_huge_patch14_xp_224.untrained': _cfg(),
    'vit_small_patch14_dinov2.lvd142m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 518, 518), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_base_patch14_dinov2.lvd142m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 518, 518), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_large_patch14_dinov2.lvd142m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 518, 518), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_giant_patch14_dinov2.lvd142m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 518, 518), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_small_patch14_reg4_dinov2.lvd142m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 518, 518), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_base_patch14_reg4_dinov2.lvd142m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 518, 518), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_large_patch14_reg4_dinov2.lvd142m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 518, 518), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_giant_patch14_reg4_dinov2.lvd142m': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 518, 518), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_base_patch14_reg1_tipsv2.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0)),
    'vit_large_patch14_reg1_tipsv2.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0)),
    'vit_so400m_patch14_reg1_tipsv2.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0)),
    'vit_giant_patch14_reg1_tipsv2.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.0, 0.0, 0.0), std=(1.0, 1.0, 1.0)),
    'vit_base_patch32_siglip_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_base_patch16_siglip_224.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_base_patch16_siglip_224.webli': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_base_patch16_siglip_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_base_patch16_siglip_256.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_base_patch16_siglip_256.webli_i18n': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_base_patch16_siglip_384.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_base_patch16_siglip_384.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_base_patch16_siglip_512.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512)),
    'vit_base_patch16_siglip_512.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512)),
    'vit_large_patch16_siglip_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_large_patch16_siglip_256.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_large_patch16_siglip_384.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_large_patch16_siglip_384.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_large_patch16_siglip_512.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512)),
    'vit_so400m_patch14_siglip_378.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 378, 378)),
    'vit_so400m_patch14_siglip_378.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 378, 378)),
    'vit_so400m_patch14_siglip_378.webli_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 378, 378), crop_pct=1.0, crop_mode='squash'),
    'vit_so400m_patch14_siglip_384.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_so400m_patch16_siglip_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_so400m_patch16_siglip_256.webli_i18n': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_so400m_patch16_siglip_384.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_so400m_patch16_siglip_512.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512)),
    'vit_giantopt_patch16_siglip_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_giantopt_patch16_siglip_384.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_base_patch32_siglip_gap_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_base_patch16_siglip_gap_224.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_base_patch16_siglip_gap_224.webli': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_base_patch16_siglip_gap_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_base_patch16_siglip_gap_256.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_base_patch16_siglip_gap_256.webli_i18n': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_base_patch16_siglip_gap_384.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_base_patch16_siglip_gap_384.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_base_patch16_siglip_gap_512.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512)),
    'vit_base_patch16_siglip_gap_512.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512)),
    'vit_large_patch16_siglip_gap_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_large_patch16_siglip_gap_256.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_large_patch16_siglip_gap_384.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_large_patch16_siglip_gap_384.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_large_patch16_siglip_gap_512.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512)),
    'vit_so400m_patch14_siglip_gap_224.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_so400m_patch14_siglip_gap_224.webli': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_so400m_patch14_siglip_gap_224.pali_mix': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_so400m_patch14_siglip_gap_224.pali_pt': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_so400m_patch14_siglip_gap_224.pali2_3b_pt': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_so400m_patch14_siglip_gap_224.pali2_10b_pt': _cfg(hf_hub_id='timm/', num_classes=0),
    'vit_so400m_patch14_siglip_gap_378.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 378, 378)),
    'vit_so400m_patch14_siglip_gap_378.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 378, 378), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_378.webli_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 378, 378), crop_pct=1.0, crop_mode='squash'),
    'vit_so400m_patch14_siglip_gap_384.webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_448.pali_mix': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_448.pali_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_448.pali_refcoco_seg': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_448.pali_ocrvqa': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_448.pali2_3b_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_448.pali2_10b_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_448.pali2_3b_docci': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_448.pali2_10b_docci': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_896.pali_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 896, 896), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_896.pali_refcoco_seg': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 896, 896), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_896.pali_ocrvqa': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 896, 896), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_896.pali2_3b_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 896, 896), crop_pct=1.0),
    'vit_so400m_patch14_siglip_gap_896.pali2_10b_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 896, 896), crop_pct=1.0),
    'vit_so400m_patch16_siglip_gap_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_so400m_patch16_siglip_gap_256.webli_i18n': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_so400m_patch16_siglip_gap_384.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_so400m_patch16_siglip_gap_512.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 512, 512)),
    'vit_giantopt_patch16_siglip_gap_256.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 256, 256)),
    'vit_giantopt_patch16_siglip_gap_384.v2_webli': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 384, 384)),
    'vit_wee_patch16_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_dwee_patch16_reg1_gap_256.sbb_nadamuon_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_dwee_patch16_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_pwee_patch16_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_dpwee_patch16_reg1_gap_256.sbb_nadamuon_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_dpwee_patch16_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_little_patch16_reg1_gap_256.sbb_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_little_patch16_reg1_gap_256.sbb_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 256, 256), crop_pct=0.95),
    'vit_medium_patch16_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_mediumd_patch16_reg4_gap_256.sbb2_e200_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_mediumd_patch16_reg4_gap_256.sbb_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_mediumd_patch16_reg4_gap_256.sbb2_e200_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 256, 256), crop_pct=0.95),
    'vit_mediumd_patch16_reg4_gap_256.sbb_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 256, 256), crop_pct=0.95),
    'vit_mediumd_patch16_reg4_gap_384.sbb2_e200_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_betwixt_patch16_reg1_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_betwixt_patch16_reg4_gap_256.sbb2_e200_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_betwixt_patch16_reg4_gap_256.sbb_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_betwixt_patch16_reg4_gap_256.sbb_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_betwixt_patch16_reg4_gap_256.sbb2_e200_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 256, 256), crop_pct=0.95),
    'vit_betwixt_patch16_reg4_gap_256.sbb_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 256, 256), crop_pct=0.95),
    'vit_betwixt_patch16_reg4_gap_384.sbb2_e200_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_base_patch16_reg4_gap_256.untrained': _cfg(input_size=(3, 256, 256)),
    'vit_so150m_patch16_reg4_map_256.untrained': _cfg(input_size=(3, 256, 256)),
    'vit_so150m_patch16_reg4_gap_256.sbb_e250_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=0.95),
    'vit_so150m_patch16_reg4_gap_256.sbb_e250_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 256, 256), crop_pct=0.95),
    'vit_so150m_patch16_reg4_gap_384.sbb_e250_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_so150m2_patch16_reg1_gap_256.sbb_e200_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), crop_pct=1.0),
    'vit_so150m2_patch16_reg1_gap_256.sbb_e200_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 256, 256), crop_pct=1.0),
    'vit_so150m2_patch16_reg1_gap_384.sbb_e200_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), crop_pct=1.0),
    'vit_so150m2_patch16_reg1_gap_448.sbb_e200_in12k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 448, 448), crop_pct=1.0, crop_mode='squash'),
    'vit_intern300m_patch14_448.ogvl_dist': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'vit_intern300m_patch14_448.ogvl_2pt5': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'aimv2_large_patch14_224.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_large_patch14_224.apple_pt_dist': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_huge_patch14_224.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_1b_patch14_224.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_3b_patch14_224.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_large_patch14_336.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_large_patch14_336.apple_pt_dist': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_huge_patch14_336.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_1b_patch14_336.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_3b_patch14_336.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 336, 336), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_large_patch14_448.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_huge_patch14_448.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_1b_patch14_448.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'aimv2_3b_patch14_448.apple_pt': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 448, 448), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711)),
    'beit3_base_patch16_224.in22k_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_base_patch16_224.indomain_in22k_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_base_patch16_224.pt': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_base_patch16_224.indomain_pt': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_large_patch16_224.in22k_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_large_patch16_224.indomain_in22k_ft_in1k': _cfg(hf_hub_id='timm/', crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_large_patch16_224.pt': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_large_patch16_224.indomain_pt': _cfg(hf_hub_id='timm/', num_classes=0, crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_giant_patch14_224.untrained': _cfg(crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'beit3_giant_patch14_336.untrained': _cfg(input_size=(3, 336, 336), crop_pct=1.0, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225)),
    'test_vit.r160_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 160, 160), crop_pct=0.95),
    'test_vit2.r160_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 160, 160), crop_pct=0.95),
    'test_vit3.r160_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 160, 160), crop_pct=0.95),
    'test_vit4.r160_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 160, 160), crop_pct=0.95),
})


def _create_vision_transformer(variant: str, pretrained: bool = False, **kwargs) -> VisionTransformer:
    out_indices = kwargs.pop('out_indices', 3)
    return build_model_with_cfg(
        VisionTransformer,
        variant,
        pretrained,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=out_indices),
        **kwargs,
    )


@register_model
def vit_tiny_patch16_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=16, embed_dim=192, depth=12, num_heads=3)
    return _create_vision_transformer('vit_tiny_patch16_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_tiny_patch16_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=16, embed_dim=192, depth=12, num_heads=3)
    return _create_vision_transformer('vit_tiny_patch16_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch32_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=32, embed_dim=384, depth=12, num_heads=6)
    return _create_vision_transformer('vit_small_patch32_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=16, embed_dim=384, depth=12, num_heads=6)
    return _create_vision_transformer('vit_small_patch16_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=16, embed_dim=384, depth=12, num_heads=6)
    return _create_vision_transformer('vit_small_patch16_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=32, embed_dim=768, depth=12, num_heads=12)
    return _create_vision_transformer('vit_base_patch32_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=16, embed_dim=768, depth=12, num_heads=12)
    return _create_vision_transformer('vit_base_patch16_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=16, embed_dim=768, depth=12, num_heads=12)
    return _create_vision_transformer('vit_base_patch16_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch8_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=8, embed_dim=768, depth=12, num_heads=12)
    return _create_vision_transformer('vit_base_patch8_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_dlittle_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """Differential-attention 'little' ViT (sbb recipe, reference
    vision_transformer.py:4440)."""
    model_args = dict(
        patch_size=16, embed_dim=320, depth=14, num_heads=5, init_values=1e-5, mlp_ratio=5.6,
        class_token=False, no_embed_class=True, reg_tokens=1, global_pool='avg', attn_layer='diff',
        img_size=256,
    )
    return _create_vision_transformer(
        'vit_dlittle_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_little_patch16_reg4_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=320, depth=14, num_heads=5, init_values=1e-5, mlp_ratio=5.6,
        class_token=False, no_embed_class=True, reg_tokens=4, global_pool='avg', img_size=256,
    )
    return _create_vision_transformer(
        'vit_little_patch16_reg4_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_medium_patch16_reg4_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=512, depth=12, num_heads=8, init_values=1e-5,
        class_token=False, no_embed_class=True, reg_tokens=4, global_pool='avg', img_size=256,
    )
    return _create_vision_transformer(
        'vit_medium_patch16_reg4_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=16, embed_dim=1024, depth=24, num_heads=16)
    return _create_vision_transformer('vit_large_patch16_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=14, embed_dim=1024, depth=24, num_heads=16)
    return _create_vision_transformer('vit_large_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(patch_size=14, embed_dim=1280, depth=32, num_heads=16)
    return _create_vision_transformer('vit_huge_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_siglip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362,
        class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_so400m_patch14_siglip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def test_vit(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """Minimal test ViT (reference vision_transformer.py:4802)."""
    model_args = dict(img_size=160, patch_size=16, embed_dim=64, depth=2, num_heads=2, mlp_ratio=3)
    return _create_vision_transformer('test_vit', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def test_vit2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """Test ViT w/ global avg pool + reg tokens + layer scale."""
    model_args = dict(
        img_size=160, patch_size=16, embed_dim=64, depth=2, num_heads=2, mlp_ratio=3,
        class_token=False, reg_tokens=1, global_pool='avg', init_values=1e-5,
    )
    return _create_vision_transformer('test_vit2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def test_vit3(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """Test ViT w/ qk-norm + map pooling."""
    model_args = dict(
        img_size=160, patch_size=16, embed_dim=96, depth=9, num_heads=3, mlp_ratio=2,
        class_token=False, reg_tokens=1, global_pool='map', qk_norm=True,
    )
    return _create_vision_transformer('test_vit3', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def test_vit4(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """Test ViT w/ dynamic img size + patch dropout."""
    model_args = dict(
        img_size=160, patch_size=16, embed_dim=64, depth=2, num_heads=2, mlp_ratio=3,
        dynamic_img_size=True, patch_drop_rate=0.25,
    )
    return _create_vision_transformer('test_vit4', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch32_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Small (ViT-S/32) at 384x384."""
    model_args = dict(patch_size=32, embed_dim=384, depth=12, num_heads=6)
    return _create_vision_transformer('vit_small_patch32_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch8_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Small (ViT-S/8)"""
    model_args = dict(patch_size=8, embed_dim=384, depth=12, num_heads=6)
    return _create_vision_transformer('vit_small_patch8_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base model (ViT-B/32) from original paper (https://arxiv.org/abs/2010.11929)."""
    model_args = dict(patch_size=32, embed_dim=768, depth=12, num_heads=12)
    return _create_vision_transformer('vit_base_patch32_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch32_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/32) from original paper (https://arxiv.org/abs/2010.11929). No pretrained weights."""
    model_args = dict(patch_size=32, embed_dim=1024, depth=24, num_heads=16)
    return _create_vision_transformer('vit_large_patch32_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch32_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/32) from original paper (https://arxiv.org/abs/2010.11929)."""
    model_args = dict(patch_size=32, embed_dim=1024, depth=24, num_heads=16)
    return _create_vision_transformer('vit_large_patch32_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/16) from original paper (https://arxiv.org/abs/2010.11929)."""
    model_args = dict(patch_size=16, embed_dim=1024, depth=24, num_heads=16)
    return _create_vision_transformer('vit_large_patch16_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giant_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Giant (little-g) model (ViT-g/14) from `Scaling Vision Transformers` - https://arxiv.org/abs/2106.04560"""
    model_args = dict(patch_size=14, embed_dim=1408, mlp_ratio=48/11, depth=40, num_heads=16)
    return _create_vision_transformer('vit_giant_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_gigantic_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Gigantic (big-G) model (ViT-G/14) from `Scaling Vision Transformers` - https://arxiv.org/abs/2106.04560"""
    model_args = dict(patch_size=14, embed_dim=1664, mlp_ratio=64/13, depth=48, num_heads=16)
    return _create_vision_transformer('vit_gigantic_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_224_miil(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base (ViT-B/16) from original paper (https://arxiv.org/abs/2010.11929)."""
    model_args = dict(patch_size=16, embed_dim=768, depth=12, num_heads=12, qkv_bias=False)
    return _create_vision_transformer('vit_base_patch16_224_miil', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_medium_patch16_gap_240(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Medium (ViT-M/16) w/o class token, w/ avg-pool @ 240x240"""
    model_args = dict(
        patch_size=16, embed_dim=512, depth=12, num_heads=8, class_token=False,
        global_pool='avg', qkv_bias=False, init_values=1e-6, fc_norm=False)
    return _create_vision_transformer('vit_medium_patch16_gap_240', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_medium_patch16_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Medium (ViT-M/16) w/o class token, w/ avg-pool @ 256x256"""
    model_args = dict(
        patch_size=16, embed_dim=512, depth=12, num_heads=8, class_token=False,
        global_pool='avg', qkv_bias=False, init_values=1e-6, fc_norm=False)
    return _create_vision_transformer('vit_medium_patch16_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_medium_patch16_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Medium (ViT-M/16) w/o class token, w/ avg-pool @ 384x384"""
    model_args = dict(
        patch_size=16, embed_dim=512, depth=12, num_heads=8, class_token=False,
        global_pool='avg', qkv_bias=False, init_values=1e-6, fc_norm=False)
    return _create_vision_transformer('vit_medium_patch16_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_betwixt_patch16_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Betwixt (ViT-b/16) w/o class token, w/ avg-pool @ 256x256"""
    model_args = dict(
        patch_size=16, embed_dim=640, depth=12, num_heads=10, class_token=False,
        global_pool='avg', qkv_bias=False, init_values=1e-6, fc_norm=False)
    return _create_vision_transformer('vit_betwixt_patch16_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_gap_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base (ViT-B/16) w/o class token, w/ avg-pool @ 224x224"""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=16, class_token=False, global_pool='avg', fc_norm=False)
    return _create_vision_transformer('vit_base_patch16_gap_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch14_gap_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Huge model (ViT-H/14) w/ no class token, avg pool"""
    model_args = dict(
        patch_size=14, embed_dim=1280, depth=32, num_heads=16, class_token=False, global_pool='avg', fc_norm=False)
    return _create_vision_transformer('vit_huge_patch14_gap_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch16_gap_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Huge model (ViT-H/16) w/ no class token, avg pool @ 448x448"""
    model_args = dict(
        patch_size=16, embed_dim=1280, depth=32, num_heads=16, class_token=False, global_pool='avg', fc_norm=False)
    return _create_vision_transformer('vit_huge_patch16_gap_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giant_patch16_gap_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Giant (little-gg) model (ViT-g/16) w/ no class token, avg pool"""
    model_args = dict(
        patch_size=16, embed_dim=1408, depth=40, num_heads=16, mlp_ratio=48/11,
        class_token=False, global_pool='avg', fc_norm=False)
    return _create_vision_transformer('vit_giant_patch16_gap_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_xsmall_patch16_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(embed_dim=256, depth=10, num_heads=4, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_xsmall_patch16_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_medium_patch32_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=32, embed_dim=512, depth=12, num_heads=8, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_medium_patch32_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_medium_patch16_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(embed_dim=512, depth=12, num_heads=8, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_medium_patch16_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_betwixt_patch32_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=32, embed_dim=640, depth=12, num_heads=10, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_betwixt_patch32_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/32 CLIP image tower @ 224x224"""
    model_args = dict(
        patch_size=32, embed_dim=768, depth=12, num_heads=12, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_base_patch32_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_clip_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/32 CLIP image tower @ 256x256"""
    model_args = dict(
        patch_size=32, embed_dim=768, depth=12, num_heads=12, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_base_patch32_clip_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_clip_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/32 CLIP image tower @ 384x384"""
    model_args = dict(
        patch_size=32, embed_dim=768, depth=12, num_heads=12, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_base_patch32_clip_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_clip_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/32 CLIP image tower @ 448x448"""
    model_args = dict(
        patch_size=32, embed_dim=768, depth=12, num_heads=12, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_base_patch32_clip_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/16 CLIP image tower"""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_base_patch16_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_clip_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/16 CLIP image tower @ 384x384"""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_base_patch16_clip_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_plus_clip_240(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base (ViT-B/16+) CLIP image tower @ 240x240"""
    model_args = dict(
        patch_size=16, embed_dim=896, depth=12, num_heads=14, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_base_patch16_plus_clip_240', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/14) CLIP image tower"""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=16, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_large_patch14_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_clip_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/14) CLIP image tower @ 336x336"""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=16, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_large_patch14_clip_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch14_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Huge model (ViT-H/14) CLIP image tower."""
    model_args = dict(
        patch_size=14, embed_dim=1280, depth=32, num_heads=16, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_huge_patch14_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch14_clip_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Huge model (ViT-H/14) CLIP image tower @ 336x336"""
    model_args = dict(
        patch_size=14, embed_dim=1280, depth=32, num_heads=16, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_huge_patch14_clip_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch14_clip_378(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Huge model (ViT-H/14) CLIP image tower @ 378x378"""
    model_args = dict(
        patch_size=14, embed_dim=1280, depth=32, num_heads=16, pre_norm=True, norm_layer=partial(LayerNorm, eps=1e-5))
    return _create_vision_transformer('vit_huge_patch14_clip_378', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giant_patch14_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Giant (little-g) model (ViT-g/14) from `Scaling Vision Transformers` - https://arxiv.org/abs/2106.04560"""
    model_args = dict(
        patch_size=14, embed_dim=1408, mlp_ratio=48/11, depth=40, num_heads=16, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_vision_transformer('vit_giant_patch14_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_gigantic_patch14_clip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-bigG model (ViT-G/14) from `Scaling Vision Transformers` - https://arxiv.org/abs/2106.04560"""
    model_args = dict(
        patch_size=14, embed_dim=1664, mlp_ratio=64/13, depth=48, num_heads=16, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_vision_transformer('vit_gigantic_patch14_clip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_gigantic_patch14_clip_378(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-bigG model (ViT-G/14) from `Scaling Vision Transformers` - https://arxiv.org/abs/2106.04560"""
    model_args = dict(
        patch_size=14, embed_dim=1664, mlp_ratio=64/13, depth=48, num_heads=16, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_vision_transformer('vit_gigantic_patch14_clip_378', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_clip_quickgelu_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/32 CLIP image tower @ 224x224"""
    model_args = dict(
        patch_size=32, embed_dim=768, depth=12, num_heads=12, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5), act_layer='quick_gelu'
    )
    return _create_vision_transformer('vit_base_patch32_clip_quickgelu_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_clip_quickgelu_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/16 CLIP image tower w/ QuickGELU act"""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5), act_layer='quick_gelu'
    )
    return _create_vision_transformer('vit_base_patch16_clip_quickgelu_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_clip_quickgelu_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/14) CLIP image tower w/ QuickGELU act"""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=16, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5), act_layer='quick_gelu'
    )
    return _create_vision_transformer('vit_large_patch14_clip_quickgelu_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_clip_quickgelu_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/14) CLIP image tower @ 336x336 w/ QuickGELU act"""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=16, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5), act_layer='quick_gelu'
    )
    return _create_vision_transformer('vit_large_patch14_clip_quickgelu_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch14_clip_quickgelu_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Huge model (ViT-H/14) CLIP image tower w/ QuickGELU act."""
    model_args = dict(
        patch_size=14, embed_dim=1280, depth=32, num_heads=16, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5), act_layer='quick_gelu'
    )
    return _create_vision_transformer('vit_huge_patch14_clip_quickgelu_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch14_clip_quickgelu_378(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Huge model (ViT-H/14) CLIP image tower @ 378x378 w/ QuickGELU act"""
    model_args = dict(
        patch_size=14, embed_dim=1280, depth=32, num_heads=16, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5), act_layer='quick_gelu'
    )
    return _create_vision_transformer('vit_huge_patch14_clip_quickgelu_378', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_gigantic_patch14_clip_quickgelu_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-bigG model (ViT-G/14) w/ QuickGELU act"""
    model_args = dict(
        patch_size=14, embed_dim=1664, mlp_ratio=64/13, depth=48, num_heads=16, pre_norm=True,
        norm_layer=partial(LayerNorm, eps=1e-5), act_layer='quick_gelu'
    )
    return _create_vision_transformer('vit_gigantic_patch14_clip_quickgelu_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_plus_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base (ViT-B/32+)"""
    model_args = dict(patch_size=32, embed_dim=896, depth=12, num_heads=14, init_values=1e-5)
    return _create_vision_transformer('vit_base_patch32_plus_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_plus_240(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base (ViT-B/16+)"""
    model_args = dict(patch_size=16, embed_dim=896, depth=12, num_heads=14, init_values=1e-5)
    return _create_vision_transformer('vit_base_patch16_plus_240', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_rpn_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base (ViT-B/16) w/ residual post-norm"""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, qkv_bias=False, init_values=1e-5,
        class_token=False, block_fn=ResPostBlock, global_pool='avg')
    return _create_vision_transformer('vit_base_patch16_rpn_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_36x1_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base w/ LayerScale + 36 x 1 (36 block serial) config. Experimental, may remove."""
    model_args = dict(patch_size=16, embed_dim=384, depth=36, num_heads=6, init_values=1e-5)
    return _create_vision_transformer('vit_small_patch16_36x1_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch16_18x2_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Small w/ LayerScale + 18 x 2 (36 block parallel) config. Experimental, may remove."""
    model_args = dict(
        patch_size=16, embed_dim=384, depth=18, num_heads=6, init_values=1e-5, block_fn=ParallelThingsBlock)
    return _create_vision_transformer('vit_small_patch16_18x2_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_18x2_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Base w/ LayerScale + 18 x 2 (36 block parallel) config. Experimental, may remove."""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=18, num_heads=12, init_values=1e-5, block_fn=ParallelThingsBlock)
    return _create_vision_transformer('vit_base_patch16_18x2_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva_large_patch14_196(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """EVA-large model https://arxiv.org/abs/2211.07636 /via MAE MIM pretrain"""
    model_args = dict(patch_size=14, embed_dim=1024, depth=24, num_heads=16, global_pool='avg')
    return _create_vision_transformer('eva_large_patch14_196', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def eva_large_patch14_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """EVA-large model https://arxiv.org/abs/2211.07636 via MAE MIM pretrain"""
    model_args = dict(patch_size=14, embed_dim=1024, depth=24, num_heads=16, global_pool='avg')
    return _create_vision_transformer('eva_large_patch14_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def flexivit_small(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """FlexiViT-Small"""
    model_args = dict(patch_size=16, embed_dim=384, depth=12, num_heads=6, no_embed_class=True)
    return _create_vision_transformer('flexivit_small', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def flexivit_base(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """FlexiViT-Base"""
    model_args = dict(patch_size=16, embed_dim=768, depth=12, num_heads=12, no_embed_class=True)
    return _create_vision_transformer('flexivit_base', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def flexivit_large(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """FlexiViT-Large"""
    model_args = dict(patch_size=16, embed_dim=1024, depth=24, num_heads=16, no_embed_class=True)
    return _create_vision_transformer('flexivit_large', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_xp_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/14) w/ parallel blocks and qk norm enabled."""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, pre_norm=True, no_embed_class=True,
        norm_layer=RmsNorm, block_fn=ParallelScalingBlock, qkv_bias=False, qk_norm=True,
    )
    return _create_vision_transformer('vit_base_patch16_xp_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_xp_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Large model (ViT-L/14) w/ parallel blocks and qk norm enabled."""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=16, pre_norm=True, no_embed_class=True,
        norm_layer=RmsNorm, block_fn=ParallelScalingBlock, qkv_bias=False, qk_norm=True,
    )
    return _create_vision_transformer('vit_large_patch14_xp_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_huge_patch14_xp_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-Huge model (ViT-H/14) w/ parallel blocks and qk norm enabled."""
    model_args = dict(
        patch_size=14, embed_dim=1280, depth=32, num_heads=16, pre_norm=True, no_embed_class=True,
        norm_layer=RmsNorm, block_fn=ParallelScalingBlock, qkv_bias=False, qk_norm=True,
    )
    return _create_vision_transformer('vit_huge_patch14_xp_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch14_dinov2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-S/14 for DINOv2"""
    model_args = dict(patch_size=14, embed_dim=384, depth=12, num_heads=6, init_values=1e-5)
    return _create_vision_transformer('vit_small_patch14_dinov2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch14_dinov2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/14 for DINOv2"""
    model_args = dict(patch_size=14, embed_dim=768, depth=12, num_heads=12, init_values=1e-5)
    return _create_vision_transformer('vit_base_patch14_dinov2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_dinov2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-L/14 for DINOv2"""
    model_args = dict(patch_size=14, embed_dim=1024, depth=24, num_heads=16, init_values=1e-5)
    return _create_vision_transformer('vit_large_patch14_dinov2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giant_patch14_dinov2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-G/14 for DINOv2"""
    model_args = dict(
        patch_size=14, embed_dim=1536, depth=40, num_heads=24, init_values=1e-5,
        mlp_ratio=2.66667 * 2, mlp_layer=SwiGLUPacked, act_layer='silu'
    )
    return _create_vision_transformer('vit_giant_patch14_dinov2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_small_patch14_reg4_dinov2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-S/14 for DINOv2 w/ 4 registers"""
    model_args = dict(
        patch_size=14, embed_dim=384, depth=12, num_heads=6, init_values=1e-5,
        reg_tokens=4, no_embed_class=True,
    )
    return _create_vision_transformer('vit_small_patch14_reg4_dinov2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch14_reg4_dinov2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/14 for DINOv2 w/ 4 registers"""
    model_args = dict(
        patch_size=14, embed_dim=768, depth=12, num_heads=12, init_values=1e-5,
        reg_tokens=4, no_embed_class=True,
    )
    return _create_vision_transformer('vit_base_patch14_reg4_dinov2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_reg4_dinov2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-L/14 for DINOv2 w/ 4 registers"""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=16, init_values=1e-5,
        reg_tokens=4, no_embed_class=True,
    )
    return _create_vision_transformer('vit_large_patch14_reg4_dinov2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giant_patch14_reg4_dinov2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-G/14 for DINOv2"""
    model_args = dict(
        patch_size=14, embed_dim=1536, depth=40, num_heads=24, init_values=1e-5, mlp_ratio=2.66667 * 2,
        mlp_layer=SwiGLUPacked, act_layer='silu', reg_tokens=4, no_embed_class=True,
    )
    return _create_vision_transformer('vit_giant_patch14_reg4_dinov2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch14_reg1_tipsv2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-B/14 for TIPSv2 (DINOv2-style w/ 1 register token, LayerScale init=1.0)."""
    model_args = dict(
        patch_size=14, embed_dim=768, depth=12, num_heads=12, init_values=1.0,
        reg_tokens=1, no_embed_class=True,
    )
    return _create_vision_transformer('vit_base_patch14_reg1_tipsv2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch14_reg1_tipsv2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-L/14 for TIPSv2 (DINOv2-style w/ 1 register token, LayerScale init=1.0)."""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=16, init_values=1.0,
        reg_tokens=1, no_embed_class=True,
    )
    return _create_vision_transformer('vit_large_patch14_reg1_tipsv2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_reg1_tipsv2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """SoViT-400M/14 for TIPSv2 (DINOv2-style w/ 1 register token, LayerScale init=1.0)."""
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, init_values=1.0,
        mlp_ratio=4304 / 1152, reg_tokens=1, no_embed_class=True,
    )
    return _create_vision_transformer('vit_so400m_patch14_reg1_tipsv2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giant_patch14_reg1_tipsv2(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT-G/14 for TIPSv2 (DINOv2-style w/ SwiGLU FFN, 1 register token, LayerScale init=1.0)."""
    model_args = dict(
        patch_size=14, embed_dim=1536, depth=40, num_heads=24, init_values=1.0,
        mlp_ratio=2.66667 * 2, mlp_layer=SwiGLUPacked, act_layer='silu',
        reg_tokens=1, no_embed_class=True,
    )
    return _create_vision_transformer('vit_giant_patch14_reg1_tipsv2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_siglip_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=32, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='map',
        act_layer='gelu_tanh',
    )
    return _create_vision_transformer('vit_base_patch32_siglip_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_siglip_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_base_patch16_siglip_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_siglip_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_base_patch16_siglip_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_siglip_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_base_patch16_siglip_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_siglip_512(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_base_patch16_siglip_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_siglip_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1024, depth=24, num_heads=16, class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_large_patch16_siglip_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_siglip_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1024, depth=24, num_heads=16, class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_large_patch16_siglip_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_siglip_512(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1024, depth=24, num_heads=16, class_token=False, global_pool='map',
        act_layer='gelu_tanh'
    )
    return _create_vision_transformer('vit_large_patch16_siglip_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_siglip_378(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362, class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_so400m_patch14_siglip_378', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_siglip_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362, class_token=False, global_pool='map',
    )
    return _create_vision_transformer('vit_so400m_patch14_siglip_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch16_siglip_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362, class_token=False, global_pool='map',
        act_layer='gelu_tanh',
    )
    return _create_vision_transformer('vit_so400m_patch16_siglip_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch16_siglip_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362, class_token=False, global_pool='map',
        act_layer='gelu_tanh',
    )
    return _create_vision_transformer('vit_so400m_patch16_siglip_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch16_siglip_512(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362, class_token=False, global_pool='map',
        act_layer='gelu_tanh',
    )
    return _create_vision_transformer('vit_so400m_patch16_siglip_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giantopt_patch16_siglip_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1536, depth=40, num_heads=16, class_token=False, global_pool='map',
        act_layer='gelu_tanh',
    )
    return _create_vision_transformer('vit_giantopt_patch16_siglip_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giantopt_patch16_siglip_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1536, depth=40, num_heads=16, class_token=False, global_pool='map',
        act_layer='gelu_tanh',
    )
    return _create_vision_transformer('vit_giantopt_patch16_siglip_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch32_siglip_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=32, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='avg', fc_norm=False,
        act_layer='gelu_tanh',
    )
    return _create_vision_transformer('vit_base_patch32_siglip_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_siglip_gap_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_base_patch16_siglip_gap_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_siglip_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_base_patch16_siglip_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_siglip_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_base_patch16_siglip_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_siglip_gap_512(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_base_patch16_siglip_gap_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_siglip_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=16, embed_dim=1024, depth=24, num_heads=16, class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_large_patch16_siglip_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_siglip_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=16, embed_dim=1024, depth=24, num_heads=16, class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_large_patch16_siglip_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_large_patch16_siglip_gap_512(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1024, depth=24, num_heads=16, class_token=False,
        global_pool='avg', fc_norm=False, act_layer='gelu_tanh'
    )
    return _create_vision_transformer('vit_large_patch16_siglip_gap_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_siglip_gap_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362,
        class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_so400m_patch14_siglip_gap_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_siglip_gap_378(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362,
        class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_so400m_patch14_siglip_gap_378', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_siglip_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362,
        class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_so400m_patch14_siglip_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_siglip_gap_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362,
        class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_so400m_patch14_siglip_gap_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch14_siglip_gap_896(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=14, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362,
        class_token=False, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_so400m_patch14_siglip_gap_896', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch16_siglip_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """A SigLIP variant of ViT with global average pooling (GAP) instead of attention pooling (MAP)."""
    model_args = dict(
        patch_size=16, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362,
        class_token=False, global_pool='avg', fc_norm=False, act_layer='gelu_tanh',
    )
    return _create_vision_transformer('vit_so400m_patch16_siglip_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch16_siglip_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362, class_token=False,
        global_pool='avg', fc_norm=False, act_layer='gelu_tanh'
    )
    return _create_vision_transformer('vit_so400m_patch16_siglip_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so400m_patch16_siglip_gap_512(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1152, depth=27, num_heads=16, mlp_ratio=3.7362, class_token=False,
        global_pool='avg', fc_norm=False, act_layer='gelu_tanh'
    )
    return _create_vision_transformer('vit_so400m_patch16_siglip_gap_512', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giantopt_patch16_siglip_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1536, depth=40, num_heads=16, class_token=False,
        global_pool='avg', fc_norm=False, act_layer='gelu_tanh'
    )
    return _create_vision_transformer('vit_giantopt_patch16_siglip_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_giantopt_patch16_siglip_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=1536, depth=40, num_heads=16, class_token=False,
        global_pool='avg', fc_norm=False, act_layer='gelu_tanh'
    )
    return _create_vision_transformer('vit_giantopt_patch16_siglip_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_wee_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=256, depth=14, num_heads=4, init_values=1e-5, mlp_ratio=5,
        class_token=False, no_embed_class=True, reg_tokens=1, global_pool='avg',
    )
    return _create_vision_transformer('vit_wee_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_dwee_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=256, depth=14, num_heads=4, init_values=1e-5, mlp_ratio=5,
        class_token=False, no_embed_class=True, reg_tokens=1, global_pool='avg', attn_layer='diff',
    )
    return _create_vision_transformer('vit_dwee_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_pwee_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=256, depth=16, num_heads=4, init_values=1e-5, mlp_ratio=5,
        class_token=False, no_embed_class=True, reg_tokens=1, global_pool='avg', block_fn=ParallelScalingBlock,
    )
    return _create_vision_transformer('vit_pwee_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_dpwee_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=256, depth=16, num_heads=4, init_values=1e-5, mlp_ratio=5,
        class_token=False, no_embed_class=True, reg_tokens=1, global_pool='avg', block_fn=DiffParallelScalingBlock,
    )
    return _create_vision_transformer('vit_dpwee_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_little_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=320, depth=14, num_heads=5, init_values=1e-5, mlp_ratio=5.6,
        class_token=False, no_embed_class=True, reg_tokens=1, global_pool='avg',
    )
    return _create_vision_transformer('vit_little_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_medium_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=512, depth=12, num_heads=8, init_values=1e-5,
        class_token=False, no_embed_class=True, reg_tokens=1, global_pool='avg',
    )
    return _create_vision_transformer('vit_medium_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_mediumd_patch16_reg4_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=512, depth=20, num_heads=8, init_values=1e-5,
        class_token=False, no_embed_class=True, reg_tokens=4, global_pool='avg',
    )
    return _create_vision_transformer('vit_mediumd_patch16_reg4_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_mediumd_patch16_reg4_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=512, depth=20, num_heads=8, init_values=1e-5,
        class_token=False, no_embed_class=True, reg_tokens=4, global_pool='avg',
    )
    return _create_vision_transformer('vit_mediumd_patch16_reg4_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_betwixt_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=640, depth=12, num_heads=10, init_values=1e-5,
        class_token=False, no_embed_class=True, reg_tokens=1, global_pool='avg',
    )
    return _create_vision_transformer('vit_betwixt_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_betwixt_patch16_reg4_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=640, depth=12, num_heads=10, init_values=1e-5,
        class_token=False, no_embed_class=True, reg_tokens=4, global_pool='avg',
    )
    return _create_vision_transformer('vit_betwixt_patch16_reg4_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_betwixt_patch16_reg4_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=640, depth=12, num_heads=10, init_values=1e-5,
        class_token=False, no_embed_class=True, reg_tokens=4, global_pool='avg',
    )
    return _create_vision_transformer('vit_betwixt_patch16_reg4_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_base_patch16_reg4_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, class_token=False,
        no_embed_class=True, global_pool='avg', reg_tokens=4,
    )
    return _create_vision_transformer('vit_base_patch16_reg4_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so150m_patch16_reg4_map_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """SO150M (shape optimized, but diff than paper def, optimized for GPU)"""
    model_args = dict(
        patch_size=16, embed_dim=896, depth=18, num_heads=14, mlp_ratio=2.572,
        class_token=False, reg_tokens=4, global_pool='map',
    )
    return _create_vision_transformer('vit_so150m_patch16_reg4_map_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so150m_patch16_reg4_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """SO150M (shape optimized, but diff than paper def, optimized for GPU)"""
    model_args = dict(
        patch_size=16, embed_dim=896, depth=18, num_heads=14, mlp_ratio=2.572,
        class_token=False, reg_tokens=4, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_so150m_patch16_reg4_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so150m_patch16_reg4_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """SO150M (shape optimized, but diff than paper def, optimized for GPU)"""
    model_args = dict(
        patch_size=16, embed_dim=896, depth=18, num_heads=14, mlp_ratio=2.572,
        class_token=False, reg_tokens=4, global_pool='avg', fc_norm=False,
    )
    return _create_vision_transformer('vit_so150m_patch16_reg4_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so150m2_patch16_reg1_gap_256(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """SO150M v2 (shape optimized, but diff than paper def, optimized for GPU)"""
    model_args = dict(
        patch_size=16, embed_dim=832, depth=21, num_heads=13, mlp_ratio=34/13, init_values=1e-5,
        qkv_bias=False, class_token=False, reg_tokens=1, global_pool='avg',
    )
    return _create_vision_transformer('vit_so150m2_patch16_reg1_gap_256', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so150m2_patch16_reg1_gap_384(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """SO150M v2 (shape optimized, but diff than paper def, optimized for GPU)"""
    model_args = dict(
        patch_size=16, embed_dim=832, depth=21, num_heads=13, mlp_ratio=34/13, init_values=1e-5,
        qkv_bias=False, class_token=False, reg_tokens=1, global_pool='avg',
    )
    return _create_vision_transformer('vit_so150m2_patch16_reg1_gap_384', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_so150m2_patch16_reg1_gap_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """SO150M v2 (shape optimized, but diff than paper def, optimized for GPU)"""
    model_args = dict(
        patch_size=16, embed_dim=832, depth=21, num_heads=13, mlp_ratio=34/13, init_values=1e-5,
        qkv_bias=False, class_token=False, reg_tokens=1, global_pool='avg',
    )
    return _create_vision_transformer('vit_so150m2_patch16_reg1_gap_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def vit_intern300m_patch14_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=16,
        init_values=0.1, final_norm=False, dynamic_img_size=True,
    )
    return _create_vision_transformer('vit_intern300m_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_large_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT Large AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=8, class_token=False, fc_norm=False,
        mlp_ratio=2.75, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_large_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_huge_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT Huge AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=1536, depth=24, num_heads=12, class_token=False, fc_norm=False,
        mlp_ratio=2.6667, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_huge_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_1b_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT 1B AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=2048, depth=24, num_heads=16, class_token=False, fc_norm=False,
        mlp_ratio=2.75, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_1b_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_3b_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT 3B AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=3072, depth=24, num_heads=24, class_token=False, fc_norm=False,
        mlp_ratio=2.6667, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_3b_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_large_patch14_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT Large AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=8, class_token=False, fc_norm=False,
        mlp_ratio=2.75, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_large_patch14_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_huge_patch14_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT Huge AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=1536, depth=24, num_heads=12, class_token=False, fc_norm=False,
        mlp_ratio=2.6667, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_huge_patch14_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_1b_patch14_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT 1B AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=2048, depth=24, num_heads=16, class_token=False, fc_norm=False,
        mlp_ratio=2.75, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_1b_patch14_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_3b_patch14_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT 3B AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=3072, depth=24, num_heads=24, class_token=False, fc_norm=False,
        mlp_ratio=2.6667, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_3b_patch14_336', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_large_patch14_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT Large AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=1024, depth=24, num_heads=8, class_token=False, fc_norm=False,
        mlp_ratio=2.75, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_large_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_huge_patch14_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT Huge AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=1536, depth=24, num_heads=12, class_token=False, fc_norm=False,
        mlp_ratio=2.6667, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_huge_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_1b_patch14_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT 1B AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=2048, depth=24, num_heads=16, class_token=False, fc_norm=False,
        mlp_ratio=2.75, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_1b_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def aimv2_3b_patch14_448(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """ViT 3B AIM-v2 model"""
    model_args = dict(
        patch_size=14, embed_dim=3072, depth=24, num_heads=24, class_token=False, fc_norm=False,
        mlp_ratio=2.6667, global_pool='avg', qkv_bias=False, proj_bias=False, act_layer='silu',
        norm_layer=partial(RmsNorm, eps=1e-5), embed_norm_layer=partial(RmsNorm, eps=1e-5), mlp_layer=SwiGLU,
    )
    return _create_vision_transformer('aimv2_3b_patch14_448', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def beit3_base_patch16_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """BEiT3 Base model (ViT-Base size) with patch size 16x16."""
    model_args = dict(
        patch_size=16, embed_dim=768, depth=12, num_heads=12, mlp_ratio=4,
        scale_attn_norm=True, scale_mlp_norm=True, class_token=True, global_pool='avg',
        norm_layer=partial(LayerNorm, eps=1e-5)
    )
    return _create_vision_transformer('beit3_base_patch16_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def beit3_large_patch16_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """BEiT3 Large model (ViT-Large size) with patch size 16x16."""
    model_args = dict(
        patch_size=16, embed_dim=1024, depth=24, num_heads=16, mlp_ratio=4,
        scale_attn_norm=True, scale_mlp_norm=True, class_token=True, global_pool='avg',
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_vision_transformer('beit3_large_patch16_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def beit3_giant_patch14_224(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """BEiT3 Giant model with patch size 14x14."""
    model_args = dict(
        patch_size=14, embed_dim=1408, depth=40, num_heads=16, mlp_ratio=4.3637,
        scale_attn_norm=True, scale_mlp_norm=True, class_token=True, global_pool='avg',
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_vision_transformer('beit3_giant_patch14_224', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def beit3_giant_patch14_336(pretrained: bool = False, **kwargs) -> VisionTransformer:
    """BEiT3 Giant model with patch size 14x14 and image size 336x336."""
    model_args = dict(
        img_size=336, patch_size=14, embed_dim=1408, depth=40, num_heads=16, mlp_ratio=4.3637,
        scale_attn_norm=True, scale_mlp_norm=True, class_token=True, global_pool='avg',
        norm_layer=partial(LayerNorm, eps=1e-5),
    )
    return _create_vision_transformer('beit3_giant_patch14_336', pretrained=pretrained, **dict(model_args, **kwargs))
