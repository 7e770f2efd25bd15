"""Pyramid Vision Transformer v2 (reference: timm/models/pvt_v2.py:1-594),
TPU-native NHWC/NLC.

Overlapping patch embeds between stages, spatial-reduction (strided-conv or
adaptive-pool 'linear') attention on flattened tokens, and an MLP with a
depthwise 3x3 conv between fc1 and the activation. Tokens stay NLC; the dw
conv reshapes to NHWC with static feat sizes, so everything compiles to fixed
shapes.
"""
from __future__ import annotations

import math
from typing import Callable, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import (
    DropPath, LayerNorm, calculate_drop_path_rates, create_conv2d, get_act_fn,
    scaled_dot_product_attention, to_2tuple, to_ntuple, trunc_normal_, zeros_,
)
from ..layers.drop import Dropout
from ._builder import build_model_with_cfg
from ._manipulate import (
    BlockStackError, resolve_stage_scan, scan_stage_stack, warn_scan_fallback,
)
from ._features import feature_take_indices
from ._registry import generate_default_cfgs, register_model

__all__ = ['PyramidVisionTransformerV2']


def _adaptive_avg_pool(x, out_size: int):
    """NHWC adaptive average pool to (out, out) with torch's bin edges."""
    B, H, W, C = x.shape
    if H % out_size == 0 and W % out_size == 0:
        kh, kw = H // out_size, W // out_size
        out = jax.lax.reduce_window(
            x, 0.0, jax.lax.add, (1, kh, kw, 1), (1, kh, kw, 1), 'VALID')
        return out / (kh * kw)
    rows = []
    for i in range(out_size):
        h0, h1 = (i * H) // out_size, -(-((i + 1) * H) // out_size)
        cols = []
        for j in range(out_size):
            w0, w1 = (j * W) // out_size, -(-((j + 1) * W) // out_size)
            cols.append(x[:, h0:h1, w0:w1].mean(axis=(1, 2)))
        rows.append(jnp.stack(cols, axis=1))
    return jnp.stack(rows, axis=1)


class MlpWithDepthwiseConv(nnx.Module):
    """fc1 → (relu) → dw3x3 → act → fc2 (reference pvt_v2.py:27-60)."""

    def __init__(self, in_features, hidden_features=None, out_features=None,
                 act_layer='gelu', drop=0.0, extra_relu=False,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        out_features = out_features or in_features
        hidden_features = hidden_features or in_features
        linear = lambda i, o: nnx.Linear(
            i, o, kernel_init=trunc_normal_(std=0.02), bias_init=zeros_,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.fc1 = linear(in_features, hidden_features)
        self.extra_relu = extra_relu
        self.dwconv = create_conv2d(
            hidden_features, hidden_features, 3, padding=1, depthwise=True, bias=True,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.act = get_act_fn(act_layer)
        self.fc2 = linear(hidden_features, out_features)
        self.drop = Dropout(drop, rngs=rngs)

    def __call__(self, x, feat_size):
        x = self.fc1(x)
        B, N, C = x.shape
        x = x.reshape(B, feat_size[0], feat_size[1], C)
        if self.extra_relu:
            x = jax.nn.relu(x)
        x = self.dwconv(x).reshape(B, N, C)
        x = self.drop(self.act(x))
        return self.drop(self.fc2(x))


class PvtAttention(nnx.Module):
    """Spatial-reduction attention (reference pvt_v2.py:62-134): kv come from
    a strided-conv (sr_ratio) or adaptive-pool-7 ('linear') reduced map."""

    def __init__(self, dim, num_heads=8, sr_ratio=1, linear_attn=False, qkv_bias=True,
                 attn_drop=0.0, proj_drop=0.0, *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        assert dim % num_heads == 0
        self.dim = dim
        self.num_heads = num_heads
        self.head_dim = dim // num_heads
        self.scale = self.head_dim ** -0.5
        self.linear_attn = linear_attn
        self.sr_ratio = sr_ratio
        linear = lambda i, o, b=True: nnx.Linear(
            i, o, use_bias=b, kernel_init=trunc_normal_(std=0.02), bias_init=zeros_,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.q = linear(dim, dim, qkv_bias)
        self.kv = linear(dim, dim * 2, qkv_bias)
        self.attn_drop = Dropout(attn_drop, rngs=rngs)
        self.proj = linear(dim, dim)
        self.proj_drop = Dropout(proj_drop, rngs=rngs)
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        if not linear_attn:
            if sr_ratio > 1:
                self.sr = create_conv2d(dim, dim, sr_ratio, stride=sr_ratio, padding=0, bias=True, **kw)
                self.norm = LayerNorm(dim, eps=1e-5, rngs=rngs)
            else:
                self.sr = None
                self.norm = None
        else:
            self.sr = create_conv2d(dim, dim, 1, stride=1, padding=0, bias=True, **kw)
            self.norm = LayerNorm(dim, eps=1e-5, rngs=rngs)

    def __call__(self, x, feat_size):
        B, N, C = x.shape
        H, W = feat_size
        q = self.q(x).reshape(B, N, self.num_heads, self.head_dim).transpose(0, 2, 1, 3)
        if self.linear_attn:
            xs = _adaptive_avg_pool(x.reshape(B, H, W, C), 7)
            xs = self.sr(xs).reshape(B, -1, C)
            xs = jax.nn.gelu(self.norm(xs), approximate=False)
            kv_in = xs
        elif self.sr is not None:
            xs = self.sr(x.reshape(B, H, W, C)).reshape(B, -1, C)
            kv_in = self.norm(xs)
        else:
            kv_in = x
        kv = self.kv(kv_in).reshape(B, -1, 2, self.num_heads, self.head_dim).transpose(2, 0, 3, 1, 4)
        k, v = kv[0], kv[1]
        from ..layers.drop import dropout_rng_key
        dropout_p = 0.0 if self.attn_drop.deterministic else self.attn_drop.rate
        dropout_key = dropout_rng_key(self.attn_drop) if dropout_p > 0.0 else None
        x = scaled_dot_product_attention(
            q, k, v, dropout_p=dropout_p, dropout_key=dropout_key, scale=self.scale)
        x = x.transpose(0, 2, 1, 3).reshape(B, N, C)
        return self.proj_drop(self.proj(x))


class PvtBlock(nnx.Module):
    def __init__(self, dim, num_heads, mlp_ratio=4.0, sr_ratio=1, linear_attn=False,
                 qkv_bias=False, proj_drop=0.0, attn_drop=0.0, drop_path=0.0,
                 act_layer='gelu', norm_layer=LayerNorm,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = norm_layer(dim, rngs=rngs)
        self.attn = PvtAttention(
            dim, num_heads=num_heads, sr_ratio=sr_ratio, linear_attn=linear_attn,
            qkv_bias=qkv_bias, attn_drop=attn_drop, proj_drop=proj_drop, **kw)
        self.drop_path1 = DropPath(drop_path, rngs=rngs)
        self.norm2 = norm_layer(dim, rngs=rngs)
        self.mlp = MlpWithDepthwiseConv(
            dim, int(dim * mlp_ratio), act_layer=act_layer, drop=proj_drop,
            extra_relu=linear_attn, **kw)
        self.drop_path2 = DropPath(drop_path, rngs=rngs)

    def __call__(self, x, feat_size):
        x = x + self.drop_path1(self.attn(self.norm1(x), feat_size))
        x = x + self.drop_path2(self.mlp(self.norm2(x), feat_size))
        return x


class OverlapPatchEmbed(nnx.Module):
    """(reference pvt_v2.py:178-204)."""

    def __init__(self, patch_size=7, stride=4, in_chans=3, embed_dim=768,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        patch_size = to_2tuple(patch_size)
        assert max(patch_size) > stride
        self.proj = create_conv2d(
            in_chans, embed_dim, patch_size, stride=stride,
            padding=patch_size[0] // 2, bias=True,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm = LayerNorm(embed_dim, eps=1e-5, rngs=rngs)

    def __call__(self, x):
        return self.norm(self.proj(x))


class PvtStage(nnx.Module):
    """(reference pvt_v2.py:206-266)."""

    def __init__(self, dim, dim_out, depth, downsample=True, num_heads=8, sr_ratio=1,
                 linear_attn=False, mlp_ratio=4.0, qkv_bias=True, proj_drop=0.0,
                 attn_drop=0.0, drop_path=0.0, norm_layer=LayerNorm,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.grad_checkpointing = False
        self.stage_scan = False
        if downsample:
            self.downsample = OverlapPatchEmbed(
                patch_size=3, stride=2, in_chans=dim, embed_dim=dim_out, **kw)
        else:
            assert dim == dim_out
            self.downsample = None
        self.blocks = nnx.List([
            PvtBlock(
                dim=dim_out, num_heads=num_heads, sr_ratio=sr_ratio, linear_attn=linear_attn,
                mlp_ratio=mlp_ratio, qkv_bias=qkv_bias, proj_drop=proj_drop,
                attn_drop=attn_drop,
                drop_path=drop_path[i] if isinstance(drop_path, (list, tuple)) else drop_path,
                norm_layer=norm_layer, **kw)
            for i in range(depth)])
        self.norm = norm_layer(dim_out, rngs=rngs)

    def __call__(self, x):
        if self.downsample is not None:
            x = self.downsample(x)
        B, H, W, C = x.shape
        feat_size = (H, W)
        x = x.reshape(B, -1, C)
        if self.stage_scan:
            try:
                x = scan_stage_stack(
                    self.blocks, x,
                    call_block=lambda blk, xx: blk(xx, feat_size),
                    remat=self.grad_checkpointing)
                x = self.norm(x)
                return x.reshape(B, H, W, -1)
            except BlockStackError as e:
                warn_scan_fallback(type(self).__name__, e, what='stage_scan')
        if self.grad_checkpointing:
            def run_block(blk, x_, fs):
                return blk(x_, fs)
            remat_block = nnx.remat(run_block, static_argnums=(2,))
            for blk in self.blocks:
                x = remat_block(blk, x, feat_size)
        else:
            for blk in self.blocks:
                x = blk(x, feat_size)
        x = self.norm(x)
        return x.reshape(B, H, W, -1)


class PyramidVisionTransformerV2(nnx.Module):
    """(reference pvt_v2.py:268-434)."""

    def __init__(
            self,
            in_chans: int = 3,
            num_classes: int = 1000,
            global_pool: str = 'avg',
            depths: Tuple[int, ...] = (3, 4, 6, 3),
            embed_dims: Tuple[int, ...] = (64, 128, 256, 512),
            num_heads: Tuple[int, ...] = (1, 2, 4, 8),
            sr_ratios: Tuple[int, ...] = (8, 4, 2, 1),
            mlp_ratios=(8.0, 8.0, 4.0, 4.0),
            qkv_bias: bool = True,
            linear: bool = False,
            drop_rate: float = 0.0,
            proj_drop_rate: float = 0.0,
            attn_drop_rate: float = 0.0,
            drop_path_rate: float = 0.0,
            norm_layer: Callable = LayerNorm,
            stage_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        assert global_pool in ('avg', '')
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.num_classes = num_classes
        self.global_pool = global_pool
        self.depths = depths
        num_stages = len(depths)
        mlp_ratios = to_ntuple(num_stages)(mlp_ratios)
        num_heads = to_ntuple(num_stages)(num_heads)
        sr_ratios = to_ntuple(num_stages)(sr_ratios)
        assert len(embed_dims) == num_stages
        self.feature_info = []

        self.patch_embed = OverlapPatchEmbed(
            patch_size=7, stride=4, in_chans=in_chans, embed_dim=embed_dims[0], **kw)

        dpr = calculate_drop_path_rates(drop_path_rate, depths, stagewise=True)
        prev_dim = embed_dims[0]
        stages = []
        for i in range(num_stages):
            stages.append(PvtStage(
                dim=prev_dim, dim_out=embed_dims[i], depth=depths[i], downsample=i > 0,
                num_heads=num_heads[i], sr_ratio=sr_ratios[i], mlp_ratio=mlp_ratios[i],
                linear_attn=linear, qkv_bias=qkv_bias, proj_drop=proj_drop_rate,
                attn_drop=attn_drop_rate, drop_path=dpr[i], norm_layer=norm_layer, **kw))
            prev_dim = embed_dims[i]
            self.feature_info += [dict(num_chs=prev_dim, reduction=4 * 2 ** i, module=f'stages.{i}')]
        self.stages = nnx.List(stages)
        self.set_stage_scan(resolve_stage_scan(stage_scan))

        self.num_features = self.head_hidden_size = embed_dims[-1]
        self.head_drop = Dropout(drop_rate, rngs=rngs)
        self.head = nnx.Linear(
            embed_dims[-1], num_classes, kernel_init=trunc_normal_(std=0.02), bias_init=zeros_,
            **kw) if num_classes > 0 else None
        self._dtype = dtype
        self._param_dtype = param_dtype

    # -- contract ------------------------------------------------------------
    def no_weight_decay(self) -> set:
        return set()

    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^patch_embed', blocks=r'^stages\.(\d+)')

    def set_grad_checkpointing(self, enable: bool = True):
        for s in self.stages:
            s.grad_checkpointing = enable

    def set_stage_scan(self, enable: bool = True):
        for s in self.stages:
            s.stage_scan = enable

    # stage scan IS this family's scan-over-layers: generic machinery that
    # toggles `set_block_scan` (probes, tests) reaches it too
    set_block_scan = set_stage_scan

    def get_classifier(self):
        return self.head

    def reset_classifier(self, num_classes: int, global_pool: Optional[str] = None, *, rngs=None):
        self.num_classes = num_classes
        if global_pool is not None:
            assert global_pool in ('avg', '')
            self.global_pool = global_pool
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        self.head = nnx.Linear(
            self.num_features, num_classes, kernel_init=trunc_normal_(std=0.02),
            dtype=self._dtype, param_dtype=self._param_dtype, rngs=rngs) if num_classes > 0 else None

    # -- forward -------------------------------------------------------------
    def forward_features(self, x):
        x = self.patch_embed(x)
        for stage in self.stages:
            x = stage(x)
        return x

    def forward_head(self, x, pre_logits: bool = False):
        if self.global_pool:
            x = x.mean(axis=(1, 2))
        x = self.head_drop(x)
        if pre_logits or self.head is None:
            return x
        return self.head(x)

    def __call__(self, x):
        return self.forward_head(self.forward_features(x))

    def forward_intermediates(
            self, x, indices=None, norm: bool = False, stop_early: bool = False,
            output_fmt: str = 'NHWC', intermediates_only: bool = False,
    ):
        assert output_fmt == 'NHWC'
        take_indices, max_index = feature_take_indices(len(self.stages), indices)
        x = self.patch_embed(x)
        intermediates = []
        stages = self.stages if not stop_early else list(self.stages)[:max_index + 1]
        for i, stage in enumerate(stages):
            x = stage(x)
            if i in take_indices:
                intermediates.append(x)
        if intermediates_only:
            return intermediates
        return x, intermediates

    def prune_intermediate_layers(self, indices=1, prune_norm: bool = False, prune_head: bool = True):
        take_indices, max_index = feature_take_indices(len(self.stages), indices)
        self.stages = nnx.List(list(self.stages)[:max_index + 1])
        if prune_head:
            self.reset_classifier(0, '')
        return take_indices


def checkpoint_filter_fn(state_dict, model):
    """Remap original PVT checkpoints → timm layout, then torch→nnx
    (reference pvt_v2.py:436-452)."""
    import re

    from ._torch_convert import convert_torch_state_dict
    if 'patch_embed.proj.weight' not in state_dict:
        out = {}
        for k, v in state_dict.items():
            if k.startswith('patch_embed'):
                k = k.replace('patch_embed1', 'patch_embed')
                k = k.replace('patch_embed2', 'stages.1.downsample')
                k = k.replace('patch_embed3', 'stages.2.downsample')
                k = k.replace('patch_embed4', 'stages.3.downsample')
            k = k.replace('dwconv.dwconv', 'dwconv')
            k = re.sub(r'block(\d+).(\d+)', lambda x: f'stages.{int(x.group(1)) - 1}.blocks.{x.group(2)}', k)
            k = re.sub(r'^norm(\d+)', lambda x: f'stages.{int(x.group(1)) - 1}.norm', k)
            out[k] = v
        state_dict = out
    state_dict = {k.replace('.mlp.dwconv.dwconv.', '.mlp.dwconv.'): v for k, v in state_dict.items()}
    return convert_torch_state_dict(state_dict, model)


def _create_pvt2(variant, pretrained=False, **kwargs):
    out_indices = kwargs.pop('out_indices', (0, 1, 2, 3))
    return build_model_with_cfg(
        PyramidVisionTransformerV2, variant, pretrained,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=out_indices),
        **kwargs,
    )


def _cfg(url='', **kwargs):
    return {
        'url': url, 'num_classes': 1000, 'input_size': (3, 224, 224), 'pool_size': (7, 7),
        'crop_pct': 0.9, 'interpolation': 'bicubic',
        'mean': (0.485, 0.456, 0.406), 'std': (0.229, 0.224, 0.225),
        'first_conv': 'patch_embed.proj', 'classifier': 'head', 'fixed_input_size': False,
        'license': 'apache-2.0',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'pvt_v2_b0.in1k': _cfg(hf_hub_id='timm/'),
    'pvt_v2_b1.in1k': _cfg(hf_hub_id='timm/'),
    'pvt_v2_b2.in1k': _cfg(hf_hub_id='timm/'),
    'pvt_v2_b3.in1k': _cfg(hf_hub_id='timm/'),
    'pvt_v2_b4.in1k': _cfg(hf_hub_id='timm/'),
    'pvt_v2_b5.in1k': _cfg(hf_hub_id='timm/'),
    'pvt_v2_b2_li.in1k': _cfg(hf_hub_id='timm/'),
})


@register_model
def pvt_v2_b0(pretrained=False, **kwargs) -> PyramidVisionTransformerV2:
    model_args = dict(depths=(2, 2, 2, 2), embed_dims=(32, 64, 160, 256), num_heads=(1, 2, 5, 8))
    return _create_pvt2('pvt_v2_b0', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def pvt_v2_b1(pretrained=False, **kwargs) -> PyramidVisionTransformerV2:
    model_args = dict(depths=(2, 2, 2, 2), embed_dims=(64, 128, 320, 512), num_heads=(1, 2, 5, 8))
    return _create_pvt2('pvt_v2_b1', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def pvt_v2_b2(pretrained=False, **kwargs) -> PyramidVisionTransformerV2:
    model_args = dict(depths=(3, 4, 6, 3), embed_dims=(64, 128, 320, 512), num_heads=(1, 2, 5, 8))
    return _create_pvt2('pvt_v2_b2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def pvt_v2_b3(pretrained=False, **kwargs) -> PyramidVisionTransformerV2:
    model_args = dict(depths=(3, 4, 18, 3), embed_dims=(64, 128, 320, 512), num_heads=(1, 2, 5, 8))
    return _create_pvt2('pvt_v2_b3', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def pvt_v2_b4(pretrained=False, **kwargs) -> PyramidVisionTransformerV2:
    model_args = dict(depths=(3, 8, 27, 3), embed_dims=(64, 128, 320, 512), num_heads=(1, 2, 5, 8))
    return _create_pvt2('pvt_v2_b4', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def pvt_v2_b5(pretrained=False, **kwargs) -> PyramidVisionTransformerV2:
    model_args = dict(
        depths=(3, 6, 40, 3), embed_dims=(64, 128, 320, 512), num_heads=(1, 2, 5, 8), mlp_ratios=(4, 4, 4, 4))
    return _create_pvt2('pvt_v2_b5', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def pvt_v2_b2_li(pretrained=False, **kwargs) -> PyramidVisionTransformerV2:
    model_args = dict(
        depths=(3, 4, 6, 3), embed_dims=(64, 128, 320, 512), num_heads=(1, 2, 5, 8), linear=True)
    return _create_pvt2('pvt_v2_b2_li', pretrained=pretrained, **dict(model_args, **kwargs))
