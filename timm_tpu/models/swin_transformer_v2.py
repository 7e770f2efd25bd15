"""Swin Transformer V2, TPU-native NHWC
(reference: timm/models/swin_transformer_v2.py:1-1320).

V2 changes vs V1 (Liu et al. 2022): residual POST-norm ordering, cosine
attention with a learned per-head temperature (clamped at 1/0.01), and a
continuous log-spaced relative-position bias generated by a small MLP.

TPU-first design notes:
  * the log-spaced relative coords table and the (N, N) gather index are
    precomputed numpy constants — only the 2-layer cpb MLP runs under jit,
    and its output is a (N, N, heads) gather feeding straight into the
    attention logits (no per-step table rebuild as in the reference's
    buffer dance).
  * shifted windows are static `jnp.roll`s with precomputed additive masks
    shared with the V1 implementation; everything under jit is fixed-shape.
  * window sizes are shrunk to the nearest divisor of the feature resolution
    instead of padding (identical for all published 192/256/384 configs).
"""
from __future__ import annotations

import math
from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from flax import nnx

from ..layers import (
    ClassifierHead, DropPath, Dropout, LayerNorm, Mlp, PatchEmbed,
    calculate_drop_path_rates, get_norm_layer, to_2tuple, trunc_normal_, zeros_,
)
from ..layers.pos_embed_rel import gen_relative_log_coords
from ._builder import build_model_with_cfg
from ._features import feature_take_indices
from ._manipulate import (
    BlockStackError, checkpoint_seq, resolve_stage_scan, scan_stage_stack,
    warn_scan_fallback,
)
from ._registry import generate_default_cfgs, register_model
from .swin_transformer import (
    _relative_position_index, _shift_attn_mask, window_partition, window_reverse,
)

__all__ = ['SwinTransformerV2', 'SwinTransformerV2Block', 'WindowAttentionV2']


class WindowAttentionV2(nnx.Module):
    """Cosine window attention w/ continuous rel-pos bias
    (reference swin_transformer_v2.py:79-289)."""

    def __init__(
            self,
            dim: int,
            window_size: Union[int, Tuple[int, int]],
            num_heads: int,
            qkv_bias: bool = True,
            attn_drop: float = 0.0,
            proj_drop: float = 0.0,
            pretrained_window_size: Tuple[int, int] = (0, 0),
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.dim = dim
        self.window_size = to_2tuple(window_size)
        win_h, win_w = self.window_size
        self.window_area = win_h * win_w
        self.num_heads = num_heads
        self.head_dim = dim // num_heads

        self.logit_scale = nnx.Param(jnp.full((num_heads, 1, 1), math.log(10.0), param_dtype))

        # continuous position bias MLP: 2 → 512 → heads (no bias on out proj)
        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs)
        self.cpb_fc1 = linear(2, 512, use_bias=True)
        self.cpb_fc2 = linear(512, num_heads, use_bias=False)

        self.qkv = linear(dim, dim * 3, use_bias=False)
        if qkv_bias:
            self.q_bias = nnx.Param(jnp.zeros((dim,), param_dtype))
            self.v_bias = nnx.Param(jnp.zeros((dim,), param_dtype))
        else:
            self.q_bias = None
            self.v_bias = None
        self.attn_drop = Dropout(attn_drop, rngs=rngs)
        self.proj = linear(dim, dim)
        self.proj_drop = Dropout(proj_drop, rngs=rngs)

        # nnx.Variable: raw array attrs break nnx graph traversal on older flax
        self._coords_table = nnx.Variable(jnp.asarray(gen_relative_log_coords(
            (win_h, win_w), to_2tuple(pretrained_window_size), mode='swin')))
        self._rel_index = nnx.Variable(jnp.asarray(_relative_position_index(win_h, win_w)))

    def _bias(self, dtype):
        # (2wh-1 * 2ww-1, heads) from the cpb MLP, gathered to (1, heads, N, N)
        table = self.cpb_fc2(nnx.relu(self.cpb_fc1(self._coords_table[...])))
        table = table.reshape(-1, self.num_heads)
        bias = table[self._rel_index[...].reshape(-1)]
        bias = bias.reshape(self.window_area, self.window_area, -1).transpose(2, 0, 1)
        bias = 16.0 * jax.nn.sigmoid(bias)
        return bias[None].astype(dtype)

    def __call__(self, x, mask=None):
        Bw, N, C = x.shape
        qkv = self.qkv(x)
        if self.q_bias is not None:
            # k gets no bias (reference: fixed zero k_bias buffer)
            bias = jnp.concatenate([
                self.q_bias[...], jnp.zeros_like(self.q_bias[...]), self.v_bias[...]])
            qkv = qkv + bias.astype(qkv.dtype)
        qkv = qkv.reshape(Bw, N, 3, self.num_heads, self.head_dim).transpose(2, 0, 3, 1, 4)
        q, k, v = qkv[0], qkv[1], qkv[2]

        # cosine attention in fp32 for stable normalize/softmax
        q = q.astype(jnp.float32)
        k = k.astype(jnp.float32)
        qn = q / jnp.maximum(jnp.linalg.norm(q, axis=-1, keepdims=True), 1e-12)
        kn = k / jnp.maximum(jnp.linalg.norm(k, axis=-1, keepdims=True), 1e-12)
        attn = jnp.einsum('bhnd,bhmd->bhnm', qn, kn)
        logit_scale = jnp.exp(jnp.minimum(
            self.logit_scale[...].astype(jnp.float32), math.log(1.0 / 0.01)))
        attn = attn * logit_scale[None]
        attn = attn + self._bias(attn.dtype)
        if mask is not None:
            nW = mask.shape[0]
            attn = attn.reshape(-1, nW, self.num_heads, N, N) + mask[None, :, None]
            attn = attn.reshape(-1, self.num_heads, N, N)
        attn = jax.nn.softmax(attn, axis=-1)
        attn = self.attn_drop(attn)
        x = jnp.einsum('bhnm,bhmd->bhnd', attn.astype(v.dtype), v)
        x = x.transpose(0, 2, 1, 3).reshape(Bw, N, C)
        x = self.proj(x)
        return self.proj_drop(x)


class SwinTransformerV2Block(nnx.Module):
    """Post-norm Swin V2 block (reference swin_transformer_v2.py:291-532)."""

    def __init__(
            self,
            dim: int,
            input_resolution: Tuple[int, int],
            num_heads: int,
            window_size: Union[int, Tuple[int, int]] = 7,
            shift_size: Union[int, Tuple[int, int]] = 0,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = True,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            drop_path: float = 0.0,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            pretrained_window_size: Union[int, Tuple[int, int]] = 0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.dim = dim
        self.input_resolution = to_2tuple(input_resolution)
        ws, ss = self._calc_window_shift(to_2tuple(window_size), to_2tuple(shift_size))
        self.window_size = ws
        self.shift_size = ss
        self.window_area = ws[0] * ws[1]

        self.attn = WindowAttentionV2(
            dim, window_size=ws, num_heads=num_heads, qkv_bias=qkv_bias,
            attn_drop=attn_drop, proj_drop=proj_drop,
            pretrained_window_size=to_2tuple(pretrained_window_size),
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = norm_layer(dim, rngs=rngs)
        self.drop_path1 = DropPath(drop_path, rngs=rngs)
        self.mlp = Mlp(dim, int(dim * mlp_ratio), act_layer=act_layer, drop=proj_drop,
                       dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm2 = norm_layer(dim, rngs=rngs)
        self.drop_path2 = DropPath(drop_path, rngs=rngs)

        if any(self.shift_size):
            H, W = self.input_resolution
            self._attn_mask = nnx.Variable(jnp.asarray(_shift_attn_mask(H, W, ws, ss)))
        else:
            self._attn_mask = None

    def _calc_window_shift(self, target_window, target_shift):
        # shrink window to the largest divisor of the resolution (static
        # shapes; identical to the reference's clamp for published configs)
        ws, ss = [], []
        for r, w, s in zip(to_2tuple(self.input_resolution), target_window, target_shift):
            if r <= w:
                ws.append(r)
                ss.append(0)
            else:
                while r % w:
                    w -= 1
                ws.append(w)
                ss.append(min(s, w // 2))
        return tuple(ws), tuple(ss)

    def _attn(self, x):
        B, H, W, C = x.shape
        sh, sw = self.shift_size
        if sh or sw:
            x = jnp.roll(x, shift=(-sh, -sw), axis=(1, 2))
        xw = window_partition(x, self.window_size)
        xw = self.attn(xw, mask=None if self._attn_mask is None else self._attn_mask[...])
        x = window_reverse(xw, self.window_size, H, W)
        if sh or sw:
            x = jnp.roll(x, shift=(sh, sw), axis=(1, 2))
        return x

    def __call__(self, x):
        # V2 post-norm residual ordering
        x = x + self.drop_path1(self.norm1(self._attn(x)))
        x = x + self.drop_path2(self.norm2(self.mlp(x)))
        return x


class PatchMergingV2(nnx.Module):
    """V2 patch merging: reduction THEN norm, sized to out_dim
    (reference swin_transformer_v2.py:534-573)."""

    def __init__(self, dim: int, out_dim: Optional[int] = None, norm_layer: Callable = LayerNorm,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.dim = dim
        self.out_dim = out_dim or 2 * dim
        self.reduction = nnx.Linear(
            4 * dim, self.out_dim, use_bias=False, kernel_init=trunc_normal_(std=0.02),
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm = norm_layer(self.out_dim, rngs=rngs)

    def __call__(self, x):
        B, H, W, C = x.shape
        x = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 4, 2, 5).reshape(B, H // 2, W // 2, 4 * C)
        return self.norm(self.reduction(x))


class SwinTransformerV2Stage(nnx.Module):
    def __init__(
            self,
            dim: int,
            out_dim: int,
            input_resolution: Tuple[int, int],
            depth: int,
            num_heads: int,
            window_size: Union[int, Tuple[int, int]],
            downsample: bool = False,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = True,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            drop_path: Union[List[float], float] = 0.0,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            pretrained_window_size: Union[int, Tuple[int, int]] = 0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.grad_checkpointing = False
        self.stage_scan = False
        if downsample:
            self.downsample = PatchMergingV2(dim, out_dim, norm_layer=norm_layer,
                                             dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            output_resolution = (input_resolution[0] // 2, input_resolution[1] // 2)
        else:
            assert dim == out_dim
            self.downsample = None
            output_resolution = input_resolution
        self.output_resolution = output_resolution

        if isinstance(drop_path, float):
            drop_path = [drop_path] * depth
        window_size = to_2tuple(window_size)
        shift = (window_size[0] // 2, window_size[1] // 2)
        self.blocks = nnx.List([
            SwinTransformerV2Block(
                out_dim,
                input_resolution=output_resolution,
                num_heads=num_heads,
                window_size=window_size,
                shift_size=(0, 0) if i % 2 == 0 else shift,
                mlp_ratio=mlp_ratio,
                qkv_bias=qkv_bias,
                proj_drop=proj_drop,
                attn_drop=attn_drop,
                drop_path=drop_path[i],
                act_layer=act_layer,
                norm_layer=norm_layer,
                pretrained_window_size=pretrained_window_size,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
            for i in range(depth)
        ])
        # res-post-norm init: zero the block norms so each residual branch
        # starts as identity (reference _init_respostnorm)
        for blk in self.blocks:
            for norm in (blk.norm1, blk.norm2):
                norm.scale[...] = jnp.zeros_like(norm.scale[...])
                if norm.bias is not None:
                    norm.bias[...] = jnp.zeros_like(norm.bias[...])

    def __call__(self, x):
        if self.downsample is not None:
            x = self.downsample(x)
        if self.stage_scan:
            try:
                return scan_stage_stack(self.blocks, x, remat=self.grad_checkpointing)
            except BlockStackError as e:
                warn_scan_fallback(type(self).__name__, e, what='stage_scan')
        if self.grad_checkpointing:
            x = checkpoint_seq(self.blocks, x)
        else:
            for blk in self.blocks:
                x = blk(x)
        return x


class SwinTransformerV2(nnx.Module):
    """Swin V2 with the reference's full model contract
    (reference swin_transformer_v2.py:719-1073)."""

    def __init__(
            self,
            img_size: Union[int, Tuple[int, int]] = 224,
            patch_size: int = 4,
            in_chans: int = 3,
            num_classes: int = 1000,
            global_pool: str = 'avg',
            embed_dim: int = 96,
            depths: Tuple[int, ...] = (2, 2, 6, 2),
            num_heads: Tuple[int, ...] = (3, 6, 12, 24),
            window_size: Union[int, Tuple[int, int]] = 7,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = True,
            drop_rate: float = 0.0,
            proj_drop_rate: float = 0.0,
            attn_drop_rate: float = 0.0,
            drop_path_rate: float = 0.1,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Optional[Union[str, Callable]] = None,
            pretrained_window_sizes: Tuple[int, ...] = (0, 0, 0, 0),
            stage_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        # reference uses torch nn.LayerNorm default eps (1e-5)
        norm_layer = get_norm_layer(norm_layer) or partial(LayerNorm, eps=1e-5)
        assert global_pool in ('', 'avg')
        self.num_classes = num_classes
        num_layers = len(depths)
        self.num_features = self.head_hidden_size = int(embed_dim * 2 ** (num_layers - 1))

        self.patch_embed = PatchEmbed(
            img_size=img_size, patch_size=patch_size, in_chans=in_chans,
            embed_dim=embed_dim, norm_layer=norm_layer, flatten=False,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        grid = self.patch_embed.grid_size

        dpr = calculate_drop_path_rates(drop_path_rate, list(depths), stagewise=True)
        stages = []
        in_dim = embed_dim
        in_res = grid
        self.feature_info = []
        scale = 1
        for i in range(num_layers):
            out_dim = int(embed_dim * 2 ** i)
            downsample = i > 0
            stages.append(SwinTransformerV2Stage(
                dim=in_dim,
                out_dim=out_dim,
                input_resolution=in_res,
                depth=depths[i],
                downsample=downsample,
                num_heads=num_heads[i],
                window_size=window_size,
                mlp_ratio=mlp_ratio,
                qkv_bias=qkv_bias,
                proj_drop=proj_drop_rate,
                attn_drop=attn_drop_rate,
                drop_path=dpr[i],
                act_layer=act_layer,
                norm_layer=norm_layer,
                pretrained_window_size=pretrained_window_sizes[i] if i < len(pretrained_window_sizes) else 0,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            ))
            in_dim = out_dim
            if downsample:
                in_res = (in_res[0] // 2, in_res[1] // 2)
                scale *= 2
            self.feature_info += [dict(num_chs=out_dim, reduction=patch_size * scale, module=f'layers.{i}')]
        self.layers = nnx.List(stages)
        self.set_stage_scan(resolve_stage_scan(stage_scan))

        self.norm = norm_layer(self.num_features, rngs=rngs)
        self.head = ClassifierHead(
            self.num_features, num_classes, pool_type=global_pool, drop_rate=drop_rate,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self._dtype = dtype
        self._param_dtype = param_dtype

    # -- contract ------------------------------------------------------------
    def no_weight_decay(self) -> set:
        # reference excludes all cpb_mlp params as well (swin_transformer_v2.py:915)
        return {'logit_scale', 'q_bias', 'v_bias', 'cpb_fc1', 'cpb_fc2'}

    def group_matcher(self, coarse: bool = False):
        return dict(
            stem=r'^patch_embed',
            blocks=r'^layers\.(\d+)' if coarse else [
                (r'^layers\.(\d+).downsample', (0,)),
                (r'^layers\.(\d+)\.blocks\.(\d+)', None),
                (r'^norm', (99999,)),
            ],
        )

    def set_grad_checkpointing(self, enable: bool = True):
        for l in self.layers:
            l.grad_checkpointing = enable

    def set_stage_scan(self, enable: bool = True):
        for s in self.layers:
            s.stage_scan = enable

    # stage scan IS this family's scan-over-layers: generic machinery that
    # toggles `set_block_scan` (probes, tests) reaches it too
    set_block_scan = set_stage_scan

    def get_classifier(self):
        return self.head.fc

    def reset_classifier(self, num_classes: int, global_pool: Optional[str] = None, *, rngs=None):
        self.num_classes = num_classes
        self.head.reset(num_classes, pool_type=global_pool, rngs=rngs)

    # -- forward -------------------------------------------------------------
    def forward_features(self, x):
        x = self.patch_embed(x)
        for stage in self.layers:
            x = stage(x)
        return self.norm(x) if self.norm is not None else x

    def forward_head(self, x, pre_logits: bool = False):
        return self.head(x, pre_logits=pre_logits)

    def __call__(self, x):
        return self.forward_head(self.forward_features(x))

    def forward_intermediates(
            self, x, indices=None, norm: bool = False, stop_early: bool = False,
            output_fmt: str = 'NHWC', intermediates_only: bool = False,
    ):
        assert output_fmt == 'NHWC'
        take_indices, max_index = feature_take_indices(len(self.layers), indices)
        x = self.patch_embed(x)
        intermediates = []
        stages = self.layers if not stop_early else list(self.layers)[:max_index + 1]
        for i, stage in enumerate(stages):
            x = stage(x)
            if i in take_indices:
                intermediates.append(
                    self.norm(x) if (norm and self.norm is not None and i == len(self.layers) - 1) else x)
        if intermediates_only:
            return intermediates
        if self.norm is not None:
            x = self.norm(x)
        return x, intermediates

    def prune_intermediate_layers(self, indices=1, prune_norm: bool = False, prune_head: bool = True):
        take_indices, max_index = feature_take_indices(len(self.layers), indices)
        self.layers = nnx.List(list(self.layers)[:max_index + 1])
        if prune_norm:
            self.norm = None
        if prune_head:
            self.reset_classifier(0, '')
        return take_indices


def _cfg(url: str = '', **kwargs) -> Dict[str, Any]:
    return {
        'url': url,
        'num_classes': 1000,
        'input_size': (3, 256, 256),
        'pool_size': (8, 8),
        'crop_pct': 0.9,
        'interpolation': 'bicubic',
        'fixed_input_size': True,
        'mean': (0.485, 0.456, 0.406),
        'std': (0.229, 0.224, 0.225),
        'first_conv': 'patch_embed.proj',
        'classifier': 'head.fc',
        'license': 'mit',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'swinv2_tiny_window8_256.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swinv2_tiny_window16_256.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swinv2_small_window8_256.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swinv2_small_window16_256.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swinv2_base_window8_256.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swinv2_base_window16_256.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swinv2_base_window12_192.ms_in22k': _cfg(
        hf_hub_id='timm/', num_classes=21841, input_size=(3, 192, 192), pool_size=(6, 6)),
    'swinv2_base_window12to16_192to256.ms_in22k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'swinv2_base_window12to24_192to384.ms_in22k_ft_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0),
    'swinv2_large_window12_192.ms_in22k': _cfg(
        hf_hub_id='timm/', num_classes=21841, input_size=(3, 192, 192), pool_size=(6, 6)),
    'swinv2_large_window12to16_192to256.ms_in22k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'swinv2_large_window12to24_192to384.ms_in22k_ft_in1k': _cfg(
        hf_hub_id='timm/', input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0),
    'test_swinv2.untrained': _cfg(input_size=(3, 96, 96), pool_size=(3, 3)),
})


def checkpoint_filter_fn(state_dict, model):
    from ._torch_convert import convert_torch_state_dict
    out = {}
    for k, v in state_dict.items():
        if k.endswith(('relative_position_index', 'relative_coords_table', 'attn_mask', 'k_bias')):
            continue
        # torch cpb_mlp Sequential indices → named fc1/fc2
        k = k.replace('cpb_mlp.0.', 'cpb_fc1.').replace('cpb_mlp.2.', 'cpb_fc2.')
        out[k] = v
    return convert_torch_state_dict(out, model)


def _create_swin_transformer_v2(variant: str, pretrained: bool = False, **kwargs) -> SwinTransformerV2:
    out_indices = kwargs.pop('out_indices', (0, 1, 2, 3))
    return build_model_with_cfg(
        SwinTransformerV2, variant, pretrained,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=out_indices),
        **kwargs,
    )


@register_model
def swinv2_tiny_window16_256(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(img_size=256, window_size=16, embed_dim=96, depths=(2, 2, 6, 2), num_heads=(3, 6, 12, 24))
    return _create_swin_transformer_v2('swinv2_tiny_window16_256', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_tiny_window8_256(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(img_size=256, window_size=8, embed_dim=96, depths=(2, 2, 6, 2), num_heads=(3, 6, 12, 24))
    return _create_swin_transformer_v2('swinv2_tiny_window8_256', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_small_window16_256(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(img_size=256, window_size=16, embed_dim=96, depths=(2, 2, 18, 2), num_heads=(3, 6, 12, 24))
    return _create_swin_transformer_v2('swinv2_small_window16_256', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_small_window8_256(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(img_size=256, window_size=8, embed_dim=96, depths=(2, 2, 18, 2), num_heads=(3, 6, 12, 24))
    return _create_swin_transformer_v2('swinv2_small_window8_256', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_base_window16_256(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(img_size=256, window_size=16, embed_dim=128, depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32))
    return _create_swin_transformer_v2('swinv2_base_window16_256', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_base_window8_256(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(img_size=256, window_size=8, embed_dim=128, depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32))
    return _create_swin_transformer_v2('swinv2_base_window8_256', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_base_window12_192(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(img_size=192, window_size=12, embed_dim=128, depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32))
    return _create_swin_transformer_v2('swinv2_base_window12_192', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_base_window12to16_192to256(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(
        img_size=256, window_size=16, embed_dim=128, depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32),
        pretrained_window_sizes=(12, 12, 12, 6))
    return _create_swin_transformer_v2('swinv2_base_window12to16_192to256', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_base_window12to24_192to384(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(
        img_size=384, window_size=24, embed_dim=128, depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32),
        pretrained_window_sizes=(12, 12, 12, 6))
    return _create_swin_transformer_v2('swinv2_base_window12to24_192to384', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_large_window12_192(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(img_size=192, window_size=12, embed_dim=192, depths=(2, 2, 18, 2), num_heads=(6, 12, 24, 48))
    return _create_swin_transformer_v2('swinv2_large_window12_192', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_large_window12to16_192to256(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(
        img_size=256, window_size=16, embed_dim=192, depths=(2, 2, 18, 2), num_heads=(6, 12, 24, 48),
        pretrained_window_sizes=(12, 12, 12, 6))
    return _create_swin_transformer_v2('swinv2_large_window12to16_192to256', pretrained, **dict(model_args, **kwargs))


@register_model
def swinv2_large_window12to24_192to384(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(
        img_size=384, window_size=24, embed_dim=192, depths=(2, 2, 18, 2), num_heads=(6, 12, 24, 48),
        pretrained_window_sizes=(12, 12, 12, 6))
    return _create_swin_transformer_v2('swinv2_large_window12to24_192to384', pretrained, **dict(model_args, **kwargs))


@register_model
def test_swinv2(pretrained=False, **kwargs) -> SwinTransformerV2:
    model_args = dict(
        img_size=96, patch_size=4, window_size=4, embed_dim=32, depths=(1, 1, 2), num_heads=(2, 2, 4))
    return _create_swin_transformer_v2('test_swinv2', pretrained, **dict(model_args, **kwargs))
