"""Swin Transformer, TPU-native NHWC
(reference: timm/models/swin_transformer.py:1-1255).

Shifted windows are static `jnp.roll`s and the shift attention masks are
precomputed numpy constants per (resolution, window, shift) — everything under
jit is fixed-shape, branch-free. Window partition is a reshape/transpose pair
that XLA fuses into the attention matmuls.
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
from ..layers.attention import scaled_dot_product_attention
from ..layers.drop import dropout_rng_key
from ._builder import build_model_with_cfg
from ._features import feature_take_indices
from ._manipulate import (
    BlockStackError, checkpoint_seq, resolve_stage_scan, scan_stage_stack,
    warn_scan_fallback,
)
from ._registry import generate_default_cfgs, register_model

__all__ = ['SwinTransformer', 'SwinTransformerBlock', 'WindowAttention']


def window_partition(x, window_size: Tuple[int, int]):
    """(B, H, W, C) → (B*nW, wh*ww, C)."""
    B, H, W, C = x.shape
    wh, ww = window_size
    x = x.reshape(B, H // wh, wh, W // ww, ww, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(-1, wh * ww, C)


def window_reverse(windows, window_size: Tuple[int, int], H: int, W: int):
    """(B*nW, wh*ww, C) → (B, H, W, C)."""
    wh, ww = window_size
    C = windows.shape[-1]
    B = windows.shape[0] // (H * W // wh // ww)
    x = windows.reshape(B, H // wh, W // ww, wh, ww, C)
    return x.transpose(0, 1, 3, 2, 4, 5).reshape(B, H, W, C)


def _relative_position_index(win_h: int, win_w: int) -> np.ndarray:
    """Static (wh*ww, wh*ww) index into the rel-bias table."""
    coords = np.stack(np.meshgrid(np.arange(win_h), np.arange(win_w), indexing='ij'))
    coords_flat = coords.reshape(2, -1)
    relative = coords_flat[:, :, None] - coords_flat[:, None, :]
    relative = relative.transpose(1, 2, 0)
    relative[:, :, 0] += win_h - 1
    relative[:, :, 1] += win_w - 1
    relative[:, :, 0] *= 2 * win_w - 1
    return relative.sum(-1)


def _shift_attn_mask(H: int, W: int, window_size: Tuple[int, int], shift_size: Tuple[int, int]) -> np.ndarray:
    """Static additive mask (nW, N, N) for shifted windows (reference swin mask)."""
    wh, ww = window_size
    sh, sw = shift_size
    img_mask = np.zeros((1, H, W, 1), np.float32)
    cnt = 0
    for h in (slice(0, -wh), slice(-wh, -sh), slice(-sh, None)):
        for w in (slice(0, -ww), slice(-ww, -sw), slice(-sw, None)):
            img_mask[:, h, w, :] = cnt
            cnt += 1
    mask_windows = img_mask.reshape(1, H // wh, wh, W // ww, ww, 1)
    mask_windows = mask_windows.transpose(0, 1, 3, 2, 4, 5).reshape(-1, wh * ww)
    attn_mask = mask_windows[:, None, :] - mask_windows[:, :, None]
    return np.where(attn_mask != 0, -100.0, 0.0).astype(np.float32)


class WindowAttention(nnx.Module):
    """Window MHSA w/ relative position bias (reference swin WindowAttention)."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            head_dim: Optional[int] = None,
            window_size: Union[int, Tuple[int, int]] = 7,
            qkv_bias: bool = True,
            attn_drop: float = 0.0,
            proj_drop: float = 0.0,
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
        head_dim = head_dim or dim // num_heads
        attn_dim = head_dim * num_heads
        self.head_dim = head_dim
        self.scale = head_dim ** -0.5

        self.relative_position_bias_table = nnx.Param(
            trunc_normal_(std=0.02)(
                rngs.params(), ((2 * win_h - 1) * (2 * win_w - 1), num_heads), param_dtype))
        # nnx.Variable: a raw array attribute breaks nnx graph traversal on
        # older flax (split/state reject array leaves); a Variable is
        # traversal-safe on every version and stays out of the Param state
        self._rel_index = nnx.Variable(jnp.asarray(_relative_position_index(win_h, win_w)))

        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs)
        self.qkv = linear(dim, attn_dim * 3, use_bias=qkv_bias)
        self.attn_drop = Dropout(attn_drop, rngs=rngs)
        self.proj = linear(attn_dim, dim)
        self.proj_drop = Dropout(proj_drop, rngs=rngs)

    def _bias(self, dtype):
        table = self.relative_position_bias_table[...]
        bias = table[self._rel_index[...].reshape(-1)]
        bias = bias.reshape(self.window_area, self.window_area, -1).transpose(2, 0, 1)
        return bias[None].astype(dtype)  # (1, H, N, N)

    def __call__(self, x, mask=None):
        # x: (B_windows, N, C); mask: (nW, N, N) additive or None
        Bw, N, C = x.shape
        qkv = self.qkv(x).reshape(Bw, N, 3, self.num_heads, self.head_dim).transpose(2, 0, 3, 1, 4)
        q, k, v = qkv[0], qkv[1], qkv[2]
        attn_bias = self._bias(jnp.float32)
        if mask is not None:
            nW = mask.shape[0]
            mask_f = mask[None, :, None, :, :]  # (1, nW, 1, N, N)
            attn_bias = attn_bias[None] + mask_f  # (1|B, nW, H, N, N) broadcast
            # fold window dim back into batch for the attention call
            attn_bias = jnp.broadcast_to(
                attn_bias, (Bw // nW, nW, self.num_heads, N, N)).reshape(Bw, self.num_heads, N, N)
        else:
            attn_bias = jnp.broadcast_to(attn_bias, (Bw, self.num_heads, N, N))
        dropout_p = 0.0 if self.attn_drop.deterministic else self.attn_drop.rate
        dropout_key = dropout_rng_key(self.attn_drop) if dropout_p > 0.0 else None
        x = scaled_dot_product_attention(
            q, k, v, attn_mask=attn_bias, dropout_p=dropout_p, dropout_key=dropout_key,
            scale=self.scale, fused=False)
        x = x.transpose(0, 2, 1, 3).reshape(Bw, N, -1)
        x = self.proj(x)
        return self.proj_drop(x)


class SwinTransformerBlock(nnx.Module):
    def __init__(
            self,
            dim: int,
            input_resolution: Tuple[int, int],
            num_heads: int = 4,
            head_dim: Optional[int] = None,
            window_size: int = 7,
            shift_size: int = 0,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = True,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            drop_path: float = 0.0,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Callable = LayerNorm,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.dim = dim
        self.input_resolution = input_resolution
        ws, ss = self._calc_window_shift(to_2tuple(window_size), to_2tuple(shift_size))
        self.window_size = ws
        self.shift_size = ss
        self.window_area = ws[0] * ws[1]

        self.norm1 = norm_layer(dim, rngs=rngs)
        self.attn = WindowAttention(
            dim, num_heads=num_heads, head_dim=head_dim, window_size=ws,
            qkv_bias=qkv_bias, attn_drop=attn_drop, proj_drop=proj_drop,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.drop_path1 = DropPath(drop_path, rngs=rngs)
        self.norm2 = norm_layer(dim, rngs=rngs)
        self.mlp = Mlp(dim, int(dim * mlp_ratio), act_layer=act_layer, drop=proj_drop,
                       dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.drop_path2 = DropPath(drop_path, rngs=rngs)

        if any(self.shift_size):
            H, W = input_resolution
            self._attn_mask = nnx.Variable(jnp.asarray(_shift_attn_mask(H, W, ws, ss)))
        else:
            self._attn_mask = None

    def _calc_window_shift(self, target_window, target_shift):
        # window can't exceed resolution, and must divide it (static shapes —
        # we shrink to the largest divisor instead of the reference's padding;
        # identical for all standard 224/384 configs where 7|56,28,14)
        ws, ss = [], []
        for r, w, s in zip(self.input_resolution, target_window, target_shift):
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
        x = x + self.drop_path1(self._attn(self.norm1(x)))
        x = x + self.drop_path2(self.mlp(self.norm2(x)))
        return x


class PatchMerging(nnx.Module):
    def __init__(self, dim: int, out_dim: Optional[int] = None, norm_layer: Callable = LayerNorm,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.dim = dim
        self.out_dim = out_dim or 2 * dim
        self.norm = norm_layer(4 * dim, rngs=rngs)
        self.reduction = nnx.Linear(
            4 * dim, self.out_dim, use_bias=False, kernel_init=trunc_normal_(std=0.02),
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)

    def __call__(self, x):
        B, H, W, C = x.shape
        x = x.reshape(B, H // 2, 2, W // 2, 2, C).transpose(0, 1, 3, 4, 2, 5).reshape(B, H // 2, W // 2, 4 * C)
        return self.reduction(self.norm(x))


class SwinTransformerStage(nnx.Module):
    def __init__(
            self,
            dim: int,
            out_dim: int,
            input_resolution: Tuple[int, int],
            depth: int,
            downsample: bool = True,
            num_heads: int = 4,
            head_dim: Optional[int] = None,
            window_size: int = 7,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = True,
            proj_drop: float = 0.0,
            attn_drop: float = 0.0,
            drop_path: Union[List[float], float] = 0.0,
            norm_layer: Callable = LayerNorm,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.grad_checkpointing = False
        self.stage_scan = False
        if downsample:
            self.downsample = PatchMerging(dim, out_dim, norm_layer=norm_layer,
                                           dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            output_resolution = (input_resolution[0] // 2, input_resolution[1] // 2)
        else:
            self.downsample = None
            output_resolution = input_resolution
        self.output_resolution = output_resolution

        if isinstance(drop_path, float):
            drop_path = [drop_path] * depth
        shift = window_size // 2
        self.blocks = nnx.List([
            SwinTransformerBlock(
                out_dim,
                input_resolution=output_resolution,
                num_heads=num_heads,
                head_dim=head_dim,
                window_size=window_size,
                shift_size=0 if i % 2 == 0 else shift,
                mlp_ratio=mlp_ratio,
                qkv_bias=qkv_bias,
                proj_drop=proj_drop,
                attn_drop=attn_drop,
                drop_path=drop_path[i],
                norm_layer=norm_layer,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
            for i in range(depth)
        ])

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


class SwinTransformer(nnx.Module):
    def __init__(
            self,
            img_size: int = 224,
            patch_size: int = 4,
            in_chans: int = 3,
            num_classes: int = 1000,
            global_pool: str = 'avg',
            embed_dim: int = 96,
            depths: Tuple[int, ...] = (2, 2, 6, 2),
            num_heads: Tuple[int, ...] = (3, 6, 12, 24),
            head_dim: Optional[int] = None,
            window_size: int = 7,
            mlp_ratio: float = 4.0,
            qkv_bias: bool = True,
            drop_rate: float = 0.0,
            proj_drop_rate: float = 0.0,
            attn_drop_rate: float = 0.0,
            drop_path_rate: float = 0.1,
            norm_layer: Optional[Union[str, Callable]] = None,
            stage_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        # reference uses torch nn.LayerNorm default eps (1e-5)
        norm_layer = get_norm_layer(norm_layer) or partial(LayerNorm, eps=1e-5)
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
            stages.append(SwinTransformerStage(
                dim=in_dim,
                out_dim=out_dim,
                input_resolution=in_res,
                depth=depths[i],
                downsample=downsample,
                num_heads=num_heads[i],
                head_dim=head_dim,
                window_size=window_size,
                mlp_ratio=mlp_ratio,
                qkv_bias=qkv_bias,
                proj_drop=proj_drop_rate,
                attn_drop=attn_drop_rate,
                drop_path=dpr[i],
                norm_layer=norm_layer,
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
        return {'relative_position_bias_table'}

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
            self.norm = None  # sized for the unpruned width; drop with the tail
        if prune_head:
            self.reset_classifier(0, '')
        return take_indices


def _cfg(url: str = '', **kwargs) -> Dict[str, Any]:
    return {
        'url': url,
        'num_classes': 1000,
        'input_size': (3, 224, 224),
        'pool_size': (7, 7),
        'crop_pct': 0.9,
        'interpolation': 'bicubic',
        'fixed_input_size': True,
        'mean': (0.485, 0.456, 0.406),
        'std': (0.229, 0.224, 0.225),
        'first_conv': 'patch_embed.proj',
        'classifier': 'head.fc',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'swin_tiny_patch4_window7_224.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swin_small_patch4_window7_224.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swin_base_patch4_window7_224.ms_in1k': _cfg(hf_hub_id='timm/'),
    'swin_large_patch4_window7_224.ms_in22k_ft_in1k': _cfg(hf_hub_id='timm/'),
    'test_swin.untrained': _cfg(input_size=(3, 96, 96)),
})


def checkpoint_filter_fn(state_dict, model):
    from ._torch_convert import convert_torch_state_dict
    out = {k: v for k, v in state_dict.items()
           if not k.endswith(('relative_position_index', 'attn_mask'))}
    return convert_torch_state_dict(out, model)


def _create_swin(variant: str, pretrained: bool = False, **kwargs) -> SwinTransformer:
    out_indices = kwargs.pop('out_indices', (0, 1, 2, 3))
    return build_model_with_cfg(
        SwinTransformer, variant, pretrained,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=out_indices),
        **kwargs,
    )


@register_model
def swin_tiny_patch4_window7_224(pretrained=False, **kwargs) -> SwinTransformer:
    model_args = dict(patch_size=4, window_size=7, embed_dim=96, depths=(2, 2, 6, 2), num_heads=(3, 6, 12, 24))
    return _create_swin('swin_tiny_patch4_window7_224', pretrained, **dict(model_args, **kwargs))


@register_model
def swin_small_patch4_window7_224(pretrained=False, **kwargs) -> SwinTransformer:
    model_args = dict(patch_size=4, window_size=7, embed_dim=96, depths=(2, 2, 18, 2), num_heads=(3, 6, 12, 24))
    return _create_swin('swin_small_patch4_window7_224', pretrained, **dict(model_args, **kwargs))


@register_model
def swin_base_patch4_window7_224(pretrained=False, **kwargs) -> SwinTransformer:
    model_args = dict(patch_size=4, window_size=7, embed_dim=128, depths=(2, 2, 18, 2), num_heads=(4, 8, 16, 32))
    return _create_swin('swin_base_patch4_window7_224', pretrained, **dict(model_args, **kwargs))


@register_model
def swin_large_patch4_window7_224(pretrained=False, **kwargs) -> SwinTransformer:
    model_args = dict(patch_size=4, window_size=7, embed_dim=192, depths=(2, 2, 18, 2), num_heads=(6, 12, 24, 48))
    return _create_swin('swin_large_patch4_window7_224', pretrained, **dict(model_args, **kwargs))


@register_model
def test_swin(pretrained=False, **kwargs) -> SwinTransformer:
    model_args = dict(
        img_size=96, patch_size=4, window_size=4, embed_dim=32, depths=(1, 1, 2), num_heads=(2, 2, 4))
    return _create_swin('test_swin', pretrained, **dict(model_args, **kwargs))
