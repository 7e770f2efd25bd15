"""MetaFormer baselines (PoolFormer v1/v2, ConvFormer, CAFormer), TPU-native
(reference: timm/models/metaformer.py:1-1370; Yu et al. 2022).

One trunk parameterized by the token mixer per stage: 3x3-avg-pool delta
(PoolFormer), separable inverted conv (ConvFormer), or vanilla attention
(CAFormer upper stages). NHWC collapses the reference's NCHW/NLC dual code
paths — attention stages just flatten the spatial axes.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import (
    Dropout, DropPath, GroupNorm1, LayerNorm, LayerNorm2d, Pool2d,
    SelectAdaptivePool2d, calculate_drop_path_rates, get_act_fn, to_ntuple,
    trunc_normal_, zeros_,
)
from ._builder import build_model_with_cfg
from ._features import feature_take_indices
from ._manipulate import (
    BlockStackError, checkpoint_seq, resolve_stage_scan, scan_stage_stack,
    warn_scan_fallback,
)
from ._registry import generate_default_cfgs, register_model

__all__ = ['MetaFormer']


class GroupNorm1NoBias(nnx.GroupNorm):
    def __init__(self, num_channels, eps: float = 1e-6, *, dtype=None,
                 param_dtype=jnp.float32, rngs: nnx.Rngs):
        super().__init__(num_channels, num_groups=1, epsilon=eps, use_bias=False,
                         use_scale=True, dtype=dtype, param_dtype=param_dtype, rngs=rngs)


class LayerNormNoBias(nnx.LayerNorm):
    def __init__(self, num_channels, eps: float = 1e-6, *, dtype=None,
                 param_dtype=jnp.float32, rngs: nnx.Rngs):
        super().__init__(num_channels, epsilon=eps, use_bias=False, use_scale=True,
                         dtype=dtype, param_dtype=param_dtype, rngs=rngs)


LayerNorm2dNoBias = LayerNormNoBias  # NHWC: per-position channel norm


class StarReLU(nnx.Module):
    """s * relu(x)^2 + b with learnable scalars (reference metaformer.py:161)."""

    def __init__(self, scale_value=1.0, bias_value=0.0, *, param_dtype=jnp.float32, rngs=None):
        self.scale = nnx.Param(jnp.full((1,), scale_value, param_dtype))
        self.bias = nnx.Param(jnp.full((1,), bias_value, param_dtype))

    def __call__(self, x):
        r = jax.nn.relu(x)
        return self.scale[...].astype(x.dtype) * r * r + self.bias[...].astype(x.dtype)


class _ActModule(nnx.Module):
    """Wraps a parameter-free activation as a module for name symmetry."""

    def __init__(self, act, *, rngs=None):
        self._fn = get_act_fn(act)

    def __call__(self, x):
        return self._fn(x)


def _make_act(act, rngs):
    if act == 'starrelu':
        return StarReLU(rngs=rngs)
    return _ActModule(act)


class Scale(nnx.Module):
    """Per-channel learned scale (reference metaformer.py:125)."""

    def __init__(self, dim, init_value=1.0, *, param_dtype=jnp.float32, rngs=None):
        self.scale = nnx.Param(jnp.full((dim,), init_value, param_dtype))

    def __call__(self, x):
        return x * self.scale[...].astype(x.dtype)


class Pooling(nnx.Module):
    """avgpool(x) - x token mixer (reference metaformer.py:316); avg pool is
    3x3 s1 p1 with count_include_pad=False (Pool2d's semantics)."""

    def __init__(self, dim=None, pool_size=3, proj_drop=0.0, *, dtype=None,
                 param_dtype=jnp.float32, rngs=None):
        self.pool = Pool2d('avg', pool_size, 1, pool_size // 2)

    def __call__(self, x):
        return self.pool(x) - x


class SepConv(nnx.Module):
    """Inverted separable conv mixer (reference metaformer.py:272)."""

    def __init__(self, dim, expansion_ratio=2.0, act1_layer='starrelu', act2_layer=None,
                 bias=False, kernel_size=7, padding=3, proj_drop=0.0,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        mid = int(expansion_ratio * dim)
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.pwconv1 = nnx.Linear(dim, mid, use_bias=bias, kernel_init=trunc_normal_(std=0.02),
                                  bias_init=zeros_, **kw)
        self.act1 = _make_act(act1_layer, rngs)
        self.dwconv = nnx.Conv(mid, mid, kernel_size=(kernel_size, kernel_size),
                               padding=[(padding, padding), (padding, padding)],
                               feature_group_count=mid, use_bias=bias, **kw)
        self.act2 = _make_act(act2_layer, rngs) if act2_layer else None
        self.pwconv2 = nnx.Linear(mid, dim, use_bias=bias, kernel_init=trunc_normal_(std=0.02),
                                  bias_init=zeros_, **kw)

    def __call__(self, x):
        x = self.act1(self.pwconv1(x))
        x = self.dwconv(x)
        if self.act2 is not None:
            x = self.act2(x)
        return self.pwconv2(x)


class MetaAttention(nnx.Module):
    """Plain MHSA over flattened spatial tokens (reference metaformer.py:188)."""

    def __init__(self, dim, head_dim=32, num_heads=None, qkv_bias=False,
                 attn_drop=0.0, proj_drop=0.0, proj_bias=False,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.head_dim = head_dim
        self.scale = head_dim ** -0.5
        self.num_heads = num_heads if num_heads else max(dim // head_dim, 1)
        self.attention_dim = self.num_heads * head_dim
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.qkv = nnx.Linear(dim, self.attention_dim * 3, use_bias=qkv_bias,
                              kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, **kw)
        self.attn_drop = Dropout(attn_drop, rngs=rngs)
        self.proj = nnx.Linear(self.attention_dim, dim, use_bias=proj_bias,
                               kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, **kw)
        self.proj_drop = Dropout(proj_drop, rngs=rngs)

    def __call__(self, x):
        B, H, W, C = x.shape
        N = H * W
        qkv = self.qkv(x).reshape(B, N, 3, self.num_heads, self.head_dim).transpose(2, 0, 3, 1, 4)
        q, k, v = qkv[0] * self.scale, qkv[1], qkv[2]
        attn = jnp.einsum('bhnd,bhmd->bhnm', q, k)
        attn = jax.nn.softmax(attn, axis=-1)
        attn = self.attn_drop(attn)
        y = jnp.einsum('bhnm,bhmd->bhnd', attn, v)
        # attention_dim may differ from dim (dim not divisible by head_dim);
        # proj maps it back
        y = y.transpose(0, 2, 1, 3).reshape(B, H, W, self.attention_dim)
        y = self.proj(y)
        return self.proj_drop(y)


_MIXERS = {'pooling': Pooling, 'sepconv': SepConv, 'attention': MetaAttention}


class MetaMlp(nnx.Module):
    """MLP with a module act (StarReLU carries params) — names fc1/act/fc2
    match the reference Mlp layout."""

    def __init__(self, dim, hidden, act='starrelu', bias=False, drop=0.0,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.fc1 = nnx.Linear(dim, hidden, use_bias=bias, kernel_init=trunc_normal_(std=0.02),
                              bias_init=zeros_, **kw)
        self.act = _make_act(act, rngs)
        self.drop1 = Dropout(drop, rngs=rngs)
        self.fc2 = nnx.Linear(hidden, dim, use_bias=bias, kernel_init=trunc_normal_(std=0.02),
                              bias_init=zeros_, **kw)
        self.drop2 = Dropout(drop, rngs=rngs)

    def __call__(self, x):
        x = self.drop1(self.act(self.fc1(x)))
        return self.drop2(self.fc2(x))


class MetaFormerBlock(nnx.Module):
    """(reference metaformer.py:364-423)."""

    def __init__(self, dim, token_mixer='pooling', mlp_act='starrelu', mlp_bias=False,
                 norm_layer: Callable = LayerNorm2d, proj_drop=0.0, drop_path=0.0,
                 layer_scale_init_value=None, res_scale_init_value=None,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = norm_layer(dim, rngs=rngs)
        self.token_mixer = _MIXERS[token_mixer](dim=dim, proj_drop=proj_drop, **kw)
        self.drop_path1 = DropPath(drop_path, rngs=rngs)
        self.layer_scale1 = Scale(dim, layer_scale_init_value, param_dtype=param_dtype) \
            if layer_scale_init_value is not None else None
        self.res_scale1 = Scale(dim, res_scale_init_value, param_dtype=param_dtype) \
            if res_scale_init_value is not None else None
        self.norm2 = norm_layer(dim, rngs=rngs)
        self.mlp = MetaMlp(dim, 4 * dim, act=mlp_act, bias=mlp_bias, drop=proj_drop, **kw)
        self.drop_path2 = DropPath(drop_path, rngs=rngs)
        self.layer_scale2 = Scale(dim, layer_scale_init_value, param_dtype=param_dtype) \
            if layer_scale_init_value is not None else None
        self.res_scale2 = Scale(dim, res_scale_init_value, param_dtype=param_dtype) \
            if res_scale_init_value is not None else None

    def __call__(self, x):
        y = self.drop_path1(self.token_mixer(self.norm1(x)))
        if self.layer_scale1 is not None:
            y = self.layer_scale1(y)
        x = (self.res_scale1(x) if self.res_scale1 is not None else x) + y
        y = self.drop_path2(self.mlp(self.norm2(x)))
        if self.layer_scale2 is not None:
            y = self.layer_scale2(y)
        x = (self.res_scale2(x) if self.res_scale2 is not None else x) + y
        return x


class Downsampling(nnx.Module):
    def __init__(self, in_chs, out_chs, kernel_size, stride=1, padding=0,
                 norm_layer=None, *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.norm = norm_layer(in_chs, rngs=rngs) if norm_layer else None
        self.conv = nnx.Conv(
            in_chs, out_chs, kernel_size=(kernel_size, kernel_size), strides=stride,
            padding=[(padding, padding), (padding, padding)],
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)

    def __call__(self, x):
        if self.norm is not None:
            x = self.norm(x)
        return self.conv(x)


class MetaFormerStage(nnx.Module):
    def __init__(self, in_chs, out_chs, depth=2, token_mixer='pooling', mlp_act='starrelu',
                 mlp_bias=False, downsample_norm=None, norm_layer: Callable = LayerNorm2d,
                 proj_drop=0.0, dp_rates=(0.0, 0.0), layer_scale_init_value=None,
                 res_scale_init_value=None,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.grad_checkpointing = False
        self.stage_scan = False
        self.downsample = None if in_chs == out_chs else Downsampling(
            in_chs, out_chs, kernel_size=3, stride=2, padding=1, norm_layer=downsample_norm, **kw)
        self.blocks = nnx.List([
            MetaFormerBlock(
                dim=out_chs, token_mixer=token_mixer, mlp_act=mlp_act, mlp_bias=mlp_bias,
                norm_layer=norm_layer, proj_drop=proj_drop, drop_path=dp_rates[i],
                layer_scale_init_value=layer_scale_init_value,
                res_scale_init_value=res_scale_init_value, **kw)
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


class _Stem(nnx.Module):
    def __init__(self, in_chs, out_chs, norm_layer=None, *, dtype=None,
                 param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.conv = nnx.Conv(
            in_chs, out_chs, kernel_size=(7, 7), strides=4, padding=[(2, 2), (2, 2)],
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm = norm_layer(out_chs, rngs=rngs) if norm_layer else None

    def __call__(self, x):
        x = self.conv(x)
        return self.norm(x) if self.norm is not None else x


class MlpHead(nnx.Module):
    """fc1 → squared relu → norm → fc2 (reference metaformer.py:330)."""

    def __init__(self, dim, num_classes=1000, mlp_ratio=4.0, drop_rate=0.0,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        hidden = int(mlp_ratio * dim)
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.fc1 = nnx.Linear(dim, hidden, kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, **kw)
        self.norm = LayerNorm(hidden, rngs=rngs)
        self.head_drop = Dropout(drop_rate, rngs=rngs)
        self.fc2 = nnx.Linear(hidden, num_classes, kernel_init=trunc_normal_(std=0.02),
                              bias_init=zeros_, **kw)

    def __call__(self, x):
        r = jax.nn.relu(self.fc1(x))
        x = self.norm(r * r)
        return self.fc2(self.head_drop(x))


class _Head(nnx.Module):
    def __init__(self, num_features, num_classes, global_pool='avg', drop_rate=0.0,
                 use_mlp_head=True, output_norm: Callable = LayerNorm2d,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.global_pool = SelectAdaptivePool2d(pool_type=global_pool, flatten=True)
        self.norm = output_norm(num_features, rngs=rngs)
        self.drop = Dropout(drop_rate if use_mlp_head else 0.0, rngs=rngs)
        if num_classes > 0:
            if use_mlp_head:
                self.fc = MlpHead(num_features, num_classes, drop_rate=drop_rate,
                                  dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            else:
                self.fc = nnx.Linear(
                    num_features, num_classes, kernel_init=trunc_normal_(std=0.02),
                    bias_init=zeros_, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        else:
            self.fc = None

    def __call__(self, x, pre_logits: bool = False):
        x = self.global_pool(x[:, None, None, :] if x.ndim == 2 else x)
        x = self.norm(x)
        x = self.drop(x)
        if pre_logits or self.fc is None:
            return x
        return self.fc(x)


class MetaFormer(nnx.Module):
    """MetaFormer with the reference's model contract
    (reference metaformer.py:499-744)."""

    def __init__(
            self,
            in_chans: int = 3,
            num_classes: int = 1000,
            global_pool: str = 'avg',
            depths: Tuple[int, ...] = (2, 2, 6, 2),
            dims: Tuple[int, ...] = (64, 128, 320, 512),
            token_mixers: Union[str, List[str]] = 'pooling',
            mlp_act: str = 'starrelu',
            mlp_bias: bool = False,
            drop_path_rate: float = 0.0,
            proj_drop_rate: float = 0.0,
            drop_rate: float = 0.0,
            layer_scale_init_values=None,
            res_scale_init_values=(None, None, 1.0, 1.0),
            downsample_norm: Optional[Callable] = LayerNorm2dNoBias,
            norm_layers: Union[Callable, List[Callable]] = LayerNorm2dNoBias,
            output_norm: Callable = LayerNorm2d,
            use_mlp_head: bool = True,
            stage_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.num_classes = num_classes
        self.num_features = dims[-1]
        self.head_hidden_size = dims[-1]
        self.drop_rate = drop_rate
        self.use_mlp_head = use_mlp_head
        num_stages = len(depths)
        if not isinstance(token_mixers, (list, tuple)):
            token_mixers = [token_mixers] * num_stages
        if not isinstance(norm_layers, (list, tuple)):
            norm_layers = [norm_layers] * num_stages
        if not isinstance(layer_scale_init_values, (list, tuple)):
            layer_scale_init_values = [layer_scale_init_values] * num_stages
        if not isinstance(res_scale_init_values, (list, tuple)):
            res_scale_init_values = [res_scale_init_values] * num_stages
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)

        self.stem = _Stem(in_chans, dims[0], norm_layer=downsample_norm, **kw)
        dp_rates = calculate_drop_path_rates(drop_path_rate, list(depths), stagewise=True)
        stages = []
        prev_dim = dims[0]
        self.feature_info = []
        for i in range(num_stages):
            stages.append(MetaFormerStage(
                prev_dim, dims[i], depth=depths[i], token_mixer=token_mixers[i],
                mlp_act=mlp_act, mlp_bias=mlp_bias, proj_drop=proj_drop_rate,
                dp_rates=dp_rates[i], layer_scale_init_value=layer_scale_init_values[i],
                res_scale_init_value=res_scale_init_values[i],
                downsample_norm=downsample_norm, norm_layer=norm_layers[i], **kw))
            prev_dim = dims[i]
            self.feature_info += [dict(num_chs=dims[i], reduction=2 ** (i + 2), module=f'stages.{i}')]
        self.stages = nnx.List(stages)
        self.set_stage_scan(resolve_stage_scan(stage_scan))
        self.head = _Head(
            self.num_features, num_classes, global_pool=global_pool, drop_rate=drop_rate,
            use_mlp_head=use_mlp_head, output_norm=output_norm, **kw)
        self._dtype = dtype
        self._param_dtype = param_dtype

    # -- contract ------------------------------------------------------------
    def no_weight_decay(self) -> set:
        return set()  # reference also decays StarReLU/Scale params

    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^stem', blocks=r'^stages\.(\d+)' if coarse else r'^stages\.(\d+)\.blocks\.(\d+)')

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
        return self.head.fc

    def reset_classifier(self, num_classes: int, global_pool: Optional[str] = None, *, rngs=None):
        # replace only the fc (reference keeps the trained head.norm)
        self.num_classes = num_classes
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        if global_pool is not None:
            self.head.global_pool = SelectAdaptivePool2d(pool_type=global_pool, flatten=True)
        if num_classes > 0:
            if self.use_mlp_head:
                self.head.fc = MlpHead(
                    self.num_features, num_classes, drop_rate=self.drop_rate,
                    dtype=self._dtype, param_dtype=self._param_dtype, rngs=rngs)
            else:
                self.head.fc = nnx.Linear(
                    self.num_features, num_classes, kernel_init=trunc_normal_(std=0.02),
                    bias_init=zeros_, dtype=self._dtype, param_dtype=self._param_dtype, rngs=rngs)
        else:
            self.head.fc = None

    # -- forward -------------------------------------------------------------
    def forward_features(self, x):
        x = self.stem(x)
        for stage in self.stages:
            x = stage(x)
        return x

    def forward_head(self, x, pre_logits: bool = False):
        return self.head(x, pre_logits=pre_logits)

    def __call__(self, x):
        return self.forward_head(self.forward_features(x))

    def forward_intermediates(
            self, x, indices=None, norm: bool = False, stop_early: bool = False,
            output_fmt: str = 'NHWC', intermediates_only: bool = False,
    ):
        assert output_fmt == 'NHWC'
        take_indices, max_index = feature_take_indices(len(self.stages), indices)
        x = self.stem(x)
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
    from ._torch_convert import convert_torch_state_dict
    return convert_torch_state_dict(state_dict, model)


def _create_metaformer(variant, pretrained=False, **kwargs):
    default_out_indices = tuple(range(len(kwargs.get('depths', (2, 2, 6, 2)))))
    out_indices = kwargs.pop('out_indices', default_out_indices)
    return build_model_with_cfg(
        MetaFormer, variant, pretrained,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=out_indices),
        **kwargs,
    )


def _cfg(url: str = '', **kwargs) -> Dict[str, Any]:
    return {
        'url': url,
        'num_classes': 1000, 'input_size': (3, 224, 224), 'pool_size': (7, 7),
        'crop_pct': 1.0, 'interpolation': 'bicubic',
        'mean': (0.485, 0.456, 0.406), 'std': (0.229, 0.224, 0.225),
        'first_conv': 'stem.conv', 'classifier': 'head.fc',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'poolformer_s12.sail_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.9),
    'poolformer_s24.sail_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.9),
    'poolformer_s36.sail_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.9),
    'poolformer_m36.sail_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.95),
    'poolformer_m48.sail_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.95),
    'poolformerv2_s12.sail_in1k': _cfg(hf_hub_id='timm/'),
    'poolformerv2_s24.sail_in1k': _cfg(hf_hub_id='timm/'),
    'poolformerv2_s36.sail_in1k': _cfg(hf_hub_id='timm/'),
    'poolformerv2_m36.sail_in1k': _cfg(hf_hub_id='timm/'),
    'poolformerv2_m48.sail_in1k': _cfg(hf_hub_id='timm/'),
    'convformer_s18.sail_in1k': _cfg(hf_hub_id='timm/'),
    'convformer_s36.sail_in1k': _cfg(hf_hub_id='timm/'),
    'convformer_m36.sail_in1k': _cfg(hf_hub_id='timm/'),
    'convformer_b36.sail_in1k': _cfg(hf_hub_id='timm/'),
    'caformer_s18.sail_in1k': _cfg(hf_hub_id='timm/'),
    'caformer_s36.sail_in1k': _cfg(hf_hub_id='timm/'),
    'caformer_m36.sail_in1k': _cfg(hf_hub_id='timm/'),
    'caformer_b36.sail_in1k': _cfg(hf_hub_id='timm/'),
})


def _poolformer_v1_args(**kwargs):
    return dict(
        downsample_norm=None, mlp_act='gelu', mlp_bias=True, norm_layers=GroupNorm1,
        layer_scale_init_values=1e-5, res_scale_init_values=None, use_mlp_head=False,
        **kwargs)


@register_model
def poolformer_s12(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = _poolformer_v1_args(depths=(2, 2, 6, 2), dims=(64, 128, 320, 512), **kwargs)
    return _create_metaformer('poolformer_s12', pretrained=pretrained, **model_kwargs)


@register_model
def poolformer_s24(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = _poolformer_v1_args(depths=(4, 4, 12, 4), dims=(64, 128, 320, 512), **kwargs)
    return _create_metaformer('poolformer_s24', pretrained=pretrained, **model_kwargs)


@register_model
def poolformer_s36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = _poolformer_v1_args(
        depths=(6, 6, 18, 6), dims=(64, 128, 320, 512), layer_scale_init_values=1e-6, **kwargs)
    return _create_metaformer('poolformer_s36', pretrained=pretrained, **model_kwargs)


@register_model
def poolformer_m36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = _poolformer_v1_args(
        depths=(6, 6, 18, 6), dims=(96, 192, 384, 768), layer_scale_init_values=1e-6, **kwargs)
    return _create_metaformer('poolformer_m36', pretrained=pretrained, **model_kwargs)


@register_model
def poolformer_m48(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = _poolformer_v1_args(
        depths=(8, 8, 24, 8), dims=(96, 192, 384, 768), layer_scale_init_values=1e-6, **kwargs)
    return _create_metaformer('poolformer_m48', pretrained=pretrained, **model_kwargs)


@register_model
def poolformerv2_s12(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(2, 2, 6, 2), dims=(64, 128, 320, 512),
                        norm_layers=GroupNorm1NoBias, use_mlp_head=False, **kwargs)
    return _create_metaformer('poolformerv2_s12', pretrained=pretrained, **model_kwargs)


@register_model
def poolformerv2_s24(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(4, 4, 12, 4), dims=(64, 128, 320, 512),
                        norm_layers=GroupNorm1NoBias, use_mlp_head=False, **kwargs)
    return _create_metaformer('poolformerv2_s24', pretrained=pretrained, **model_kwargs)


@register_model
def poolformerv2_s36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(6, 6, 18, 6), dims=(64, 128, 320, 512),
                        norm_layers=GroupNorm1NoBias, use_mlp_head=False, **kwargs)
    return _create_metaformer('poolformerv2_s36', pretrained=pretrained, **model_kwargs)


@register_model
def poolformerv2_m36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(6, 6, 18, 6), dims=(96, 192, 384, 768),
                        norm_layers=GroupNorm1NoBias, use_mlp_head=False, **kwargs)
    return _create_metaformer('poolformerv2_m36', pretrained=pretrained, **model_kwargs)


@register_model
def poolformerv2_m48(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(8, 8, 24, 8), dims=(96, 192, 384, 768),
                        norm_layers=GroupNorm1NoBias, use_mlp_head=False, **kwargs)
    return _create_metaformer('poolformerv2_m48', pretrained=pretrained, **model_kwargs)


@register_model
def convformer_s18(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(3, 3, 9, 3), dims=(64, 128, 320, 512),
                        token_mixers='sepconv', norm_layers=LayerNorm2dNoBias, **kwargs)
    return _create_metaformer('convformer_s18', pretrained=pretrained, **model_kwargs)


@register_model
def convformer_s36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(3, 12, 18, 3), dims=(64, 128, 320, 512),
                        token_mixers='sepconv', norm_layers=LayerNorm2dNoBias, **kwargs)
    return _create_metaformer('convformer_s36', pretrained=pretrained, **model_kwargs)


@register_model
def convformer_m36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(3, 12, 18, 3), dims=(96, 192, 384, 576),
                        token_mixers='sepconv', norm_layers=LayerNorm2dNoBias, **kwargs)
    return _create_metaformer('convformer_m36', pretrained=pretrained, **model_kwargs)


@register_model
def convformer_b36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(depths=(3, 12, 18, 3), dims=(128, 256, 512, 768),
                        token_mixers='sepconv', norm_layers=LayerNorm2dNoBias, **kwargs)
    return _create_metaformer('convformer_b36', pretrained=pretrained, **model_kwargs)


@register_model
def caformer_s18(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(
        depths=(3, 3, 9, 3), dims=(64, 128, 320, 512),
        token_mixers=['sepconv', 'sepconv', 'attention', 'attention'],
        norm_layers=[LayerNorm2dNoBias] * 2 + [LayerNormNoBias] * 2, **kwargs)
    return _create_metaformer('caformer_s18', pretrained=pretrained, **model_kwargs)


@register_model
def caformer_s36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(
        depths=(3, 12, 18, 3), dims=(64, 128, 320, 512),
        token_mixers=['sepconv', 'sepconv', 'attention', 'attention'],
        norm_layers=[LayerNorm2dNoBias] * 2 + [LayerNormNoBias] * 2, **kwargs)
    return _create_metaformer('caformer_s36', pretrained=pretrained, **model_kwargs)


@register_model
def caformer_m36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(
        depths=(3, 12, 18, 3), dims=(96, 192, 384, 576),
        token_mixers=['sepconv', 'sepconv', 'attention', 'attention'],
        norm_layers=[LayerNorm2dNoBias] * 2 + [LayerNormNoBias] * 2, **kwargs)
    return _create_metaformer('caformer_m36', pretrained=pretrained, **model_kwargs)


@register_model
def caformer_b36(pretrained=False, **kwargs) -> MetaFormer:
    model_kwargs = dict(
        depths=(3, 12, 18, 3), dims=(128, 256, 512, 768),
        token_mixers=['sepconv', 'sepconv', 'attention', 'attention'],
        norm_layers=[LayerNorm2dNoBias] * 2 + [LayerNormNoBias] * 2, **kwargs)
    return _create_metaformer('caformer_b36', pretrained=pretrained, **model_kwargs)
