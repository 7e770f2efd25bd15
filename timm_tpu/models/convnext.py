"""ConvNeXt / ConvNeXt-V2, TPU-native NHWC.

Re-designed from the reference (timm/models/convnext.py:1-1437): blocks are
dwconv7x7 → LN → pointwise-MLP (Linear on channels-last) → LayerScale →
DropPath, all in NHWC so the MLP is a plain matmul on the MXU. V2 swaps
LayerScale for GRN in the MLP.

Contract parity: forward_features/forward_head, get/reset_classifier,
group_matcher, set_grad_checkpointing, forward_intermediates, feature_info.
"""
from __future__ import annotations

from functools import partial
from typing import Any, Callable, Dict, List, Optional, Tuple, Union

import jax.numpy as jnp
from flax import nnx

from ..layers import (
    ClassifierHead, DropPath, GlobalResponseNormMlp, LayerNorm, LayerScale, Mlp,
    NormMlpClassifierHead, calculate_drop_path_rates, create_conv2d, get_act_fn,
    get_norm_layer, make_divisible, trunc_normal_,
)
from ..utils import tracing
from ._builder import build_model_with_cfg
from ._features import feature_take_indices
from ._manipulate import (
    BlockStackError, checkpoint_seq, resolve_stage_scan, scan_stage_stack,
    warn_scan_fallback,
)
from ._registry import generate_default_cfgs, register_model

__all__ = ['ConvNeXt', 'ConvNeXtBlock']


class Downsample(nnx.Module):
    def __init__(self, in_chs, out_chs, stride=1, dilation=1, *, dtype=None, param_dtype=jnp.float32, rngs):
        if in_chs != out_chs or stride > 1:
            self.conv = create_conv2d(
                in_chs, out_chs, 1, stride=stride, dilation=dilation,
                bias=True, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        else:
            self.conv = None

    def __call__(self, x):
        if self.conv is None:
            return x
        with tracing.scope('img.downsample'):
            return self.conv(x)


class ConvNeXtBlock(nnx.Module):
    """(reference convnext.py ConvNeXtBlock)."""

    def __init__(
            self,
            in_chs: int,
            out_chs: Optional[int] = None,
            kernel_size: int = 7,
            stride: int = 1,
            dilation: int = 1,
            mlp_ratio: float = 4.0,
            conv_bias: bool = True,
            use_grn: bool = False,
            ls_init_value: Optional[float] = 1e-6,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Optional[Callable] = None,
            drop_path: float = 0.0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        out_chs = out_chs or in_chs
        norm_layer = norm_layer or LayerNorm
        self.use_shortcut = stride == 1 and in_chs == out_chs

        self.conv_dw = create_conv2d(
            in_chs, out_chs, kernel_size, stride=stride, dilation=dilation,
            depthwise=True, bias=conv_bias, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm = norm_layer(out_chs, rngs=rngs)
        mlp_layer = GlobalResponseNormMlp if use_grn else Mlp
        self.mlp = mlp_layer(
            out_chs, int(mlp_ratio * out_chs), act_layer=act_layer,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.ls = LayerScale(out_chs, ls_init_value, param_dtype=param_dtype, rngs=rngs) \
            if ls_init_value is not None else None
        self.drop_path = DropPath(drop_path, rngs=rngs)
        self.shortcut = None if self.use_shortcut else Downsample(
            in_chs, out_chs, stride=stride, dilation=dilation,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)

    def __call__(self, x):
        with tracing.scope('img.block'):
            shortcut = x
            with tracing.scope('img.conv_dw'):
                x = self.conv_dw(x)
            x = self.norm(x)
            x = self.mlp(x)
            if self.ls is not None:
                x = self.ls(x)
            x = self.drop_path(x)
            if self.shortcut is not None:
                shortcut = self.shortcut(shortcut)
            return x + shortcut


class ConvNeXtStage(nnx.Module):
    def __init__(
            self,
            in_chs: int,
            out_chs: int,
            kernel_size: int = 7,
            stride: int = 2,
            depth: int = 2,
            dilation=(1, 1),
            drop_path_rates=None,
            ls_init_value: Optional[float] = 1.0,
            conv_bias: bool = True,
            use_grn: bool = False,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Optional[Callable] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        norm_layer = norm_layer or LayerNorm
        if in_chs != out_chs or stride > 1 or dilation[0] != dilation[1]:
            self.downsample_norm = norm_layer(in_chs, rngs=rngs)
            self.downsample_conv = create_conv2d(
                in_chs, out_chs, stride if stride > 1 else 1,
                stride=stride, dilation=dilation[0], padding=0, bias=conv_bias,
                dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            in_chs = out_chs
        else:
            self.downsample_norm = None
            self.downsample_conv = None

        drop_path_rates = drop_path_rates or [0.0] * depth
        self.blocks = nnx.List([
            ConvNeXtBlock(
                in_chs=in_chs if i == 0 else out_chs,
                out_chs=out_chs,
                kernel_size=kernel_size,
                dilation=dilation[1],
                drop_path=drop_path_rates[i],
                ls_init_value=ls_init_value,
                conv_bias=conv_bias,
                use_grn=use_grn,
                act_layer=act_layer,
                norm_layer=norm_layer,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
            for i in range(depth)
        ])
        self.grad_checkpointing = False
        self.stage_scan = False

    def __call__(self, x):
        if self.downsample_norm is not None:
            with tracing.scope('img.downsample'):
                x = self.downsample_norm(x)
                x = self.downsample_conv(x)
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


class ConvNeXt(nnx.Module):
    def __init__(
            self,
            in_chans: int = 3,
            num_classes: int = 1000,
            global_pool: str = 'avg',
            output_stride: int = 32,
            depths: Tuple[int, ...] = (3, 3, 9, 3),
            dims: Tuple[int, ...] = (96, 192, 384, 768),
            kernel_sizes: Union[int, Tuple[int, ...]] = 7,
            ls_init_value: Optional[float] = 1e-6,
            stem_type: str = 'patch',
            patch_size: int = 4,
            head_init_scale: float = 1.0,
            head_norm_first: bool = False,
            head_hidden_size: Optional[int] = None,
            conv_bias: bool = True,
            use_grn: bool = False,
            conv_mlp: bool = False,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Optional[Union[str, Callable]] = None,
            norm_eps: Optional[float] = None,
            drop_rate: float = 0.0,
            drop_path_rate: float = 0.0,
            stage_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        assert output_stride in (8, 16, 32)
        if isinstance(kernel_sizes, int):
            kernel_sizes = (kernel_sizes,) * 4
        # conv_mlp only changes the reference's torch memory layout (1x1-conv
        # MLP in NCHW vs Linear in NLC); in NHWC a Linear IS a 1x1 conv, so the
        # flag is accepted for cfg parity but structurally a no-op here.
        del conv_mlp
        norm_layer = get_norm_layer(norm_layer) or LayerNorm
        if norm_eps is not None:
            norm_layer = partial(norm_layer, eps=norm_eps)

        self.num_classes = num_classes
        self.drop_rate = drop_rate

        # stem
        assert stem_type in ('patch', 'overlap', 'overlap_tiered', 'overlap_act')
        if stem_type == 'patch':
            self.stem_conv = create_conv2d(
                in_chans, dims[0], patch_size, stride=patch_size, padding=0, bias=conv_bias,
                dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            self.stem_conv2 = None
            self.stem_norm = norm_layer(dims[0], rngs=rngs)
            stem_stride = patch_size
        else:
            mid_chs = make_divisible(dims[0] // 2) if 'tiered' in stem_type else dims[0]
            self.stem_conv = create_conv2d(
                in_chans, mid_chs, 3, stride=2, padding=None, bias=conv_bias,
                dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            self.stem_act = get_act_fn(act_layer) if 'act' in stem_type else None
            self.stem_conv2 = create_conv2d(
                mid_chs, dims[0], 3, stride=2, padding=None, bias=conv_bias,
                dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            self.stem_norm = norm_layer(dims[0], rngs=rngs)
            stem_stride = 4

        # stages
        dp_rates = calculate_drop_path_rates(drop_path_rate, list(depths), stagewise=True)
        stages = []
        prev_chs = dims[0]
        curr_stride = stem_stride
        dilation = 1
        self.feature_info = []
        for i in range(len(depths)):
            stride = 2 if curr_stride == 2 or i > 0 else 1
            if curr_stride >= output_stride and stride > 1:
                dilation *= stride
                stride = 1
            curr_stride *= stride
            first_dilation = 1 if dilation in (1, 2) else 2
            out_chs = dims[i]
            stages.append(ConvNeXtStage(
                prev_chs,
                out_chs,
                kernel_size=kernel_sizes[i],
                stride=stride,
                dilation=(first_dilation, dilation),
                depth=depths[i],
                drop_path_rates=dp_rates[i],
                ls_init_value=ls_init_value,
                conv_bias=conv_bias,
                use_grn=use_grn,
                act_layer=act_layer,
                norm_layer=norm_layer,
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            ))
            prev_chs = out_chs
            self.feature_info += [dict(num_chs=prev_chs, reduction=curr_stride, module=f'stages.{i}')]
        self.stages = nnx.List(stages)
        self.set_stage_scan(resolve_stage_scan(stage_scan))

        self.num_features = self.head_hidden_size = prev_chs
        if head_norm_first:
            self.norm_pre = norm_layer(self.num_features, rngs=rngs)
            self.head = ClassifierHead(
                self.num_features, num_classes, pool_type=global_pool, drop_rate=drop_rate,
                dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        else:
            self.norm_pre = None
            self.head = NormMlpClassifierHead(
                self.num_features, num_classes,
                hidden_size=head_hidden_size,
                pool_type=global_pool,
                drop_rate=drop_rate,
                norm_layer=norm_layer,
                act_layer='gelu',
                dtype=dtype,
                param_dtype=param_dtype,
                rngs=rngs,
            )
            if head_hidden_size:
                self.head_hidden_size = head_hidden_size
        self._dtype = dtype
        self._param_dtype = param_dtype

    # -- contract ------------------------------------------------------------
    def no_weight_decay(self) -> set:
        return set()

    def group_matcher(self, coarse: bool = False):
        return dict(
            stem=r'^stem_',
            blocks=r'^stages\.(\d+)' if coarse else [
                (r'^stages\.(\d+)\.downsample', (0,)),
                (r'^stages\.(\d+)\.blocks\.(\d+)', None),
                (r'^norm_pre', (99999,)),
            ],
        )

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
        self.num_classes = num_classes
        self.head.reset(num_classes, pool_type=global_pool, rngs=rngs)

    # -- forward -------------------------------------------------------------
    def _stem(self, x):
        with tracing.scope('img.stem'):
            x = self.stem_conv(x)
            if self.stem_conv2 is not None:
                if getattr(self, 'stem_act', None) is not None:
                    x = self.stem_act(x)
                x = self.stem_conv2(x)
            return self.stem_norm(x)

    def forward_features(self, x):
        x = self._stem(x)
        for stage in self.stages:
            x = stage(x)
        if self.norm_pre is not None:
            x = self.norm_pre(x)
        return x

    def forward_head(self, x, pre_logits: bool = False):
        with tracing.scope('img.head'):
            return self.head(x, pre_logits=pre_logits)

    def __call__(self, x):
        return self.forward_head(self.forward_features(x))

    def forward_intermediates(
            self,
            x,
            indices: Optional[Union[int, List[int]]] = None,
            norm: bool = False,
            stop_early: bool = False,
            output_fmt: str = 'NHWC',
            intermediates_only: bool = False,
    ):
        assert output_fmt == 'NHWC', 'Conv models emit NHWC features'
        take_indices, max_index = feature_take_indices(len(self.stages), indices)
        x = self._stem(x)
        intermediates = []
        stages = self.stages if not stop_early else list(self.stages)[:max_index + 1]
        for i, stage in enumerate(stages):
            x = stage(x)
            if i in take_indices:
                intermediates.append(x)
        if intermediates_only:
            return intermediates
        if self.norm_pre is not None:
            x = self.norm_pre(x)
        return x, intermediates

    def prune_intermediate_layers(self, indices=1, prune_norm: bool = False, prune_head: bool = True):
        take_indices, max_index = feature_take_indices(len(self.stages), indices)
        self.stages = nnx.List(list(self.stages)[:max_index + 1])
        if prune_norm:
            self.norm_pre = None
        if prune_head:
            self.reset_classifier(0, '')
        return take_indices


def _cfg(url: str = '', **kwargs) -> Dict[str, Any]:
    return {
        'url': url,
        'num_classes': 1000,
        'input_size': (3, 224, 224),
        'pool_size': (7, 7),
        'crop_pct': 0.875,
        'interpolation': 'bicubic',
        'mean': (0.485, 0.456, 0.406),
        'std': (0.229, 0.224, 0.225),
        'first_conv': 'stem_conv',
        'classifier': 'head.fc',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'convnext_atto.d2_in1k': _cfg(hf_hub_id='timm/'),
    'convnext_femto.d1_in1k': _cfg(hf_hub_id='timm/'),
    'convnext_pico.d1_in1k': _cfg(hf_hub_id='timm/'),
    'convnext_nano.d1h_in1k': _cfg(hf_hub_id='timm/'),
    'convnext_tiny.fb_in1k': _cfg(hf_hub_id='timm/'),
    'convnext_small.fb_in1k': _cfg(hf_hub_id='timm/'),
    'convnext_base.fb_in1k': _cfg(hf_hub_id='timm/'),
    'convnext_large.fb_in1k': _cfg(hf_hub_id='timm/'),
    'convnextv2_atto.fcmae_ft_in1k': _cfg(hf_hub_id='timm/'),
    'convnextv2_nano.fcmae_ft_in1k': _cfg(hf_hub_id='timm/'),
    'convnextv2_tiny.fcmae_ft_in1k': _cfg(hf_hub_id='timm/'),
    'convnextv2_base.fcmae_ft_in1k': _cfg(hf_hub_id='timm/'),
    'test_convnext.r160_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 160, 160), crop_pct=0.95),
    'test_convnext2.r160_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 160, 160), crop_pct=0.95),
    'test_convnext3.r160_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 160, 160), crop_pct=0.95),
    'convnext_zepto_rms.ra4_e3600_r224_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), first_conv='stem.0', classifier='head.fc'),
    'convnext_zepto_rms_ols.ra4_e3600_r224_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.9, mean=(0.5, 0.5, 0.5), std=(0.5, 0.5, 0.5), first_conv='stem.0', classifier='head.fc'),
    'convnext_atto_ols.a2_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=0.95, first_conv='stem.0', classifier='head.fc'),
    'convnext_atto_rms.untrained': _cfg(input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 256, 256), test_crop_pct=0.95, first_conv='stem.0', classifier='head.fc'),
    'convnext_femto_ols.d1_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=0.95, first_conv='stem.0', classifier='head.fc'),
    'convnext_pico_ols.d1_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.95, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=1.0, first_conv='stem.0', classifier='head.fc'),
    'convnext_nano_ols.d1h_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.95, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=1.0, first_conv='stem.0', classifier='head.fc'),
    'convnext_tiny_hnf.a2h_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.95, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=1.0, first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_soup_ft_in12k_in1k_320': _cfg(hf_hub_id='timm/', input_size=(3, 320, 320), pool_size=(10, 10), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_soup_ft_in12k_in1k_384': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_augreg_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), pool_size=(8, 8), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_augreg_ft_in1k_384': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_soup_ft_in12k_320': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 320, 320), pool_size=(10, 10), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_augreg_ft_in12k_384': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_soup_ft_in12k_384': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0, crop_mode='squash', mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_augreg': _cfg(hf_hub_id='timm/', num_classes=768, input_size=(3, 256, 256), pool_size=(8, 8), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_ft_320': _cfg(hf_hub_id='timm/', num_classes=768, input_size=(3, 320, 320), pool_size=(10, 10), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_large_mlp.clip_laion2b_ft_soup_320': _cfg(hf_hub_id='timm/', num_classes=768, input_size=(3, 320, 320), pool_size=(10, 10), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_xlarge.fb_in22k_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=1.0, first_conv='stem.0', classifier='head.fc'),
    'convnext_xlarge.fb_in22k_ft_in1k_384': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0, crop_mode='squash', mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnext_xlarge.fb_in22k': _cfg(hf_hub_id='timm/', num_classes=21841, input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnext_xxlarge.clip_laion2b_soup_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 256, 256), pool_size=(8, 8), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_xxlarge.clip_laion2b_soup_ft_in12k': _cfg(hf_hub_id='timm/', num_classes=11821, input_size=(3, 256, 256), pool_size=(8, 8), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_xxlarge.clip_laion2b_soup': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 256, 256), pool_size=(8, 8), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnext_xxlarge.clip_laion2b_rewind': _cfg(hf_hub_id='timm/', num_classes=1024, input_size=(3, 256, 256), pool_size=(8, 8), crop_pct=1.0, mean=(0.48145466, 0.4578275, 0.40821073), std=(0.26862954, 0.26130258, 0.27577711), first_conv='stem.0', classifier='head.fc'),
    'convnextv2_femto.fcmae_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=0.95, first_conv='stem.0', classifier='head.fc'),
    'convnextv2_femto.fcmae': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnextv2_pico.fcmae_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=0.95, first_conv='stem.0', classifier='head.fc'),
    'convnextv2_pico.fcmae': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnextv2_small.untrained': _cfg(input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnextv2_large.fcmae_ft_in22k_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=1.0, first_conv='stem.0', classifier='head.fc'),
    'convnextv2_large.fcmae_ft_in22k_in1k_384': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0, crop_mode='squash', mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnextv2_large.fcmae_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=1.0, first_conv='stem.0', classifier='head.fc'),
    'convnextv2_large.fcmae': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnextv2_huge.fcmae_ft_in22k_in1k_384': _cfg(hf_hub_id='timm/', input_size=(3, 384, 384), pool_size=(12, 12), crop_pct=1.0, crop_mode='squash', mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnextv2_huge.fcmae_ft_in22k_in1k_512': _cfg(hf_hub_id='timm/', input_size=(3, 512, 512), pool_size=(15, 15), crop_pct=1.0, crop_mode='squash', mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
    'convnextv2_huge.fcmae_ft_in1k': _cfg(hf_hub_id='timm/', input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), test_input_size=(3, 288, 288), test_crop_pct=1.0, first_conv='stem.0', classifier='head.fc'),
    'convnextv2_huge.fcmae': _cfg(hf_hub_id='timm/', num_classes=0, input_size=(3, 224, 224), pool_size=(7, 7), crop_pct=0.875, mean=(0.485, 0.456, 0.406), std=(0.229, 0.224, 0.225), first_conv='stem.0', classifier='head.fc'),
})


def checkpoint_filter_fn(state_dict, model):
    """Map reference-timm convnext names → this module's layout
    (stem/downsample Sequential indices, bare `gamma` LayerScale)."""
    import re
    from ._torch_convert import convert_torch_state_dict
    import numpy as np
    # overlap stems: stem.0/stem.1 are convs (4D), stem.2 is the norm;
    # overlap_act stems have a paramless act at index 1 (conv at 2, norm at 3)
    overlap_act_stem = any(k.startswith('stem.3.') for k in state_dict)
    overlap_stem = any(k.startswith('stem.2.') for k in state_dict)
    out = {}
    for k, v in state_dict.items():
        if overlap_act_stem:
            k = re.sub(r'^stem\.0\.', 'stem_conv.', k)
            k = re.sub(r'^stem\.2\.', 'stem_conv2.', k)
            k = re.sub(r'^stem\.3\.', 'stem_norm.', k)
        elif overlap_stem:
            k = re.sub(r'^stem\.0\.', 'stem_conv.', k)
            k = re.sub(r'^stem\.1\.', 'stem_conv2.', k)
            k = re.sub(r'^stem\.2\.', 'stem_norm.', k)
        else:
            k = re.sub(r'^stem\.0\.', 'stem_conv.', k)
            k = re.sub(r'^stem\.1\.', 'stem_norm.', k)
        k = re.sub(r'(stages\.\d+)\.downsample\.0\.', r'\1.downsample_norm.', k)
        k = re.sub(r'(stages\.\d+)\.downsample\.1\.', r'\1.downsample_conv.', k)
        k = re.sub(r'(blocks\.\d+)\.gamma$', r'\1.ls.gamma', k)
        if k.endswith(('.grn.weight', '.grn.bias')):
            v = v.reshape(-1)  # reference stores (1,1,1,C)
        out[k] = v
    return convert_torch_state_dict(out, model)


def _create_convnext(variant: str, pretrained: bool = False, **kwargs) -> ConvNeXt:
    out_indices = kwargs.pop('out_indices', (0, 1, 2, 3))
    return build_model_with_cfg(
        ConvNeXt,
        variant,
        pretrained,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=out_indices),
        **kwargs,
    )


@register_model
def convnext_atto(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 6, 2), dims=(40, 80, 160, 320), )
    return _create_convnext('convnext_atto', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_femto(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 6, 2), dims=(48, 96, 192, 384), )
    return _create_convnext('convnext_femto', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_pico(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 6, 2), dims=(64, 128, 256, 512), )
    return _create_convnext('convnext_pico', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_nano(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 8, 2), dims=(80, 160, 320, 640), )
    return _create_convnext('convnext_nano', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_tiny(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(3, 3, 9, 3), dims=(96, 192, 384, 768))
    return _create_convnext('convnext_tiny', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_small(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(3, 3, 27, 3), dims=(96, 192, 384, 768))
    return _create_convnext('convnext_small', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_base(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(3, 3, 27, 3), dims=(128, 256, 512, 1024))
    return _create_convnext('convnext_base', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_large(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(3, 3, 27, 3), dims=(192, 384, 768, 1536))
    return _create_convnext('convnext_large', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_atto(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 6, 2), dims=(40, 80, 160, 320), use_grn=True, ls_init_value=None, conv_bias=True)
    return _create_convnext('convnextv2_atto', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_nano(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 8, 2), dims=(80, 160, 320, 640), use_grn=True, ls_init_value=None, conv_bias=True)
    return _create_convnext('convnextv2_nano', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_tiny(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(3, 3, 9, 3), dims=(96, 192, 384, 768), use_grn=True, ls_init_value=None)
    return _create_convnext('convnextv2_tiny', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_base(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(3, 3, 27, 3), dims=(128, 256, 512, 1024), use_grn=True, ls_init_value=None)
    return _create_convnext('convnextv2_base', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def test_convnext(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(1, 2, 4, 2), dims=(24, 32, 48, 64), norm_layer='layernorm')
    return _create_convnext('test_convnext', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def test_convnext2(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(1, 1, 1, 1), dims=(32, 64, 96, 128))
    return _create_convnext('test_convnext2', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def test_convnext3(pretrained=False, **kwargs) -> ConvNeXt:
    model_args = dict(
        depths=(1, 1, 1, 1), dims=(32, 64, 96, 128), stem_type='overlap_tiered', use_grn=True, ls_init_value=None)
    return _create_convnext('test_convnext3', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_zepto_rms(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 4, 2), dims=(32, 64, 128, 256), conv_mlp=True, norm_layer='simplenorm')
    return _create_convnext('convnext_zepto_rms', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_zepto_rms_ols(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(
        depths=(2, 2, 4, 2), dims=(32, 64, 128, 256), conv_mlp=True, norm_layer='simplenorm', stem_type='overlap_act')
    return _create_convnext('convnext_zepto_rms_ols', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_atto_ols(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 6, 2), dims=(40, 80, 160, 320), conv_mlp=True, stem_type='overlap_tiered')
    return _create_convnext('convnext_atto_ols', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_atto_rms(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 6, 2), dims=(40, 80, 160, 320), conv_mlp=True, norm_layer='rmsnorm2d')
    return _create_convnext('convnext_atto_rms', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_femto_ols(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 6, 2), dims=(48, 96, 192, 384), conv_mlp=True, stem_type='overlap_tiered')
    return _create_convnext('convnext_femto_ols', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_pico_ols(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 6, 2), dims=(64, 128, 256, 512), conv_mlp=True,  stem_type='overlap_tiered')
    return _create_convnext('convnext_pico_ols', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_nano_ols(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(2, 2, 8, 2), dims=(80, 160, 320, 640), conv_mlp=True, stem_type='overlap')
    return _create_convnext('convnext_nano_ols', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_tiny_hnf(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=(3, 3, 9, 3), dims=(96, 192, 384, 768), head_norm_first=True, conv_mlp=True)
    return _create_convnext('convnext_tiny_hnf', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_large_mlp(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=[3, 3, 27, 3], dims=[192, 384, 768, 1536], head_hidden_size=1536)
    return _create_convnext('convnext_large_mlp', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_xlarge(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=[3, 3, 27, 3], dims=[256, 512, 1024, 2048])
    return _create_convnext('convnext_xlarge', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnext_xxlarge(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=[3, 4, 30, 3], dims=[384, 768, 1536, 3072], norm_eps=kwargs.pop('norm_eps', 1e-5))
    return _create_convnext('convnext_xxlarge', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_femto(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(
        depths=(2, 2, 6, 2), dims=(48, 96, 192, 384), use_grn=True, ls_init_value=None, conv_mlp=True)
    return _create_convnext('convnextv2_femto', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_pico(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(
        depths=(2, 2, 6, 2), dims=(64, 128, 256, 512), use_grn=True, ls_init_value=None, conv_mlp=True)
    return _create_convnext('convnextv2_pico', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_small(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=[3, 3, 27, 3], dims=[96, 192, 384, 768], use_grn=True, ls_init_value=None)
    return _create_convnext('convnextv2_small', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_large(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=[3, 3, 27, 3], dims=[192, 384, 768, 1536], use_grn=True, ls_init_value=None)
    return _create_convnext('convnextv2_large', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def convnextv2_huge(pretrained: bool = False, **kwargs) -> ConvNeXt:
    model_args = dict(depths=[3, 3, 27, 3], dims=[352, 704, 1408, 2816], use_grn=True, ls_init_value=None)
    return _create_convnext('convnextv2_huge', pretrained=pretrained, **dict(model_args, **kwargs))
