"""RegNet X/Y (reference: timm/models/regnet.py:1-1490), TPU-native NHWC.

Widths/depths from the RegNet linear log-space parameterization; Y variants
add SE. Bottleneck blocks with group conv reuse the conv/norm-act stack.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional, Union

import numpy as np
import jax.numpy as jnp
from flax import nnx

from ..layers import BatchNormAct2d, ClassifierHead, DropPath, SEModule, create_conv2d, get_act_fn
from ._builder import build_model_with_cfg
from ._features import feature_take_indices
from ._manipulate import (
    BlockStackError, checkpoint_seq, resolve_stage_scan, scan_stage_stack,
    warn_scan_fallback,
)
from ._registry import generate_default_cfgs, register_model

__all__ = ['RegNet']


def generate_regnet_widths(width_slope: float, width_initial: int, width_mult: float, depth: int,
                           group_size: int, quant: int = 8):
    """Per-stage (widths, depths) from the RegNet parameterization
    (reference regnet.py generate_regnet)."""
    widths_cont = np.arange(depth) * width_slope + width_initial
    width_exps = np.round(np.log(widths_cont / width_initial) / np.log(width_mult))
    widths = width_initial * np.power(width_mult, width_exps)
    widths = np.round(np.divide(widths, quant)) * quant
    num_stages = len(np.unique(widths))
    widths = widths.astype(int)
    # adjust for group divisibility
    stage_widths, stage_depths = np.unique(widths, return_counts=True)
    stage_widths = [int(round(w / group_size) * group_size) or group_size for w in stage_widths]
    return list(stage_widths), list(stage_depths.astype(int)), num_stages


class RegNetBottleneck(nnx.Module):
    def __init__(
            self,
            in_chs: int,
            out_chs: int,
            stride: int = 1,
            group_size: int = 1,
            bottle_ratio: float = 1.0,
            se_ratio: float = 0.0,
            act_layer: Union[str, Callable] = 'relu',
            norm_layer: Callable = BatchNormAct2d,
            drop_path: float = 0.0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        bottleneck_chs = int(round(out_chs * bottle_ratio))
        groups = max(1, bottleneck_chs // group_size)

        self.conv1 = create_conv2d(in_chs, bottleneck_chs, 1, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.bn1 = norm_layer(bottleneck_chs, act_layer=act_layer, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.conv2 = create_conv2d(
            bottleneck_chs, bottleneck_chs, 3, stride=stride, groups=groups,
            padding=None, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.bn2 = norm_layer(bottleneck_chs, act_layer=act_layer, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.se = SEModule(
            bottleneck_chs, rd_channels=int(round(in_chs * se_ratio)), act_layer=act_layer,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs) if se_ratio > 0 else None
        self.conv3 = create_conv2d(bottleneck_chs, out_chs, 1, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.bn3 = norm_layer(out_chs, apply_act=False, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.act = get_act_fn(act_layer)
        self.drop_path = DropPath(drop_path, rngs=rngs)

        if in_chs != out_chs or stride != 1:
            self.downsample_conv = create_conv2d(
                in_chs, out_chs, 1, stride=stride, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
            self.downsample_bn = norm_layer(
                out_chs, apply_act=False, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        else:
            self.downsample_conv = None
            self.downsample_bn = None

    def __call__(self, x):
        shortcut = x
        x = self.bn1(self.conv1(x))
        x = self.bn2(self.conv2(x))
        if self.se is not None:
            x = self.se(x)
        x = self.bn3(self.conv3(x))
        x = self.drop_path(x)
        if self.downsample_conv is not None:
            shortcut = self.downsample_bn(self.downsample_conv(shortcut))
        return self.act(x + shortcut)


class RegNet(nnx.Module):
    def __init__(
            self,
            cfg: Dict[str, Any],
            in_chans: int = 3,
            num_classes: int = 1000,
            output_stride: int = 32,
            global_pool: str = 'avg',
            drop_rate: float = 0.0,
            drop_path_rate: float = 0.0,
            act_layer: Union[str, Callable] = 'relu',
            norm_layer: Callable = BatchNormAct2d,
            stage_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        assert output_stride == 32
        self.num_classes = num_classes
        self.drop_rate = drop_rate

        stem_width = cfg.get('stem_width', 32)
        self.stem_conv = create_conv2d(
            in_chans, stem_width, 3, stride=2, padding=None,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.stem_bn = norm_layer(stem_width, act_layer=act_layer, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.feature_info = [dict(num_chs=stem_width, reduction=2, module='stem_bn')]

        widths, depths, _ = generate_regnet_widths(
            cfg['wa'], cfg['w0'], cfg['wm'], cfg['depth'], cfg['group_size'])
        se_ratio = cfg.get('se_ratio', 0.0)
        bottle_ratio = cfg.get('bottle_ratio', 1.0)

        total_blocks = sum(depths)
        block_idx = 0
        prev_chs = stem_width
        stride_total = 2
        stages = []
        for si, (w, d) in enumerate(zip(widths, depths)):
            blocks = []
            for bi in range(d):
                stride = 2 if bi == 0 else 1
                dpr = drop_path_rate * block_idx / max(total_blocks - 1, 1)
                blocks.append(RegNetBottleneck(
                    prev_chs, w, stride=stride,
                    group_size=cfg['group_size'],
                    bottle_ratio=bottle_ratio,
                    se_ratio=se_ratio,
                    act_layer=act_layer,
                    norm_layer=norm_layer,
                    drop_path=dpr,
                    dtype=dtype, param_dtype=param_dtype, rngs=rngs))
                prev_chs = w
                block_idx += 1
            stride_total *= 2
            stages.append(nnx.List(blocks))
            self.feature_info.append(dict(num_chs=w, reduction=stride_total, module=f's{si + 1}'))
        self.stages = nnx.List(stages)

        self.num_features = self.head_hidden_size = prev_chs
        self.head = ClassifierHead(
            prev_chs, num_classes, pool_type=global_pool, drop_rate=drop_rate,
            dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.grad_checkpointing = False
        self.stage_scan = resolve_stage_scan(stage_scan)

    def no_weight_decay(self) -> set:
        return set()

    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^stem_', blocks=r'^stages\.(\d+)' if coarse else r'^stages\.(\d+)\.(\d+)')

    def set_grad_checkpointing(self, enable: bool = True):
        self.grad_checkpointing = enable

    def set_stage_scan(self, enable: bool = True):
        # regnet has no Stage module; forward_features scans each block list.
        # BatchNorm running stats gate scan to eval mode (loud loop fallback
        # in train mode), so the flag is safe to leave on.
        self.stage_scan = enable

    # stage scan IS this family's scan-over-layers: generic machinery that
    # toggles `set_block_scan` (probes, tests) reaches it too
    set_block_scan = set_stage_scan

    def get_classifier(self):
        return self.head.fc

    def reset_classifier(self, num_classes: int, global_pool: Optional[str] = None, *, rngs=None):
        self.num_classes = num_classes
        self.head.reset(num_classes, pool_type=global_pool, rngs=rngs)

    def forward_features(self, x):
        x = self.stem_bn(self.stem_conv(x))
        for stage in self.stages:
            if self.stage_scan:
                try:
                    x = scan_stage_stack(stage, x, remat=self.grad_checkpointing)
                    continue
                except BlockStackError as e:
                    warn_scan_fallback(type(self).__name__, e, what='stage_scan')
            if self.grad_checkpointing:
                x = checkpoint_seq(stage, x)
            else:
                for b in stage:
                    x = b(x)
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
        take_indices, max_index = feature_take_indices(len(self.stages) + 1, indices)
        x = self.stem_bn(self.stem_conv(x))
        intermediates = []
        if 0 in take_indices:
            intermediates.append(x)
        for i, stage in enumerate(self.stages):
            if stop_early and i > max_index - 1:
                break
            for b in stage:
                x = b(x)
            if (i + 1) in take_indices:
                intermediates.append(x)
        if intermediates_only:
            return intermediates
        return x, intermediates

    def prune_intermediate_layers(self, indices=1, prune_norm: bool = False, prune_head: bool = True):
        take_indices, _ = feature_take_indices(len(self.stages) + 1, indices)
        if prune_head:
            self.reset_classifier(0, '')
        return take_indices


# RegNet parameterizations (reference regnet.py model_cfgs)
_model_cfgs = dict(
    regnetx_002=dict(w0=24, wa=36.44, wm=2.49, group_size=8, depth=13),
    regnetx_004=dict(w0=24, wa=24.48, wm=2.54, group_size=16, depth=22),
    regnetx_008=dict(w0=56, wa=35.73, wm=2.28, group_size=16, depth=16),
    regnetx_016=dict(w0=80, wa=34.01, wm=2.25, group_size=24, depth=18),
    regnetx_032=dict(w0=88, wa=26.31, wm=2.25, group_size=48, depth=25),
    regnety_002=dict(w0=24, wa=36.44, wm=2.49, group_size=8, depth=13, se_ratio=0.25),
    regnety_004=dict(w0=48, wa=27.89, wm=2.09, group_size=8, depth=16, se_ratio=0.25),
    regnety_008=dict(w0=56, wa=38.84, wm=2.4, group_size=16, depth=14, se_ratio=0.25),
    regnety_016=dict(w0=48, wa=20.71, wm=2.65, group_size=24, depth=27, se_ratio=0.25),
    regnety_032=dict(w0=80, wa=42.63, wm=2.66, group_size=24, depth=21, se_ratio=0.25),
)


def _cfg(url: str = '', **kwargs) -> Dict[str, Any]:
    return {
        'url': url, 'num_classes': 1000, 'input_size': (3, 224, 224), 'pool_size': (7, 7),
        'crop_pct': 0.875, 'interpolation': 'bicubic',
        'mean': (0.485, 0.456, 0.406), 'std': (0.229, 0.224, 0.225),
        'first_conv': 'stem_conv', 'classifier': 'head.fc',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'test_regnet.untrained': _cfg(input_size=(3, 160, 160)),
    'regnetx_002.pycls_in1k': _cfg(hf_hub_id='timm/'),
    'regnetx_004.pycls_in1k': _cfg(hf_hub_id='timm/'),
    'regnetx_008.pycls_in1k': _cfg(hf_hub_id='timm/'),
    'regnetx_032.pycls_in1k': _cfg(hf_hub_id='timm/'),
    'regnety_004.pycls_in1k': _cfg(hf_hub_id='timm/'),
    'regnety_008.pycls_in1k': _cfg(hf_hub_id='timm/'),
    'regnetx_016.pycls_in1k': _cfg(hf_hub_id='timm/'),
    'regnety_002.pycls_in1k': _cfg(hf_hub_id='timm/'),
    'regnety_016.tv2_in1k': _cfg(hf_hub_id='timm/'),
    'regnety_032.ra_in1k': _cfg(hf_hub_id='timm/', crop_pct=0.95),
})


def checkpoint_filter_fn(state_dict, model):
    """Map reference regnet names (stem.conv/bn, s1..s4 stages, b1.. blocks,
    SE fc1/fc2) → this layout."""
    import re
    from ._torch_convert import convert_torch_state_dict
    out = {}
    for k, v in state_dict.items():
        k = re.sub(r'^stem\.conv\.', 'stem_conv.', k)
        k = re.sub(r'^stem\.bn\.', 'stem_bn.', k)
        m = re.match(r'^s(\d+)\.b(\d+)\.(.*)$', k)
        if m:
            rest = m.group(3)
            rest = rest.replace('downsample.conv.', 'downsample_conv.')
            rest = rest.replace('downsample.bn.', 'downsample_bn.')
            rest = re.sub(r'^conv(\d)\.conv\.', r'conv\1.', rest)
            rest = re.sub(r'^conv(\d)\.bn\.', r'bn\1.', rest)
            k = f'stages.{int(m.group(1)) - 1}.{int(m.group(2)) - 1}.{rest}'
        out[k] = v
    return convert_torch_state_dict(out, model)


def _create_regnet(variant: str, pretrained: bool = False, **kwargs) -> RegNet:
    return build_model_with_cfg(
        RegNet, variant, pretrained,
        model_cfg=_model_cfgs[variant],
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=(0, 1, 2, 3, 4)),
        **kwargs,
    )


@register_model
def regnetx_002(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnetx_002', pretrained, **kwargs)


@register_model
def regnetx_004(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnetx_004', pretrained, **kwargs)


@register_model
def regnetx_008(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnetx_008', pretrained, **kwargs)


@register_model
def regnetx_016(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnetx_016', pretrained, **kwargs)


@register_model
def regnetx_032(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnetx_032', pretrained, **kwargs)


@register_model
def regnety_004(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnety_004', pretrained, **kwargs)


@register_model
def regnety_008(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnety_008', pretrained, **kwargs)


@register_model
def regnety_002(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnety_002', pretrained, **kwargs)


@register_model
def regnety_016(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnety_016', pretrained, **kwargs)


@register_model
def regnety_032(pretrained=False, **kwargs) -> RegNet:
    return _create_regnet('regnety_032', pretrained, **kwargs)


@register_model
def test_regnet(pretrained=False, **kwargs) -> RegNet:
    """Tiny fixture for the default test sweeps."""
    cfg = dict(w0=24, wa=24.0, wm=2.5, group_size=8, depth=4, se_ratio=0.25, stem_width=16)
    return build_model_with_cfg(
        RegNet, 'test_regnet', pretrained,
        model_cfg=cfg,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=(0, 1, 2)),
        **kwargs,
    )
