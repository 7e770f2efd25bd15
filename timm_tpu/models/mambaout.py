"""MambaOut — gated CNN blocks, "do we need mamba for vision?" (NHWC / nnx).

Re-implements reference timm/models/mambaout.py:1-737 (MambaOut): a
channels-last four-stage net of Gated CNN blocks (the MetaFormer/Mamba token
mixer with the SSM removed): LN → fc1 → split(gate, identity, conv) → dw conv
on the conv split → gate * concat → fc2, plus an unusual MLP classifier head
(norm → fc → act → norm → fc).

TPU notes: the reference is already channels-last internally and permutes
around every conv; here the whole net is NHWC so only the gated split/concat
remains — XLA fuses the gate multiply into the fc2 matmul's prologue. The
partial-channel dw conv is a static slice.
"""
from functools import partial
from typing import Optional, Tuple, Union

import jax.numpy as jnp
from flax import nnx

from timm_tpu.data.constants import IMAGENET_DEFAULT_MEAN, IMAGENET_DEFAULT_STD
from ..layers import (
    ClNormMlpClassifierHead, Dropout, DropPath, LayerNorm, LayerScale,
    calculate_drop_path_rates, get_act_fn, trunc_normal_, zeros_,
)
from ._builder import build_model_with_cfg
from ._manipulate import (
    BlockStackError, resolve_stage_scan, scan_stage_stack, warn_scan_fallback,
)
from ._features import feature_take_indices
from ._registry import generate_default_cfgs, register_model

__all__ = ['MambaOut']


def _conv(in_c, out_c, k, s=1, p=0, groups=1, *, dtype, param_dtype, rngs):
    return nnx.Conv(
        in_c, out_c, kernel_size=(k, k), strides=s, padding=[(p, p), (p, p)],
        feature_group_count=groups, use_bias=True,
        kernel_init=trunc_normal_(std=0.02), bias_init=zeros_,
        dtype=dtype, param_dtype=param_dtype, rngs=rngs)


def _linear(in_f, out_f, bias=True, *, dtype, param_dtype, rngs):
    return nnx.Linear(in_f, out_f, use_bias=bias, kernel_init=trunc_normal_(std=0.02),
                      bias_init=zeros_, dtype=dtype, param_dtype=param_dtype, rngs=rngs)


class Stem(nnx.Module):
    """Two strided 3x3 convs with LN(s) (reference mambaout.py:22-69)."""

    def __init__(self, in_chs=3, out_chs=96, mid_norm=True, act_layer='gelu',
                 norm_layer=LayerNorm, *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.conv1 = _conv(in_chs, out_chs // 2, 3, 2, 1, **kw)
        self.norm1 = norm_layer(out_chs // 2, rngs=rngs) if mid_norm else None
        self.act = get_act_fn(act_layer)
        self.conv2 = _conv(out_chs // 2, out_chs, 3, 2, 1, **kw)
        self.norm2 = norm_layer(out_chs, rngs=rngs)

    def __call__(self, x):
        x = self.conv1(x)
        if self.norm1 is not None:
            x = self.norm1(x)
        x = self.act(x)
        return self.norm2(self.conv2(x))


class DownsampleNormFirst(nnx.Module):
    """LN → strided conv (reference mambaout.py:72-99)."""

    def __init__(self, in_chs=96, out_chs=198, norm_layer=LayerNorm,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.norm = norm_layer(in_chs, rngs=rngs)
        self.conv = _conv(in_chs, out_chs, 3, 2, 1, dtype=dtype, param_dtype=param_dtype, rngs=rngs)

    def __call__(self, x):
        return self.conv(self.norm(x))


class Downsample(nnx.Module):
    """Strided conv → LN (reference mambaout.py:102-129)."""

    def __init__(self, in_chs=96, out_chs=198, norm_layer=LayerNorm,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.conv = _conv(in_chs, out_chs, 3, 2, 1, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm = norm_layer(out_chs, rngs=rngs)

    def __call__(self, x):
        return self.norm(self.conv(x))


class _FcActNorm(nnx.Module):
    """fc → act → norm pre-logits (keys pre_logits.fc/.norm)."""

    def __init__(self, in_features, hidden_size, act_layer='gelu', norm_layer=LayerNorm,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        self.fc = _linear(in_features, hidden_size, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.act = get_act_fn(act_layer)
        self.norm = norm_layer(hidden_size, rngs=rngs)

    def __call__(self, x):
        return self.norm(self.act(self.fc(x)))


class MlpHead(nnx.Module):
    """MambaOut's norm → fc → act → norm → fc head (reference mambaout.py:132-193)."""

    def __init__(self, in_features, num_classes=1000, pool_type='avg', act_layer='gelu',
                 mlp_ratio: Optional[int] = 4, norm_layer=LayerNorm, drop_rate=0., bias=True,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        hidden_size = int(mlp_ratio * in_features) if mlp_ratio is not None else None
        self.pool_type = pool_type
        self.in_features = in_features
        self.num_features = hidden_size or in_features
        self._dd = dict(dtype=dtype, param_dtype=param_dtype)

        self.norm = norm_layer(in_features, rngs=rngs)
        self.pre_logits = _FcActNorm(in_features, hidden_size, act_layer, norm_layer, **kw) \
            if hidden_size else None
        self.fc = _linear(self.num_features, num_classes, bias=bias, **kw) if num_classes > 0 else None
        self.head_dropout = Dropout(drop_rate, rngs=rngs)

    def reset(self, num_classes: int, pool_type: Optional[str] = None,
              reset_other: bool = False, *, rngs=None):
        if pool_type is not None:
            self.pool_type = pool_type
        if reset_other:
            self.norm = None
            self.pre_logits = None
            self.num_features = self.in_features
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        self.fc = _linear(self.num_features, num_classes, rngs=rngs, **self._dd) \
            if num_classes > 0 else None

    def __call__(self, x, pre_logits: bool = False):
        if self.pool_type == 'avg':
            x = x.mean(axis=(1, 2))
        if self.norm is not None:
            x = self.norm(x)
        if self.pre_logits is not None:
            x = self.pre_logits(x)
        x = self.head_dropout(x)
        if pre_logits or self.fc is None:
            return x
        return self.fc(x)


class GatedConvBlock(nnx.Module):
    """Gated CNN block: LN → fc1 → (gate | id | dw-conv split) → fc2
    (reference mambaout.py:195-249). The conv runs on a static channel slice."""

    def __init__(self, dim, expansion_ratio=8 / 3, kernel_size=7, conv_ratio=1.0,
                 ls_init_value=None, norm_layer=LayerNorm, act_layer='gelu', drop_path=0.,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs, **kwargs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm = norm_layer(dim, rngs=rngs)
        hidden = int(expansion_ratio * dim)
        self.fc1 = _linear(dim, hidden * 2, **kw)
        self.act = get_act_fn(act_layer)
        conv_channels = int(conv_ratio * dim)
        self.split_indices = (hidden, hidden - conv_channels, conv_channels)
        self.conv = _conv(conv_channels, conv_channels, kernel_size, 1, kernel_size // 2,
                          groups=conv_channels, **kw)
        self.fc2 = _linear(hidden, dim, **kw)
        self.ls = LayerScale(dim, ls_init_value, param_dtype=param_dtype, rngs=rngs) \
            if ls_init_value is not None else None
        self.drop_path = DropPath(drop_path, rngs=rngs) if drop_path > 0. else None

    def __call__(self, x):
        shortcut = x  # (B, H, W, C)
        x = self.fc1(self.norm(x))
        g_end, i_end = self.split_indices[0], self.split_indices[0] + self.split_indices[1]
        g, i, c = x[..., :g_end], x[..., g_end:i_end], x[..., i_end:]
        c = self.conv(c)
        x = self.fc2(self.act(g) * jnp.concatenate([i, c], axis=-1))
        if self.ls is not None:
            x = self.ls(x)
        if self.drop_path is not None:
            x = self.drop_path(x)
        return x + shortcut


class MambaOutStage(nnx.Module):
    """Optional downsample + gated conv blocks (reference mambaout.py:252-305)."""

    def __init__(self, dim, dim_out=None, depth=4, expansion_ratio=8 / 3, kernel_size=7,
                 conv_ratio=1.0, downsample='', ls_init_value=None, norm_layer=LayerNorm,
                 act_layer='gelu', drop_path=0.,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        dim_out = dim_out or dim
        self.grad_checkpointing = False
        self.stage_scan = False
        if downsample == 'conv':
            self.downsample = Downsample(dim, dim_out, norm_layer=norm_layer, **kw)
        elif downsample == 'conv_nf':
            self.downsample = DownsampleNormFirst(dim, dim_out, norm_layer=norm_layer, **kw)
        else:
            assert dim == dim_out
            self.downsample = None
        self.blocks = nnx.List([
            GatedConvBlock(
                dim=dim_out, expansion_ratio=expansion_ratio, kernel_size=kernel_size,
                conv_ratio=conv_ratio, ls_init_value=ls_init_value, norm_layer=norm_layer,
                act_layer=act_layer,
                drop_path=drop_path[j] if isinstance(drop_path, (list, tuple)) else drop_path,
                **kw)
            for j in range(depth)])

    def __call__(self, x):
        if self.downsample is not None:
            x = self.downsample(x)
        if self.stage_scan:
            try:
                return scan_stage_stack(self.blocks, x, remat=self.grad_checkpointing)
            except BlockStackError as e:
                warn_scan_fallback(type(self).__name__, e, what='stage_scan')
        remat_blk = nnx.remat(GatedConvBlock.__call__) if self.grad_checkpointing else None
        for blk in self.blocks:
            x = remat_blk(blk, x) if remat_blk is not None else blk(x)
        return x


class MambaOut(nnx.Module):
    """MambaOut (reference mambaout.py:307-527)."""

    def __init__(
            self,
            in_chans: int = 3,
            num_classes: int = 1000,
            global_pool: str = 'avg',
            depths: Tuple[int, ...] = (3, 3, 9, 3),
            dims: Tuple[int, ...] = (96, 192, 384, 576),
            norm_layer=LayerNorm,
            act_layer='gelu',
            conv_ratio: float = 1.0,
            expansion_ratio: float = 8 / 3,
            kernel_size: int = 7,
            stem_mid_norm: bool = True,
            ls_init_value: Optional[float] = None,
            downsample: str = 'conv',
            drop_path_rate: float = 0.,
            drop_rate: float = 0.,
            head_fn: str = 'default',
            stage_scan: Optional[bool] = None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: Optional[nnx.Rngs] = None,
    ):
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.num_classes = num_classes
        self.drop_rate = drop_rate
        self.output_fmt = 'NHWC'
        if not isinstance(depths, (list, tuple)):
            depths = (depths,)
        if not isinstance(dims, (list, tuple)):
            dims = (dims,)

        num_stage = len(depths)
        self.num_stage = num_stage
        self.feature_info = []

        self.stem = Stem(in_chans, dims[0], mid_norm=stem_mid_norm,
                         act_layer=act_layer, norm_layer=norm_layer, **kw)
        prev_dim = dims[0]
        dp_rates = calculate_drop_path_rates(drop_path_rate, depths, stagewise=True)
        stages = []
        curr_stride = 4
        for i in range(num_stage):
            dim = dims[i]
            stride = 2 if curr_stride == 2 or i > 0 else 1
            curr_stride *= stride
            stages.append(MambaOutStage(
                dim=prev_dim, dim_out=dim, depth=depths[i], kernel_size=kernel_size,
                conv_ratio=conv_ratio, expansion_ratio=expansion_ratio,
                downsample=downsample if i > 0 else '',
                ls_init_value=ls_init_value, norm_layer=norm_layer, act_layer=act_layer,
                drop_path=dp_rates[i], **kw))
            prev_dim = dim
            self.feature_info += [dict(num_chs=prev_dim, reduction=curr_stride, module=f'stages.{i}')]
        self.stages = nnx.List(stages)
        self.set_stage_scan(resolve_stage_scan(stage_scan))

        if head_fn == 'default':
            # unusual norm → pool → fc → act → norm → fc combo
            self.head = MlpHead(
                prev_dim, num_classes, pool_type=global_pool, drop_rate=drop_rate,
                norm_layer=norm_layer, **kw)
        else:
            self.head = ClNormMlpClassifierHead(
                prev_dim, num_classes, hidden_size=int(prev_dim * 4), pool_type=global_pool,
                norm_layer=norm_layer, drop_rate=drop_rate, **kw)
        self.num_features = prev_dim
        self.head_hidden_size = self.head.num_features

    # -- contract ------------------------------------------------------------
    def no_weight_decay(self) -> set:
        return set()

    def group_matcher(self, coarse: bool = False):
        return dict(
            stem=r'^stem',
            blocks=r'^stages\.(\d+)' if coarse else [
                (r'^stages\.(\d+)\.downsample', (0,)),
                (r'^stages\.(\d+)\.blocks\.(\d+)', None),
            ])

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
        self.head.reset(num_classes, global_pool, rngs=rngs)

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

    def forward_intermediates(self, x, indices=None, norm: bool = False,
                              stop_early: bool = False, output_fmt: str = 'NHWC',
                              intermediates_only: bool = False):
        assert output_fmt == 'NHWC'
        take_indices, max_index = feature_take_indices(len(self.stages), indices)
        intermediates = []
        x = self.stem(x)
        stages = self.stages if not stop_early else self.stages[:max_index + 1]
        for feat_idx, stage in enumerate(stages):
            x = stage(x)
            if feat_idx in take_indices:
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
    if 'model' in state_dict:
        state_dict = state_dict['model']
    if 'stem.conv1.weight' not in state_dict and any(k.startswith('downsample_layers') for k in state_dict):
        # original (non-timm) checkpoint layout (reference mambaout.py:529-551)
        import re
        out = {}
        for k, v in state_dict.items():
            k = k.replace('downsample_layers.0.', 'stem.')
            k = re.sub(r'stages.([0-9]+).([0-9]+)', r'stages.\1.blocks.\2', k)
            k = re.sub(r'downsample_layers.([0-9]+)', r'stages.\1.downsample', k)
            if k.startswith('norm.'):
                k = k.replace('norm.', 'head.norm.')
            elif k.startswith('head.'):
                k = k.replace('head.fc1.', 'head.pre_logits.fc.')
                k = k.replace('head.norm.', 'head.pre_logits.norm.')
                k = k.replace('head.fc2.', 'head.fc.')
            out[k] = v
        state_dict = out
    return convert_torch_state_dict(state_dict, model)


def _cfg(url: str = '', **kwargs):
    return {
        'url': url,
        'num_classes': 1000, 'input_size': (3, 224, 224), 'test_input_size': (3, 288, 288),
        'pool_size': (7, 7), 'crop_pct': 1.0, 'interpolation': 'bicubic',
        'mean': IMAGENET_DEFAULT_MEAN, 'std': IMAGENET_DEFAULT_STD,
        'first_conv': 'stem.conv1', 'classifier': 'head.fc',
        'license': 'apache-2.0',
        **kwargs,
    }


default_cfgs = generate_default_cfgs({
    'mambaout_femto.in1k': _cfg(),
    'mambaout_kobe.in1k': _cfg(),
    'mambaout_tiny.in1k': _cfg(),
    'mambaout_small.in1k': _cfg(),
    'mambaout_base.in1k': _cfg(),
    'mambaout_small_rw.sw_e450_in1k': _cfg(),
    'mambaout_base_short_rw.sw_e500_in1k': _cfg(crop_pct=0.95, test_crop_pct=1.0),
    'mambaout_base_tall_rw.sw_e500_in1k': _cfg(crop_pct=0.95, test_crop_pct=1.0),
    'mambaout_base_wide_rw.sw_e500_in1k': _cfg(crop_pct=0.95, test_crop_pct=1.0),
    'mambaout_base_plus_rw.sw_e150_in12k_ft_in1k': _cfg(),
    'test_mambaout': _cfg(input_size=(3, 160, 160), test_input_size=(3, 192, 192), pool_size=(5, 5)),
})


def _create_mambaout(variant, pretrained=False, **kwargs):
    return build_model_with_cfg(
        MambaOut, variant, pretrained,
        pretrained_filter_fn=checkpoint_filter_fn,
        feature_cfg=dict(out_indices=(0, 1, 2, 3), feature_cls='getter'),
        **kwargs,
    )


@register_model
def mambaout_femto(pretrained=False, **kwargs):
    model_args = dict(depths=(3, 3, 9, 3), dims=(48, 96, 192, 288))
    return _create_mambaout('mambaout_femto', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_kobe(pretrained=False, **kwargs):
    """Kobe Memorial Version with 24 Gated CNN blocks."""
    model_args = dict(depths=(3, 3, 15, 3), dims=(48, 96, 192, 288))
    return _create_mambaout('mambaout_kobe', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_tiny(pretrained=False, **kwargs):
    model_args = dict(depths=(3, 3, 9, 3), dims=(96, 192, 384, 576))
    return _create_mambaout('mambaout_tiny', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_small(pretrained=False, **kwargs):
    model_args = dict(depths=(3, 4, 27, 3), dims=(96, 192, 384, 576))
    return _create_mambaout('mambaout_small', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_base(pretrained=False, **kwargs):
    model_args = dict(depths=(3, 4, 27, 3), dims=(128, 256, 512, 768))
    return _create_mambaout('mambaout_base', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_small_rw(pretrained=False, **kwargs):
    model_args = dict(
        depths=(3, 4, 27, 3), dims=(96, 192, 384, 576), stem_mid_norm=False,
        downsample='conv_nf', ls_init_value=1e-6, head_fn='norm_mlp')
    return _create_mambaout('mambaout_small_rw', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_base_short_rw(pretrained=False, **kwargs):
    model_args = dict(
        depths=(3, 3, 25, 3), dims=(128, 256, 512, 768), expansion_ratio=3.0, conv_ratio=1.25,
        stem_mid_norm=False, downsample='conv_nf', ls_init_value=1e-6, head_fn='norm_mlp')
    return _create_mambaout('mambaout_base_short_rw', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_base_tall_rw(pretrained=False, **kwargs):
    model_args = dict(
        depths=(3, 4, 30, 3), dims=(128, 256, 512, 768), expansion_ratio=2.5, conv_ratio=1.25,
        stem_mid_norm=False, downsample='conv_nf', ls_init_value=1e-6, head_fn='norm_mlp')
    return _create_mambaout('mambaout_base_tall_rw', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_base_wide_rw(pretrained=False, **kwargs):
    model_args = dict(
        depths=(3, 4, 27, 3), dims=(128, 256, 512, 768), expansion_ratio=3.0, conv_ratio=1.5,
        stem_mid_norm=False, downsample='conv_nf', ls_init_value=1e-6, act_layer='silu',
        head_fn='norm_mlp')
    return _create_mambaout('mambaout_base_wide_rw', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def mambaout_base_plus_rw(pretrained=False, **kwargs):
    model_args = dict(
        depths=(3, 4, 30, 3), dims=(128, 256, 512, 768), expansion_ratio=3.0, conv_ratio=1.5,
        stem_mid_norm=False, downsample='conv_nf', ls_init_value=1e-6, act_layer='silu',
        head_fn='norm_mlp')
    return _create_mambaout('mambaout_base_plus_rw', pretrained=pretrained, **dict(model_args, **kwargs))


@register_model
def test_mambaout(pretrained=False, **kwargs):
    model_args = dict(
        depths=(1, 1, 3, 1), dims=(16, 32, 48, 64), expansion_ratio=3, stem_mid_norm=False,
        downsample='conv_nf', ls_init_value=1e-4, act_layer='silu', head_fn='norm_mlp')
    return _create_mambaout('test_mambaout', pretrained=pretrained, **dict(model_args, **kwargs))
