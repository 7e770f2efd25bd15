"""Weight-standardized convs (reference: timm/layers/std_conv.py:1-232).

`ScaledStdConv2d` is the NFNet building block: per-output-channel weight
standardization with a learned gain. The kernel parameter itself stays
unstandardized (matching the reference's F.batch_norm trick); the
standardized weight is computed at call time and fed to the conv directly —
XLA folds the standardization into the conv's weight preprocessing, and for
inference the whole thing constant-folds when params are frozen.

Param names mirror the reference conv (`kernel`/`bias`/`gain` on the module
itself), so torch checkpoints remap without special cases.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import nnx

from .create_conv2d import _resolve_padding
from .helpers import to_2tuple
from .weight_init import variance_scaling_, zeros_

__all__ = ['StdConv2d', 'ScaledStdConv2d', 'ScaledStdConv2dSame']


def _bias_value(bias):
    # use_bias=False leaves the attribute None
    return None if bias is None else bias[...]


def _conv_nhwc(x, kernel, bias, strides, padding, dilation, groups):
    out = jax.lax.conv_general_dilated(
        x, kernel.astype(x.dtype),
        window_strides=strides,
        padding=padding,
        rhs_dilation=dilation,
        feature_group_count=groups,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'))
    if bias is not None:
        out = out + bias.astype(out.dtype)
    return out


class StdConv2d(nnx.Conv):
    """Conv with weight standardization (BiT / pre-act ResNets)."""

    def __init__(self, in_channels, out_channels, kernel_size=3, stride=1, padding=None,
                 dilation=1, groups=1, bias=False, eps=1e-6,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kernel_size = to_2tuple(kernel_size)
        super().__init__(
            in_channels, out_channels, kernel_size=kernel_size, strides=to_2tuple(stride),
            padding=_resolve_padding(padding, kernel_size, stride, dilation),
            kernel_dilation=to_2tuple(dilation), feature_group_count=groups, use_bias=bias,
            dtype=dtype, param_dtype=param_dtype,
            kernel_init=variance_scaling_(2.0, 'fan_out', 'normal'), bias_init=zeros_, rngs=rngs)
        self.eps = eps

    def _std_kernel(self):
        w = self.kernel[...]
        axes = (0, 1, 2)  # HWI of HWIO → per-output-channel stats over fan-in
        mean = w.mean(axis=axes, keepdims=True)
        var = w.var(axis=axes, keepdims=True)
        return (w - mean) / jnp.sqrt(var + self.eps)

    def __call__(self, x):
        return _conv_nhwc(
            x, self._std_kernel(), _bias_value(self.bias),
            self.strides, self.padding, self.kernel_dilation, self.feature_group_count)


class ScaledStdConv2d(nnx.Conv):
    """NFNet scaled weight standardization w/ per-channel gain
    (reference std_conv.py:115-170 ScaledStdConv2d)."""

    def __init__(self, in_channels, out_channels, kernel_size=3, stride=1, padding=None,
                 dilation=1, groups=1, bias=True, gamma=1.0, eps=1e-6, gain_init=1.0,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kernel_size = to_2tuple(kernel_size)
        super().__init__(
            in_channels, out_channels, kernel_size=kernel_size, strides=to_2tuple(stride),
            padding=_resolve_padding(padding, kernel_size, stride, dilation),
            kernel_dilation=to_2tuple(dilation), feature_group_count=groups, use_bias=bias,
            dtype=dtype, param_dtype=param_dtype,
            kernel_init=variance_scaling_(2.0, 'fan_out', 'normal'), bias_init=zeros_, rngs=rngs)
        self.gain = nnx.Param(jnp.full((out_channels,), gain_init, param_dtype))
        fan_in = kernel_size[0] * kernel_size[1] * in_channels / groups
        self.scale = gamma * fan_in ** -0.5
        self.eps = eps

    def __call__(self, x):
        w = self.kernel[...]
        axes = (0, 1, 2)
        mean = w.mean(axis=axes, keepdims=True)
        var = w.var(axis=axes, keepdims=True)
        w_std = (self.scale * self.gain[...]).astype(w.dtype) * (w - mean) / jnp.sqrt(var + self.eps)
        return _conv_nhwc(
            x, w_std, _bias_value(self.bias),
            self.strides, self.padding, self.kernel_dilation, self.feature_group_count)


class ScaledStdConv2dSame(ScaledStdConv2d):
    """TF-SAME-padded variant (reference ScaledStdConv2dSame) used by the
    DeepMind-weight-compatible dm_nfnet models."""

    def __init__(self, in_channels, out_channels, kernel_size=3, stride=1, padding='same',
                 dilation=1, groups=1, bias=True, gamma=1.0, eps=1e-6, gain_init=1.0,
                 *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        super().__init__(
            in_channels, out_channels, kernel_size=kernel_size, stride=stride, padding='same',
            dilation=dilation, groups=groups, bias=bias, gamma=gamma, eps=eps,
            gain_init=gain_init, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
