"""MLP blocks (reference: timm/layers/mlp.py:1-290).

All variants operate on channels-last inputs of any rank — the same module
serves transformer tokens (B, N, C) and NHWC conv features (B, H, W, C); a
1x1 conv over NHWC *is* a Linear on the last axis. `ConvMlp` still exists as
its own class because its op order differs (norm *before* act, relu default).
"""
from __future__ import annotations

from functools import partial
from typing import Callable, Optional, Union

import jax.numpy as jnp
from flax import nnx
from jax.ad_checkpoint import checkpoint_name

from ..utils import tracing
from .create_act import get_act_fn
from .drop import Dropout
from .helpers import to_2tuple
from .norm import LayerNorm
from .weight_init import trunc_normal_, zeros_

__all__ = ['Mlp', 'GluMlp', 'SwiGLU', 'SwiGLUPacked', 'GatedMlp', 'ConvMlp', 'GlobalResponseNormMlp']

FFN_UP = 'ffn_up'   # checkpoint_name of `SwiGLU`'s two up-products, for a block-level remat policy that lists it


def _shard_hidden(x):
    """Pin the post-fc1 hidden tensor over the 'model' mesh axis (no-op
    without one): fc1 is column-parallel under tensor parallelism, so the
    act/drop/norm elementwise chain runs on the shard fc1 produced instead of
    an all-gathered copy (parallel/constraints.py)."""
    from ..parallel import shard_activation
    return shard_activation(x, 'hidden')


class Mlp(nnx.Module):
    """fc1 → act → drop → (norm) → fc2 → drop."""

    def __init__(
            self,
            in_features: int,
            hidden_features: Optional[int] = None,
            out_features: Optional[int] = None,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Optional[Callable] = None,
            bias: Union[bool, tuple] = True,
            drop: Union[float, tuple] = 0.0,
            use_conv: bool = False,  # accepted for API parity; layout makes it moot
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        out_features = out_features or in_features
        hidden_features = hidden_features or in_features
        bias = to_2tuple(bias)
        drop_probs = to_2tuple(drop)
        linear = partial(
            nnx.Linear,
            dtype=dtype,
            param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02),
            bias_init=zeros_,
            rngs=rngs,
        )
        self.fc1 = linear(in_features, hidden_features, use_bias=bias[0])
        self.act = get_act_fn(act_layer)
        self.drop1 = Dropout(drop_probs[0], rngs=rngs)
        self.norm = norm_layer(hidden_features, rngs=rngs) if norm_layer is not None else None
        self.fc2 = linear(hidden_features, out_features, use_bias=bias[1])
        self.drop2 = Dropout(drop_probs[1], rngs=rngs)

    def __call__(self, x):
        with tracing.scope('img.mlp'):
            x = _shard_hidden(self.fc1(x))
            x = self.act(x)
            x = self.drop1(x)
            if self.norm is not None:
                x = self.norm(x)
            x = self.fc2(x)
            x = self.drop2(x)
        return x


class ConvMlp(nnx.Module):
    """fc1 → norm → act → drop → fc2 (reference mlp.py:215-248). The 1x1 convs
    collapse to Linear on the trailing axis in NHWC; note the norm sits
    *before* the activation, unlike `Mlp`."""

    def __init__(
            self,
            in_features: int,
            hidden_features: Optional[int] = None,
            out_features: Optional[int] = None,
            act_layer: Union[str, Callable] = 'relu',
            norm_layer: Optional[Callable] = None,
            bias: Union[bool, tuple] = True,
            drop: float = 0.0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        out_features = out_features or in_features
        hidden_features = hidden_features or in_features
        bias = to_2tuple(bias)
        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs,
        )
        self.fc1 = linear(in_features, hidden_features, use_bias=bias[0])
        self.norm = norm_layer(hidden_features, rngs=rngs) if norm_layer is not None else None
        self.act = get_act_fn(act_layer)
        self.drop = Dropout(drop, rngs=rngs)
        self.fc2 = linear(hidden_features, out_features, use_bias=bias[1])

    def __call__(self, x):
        x = self.fc1(x)
        if self.norm is not None:
            x = self.norm(x)
        x = self.act(x)
        x = self.drop(x)
        return self.fc2(x)


class GluMlp(nnx.Module):
    """GLU-style MLP: fc1 projects to 2*hidden, gate half through act."""

    def __init__(
            self,
            in_features: int,
            hidden_features: Optional[int] = None,
            out_features: Optional[int] = None,
            act_layer: Union[str, Callable] = 'sigmoid',
            norm_layer: Optional[Callable] = None,
            bias: Union[bool, tuple] = True,
            drop: Union[float, tuple] = 0.0,
            gate_last: bool = True,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        out_features = out_features or in_features
        hidden_features = hidden_features or in_features
        assert hidden_features % 2 == 0
        bias = to_2tuple(bias)
        drop_probs = to_2tuple(drop)
        self.gate_last = gate_last
        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs,
        )
        self.fc1 = linear(in_features, hidden_features, use_bias=bias[0])
        self.act = get_act_fn(act_layer)
        self.drop1 = Dropout(drop_probs[0], rngs=rngs)
        self.norm = norm_layer(hidden_features // 2, rngs=rngs) if norm_layer is not None else None
        self.fc2 = linear(hidden_features // 2, out_features, use_bias=bias[1])
        self.drop2 = Dropout(drop_probs[1], rngs=rngs)

    def __call__(self, x):
        x = self.fc1(x)
        x1, x2 = jnp.split(x, 2, axis=-1)
        x = x1 * self.act(x2) if self.gate_last else self.act(x1) * x2
        x = _shard_hidden(x)
        x = self.drop1(x)
        if self.norm is not None:
            x = self.norm(x)
        x = self.fc2(x)
        x = self.drop2(x)
        return x


class SwiGLU(nnx.Module):
    """SwiGLU with separate gate/value projections (reference mlp.py SwiGLU)."""

    def __init__(
            self,
            in_features: int,
            hidden_features: Optional[int] = None,
            out_features: Optional[int] = None,
            act_layer: Union[str, Callable] = 'silu',
            norm_layer: Optional[Callable] = None,
            bias: Union[bool, tuple] = True,
            drop: Union[float, tuple] = 0.0,
            align_to: int = 0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        out_features = out_features or in_features
        hidden_features = hidden_features or in_features
        if align_to:
            hidden_features = hidden_features + (-hidden_features % align_to)
        bias = to_2tuple(bias)
        drop_probs = to_2tuple(drop)
        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs,
        )
        self.fc1_g = linear(in_features, hidden_features, use_bias=bias[0])
        self.fc1_x = linear(in_features, hidden_features, use_bias=bias[0])
        self.act = get_act_fn(act_layer)
        self.drop1 = Dropout(drop_probs[0], rngs=rngs)
        self.norm = norm_layer(hidden_features, rngs=rngs) if norm_layer is not None else None
        self.fc2 = linear(hidden_features, out_features, use_bias=bias[1])
        self.drop2 = Dropout(drop_probs[1], rngs=rngs)

    def __call__(self, x):
        # the names are identities unless the caller's remat policy lists `FFN_UP` (`models/evabyte.py` does)
        g, u = checkpoint_name(self.fc1_g(x), FFN_UP), checkpoint_name(self.fc1_x(x), FFN_UP)
        x = _shard_hidden(self.act(g) * u)
        x = self.drop1(x)
        if self.norm is not None:
            x = self.norm(x)
        x = self.fc2(x)
        x = self.drop2(x)
        return x


def SwiGLUPacked(in_features, hidden_features=None, **kwargs):
    """Packed-projection SwiGLU == GluMlp with silu gate on first half.

    Contract matches the reference (mlp.py SwiGLUPacked = partial(GluMlp, ...)):
    the caller passes the already-doubled hidden width.
    """
    return GluMlp(
        in_features,
        hidden_features=hidden_features,
        act_layer=kwargs.pop('act_layer', 'silu'),
        gate_last=False,
        **kwargs,
    )


class GatedMlp(nnx.Module):
    """MLP with a custom gating unit between fc1 and fc2 (gMLP)."""

    def __init__(
            self,
            in_features: int,
            hidden_features: Optional[int] = None,
            out_features: Optional[int] = None,
            act_layer: Union[str, Callable] = 'gelu',
            norm_layer: Optional[Callable] = None,
            gate_layer: Optional[Callable] = None,
            bias: Union[bool, tuple] = True,
            drop: Union[float, tuple] = 0.0,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        out_features = out_features or in_features
        hidden_features = hidden_features or in_features
        bias = to_2tuple(bias)
        drop_probs = to_2tuple(drop)
        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs,
        )
        self.fc1 = linear(in_features, hidden_features, use_bias=bias[0])
        self.act = get_act_fn(act_layer)
        self.drop1 = Dropout(drop_probs[0], rngs=rngs)
        if gate_layer is not None:
            self.gate = gate_layer(hidden_features, rngs=rngs)
            hidden_features = hidden_features // 2
        else:
            self.gate = None
        self.norm = norm_layer(hidden_features, rngs=rngs) if norm_layer is not None else None
        self.fc2 = linear(hidden_features, out_features, use_bias=bias[1])
        self.drop2 = Dropout(drop_probs[1], rngs=rngs)

    def __call__(self, x):
        x = self.fc1(x)
        x = self.act(x)
        x = self.drop1(x)
        if self.gate is not None:
            x = self.gate(x)
        if self.norm is not None:
            x = self.norm(x)
        x = self.fc2(x)
        x = self.drop2(x)
        return x


class GlobalResponseNorm(nnx.Module):
    """GRN from ConvNeXt-V2 (reference: timm/layers/grn.py) — channels-last."""

    def __init__(self, dim: int, eps: float = 1e-6, *, param_dtype=jnp.float32, rngs: nnx.Rngs = None):
        self.eps = eps
        self.weight = nnx.Param(jnp.zeros((dim,), param_dtype))
        self.bias = nnx.Param(jnp.zeros((dim,), param_dtype))

    def __call__(self, x):
        # spatial axes = all but batch and channel
        spatial_axes = tuple(range(1, x.ndim - 1))
        gx = jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)), axis=spatial_axes, keepdims=True))
        nx = gx / (jnp.mean(gx, axis=-1, keepdims=True) + self.eps)
        nx = nx.astype(x.dtype)
        return x + x * nx * self.weight[...].astype(x.dtype) + self.bias[...].astype(x.dtype)


class GlobalResponseNormMlp(nnx.Module):
    """Mlp w/ GRN inserted after activation (ConvNeXt-V2 block MLP)."""

    def __init__(
            self,
            in_features: int,
            hidden_features: Optional[int] = None,
            out_features: Optional[int] = None,
            act_layer: Union[str, Callable] = 'gelu',
            bias: Union[bool, tuple] = True,
            drop: Union[float, tuple] = 0.0,
            use_conv: bool = False,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        out_features = out_features or in_features
        hidden_features = hidden_features or in_features
        bias = to_2tuple(bias)
        drop_probs = to_2tuple(drop)
        linear = partial(
            nnx.Linear, dtype=dtype, param_dtype=param_dtype,
            kernel_init=trunc_normal_(std=0.02), bias_init=zeros_, rngs=rngs,
        )
        self.fc1 = linear(in_features, hidden_features, use_bias=bias[0])
        self.act = get_act_fn(act_layer)
        self.drop1 = Dropout(drop_probs[0], rngs=rngs)
        self.grn = GlobalResponseNorm(hidden_features, param_dtype=param_dtype, rngs=rngs)
        self.fc2 = linear(hidden_features, out_features, use_bias=bias[1])
        self.drop2 = Dropout(drop_probs[1], rngs=rngs)

    def __call__(self, x):
        x = self.fc1(x)
        x = self.act(x)
        x = self.drop1(x)
        x = self.grn(x)
        x = self.fc2(x)
        x = self.drop2(x)
        return x
