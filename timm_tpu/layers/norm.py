"""Normalization layers (reference: timm/layers/norm.py:1-575, fast_norm.py).

All activations live in NHWC / NLC layouts, so the channel axis is always the
last axis and every '2d' variant is the same computation as its 1d cousin —
no permutes, no special cases. XLA fuses these for free, which subsumes the
reference's fast_norm/APEX machinery.

Compute-precision policy: LayerNorm / RmsNorm / SimpleNorm consult
`config.norm_internal_dtype()` (or a per-instance `internal_dtype` override).
When unset (the default) the framework path runs untouched — bit-identical to
the pre-policy code. When set (e.g. bf16), statistics are computed in that
dtype, removing the fp32 upcast of ~25 LayerNorms on the ViT hot path
(PERF.md §2 item 2); the output dtype is unchanged either way.

Frameworks note: these subclass flax.nnx norm modules but expose the
reference's constructor conventions (`eps`, `affine`, positional num_channels).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx

from ..utils import tracing
from .config import norm_internal_dtype, resolve_dtype_arg

__all__ = [
    'LayerNorm', 'LayerNorm2d', 'LayerNormFp32', 'RmsNorm', 'RmsNorm2d',
    'SimpleNorm', 'SimpleNorm2d', 'GroupNorm', 'GroupNorm1', 'BatchNorm2d',
]


def _param_value(p):
    # affine=False leaves the attribute None
    return None if p is None else p[...]


def _resolve_internal(instance_dtype):
    """Per-instance override wins; else the process policy. fp32 (or None)
    means 'take the framework path' — flax already computes stats in fp32,
    so only a reduced dtype needs the custom trace."""
    dt = instance_dtype if instance_dtype is not None else norm_internal_dtype()
    if dt is None or dt == jnp.float32:
        return None
    return dt


def _layernorm_fast(x, scale, bias, eps, dt):
    """LayerNorm with stats in `dt` (flax fast-variance semantics:
    var = E[x²] − E[x]², clamped at 0). Output keeps x.dtype."""
    xf = x.astype(dt)
    mean = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.maximum(jnp.mean(xf * xf, axis=-1, keepdims=True) - mean * mean, 0.0)
    y = (xf - mean) * jax.lax.rsqrt(var + jnp.asarray(eps, dt))
    if scale is not None:
        y = y * scale.astype(dt)
    if bias is not None:
        y = y + bias.astype(dt)
    return y.astype(x.dtype)


def _rmsnorm_fast(x, scale, eps, dt):
    """RMSNorm with the mean-square reduction in `dt`."""
    xf = x.astype(dt)
    ms = jnp.mean(xf * xf, axis=-1, keepdims=True)
    y = xf * jax.lax.rsqrt(ms + jnp.asarray(eps, dt))
    if scale is not None:
        y = y * scale.astype(dt)
    return y.astype(x.dtype)


class LayerNorm(nnx.LayerNorm):
    """LayerNorm over the channel (last) axis.

    `internal_dtype` pins this instance's statistics dtype regardless of the
    process policy ('float32' = always the framework fp32 path); None defers
    to `config.norm_internal_dtype()`.
    """

    def __init__(
            self,
            num_channels: int,
            eps: float = 1e-6,
            affine: bool = True,
            internal_dtype=None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        super().__init__(
            num_channels,
            epsilon=eps,
            use_bias=affine,
            use_scale=affine,
            dtype=dtype,
            param_dtype=param_dtype,
            rngs=rngs,
        )
        self.internal_dtype = resolve_dtype_arg(internal_dtype)

    def __call__(self, x):
        dt = _resolve_internal(getattr(self, 'internal_dtype', None))
        with tracing.scope('img.norm'):
            if dt is None:
                return super().__call__(x)
            return _layernorm_fast(x, _param_value(self.scale), _param_value(self.bias), self.epsilon, dt)


# NHWC: channels are already last, identical computation.
LayerNorm2d = LayerNorm


class LayerNormFp32(LayerNorm):
    """LayerNorm forced to fp32 statistics (reference norm.py LayerNormFp32).
    Pinned: the precision policy never downgrades this variant."""

    def __init__(self, num_channels, eps: float = 1e-6, affine: bool = True, *, rngs: nnx.Rngs, **kw):
        super().__init__(
            num_channels, eps=eps, affine=affine, internal_dtype=jnp.float32,
            dtype=jnp.float32, rngs=rngs)


class RmsNorm(nnx.RMSNorm):
    """RMSNorm over the channel axis; `internal_dtype` as in LayerNorm."""

    def __init__(
            self,
            num_channels: int,
            eps: float = 1e-6,
            affine: bool = True,
            internal_dtype=None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        super().__init__(
            num_channels,
            epsilon=eps,
            use_scale=affine,
            dtype=dtype,
            param_dtype=param_dtype,
            rngs=rngs,
        )
        self.internal_dtype = resolve_dtype_arg(internal_dtype)

    def __call__(self, x):
        dt = _resolve_internal(getattr(self, 'internal_dtype', None))
        if dt is None:
            return super().__call__(x)
        return _rmsnorm_fast(x, _param_value(self.scale), self.epsilon, dt)


RmsNorm2d = RmsNorm


class SimpleNorm(nnx.Module):
    """x * rsqrt(var(x) + eps) — mean-centered UNBIASED variance but no mean
    subtraction of x itself (reference norm.py:394-439 via fast_norm.py
    simple_norm, which uses torch.var's default correction=1). Distinct from
    RMSNorm, which divides by sqrt(mean(x²))."""

    def __init__(
            self,
            num_channels: int,
            eps: float = 1e-6,
            affine: bool = True,
            internal_dtype=None,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.eps = eps
        self.scale = nnx.Param(jnp.ones((num_channels,), param_dtype)) if affine else None
        self.internal_dtype = resolve_dtype_arg(internal_dtype)

    def __call__(self, x):
        dtype = x.dtype
        dt = _resolve_internal(getattr(self, 'internal_dtype', None)) or jnp.float32
        xf = x.astype(dt)
        v = jnp.var(xf, axis=-1, keepdims=True, ddof=1)
        xf = xf * jax.lax.rsqrt(v + jnp.asarray(self.eps, dt))
        if self.scale is not None:
            xf = xf * self.scale[...].astype(dt)
        return xf.astype(dtype)


SimpleNorm2d = SimpleNorm


class GroupNorm(nnx.GroupNorm):
    def __init__(
            self,
            num_channels: int,
            num_groups: int = 32,
            eps: float = 1e-5,
            affine: bool = True,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        super().__init__(
            num_channels,
            num_groups=num_groups,
            epsilon=eps,
            use_bias=affine,
            use_scale=affine,
            dtype=dtype,
            param_dtype=param_dtype,
            rngs=rngs,
        )


class GroupNorm1(GroupNorm):
    """Group normalization with 1 group == LayerNorm over (H, W, C)."""

    def __init__(self, num_channels, **kwargs):
        super().__init__(num_channels, num_groups=1, **kwargs)


class BatchNorm2d(nnx.BatchNorm):
    """BatchNorm over N,H,W for NHWC inputs.

    Under pjit with a batch-sharded input, the mean/var reductions are global
    across the device mesh — XLA inserts the cross-replica collectives — so
    this is natively a SyncBatchNorm (reference norm_act.py SyncBatchNormAct /
    convert_sync_batchnorm have no separate TPU equivalent).
    """

    def __init__(
            self,
            num_features: int,
            eps: float = 1e-5,
            momentum: float = 0.1,
            affine: bool = True,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        # torch-style momentum (weight of the *new* batch stat) → flax decay
        super().__init__(
            num_features,
            use_running_average=False,
            momentum=1.0 - momentum,
            epsilon=eps,
            use_bias=affine,
            use_scale=affine,
            dtype=dtype,
            param_dtype=param_dtype,
            rngs=rngs,
        )
