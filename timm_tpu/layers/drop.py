"""Stochastic-depth / dropout regularizers (reference: timm/layers/drop.py).

RNG is explicit: modules own an `nnx.Rngs` stream; `model.eval()` flips the
standard `deterministic` flag the same way flax dropout does.
"""
from __future__ import annotations

from typing import List, Optional, Union

import jax
import jax.numpy as jnp
from flax import nnx

__all__ = ['DropPath', 'Dropout', 'DropBlock2d', 'calculate_drop_path_rates', 'drop_path',
           'apply_drop_path', 'drop_block_2d']


def drop_path(x, key, drop_prob=0.0, scale_by_keep: bool = True):
    """Per-sample stochastic depth (reference drop.py:~140).

    `drop_prob` may be a traced scalar: scan-over-layers threads the per-layer
    rate as data (`_manipulate.drop_path_scan_inputs`), where the zero-rate
    early-out can't apply — a traced rate of 0 still reduces to the identity
    (keep mask all-True, scale 1).
    """
    static = isinstance(drop_prob, (int, float))
    if static and drop_prob == 0.0:
        return x
    keep_prob = 1.0 - drop_prob
    shape = (x.shape[0],) + (1,) * (x.ndim - 1)
    mask = jax.random.bernoulli(
        key, keep_prob if static else jnp.asarray(keep_prob, jnp.float32), shape)
    if scale_by_keep:
        denom = keep_prob if static else jnp.asarray(keep_prob, x.dtype)
        return jnp.where(mask, x / denom, jnp.zeros((), x.dtype))
    return jnp.where(mask, x, jnp.zeros((), x.dtype))


def apply_drop_path(y, module: 'DropPath', override, site: int):
    """Run a DropPath site: the module itself in loop mode, or the functional
    form with the scanned per-layer ``(rates[S], keys[S])`` override in scan
    mode (the merged block's DropPath modules are structural no-ops there)."""
    if override is None:
        return module(y)
    rates, keys = override
    return drop_path(y, keys[site], rates[site], module.scale_by_keep)


class DropPath(nnx.Module):
    """Drop residual-branch output per sample (stochastic depth)."""

    def __init__(self, drop_prob: float = 0.0, scale_by_keep: bool = True, *, rngs: Optional[nnx.Rngs] = None):
        self.drop_prob = float(drop_prob)
        self.scale_by_keep = scale_by_keep
        self.deterministic = False
        # declared as data even when None: the first block of a linear ramp has
        # rate 0 and no stream, and must still split to the same graphdef as
        # its neighbours for block/stage scan
        self.rngs = nnx.data(rngs.fork() if rngs is not None and self.drop_prob > 0.0 else None)

    def __call__(self, x):
        # scan mode (models/_manipulate.scan_stage_stack): the merged block's
        # DropPath is a structural no-op (rate/rngs neutralized before the
        # split) and the per-layer (rate, key) ride the scanned inputs — the
        # scan body pins them here because stage blocks, unlike ViT blocks,
        # take no drop_path_override argument.
        ov = getattr(self, '_scan_override', None)
        if ov is not None:
            rate, key = ov
            return drop_path(x, key, rate, self.scale_by_keep)
        if self.deterministic or self.drop_prob == 0.0 or self.rngs is None:
            return x
        return drop_path(x, self.rngs.dropout(), self.drop_prob, self.scale_by_keep)


class Dropout(nnx.Dropout):
    """nnx Dropout with a torch-ish positional-rate constructor.

    `broadcast_dims=(1, 2)` on NHWC input gives nn.Dropout2d semantics
    (whole feature maps dropped together).
    """

    def __init__(self, rate: float = 0.0, broadcast_dims=(), *, rngs: Optional[nnx.Rngs] = None):
        super().__init__(rate=rate, broadcast_dims=broadcast_dims,
                         rngs=rngs if rate > 0.0 else None)


def dropout_rng_key(drop) -> Optional[jax.Array]:
    """Draw a key from a Dropout module's stream (nnx stores an RngStream or
    an Rngs depending on construction), or None if it has no stream."""
    r = getattr(drop, 'rngs', None)
    if r is None:
        return None
    if hasattr(r, 'dropout'):
        return r.dropout()
    return r()


def calculate_drop_path_rates(
        drop_path_rate: float,
        depths: Union[int, List[int]],
        stagewise: bool = False,
) -> Union[List[float], List[List[float]]]:
    """Linearly-increasing per-block drop-path rates (reference drop.py:~190).

    Returns a flat per-block list; `stagewise=True` (requires list depths)
    groups the flat rates per stage instead.
    """
    if isinstance(depths, int):
        if stagewise:
            raise ValueError('stagewise=True requires a list of per-stage depths')
        depths = [depths]
    total = sum(depths)
    rates = [drop_path_rate * i / max(total - 1, 1) for i in range(total)]
    if not stagewise:
        return rates
    out, idx = [], 0
    for d in depths:
        out.append(rates[idx:idx + d])
        idx += d
    return out


def drop_block_2d(
        x, key,
        drop_prob: float = 0.1,
        block_size: int = 7,
        gamma_scale: float = 1.0,
        with_noise: bool = False,
        couple_channels: bool = True,
        scale_by_keep: bool = True,
):
    """DropBlock on NHWC features (reference drop.py:24-100, arXiv:1810.12890).
    Block centres drawn at rate gamma; a stride-1 max-pool dilates them to
    kh x kw blocks."""
    B, H, W, C = x.shape
    kh, kw = min(block_size, H), min(block_size, W)
    gamma = float(gamma_scale * drop_prob * H * W) / float(kh * kw) / float((H - kh + 1) * (W - kw + 1))

    noise_shape = (B, H, W, 1 if couple_channels else C)
    k1, k2 = jax.random.split(key)
    centers = jax.random.bernoulli(k1, gamma, noise_shape).astype(x.dtype)
    pad_h, pad_w = kh // 2, kw // 2
    block_mask = jax.lax.reduce_window(
        centers, -jnp.inf, jax.lax.max, (1, kh, kw, 1), (1, 1, 1, 1),
        [(0, 0), (pad_h, pad_h), (pad_w, pad_w), (0, 0)])
    if kh % 2 == 0 or kw % 2 == 0:
        block_mask = block_mask[:, (kh + 1) % 2:, (kw + 1) % 2:, :]
        block_mask = block_mask[:, :H, :W, :]
    keep_mask = 1.0 - block_mask

    if with_noise:
        noise = jax.random.normal(k2, keep_mask.shape, x.dtype) * block_mask
        return x * keep_mask + noise
    if scale_by_keep:
        scale = keep_mask.size / (keep_mask.astype(jnp.float32).sum() + 1e-7)
        keep_mask = keep_mask * scale.astype(x.dtype)
    return x * keep_mask


class DropBlock2d(nnx.Module):
    """DropBlock regularizer module (reference drop.py:~103)."""

    def __init__(
            self,
            drop_prob: float = 0.1,
            block_size: int = 7,
            gamma_scale: float = 1.0,
            with_noise: bool = False,
            inplace: bool = False,  # parity arg; jax arrays are immutable
            couple_channels: bool = True,
            scale_by_keep: bool = True,
            *,
            rngs: Optional[nnx.Rngs] = None,
    ):
        self.drop_prob = float(drop_prob)
        self.block_size = block_size
        self.gamma_scale = gamma_scale
        self.with_noise = with_noise
        self.couple_channels = couple_channels
        self.scale_by_keep = scale_by_keep
        self.deterministic = False
        # declared as data even when None: the first block of a linear ramp has
        # rate 0 and no stream, and must still split to the same graphdef as
        # its neighbours for block/stage scan
        self.rngs = nnx.data(rngs.fork() if rngs is not None and self.drop_prob > 0.0 else None)

    def __call__(self, x):
        if self.deterministic or self.drop_prob == 0.0 or self.rngs is None:
            return x
        return drop_block_2d(
            x, self.rngs.dropout(), self.drop_prob, self.block_size, self.gamma_scale,
            self.with_noise, self.couple_channels, self.scale_by_keep)
