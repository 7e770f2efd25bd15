"""Sin-cos / Fourier / rotary position embeddings
(reference: timm/layers/pos_embed_sincos.py:1-1357).

Everything here is pure-functional and shape-static: tables are built at trace
time from python ints, so they constant-fold under jit.
"""
from __future__ import annotations

import math
from typing import List, Optional, Tuple

import jax.numpy as jnp
from flax import nnx

__all__ = [
    'build_sincos2d_pos_embed', 'build_fourier_pos_embed', 'build_rotary_pos_embed', 'build_rotary_pos_embed_1d',
    'RotaryEmbeddingCat', 'RotaryEmbeddingMixed', 'RotaryEmbeddingDinoV3',
    'create_rope_embed', 'freq_bands', 'pixel_freq_bands',
]


def freq_bands(num_bands: int, temperature: float = 10000.0, step: int = 2) -> jnp.ndarray:
    exp = jnp.arange(0, num_bands, step, dtype=jnp.float32) / num_bands
    return 1.0 / (temperature ** exp)


def pixel_freq_bands(num_bands: int, max_freq: float = 224.0, linear_bands: bool = True) -> jnp.ndarray:
    if linear_bands:
        bands = jnp.linspace(1.0, max_freq / 2, num_bands, dtype=jnp.float32)
    else:
        bands = 2.0 ** jnp.linspace(0, math.log2(max_freq / 2), num_bands, dtype=jnp.float32)
    return bands * jnp.pi


def build_sincos2d_pos_embed(
        feat_shape: Tuple[int, int],
        dim: int = 64,
        temperature: float = 10000.0,
        reverse_coord: bool = False,
        interleave_sin_cos: bool = False,
        dtype=jnp.float32,
) -> jnp.ndarray:
    """Fixed 2D sin-cos position embedding, (H*W, dim)."""
    assert dim % 4 == 0, 'Embed dim must be divisible by 4 for sin-cos 2d pos embed'
    h, w = feat_shape
    grid_y, grid_x = jnp.meshgrid(jnp.arange(h, dtype=jnp.float32), jnp.arange(w, dtype=jnp.float32), indexing='ij')
    if reverse_coord:
        grid_y, grid_x = grid_x, grid_y
    pos_dim = dim // 4
    omega = freq_bands(pos_dim * 2, temperature=temperature, step=2)
    out_x = grid_x.reshape(-1, 1) * omega[None, :]
    out_y = grid_y.reshape(-1, 1) * omega[None, :]
    if interleave_sin_cos:
        emb = jnp.stack([jnp.sin(out_x), jnp.cos(out_x), jnp.sin(out_y), jnp.cos(out_y)], axis=-1).reshape(h * w, -1)
    else:
        emb = jnp.concatenate([jnp.sin(out_x), jnp.cos(out_x), jnp.sin(out_y), jnp.cos(out_y)], axis=1)
    return emb.astype(dtype)


def build_fourier_pos_embed(
        feat_shape: Tuple[int, ...],
        bands: Optional[jnp.ndarray] = None,
        num_bands: int = 64,
        max_res: int = 224,
        temperature: float = 10000.0,
        linear_bands: bool = False,
        include_grid: bool = False,
        in_pixels: bool = True,
        ref_feat_shape: Optional[Tuple[int, ...]] = None,
        grid_offset: float = 0.0,
        grid_indexing: str = 'ij',
        dtype=jnp.float32,
) -> List[jnp.ndarray]:
    if bands is None:
        if in_pixels:
            bands = pixel_freq_bands(num_bands, float(max_res), linear_bands=linear_bands)
        else:
            bands = freq_bands(num_bands, temperature=temperature, step=1)
    if in_pixels:
        t = [jnp.linspace(-1.0, 1.0, s, dtype=jnp.float32) for s in feat_shape]
    else:
        t = [jnp.arange(s, dtype=jnp.float32) + grid_offset for s in feat_shape]
        if ref_feat_shape is not None:
            t = [x / s * r for x, s, r in zip(t, feat_shape, ref_feat_shape)]
    grid = jnp.stack(jnp.meshgrid(*t, indexing=grid_indexing), axis=-1)
    grid = grid[..., None]
    pos = grid * bands
    pos_sin, pos_cos = jnp.sin(pos).astype(dtype), jnp.cos(pos).astype(dtype)
    out = [grid, pos_sin, pos_cos] if include_grid else [pos_sin, pos_cos]
    return out


def build_rotary_pos_embed(
        feat_shape: Tuple[int, ...],
        bands: Optional[jnp.ndarray] = None,
        dim: int = 64,
        max_res: int = 224,
        temperature: float = 10000.0,
        linear_bands: bool = False,
        in_pixels: bool = True,
        ref_feat_shape: Optional[Tuple[int, ...]] = None,
        grid_offset: float = 0.0,
        grid_indexing: str = 'ij',
        dtype=jnp.float32,
):
    """Returns (sin_emb, cos_emb), each (num_tokens, dim) for 2D rotary."""
    sin_emb, cos_emb = build_fourier_pos_embed(
        feat_shape,
        bands=bands,
        num_bands=dim // 4,
        max_res=max_res,
        temperature=temperature,
        linear_bands=linear_bands,
        in_pixels=in_pixels,
        ref_feat_shape=ref_feat_shape,
        grid_offset=grid_offset,
        grid_indexing=grid_indexing,
        dtype=dtype,
    )
    num_spatial_dim = 1
    for x in feat_shape:
        num_spatial_dim *= x
    sin_emb = sin_emb.reshape(num_spatial_dim, -1)
    sin_emb = jnp.repeat(sin_emb, 2, axis=-1)
    cos_emb = cos_emb.reshape(num_spatial_dim, -1)
    cos_emb = jnp.repeat(cos_emb, 2, axis=-1)
    return sin_emb, cos_emb


class RotaryEmbeddingCat(nnx.Module):
    """2D ROPE producing a concatenated (sin, cos) table
    (reference pos_embed_sincos.py RotaryEmbeddingCat)."""

    def __init__(
            self,
            dim: int,
            max_res: int = 224,
            temperature: float = 10000.0,
            in_pixels: bool = True,
            linear_bands: bool = False,
            feat_shape: Optional[Tuple[int, int]] = None,
            ref_feat_shape: Optional[Tuple[int, int]] = None,
            grid_offset: float = 0.0,
            grid_indexing: str = 'ij',
            *,
            rngs: nnx.Rngs = None,
    ):
        self.dim = dim
        self.max_res = max_res
        self.temperature = temperature
        self.in_pixels = in_pixels
        self.linear_bands = linear_bands
        self.feat_shape = feat_shape
        self.ref_feat_shape = ref_feat_shape
        self.grid_offset = grid_offset
        self.grid_indexing = grid_indexing

    def get_embed(self, shape: Optional[Tuple[int, int]] = None):
        shape = shape if shape is not None else self.feat_shape
        assert shape is not None
        sin_emb, cos_emb = build_rotary_pos_embed(
            shape,
            dim=self.dim,
            max_res=self.max_res,
            temperature=self.temperature,
            linear_bands=self.linear_bands,
            in_pixels=self.in_pixels,
            ref_feat_shape=self.ref_feat_shape,
            grid_offset=self.grid_offset,
            grid_indexing=self.grid_indexing,
        )
        return jnp.concatenate([sin_emb, cos_emb], axis=-1)


def _swap_shape_xy(shape):
    return (shape[1], shape[0]) if len(shape) >= 2 else shape


def init_random_2d_freqs(key, head_dim: int, depth: int, num_heads: int,
                         temperature: float = 10.0, rotate: bool = True) -> jnp.ndarray:
    """Per-depth/per-head randomly-rotated 2D rope frequencies for mixed-mode
    rope (reference pos_embed_sincos.py:721-752). Returns (2, depth, num_heads,
    head_dim//2)."""
    import jax
    mag = 1.0 / (temperature ** (jnp.arange(0, head_dim, 4, dtype=jnp.float32) / head_dim))
    mag = mag[None, None, :]
    if rotate:
        angles = jax.random.uniform(key, (depth, num_heads, 1), jnp.float32) * 2 * math.pi
    else:
        angles = jnp.zeros((depth, num_heads, 1), jnp.float32)
    fx = jnp.concatenate([mag * jnp.cos(angles), mag * jnp.cos(angles + math.pi / 2)], axis=-1)
    fy = jnp.concatenate([mag * jnp.sin(angles), mag * jnp.sin(angles + math.pi / 2)], axis=-1)
    return jnp.stack([fx, fy], axis=0)


class RotaryEmbeddingMixed(nnx.Module):
    """Learnable depth/head-dependent rope frequencies — naver rope-vit
    'mixed' mode (reference pos_embed_sincos.py:873-1056). ``get_embed``
    returns a (depth, num_heads, H*W, head_dim) cat(sin, cos) table; the model
    indexes depth per block."""

    def __init__(
            self,
            dim: int,
            depth: int,
            num_heads: int,
            temperature: float = 10.0,
            feat_shape: Optional[Tuple[int, int]] = None,
            grid_indexing: str = 'xy',
            *,
            rngs: nnx.Rngs = None,
    ):
        self.dim = dim
        self.depth = depth
        self.num_heads = num_heads
        self.temperature = temperature
        self.feat_shape = feat_shape
        self.grid_indexing = grid_indexing
        head_dim = dim // num_heads
        assert head_dim % 4 == 0, f'head_dim must be divisible by 4, got {head_dim}'
        rngs = rngs if rngs is not None else nnx.Rngs(0)
        self.freqs = nnx.Param(init_random_2d_freqs(
            rngs.params(), head_dim, depth, num_heads, temperature=temperature, rotate=True))

    def _grid(self, shape):
        if self.grid_indexing == 'xy':
            shape = _swap_shape_xy(shape)
        xs = jnp.arange(shape[0], dtype=jnp.float32)
        ys = jnp.arange(shape[1], dtype=jnp.float32)
        x_pos, y_pos = jnp.meshgrid(xs, ys, indexing=self.grid_indexing if self.grid_indexing in ('ij', 'xy') else 'ij')
        return x_pos.reshape(-1), y_pos.reshape(-1)

    def get_embed(self, shape: Optional[Tuple[int, int]] = None):
        shape = shape if shape is not None else self.feat_shape
        assert shape is not None
        t_x, t_y = self._grid(shape)
        freqs = self.freqs[...].astype(jnp.float32)
        freqs_x = t_x[:, None] @ freqs[0][..., None, :]   # (depth, nH, N, hd//4... broadcast)
        freqs_y = t_y[:, None] @ freqs[1][..., None, :]
        combined = freqs_x + freqs_y                      # (depth, num_heads, N, head_dim//2)
        sin_emb = jnp.repeat(jnp.sin(combined), 2, axis=-1)
        cos_emb = jnp.repeat(jnp.cos(combined), 2, axis=-1)
        return jnp.concatenate([sin_emb, cos_emb], axis=-1)


def make_coords_dinov3(height: int, width: int, normalize_coords: str = 'separate',
                       grid_indexing: str = 'ij', grid_offset: float = 0.0) -> jnp.ndarray:
    """DINOv3 coordinate grid: 0.5-centered, normalized, mapped to [-1, 1]
    (reference pos_embed_sincos.py:1059-1105). Returns (H*W, 2)."""
    coords_h = jnp.arange(0.5, height, dtype=jnp.float32) + grid_offset
    coords_w = jnp.arange(0.5, width, dtype=jnp.float32) + grid_offset
    if normalize_coords == 'max':
        h_denom = w_denom = float(max(height, width))
    elif normalize_coords == 'min':
        h_denom = w_denom = float(min(height, width))
    elif normalize_coords == 'separate':
        h_denom, w_denom = float(height), float(width)
    else:
        raise ValueError(f'Unknown normalize_coords: {normalize_coords}')
    coords_h = coords_h / h_denom
    coords_w = coords_w / w_denom
    if grid_indexing == 'xy':
        grid_w, grid_h = jnp.meshgrid(coords_w, coords_h, indexing='xy')
        coords = jnp.stack([grid_h, grid_w], axis=-1)
    else:
        gh, gw = jnp.meshgrid(coords_h, coords_w, indexing='ij')
        coords = jnp.stack([gh, gw], axis=-1)
    return 2.0 * coords.reshape(-1, 2) - 1.0


class RotaryEmbeddingDinoV3(nnx.Module):
    """DINOv3-numerics rope: 0.5-centered normalized coords in [-1, 1], a
    geometric period schedule, and (by default) the 'half' rotation layout
    (reference pos_embed_sincos.py:1107-1313). ``get_embed`` returns
    (H*W, 2 * dim) cat(sin, cos); consume with apply_rot_embed_cat(half=True).

    The reference's train-time coordinate augmentations (shift/jitter/rescale)
    are accepted for interface parity but not implemented — no released model
    cfg enables them at inference, and training augs belong in the data
    pipeline here.
    """

    def __init__(
            self,
            dim: int,
            temperature: Optional[float] = 100.0,
            min_period: Optional[float] = None,
            max_period: Optional[float] = None,
            feat_shape: Optional[Tuple[int, int]] = None,
            normalize_coords: str = 'separate',
            grid_offset: float = 0.0,
            grid_indexing: str = 'ij',
            rotate_half: bool = True,
            shift_coords: Optional[float] = None,
            jitter_coords: Optional[float] = None,
            rescale_coords: Optional[float] = None,
            *,
            rngs: nnx.Rngs = None,
    ):
        if any(a is not None for a in (shift_coords, jitter_coords, rescale_coords)):
            raise NotImplementedError('DINOv3 rope train-time coord augs not implemented')
        self.dim = dim
        self.rotate_half = rotate_half
        self.temperature = float(temperature) if temperature is not None else None
        self.min_period = min_period
        self.max_period = max_period
        self.normalize_coords = normalize_coords
        self.feat_shape = feat_shape
        self.grid_offset = grid_offset
        self.grid_indexing = grid_indexing

    def _periods(self) -> jnp.ndarray:
        d = self.dim // 4
        if self.min_period is not None and self.max_period is not None:
            exponents = jnp.linspace(0.0, 1.0, d)
            return self.min_period * ((self.max_period / self.min_period) ** exponents)
        if self.temperature is None:
            raise ValueError('Provide either min/max periods or `temperature`.')
        exponents = 2.0 * jnp.arange(d, dtype=jnp.float32) / (self.dim // 2)
        return self.temperature ** exponents

    def get_embed(self, shape: Optional[Tuple[int, int]] = None):
        shape = shape if shape is not None else self.feat_shape
        assert shape is not None
        coords = make_coords_dinov3(
            shape[0], shape[1], normalize_coords=self.normalize_coords,
            grid_indexing=self.grid_indexing, grid_offset=self.grid_offset)  # (HW, 2)
        periods = self._periods()
        angles = 2 * math.pi * coords[:, :, None] / periods[None, None, :]
        angles = angles.reshape(angles.shape[0], -1)  # (HW, dim//2)
        if self.rotate_half:
            angles = jnp.tile(angles, (1, 2))
        else:
            angles = jnp.repeat(angles, 2, axis=-1)
        return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1)


def create_rope_embed(rope_type: str = 'cat', dim: int = 768, num_heads: int = 12,
                      *, rngs: nnx.Rngs = None, **kwargs):
    """Rope factory matching reference pos_embed_sincos.py:1315-1357 ('cat',
    'mixed', 'dinov3' supported here)."""
    if rope_type == 'cat':
        kwargs.pop('rotate_half', None)
        return RotaryEmbeddingCat(dim=dim // num_heads, rngs=rngs, **kwargs)
    if rope_type == 'mixed':
        kwargs.pop('in_pixels', None)
        kwargs.pop('ref_feat_shape', None)
        kwargs.pop('rotate_half', None)
        kwargs.pop('grid_offset', None)
        return RotaryEmbeddingMixed(dim=dim, num_heads=num_heads, rngs=rngs, **kwargs)
    if rope_type == 'dinov3':
        kwargs.pop('in_pixels', None)
        kwargs.pop('ref_feat_shape', None)
        return RotaryEmbeddingDinoV3(dim=dim // num_heads, rngs=rngs, **kwargs)
    raise ValueError(f'Unknown RoPE type: {rope_type}')


def build_rotary_pos_embed_1d(seq_len: int, dim: int, theta: float = 10000.0, dtype=jnp.float32) -> jnp.ndarray:
    """1-D rotary table for token sequences: (seq_len, 2 * dim), sin then cos
    concatenated as `apply_rot_embed_cat` takes them, in the *half* layout
    (frequency j turns dimensions j and j + dim/2; `half=True` there).
    Frequencies theta^(-2j/dim), positions 0..seq_len-1, no scaling."""
    inv_freq = 1.0 / (theta ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    angles = jnp.arange(seq_len, dtype=jnp.float32)[:, None] * inv_freq[None, :]      # (S, dim/2)
    angles = jnp.concatenate([angles, angles], axis=-1)
    return jnp.concatenate([jnp.sin(angles), jnp.cos(angles)], axis=-1).astype(dtype)
