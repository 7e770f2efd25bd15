"""Image-to-patch embedding (reference: timm/layers/patch_embed.py:26-170).

TPU-first: input images are NHWC; the patch projection is an NHWC conv with
stride == kernel == patch size (XLA lowers this to a single reshaped matmul on
the MXU). Output is (B, N, C) tokens when flatten=True else an NHWC grid.
"""
from __future__ import annotations

from typing import Callable, Optional, Tuple, Union

import jax.numpy as jnp
from flax import nnx

from ..utils import tracing
from .helpers import to_2tuple
from .weight_init import lecun_normal_, zeros_

__all__ = ['PatchEmbed', 'resample_patch_embed']


class PatchEmbed(nnx.Module):
    def __init__(
            self,
            img_size: Optional[int] = 224,
            patch_size: int = 16,
            in_chans: int = 3,
            embed_dim: int = 768,
            norm_layer: Optional[Callable] = None,
            flatten: bool = True,
            bias: bool = True,
            strict_img_size: bool = True,
            dynamic_img_pad: bool = False,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.patch_size = to_2tuple(patch_size)
        if img_size is not None:
            self.img_size = to_2tuple(img_size)
            self.grid_size = tuple(s // p for s, p in zip(self.img_size, self.patch_size))
            self.num_patches = self.grid_size[0] * self.grid_size[1]
        else:
            self.img_size = None
            self.grid_size = None
            self.num_patches = None
        self.flatten = flatten
        self.strict_img_size = strict_img_size
        self.dynamic_img_pad = dynamic_img_pad

        self.proj = nnx.Conv(
            in_chans, embed_dim,
            kernel_size=self.patch_size,
            strides=self.patch_size,
            padding='VALID',
            use_bias=bias,
            dtype=dtype,
            param_dtype=param_dtype,
            kernel_init=lecun_normal_(),
            bias_init=zeros_,
            rngs=rngs,
        )
        self.norm = norm_layer(embed_dim, rngs=rngs) if norm_layer is not None else None

    def set_input_size(self, img_size=None, patch_size=None):
        if patch_size is not None:
            assert to_2tuple(patch_size) == self.patch_size, 'patch resize not supported post-init'
        if img_size is not None:
            self.img_size = to_2tuple(img_size)
            self.grid_size = tuple(s // p for s, p in zip(self.img_size, self.patch_size))
            self.num_patches = self.grid_size[0] * self.grid_size[1]

    def dynamic_feat_size(self, img_size: Tuple[int, int]) -> Tuple[int, int]:
        if self.dynamic_img_pad:
            return tuple(-(-s // p) for s, p in zip(img_size, self.patch_size))
        return tuple(s // p for s, p in zip(img_size, self.patch_size))

    def __call__(self, x):
        B, H, W, C = x.shape
        if self.img_size is not None and self.strict_img_size and not self.dynamic_img_pad:
            assert (H, W) == self.img_size, f'Input size ({H},{W}) != model ({self.img_size})'
        if self.dynamic_img_pad:
            ph, pw = self.patch_size
            pad_h = (ph - H % ph) % ph
            pad_w = (pw - W % pw) % pw
            if pad_h or pad_w:
                x = jnp.pad(x, ((0, 0), (0, pad_h), (0, pad_w), (0, 0)))
        with tracing.scope('img.patch_embed'):
            x = self.proj(x)
            if self.norm is not None:
                x = self.norm(x)
            if self.flatten:
                x = x.reshape(x.shape[0], -1, x.shape[-1])  # (B, H*W, C)
        return x


def resample_patch_embed(kernel, new_size, interpolation: str = 'cubic', antialias: bool = True):
    """PI-resize a patch-projection kernel (HWIO) to a new patch size.

    FlexiViT-style resampling (reference patch_embed.py:176+) approximated with
    a direct resize of the spatial dims; adequate for fine-tuning conversions.
    """
    import jax
    kh, kw, ci, co = kernel.shape
    if (kh, kw) == tuple(new_size):
        return kernel
    return jax.image.resize(kernel, (*new_size, ci, co), method=interpolation, antialias=antialias)
