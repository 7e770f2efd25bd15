"""Grouped-query causal attention for token sequences, with an optional window
and an optional rotary turn (SmallThinker arXiv:2507.20984: three layers of four
turn their queries and keys and see the last `window` positions, the fourth
turns nothing and sees everything before it, so it carries no position signal
of its own).

`num_heads` query heads read `num_kv_heads` key/value heads, query head g the
key/value head g // (num_heads / num_kv_heads); every linear map is bias-free.
Key j is seen by query i when j <= i and, with a window, i - j < window.
Nothing is cached here: a decode cache that keeps a ring of `window` positions
for the window layers beside a full one belongs to `serve/` (ROADMAP "Reach").

The core runs on the Pallas kernel wherever `causal_flash_supported`
(`kernels/causal_attention.py`: splash attention's multi-query form a key/value
head, the window as the kernel's own mask) and on `grouped_causal_attention`
elsewhere (the CPU tests' toy sizes): XLA alone, queries in blocks of
`block_q`, each rematerialised in the backward pass, the block at `i0` against
the keys `[max(0, i0 - window + 1), i0 + block_q)` by a static slice, so what
the mask excludes whole is never multiplied, and K and V are never repeated to
the query heads (the group is an axis of the einsum). Both paths also say how
many (query block, key block) tiles of a sequence they multiply, the step's
`attn.full_blocks` / `attn.window_blocks` counters.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from flax import nnx
from jax.ad_checkpoint import checkpoint_name

from ..utils import tracing
from .attention import apply_rot_embed_cat
from .latent_attention import CORE_OUT, SLOW_FROM, _warn_xla_core
from .weight_init import trunc_normal_

__all__ = ['GroupedQueryAttention', 'grouped_causal_attention']


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5, 6))
def _query_block(q, k, v, scale: float, start: int, first_key: int, window: Optional[int]):
    """Queries [start, start + bq) of every group, q (B, H_kv, G, bq, D), against keys [first_key, first_key + keys)."""
    s = jnp.einsum('bhgqd,bhkd->bhgqk', q, k, preferred_element_type=jnp.float32) * scale
    s = jax.lax.optimization_barrier(s)
    qi = (start + jnp.arange(q.shape[3]))[:, None]
    kj = (first_key + jnp.arange(k.shape[2]))[None, :]
    seen = kj <= qi if window is None else (kj <= qi) & (qi - kj < window)
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum('bhgqk,bhkd->bhgqd', p.astype(v.dtype), v)


def grouped_causal_attention(q, k, v, scale: float, window: Optional[int] = None, block_q: int = 1024,
                             with_tiles: bool = False):
    """softmax(q k^T * scale + mask) v for q (B, H, S, D) on k, v (B, H_kv, S, D), H a multiple of H_kv;
    the mask is causal and, with `window`, within the last `window` positions; softmax in float32, in query
    blocks. `with_tiles` also returns the (query block, key block) tiles of one sequence the slices span,
    in blocks of `block_q` both ways."""
    (B, H, S, D), H_kv = q.shape, k.shape[1]
    if H % H_kv:
        raise ValueError(f'{H} query heads are not a multiple of {H_kv} key/value heads')
    block_q = min(block_q, S)
    if S % block_q:
        raise ValueError(f'sequence length {S} is not a multiple of the query block {block_q}')
    q = q.reshape(B, H_kv, H // H_kv, S, D)
    out, tiles = [], 0
    for i in range(0, S, block_q):
        first = 0 if window is None else max(0, i - window + 1)
        out.append(_query_block(q[:, :, :, i:i + block_q], k[:, :, first:i + block_q], v[:, :, first:i + block_q],
                                float(scale), i, first, window))
        tiles += -(-(i + block_q - first) // block_q)
    out = (out[0] if len(out) == 1 else jnp.concatenate(out, axis=3)).reshape(B, H, S, D)
    return (out, tiles) if with_tiles else out


class GroupedQueryAttention(nnx.Module):
    """x (B, S, dim) -> (y (B, S, dim), tiles): `rope` is the (S, 2 * head_dim) table of
    `build_rotary_pos_embed_1d`, read only where the layer was built with `rotary`; `tiles` is a Python int,
    the (query block, key block) tiles the core multiplies for one sequence."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            num_kv_heads: int,
            head_dim: int,
            window: Optional[int] = None,
            rotary: bool = True,
            block_q: int = 1024,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        if num_heads % num_kv_heads:
            raise ValueError(f'{num_heads} query heads are not a multiple of {num_kv_heads} key/value heads')
        self.num_heads, self.num_kv_heads, self.head_dim = num_heads, num_kv_heads, head_dim
        self.window, self.rotary, self.block_q = window, rotary, block_q
        self.scale = head_dim ** -0.5
        linear = functools.partial(nnx.Linear, use_bias=False, dtype=dtype, param_dtype=param_dtype,
                         kernel_init=trunc_normal_(std=0.02), rngs=rngs)
        # the names the tensor-parallel rules of `parallel/sharding.py` know: heads over 'model', `proj` row-wise
        self.q_proj = linear(dim, num_heads * head_dim)
        self.k_proj = linear(dim, num_kv_heads * head_dim)
        self.v_proj = linear(dim, num_kv_heads * head_dim)
        self.proj = linear(num_heads * head_dim, dim)

    def qkv(self, x, rope=None):
        """-> q (B, H, S, D), k and v (B, H_kv, S, D), q and k turned where the layer turns."""
        B, S, _ = x.shape
        heads = lambda t, n: t.reshape(B, S, n, self.head_dim).transpose(0, 2, 1, 3)  # noqa: E731
        q, k = heads(self.q_proj(x), self.num_heads), heads(self.k_proj(x), self.num_kv_heads)
        v = heads(self.v_proj(x), self.num_kv_heads)
        if self.rotary:
            rope = rope.astype(jnp.float32)
            q = apply_rot_embed_cat(q.astype(jnp.float32), rope, half=True).astype(q.dtype)
            k = apply_rot_embed_cat(k.astype(jnp.float32), rope, half=True).astype(k.dtype)
        return q, k, v

    def __call__(self, x, rope=None):
        B, S, _ = x.shape
        with tracing.scope('swa.attn.proj'):
            q, k, v = self.qkv(x, rope)
        window = self.window if self.window is not None and self.window < S else None   # a window over all of S masks nothing
        core = tracing.scope('swa.attn.core_full') if self.window is None else tracing.scope('swa.attn.core_window')
        with core:
            from ..kernels import causal_flash_attention, causal_flash_supported
            if causal_flash_supported(q, k, v, window=window):
                out, tiles = causal_flash_attention(q, k, v, self.scale, window, with_tiles=True)
            else:
                if S >= SLOW_FROM and jax.default_backend() == 'tpu':
                    _warn_xla_core(q.shape, v.shape)
                out, tiles = grouped_causal_attention(q, k, v, self.scale, window, self.block_q, with_tiles=True)
            out = checkpoint_name(out, CORE_OUT)
        with tracing.scope('swa.attn.proj'):
            return self.proj(out.transpose(0, 2, 1, 3).reshape(B, S, self.num_heads * self.head_dim)), tiles
