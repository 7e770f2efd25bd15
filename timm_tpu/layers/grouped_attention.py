"""Grouped-query causal attention for token sequences, with an optional window
and an optional rotary turn (SmallThinker arXiv:2507.20984: three layers of four
turn their queries and keys and see the last `window` positions, the fourth
turns nothing and sees everything before it, so it carries no position signal
of its own), or under the block-diffusion mask over a noised copy beside the
clean sequence, with an RMSNorm on every head's query and key before the turn
(SDAR, whose trunk is Qwen3-MoE's).

`num_heads` query heads read `num_kv_heads` key/value heads, query head g the
key/value head g // (num_heads / num_kv_heads); every linear map is bias-free.
With `gate` the core's output is multiplied elementwise by the sigmoid of one
more product of the layer's input before the output product (Solar-Open2's
`use_gqa_gate`; its attention layers turn nothing and see everything, as
SmallThinker's full layers). SmallThinker, SDAR and LFM2 hold ALL heads on
every chip; Solar-Open2's share is TOLD WHICH HEADS IT HOLDS (`heads_held`,
`head_offset`: whole key/value heads with their query groups, as
`ChunkedLinearAttention` and `KimiDeltaAttention` are told theirs): it projects
to those heads only and returns their PART of the output product, a sum over
heads, so the parts of all shares add up to the whole layer's output; on one
chip nothing stands in for the absent heads or their sum.
Key j is seen by query i when j <= i and, with a window, i - j < window. With
`block_diffusion` (a block length K) the input holds 2 L rows, L noised and
then L clean ones, row r at position r mod L (the rotary table is read there),
and the mask is `kernels.causal_attention.block_diffusion_seen`: a noised
query sees the noised keys of its own block of K and the clean keys of earlier
blocks, a clean query the clean keys of its own and earlier blocks. A layer
whose output is read at its noised rows only (a model's last) is called with
`queries=L`: queries, core and output projection run on those rows, and of the
clean rows only K and V are made.
Nothing is cached here: a decode cache that keeps a ring of `window` positions
for the window layers beside a full one belongs to `serve/` (ROADMAP "Reach").

The core runs on the Pallas kernel wherever `causal_flash_supported`
(`kernels/causal_attention.py`: splash attention's multi-query form a key/value
head, the window as the kernel's own mask) and on `grouped_causal_attention`
elsewhere (the CPU tests' toy sizes): XLA alone, queries in blocks of
`block_q`, each rematerialised in the backward pass, the block at `i0` against
the keys `[max(0, i0 - window + 1), i0 + block_q)` by a static slice, so what
the mask excludes whole is never multiplied, and K and V are never repeated to
the query heads (the group is an axis of the einsum). Under the block-diffusion
mask the XLA path is `grouped_block_diffusion_attention`: a block of noised
queries against its own blocks' noised keys and the clean keys before them
under ONE softmax, a block of clean queries against the clean keys up to its
last block. Both paths also say how many (query block, key block) tiles of a
sequence they multiply, the step's `attn.full_blocks` / `attn.window_blocks` /
`attn.bd_blocks` counters.
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
from .helpers import head_slice
from .latent_attention import CORE_OUT, SLOW_FROM, _warn_xla_core
from .norm import RmsNorm
from .weight_init import trunc_normal_

__all__ = ['GroupedQueryAttention', 'grouped_causal_attention', 'grouped_block_diffusion_attention']


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


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5, 6, 7))
def _block_diffusion_query_block(q, k, v, scale: float, start: int, spans: tuple, length: int, block: int):
    """Query rows [start, start + bq) of every group, q (B, H_kv, G, bq, D), against the key rows of `spans`
    ((first, last) ranges of the 2 x length rows, concatenated in k and v) under one softmax."""
    from ..kernels.causal_attention import block_diffusion_seen
    s = jnp.einsum('bhgqd,bhkd->bhgqk', q, k, preferred_element_type=jnp.float32) * scale
    s = jax.lax.optimization_barrier(s)
    qi = (start + jnp.arange(q.shape[3]))[:, None]
    kj = jnp.concatenate([jnp.arange(a, b) for a, b in spans])[None, :]
    p = jax.nn.softmax(jnp.where(block_diffusion_seen(qi, kj, length, block), s, -jnp.inf), axis=-1)
    return jnp.einsum('bhgqk,bhkd->bhgqd', p.astype(v.dtype), v)


def grouped_block_diffusion_attention(q, k, v, scale: float, block_diffusion: int, block_q: int = 1024, with_tiles: bool = False):
    """softmax(q k^T * scale + mask) v under the block-diffusion mask of blocks of `block_diffusion`: k, v (B, H_kv, 2 L,
    D) hold L noised rows and then L clean ones, q (B, H, R, D) their queries, all 2 L or the L noised ones.
    Queries go in blocks of `block_q` rows, each rematerialised in the backward pass, a noised block at
    positions [i0, i1) against the noised keys of the blocks it touches and the clean keys of the blocks
    before its last, a clean block against the clean keys up to the end of its last block: the key tiles the
    mask leaves empty are never multiplied. `with_tiles` also returns the (query block, key block) tiles of
    one sequence those slices span, in blocks of `block_q` both ways."""
    (B, H, R, D), (H_kv, rows), block = q.shape, k.shape[1:3], block_diffusion
    L = rows // 2
    if H % H_kv or rows != 2 * L or R not in (L, 2 * L) or L % block:
        raise ValueError(f'q {q.shape} on k {k.shape} is no noised copy beside a clean sequence in blocks of {block}')
    block_q = min(block_q, L)
    if L % block_q:
        raise ValueError(f'sequence length {L} is not a multiple of the query block {block_q}')
    q = q.reshape(B, H_kv, H // H_kv, R, D)
    out, tiles = [], 0
    for start in range(0, R, block_q):
        i0 = start % L
        last = (i0 + block_q - 1) // block                     # the last block this query block touches
        if start < L:
            spans = ((i0 // block * block, min(L, (last + 1) * block)), (L, L + last * block))
        else:
            spans = ((L, L + min(L, (last + 1) * block)),)
        spans = tuple((a, b) for a, b in spans if b > a)
        ks, vs = (t[:, :, spans[0][0]:spans[0][1]] if len(spans) == 1 else
                  jnp.concatenate([t[:, :, a:b] for a, b in spans], axis=2) for t in (k, v))
        out.append(_block_diffusion_query_block(q[:, :, :, start:start + block_q], ks, vs, float(scale), start, spans, L, block))
        tiles += sum(-(-b // block_q) - a // block_q for a, b in spans)
    out = (out[0] if len(out) == 1 else jnp.concatenate(out, axis=3)).reshape(B, H, R, D)
    return (out, tiles) if with_tiles else out


class GroupedQueryAttention(nnx.Module):
    """x (B, S, dim) -> (y (B, S, dim), tiles): `rope` is the (S, 2 * head_dim) table of
    `build_rotary_pos_embed_1d`, read only where the layer was built with `rotary`; `tiles` is a Python int,
    the (query block, key block) tiles the core multiplies for one sequence. With `block_diffusion` S is 2 L,
    the table has L rows, and `queries=L` gives y for the L noised rows alone."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            num_kv_heads: int,
            head_dim: int,
            window: Optional[int] = None,
            rotary: bool = True,
            block_q: int = 1024,
            qk_norm: bool = False,
            block_diffusion: Optional[int] = None,
            eps: float = 1e-6,
            heads_held: Optional[int] = None,
            head_offset: int = 0,
            gate: bool = False,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        if num_heads % num_kv_heads:
            raise ValueError(f'{num_heads} query heads are not a multiple of {num_kv_heads} key/value heads')
        group, held = num_heads // num_kv_heads, heads_held or num_heads
        if head_offset < 0 or head_offset + held > num_heads or held % group or head_offset % group:
            raise ValueError(f'query heads {head_offset} .. {head_offset + held} of {num_heads} are no whole key/value '
                             f'heads with their groups of {group}')
        # the share: `num_heads` / `num_kv_heads` are the heads HELD from here on, `head_offset` the first query head
        self.head_offset = head_offset
        num_heads, num_kv_heads = held, held // group
        self.num_heads, self.num_kv_heads, self.head_dim = num_heads, num_kv_heads, head_dim
        if window is not None and block_diffusion is not None:
            raise ValueError('a window under the block-diffusion mask is not a mask this layer knows')
        self.window, self.rotary, self.block_q, self.block_diffusion = window, rotary, block_q, block_diffusion
        self.scale = head_dim ** -0.5
        linear = functools.partial(nnx.Linear, use_bias=False, dtype=dtype, param_dtype=param_dtype,
                         kernel_init=trunc_normal_(std=0.02), rngs=rngs)
        # the names the tensor-parallel rules of `parallel/sharding.py` know: heads over 'model', `proj` row-wise
        self.q_proj = linear(dim, num_heads * head_dim)
        self.k_proj = linear(dim, num_kv_heads * head_dim)
        self.v_proj = linear(dim, num_kv_heads * head_dim)
        self.proj = linear(num_heads * head_dim, dim)
        # an output gate (Solar-Open2's `use_gqa_gate`): sigmoid of one more product of the layer's input, on the core's output
        self.gate_proj = linear(dim, num_heads * head_dim) if gate else None
        # Qwen3's q_norm / k_norm: one learned scale of `head_dim`, every head alike, statistics in float32
        norm = functools.partial(RmsNorm, head_dim, eps=eps, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.q_norm, self.k_norm = (norm(), norm()) if qk_norm else (None, None)

    def take_heads(self, name: str, whole):
        """This share's slice of a leaf of the whole layer, by the leaf's name in this module (`q_proj.kernel`, ..): query
        heads `head_offset` .. + `num_heads` and the key/value heads they read."""
        if name in ('q_norm.scale', 'k_norm.scale'):
            return whole
        group = self.num_heads // self.num_kv_heads
        offset, held = (self.head_offset // group, self.num_kv_heads) if name[0] in 'kv' else (self.head_offset, self.num_heads)
        return head_slice(whole, 0 if name == 'proj.kernel' else 1, offset, held, self.head_dim)

    def qkv(self, x, rope=None, queries: Optional[int] = None):
        """-> q (B, H, S, D), k and v (B, H_kv, S, D), q and k normalised where the layer has the norms and turned
        where it turns; with `queries`, q of the first `queries` rows alone."""
        B, S, _ = x.shape
        heads = lambda t, n: t.reshape(B, -1, n, self.head_dim).transpose(0, 2, 1, 3)  # noqa: E731
        q = heads(self.q_proj(x if queries is None else x[:, :queries]), self.num_heads)
        k, v = heads(self.k_proj(x), self.num_kv_heads), heads(self.v_proj(x), self.num_kv_heads)
        if self.q_norm is not None:
            q, k = self.q_norm(q), self.k_norm(k)
        if self.rotary:
            rope = rope.astype(jnp.float32)
            if self.block_diffusion is not None:
                rope = jnp.concatenate([rope, rope])            # row r of the two halves carries position r mod L
            q = apply_rot_embed_cat(q.astype(jnp.float32), rope if queries is None else rope[:queries], half=True).astype(q.dtype)
            k = apply_rot_embed_cat(k.astype(jnp.float32), rope, half=True).astype(k.dtype)
        return q, k, v

    def __call__(self, x, rope=None, queries: Optional[int] = None):
        B, S, _ = x.shape
        with tracing.scope('swa.attn.proj'):
            q, k, v = self.qkv(x, rope, queries)
        if self.block_diffusion is not None:
            mask, core = dict(block_diffusion=self.block_diffusion), tracing.scope('swa.attn.core_bd')
        else:
            window = self.window if self.window is not None and self.window < S else None   # a window over all of S masks nothing
            mask = dict(window=window)
            core = tracing.scope('swa.attn.core_full') if self.window is None else tracing.scope('swa.attn.core_window')
        with core:
            from ..kernels import causal_flash_attention, causal_flash_supported
            if causal_flash_supported(q, k, v, **mask):
                out, tiles = causal_flash_attention(q, k, v, self.scale, with_tiles=True, **mask)
            else:
                if S >= SLOW_FROM and jax.default_backend() == 'tpu':
                    _warn_xla_core(q.shape, v.shape)
                xla = grouped_causal_attention if self.block_diffusion is None else grouped_block_diffusion_attention
                out, tiles = xla(q, k, v, self.scale, block_q=self.block_q, with_tiles=True, **mask)
            out = checkpoint_name(out, CORE_OUT)
        with tracing.scope('swa.attn.proj'):
            out = out.transpose(0, 2, 1, 3).reshape(B, -1, self.num_heads * self.head_dim)
            if self.gate_proj is not None:
                gate = self.gate_proj(x if queries is None else x[:, :queries])
                out = (out.astype(jnp.float32) * jax.nn.sigmoid(gate.astype(jnp.float32))).astype(out.dtype)
            return self.proj(out), tiles
