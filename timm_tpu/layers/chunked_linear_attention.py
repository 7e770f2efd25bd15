"""Chunk-pooled linear attention under one softmax with a causal window (EVA,
"Efficient Attention via Control Variates", arXiv:2302.04542, in the
deterministic form EvaByte's public modelling code uses). Not `models/eva.py`,
the image model EVA-02, which shares the name and nothing else.

A sequence of N positions is cut into windows of `window` positions and chunks
of `chunk` (N a multiple of the window, the window of the chunk; a sequence
shorter than the window is one window of its own length). Every head
has two learned vectors phi and mu of `head_dim`. After the rotary turn of q
and k:

  summaries   alpha_t = softmax over chunk j's `chunk` positions of (k_t . phi)
              k~_j = sum_t alpha_t k_t + mu        v~_j = sum_t alpha_t v_t
  core        query i sees the single keys L(i) = {t : t // window == i // window, t <= i} and the
              summaries R(i) = {j : j < (window / chunk) (i // window)}: every chunk of every EARLIER
              window and none of its own;
              o_i = softmax over L(i) and R(i) TOGETHER of (scale q_i . key) applied to their values

so a query's cost is its window plus N / chunk summaries at most, not N keys.
Both softmaxes run in float32 on operands in the compute dtype.

The layer is TOLD WHICH HEADS IT HOLDS (`heads_held`, `head_offset`): of
`num_heads` published heads it projects to heads `head_offset` ..
`head_offset + heads_held` only, carries their phi and mu, and returns their
part of the output product (a sum over heads: the parts of all shares add up to
the whole layer's output). What tensor parallelism over heads asks of an
attention layer, as `layers/moe.py` is told which experts it holds; on one chip
nothing stands in for the absent heads or their reduction. `take_heads` gives
this share's slice of a leaf of the whole layer.

The core runs on the Pallas kernel wherever `causal_flash_supported(...,
chunk_window=...)` (`kernels/causal_attention.py`: splash attention over the
rectangle of N queries on N / chunk + N keys, summaries first, the chunk-window
mask as the kernel's own, empty tiles skipped forward and backward) and on
`chunk_window_attention` elsewhere (the CPU tests' sizes): XLA alone, queries in
blocks of `block_q`, a block against the single keys of its window up to its
own last row and the summaries before its window, concatenated under one
softmax, so no tile the mask leaves empty is multiplied. Both say how many
(query block, key block) tiles of a sequence they multiply, the step's
`attn.eva_blocks` counter. Nothing is cached here: a decode cache of two kinds
(a ring of window keys beside a growing list of summaries) belongs to `serve/`
(ROADMAP "Reach").
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
from .weight_init import trunc_normal_

__all__ = ['ChunkedLinearAttention', 'chunk_summaries', 'chunk_window_attention', 'chunk_window_pairs']


def chunk_window_pairs(seq_len: int, window: int, chunk: int) -> int:
    """(query, key) pairs the chunk-window mask leaves, single keys and summaries, for one head of one sequence."""
    windows = seq_len // window
    return windows * window * (window + 1) // 2 + window * (window // chunk) * windows * (windows - 1) // 2


def chunk_summaries(k, v, phi, mu, chunk: int):
    """k, v (B, H, N, D), phi, mu (H, D) -> the N / chunk summary keys and values (B, H, N / chunk, D): a
    softmax over each chunk's positions of k . phi, in float32, weighs the chunk's keys and values; mu is added
    to the pooled key."""
    B, H, N, D = k.shape
    kc, vc = (t.reshape(B, H, N // chunk, chunk, D) for t in (k, v))
    logits = jnp.einsum('bhjtd,hd->bhjt', kc, phi.astype(k.dtype), preferred_element_type=jnp.float32)
    alpha = jax.nn.softmax(logits, axis=-1)
    pool = lambda t: jnp.einsum('bhjt,bhjtd->bhjd', alpha, t.astype(jnp.float32))  # noqa: E731
    return (pool(kc) + mu.astype(jnp.float32)[:, None, :]).astype(k.dtype), pool(vc).astype(v.dtype)


@functools.partial(jax.checkpoint, static_argnums=(3, 4, 5))
def _query_block(q, k, v, scale: float, first_single: int, first_query: int):
    """Queries [first_query, first_query + bq) of every head, q (B, H, bq, D), against keys of which the first
    `first_single` are summaries, all seen, and the rest the single keys from the window's first position to the
    block's last row, seen causally: one softmax over both."""
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k, preferred_element_type=jnp.float32) * scale
    s = jax.lax.optimization_barrier(s)
    row = jnp.arange(q.shape[2])[:, None]
    col = jnp.arange(k.shape[2])[None, :] - first_single
    seen = col <= row + first_query
    p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', p.astype(v.dtype), v)


def chunk_window_attention(q, k, v, scale: float, window: int, chunk: int, block_q: int = 1024, with_tiles: bool = False):
    """The core for q (B, H, N, D) on k, v (B, H, N / chunk + N, D), the summaries before the single keys,
    under the chunk-window mask (`kernels.causal_attention.chunk_window_seen`); softmax in float32. Queries go in
    blocks of `block_q` rows, each rematerialised in the backward pass. `with_tiles` also returns the (query
    block, key block) tiles of one sequence those slices span, in blocks of `block_q` both ways."""
    B, H, N, D = q.shape
    summaries = N // chunk
    if N % window or window % chunk or k.shape != (B, H, summaries + N, D) or k.shape != v.shape:
        raise ValueError(f'q {q.shape} on k {k.shape} v {v.shape} is no {N} queries on {summaries} summaries and {N} '
                         f'single keys in windows of {window} and chunks of {chunk}')
    block_q = min(block_q, window)
    if window % block_q:
        raise ValueError(f'the window {window} is not a multiple of the query block {block_q}')
    out, tiles = [], 0
    for i in range(0, N, block_q):
        first = i // window * window                    # the window's first position
        before = first // chunk                         # summaries of the windows before it
        keys = lambda t: jnp.concatenate([t[:, :, :before], t[:, :, summaries + first:summaries + i + block_q]], axis=2)  # noqa: E731
        out.append(_query_block(q[:, :, i:i + block_q], keys(k), keys(v), float(scale), before, i - first))
        tiles += -(-before // block_q) + (i + block_q - first) // block_q
    out = out[0] if len(out) == 1 else jnp.concatenate(out, axis=2)
    return (out, tiles) if with_tiles else out


class ChunkedLinearAttention(nnx.Module):
    """x (B, N, dim) -> (this share's part of the output product (B, N, dim), tiles): `rope` is the (N, 2 *
    head_dim) table of `build_rotary_pos_embed_1d`; `tiles` a Python int, the (query block, key block) tiles
    the core multiplies for one sequence."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            head_dim: int,
            window: int,
            chunk: int,
            heads_held: Optional[int] = None,
            head_offset: int = 0,
            block_q: int = 1024,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        held = heads_held or num_heads
        if head_offset < 0 or head_offset + held > num_heads:
            raise ValueError(f'heads {head_offset} .. {head_offset + held} are not among {num_heads}')
        if window % chunk:
            raise ValueError(f'the window {window} is not a multiple of the chunk {chunk}')
        self.num_heads, self.heads_held, self.head_offset, self.head_dim = num_heads, held, head_offset, head_dim
        self.window, self.chunk, self.block_q = window, chunk, block_q
        self.scale = head_dim ** -0.5
        linear = functools.partial(nnx.Linear, use_bias=False, dtype=dtype, param_dtype=param_dtype,
                                   kernel_init=trunc_normal_(std=0.02), rngs=rngs)
        # the names the tensor-parallel rules of `parallel/sharding.py` know: heads over 'model', `proj` row-wise
        self.q_proj = linear(dim, held * head_dim)
        self.k_proj = linear(dim, held * head_dim)
        self.v_proj = linear(dim, held * head_dim)
        self.proj = linear(held * head_dim, dim)
        # a standard normal clipped to [-1, 1] and scaled by head_dim^-0.5, a vector a head
        vector = lambda: nnx.Param(jax.random.truncated_normal(  # noqa: E731
            rngs.params(), -1.0, 1.0, (held, head_dim), param_dtype) * head_dim ** -0.5)
        self.phi, self.mu = vector(), vector()

    def take_heads(self, name: str, whole):
        """This share's slice of a leaf of the whole `num_heads`-head layer, by the leaf's name in this module
        (`q_proj.kernel`, `proj.kernel`, `phi`, ..): what a loader of published weights hands a share."""
        if name in ('phi', 'mu'):
            return head_slice(whole, 0, self.head_offset, self.heads_held, 1)
        return head_slice(whole, 0 if name == 'proj.kernel' else 1, self.head_offset, self.heads_held, self.head_dim)

    def qkv(self, x, rope):
        """-> q, k, v (B, H, N, D) of the heads held, q and k turned."""
        B = x.shape[0]
        heads = lambda t: t.reshape(B, -1, self.heads_held, self.head_dim).transpose(0, 2, 1, 3)  # noqa: E731
        q, k, v = heads(self.q_proj(x)), heads(self.k_proj(x)), heads(self.v_proj(x))
        rope = rope.astype(jnp.float32)
        q = apply_rot_embed_cat(q.astype(jnp.float32), rope, half=True).astype(q.dtype)
        k = apply_rot_embed_cat(k.astype(jnp.float32), rope, half=True).astype(k.dtype)
        return q, k, v

    def __call__(self, x, rope):
        B, N, _ = x.shape
        with tracing.scope('evabyte.attn.proj'):
            q, k, v = self.qkv(x, rope)
        with tracing.scope('evabyte.attn.summary'):
            ks, vs = chunk_summaries(k, v, self.phi[...], self.mu[...], self.chunk)
        with tracing.scope('evabyte.attn.core'):
            from ..kernels import causal_flash_attention, causal_flash_supported
            k, v = jnp.concatenate([ks, k], axis=2), jnp.concatenate([vs, v], axis=2)
            mask = (min(self.window, N), self.chunk)
            if causal_flash_supported(q, k, v, chunk_window=mask):
                out, tiles = causal_flash_attention(q, k, v, self.scale, with_tiles=True, chunk_window=mask)
            else:
                if N >= SLOW_FROM and jax.default_backend() == 'tpu':
                    _warn_xla_core(q.shape, v.shape)
                out, tiles = chunk_window_attention(q, k, v, self.scale, *mask, block_q=self.block_q, with_tiles=True)
            out = checkpoint_name(out, CORE_OUT)
        with tracing.scope('evabyte.attn.proj'):
            return self.proj(out.transpose(0, 2, 1, 3).reshape(B, N, self.heads_held * self.head_dim)), tiles
