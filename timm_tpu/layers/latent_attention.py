"""Multi-head latent attention (DeepSeek-V2 arXiv:2405.04434 section 2.1, as
DeepSeek-V3 and GLM-4.7-Flash `glm4_moe_lite` use it), causal, for training
and prefill.

Queries and keys/values go through low-rank bottlenecks with an RMSNorm on the
latent; each head's query and key carry a `nope` part from the latent and a
rotary part, the key's rotary part being ONE vector a position shared by all
heads. Nothing is cached here: a decode cache for the latent belongs to
`serve/` (ROADMAP "Reach").

q, k and v leave their products as (B, H, S, D), the layout the core reads, and the core's output enters `o`'s
product as it is: every (B, S, H, D) transpose of an activation was a copy of 168-294 MB in the GLM cell's step, nine
of them and four slices a layer between the products and the kernel (PERF.md section 6, PR 44).

The core, `causal_attention`, never builds the (heads, S, S) scores: queries
go in blocks of `block_q`, block i sees keys [0, (i+1) * block_q) by a static
slice, so the causally dead blocks are never computed, and each block is
rematerialised in the backward pass (`jax.checkpoint`), so at most one block's
(heads, block_q, keys) scores are alive, forward or backward. That path is XLA
alone (einsum, float32 softmax, einsum) and serves the shapes the Pallas kernel
does not take (`kernels/causal_attention.py` `causal_flash_supported`: head
widths that are lane multiples, sequences of 256 and more), the CPU tests' toy
sizes among them; wherever the kernel applies, `LatentAttention` calls it, with
no switch. It is the reference, not a production path: at 8192 positions it
ran at 2.8 % of a v5e's peak (PERF.md section 6, PR 26), so a layer that takes
it on a TPU at 2048 positions or more says so once in the log. The `optimization_barrier` after the scores keeps XLA from fusing
the mask and the softmax's reductions into the product's output (PERF.md
section 6, PR 26).
"""
from __future__ import annotations

import functools
import logging
from functools import partial

import jax
import jax.numpy as jnp
from flax import nnx
from jax.ad_checkpoint import checkpoint_name

from ..utils import tracing
from .attention import apply_rot_embed_cat
from .norm import RmsNorm
from .weight_init import trunc_normal_

__all__ = ['LatentAttention', 'causal_attention']

CORE_OUT = 'mla_core_out'   # checkpoint_name of the core's output, for a block-level remat policy
SLOW_FROM = 2048            # positions from which the XLA core on a TPU is worth a line in the log

_logger = logging.getLogger(__name__)
_WARNED_SHAPES = set()
HEAD_MAJOR = 'bsr,rhd->bhsd'   # x (B, S, rank) by a (rank, H, D) view of a kernel, written as the core reads it


def _warn_xla_core(q_shape, v_shape):
    """Once a shape: a long sequence on a TPU outside what the Pallas kernel takes."""
    if (q_shape, v_shape) not in _WARNED_SHAPES:
        _WARNED_SHAPES.add((q_shape, v_shape))
        _logger.warning(f'LatentAttention: q/k {q_shape} v {v_shape} is outside `causal_flash_supported` (one head width, '
                        f'a multiple of 128, for q, k and v; S a multiple of its block): the XLA query-block core '
                        f'runs instead, about 10x slower at this length')


@functools.partial(jax.checkpoint, static_argnums=(3, 4))
def _query_block(q, k, v, scale: float, start: int):
    """One block of queries [start, start + bq) against keys [0, start + bq)."""
    s = jnp.einsum('bhqd,bhkd->bhqk', q, k, preferred_element_type=jnp.float32) * scale
    s = jax.lax.optimization_barrier(s)
    qi = start + jnp.arange(q.shape[2])
    s = jnp.where(jnp.arange(k.shape[2])[None, :] <= qi[:, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum('bhqk,bhkd->bhqd', p.astype(v.dtype), v)


def causal_attention(q, k, v, scale: float, block_q: int = 1024):
    """softmax(q k^T * scale + causal mask) v on (B, H, S, D) tensors (v may
    have another width), softmax in float32, in query blocks."""
    S = q.shape[2]
    block_q = min(block_q, S)
    if S % block_q:
        raise ValueError(f'sequence length {S} is not a multiple of the query block {block_q}')
    out = [_query_block(q[:, :, i:i + block_q], k[:, :, :i + block_q], v[:, :, :i + block_q], float(scale), i)
           for i in range(0, S, block_q)]
    return out[0] if len(out) == 1 else jnp.concatenate(out, axis=2)


def _per_head(linear, x, num_heads: int):
    """`linear(x)`'s operands as `nnx.Linear` promotes them, the (rank, H x D) kernel seen as (rank, H, D): what is
    reshaped and cut into column blocks is the kernel (megabytes), never the (B, S, H x D) product of it."""
    x, kernel = linear.promote_dtype((x, linear.kernel[...]), dtype=linear.dtype)
    return x, kernel.reshape(kernel.shape[0], num_heads, -1)


class LatentAttention(nnx.Module):
    """x (B, S, dim) -> (B, S, dim); `rope` is the (S, 2 * qk_rope_head_dim)
    table of `build_rotary_pos_embed_1d`."""

    def __init__(
            self,
            dim: int,
            num_heads: int,
            q_lora_rank: int,
            kv_lora_rank: int,
            qk_nope_head_dim: int,
            qk_rope_head_dim: int,
            v_head_dim: int,
            eps: float = 1e-5,
            block_q: int = 1024,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        self.num_heads = num_heads
        self.kv_lora_rank = kv_lora_rank
        self.nope, self.rope, self.v_dim = qk_nope_head_dim, qk_rope_head_dim, v_head_dim
        self.scale = (qk_nope_head_dim + qk_rope_head_dim) ** -0.5
        self.block_q = block_q
        linear = partial(nnx.Linear, use_bias=False, dtype=dtype, param_dtype=param_dtype,
                         kernel_init=trunc_normal_(std=0.02), rngs=rngs)
        norm = partial(RmsNorm, eps=eps, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.q_a = linear(dim, q_lora_rank)
        self.q_norm = norm(q_lora_rank)
        self.q_b = linear(q_lora_rank, num_heads * (qk_nope_head_dim + qk_rope_head_dim))
        self.kv_a = linear(dim, kv_lora_rank + qk_rope_head_dim)
        self.kv_norm = norm(kv_lora_rank)
        self.kv_b = linear(kv_lora_rank, num_heads * (qk_nope_head_dim + v_head_dim))
        self.o = linear(num_heads * v_head_dim, dim)

    def qkv(self, x, rope):
        """-> q, k (B, H, S, nope + rope) and v (B, H, S, v_dim), each written in that layout by the product that makes it."""
        H = self.num_heads
        c_q, w_q = _per_head(self.q_b, self.q_norm(self.q_a(x)), H)
        q = jnp.einsum(HEAD_MAJOR, c_q, w_q)       # a head's whole width in one product: its rotary columns alone are half a lane tile in GLM
        q_nope, q_rope = q[..., :self.nope], q[..., self.nope:]
        kv = self.kv_a(x)
        c_kv, k_rope = kv[..., :self.kv_lora_rank], kv[..., self.kv_lora_rank:]
        c_kv, w_kv = _per_head(self.kv_b, self.kv_norm(c_kv), H)
        v = jnp.einsum(HEAD_MAJOR, c_kv, w_kv[..., self.nope:])
        rope = rope.astype(jnp.float32)
        q_rope = apply_rot_embed_cat(q_rope.astype(jnp.float32), rope, half=True).astype(q.dtype)
        k_rope = apply_rot_embed_cat(k_rope.astype(jnp.float32), rope, half=True).astype(q.dtype)   # (B, S, rope)
        # k leaves its product whole: zero columns of the kernel where the rotary key, one vector a position for all
        # heads, is added in the product's own output fusion (no concatenate, no broadcast to H heads in memory)
        w_k = jnp.pad(w_kv[..., :self.nope], ((0, 0), (0, 0), (0, self.rope)))
        k = jnp.einsum(HEAD_MAJOR, c_kv, w_k) + jnp.pad(k_rope, ((0, 0), (0, 0), (self.nope, 0)))[:, None]
        return jnp.concatenate([q_nope, q_rope], -1), k, v

    def __call__(self, x, rope):
        S = x.shape[1]
        with tracing.scope('glm.mla.proj'):
            q, k, v = self.qkv(x, rope)
        with tracing.scope('glm.mla.core'):
            from ..kernels import causal_flash_attention, causal_flash_supported
            if causal_flash_supported(q, k, v):
                out = causal_flash_attention(q, k, v, self.scale)         # the Pallas kernel (kernels/causal_attention.py)
            else:
                if S >= SLOW_FROM and jax.default_backend() == 'tpu':
                    _warn_xla_core(q.shape, v.shape)
                out = causal_attention(q, k, v, self.scale, self.block_q)
            out = checkpoint_name(out, CORE_OUT)
        with tracing.scope('glm.mla.proj'):
            out, w_o = self.o.promote_dtype((out, self.o.kernel[...]), dtype=self.o.dtype)
            return jnp.einsum('bhsd,hdm->bsm', out, w_o.reshape(self.num_heads, self.v_dim, -1))
