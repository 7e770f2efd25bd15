"""EvaByte (EvaByte/EvaByte 6.5B, 2025-01): a byte-level decoder-only language
model over 320 ids whose mixer is EVA, a chunk-pooled linear attention under one
softmax with a causal window, and whose head predicts 8 bytes ahead. It shares a
name with `models/eva.py` (the IMAGE model EVA-02) and nothing else.

Layer, pre-norm, no bias: h = h + Attn(RMSNorm_1(h)); h = h + SwiGLU(RMSNorm_2(h)).
RMSNorm is x / rms(x) * (1 + g) with g zero at the start (`norm_add_unit_offset`);
the residual stream h is carried and added in float32 whatever the compute dtype
(`fp32_skip_add`); the mixer's equations are in `layers/chunked_linear_attention.py`;
the head is ONE product hidden -> `num_pred_heads` x vocabulary, head-major, its
logits float32 (`fp32_logits`); head p predicts the id p + 1 positions on, and
`CausalLMTask` reads `num_pred_heads` to build the 8 targets and weigh the 8
losses equally. The plain reference is `benchmarks/reference/evabyte.py`.

Like the other token models it can be built as ONE CHIP'S SHARE of a deployment
that divides each layer over several chips, here by HEADS (`heads_held`,
`head_offset`): the attention block projects to its own heads, carries their two
learned vectors and returns their part of the output product; norms, the
feed-forward block (a width, never cut), embedding and head are whole on every
chip, and nothing stands in for the absent heads or their reduction. The model
contract is the one `CausalLMTask` and `train.py` use (`task_kind`,
`forward_features(ids, with_counters)`, `forward_head`); there is no router, so
no `routes`, and no multi-token-prediction MODULE (`mtp` is None: the 8 heads are
linear).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import ChunkedLinearAttention, SwiGLU, build_rotary_pos_embed_1d, chunk_window_pairs, trunc_normal_
from ..layers.latent_attention import CORE_OUT
from ..layers.mlp import FFN_UP
from ..layers.moe import merge_counters
from ..utils import tracing
from ._builder import build_model_with_cfg
from ._registry import register_model

__all__ = ['EvaByte']


class UnitOffsetRmsNorm(nnx.Module):
    """x / sqrt(mean(x^2) + eps) * (1 + g), statistics in float32, the result in the compute dtype."""

    def __init__(self, dim: int, eps: float, *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        del rngs
        self.scale = nnx.Param(jnp.zeros((dim,), param_dtype))
        self.eps, self.dtype = eps, dtype

    def __call__(self, x):
        x = x.astype(jnp.float32)
        y = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True) + self.eps) * (1.0 + self.scale[...])
        return y.astype(self.dtype or y.dtype)


class EvaByteBlock(nnx.Module):
    """(h float32, rope) -> (h float32, counters)."""

    def __init__(self, dim, attn_args: dict, hidden: int, eps: float, *, dtype=None, param_dtype=jnp.float32,
                 rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = UnitOffsetRmsNorm(dim, eps, **kw)
        self.attn = ChunkedLinearAttention(dim, **attn_args, **kw)
        self.norm2 = UnitOffsetRmsNorm(dim, eps, **kw)
        self.mlp = SwiGLU(dim, hidden, bias=False, **kw)

    def __call__(self, x, rope):
        with tracing.scope('evabyte.attn.proj'):
            a = self.norm1(x)
        y, tiles = self.attn(a, rope)
        with tracing.scope('evabyte.attn.proj'):
            x = x + y.astype(jnp.float32)
        with tracing.scope('evabyte.ffn'):
            x = x + self.mlp(self.norm2(x)).astype(jnp.float32)
        B, N = x.shape[:2]
        pairs = chunk_window_pairs(N, min(self.attn.window, N), self.attn.chunk) * self.attn.heads_held * B
        return x, {'attn.eva_blocks': tracing.device_counter('attn.eva_blocks', jnp.int32(tiles * B)),
                   'attn.eva_pairs': tracing.device_counter('attn.eva_pairs', jnp.float32(pairs))}


class EvaByte(nnx.Module):
    task_kind = 'causal_lm'
    mtp = None      # no multi-token-prediction module: the heads are columns of one product

    def __init__(
            self,
            vocab_size: int = 320,
            hidden_size: int = 4096,
            intermediate_size: int = 11008,
            num_hidden_layers: int = 32,
            num_attention_heads: int = 32,
            head_dim: int = 128,
            window_size: int = 2048,
            chunk_size: int = 16,
            num_pred_heads: int = 8,
            rope_theta: float = 1e5,
            rms_norm_eps: float = 1e-5,
            heads_held: Optional[int] = None,
            head_offset: int = 0,
            block_q: int = 1024,
            num_classes: Optional[int] = None,      # the image factory's defaults: a token model has neither
            in_chans: int = 3,
            *,
            dtype=None,
            param_dtype=jnp.float32,
            rngs: nnx.Rngs,
    ):
        del num_classes, in_chans
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        dim = hidden_size
        self.vocab_size = self.vocab_held = vocab_size      # 320 ids: the vocabulary is whole on every chip
        self.num_pred_heads = num_pred_heads
        self.num_classes = num_pred_heads * vocab_size      # the head's width: what `__call__` owes a position
        self.num_features = self.head_hidden_size = self.embed_dim = dim
        self.heads_held, self.head_offset = heads_held or num_attention_heads, head_offset
        self.rope_dim, self.rope_theta = head_dim, rope_theta
        self.compute_dtype = dtype
        self.grad_checkpointing = False
        attn_args = dict(num_heads=num_attention_heads, head_dim=head_dim, window=window_size, chunk=chunk_size,
                         heads_held=heads_held, head_offset=head_offset, block_q=block_q)
        # the residual stream is float32 from the lookup on (`fp32_skip_add`)
        self.embed = nnx.Embed(vocab_size, dim, embedding_init=trunc_normal_(std=0.02), dtype=jnp.float32,
                               param_dtype=param_dtype, rngs=rngs)
        self.blocks = nnx.List([EvaByteBlock(dim, attn_args, intermediate_size, rms_norm_eps, **kw)
                                for _ in range(num_hidden_layers)])
        self.norm = UnitOffsetRmsNorm(dim, rms_norm_eps, **kw)
        self.head = nnx.Linear(dim, self.num_classes, use_bias=False, kernel_init=trunc_normal_(std=0.02), **kw)

    # -- the model contract -------------------------------------------------------------
    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^embed', blocks=[(r'^blocks\.(\d+)', None), (r'^norm|^head', (99999,))])

    def set_grad_checkpointing(self, enable: bool = True):
        self.grad_checkpointing = enable

    def no_weight_decay(self):
        """The two learned vectors a head (the norms' g are vectors, which no optimizer here decays)."""
        return {f'blocks.{i}.attn.{name}' for i in range(len(self.blocks)) for name in ('phi', 'mu')}

    def get_classifier(self):
        return self.head

    def _rope(self, seq_len: int):
        return build_rotary_pos_embed_1d(seq_len, self.rope_dim, self.rope_theta)

    def _run_block(self, blk, x, rope):
        if not self.grad_checkpointing:
            return blk(x, rope)
        # as `Glm4MoeLite._run_block`: a block is recomputed in the backward pass, but for the core's output, and
        # here for the feed-forward block's two up-products, so the second pass multiplies nothing in the SwiGLU
        # (9 products a layer, not 11). The price is bfloat16 2 x S x intermediate a layer, held from the layer's
        # forward pass to its backward one: every layer's pair but the running one's stands on top of the peak
        policy = jax.checkpoint_policies.save_only_these_names(CORE_OUT, FFN_UP)
        return nnx.remat(lambda b, x, rope: b(x, rope), policy=policy)(blk, x, rope)

    def forward_features(self, ids, with_counters: bool = False):
        """ids (B, S) int -> the last block's output (B, S, dim) float32, before the final norm."""
        with tracing.scope('glm.embed'):
            x = self.embed(ids)
        rope = self._rope(ids.shape[1])
        counters = {}
        for blk in self.blocks:
            x, c = self._run_block(blk, x, rope)
            counters = merge_counters(counters, c)
        return (x, counters) if with_counters else x

    def forward_head(self, h, pre_logits: bool = False):
        """-> float32 logits (B, S, num_pred_heads * vocabulary), head-major: head p's are columns
        [p * vocabulary, (p + 1) * vocabulary)."""
        h = self.norm(h)
        if pre_logits:
            return h
        kernel = self.head.kernel[...]
        dtype = self.compute_dtype or kernel.dtype
        return jnp.einsum('...d,dv->...v', h.astype(dtype), kernel.astype(dtype), preferred_element_type=jnp.float32)

    def __call__(self, ids):
        return self.forward_head(self.forward_features(ids))


def _create(variant, pretrained=False, **kwargs):
    return build_model_with_cfg(EvaByte, variant, pretrained, **kwargs)


@register_model
def evabyte_6b5(pretrained=False, **kwargs) -> EvaByte:
    """EvaByte 6.5B as published: 32 layers of 32 heads, 6,488,330,240 parameters (no single chip trains it)."""
    return _create('evabyte_6b5', pretrained, **kwargs)


@register_model
def evabyte_6b5_hp2(pretrained=False, **kwargs) -> EvaByte:
    """One chip's share of EvaByte 6.5B where 2 chips share each layer by heads: heads 0-15 of 32 with their
    learned vectors, the feed-forward block, norms, embedding and head whole, 4 of 32 layers (the rest would be
    further pipeline stages): 687,132,672 parameters."""
    share = dict(num_hidden_layers=4, heads_held=16, head_offset=0)
    return _create('evabyte_6b5_hp2', pretrained, **dict(share, **kwargs))


@register_model
def evabyte_toy(pretrained=False, **kwargs) -> EvaByte:
    """The CPU tests' size: every mechanism of the published model (8 heads over the 320 ids among them),
    nothing of its widths."""
    toy = dict(hidden_size=64, intermediate_size=160, num_hidden_layers=2, num_attention_heads=4, head_dim=16,
               window_size=32, chunk_size=4, block_q=16)
    return _create('evabyte_toy', pretrained, **dict(toy, **kwargs))
