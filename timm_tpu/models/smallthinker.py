"""SmallThinker-21BA3B-Instruct (PowerInfer, arXiv:2507.20984): a decoder-only
language model whose layer kind changes with the layer index. Every layer is
grouped-query attention (28 query heads on 4 key/value heads) and a sparse
mixture of 64 ReGLU experts, 6 a token, no shared expert. `rope_layout[l]` says
whether layer l turns its queries and keys by the rotary table,
`sliding_window_layout[l]` whether it sees only the last `sliding_window_size`
positions: in the published model the same three layers of four do both, and
the fourth sees everything and carries no position signal. The router reads the
attention's normalised input, not the expert layer's, and weights the experts
by a softmax over the chosen logits.

Layer l: a = RMSNorm_1(x); routing from a; x = x + Attn_l(a); x = x +
Experts(RMSNorm_2(x), routed on a). The layer equations are in
`layers/grouped_attention.py` and `layers/moe.py`; the plain reference is
`benchmarks/reference/smallthinker.py`.

Like `models/glm4_moe_lite.py` the model can be built as ONE CHIP'S SHARE of a
deployment that divides each layer over several chips (`experts_held`,
`expert_offset`, `vocab_held`); attention and the router are whole on every
chip, and nothing stands in for the absent chips. The model contract is the one
`CausalLMTask` and `train.py` use (`task_kind`, `forward_features(ids,
with_counters)`, `forward_head`, `routes`); there is no multi-token-prediction
module (`mtp` is None).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import GroupedQueryAttention, RmsNorm, SparseMoe, build_rotary_pos_embed_1d, trunc_normal_
from ..layers.latent_attention import CORE_OUT
from ..layers.moe import merge_counters
from ..utils import tracing
from ._builder import build_model_with_cfg
from ._registry import register_model

__all__ = ['SmallThinker']

PERIOD = (0, 1, 1, 1)   # the published layouts: layer l turns and is windowed unless l mod 4 == 0


class SmallThinkerBlock(nnx.Module):
    """(x, rope) -> (x, counters); `rotary` and `window` are this layer's kind."""

    def __init__(self, dim, attn_args: dict, moe_args: dict, rotary: bool, window: Optional[int], eps: float, *,
                 dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = RmsNorm(dim, eps=eps, **kw)
        self.attn = GroupedQueryAttention(dim, rotary=rotary, window=window, **attn_args, **kw)
        self.norm2 = RmsNorm(dim, eps=eps, **kw)
        self.mlp = SparseMoe(dim, n_shared=0, scoring='softmax_topk', activation='relu', **moe_args, **kw)

    def __call__(self, x, rope, routes: Optional[list] = None):
        """`routes`, a list, gets the layer's chosen expert ids appended (the comparison with the reference)."""
        with tracing.scope('swa.attn.proj'):
            a = self.norm1(x)
        if routes is not None:
            routes.append(self.mlp.choose(a))
        y, tiles = self.attn(a, rope)
        x = x + y
        y, counters = self.mlp(self.norm2(x), router_in=a)
        blocks = jnp.int32(tiles * x.shape[0])      # the tiles of one sequence, every sequence alike
        windowed = self.attn.window is not None
        counters = dict(counters, **{
            'attn.full_blocks': tracing.device_counter('attn.full_blocks', jnp.int32(0) if windowed else blocks),
            'attn.window_blocks': tracing.device_counter('attn.window_blocks', blocks if windowed else jnp.int32(0))})
        return x + y, counters


class SmallThinker(nnx.Module):
    task_kind = 'causal_lm'
    mtp = None      # no multi-token-prediction module: `CausalLMTask` leaves its branch out

    def __init__(
            self,
            vocab_size: int = 151936,
            hidden_size: int = 2560,
            num_hidden_layers: int = 52,
            num_attention_heads: int = 28,
            num_key_value_heads: int = 4,
            head_dim: int = 128,
            moe_ffn_hidden_size: int = 768,
            moe_num_primary_experts: int = 64,
            moe_num_active_primary_experts: int = 6,
            rope_layout: Optional[Sequence[int]] = None,
            sliding_window_layout: Optional[Sequence[int]] = None,
            sliding_window_size: int = 4096,
            rope_theta: float = 1.5e6,
            rms_norm_eps: float = 1e-6,
            experts_held: Optional[int] = None,
            expert_offset: int = 0,
            vocab_held: Optional[int] = None,
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
        layout = lambda given: tuple(given) if given is not None else tuple(  # noqa: E731
            PERIOD[i % len(PERIOD)] for i in range(num_hidden_layers))
        self.rope_layout, self.window_layout = layout(rope_layout), layout(sliding_window_layout)
        if not len(self.rope_layout) == len(self.window_layout) == num_hidden_layers:
            raise ValueError(f'the layouts name {len(self.rope_layout)} and {len(self.window_layout)} layers, '
                             f'the model has {num_hidden_layers}')
        self.vocab_size = vocab_size
        self.vocab_held = self.num_classes = vocab_held or vocab_size
        self.num_features = self.head_hidden_size = self.embed_dim = dim
        self.rope_dim, self.rope_theta = head_dim, rope_theta
        self.grad_checkpointing = False
        attn_args = dict(num_heads=num_attention_heads, num_kv_heads=num_key_value_heads, head_dim=head_dim,
                         block_q=block_q)
        moe_args = dict(hidden=moe_ffn_hidden_size, num_experts=moe_num_primary_experts,
                        top_k=moe_num_active_primary_experts, experts_held=experts_held, expert_offset=expert_offset)
        self.embed = nnx.Embed(self.vocab_held, dim, embedding_init=trunc_normal_(std=0.02), **kw)
        self.blocks = nnx.List([
            SmallThinkerBlock(dim, attn_args, moe_args, rotary=bool(turn), window=sliding_window_size if windowed else None,
                              eps=rms_norm_eps, **kw)
            for turn, windowed in zip(self.rope_layout, self.window_layout)])
        self.norm = RmsNorm(dim, eps=rms_norm_eps, **kw)
        self.head = nnx.Linear(dim, self.vocab_held, use_bias=False, kernel_init=trunc_normal_(std=0.02), **kw)

    # -- the model contract -------------------------------------------------------------
    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^embed', blocks=[(r'^blocks\.(\d+)', None), (r'^norm|^head', (99999,))])

    def set_grad_checkpointing(self, enable: bool = True):
        self.grad_checkpointing = enable

    def no_weight_decay(self):
        return set()

    def get_classifier(self):
        return self.head

    def _rope(self, seq_len: int):
        return build_rotary_pos_embed_1d(seq_len, self.rope_dim, self.rope_theta)

    def _run_block(self, blk, x, rope):
        if not self.grad_checkpointing:
            return blk(x, rope)
        # as `Glm4MoeLite._run_block`: a block is recomputed in the backward pass, but for the core's output
        policy = jax.checkpoint_policies.save_only_these_names(CORE_OUT)
        return nnx.remat(lambda b, x, rope: b(x, rope), policy=policy)(blk, x, rope)

    def forward_features(self, ids, with_counters: bool = False):
        """ids (B, S) int -> the last block's output (B, S, dim), before the final norm."""
        with tracing.scope('glm.embed'):
            x = self.embed(ids)
        rope = self._rope(ids.shape[1])
        counters = {}
        for blk in self.blocks:
            x, c = self._run_block(blk, x, rope)
            counters = merge_counters(counters, c)
        return (x, counters) if with_counters else x

    def forward_head(self, h, pre_logits: bool = False):
        h = self.norm(h)
        return h if pre_logits else self.head(h)

    def __call__(self, ids):
        return self.forward_head(self.forward_features(ids))

    def routes(self, ids, next_ids=None):
        """Chosen expert ids (layers, B, S, top_k) of a forward pass; no gradient, no remat."""
        x, rope, chosen = self.embed(ids), self._rope(ids.shape[1]), []
        for blk in self.blocks:
            x, _ = blk(x, rope, chosen)
        return jnp.stack(chosen)


def _create(variant, pretrained=False, **kwargs):
    return build_model_with_cfg(SmallThinker, variant, pretrained, **kwargs)


@register_model
def smallthinker_21b(pretrained=False, **kwargs) -> SmallThinker:
    """SmallThinker-21BA3B-Instruct as published: 52 layers, 64 experts, vocabulary 151936 (21B; no single chip
    holds it)."""
    return _create('smallthinker_21b', pretrained, **kwargs)


@register_model
def smallthinker_21b_ep8(pretrained=False, **kwargs) -> SmallThinker:
    """One chip's share of SmallThinker-21BA3B where 8 chips share each layer: experts 0-7 of 64, 18992 of
    151936 vocabulary rows, two periods of the layer pattern (full, window, window, window, twice; the rest
    would be further pipeline stages)."""
    share = dict(num_hidden_layers=8, experts_held=8, expert_offset=0, vocab_held=18992)
    return _create('smallthinker_21b_ep8', pretrained, **dict(share, **kwargs))


@register_model
def smallthinker_toy(pretrained=False, **kwargs) -> SmallThinker:
    """The CPU tests' size: one period of the layer pattern, every mechanism of the published model, nothing
    of its widths."""
    toy = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_ffn_hidden_size=32, moe_num_primary_experts=8, moe_num_active_primary_experts=2,
               experts_held=2, sliding_window_size=8, block_q=8)
    return _create('smallthinker_toy', pretrained, **dict(toy, **kwargs))
