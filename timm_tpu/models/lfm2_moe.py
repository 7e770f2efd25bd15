"""LFM2-8B-A1B (LiquidAI `lfm2_moe`, 2025-10; 8.3B parameters, 1.5B active): a
decoder-only language model whose sequence mixer changes with the layer.
`layer_types[l]` is 'conv', a gated short convolution (`layers/short_conv.py`:
a product to three gates' worth of channels, a causal depthwise convolution of
3 taps between two elementwise gates, a product back; no softmax, no
positions), or 'full_attention', grouped-query attention (32 query heads on 8
key/value heads of width 64, an RMSNorm on every head's query and key before a
rotary turn): 18 and 6 of the published 24 layers. The feed-forward is a dense
SwiGLU in the first `num_dense_layers` layers and, after them, a sparse mixture
of 32 SwiGLU experts, 4 a token, chosen by sigmoid score + a bias buffer and
weighted by the chosen scores normalised (`layers/moe.py` 'sigmoid_bias', the
published code's epsilon 1e-6), no shared expert. Embedding and output head are
ONE leaf (`tie_word_embeddings`): read as a lookup and, transposed, as the head,
so its gradient is the sum of both uses.

Layer l: a = RMSNorm_1(x); x = x + Mixer_l(a); x = x + FF_l(RMSNorm_2(x)). The
plain reference is `benchmarks/reference/lfm2_moe.py`.

Like the other decoders the model can be built as ONE CHIP'S SHARE of a
deployment that divides each layer over several chips (`experts_held`,
`expert_offset`, `vocab_held`); both mixers, the dense layer, the norms and the
router are whole on every chip, and nothing stands in for the absent chips. The
model contract is the one `CausalLMTask` and `train.py` use (`task_kind`,
`forward_features(ids, with_counters)`, `forward_head`, `routes`); there is no
multi-token-prediction module (`mtp` is None).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import GroupedQueryAttention, RmsNorm, ShortConv, SparseMoe, SwiGLU, build_rotary_pos_embed_1d, trunc_normal_
from ..layers.latent_attention import CORE_OUT
from ..layers.moe import merge_counters
from ..utils import tracing
from ._builder import build_model_with_cfg
from ._registry import register_model

__all__ = ['Lfm2Moe']

KINDS = ('conv', 'full_attention')
# the published `layer_types`: conv, conv, then (attention, conv, conv, conv) four times, (attention, conv, conv) twice
PUBLISHED_LAYER_TYPES = tuple('full_attention' if l in (2, 6, 10, 14, 18, 21) else 'conv' for l in range(24))
TOPK_NORM_EPS = 1e-6    # the published modelling code's: chosen scores over (their sum + 1e-6)


class Lfm2Block(nnx.Module):
    """(x, rope) -> (x, counters); `kind` is this layer's mixer, `dense_hidden` makes the FFN a dense SwiGLU."""

    def __init__(self, dim, kind: str, attn_args: dict, moe_args: dict, dense_hidden: Optional[int], kernel_size: int,
                 eps: float, *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = RmsNorm(dim, eps=eps, **kw)
        self.conv = ShortConv(dim, kernel_size, **kw) if kind == 'conv' else None
        self.attn = None if kind == 'conv' else GroupedQueryAttention(dim, rotary=True, qk_norm=True, eps=eps, **attn_args, **kw)
        self.norm2 = RmsNorm(dim, eps=eps, **kw)
        self.mlp = SwiGLU(dim, dense_hidden, bias=False, **kw) if dense_hidden else SparseMoe(
            dim, n_shared=0, scoring='sigmoid_bias', activation='silu', norm_eps=TOPK_NORM_EPS, **moe_args, **kw)
        self.dense = bool(dense_hidden)

    def __call__(self, x, rope, routes: Optional[list] = None):
        """`routes`, a list, gets an expert layer's chosen ids appended (the comparison with the reference)."""
        if self.conv is not None:
            with tracing.scope('sconv.proj'):
                a = self.norm1(x)
            x = x + self.conv(a)
            rows = x.shape[0] * x.shape[1]
            counters = {'sconv.rows': tracing.device_counter('sconv.rows', jnp.int32(rows))}
        else:
            with tracing.scope('swa.attn.proj'):
                a = self.norm1(x)
            y, tiles = self.attn(a, rope)
            x = x + y
            blocks = jnp.int32(tiles * x.shape[0])      # the tiles of one sequence, every sequence alike
            counters = {'attn.full_blocks': tracing.device_counter('attn.full_blocks', blocks)}
        if self.dense:
            with tracing.scope('glm.dense_ffn'):
                return x + self.mlp(self.norm2(x)), counters
        e = self.norm2(x)
        if routes is not None:
            routes.append(self.mlp.choose(e))
        y, moe = self.mlp(e)
        return x + y, dict(counters, **moe)


class Lfm2Moe(nnx.Module):
    task_kind = 'causal_lm'
    mtp = None      # no multi-token-prediction module: `CausalLMTask` leaves its branch out

    def __init__(
            self,
            vocab_size: int = 65536,
            hidden_size: int = 2048,
            num_hidden_layers: int = 24,
            layer_types: Optional[Sequence[str]] = None,
            num_attention_heads: int = 32,
            num_key_value_heads: int = 8,
            conv_L_cache: int = 3,
            intermediate_size: int = 7168,
            num_dense_layers: int = 2,
            moe_intermediate_size: int = 1792,
            num_experts: int = 32,
            num_experts_per_tok: int = 4,
            routed_scaling_factor: float = 1.0,
            rope_theta: float = 1e6,
            norm_eps: float = 1e-5,
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
        self.layer_types = tuple(PUBLISHED_LAYER_TYPES if layer_types is None else layer_types)
        if len(self.layer_types) != num_hidden_layers or not set(self.layer_types) <= set(KINDS):
            raise ValueError(f'layer_types names {len(self.layer_types)} layers of kinds {sorted(set(self.layer_types))}: '
                             f'the model has {num_hidden_layers} of {KINDS}')
        if dim % num_attention_heads:
            raise ValueError(f'{num_attention_heads} heads do not divide the hidden size {dim}')
        head_dim = dim // num_attention_heads           # the published config has no key of its own for it
        self.vocab_size = vocab_size
        self.vocab_held = self.num_classes = vocab_held or vocab_size
        self.num_features = self.head_hidden_size = self.embed_dim = dim
        self.rope_dim, self.rope_theta = head_dim, rope_theta
        self.experts_held, self.expert_offset = experts_held or num_experts, expert_offset
        self.grad_checkpointing = False
        attn_args = dict(num_heads=num_attention_heads, num_kv_heads=num_key_value_heads, head_dim=head_dim, block_q=block_q)
        moe_args = dict(hidden=moe_intermediate_size, num_experts=num_experts, top_k=num_experts_per_tok,
                        experts_held=experts_held, expert_offset=expert_offset, routed_scaling_factor=routed_scaling_factor)
        # ONE leaf for the lookup and the head (`forward_head` reads it transposed): no `head` leaf exists
        self.embed = nnx.Embed(self.vocab_held, dim, embedding_init=trunc_normal_(std=0.02), **kw)
        self.blocks = nnx.List([
            Lfm2Block(dim, kind, attn_args, moe_args, intermediate_size if l < num_dense_layers else None, conv_L_cache,
                      norm_eps, **kw) for l, kind in enumerate(self.layer_types)])
        self.norm = RmsNorm(dim, eps=norm_eps, **kw)

    # -- the model contract -------------------------------------------------------------
    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^embed', blocks=[(r'^blocks\.(\d+)', None), (r'^norm', (99999,))])

    def set_grad_checkpointing(self, enable: bool = True):
        self.grad_checkpointing = enable

    def no_weight_decay(self):
        """Nothing by name. Norm scales (the q/k norms' among them) are vectors, which the optimizer's rule leaves
        undecayed by their rank; a layer's taps (dim, 3) are a depthwise convolution's weight and are decayed like
        every matrix, as the image models' depthwise kernels are (the reference's mask says the same: rank > 1)."""
        return set()

    def get_classifier(self):
        return self.embed       # the tied head: the embedding's own rows

    def _rope(self, seq_len: int):
        return build_rotary_pos_embed_1d(seq_len, self.rope_dim, self.rope_theta)

    def _run_block(self, blk, x, rope):
        if not self.grad_checkpointing:
            return blk(x, rope)
        # as `Glm4MoeLite._run_block`: a block is recomputed in the backward pass, but for an attention core's
        # output and log-sum-exp (a conv block keeps nothing: its middle is recomputed from the product's output)
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
        return h if pre_logits else self.embed.attend(h)

    def __call__(self, ids):
        return self.forward_head(self.forward_features(ids))

    def routes(self, ids, next_ids=None):
        """Chosen expert ids (expert layers, B, S, top_k) of a forward pass; no gradient, no remat."""
        x, rope, chosen = self.embed(ids), self._rope(ids.shape[1]), []
        for blk in self.blocks:
            x, _ = blk(x, rope, chosen)
        return jnp.stack(chosen)


def _create(variant, pretrained=False, **kwargs):
    return build_model_with_cfg(Lfm2Moe, variant, pretrained, **kwargs)


@register_model
def lfm2_8b_a1b(pretrained=False, **kwargs) -> Lfm2Moe:
    """LFM2-8B-A1B as published: 24 layers (18 conv, 6 attention), 32 experts, vocabulary 65536, a tied head
    (8,339,929,856 parameters; no single chip trains it)."""
    return _create('lfm2_8b_a1b', pretrained, **kwargs)


@register_model
def lfm2_8b_a1b_ep4(pretrained=False, **kwargs) -> Lfm2Moe:
    """One chip's share of LFM2-8B-A1B where 4 chips (one host) share each layer: experts 0-7 of 32, 16384 of 65536
    vocabulary rows, published layers 1-5: one leading dense layer (conv) and one whole period after the dense
    ones (attention, conv, conv, conv); the rest would be further pipeline stages. 507,820,160 parameters."""
    share = dict(num_hidden_layers=5, layer_types=PUBLISHED_LAYER_TYPES[1:6], num_dense_layers=1, experts_held=8,
                 expert_offset=0, vocab_held=16384)
    return _create('lfm2_8b_a1b_ep4', pretrained, **dict(share, **kwargs))


@register_model
def lfm2_moe_toy(pretrained=False, **kwargs) -> Lfm2Moe:
    """The CPU tests' size: the share's five layers (dense conv; attention, conv, conv, conv on experts), every
    mechanism of the published model, nothing of its widths."""
    toy = dict(vocab_size=256, hidden_size=64, num_hidden_layers=5, layer_types=PUBLISHED_LAYER_TYPES[1:6],
               num_attention_heads=4, num_key_value_heads=2, intermediate_size=160, num_dense_layers=1,
               moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, experts_held=2, block_q=8)
    return _create('lfm2_moe_toy', pretrained, **dict(toy, **kwargs))
