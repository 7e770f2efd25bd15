"""Solar-Open2-250B (upstage `solar_open2`, 2026-07; 250B parameters, 15B active):
a decoder-only language model whose sequence mixer changes with the layer. Layer
l in the published `gqa_layers` (0, 4, 8, ..: one of four) is a softmax attention
of 64 query heads on 8 key/value heads of width 128, WITHOUT positions
(`use_rope` false) and with an output gate (`use_gqa_gate`:
`GroupedQueryAttention(rotary=False, gate=True)`); every other layer is a gated
delta-rule linear attention with a per-channel decay (KDA,
`layers/delta_attention.py`: 64 heads of 128, 4 taps, a carried 128 x 128 state a
head, eigenvalues in (-1, 1)). Every feed-forward is a sparse mixture of 320
SwiGLU experts of width 1280, 8 a token, chosen by sigmoid score + a bias buffer
and weighted by the chosen scores normalised, plus one shared expert
(`layers/moe.py` 'sigmoid_bias', as GLM-4.7-Flash configures it);
`intermediate_size` 10240 names a dense width no layer uses
(`first_k_dense_replace` 0). Embedding and head are untied.

Layer l: a = RMSNorm_1(x); x = x + Mixer_l(a); x = x + MoE(RMSNorm_2(x)). The
plain reference is `benchmarks/reference/solar_open2.py`.

Parameters by shapes, as published (d = 4096, H D = 8192, rank 128, vocabulary 196608):
  a KDA layer        3 x d x 8192 (q, k, v) + 3 x 8192 x 4 (taps) + 2 x (d x 128 + 128 x 8192) (the two low-rank gates)
                     + d x 64 (beta) + 64 (A_log) + 8192 (dt_bias) + 128 (the head norm) + 8192 x d = 137,732,288
  an attention layer d x 8192 (q) + 2 x d x 1024 (k, v) + d x 8192 (gate) + 8192 x d = 109,051,904
  an expert layer    d x 320 (router) + 321 x 3 x d x 1280 (320 experts and the shared one) = 5,050,204,160
  a layer's norms    2 x d = 8,192
  36 KDA + 12 attention + 48 expert layers + 48 x 8,192 + 2 x 196608 x d + d (the final norm) = 250,287,794,944.

Like the other decoders the model can be built as ONE CHIP'S SHARE of a
deployment that divides each layer over several chips (`experts_held`,
`expert_offset`, `heads_held`, `head_offset` for both mixers, `vocab_held`); the
norms, the router, the shared expert and the gates' low-rank down-products are
whole on every chip, and nothing stands in for the absent chips. The model
contract is the one `CausalLMTask` and `train.py` use (`task_kind`,
`forward_features(ids, with_counters)`, `forward_head`, `routes`); there is no
multi-token-prediction module (`mtp` is None).
"""
from __future__ import annotations

from typing import Optional, Sequence

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import GroupedQueryAttention, KimiDeltaAttention, RmsNorm, SparseMoe, trunc_normal_
from ..layers.latent_attention import CORE_OUT
from ..layers.moe import merge_counters
from ..utils import tracing
from ._builder import build_model_with_cfg
from ._registry import register_model

__all__ = ['SolarOpen2']

PUBLISHED_GQA_LAYERS = tuple(range(0, 48, 4))


class SolarOpen2Block(nnx.Module):
    """x -> (x, counters); `attention` makes this layer's mixer the gated softmax attention, else KDA."""

    def __init__(self, dim, attention: bool, attn_args: dict, kda_args: dict, moe_args: dict, eps: float, *,
                 dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = RmsNorm(dim, eps=eps, **kw)
        self.attn = GroupedQueryAttention(dim, rotary=False, gate=True, **attn_args, **kw) if attention else None
        self.kda = None if attention else KimiDeltaAttention(dim, eps=eps, **kda_args, **kw)
        self.norm2 = RmsNorm(dim, eps=eps, **kw)
        self.mlp = SparseMoe(dim, scoring='sigmoid_bias', activation='silu', **moe_args, **kw)

    def __call__(self, x, routes: Optional[list] = None):
        """`routes`, a list, gets the expert layer's chosen ids appended (the comparison with the reference)."""
        B, S, _ = x.shape
        if self.kda is not None:
            with tracing.scope('kda.proj'):
                a = self.norm1(x)
            x = x + self.kda(a)
            chunks = B * self.kda.heads_held * (S // min(self.kda.chunk, S))
            counters = {'kda.rows': tracing.device_counter('kda.rows', jnp.int32(B * S)),
                        'kda.chunks': tracing.device_counter('kda.chunks', jnp.int32(chunks))}
        else:
            with tracing.scope('swa.attn.proj'):
                a = self.norm1(x)
            y, tiles = self.attn(a)
            x = x + y
            counters = {'attn.full_blocks': tracing.device_counter('attn.full_blocks', jnp.int32(tiles * B))}
        e = self.norm2(x)
        if routes is not None:
            routes.append(self.mlp.choose(e))
        y, moe = self.mlp(e)
        return x + y, dict(counters, **moe)


class SolarOpen2(nnx.Module):
    task_kind = 'causal_lm'
    mtp = None      # no multi-token-prediction module: `CausalLMTask` leaves its branch out

    def __init__(
            self,
            vocab_size: int = 196608,
            hidden_size: int = 4096,
            num_hidden_layers: int = 48,
            gqa_layers: Optional[Sequence[int]] = None,
            num_attention_heads: int = 64,
            num_key_value_heads: int = 8,
            head_dim: int = 128,
            kda_num_heads: int = 64,
            kda_head_dim: int = 128,
            short_conv_kernel_size: int = 4,
            gate_rank: Optional[int] = None,
            kda_chunk: int = 64,
            moe_intermediate_size: int = 1280,
            n_routed_experts: int = 320,
            num_experts_per_tok: int = 8,
            n_shared_experts: int = 1,
            routed_scaling_factor: float = 1.0,
            rms_norm_eps: float = 1e-5,
            experts_held: Optional[int] = None,
            expert_offset: int = 0,
            heads_held: Optional[int] = None,
            head_offset: int = 0,
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
        self.gqa_layers = tuple(PUBLISHED_GQA_LAYERS if gqa_layers is None else gqa_layers)
        if heads_held is not None and num_attention_heads != kda_num_heads:
            raise ValueError(f'one share of heads for {num_attention_heads} attention heads and {kda_num_heads} KDA heads')
        self.vocab_size = vocab_size
        self.vocab_held = self.num_classes = vocab_held or vocab_size
        self.num_features = self.head_hidden_size = self.embed_dim = dim
        self.experts_held, self.expert_offset = experts_held or n_routed_experts, expert_offset
        self.heads_held, self.head_offset = heads_held or num_attention_heads, head_offset
        self.grad_checkpointing = False
        share = dict(heads_held=heads_held, head_offset=head_offset)
        attn_args = dict(num_heads=num_attention_heads, num_kv_heads=num_key_value_heads, head_dim=head_dim, block_q=block_q, **share)
        kda_args = dict(num_heads=kda_num_heads, head_dim=kda_head_dim, conv_size=short_conv_kernel_size, gate_rank=gate_rank,
                        chunk=kda_chunk, **share)
        moe_args = dict(hidden=moe_intermediate_size, num_experts=n_routed_experts, top_k=num_experts_per_tok,
                        experts_held=experts_held, expert_offset=expert_offset, n_shared=n_shared_experts,
                        routed_scaling_factor=routed_scaling_factor)
        self.embed = nnx.Embed(self.vocab_held, dim, embedding_init=trunc_normal_(std=0.02), **kw)
        self.blocks = nnx.List([SolarOpen2Block(dim, l in self.gqa_layers, attn_args, kda_args, moe_args, rms_norm_eps, **kw)
                                for l in range(num_hidden_layers)])
        self.norm = RmsNorm(dim, eps=rms_norm_eps, **kw)
        self.head = nnx.Linear(dim, self.vocab_held, use_bias=False, kernel_init=trunc_normal_(std=0.02), **kw)

    # -- the model contract -------------------------------------------------------------
    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^embed', blocks=[(r'^blocks\.(\d+)', None), (r'^norm', (99999,))])

    def set_grad_checkpointing(self, enable: bool = True):
        self.grad_checkpointing = enable

    def no_weight_decay(self):
        """Nothing by name: norm scales, `A_log` and `dt_bias` are vectors, which the optimizer's rule leaves undecayed
        by their rank; the (channels, 4) taps are a depthwise convolution's weight and are decayed like every matrix."""
        return set()

    def get_classifier(self):
        return self.head

    def _run_block(self, blk, x):
        if not self.grad_checkpointing:
            return blk(x)
        # as `Glm4MoeLite._run_block`: a block is recomputed in the backward pass, but for an attention core's output
        # and log-sum-exp; a KDA block keeps its core's output and chunk-boundary states (`layers/delta_attention.py`
        # names them `CORE_OUT` too: 67 + 17 MB a layer at the cell's size), so the scan over chunks is not run again
        policy = jax.checkpoint_policies.save_only_these_names(CORE_OUT)
        return nnx.remat(lambda b, x: b(x), policy=policy)(blk, x)

    def forward_features(self, ids, with_counters: bool = False):
        """ids (B, S) int -> the last block's output (B, S, dim), before the final norm."""
        with tracing.scope('glm.embed'):
            x = self.embed(ids)
        counters = {}
        for blk in self.blocks:
            x, c = self._run_block(blk, x)
            counters = merge_counters(counters, c)
        return (x, counters) if with_counters else x

    def forward_head(self, h, pre_logits: bool = False):
        h = self.norm(h)
        return h if pre_logits else self.head(h)

    def __call__(self, ids):
        return self.forward_head(self.forward_features(ids))

    def routes(self, ids, next_ids=None):
        """Chosen expert ids (layers, B, S, top_k) of a forward pass; no gradient, no remat."""
        x, chosen = self.embed(ids), []
        for blk in self.blocks:
            x, _ = blk(x, chosen)
        return jnp.stack(chosen)


def _create(variant, pretrained=False, **kwargs):
    return build_model_with_cfg(SolarOpen2, variant, pretrained, **kwargs)


@register_model
def solar_open2_250b(pretrained=False, **kwargs) -> SolarOpen2:
    """Solar-Open2-250B as published: 48 layers (36 KDA, 12 gated attention), 320 experts and a shared one, vocabulary
    196608, an untied head (250,287,794,944 parameters; no single chip trains it)."""
    return _create('solar_open2_250b', pretrained, **kwargs)


@register_model
def solar_open2_250b_ep40(pretrained=False, **kwargs) -> SolarOpen2:
    """One chip's share of Solar-Open2-250B where 40 chips share each layer: experts 0-7 of 320, heads 0-7 of 64 of
    either mixer (key/value head 0 of 8), 24576 of 196608 vocabulary rows, published layers 0-3: one whole period
    (attention, KDA, KDA, KDA); the rest would be further pipeline stages. 840,871,320 parameters."""
    share = dict(num_hidden_layers=4, gqa_layers=(0,), experts_held=8, expert_offset=0, heads_held=8, head_offset=0,
                 vocab_held=24576)
    return _create('solar_open2_250b_ep40', pretrained, **dict(share, **kwargs))


@register_model
def solar_open2_toy(pretrained=False, **kwargs) -> SolarOpen2:
    """The CPU tests' size: the share's four layers (attention, KDA, KDA, KDA, all on experts), every mechanism of the
    published model, nothing of its widths."""
    toy = dict(vocab_size=256, hidden_size=64, num_hidden_layers=4, gqa_layers=(0,), num_attention_heads=8,
               num_key_value_heads=4, head_dim=16, kda_num_heads=8, kda_head_dim=16, gate_rank=8, kda_chunk=16,
               moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2, experts_held=2, heads_held=4,
               block_q=8)
    return _create('solar_open2_toy', pretrained, **dict(toy, **kwargs))
