"""SDAR-30B-A3B-Chat (JetLM `sdar_moe`; SDAR, "Synergistic Diffusion-
AutoRegression", JetAstra/SDAR 2025): a Qwen3-MoE trunk trained as a BLOCK-
DIFFUSION language model (BD3-LM arXiv:2503.09573, with LLaDA's forward process
arXiv:2502.09992). Every layer is alike: grouped-query attention (32 query heads
on 4 key/value heads of width 128, an RMSNorm on every head's query and key
before the rotary turn) and a sparse mixture of 128 SwiGLU experts, 8 a token,
weighted by a softmax over all 128 renormalised over the 8 chosen (= a softmax
over the chosen logits), no shared expert, no dense layer.

A training step sees each sequence twice: the forward pass takes the NOISED ids
(some positions replaced by the mask token) and the CLEAN ids, runs both as one
tensor of 2 L rows, noised rows first, row r at position r mod L, under the
block-diffusion mask (`kernels.causal_attention.block_diffusion_seen`: a noised
row sees the noised rows of its own block of `block_length` and the clean rows
of earlier blocks; a clean row sees the clean rows of its own and earlier
blocks), and returns the L noised rows: the loss reads a masked position's own
logits (`task/block_diffusion_lm.py`). The last layer therefore needs of its
clean rows only RMSNorm_1, K and V: its queries, core, output projection and
experts run on the noised rows alone.

Layer: a = RMSNorm_1(x); x = x + Attn(a); e = RMSNorm_2(x); x = x + Experts(e),
routed on e. The layer equations are in `layers/grouped_attention.py` and
`layers/moe.py`; the plain reference is `benchmarks/reference/sdar_moe.py`.

Like the other two decoders the model can be built as ONE CHIP'S SHARE of a
deployment that divides each layer over several chips (`experts_held`,
`expert_offset`, `vocab_held`); attention and the router are whole on every
chip, and nothing stands in for the absent chips. The model also carries the
state of the step's noise (`noise_key`, `noise_count`: the task folds the count
into the key, draws, and counts on; both ride in the step's non-parameter
state), seeded from the model's seed.
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import GroupedQueryAttention, RmsNorm, SparseMoe, build_rotary_pos_embed_1d, trunc_normal_
from ..layers.latent_attention import CORE_OUT
from ..layers.moe import merge_counters
from ..utils import tracing
from ._builder import build_model_with_cfg
from ._registry import register_model

__all__ = ['SdarMoe', 'NoiseState']

PUBLISHED_MASK_TOKEN_ID = 151669


class NoiseState(nnx.Variable):
    """The noise stream's key data and its count of draws: no parameter, carried through the step."""


class SdarMoeBlock(nnx.Module):
    """(x (B, 2 L, dim), rope) -> (x, counters); with `queries=L` the L noised rows alone come back."""

    def __init__(self, dim, attn_args: dict, moe_args: dict, eps: float, *, dtype=None, param_dtype=jnp.float32,
                 rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = RmsNorm(dim, eps=eps, **kw)
        self.attn = GroupedQueryAttention(dim, rotary=True, qk_norm=True, eps=eps, **attn_args, **kw)
        self.norm2 = RmsNorm(dim, eps=eps, **kw)
        self.mlp = SparseMoe(dim, n_shared=0, scoring='softmax_topk', activation='silu', **moe_args, **kw)

    def __call__(self, x, rope, routes: Optional[list] = None, queries: Optional[int] = None):
        """`routes`, a list, gets the layer's chosen expert ids appended (the comparison with the reference)."""
        with tracing.scope('swa.attn.proj'):
            a = self.norm1(x)
        y, tiles = self.attn(a, rope, queries)
        x = (x if queries is None else x[:, :queries]) + y
        e = self.norm2(x)
        if routes is not None:
            routes.append(self.mlp.choose(e))
        y, counters = self.mlp(e)
        blocks = tracing.device_counter('attn.bd_blocks', jnp.int32(tiles * x.shape[0]))   # every sequence alike
        return x + y, dict(counters, **{'attn.bd_blocks': blocks})


class SdarMoe(nnx.Module):
    task_kind = 'block_diffusion_lm'

    def __init__(
            self,
            vocab_size: int = 151936,
            hidden_size: int = 2048,
            num_hidden_layers: int = 48,
            num_attention_heads: int = 32,
            num_key_value_heads: int = 4,
            head_dim: int = 128,
            moe_intermediate_size: int = 768,
            num_experts: int = 128,
            num_experts_per_tok: int = 8,
            rope_theta: float = 1e6,
            rms_norm_eps: float = 1e-6,
            block_length: int = 4,
            mask_token_id: Optional[int] = None,
            noise_eps: float = 1e-3,
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
        self.vocab_size = vocab_size
        self.vocab_held = self.num_classes = vocab_held or vocab_size
        self.num_features = self.head_hidden_size = self.embed_dim = dim
        self.rope_dim, self.rope_theta = head_dim, rope_theta
        self.block_length, self.noise_eps = block_length, noise_eps
        # the published mask token where the rows held reach it, else the last row held (the traffic keeps off it)
        published = PUBLISHED_MASK_TOKEN_ID if PUBLISHED_MASK_TOKEN_ID < self.vocab_held else self.vocab_held - 1
        self.mask_token_id = published if mask_token_id is None else mask_token_id
        if not 0 <= self.mask_token_id < self.vocab_held:
            raise ValueError(f'the mask token {self.mask_token_id} is not among the {self.vocab_held} rows held')
        self.grad_checkpointing = False
        attn_args = dict(num_heads=num_attention_heads, num_kv_heads=num_key_value_heads, head_dim=head_dim,
                         block_q=block_q, block_diffusion=block_length)
        moe_args = dict(hidden=moe_intermediate_size, num_experts=num_experts, top_k=num_experts_per_tok,
                        experts_held=experts_held, expert_offset=expert_offset)
        self.embed = nnx.Embed(self.vocab_held, dim, embedding_init=trunc_normal_(std=0.02), **kw)
        self.blocks = nnx.List([SdarMoeBlock(dim, attn_args, moe_args, rms_norm_eps, **kw) for _ in range(num_hidden_layers)])
        self.norm = RmsNorm(dim, eps=rms_norm_eps, **kw)
        self.head = nnx.Linear(dim, self.vocab_held, use_bias=False, kernel_init=trunc_normal_(std=0.02), **kw)
        self.noise_key = NoiseState(jax.random.key_data(rngs.params()))
        self.noise_count = NoiseState(jnp.zeros((), jnp.uint32))

    # -- the model contract -------------------------------------------------------------
    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^embed', blocks=[(r'^blocks\.(\d+)', None), (r'^norm|^head', (99999,))])

    def set_grad_checkpointing(self, enable: bool = True):
        self.grad_checkpointing = enable

    def no_weight_decay(self):
        return set()

    def get_classifier(self):
        return self.head

    def _run_block(self, blk, x, rope, queries):
        if not self.grad_checkpointing:
            return blk(x, rope, None, queries)
        # as the other decoders' `_run_block`: a block is recomputed in the backward pass, but for the core's output
        policy = jax.checkpoint_policies.save_only_these_names(CORE_OUT)
        return nnx.remat(lambda b, x, rope: b(x, rope, None, queries), policy=policy)(blk, x, rope)

    def _inputs(self, noised, clean):
        if noised.shape != clean.shape:
            raise ValueError(f'noised ids {noised.shape} and clean ids {clean.shape} are not one sequence twice')
        with tracing.scope('glm.embed'):
            x = self.embed(jnp.concatenate([noised, clean], axis=1))        # 2 L rows, the noised ones first
        return x, build_rotary_pos_embed_1d(noised.shape[1], self.rope_dim, self.rope_theta)

    def forward_features(self, noised, clean, with_counters: bool = False):
        """noised, clean ids (B, L) int -> the last block's output at the L noised rows (B, L, dim), before the
        final norm."""
        x, rope = self._inputs(noised, clean)
        counters = {}
        for i, blk in enumerate(self.blocks):
            x, c = self._run_block(blk, x, rope, noised.shape[1] if i == len(self.blocks) - 1 else None)
            counters = merge_counters(counters, c)
        return (x, counters) if with_counters else x

    def forward_head(self, h, pre_logits: bool = False):
        h = self.norm(h)
        return h if pre_logits else self.head(h)

    def __call__(self, noised, clean):
        return self.forward_head(self.forward_features(noised, clean))

    def routes(self, noised, clean):
        """Chosen expert ids (layers, B, 2 L, top_k) of a forward pass over both halves, the last layer's clean
        rows too (which a training step does not route); no gradient, no remat."""
        (x, rope), chosen = self._inputs(noised, clean), []
        for blk in self.blocks:
            x, _ = blk(x, rope, chosen)
        return jnp.stack(chosen)


def _create(variant, pretrained=False, **kwargs):
    return build_model_with_cfg(SdarMoe, variant, pretrained, **kwargs)


@register_model
def sdar_30b_a3b(pretrained=False, **kwargs) -> SdarMoe:
    """SDAR-30B-A3B-Chat as published: 48 layers, 128 experts, vocabulary 151936 (30B; no single chip holds it)."""
    return _create('sdar_30b_a3b', pretrained, **kwargs)


@register_model
def sdar_30b_a3b_ep8(pretrained=False, **kwargs) -> SdarMoe:
    """One chip's share of SDAR-30B-A3B-Chat where 8 chips share each layer: experts 0-15 of 128, 18992 of
    151936 vocabulary rows (the mask token is the last row held), 6 of the 48 layers (the rest would be
    further pipeline stages)."""
    share = dict(num_hidden_layers=6, experts_held=16, expert_offset=0, vocab_held=18992)
    return _create('sdar_30b_a3b_ep8', pretrained, **dict(share, **kwargs))


@register_model
def sdar_moe_toy(pretrained=False, **kwargs) -> SdarMoe:
    """The CPU tests' size: every mechanism of the published model, nothing of its widths."""
    toy = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, num_key_value_heads=2,
               head_dim=16, moe_intermediate_size=32, num_experts=8, num_experts_per_tok=2, experts_held=2,
               block_length=4, block_q=8)
    return _create('sdar_moe_toy', pretrained, **dict(toy, **kwargs))
