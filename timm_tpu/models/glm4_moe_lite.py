"""GLM-4.7-Flash (`glm4_moe_lite`, zai-org, 30B-A3B): a decoder-only language
model of multi-head latent attention blocks, one leading dense SwiGLU layer,
then sparse mixture-of-experts layers (sigmoid-routed, one shared expert), and
a multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437 section 2.2).

Block: x = x + MLA(RMSNorm(x)); x = x + FFN(RMSNorm(x)). The layer equations
are in `layers/latent_attention.py` and `layers/moe.py`; the plain reference is
`benchmarks/reference/glm4_moe_lite.py`.

The model can be built as ONE CHIP'S SHARE of a deployment that divides each
layer over several chips: `experts_held` / `expert_offset` say which routed
experts live here (the router still scores all of them), `vocab_held` how many
rows of the embedding and the output head (ids, logits and the loss are then
over that slice). Attention, the dense layer, the shared expert and the router
are whole on every chip. Nothing stands in for the absent chips.

The uniform model contract, read for tokens: `forward_features(ids)` gives the
last block's output before the final norm, `forward_head` the logits,
`forward_mtp(h, next_ids)` the MTP module's logits; `task_kind` tells
`train.py` which task drives it.
"""
from __future__ import annotations

from functools import partial
from typing import Optional

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers import LatentAttention, RmsNorm, SparseMoe, SwiGLU, build_rotary_pos_embed_1d, trunc_normal_
from ..layers.latent_attention import CORE_OUT
from ..layers.moe import merge_counters  # noqa: F401  (re-exported: its first home)
from ..utils import tracing
from ._builder import build_model_with_cfg
from ._registry import register_model

__all__ = ['Glm4MoeLite']

class Glm4Block(nnx.Module):
    """(x, rope) -> (x, counters); `dense_hidden` makes the FFN a dense SwiGLU."""

    def __init__(self, dim, attn_args: dict, moe_args: dict, dense_hidden: Optional[int], eps: float, *,
                 dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.norm1 = RmsNorm(dim, eps=eps, **kw)
        self.attn = LatentAttention(dim, eps=eps, **attn_args, **kw)
        self.norm2 = RmsNorm(dim, eps=eps, **kw)
        self.mlp = SwiGLU(dim, dense_hidden, bias=False, **kw) if dense_hidden else SparseMoe(dim, **moe_args, **kw)
        self.dense = bool(dense_hidden)

    def __call__(self, x, rope, routes: Optional[list] = None):
        """`routes`, a list, gets an expert layer's chosen ids appended (the comparison with the reference)."""
        x = x + self.attn(self.norm1(x), rope)
        h = self.norm2(x)
        if routes is not None and not self.dense:
            routes.append(self.mlp.choose(h))
        if self.dense:
            with tracing.scope('glm.dense_ffn'):
                return x + self.mlp(h), {}
        y, counters = self.mlp(h)
        return x + y, counters


class Glm4Mtp(nnx.Module):
    """One multi-token-prediction depth: h' = W_eh [RMSNorm(Emb(t_{i+1})) | RMSNorm(h_i)],
    one more MoE block, its own final norm; embedding and head are the model's."""

    def __init__(self, dim, block: Glm4Block, eps: float, *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        kw = dict(dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.enorm = RmsNorm(dim, eps=eps, **kw)
        self.hnorm = RmsNorm(dim, eps=eps, **kw)
        self.eh_proj = nnx.Linear(2 * dim, dim, use_bias=False, kernel_init=trunc_normal_(std=0.02), **kw)
        self.block = block
        self.norm = RmsNorm(dim, eps=eps, **kw)


class Glm4MoeLite(nnx.Module):
    task_kind = 'causal_lm'

    def __init__(
            self,
            vocab_size: int = 154880,
            hidden_size: int = 2048,
            num_hidden_layers: int = 47,
            num_attention_heads: int = 20,
            q_lora_rank: int = 768,
            kv_lora_rank: int = 512,
            qk_nope_head_dim: int = 192,
            qk_rope_head_dim: int = 64,
            v_head_dim: int = 256,
            intermediate_size: int = 10240,
            moe_intermediate_size: int = 1536,
            n_routed_experts: int = 64,
            num_experts_per_tok: int = 4,
            n_shared_experts: int = 1,
            routed_scaling_factor: float = 1.8,
            first_k_dense_replace: int = 1,
            num_nextn_predict_layers: int = 1,
            rope_theta: float = 1e6,
            rms_norm_eps: float = 1e-5,
            experts_held: Optional[int] = None,
            expert_offset: int = 0,
            vocab_held: Optional[int] = None,
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
        self.rope_dim, self.rope_theta = qk_rope_head_dim, rope_theta
        self.grad_checkpointing = False
        attn_args = dict(num_heads=num_attention_heads, q_lora_rank=q_lora_rank, kv_lora_rank=kv_lora_rank,
                         qk_nope_head_dim=qk_nope_head_dim, qk_rope_head_dim=qk_rope_head_dim,
                         v_head_dim=v_head_dim)
        moe_args = dict(hidden=moe_intermediate_size, num_experts=n_routed_experts, top_k=num_experts_per_tok,
                        experts_held=experts_held, expert_offset=expert_offset, n_shared=n_shared_experts,
                        routed_scaling_factor=routed_scaling_factor)
        block = partial(Glm4Block, dim, attn_args, moe_args, eps=rms_norm_eps, **kw)
        self.embed = nnx.Embed(self.vocab_held, dim, embedding_init=trunc_normal_(std=0.02), **kw)
        self.blocks = nnx.List([block(dense_hidden=intermediate_size if i < first_k_dense_replace else None)
                                for i in range(num_hidden_layers)])
        self.norm = RmsNorm(dim, eps=rms_norm_eps, **kw)
        self.head = nnx.Linear(dim, self.vocab_held, use_bias=False, kernel_init=trunc_normal_(std=0.02), **kw)
        self.mtp = Glm4Mtp(dim, block(dense_hidden=None), rms_norm_eps, **kw) if num_nextn_predict_layers else None

    # -- the model contract -------------------------------------------------------------
    def group_matcher(self, coarse: bool = False):
        return dict(stem=r'^embed', blocks=[(r'^blocks\.(\d+)', None), (r'^mtp', (99998,)), (r'^norm|^head', (99999,))])

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
        # a block is recomputed in the backward pass, but for the attention core's output: with it kept, the
        # recomputation skips the core's forward, and the core's own per-query-block remat does the rest
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

    def _mtp_input(self, h, next_ids):
        mtp = self.mtp
        return mtp.eh_proj(jnp.concatenate([mtp.enorm(self.embed(next_ids)), mtp.hnorm(h)], axis=-1))

    def forward_mtp(self, h, next_ids, pre_logits: bool = False, with_counters: bool = False):
        """h (B, S, dim) from `forward_features`, next_ids (B, S) = the token after each position ->
        logits (B, S, vocab) for the token after that."""
        mtp = self.mtp
        # the block runs outside the module's scope: its ops carry the scopes of the layers inside it, and the
        # conditional of its expert layer, which a trace shows as one event over the branch taken, carries none
        with tracing.scope('glm.mtp'):
            x = self._mtp_input(h, next_ids)
        x, counters = self._run_block(mtp.block, x, self._rope(h.shape[1]))
        with tracing.scope('glm.mtp'):
            x = mtp.norm(x)
            out = x if pre_logits else self.head(x)
        return (out, counters) if with_counters else out

    def __call__(self, ids):
        return self.forward_head(self.forward_features(ids))

    def routes(self, ids, next_ids):
        """Chosen expert ids (expert layers + MTP, B, S, top_k) of a forward pass; no gradient, no remat."""
        x, rope, chosen = self.embed(ids), self._rope(ids.shape[1]), []
        for blk in self.blocks:
            x, _ = blk(x, rope, chosen)
        if self.mtp is not None:
            self.mtp.block(self._mtp_input(x, next_ids), rope, chosen)
        return jnp.stack(chosen)


def _create(variant, pretrained=False, **kwargs):
    return build_model_with_cfg(Glm4MoeLite, variant, pretrained, **kwargs)


@register_model
def glm4_moe_lite_flash(pretrained=False, **kwargs) -> Glm4MoeLite:
    """GLM-4.7-Flash as published: 47 layers, 64 experts, vocabulary 154880 (30B; no single chip holds it)."""
    return _create('glm4_moe_lite_flash', pretrained, **kwargs)


@register_model
def glm4_moe_lite_flash_ep8(pretrained=False, **kwargs) -> Glm4MoeLite:
    """One chip's share of GLM-4.7-Flash where 8 chips share each layer: experts 0-7 of 64, 19360 of 154880
    vocabulary rows, the leading dense layer and four expert layers (the rest would be further pipeline stages)."""
    share = dict(num_hidden_layers=5, experts_held=8, expert_offset=0, vocab_held=19360)
    return _create('glm4_moe_lite_flash_ep8', pretrained, **dict(share, **kwargs))


@register_model
def glm4_moe_lite_toy(pretrained=False, **kwargs) -> Glm4MoeLite:
    """The CPU tests' size: every mechanism of the published model, nothing of its widths."""
    toy = dict(vocab_size=256, hidden_size=64, num_hidden_layers=3, num_attention_heads=4, q_lora_rank=24,
               kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=8, v_head_dim=16, intermediate_size=160,
               moe_intermediate_size=32, n_routed_experts=8, num_experts_per_tok=2, experts_held=2)
    return _create('glm4_moe_lite_toy', pretrained, **dict(toy, **kwargs))
