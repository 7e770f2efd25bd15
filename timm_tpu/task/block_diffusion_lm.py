"""Block-diffusion language modelling (BD3-LM arXiv:2503.09573, with LLaDA's
forward process arXiv:2502.09992), as SDAR trains: a step noises each sequence
once, hands the model the noised and the clean ids, and its loss is the
cross-entropy of the MASKED positions against the clean token at the SAME
position, each divided by the sequence's masking probability, over all
positions:

    u ~ U[0, 1), p = (1 - eps) u + eps;  m_i ~ Bernoulli(p);  n_i = MASK where m_i, else x_i
    loss = 1 / (B L) * sum over sequences and i of m_i * nll(z_i, x_i) / p

A batch is the token feed's `{'input': clean ids (B, L), 'target': ..}`; the
next-token targets are not read. The noise of a step is drawn on the device,
inside the step, by `draw_noise` at the key `noise_key(model)`: the model
carries the stream's key and a count of draws in its non-parameter state
(`models/sdar_moe.py`), so no two steps draw alike, the same seed draws the
same, and whoever holds the model can compute the noise a step is about to
draw (`BlockDiffusionLMTask.next_noise`: the benchmark's runner follows steps
with it). The chunked, rematerialised head and loss are `CausalLMTask`'s, with
a weight a position; the head runs on the L noised rows only.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import nnx

from ..utils import tracing
from .causal_lm import IGNORE, CausalLMTask

__all__ = ['BlockDiffusionLMTask', 'draw_noise', 'noise_key']

EVAL_P = 0.5        # evaluation masks every position with this probability, from one fixed key


def noise_key(model):
    """The key of the model's next draw: its stream's key with the count of draws folded in."""
    return jax.random.fold_in(jax.random.wrap_key_data(model.noise_key[...]), model.noise_count[...])


def draw_noise(key, clean, mask_token_id: int, eps: float, p=None):
    """clean ids (B, L) -> (noised ids, masked (B, L) bool, p (B,)): one masking probability a sequence from
    U[eps, 1) (or the `p` given), one Bernoulli draw a position."""
    key_p, key_m = jax.random.split(key)
    if p is None:
        p = (1.0 - eps) * jax.random.uniform(key_p, clean.shape[:1], jnp.float32) + eps
    masked = jax.random.uniform(key_m, clean.shape, jnp.float32) < p[:, None]
    return jnp.where(masked, jnp.asarray(mask_token_id, clean.dtype), clean), masked, p


_draw_noise = jax.jit(draw_noise, static_argnums=(2, 3))      # no-donate: the clean ids are the batch the step reads next


class BlockDiffusionLMTask(CausalLMTask):
    def __init__(self, model: nnx.Module, optimizer=None, **kwargs):
        super().__init__(model, optimizer=optimizer, mtp_loss_weight=0.0, **kwargs)

    def _masked_loss(self, model, clean, noised, masked, p, topk: bool = False):
        h, counters = model.forward_features(noised, clean, with_counters=True)
        with tracing.scope('glm.head_loss'):
            sums = self._head_loss(model, h, jnp.where(masked, clean, IGNORE), lambda m, hc: m.forward_head(hc), topk,
                                   weight=jnp.broadcast_to(1.0 / p[:, None], clean.shape))
        return sums, counters

    def loss_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        clean = batch['input']
        noised, masked, p = draw_noise(noise_key(model), clean, model.mask_token_id, model.noise_eps)
        model.noise_count[...] += 1
        sums, counters = self._masked_loss(model, clean, noised, masked, p)
        loss = sums['weighted_sum'] / clean.size
        # beside the weighted loss, the plain sum over the masked positions and their count: their ratio, the mean
        # cross-entropy of a masked position, is what an untrained head owes ln V for, whatever p was drawn
        counters = dict(counters, **{
            'lm.tokens': tracing.device_counter('lm.tokens', jnp.int32(clean.size)),
            'lm.noised_masked': tracing.device_counter('lm.noised_masked', masked.sum().astype(jnp.int32)),
            'lm.masked_nll': tracing.device_counter('lm.masked_nll', sums['loss_sum'])})
        return loss, {'counters': counters}

    def next_noise(self, clean):
        """(noised ids, masked, p) that the next step will draw for the batch `clean`, by the same pure function
        at the same key; reads the model's state, changes nothing."""
        model = self.model
        return _draw_noise(noise_key(model), clean, model.mask_token_id, model.noise_eps)

    def eval_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        """Sums over the batch's masked positions at p = `EVAL_P` from one fixed key: the loss at the position's
        own logits, top-1 and top-5 hits, and their count."""
        clean = batch['input']
        noised, masked, p = draw_noise(jax.random.key(0), clean, model.mask_token_id, model.noise_eps,
                                       p=jnp.full(clean.shape[:1], EVAL_P, jnp.float32))
        sums, _ = self._masked_loss(model, clean, noised, masked, p, topk=True)
        return {k: sums[k] for k in ('loss_sum', 'top1', 'top5')} | {'count': masked.sum()}
