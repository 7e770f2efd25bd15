"""Causal language modelling: next-token cross-entropy plus the weighted loss
of the model's multi-token-prediction module (DeepSeek-V3, arXiv:2412.19437
section 2.2: L = L_main + lambda * L_mtp).

A batch is `{'input': ids (B, S), 'target': (B, S)}`, `target[i]` the token after
`input[i]`, `IGNORE` where there is none (the last position of a window). The
MTP module at position i sees the trunk's output and the embedding of
`target[i]` and predicts `target[i + 1]`. Both losses are means over their own
valid positions, in float32; the output head and the cross-entropy run in
chunks of the sequence, each rematerialised in the backward pass, so the
(tokens, vocabulary) logits of a whole batch never exist.

A model with `num_pred_heads` P > 1 (EvaByte: P linear heads from ONE product,
the logits head-major) is trained on P targets a position: head p's is the id
p + 1 positions on, `target[i + p]`, and IGNORE where that runs past the window
the feed gave (the last p + 1 positions). The loss is the equal-weight mean of
the P heads' means over their own valid positions; `loss_main` is head 0's, the
next-token loss, and the step counter `lm.head_nll` carries all P.
"""
from __future__ import annotations

from typing import Any, Dict

import jax
import jax.numpy as jnp
from flax import nnx

from ..layers.moe import merge_counters
from ..utils import tracing
from .task import TrainingTask

__all__ = ['CausalLMTask', 'IGNORE']

IGNORE = -1


def head_targets(target, heads: int):
    """target (B, S), `target[i]` the id after position i -> (B, S, heads): head p's target at i is the id
    p + 1 positions on, IGNORE where the window ends before it."""
    return jnp.stack([jnp.pad(target[:, p:], ((0, 0), (0, p)), constant_values=IGNORE) for p in range(heads)], axis=-1)


def _ce_sums(logits, target, topk: bool = False, weight=None):
    """Summed cross-entropy over positions whose target is not IGNORE (float32),
    with `topk` the top-1 / top-5 hits there, and with `weight` (a float a
    position) the weighted sum beside the plain one. A target (B, S, P) of P
    prediction heads takes logits (B, S, P * V), head-major, and gives each
    sum a head, (P,)."""
    over = None
    if target.ndim == 3:
        logits, over = logits.reshape(*target.shape, -1), (0, 1)
    valid = target != IGNORE
    safe = jnp.where(valid, target, 0)
    logits = logits.astype(jnp.float32)
    nll = jax.nn.logsumexp(logits, axis=-1) - jnp.take_along_axis(logits, safe[..., None], axis=-1)[..., 0]
    out = {'loss_sum': jnp.where(valid, nll, 0.0).sum(over)}
    if weight is not None:
        out['weighted_sum'] = jnp.where(valid, nll * weight, 0.0).sum()
    if topk:
        top = jax.lax.top_k(logits, 5)[1]
        out['top1'] = (valid & (top[..., 0] == safe)).sum()
        out['top5'] = (valid & (top == safe[..., None]).any(-1)).sum()
    return out


class CausalLMTask(TrainingTask):
    def __init__(self, model: nnx.Module, optimizer=None, mtp_loss_weight: float = 0.3, loss_chunk: int = 2048,
                 **kwargs):
        kwargs.pop('mean', None), kwargs.pop('std', None)      # token ids are not normalised
        super().__init__(model, optimizer=optimizer, **kwargs)
        self.mtp_loss_weight = mtp_loss_weight
        self.loss_chunk = loss_chunk

    def _head_loss(self, model, h, target, head, topk: bool = False, weight=None):
        """Sums of `_ce_sums` over the sequence in chunks; `head(model, h_chunk)` gives a chunk's logits,
        `weight` (B, S) a weight a position."""
        S = h.shape[1]
        chunk = min(self.loss_chunk, S)
        one = nnx.remat(lambda m, hc, tc, *wc: _ce_sums(head(m, hc), tc, topk, *wc))
        rows = (h, target) if weight is None else (h, target, weight)
        sums = [one(model, *(t[:, i:i + chunk] for t in rows)) for i in range(0, S, chunk)]
        return jax.tree.map(lambda *xs: sum(xs), *sums)

    def loss_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        ids, target = batch['input'], batch['target']
        h, counters = model.forward_features(ids, with_counters=True)
        heads = getattr(model, 'num_pred_heads', 1)
        with tracing.scope('glm.head_loss'):
            if heads > 1:
                targets = head_targets(target, heads)
                main = self._head_loss(model, h, targets, lambda m, hc: m.forward_head(hc))
                each = main['loss_sum'] / jnp.maximum((targets != IGNORE).sum((0, 1)), 1)
                loss, first = each.mean(), each[0]
                counters = dict(counters, **{'lm.head_nll': tracing.device_counter('lm.head_nll', each)})
            else:
                main = self._head_loss(model, h, target, lambda m, hc: m.forward_head(hc))
                loss = first = main['loss_sum'] / jnp.maximum((target != IGNORE).sum(), 1)
        output = {'loss_main': first}
        if getattr(model, 'mtp', None) is not None and self.mtp_loss_weight:
            # the module's input at i is the embedding of target[i]; it predicts target[i + 1]
            next_ids = jnp.where(target == IGNORE, 0, target)
            target2 = jnp.concatenate([target[:, 1:], jnp.full_like(target[:, :1], IGNORE)], axis=1)
            target2 = jnp.where(target == IGNORE, IGNORE, target2)
            h2, c2 = model.forward_mtp(h, next_ids, pre_logits=True, with_counters=True)
            with tracing.scope('glm.head_loss'):
                mtp = self._head_loss(model, h2, target2, lambda m, hc: m.head(hc))
                loss_mtp = mtp['loss_sum'] / jnp.maximum((target2 != IGNORE).sum(), 1)
            counters = merge_counters(counters, c2)
            output['loss_mtp'] = loss_mtp
            loss = loss + self.mtp_loss_weight * loss_mtp
        output['counters'] = dict(counters, **{'lm.tokens': tracing.device_counter('lm.tokens', jnp.int32(ids.size))})
        return loss, output

    def step_counters(self, output) -> Dict[str, Any]:
        return output['counters']

    def eval_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        """Sums over the batch's valid positions: next-token loss, top-1 and top-5 hits, and their count."""
        h = model.forward_features(batch['input'])
        vocab = model.vocab_held        # of several prediction heads the first, the next-token one, is scored
        sums = self._head_loss(model, h, batch['target'], lambda m, hc: m.forward_head(hc)[..., :vocab], topk=True)
        return dict(sums, count=(batch['target'] != IGNORE).sum())
