"""Classification task (reference: timm/task/classification.py:13-100)."""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

from flax import nnx

from ..loss import LabelSmoothingCrossEntropy
from ..utils import tracing
from .task import TrainingTask

__all__ = ['ClassificationTask']


class ClassificationTask(TrainingTask):
    def __init__(
            self,
            model: nnx.Module,
            optimizer=None,
            train_loss_fn: Optional[Callable] = None,
            eval_loss_fn: Optional[Callable] = None,
            **kwargs,
    ):
        super().__init__(model, optimizer=optimizer, **kwargs)
        self.train_loss_fn = train_loss_fn or LabelSmoothingCrossEntropy(0.0)
        self.eval_loss_fn = eval_loss_fn or self.train_loss_fn

    def loss_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        output = model(batch['input'])
        with tracing.scope('step.loss'):
            loss = self.train_loss_fn(output, batch['target'])
        return loss, output


class NaFlexClassificationTask(ClassificationTask):
    """Classification over NaFlex dict batches ({patches, patch_coord,
    patch_valid, target[, target_b, lam]}); each (seq_len, patch_size)
    bucket traces once. When the loader performed variable-size mixup/cutmix,
    the per-sample lam-mixed (and optionally smoothed) soft target
    distribution is built here and fed to the CONFIGURED train loss
    (SoftTargetCrossEntropy, BCE, ... — anything accepting dense targets),
    mirroring how the reference's Mixup builds soft labels for the tuple
    pipeline (reference mixup.py mixup_target)."""

    def __init__(self, *args, mixup_label_smoothing: Optional[float] = None, **kwargs):
        super().__init__(*args, **kwargs)
        # not-None ⇒ the train loss expects DENSE targets (mixup configured);
        # un-mixed batches then get smoothed one-hot targets too
        self.mixup_label_smoothing = mixup_label_smoothing

    def _soft_targets(self, batch, nc):
        import jax.numpy as jnp
        s = self.mixup_label_smoothing or 0.0
        off, on = s / nc, 1.0 - s + s / nc
        B = batch['target'].shape[0]
        oh_a = jnp.full((B, nc), off).at[jnp.arange(B), batch['target']].set(on)
        if 'lam' not in batch:
            return oh_a
        oh_b = jnp.full((B, nc), off).at[jnp.arange(B), batch['target_b']].set(on)
        lam = batch['lam'].astype(jnp.float32)[:, None]
        return lam * oh_a + (1.0 - lam) * oh_b

    def loss_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        output = model({
            'patches': batch['patches'],
            'patch_coord': batch['patch_coord'],
            'patch_valid': batch['patch_valid'],
        })
        if self.mixup_label_smoothing is not None or 'lam' in batch:
            loss = self.train_loss_fn(output, self._soft_targets(batch, output.shape[-1]))
        else:
            loss = self.train_loss_fn(output, batch['target'])
        return loss, output

    def eval_forward(self, model: nnx.Module, batch: Dict[str, Any]):
        return model({
            'patches': batch['patches'],
            'patch_coord': batch['patch_coord'],
            'patch_valid': batch['patch_valid'],
        })
