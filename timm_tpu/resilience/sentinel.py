"""Non-finite (NaN/Inf) step sentinel.

Device side: `tree_all_finite` is a jit-compatible all-finite reduction over
loss + gradients that fuses into the compiled train step; the step keeps a
device-resident `[consecutive, total]` int32 counter pair and selects between
the updated and previous (params, opt_state, EMA) with `jnp.where`, so a bad
step costs its compute but commits nothing — no retrace, no host round-trip.

Host side: `NonFiniteSentinel.observe` reads one step's counters and raises
`NonFiniteError` after K consecutive bad steps (K =
TIMM_TPU_NONFINITE_TOLERANCE, default 3). Every step's counters are read,
once, in order — but one call late: `TrainingTask.train_step` N hands it the
counters step N-1 returned (its own `metrics['nonfinite_count']` /
`['nonfinite_total']`, outputs of the program and a snapshot of that step), so
the read waits for a step that has a successor queued behind it and the host's
work between two steps runs behind the device. The abort therefore arrives one
call after the step that trips it; `TrainingTask.drain()` reads the step still
unread wherever the old ordering matters (before a checkpoint, a recovery file,
an evaluation).

Because loss and grads are computed from the globally-sharded batch with
replicated params, the all-finite flag is identical on every host of a pod —
all hosts skip the same step and abort at the same poll without extra
cross-host coordination (see parallel.all_hosts_flag for host-local signals
like preemption, which DO need it).
"""
from __future__ import annotations

import logging
import os
from typing import Optional

import jax
import jax.numpy as jnp

from ..utils import tracing

_logger = logging.getLogger(__name__)

__all__ = ['NonFiniteError', 'NonFiniteSentinel', 'tree_all_finite',
           'new_sentinel_state', 'update_sentinel_state', 'guard_enabled']

DEFAULT_TOLERANCE = 3


class NonFiniteError(RuntimeError):
    def __init__(self, consecutive: int, total: int, step: int, tolerance: int):
        self.consecutive = consecutive
        self.total = total
        self.step = step
        self.tolerance = tolerance
        super().__init__(
            f'{consecutive} consecutive non-finite train steps at update {step} '
            f'(tolerance {tolerance}, {total} bad steps total). The last '
            f'committed checkpoint is intact; lower the LR / enable grad '
            f'clipping, or resume with --nonfinite-rollback to retry from it. '
            f'Set TIMM_TPU_NONFINITE_TOLERANCE to adjust the abort threshold.')


def guard_enabled(explicit: Optional[bool] = None) -> bool:
    """Guard default: on, unless TIMM_TPU_NONFINITE_GUARD=0."""
    if explicit is not None:
        return explicit
    return os.environ.get('TIMM_TPU_NONFINITE_GUARD', '1') not in ('0', 'false', 'off')


def tree_all_finite(*trees) -> jax.Array:
    """Scalar bool: every inexact-dtype leaf of every tree is finite.
    Jit-compatible; integer/bool leaves (e.g. optimizer step counts) are
    finite by construction and skipped."""
    ok = jnp.asarray(True)
    for tree in trees:
        for leaf in jax.tree.leaves(tree):
            if jnp.issubdtype(jnp.asarray(leaf).dtype, jnp.inexact):
                ok = jnp.logical_and(ok, jnp.isfinite(leaf).all())
    return ok


def new_sentinel_state() -> jax.Array:
    """[consecutive_bad, total_bad] int32 device counters."""
    return jnp.zeros((2,), jnp.int32)


def update_sentinel_state(state: jax.Array, ok: jax.Array) -> jax.Array:
    bad = jnp.logical_not(ok).astype(jnp.int32)
    consecutive = jnp.where(ok, 0, state[0] + 1)
    return jnp.stack([consecutive, state[1] + bad])


class NonFiniteSentinel:
    def __init__(self, tolerance: Optional[int] = None):
        if tolerance is None:
            tolerance = int(os.environ.get('TIMM_TPU_NONFINITE_TOLERANCE', DEFAULT_TOLERANCE))
        assert tolerance >= 1, 'nonfinite tolerance must be >= 1'
        self.tolerance = tolerance
        self.consecutive = 0   # as of the last poll
        self.total = 0

    def reset(self):
        """With fresh device counters (`TrainingTask.reset_nonfinite`): both start at 0 again."""
        self.consecutive = self.total = 0

    def observe(self, sentinel_state, step: int = 0) -> bool:
        """Read one step's `[consecutive, total]` counters (the state array or
        the pair of scalars the step returns in its metrics); `step` is the
        update they belong to. True if that step was skipped. Raises
        NonFiniteError once `tolerance` consecutive steps went bad."""
        tracing.count('task.sentinel_polls')
        counts = jax.device_get(sentinel_state)
        consecutive, total = int(counts[0]), int(counts[1])
        newly_bad = total - self.total
        self.consecutive, self.total = consecutive, total
        if newly_bad > 0:
            _logger.warning(
                f'Non-finite loss/grads at update {step}: update skipped '
                f'({consecutive} consecutive, {total} total)')
        if consecutive >= self.tolerance:
            raise NonFiniteError(consecutive, total, step, self.tolerance)
        return newly_bad > 0
