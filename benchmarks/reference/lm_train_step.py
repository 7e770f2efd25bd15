"""Plain reference of one causal-LM training step, what `train_step.py` (soft
targets, images, EMA) lacks: the batch's loss and gradient one sequence at a
time, clipping by global norm, AdamW with the recipe's betas — and the
reduction of a few followed steps to the numbers a cell's `correct` compares.
Imports nothing of the program.

At the timed size the weights take 2.8 GB in float32, so four copies (weights,
two moments, gradient) and a sequence's activations are all a chip holds: the
moments wait on the host while a gradient is taken, the start weights are made
again from the seed when the change is measured, and every buffer an update
replaces is donated.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from .train_step import CLIP_EPS, hashable, leaf_norms

ADAM_EPS = 1e-8
IGNORE = -1


@functools.partial(jax.jit, static_argnames=('module', 'cfg_key', 'precision', 'block_q'))
def loss_and_grads(module, cfg_key, params, ids, target, precision='float32', block_q=1024):
    """-> (loss, gradient, chosen experts (B, layers, S, k)) of the batch ids, target (B, S): the mean
    next-token cross-entropy over the batch's valid positions plus the weighted MTP term, summed over
    sequences taken one at a time."""
    cfg = dict(cfg_key)
    n_main = (target != IGNORE).sum()
    after = jnp.concatenate([target[:, 1:], jnp.full_like(target[:, :1], IGNORE)], axis=1)   # the MTP module's target
    n_mtp = ((target != IGNORE) & (after != IGNORE)).sum()
    one = jax.value_and_grad(lambda p, i, t: module.loss(cfg, p, i, t, n_main, n_mtp, precision, block_q), has_aux=True)

    def body(carry, row):
        loss, grads = carry
        (l, chosen), g = one(params, *row)
        return (loss + l, jax.tree.map(jnp.add, grads, g)), chosen

    init = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), chosen = jax.lax.scan(body, init, (ids, target))
    return loss, grads, chosen


@functools.partial(jax.jit, donate_argnums=(0, 1, 2, 3))
def adamw(params, grads, m, v, lr, t, clip, weight_decay, b1, b2, decay_mask):
    """Clip by global norm, then one AdamW update (arXiv:1711.05101) -> (params, the clipped gradient's norm
    a leaf, m, v)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (norm + CLIP_EPS))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda a, g: b1 * a + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda a, g: b2 * a + (1 - b2) * jnp.square(g), v, grads)

    def new(p, a, b, decay):
        step = (a / (1 - b1 ** t)) / (jnp.sqrt(b / (1 - b2 ** t)) + ADAM_EPS)
        return p - lr * (step + weight_decay * decay * p)

    return jax.tree.map(new, params, m, v, decay_mask), leaf_norms(grads), m, v


def follow(module, cfg, make_params, steps, *, clip: float, weight_decay: float, betas, precision: str = 'float32',
           block_q: int = 1024) -> dict:
    """Follow `steps` (each `input`, `target` (B, S) and `lr`) from `make_params()`. Returns each step's
    loss, the norm of every leaf of the first gradient as AdamW gets it (after clipping), the norm of every
    leaf's change over all the steps, and the first step's chosen experts — as Python floats and numpy."""
    cfg_key = hashable(cfg)
    params = make_params()
    decay_mask = {k: jnp.float32(p.ndim > 1 and not module.no_weight_decay(k)) for k, p in params.items()}
    m = v = None
    losses, first_grad, first_routes = [], None, None
    clock, spent = time.perf_counter(), {'gradient': 0.0, 'moments_to_device': 0.0, 'update': 0.0, 'moments_to_host': 0.0}

    def lap(what, *wait):
        nonlocal clock
        jax.block_until_ready(wait)
        spent[what] += time.perf_counter() - clock
        clock = time.perf_counter()

    for t, step in enumerate(steps, start=1):
        loss, grads, chosen = loss_and_grads(module, cfg_key, params, jnp.asarray(step['input'], jnp.int32),
                                             jnp.asarray(step['target'], jnp.int32), precision=precision,
                                             block_q=block_q)
        lap('gradient', loss, grads)
        if first_routes is None:
            first_routes = np.asarray(chosen)
        # the moments come back from the host only now, with the sequence's activations gone
        m = jax.tree.map(jnp.zeros_like, params) if m is None else jax.device_put(m)
        v = jax.tree.map(jnp.zeros_like, params) if v is None else jax.device_put(v)
        lap('moments_to_device', m, v)
        params, clipped, m, v = adamw(params, grads, m, v, jnp.float32(step['lr']), jnp.float32(t), jnp.float32(clip),
                                      jnp.float32(weight_decay), jnp.float32(betas[0]), jnp.float32(betas[1]), decay_mask)
        lap('update', params)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {k: float(n) for k, n in clipped.items()}
        del grads, clipped
        if t < len(steps):
            m, v = jax.device_get((m, v))
            lap('moments_to_host')
    del m, v
    start = make_params()
    moved = leaf_norms({k: params[k] - start[k] for k in params})
    return {'losses': losses, 'first_grad_norms': first_grad,
            'param_change_norms': {k: float(n) for k, n in moved.items()}, 'routes': first_routes,
            'seconds': spent}
