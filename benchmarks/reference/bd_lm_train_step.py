"""Plain reference of one block-diffusion training step, what `lm_train_step.py`
(one id tensor and its next-token targets) cannot take: the batch's weighted
loss and gradient one sequence at a time from the noised ids, the clean ids
and each sequence's masking probability, all GIVEN (nothing is drawn here);
then `lm_train_step.py`'s clipping and AdamW, and the reduction of a few
followed steps to the numbers a cell's `correct` compares. Imports nothing of
the program.
"""
from __future__ import annotations

import functools
import time

import jax
import jax.numpy as jnp
import numpy as np

from .lm_train_step import adamw
from .train_step import hashable, leaf_norms


@functools.partial(jax.jit, static_argnames=('module', 'cfg_key', 'precision', 'block_q'))
def loss_and_grads(module, cfg_key, params, noised, clean, prob, precision='float32', block_q=1024):
    """-> (loss, gradient, chosen experts (B, layers, 2 L, k), mean cross-entropy of the masked positions) of
    the batch noised, clean (B, L), prob (B,): sum over sequences and masked positions of nll / prob, over B L."""
    cfg = dict(cfg_key)
    one = jax.value_and_grad(lambda p, n, c, pr: module.loss(cfg, p, n, c, pr, clean.size, precision, block_q), has_aux=True)

    def body(carry, row):
        loss, grads, nll, count = carry
        (l, (chosen, nll_sum, masked)), g = one(params, *row)
        return (loss + l, jax.tree.map(jnp.add, grads, g), nll + nll_sum, count + masked), chosen

    init = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params), jnp.zeros((), jnp.float32), jnp.zeros((), jnp.int32))
    (loss, grads, nll, count), chosen = jax.lax.scan(body, init, (noised, clean, prob))
    return loss, grads, chosen, nll / jnp.maximum(count, 1)


def follow(module, cfg, make_params, steps, *, clip: float, weight_decay: float, betas, precision: str = 'float32',
           block_q: int = 1024) -> dict:
    """Follow `steps` (each `noised`, `clean` (B, L), `p` (B,) and `lr`) from `make_params()`, the moments parked
    on the host while a gradient is taken, as `lm_train_step.follow` does. Returns each step's loss and mean
    masked cross-entropy, the norm of every leaf of the first gradient as AdamW gets it (after clipping), the norm
    of every leaf's change over all the steps, and the first step's chosen experts."""
    cfg_key = hashable(cfg)
    params = make_params()
    decay_mask = {k: jnp.float32(p.ndim > 1 and not module.no_weight_decay(k)) for k, p in params.items()}
    m = v = None
    losses, masked_nll, first_grad, first_routes = [], [], None, None
    clock, spent = time.perf_counter(), {'gradient': 0.0, 'moments_to_device': 0.0, 'update': 0.0, 'moments_to_host': 0.0}

    def lap(what, *wait):
        nonlocal clock
        jax.block_until_ready(wait)
        spent[what] += time.perf_counter() - clock
        clock = time.perf_counter()

    for t, step in enumerate(steps, start=1):
        loss, grads, chosen, nll = loss_and_grads(
            module, cfg_key, params, jnp.asarray(step['noised'], jnp.int32), jnp.asarray(step['clean'], jnp.int32),
            jnp.asarray(step['p'], jnp.float32), precision=precision, block_q=block_q)
        lap('gradient', loss, grads)
        if first_routes is None:
            first_routes = np.asarray(chosen)
        m = jax.tree.map(jnp.zeros_like, params) if m is None else jax.device_put(m)
        v = jax.tree.map(jnp.zeros_like, params) if v is None else jax.device_put(v)
        lap('moments_to_device', m, v)
        params, clipped, m, v = adamw(params, grads, m, v, jnp.float32(step['lr']), jnp.float32(t), jnp.float32(clip),
                                      jnp.float32(weight_decay), jnp.float32(betas[0]), jnp.float32(betas[1]), decay_mask)
        lap('update', params)
        losses.append(float(loss))
        masked_nll.append(float(nll))
        if first_grad is None:
            first_grad = {k: float(n) for k, n in clipped.items()}
        del grads, clipped
        if t < len(steps):
            m, v = jax.device_get((m, v))
            lap('moments_to_host')
    del m, v
    start = make_params()
    moved = leaf_norms({k: params[k] - start[k] for k in params})
    return {'losses': losses, 'masked_nll': masked_nll, 'first_grad_norms': first_grad,
            'param_change_norms': {k: float(n) for k, n in moved.items()}, 'routes': first_routes,
            'seconds': spent}
