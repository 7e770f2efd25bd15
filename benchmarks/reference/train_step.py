"""Plain reference of one training step, shared by the configurations: soft-
target cross-entropy, its gradient by `jax.grad`, clipping by global norm,
AdamW (arXiv:1711.05101), the exponential moving average of the parameters —
and the reduction of a few steps to the numbers a cell's `correct` compares.
Imports nothing of the program.

The gradient is taken in blocks of rows and summed, so that a float32 step at
the timed batch fits on one chip once the program's state is freed.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
CLIP_EPS = 1e-6  # torch.nn.utils.clip_grad_norm_: max_norm / (norm + 1e-6)
EMA_COPIES = 2   # timm ModelEmaV3.get_decay with update_after_step 0: the first two updates copy the parameters


def soft_target_cross_entropy_sum(logits, target):
    return -(target * jax.nn.log_softmax(logits, axis=-1)).sum()


def hashable(cfg) -> tuple:
    """The sizes of a configuration as a jit-static key."""
    return tuple(sorted((k, tuple(v) if isinstance(v, list) else v) for k, v in cfg.items()
                        if isinstance(v, (int, float, str, list)) and k != 'source'))


def draw_keep_rows(module, cfg, drop_keys: dict, batch: int) -> dict:
    """Each stochastic-depth site's draw for the whole batch from the step's
    key for that site: Bernoulli(1 - rate), one a row, as `jax.random` draws it
    for an array of the site's rank."""
    shape = (batch,) + (1,) * (module.DROP_PATH_NDIM - 1)
    rates = module.drop_path_rates(cfg)
    missing = sorted(set(rates) - set(drop_keys))
    if missing:
        raise KeyError(f'no stochastic-depth key for {missing[:3]} ({len(missing)} sites)')
    return {name: jax.random.bernoulli(drop_keys[name], 1.0 - rate, shape).reshape(batch)
            for name, rate in rates.items()}


@functools.partial(jax.jit, static_argnames=('forward', 'cfg_key', 'precision', 'rows'))
def loss_and_grads(forward, cfg_key, params, x, target, keep_rows, precision='float32', rows=32):
    """Mean soft-target cross-entropy over the batch and its gradient, summed
    over blocks of `rows` rows."""
    cfg = {k: list(v) if isinstance(v, tuple) else v for k, v in cfg_key}
    batch = x.shape[0]

    def block_loss(p, xb, tb, kb):
        return soft_target_cross_entropy_sum(forward(cfg, p, xb, kb, precision), tb) / batch

    def body(carry, i):
        loss, grads = carry
        cut = functools.partial(jax.lax.dynamic_slice_in_dim, start_index=i * rows, slice_size=rows)
        l, g = jax.value_and_grad(block_loss)(params, cut(x), cut(target), jax.tree.map(cut, keep_rows))
        return (loss + l, jax.tree.map(jnp.add, grads, g)), None

    init = (jnp.zeros((), jnp.float32), jax.tree.map(jnp.zeros_like, params))
    (loss, grads), _ = jax.lax.scan(body, init, jnp.arange(batch // rows))
    return loss, grads


@jax.jit
def adamw(params, grads, m, v, lr, t, clip, weight_decay, decay_mask):
    """Clip by global norm, then one AdamW update; returns the clipped
    gradient too (what the optimizer got)."""
    norm = jnp.sqrt(sum(jnp.sum(jnp.square(g)) for g in jax.tree.leaves(grads)))
    scale = jnp.minimum(1.0, clip / (norm + CLIP_EPS))
    grads = jax.tree.map(lambda g: g * scale, grads)
    m = jax.tree.map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = jax.tree.map(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * jnp.square(g), v, grads)

    def new(p, a, b, decay):
        step = (a / (1 - ADAM_B1 ** t)) / (jnp.sqrt(b / (1 - ADAM_B2 ** t)) + ADAM_EPS)
        return p - lr * (step + weight_decay * decay * p)

    return jax.tree.map(new, params, m, v, decay_mask), grads, m, v


@jax.jit
def ema_update(ema, params, decay):
    return jax.tree.map(lambda e, p: e * decay + p * (1.0 - decay), ema, params)


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def follow(module, cfg, params, steps, *, clip: float, weight_decay: float, ema_decay: float,
           precision: str = 'float32', rows: int = 32) -> dict:
    """Follow `steps` (each a dict of `input`, `target`, `lr` and `drop_keys`, the raw key
    data of each stochastic-depth site's draw)
    from `params`. Returns each step's loss, the norm of every leaf of the
    first gradient as AdamW gets it (after clipping), and the norm of every
    leaf's change over all the steps, of the parameters and of their moving
    average (decay `ema_decay`, updated after each step) — as Python floats."""
    cfg_key = hashable(cfg)
    decay_mask = {k: jnp.float32(p.ndim > 1 and not module.no_weight_decay(k)) for k, p in params.items()}
    start = ema = params
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    losses, first_grad = [], None
    for t, step in enumerate(steps, start=1):
        batch = step['input'].shape[0]
        r = min(rows, batch)
        if batch % r:
            raise ValueError(f'batch {batch} does not divide into blocks of {r} rows')
        keys = {k: jax.random.wrap_key_data(jnp.asarray(v)) for k, v in step['drop_keys'].items()}
        keep_rows = draw_keep_rows(module, cfg, keys, batch)
        loss, grads = loss_and_grads(
            module.forward, cfg_key, params, jnp.asarray(step['input'], jnp.float32),
            jnp.asarray(step['target'], jnp.float32), keep_rows, precision=precision, rows=r)
        params, clipped, m, v = adamw(
            params, grads, m, v, jnp.float32(step['lr']), jnp.float32(t), jnp.float32(clip),
            jnp.float32(weight_decay), decay_mask)
        decay = jnp.float32(0.0 if t <= EMA_COPIES else ema_decay)
        ema = ema_update(ema, params, decay)
        losses.append(float(loss))
        if first_grad is None:
            first_grad = {k: float(n) for k, n in leaf_norms(clipped).items()}
        del grads, clipped
    moved = leaf_norms({k: params[k] - start[k] for k in params})
    ema_moved = leaf_norms({k: ema[k] - start[k] for k in params})
    return {'losses': losses, 'first_grad_norms': first_grad,
            'param_change_norms': {k: float(n) for k, n in moved.items()},
            'ema_change_norms': {k: float(n) for k, n in ema_moved.items()}}
