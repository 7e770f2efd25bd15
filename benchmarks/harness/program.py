"""All the benchmark knows of the program's insides, in one place: how a
`timm_tpu` model names its parameters, where a `ClassificationTask` keeps its
optimizer state and its EMA, how a stochastic-depth site draws its key for a
step and counts its draws, and how the task hands out its compiled step.
A PR that changes one of these needs a `benchmark` PR first (PERF.md section 7).
"""
from __future__ import annotations

import re

import jax
import jax.numpy as jnp
from flax import nnx


def dotted(path) -> str:
    """`['blocks'][0]['attn']['qkv']['kernel'].value` -> `blocks.0.attn.qkv.kernel`."""
    return '.'.join(m[1] for m in re.findall(r"\[('?)([^'\]]+)\1\]", jax.tree_util.keystr(path)))


def named_leaves(tree) -> dict:
    return {dotted(p): leaf for p, leaf in jax.tree_util.tree_leaves_with_path(tree)}


def _replace_params(state, weights: dict, what: str):
    names = set(named_leaves(state))
    if names != set(weights):
        odd = sorted(names ^ set(weights))
        raise ValueError(f'{what}: the reference and the program disagree on {len(odd)} parameter names, e.g. {odd[:4]}')

    def pick(path, leaf):
        w = weights[dotted(path)]
        if tuple(w.shape) != tuple(leaf.shape):
            raise ValueError(f'{what}: {dotted(path)} is {tuple(leaf.shape)} in the program, {tuple(w.shape)} in the reference')
        # a copy of its own: the step donates parameters and EMA, and one buffer cannot be donated twice
        return jax.device_put(jnp.array(w, dtype=leaf.dtype, copy=True), leaf.sharding)

    return jax.tree_util.tree_map_with_path(pick, state)


def load_weights(model, weights: dict) -> None:
    """Overwrite every parameter of `model` with the benchmark's weights."""
    nnx.update(model, _replace_params(nnx.state(model, nnx.Param), weights, type(model).__name__))


def load_task_weights(task, weights: dict) -> None:
    """The same for a training task before its first step: the parameters and
    the EMA copy made from them."""
    load_weights(task.model, weights)
    if task.ema_params is not None:
        task.ema_params = _replace_params(task.ema_params, weights, 'EMA')


def drop_path_keys(model) -> dict:
    """The key each live stochastic-depth site will draw at the next step:
    `fold_in(stream key, stream count)` of the site's `dropout` stream, as
    `nnx.RngStream.__call__` derives it. Site names are dotted paths up to the
    module (`blocks.3.drop_path1`)."""
    leaves = named_leaves(nnx.state(model, nnx.RngState))
    keys = {}
    for name, key in leaves.items():
        if name.endswith('.rngs.dropout.key') and '.drop_path' in '.' + name:
            site = name[:-len('.rngs.dropout.key')]
            keys[site] = jax.random.fold_in(key, leaves[site + '.rngs.dropout.count'])
    return keys


def drop_path_counts(model) -> dict:
    """How many keys each stochastic-depth site's stream has handed out."""
    leaves = named_leaves(nnx.state(model, nnx.RngState))
    suffix = '.rngs.dropout.count'
    return {name[:-len(suffix)]: int(count) for name, count in jax.device_get(leaves).items()
            if name.endswith(suffix) and '.drop_path' in '.' + name}


def step_memory(task, batch, lr, step) -> dict:
    """What the compiler planned for the task's step program, in bytes, from
    `memory_analysis()` of `lower_train_step` (a second trace of the step and a
    read from the compile cache: a traced run asks once, after its window)."""
    analysis = task.lower_train_step(batch, lr, step).memory_analysis()
    return {k: int(getattr(analysis, k + '_size_in_bytes')) for k in ('temp', 'argument', 'output', 'alias')}


def _adam_mu(opt_state):
    found = [s.mu for s in jax.tree.leaves(opt_state, is_leaf=lambda x: hasattr(x, 'mu') and hasattr(x, 'nu'))
             if hasattr(s, 'mu')]
    if len(found) != 1:
        raise ValueError(f'expected one Adam state in the optimizer state, found {len(found)}')
    return found[0]


@jax.jit
def _leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def first_grad_norms(task, b1: float = 0.9) -> dict:
    """After the task's FIRST step: the norm of every leaf of the gradient as
    the optimizer got it, from Adam's first moment, m_1 = (1 - b1) g_1."""
    norms = named_leaves(_leaf_norms(_adam_mu(task.opt_state)))
    return {k: float(v) / (1.0 - b1) for k, v in norms.items()}


def _change_norms(state, start: dict) -> dict:
    now = named_leaves(state)
    moved = _leaf_norms({k: now[k].astype(jnp.float32) - start[k] for k in start})
    return {k: float(v) for k, v in moved.items()}


def param_change_norms(task, start: dict) -> dict:
    """Norm of every parameter leaf's change from the weights it started at."""
    return _change_norms(nnx.state(task.model, nnx.Param), start)


def ema_change_norms(task, start: dict) -> dict:
    """The same for the task's moving average of the parameters."""
    if task.ema_params is None:
        raise ValueError('the recipe states an EMA decay and the task keeps no EMA')
    return _change_norms(task.ema_params, start)
