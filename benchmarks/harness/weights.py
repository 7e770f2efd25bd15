"""Weights from `--seed`, made on the device in one jitted call from a
reference's `init_spec` — by the benchmark, for the program and the reference
alike, so the reference takes nothing the program has made."""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

STD = 0.02


@functools.partial(jax.jit, static_argnames=('spec',))
def _make(key, spec):
    drawn = [(name, shape) for name, shape, kind in spec if kind in ('normal', 'ones')]
    flat = jax.random.normal(key, (sum(math.prod(s) for _, s in drawn),), jnp.float32) * STD
    out, at = {}, 0
    for name, shape, kind in spec:
        if kind in ('normal', 'ones'):
            n = math.prod(shape)
            x = flat[at:at + n].reshape(shape)
            out[name] = x + 1.0 if kind == 'ones' else x
            at += n
        else:
            out[name] = jnp.full(shape, kind, jnp.float32)
    return out


def make(seed: int, init_spec: dict) -> dict:
    """name -> float32 array. 'normal' leaves are N(0, 0.02), 'ones' leaves
    1 + N(0, 0.02), a float kind is that constant."""
    spec = tuple((name, tuple(shape), kind) for name, (shape, kind) in sorted(init_spec.items()))
    return _make(jax.random.key(seed % (2 ** 31)), spec)
