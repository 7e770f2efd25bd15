# no-kernel-registry: infrastructure module — consumes the registry, not a kernel
"""Parity harness over the kernel registry's one spec table (registry.py). What reads it:

1. **Parity** — `parity_check` runs kernel vs reference at a case's `dry`
   shapes with BOTH arms jitted (on non-TPU backends the kernel arm lowers
   via ``pallas_call(interpret=True)``). Jitting both arms matters: XLA
   normalizes bf16 arithmetic to f32 compute, so an eager reference would
   round intermediates the compiled train step never rounds.
   tests/test_kernels.py parametrizes over `parity_cases()` — that's the
   auto-generated per-kernel parity test.

2. **The chip** — `live=True`: `chip_smoke.py` runs a cell's shapes, tests/test_chip_smoke.py compiles them for a v5e.
"""
from __future__ import annotations

import functools
from typing import Dict, List, Tuple

from . import registry
from .registry import KernelCase, KernelSpec

__all__ = ['parity_cases', 'parity_check']


def _jit_arm(fn, statics: Dict):
    """Jit an arm over the inputs pytree; `statics` are partial-bound python
    values (dtypes, masks, coefficients), never traced."""
    import jax
    bound = functools.partial(fn, **statics)
    return jax.jit(lambda kw: bound(**kw))


def parity_cases() -> List[Tuple[KernelSpec, KernelCase]]:
    """Every (spec, case) pair in the registry — the parametrization grid
    for the auto-generated parity tests."""
    return [(spec, case) for spec in registry.all_specs() for case in spec.cases]


def parity_check(spec: KernelSpec, case: KernelCase, seed: int = 0,
                 live: bool = False) -> Dict:
    """Max abs error between jitted kernel and jitted reference at the
    case's dry (or, with `live`, its claimed) shapes, leaf-for-leaf over the
    output pytree. `tpu_custom_call` says whether the compiled kernel arm
    holds a Mosaic kernel (True on TPU) or ran interpreted (False on CPU)."""
    import jax
    import jax.numpy as jnp

    inputs = spec.make_inputs(seed=seed, **(case.live if live else case.dry))
    compiled_k = _jit_arm(spec.kernel_fn, case.statics).lower(inputs).compile()
    out_k = compiled_k(inputs)
    out_r = _jit_arm(spec.reference_fn, case.statics)(inputs)
    leaves_k, leaves_r = jax.tree.leaves(out_k), jax.tree.leaves(out_r)
    assert len(leaves_k) == len(leaves_r), (
        f'{spec.name}/{case.name}: kernel and reference output pytrees '
        f'disagree ({len(leaves_k)} vs {len(leaves_r)} leaves)')
    err = 0.0
    for a, b in zip(leaves_k, leaves_r):
        d = jnp.max(jnp.abs(a.astype(jnp.float32) - b.astype(jnp.float32)))
        err = max(err, float(d))
    return {'kernel': spec.name, 'case': case.name, 'max_abs_err': err,
            'tol': spec.parity_tol, 'ok': err <= spec.parity_tol,
            'tpu_custom_call': 'tpu_custom_call' in compiled_k.as_text()}
