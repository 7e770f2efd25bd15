"""Tier B — jaxpr rules: the traced program, before XLA sees it.

* ``large-literal`` — walks the jaxprs of every program the perfbudget
  probes lower and fails on any baked constant > 1 MB. This is the PR 9
  landmine (a 19 MB uint8 batch closed over into the compiled augment
  program) as a pass instead of a memory.
* ``dtype-promotion`` — audits the canonical softmax program under a
  declared-bf16 policy: the exp/div pipeline must stay in the declared
  dtype (the f32 max-subtraction is the one allowed upcast — it is
  stop-gradient'd and numerically load-bearing).
"""
from __future__ import annotations

import importlib.util
import os
from typing import Iterable, List, Optional, Tuple

import numpy as np

from .pragmas import FilePragmas
from .registry import AnalysisContext, rule
from .report import Finding

__all__ = ['LARGE_LITERAL_BYTES', 'large_literals', 'unintended_upcasts',
           'scan_module_program']

LARGE_LITERAL_BYTES = 1 << 20  # 1 MB


def _jaxpr_of(closed):
    return getattr(closed, 'jaxpr', closed)


def _consts_of(closed):
    return getattr(closed, 'consts', ()) or ()


def _sub_jaxprs(params) -> Iterable:
    for v in params.values():
        for item in (v if isinstance(v, (list, tuple)) else (v,)):
            if hasattr(item, 'eqns') or hasattr(item, 'jaxpr'):
                yield item


def _iter_constants(closed, _seen=None) -> Iterable[object]:
    """Every baked array in a (Closed)Jaxpr: top-level consts, eqn literals,
    and everything the same way down in sub-jaxprs (scan/cond/pjit bodies)."""
    if _seen is None:
        _seen = set()
    if id(closed) in _seen:
        return
    _seen.add(id(closed))
    yield from _consts_of(closed)
    jaxpr = _jaxpr_of(closed)
    for eqn in getattr(jaxpr, 'eqns', ()):
        for invar in eqn.invars:
            val = getattr(invar, 'val', None)
            if val is not None:
                yield val
        yield from (c for sub in _sub_jaxprs(eqn.params)
                    for c in _iter_constants(sub, _seen))


def large_literals(closed,
                   threshold: int = LARGE_LITERAL_BYTES
                   ) -> List[Tuple[int, str]]:
    """(nbytes, 'dtype[shape]') for every baked constant over `threshold`."""
    out = []
    for val in _iter_constants(closed):
        arr = np.asarray(val) if not hasattr(val, 'nbytes') else val
        nbytes = int(getattr(arr, 'nbytes', 0))
        if nbytes > threshold:
            shape = 'x'.join(map(str, getattr(arr, 'shape', ())))
            out.append((nbytes, f'{getattr(arr, "dtype", "?")}[{shape}]'))
    return out


@rule('large-literal', 'B',
      'no program the perfbudget probes lower may close over a baked '
      'constant > 1 MB — big arrays must arrive as arguments (donatable, '
      'shardable), never as compiled-in literals (the PR 9 landmine)',
      needs_programs=True)
def large_literal(ctx: AnalysisContext) -> List[Finding]:
    findings = []
    for rec in ctx.ensure_programs():
        if rec.get('jaxpr') is None:
            continue
        for nbytes, desc in large_literals(rec['jaxpr']):
            findings.append(Finding(
                'large-literal', rec['name'], 0,
                f'baked constant {desc} = {nbytes / 1e6:.1f} MB in the '
                f'traced program (pass it as an argument instead)'))
    return findings


def scan_module_program(path: str,
                        threshold: int = LARGE_LITERAL_BYTES
                        ) -> List[Finding]:
    """Fixture entry point: load a module file defining ``program`` and
    ``example_args()``, trace it, and run the large-literal check with the
    module's own pragmas honored (file-wide waivers apply)."""
    import jax

    name = os.path.splitext(os.path.basename(path))[0]
    spec = importlib.util.spec_from_file_location(f'_timm_tpu_lint_{name}', path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    closed = jax.make_jaxpr(mod.program)(*mod.example_args())
    with open(path, encoding='utf-8') as f:
        pragmas = FilePragmas(f.read(), path=path)
    reason = pragmas.waiver_for('large-literal')
    return [Finding('large-literal', path, 0,
                    f'baked constant {desc} = {nbytes / 1e6:.1f} MB',
                    waived=reason is not None, waive_reason=reason or '')
            for nbytes, desc in large_literals(closed, threshold)]


# ---- dtype-promotion --------------------------------------------------------

_AUDITED_PRIMS = ('exp', 'div')


def unintended_upcasts(closed, declared: str = 'bfloat16'
                       ) -> List[Tuple[str, str]]:
    """(prim, dtype) for every exp/div equation whose OUTPUT left the
    declared dtype — in a declared-bf16 softmax region only the
    max-subtraction may run f32; the exp/div pipeline staying f32 means the
    policy lever silently disconnected."""
    out = []

    def walk(c, seen):
        if id(c) in seen:
            return
        seen.add(id(c))
        jaxpr = _jaxpr_of(c)
        for eqn in getattr(jaxpr, 'eqns', ()):
            prim = getattr(eqn.primitive, 'name', str(eqn.primitive))
            if prim in _AUDITED_PRIMS:
                for outvar in eqn.outvars:
                    dt = str(getattr(outvar.aval, 'dtype', ''))
                    if dt and dt != declared:
                        out.append((prim, dt))
            for sub in _sub_jaxprs(eqn.params):
                walk(sub, seen)

    walk(closed, set())
    return out


def audit_softmax_policy(fn=None, args=None,
                         declared: str = 'bfloat16') -> List[Finding]:
    """Trace `fn(*args)` (default: the canonical softmax_with_policy
    program) under a declared-bf16 softmax policy and report upcasts."""
    import jax
    import jax.numpy as jnp

    from ..layers import config as layer_config

    if fn is None:
        fn = layer_config.softmax_with_policy
        args = (jnp.zeros((2, 4, 16, 16), jnp.bfloat16),)
    with layer_config.set_softmax_dtype(declared):
        closed = jax.make_jaxpr(fn)(*args)
    return [Finding('dtype-promotion', getattr(fn, '__name__', 'program'), 0,
                    f'`{prim}` ran in {dt} inside a declared-{declared} '
                    f'softmax region (policy upcast leak)')
            for prim, dt in unintended_upcasts(closed, declared)]


@rule('dtype-promotion', 'B',
      'under a declared-bf16 softmax policy the exp/div pipeline stays '
      'bf16 (the f32 max-subtraction is the one allowed upcast) — a stray '
      'upcast means set_softmax_dtype silently disconnected')
def dtype_promotion(ctx: AnalysisContext) -> List[Finding]:
    return audit_softmax_policy()
