# no-kernel-registry: infrastructure module — consumes the registry, not a kernel
"""Win-or-delete harness over the kernel registry.

Three consumers, one spec table (registry.py):

1. **Parity** — `parity_check` runs kernel vs reference at a case's `dry`
   shapes with BOTH arms jitted (on non-TPU backends the kernel arm lowers
   via ``pallas_call(interpret=True)``). Jitting both arms matters: XLA
   normalizes bf16 arithmetic to f32 compute, so an eager reference would
   round intermediates the compiled train step never rounds.
   tests/test_kernels.py parametrizes over `parity_cases()` — that's the
   auto-generated per-kernel parity test.

2. **Budgets** — `lower_case` lowers both arms and reports jaxpr eqn counts
   plus the bytes story: analytic one-pass `io_bytes` for the kernel arm
   (registry.default_io_bytes — interpret-mode cost_analysis numbers are
   emulation artifacts, so we budget the HBM contract instead) vs the
   compiled reference's ``cost_analysis()['bytes accessed']``. The
   perfbudget `kernels` probe pins these per kernel.

3. **Verdicts** — `ab_verdict` produces the keep/delete/pending line for
   `run_kernel_ab`: parity failure is an
   immediate `delete` (a wrong kernel loses regardless of speed); on a
   backend outside the spec's declared `backends` the verdict is `pending`
   (a timed run on the claimed hardware settles it); otherwise
   the kernel must win wall-clock at EVERY declared regime case or it is
   `delete`.
"""
from __future__ import annotations

import functools
import time
from typing import Dict, List, Optional, Tuple

from . import registry
from .registry import KernelCase, KernelSpec, default_io_bytes

__all__ = ['parity_cases', 'parity_check', 'lower_case', 'kernel_metrics',
           'ab_case', 'ab_verdict', 'run_kernel_ab', 'format_verdict_line']


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


def lower_case(spec: KernelSpec, case: KernelCase, seed: int = 0) -> Dict:
    """Lower both arms at the case's dry shapes; return the budgetable
    numbers (all deterministic on a fixed jax/XLA version)."""
    import jax

    from ..utils.compile_cache import count_jaxpr_eqns

    inputs = spec.make_inputs(seed=seed, **case.dry)
    fk = _jit_arm(spec.kernel_fn, case.statics)
    fr = _jit_arm(spec.reference_fn, case.statics)
    eqns_k = count_jaxpr_eqns(jax.make_jaxpr(fk)(inputs).jaxpr)
    eqns_r = count_jaxpr_eqns(jax.make_jaxpr(fr)(inputs).jaxpr)
    cost = fr.lower(inputs).compile().cost_analysis()
    if isinstance(cost, (list, tuple)):
        cost = cost[0] if cost else {}
    ref_bytes = int(cost.get('bytes accessed', 0))
    io = default_io_bytes(spec, case, inputs=inputs)
    return {
        'kernel': spec.name,
        'case': case.name,
        'kernel_eqns': int(eqns_k),
        'ref_eqns': int(eqns_r),
        'io_bytes': int(io),
        'ref_bytes_accessed': ref_bytes,
        'wins_bytes': bool(io < ref_bytes),
    }


def kernel_metrics(seed: int = 0) -> Dict[str, object]:
    """Flat metrics dict for the perfbudget `kernels` probe: per kernel the
    first declared case is the budget anchor."""
    metrics: Dict[str, object] = {'kernels_registered': len(registry.all_specs())}
    for spec in registry.all_specs():
        m = lower_case(spec, spec.cases[0], seed=seed)
        metrics[f'{spec.name}_eqns'] = m['kernel_eqns']
        metrics[f'{spec.name}_ref_eqns'] = m['ref_eqns']
        metrics[f'{spec.name}_io_bytes'] = m['io_bytes']
        metrics[f'{spec.name}_ref_bytes_accessed'] = m['ref_bytes_accessed']
        metrics[f'{spec.name}_wins_bytes'] = m['wins_bytes']
    return metrics


def _best_ms(fn, inputs, steps: int) -> float:
    import jax
    jax.block_until_ready(fn(inputs))  # warmup / compile
    best = float('inf')
    for _ in range(max(1, steps)):
        t0 = time.perf_counter()
        jax.block_until_ready(fn(inputs))
        best = min(best, (time.perf_counter() - t0) * 1e3)
    return best


def ab_case(spec: KernelSpec, case: KernelCase, *, live: bool = False,
            steps: int = 5, seed: int = 0) -> Dict:
    """Time kernel vs reference at one regime case (dry or live arm)."""
    inputs = spec.make_inputs(seed=seed, **(case.live if live else case.dry))
    fk = _jit_arm(spec.kernel_fn, case.statics)
    fr = _jit_arm(spec.reference_fn, case.statics)
    tk = _best_ms(fk, inputs, steps)
    tr = _best_ms(fr, inputs, steps)
    return {'case': case.name, 'arm': 'live' if live else 'dry',
            'kernel_ms': round(tk, 4), 'ref_ms': round(tr, 4),
            'win': bool(tk < tr)}


def ab_verdict(spec: KernelSpec, *, live: bool = False, steps: int = 5,
               seed: int = 0) -> Dict:
    """The keep/delete/pending record for one kernel."""
    import jax

    backend = jax.default_backend()
    rec: Dict = {
        'kernel': spec.name,
        'regime': spec.regime,
        'gate': spec.gate,
        'backend': backend,
        'backends_claimed': list(spec.backends),
    }
    parity = [parity_check(spec, case, seed=seed) for case in spec.cases]
    rec['parity_max_err'] = max(p['max_abs_err'] for p in parity)
    rec['parity_tol'] = spec.parity_tol
    rec['parity_ok'] = all(p['ok'] for p in parity)
    if not rec['parity_ok']:
        rec['verdict'] = 'delete'
        rec['reason'] = (f'parity failure: max err {rec["parity_max_err"]:.3g} '
                         f'> tol {spec.parity_tol:.3g} — wrong beats slow')
        return rec
    if backend not in spec.backends:
        rec['verdict'] = 'pending'
        rec['reason'] = (f'regime claims {"/".join(spec.backends)}; this run is '
                         f'on {backend} (parity only) — a timed run on the '
                         f'claimed hardware settles the gate')
        return rec
    rec['cases'] = [ab_case(spec, case, live=live, steps=steps, seed=seed)
                    for case in spec.cases]
    wins = all(c['win'] for c in rec['cases'])
    rec['verdict'] = 'keep' if wins else 'delete'
    lost = [c['case'] for c in rec['cases'] if not c['win']]
    rec['reason'] = ('wins wall-clock at every declared regime case' if wins
                     else f'loses to the XLA reference at: {", ".join(lost)}')
    return rec


def run_kernel_ab(*, live: bool = False, steps: int = 5,
                  seed: int = 0) -> List[Dict]:
    """One verdict record per registered kernel (sorted by name)."""
    return [ab_verdict(spec, live=live, steps=steps, seed=seed)
            for spec in registry.all_specs()]


def format_verdict_line(rec: Dict) -> str:
    return (f"kernel {rec['kernel']}: {rec['verdict'].upper()} "
            f"[parity {rec['parity_max_err']:.2e} <= {rec['parity_tol']:.0e}: "
            f"{'ok' if rec['parity_ok'] else 'FAIL'}] — {rec['reason']}")
