# no-kernel-registry: infrastructure module — the registry itself, not a kernel
"""Kernel registry: every Pallas kernel declares its win regime as DATA.

SNIPPETS.md [3]'s pjit premise is that the compiler owns layout, so a
hand-written kernel is guilty until proven innocent: it must carry (a) a
**reference XLA implementation** (the parity oracle AND the A/B baseline it
has to beat), (b) a **declared regime** — the concrete shapes/dtypes/mask
pattern where it claims to win, split into a `dry` arm (tiny, CPU-interpret,
tier-1-smoked) and a `live` arm (the claimed shapes, decided on hardware) —
and (c) a **parity tolerance**. harness.py consumes these specs to
auto-generate the per-kernel parity test; an
unregistered kernel module cannot land (tests/test_kernels.py lint).

Kernel modules register themselves at import time; `ensure_registered()`
imports the portfolio so registry consumers never observe a half-populated
table.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Callable, Dict, Tuple

__all__ = ['KernelCase', 'KernelSpec', 'register', 'get', 'all_specs', 'kernel_names', 'ensure_registered']

# modules whose import populates the registry (the portfolio)
_PORTFOLIO = ('flash_attention', 'causal_attention')


@dataclasses.dataclass(frozen=True)
class KernelCase:
    """One point of a kernel's declared regime. `dry` / `live` are kwargs for
    the spec's `make_inputs` — same runner, different scale: dry is tiny and CPU-provable, live is the claimed shape
    the hardware A/B decides on. `statics` are forwarded to BOTH the kernel
    and the reference (compile-time config: dtypes, masks, coefficients)."""
    name: str
    dry: Dict = dataclasses.field(default_factory=dict)
    live: Dict = dataclasses.field(default_factory=dict)
    statics: Dict = dataclasses.field(default_factory=dict)
    desc: str = ''


@dataclasses.dataclass(frozen=True)
class KernelSpec:
    """A registered kernel: implementation + oracle + executable win claim.

    `kernel_fn` / `reference_fn` share one signature: ``fn(**inputs,
    **case.statics)`` where `inputs = make_inputs(seed=..., **case.dry)`
    (or `.live`). Outputs may be a single array or a pytree; parity compares
    them leaf-for-leaf."""
    name: str
    module: str                      # python module the lint checks off
    parity_tol: float
    kernel_fn: Callable
    reference_fn: Callable
    make_inputs: Callable            # (seed=0, **case_kwargs) -> {name: array}
    cases: Tuple[KernelCase, ...]

    def __post_init__(self):
        if not self.cases:
            raise ValueError(f'kernel {self.name!r}: declared regime is empty '
                             '(at least one KernelCase required)')
        if not (self.parity_tol > 0):
            raise ValueError(f'kernel {self.name!r}: parity_tol must be > 0')


_REGISTRY: Dict[str, KernelSpec] = {}


def register(spec: KernelSpec) -> KernelSpec:
    if spec.name in _REGISTRY:
        raise ValueError(f'kernel {spec.name!r} already registered')
    _REGISTRY[spec.name] = spec
    return spec


def ensure_registered() -> None:
    """Import the portfolio modules (idempotent) so every kernel's
    import-time registration has run before the registry is consumed."""
    for mod in _PORTFOLIO:
        importlib.import_module(f'{__package__}.{mod}')


def get(name: str) -> KernelSpec:
    ensure_registered()
    if name not in _REGISTRY:
        raise KeyError(f'kernel {name!r} not registered '
                       f'(have: {sorted(_REGISTRY)})')
    return _REGISTRY[name]


def all_specs() -> Tuple[KernelSpec, ...]:
    ensure_registered()
    return tuple(_REGISTRY[name] for name in sorted(_REGISTRY))


def kernel_names() -> Tuple[str, ...]:
    ensure_registered()
    return tuple(sorted(_REGISTRY))
