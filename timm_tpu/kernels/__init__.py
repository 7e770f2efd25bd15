# no-kernel-registry: package init — re-exports, no kernel defined here
"""TPU Pallas kernels a cell's step runs; a call site takes one where its `*_supported` predicate holds, no switch.

Every kernel module here registers a `KernelSpec` (registry.py): a declared
regime (the shapes/dtypes/mask pattern where it claims to beat XLA), a
reference XLA implementation, and a parity tolerance. harness.py turns those
specs into the auto-generated CPU-interpreter parity tests; an
unregistered kernel module fails the lint in tests/test_kernels.py.

Portfolio:
- `flash_attention` — self-attention at image-model lengths as one forward and
  one backward kernel on the qkv product's own layout; the default core of
  `layers/attention.py` wherever `flash_attention_supported` holds.
- `causal_attention` — causal flash attention for long token sequences (JAX's
  Pallas splash-attention kernel, wrapped); the default core of
  `layers/latent_attention.py` wherever its shapes apply.
"""
from .flash_attention import flash_attention, flash_attention_supported, packed_attention
from .causal_attention import causal_flash_attention, causal_flash_supported
from .registry import KernelCase, KernelSpec, all_specs, ensure_registered

__all__ = [
    'flash_attention', 'flash_attention_supported', 'packed_attention',
    'causal_flash_attention', 'causal_flash_supported',
    'KernelCase', 'KernelSpec', 'all_specs', 'ensure_registered',
]
