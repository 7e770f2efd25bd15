# no-kernel-registry: package init — re-exports, no kernel defined here
"""TPU Pallas kernels for hot ops, behind a win-or-delete registry.

Every kernel module here registers a `KernelSpec` (registry.py): a declared
regime (the shapes/dtypes/mask pattern where it claims to beat XLA), a
reference XLA implementation, and a parity tolerance. harness.py turns those
specs into the auto-generated CPU-interpreter parity tests, the perfbudget
`kernels` probe, and `run_kernel_ab`'s keep/delete verdicts (no timed run on
the chip has been made yet: ROADMAP S6); an
unregistered kernel module fails the lint in tests/test_kernels.py.

Portfolio:
- `flash_attention` — self-attention at image-model lengths as one forward and
  one backward kernel on the qkv product's own layout; the default core of
  `layers/attention.py` wherever `flash_attention_supported` holds.
- `fused_adamw` — one-HBM-pass AdamW+EMA update, the opt-in
  `TrainingTask(fused_update=True)` path; optax stays default + oracle.
- `augment_epilogue` — one-pass uint8->erase->mix->normalize epilogue for
  the PR-9 `DeviceAugment` program ('const' erase regime).
- `causal_attention` — causal flash attention for long token sequences (JAX's
  Pallas splash-attention kernel, wrapped); the default core of
  `layers/latent_attention.py` wherever its shapes apply.
"""
from .flash_attention import flash_attention, flash_attention_supported, packed_attention
from .fused_adamw import fused_adamw_apply, fused_adamw_step
from .augment_epilogue import augment_epilogue_supported, augment_image_batch_fused
from .causal_attention import causal_flash_attention, causal_flash_supported
from .registry import KernelCase, KernelSpec, all_specs, ensure_registered

__all__ = [
    'flash_attention', 'flash_attention_supported', 'packed_attention',
    'fused_adamw_apply', 'fused_adamw_step',
    'augment_epilogue_supported', 'augment_image_batch_fused',
    'causal_flash_attention', 'causal_flash_supported',
    'KernelCase', 'KernelSpec', 'all_specs', 'ensure_registered',
]
