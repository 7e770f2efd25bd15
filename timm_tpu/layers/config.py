"""Global layer behaviour flags + the compute-precision policy.

TPU-native re-design of the reference's layer-config singleton
(reference: timm/layers/config.py:101-165). Unlike the reference we keep the
surface minimal: flags only select which code path gets *traced* (e.g. Pallas
flash attention vs. plain XLA dot-product attention, fp32 vs bf16 softmax
internals); they never mutate state inside a jitted computation, so they are
safe process-level switches.

Compute-precision policy (mirrors the reference's `fast_norm` global):

* ``softmax_dtype`` — dtype for attention-softmax internals. Default ``None``
  keeps the historical fp32-upcast softmax bit-for-bit. Setting ``bfloat16``
  traces the fast path: max-subtraction in fp32 (for range safety), exp and
  normalization in bf16 — halving vector-unit and VMEM traffic on the
  (B·H, N, N) probability tensor (PERF.md §2 item 2).
* ``norm_internal_dtype`` — dtype for LayerNorm/RmsNorm statistics. Default
  ``None`` keeps the framework fp32-stats path bit-for-bit; ``bfloat16``
  computes mean/var in bf16 (PERF.md: ~25 LayerNorms upcast per ViT step).

The norm policy is seeded from ``TIMM_TPU_NORM_DTYPE`` (values: ``float32`` |
``bfloat16`` | empty = default), and both are overridable per call/instance.
Every knob ships OFF by default with an exact-parity guarantee when disabled.
"""
from __future__ import annotations

import os
from contextlib import contextmanager
from typing import Optional

__all__ = [
    'is_exportable', 'is_scriptable', 'set_exportable', 'set_scriptable',
    'use_fused_attn', 'set_fused_attn',
    'softmax_dtype', 'set_softmax_dtype', 'norm_internal_dtype',
    'set_norm_internal_dtype', 'resolve_dtype_arg', 'softmax_with_policy',
]

# Pallas flash-attention toggle. 0 = never, 1 = on TPU when shapes allow,
# 2 = always (error if unsupported).  Seeded from env like TIMM_FUSED_ATTN.
_USE_FUSED_ATTN = int(os.environ.get('TIMM_TPU_FUSED_ATTN', '1'))

# Export mode: prefer the most portable lowering (no Pallas custom kernels).
_EXPORTABLE = False
# Kept for API parity with the reference; TorchScript has no TPU analogue.
_SCRIPTABLE = False


def resolve_dtype_arg(value, allow_none: bool = True):
    """'bfloat16' / 'float32' / '' / dtype / None → jnp dtype or None."""
    import jax.numpy as jnp
    if value is None or value == '':
        if allow_none:
            return None
        raise ValueError('a dtype is required')
    if isinstance(value, str):
        v = value.strip().lower()
        if v in ('', 'none', 'default'):
            return None
        return jnp.dtype({'bf16': 'bfloat16', 'fp32': 'float32', 'f32': 'float32'}.get(v, v))
    return jnp.dtype(value)


_SOFTMAX_DTYPE = None
_NORM_DTYPE = resolve_dtype_arg(os.environ.get('TIMM_TPU_NORM_DTYPE', ''))


def is_exportable() -> bool:
    return _EXPORTABLE


def is_scriptable() -> bool:
    return _SCRIPTABLE


@contextmanager
def set_exportable(value: bool):
    global _EXPORTABLE
    prev = _EXPORTABLE
    _EXPORTABLE = value
    try:
        yield
    finally:
        _EXPORTABLE = prev


@contextmanager
def set_scriptable(value: bool):
    global _SCRIPTABLE
    prev = _SCRIPTABLE
    _SCRIPTABLE = value
    try:
        yield
    finally:
        _SCRIPTABLE = prev


def use_fused_attn(experimental: bool = False) -> bool:
    """Whether attention layers should trace the Pallas fused kernel path."""
    if _EXPORTABLE:
        return False
    if _USE_FUSED_ATTN > 1:
        return True
    if _USE_FUSED_ATTN < 1:
        return False
    # Default: fused on real TPU backends only; CPU tests use the XLA path.
    import jax
    return jax.default_backend() == 'tpu'


def set_fused_attn(enable: bool = True, experimental: bool = False):
    global _USE_FUSED_ATTN
    _USE_FUSED_ATTN = 2 if (enable and experimental) else (1 if enable else 0)


# ---- compute-precision policy ------------------------------------------------

def softmax_dtype():
    """Process-level softmax internal dtype. None = legacy fp32 upcast."""
    return _SOFTMAX_DTYPE


def norm_internal_dtype():
    """Process-level norm-statistics dtype. None = framework fp32 stats."""
    return _NORM_DTYPE


class _PolicySetting:
    """Sets a module-level policy global immediately; restores the previous
    value if used as a context manager. Supports both styles:

        set_softmax_dtype('bfloat16')          # process-level, stays set
        with set_softmax_dtype('bfloat16'):    # scoped (tests / A-B)
            ...
    """

    def __init__(self, name: str, dtype):
        self._name = name
        self._prev = globals()[name]
        globals()[name] = resolve_dtype_arg(dtype)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        globals()[self._name] = self._prev
        return False


def set_softmax_dtype(dtype):
    """Set the softmax policy dtype (plain call or context manager)."""
    return _PolicySetting('_SOFTMAX_DTYPE', dtype)


def set_norm_internal_dtype(dtype):
    """Set the norm-internals policy dtype (plain call or context manager)."""
    return _PolicySetting('_NORM_DTYPE', dtype)


def softmax_with_policy(x, axis: int = -1, dtype=None):
    """The canonical softmax for attention layers.

    This is the ONLY place in `timm_tpu.layers` allowed to pick a softmax
    compute dtype (tests/test_layers.py lints for strays). `dtype=None`
    defers to the process policy; the policy's own default (None) is the
    historical fp32-upcast softmax, bit-identical to the pre-policy code.
    The result is returned in the *compute* dtype — callers cast back to
    their activation dtype, exactly as before.
    """
    import jax
    import jax.numpy as jnp
    dt = resolve_dtype_arg(dtype) if dtype is not None else _SOFTMAX_DTYPE
    if dt is None or dt == jnp.float32:
        return jax.nn.softmax(x.astype(jnp.float32), axis=axis)
    # Fast path: subtract the row max in fp32 (range safety — bf16 has fp32's
    # exponent but only 8 mantissa bits, so the subtraction itself is the
    # step that must not lose the large-magnitude cancellation), then exp and
    # normalize in the reduced dtype.
    xf = x.astype(jnp.float32)
    m = jax.lax.stop_gradient(jnp.max(xf, axis=axis, keepdims=True))
    e = jnp.exp((xf - m).astype(dt))
    return e / jnp.sum(e, axis=axis, keepdims=True)
