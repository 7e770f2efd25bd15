"""Plain float32 building blocks of the references, and the lower precisions
their controls run in.

`precision` names how every matrix multiplication and convolution rounds its
operands: 'float32' (the reference: float32 operands, `Precision.HIGHEST`, so
a TPU does not quietly take one bfloat16 pass); 'bfloat16'; 'float8' (each
operand scaled by its largest magnitude to the top of `float8_e4m3fn`'s range,
rounded to it and scaled back; products accumulated in float32). The casts are
differentiated as they stand, so the backward pass's cotangents are rounded to
the narrow type too: the arithmetic is in that precision both ways.
'float8_wide_grad' rounds the operands alike but lets gradients pass
unrounded (straight-through), the most careful float8 a later PR could build
(PERF.md section 7: the compared numbers do not tell it from bfloat16).
Nothing here imports the program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

PRECISIONS = ('float32', 'bfloat16', 'float8', 'float8_wide_grad')
FLOAT8_MAX = 448.0  # largest finite float8_e4m3fn


def _operand(x, precision: str):
    x = x.astype(jnp.float32)
    if precision == 'float32':
        return x
    if precision == 'bfloat16':
        return x.astype(jnp.bfloat16).astype(jnp.float32)
    if precision in ('float8', 'float8_wide_grad'):
        scale = jnp.maximum(jnp.max(jnp.abs(lax.stop_gradient(x))), 1e-30) / FLOAT8_MAX
        rounded = (x / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale
        return rounded if precision == 'float8' else x + lax.stop_gradient(rounded - x)
    raise ValueError(f'unknown precision {precision!r}, have {PRECISIONS}')


def matmul(x, w, precision: str):
    return jnp.matmul(_operand(x, precision), _operand(w, precision),
                      precision=lax.Precision.HIGHEST)


def einsum(spec: str, a, b, precision: str):
    return jnp.einsum(spec, _operand(a, precision), _operand(b, precision),
                      precision=lax.Precision.HIGHEST)


def conv(x, kernel, stride: int, padding, precision: str, groups: int = 1):
    """NHWC input, HWIO kernel."""
    return lax.conv_general_dilated(
        _operand(x, precision), _operand(kernel, precision), (stride, stride), padding,
        dimension_numbers=('NHWC', 'HWIO', 'NHWC'), feature_group_count=groups,
        precision=lax.Precision.HIGHEST)


def layer_norm(x, scale, bias, eps: float):
    mean = x.mean(-1, keepdims=True)
    var = jnp.square(x - mean).mean(-1, keepdims=True)
    return (x - mean) * lax.rsqrt(var + eps) * scale + bias


def gelu(x):
    """The exact (erf) GELU of arXiv:1606.08415, as both papers' code uses."""
    return jax.nn.gelu(x, approximate=False)


def drop_path(x, keep_rows, rate: float):
    """Stochastic depth (arXiv:1603.09382): the residual branch of a whole row
    is dropped with probability `rate`, survivors scaled by 1/(1-rate).
    `keep_rows` is the step's draw, one boolean a row, or None where none was
    drawn (rate 0, or evaluation)."""
    if keep_rows is None or rate == 0.0:
        return x
    keep_rows = keep_rows.reshape((x.shape[0],) + (1,) * (x.ndim - 1))
    return jnp.where(keep_rows, x / (1.0 - rate), 0.0)


def drop_path_rates(rate: float, depth: int):
    """Linear ramp 0 .. rate over `depth` residual blocks."""
    return [rate * i / max(depth - 1, 1) for i in range(depth)]
