"""A gated short convolution over token sequences (LFM2, LiquidAI 2025: the
sequence mixer of three layers of four in LFM2-8B-A1B, beside grouped-query
attention on the fourth): no softmax, no positions, a state of `kernel_size - 1`
rows.

For x (B, S, dim), every linear map bias-free:
  (b, c, u) = split of x W_in into three of `dim`, IN THAT ORDER (W_in: dim -> 3 dim)
  z = b * u
  m_t = sum over j < K of w[:, j] * z_{t - (K - 1 - j)}, z_t = 0 for t < 0     (w: (dim, K), a tap a channel)
  y = (c * m) W_out
which is the published `Conv1d(dim, dim, K, groups=dim, padding=K - 1)` cut to
its first S outputs: the LAST tap meets the current position, and nothing is
read across the sequences of a batch.

Two products around a memory-bound middle. The middle (`gated_short_conv`: two
gates and the taps) is a `jax.custom_vjp` whose only residuals are its inputs:
the backward pass recomputes z and m from the product's output (b, c, u), which
is the one large tensor a layer holds (or, with the block rematerialised,
recomputes), reads the cotangent once and writes the product's cotangent once
(`_written`: behind barriers, so that the two products stay operations of their
own and nothing of the middle is recomputed inside them).
Elementwise at float32 inside the fusion, stored at the compute dtype: 4 reads
and writes of (B, S, dim) forward, 7 backward. The taps' gradient is a sum over
every position of the batch, at float32. `in_proj` is ONE kernel (dim, 3 dim),
multiplied as its three column blocks, so that b, c, u and their cotangents are
three tensors and none is split from or concatenated into a wider one.
Nothing is cached here: the two rows of state a decode step carries belong to
`serve/` (ROADMAP "Reach").
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from flax import nnx

from ..utils import tracing
from .weight_init import trunc_normal_

__all__ = ['ShortConv', 'gated_short_conv']


def _shift(z, back: int):
    """z (B, S, dim) -> row t holds z_{t - back}, zero before the sequence starts."""
    return z if back == 0 else jnp.pad(z, ((0, 0), (back, 0), (0, 0)))[:, :z.shape[1]]


def _ahead(d, by: int):
    """The transpose of `_shift`: row t holds d_{t + by}, zero past the sequence's end."""
    return d if by == 0 else jnp.pad(d, ((0, 0), (0, by), (0, 0)))[:, by:]


def _taps(x, y, w, move):
    """sum over j of w[:, j] * move(x * y, K - 1 - j), at float32: the causal convolution of x * y with `_shift`,
    its transpose with `_ahead`. The FACTORS are moved, in the dtype they are stored in, not their product: every
    term is then elementwise on slices of what the caller was given, so one fusion reads them and nothing the size
    of the product is written between (a moved float32 product is: 3.6 x the bytes, v5e, PERF.md section 6, PR 43)."""
    K, f32 = w.shape[1], jnp.float32
    return sum(w[:, j] * (move(x, K - 1 - j).astype(f32) * move(y, K - 1 - j).astype(f32)) for j in range(K))


# What the middle reads and what it gives stand behind an `optimization_barrier`, forward and backward: each tensor is
# WRITTEN once, by the op that makes it, and read by the ops that take it. Left alone XLA moves the middle into the
# products around it: the c gate's product takes the forward middle as its tail, the out_proj weight gradient recomputes
# the result from b, c and u, and each of `in_proj`'s six gradient products recomputes its cotangent from four tensors
# where it would read one (67.8 ms of a step's 128.2 under `sconv.proj` ran in such fusions, v5e: PERF.md section 6, PR 43).
# With the barriers the time under `sconv.mix` is the middle's, all of it and nothing else, which
# `sconv_mix_hbm_share.train` divides its bytes by.
_written = jax.lax.optimization_barrier


def _mix(b, c, u, w):
    b, c, u = _written((b, c, u))
    return _written((c.astype(jnp.float32) * _taps(b, u, w.astype(jnp.float32), _shift)).astype(b.dtype))


def _mix_bwd(res, dy):
    b, c, u, w = res
    dy = _written(dy)
    wf, f32, K = w.astype(jnp.float32), jnp.float32, w.shape[1]
    dz = _taps(dy, c, wf, _ahead)                                     # dm = dy * c, moved back through the taps
    db, dc, du = dz * u.astype(f32), dy.astype(f32) * _taps(b, u, wf, _shift), dz * b.astype(f32)
    dm = dy.astype(f32) * c.astype(f32)
    dw = jnp.stack([(dm * (_shift(b, K - 1 - j).astype(f32) * _shift(u, K - 1 - j).astype(f32))).sum((0, 1)) for j in range(K)], axis=-1)
    db, dc, du = _written((db.astype(b.dtype), dc.astype(c.dtype), du.astype(u.dtype)))
    return db, dc, du, dw.astype(w.dtype)


gated_short_conv = jax.custom_vjp(_mix)
gated_short_conv.defvjp(lambda b, c, u, w: (_mix(b, c, u, w), (b, c, u, w)), _mix_bwd)
gated_short_conv.__doc__ = """b, c, u (B, S, dim), w (dim, K) -> c * causal_conv_w(b * u), (B, S, dim) in b's dtype."""


class ShortConv(nnx.Module):
    """x (B, S, dim) -> y (B, S, dim): `in_proj`, the gated convolution by `taps` (dim, kernel_size), `out_proj`."""

    def __init__(self, dim: int, kernel_size: int = 3, *, dtype=None, param_dtype=jnp.float32, rngs: nnx.Rngs):
        init = trunc_normal_(std=0.02)
        self.dim, self.kernel_size = dim, kernel_size
        self.in_proj = nnx.Linear(dim, 3 * dim, use_bias=False, kernel_init=init, dtype=dtype, param_dtype=param_dtype, rngs=rngs)
        self.taps = nnx.Param(init(rngs.params(), (dim, kernel_size), param_dtype))
        self.out_proj = nnx.Linear(dim, dim, use_bias=False, kernel_init=init, dtype=dtype, param_dtype=param_dtype, rngs=rngs)

    def __call__(self, x):
        dim = self.dim
        with tracing.scope('sconv.proj'):
            # the three gates as three products over column blocks of ONE kernel: the middle's backward pass then
            # writes three cotangents and nobody concatenates them (6 more passes over (B, S, dim) where it did)
            x, kernel = self.in_proj.promote_dtype((x, self.in_proj.kernel[...]), dtype=self.in_proj.dtype)
            b, c, u = (x @ kernel[:, i * dim:(i + 1) * dim] for i in range(3))
        with tracing.scope('sconv.mix'):
            y = gated_short_conv(b, c, u, self.taps[...])
        with tracing.scope('sconv.proj'):
            return self.out_proj(y)
