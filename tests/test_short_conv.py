"""The gated short convolution (`layers/short_conv.py`) on the CPU: the middle against a literal per-position loop,
its hand-written backward pass against autodiff of the plain form, causality, the sequences of a batch apart, the
shortest sequences, and the layer against the plain reference's."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from timm_tpu.layers import ShortConv, gated_short_conv  # noqa: E402
from timm_tpu.layers.short_conv import _mix  # noqa: E402


def _inputs(B=2, S=12, dim=8, K=3, seed=0):
    """b, c, u (B, S, dim) and the taps (dim, K)."""
    rng = np.random.default_rng(seed)
    return (*(jnp.asarray(rng.standard_normal((B, S, dim)), jnp.float32) for _ in range(3)), jnp.asarray(rng.standard_normal((dim, K)), jnp.float32))


def literal(b, c, u, w):
    """The equations with loops: z_t = b_t * u_t; m_t = sum_j w[:, j] * z_{t - (K - 1 - j)}; y_t = c_t * m_t."""
    b, c, u, w = (np.asarray(t, np.float64) for t in (b, c, u, w))
    (B, S, dim), K = b.shape, w.shape[1]
    y = np.zeros((B, S, dim))
    for n in range(B):
        z = b[n] * u[n]
        for t in range(S):
            m = np.zeros(dim)
            for j in range(K):
                if t - (K - 1 - j) >= 0:
                    m += w[:, j] * z[t - (K - 1 - j)]
            y[n, t] = c[n, t] * m
    return y


@pytest.mark.parametrize('S,K', [(12, 3), (1, 3), (2, 3), (7, 4), (5, 1)])
def test_the_middle_is_the_literal_loop(S, K):
    args = _inputs(S=S, K=K)
    assert np.abs(np.asarray(gated_short_conv(*args)) - literal(*args)).max() < 1e-5


@pytest.mark.parametrize('S', [12, 1, 2])
def test_the_hand_written_backward_pass_is_the_plain_forms_gradient(S):
    args = _inputs(S=S)
    weight = jnp.asarray(np.random.default_rng(1).standard_normal((2, S, 8)), jnp.float32)
    got = jax.grad(lambda *a: (gated_short_conv(*a) * weight).sum(), argnums=(0, 1, 2, 3))(*args)
    want = jax.grad(lambda *a: (_mix(*a) * weight).sum(), argnums=(0, 1, 2, 3))(*args)
    assert all(float(jnp.abs(g - r).max()) < 1e-5 for g, r in zip(got, want))
    # what the backward pass keeps: the product's output and the taps, nothing computed from them
    kept = jax.vjp(gated_short_conv, *args)[1]
    assert sorted(x.shape for x in jax.tree.leaves(kept)) == sorted(a.shape for a in args)


def test_bfloat16_inputs_give_bfloat16_out_and_float32_taps_gradient():
    b, c, u, w = _inputs()
    y, back = jax.vjp(gated_short_conv, *(t.astype(jnp.bfloat16) for t in (b, c, u)), w)
    *d_gates, d_w = back(jnp.ones_like(y))
    assert {y.dtype, *(d.dtype for d in d_gates)} == {jnp.dtype(jnp.bfloat16)} and d_w.dtype == jnp.float32
    assert float(jnp.abs(y.astype(jnp.float32) - gated_short_conv(b, c, u, w)).max()) < 0.1


def test_the_middle_stands_behind_barriers_forward_and_backward():
    """What `sconv_mix_hbm_share.train` divides by: what the middle reads (b, c, u; the result's cotangent) and what it
    gives (the result; the three cotangents) pass an `optimization_barrier`, under the middle's own scope, so that no
    product's fusion holds an op of the middle and the time under `sconv.mix` is the middle's."""
    layer = ShortConv(16, rngs=nnx.Rngs(0))
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 10, 16)), jnp.float32)
    graphdef, state = nnx.split(layer)
    loss = lambda st: (nnx.merge(graphdef, st)(x) ** 2).sum()  # noqa: E731
    text = jax.jit(jax.grad(loss)).lower(state).as_text(debug_info=True).splitlines()
    ops = [l for l in text if 'stablehlo.optimization_barrier' in l]
    assert sorted(l.count('tensor<2x10x16xf32>') for l in ops) == [1, 1, 3, 3]      # result, its cotangent; b c u, theirs
    where = [l for l in text if l.startswith('#loc') and 'optimization_barrier' in l]
    assert where and all('sconv.mix' in l for l in where)


def test_the_mixer_is_causal_and_keeps_the_sequences_of_a_batch_apart():
    layer = ShortConv(16, rngs=nnx.Rngs(0))
    x = jnp.asarray(np.random.default_rng(2).standard_normal((3, 10, 16)), jnp.float32)
    y = layer(x)
    later = x.at[:, 6:].set(5.0)                              # inputs after position 5 change
    assert float(jnp.abs(layer(later)[:, :6] - y[:, :6]).max()) == 0.0 and float(jnp.abs(layer(later)[:, 6:] - y[:, 6:]).max()) > 0
    other = x.at[1].set(-3.0)                                 # another sequence of the batch changes
    assert float(jnp.abs(layer(other)[jnp.array([0, 2])] - y[jnp.array([0, 2])]).max()) == 0.0
    assert float(jnp.abs(layer(x[:1])[0] - y[0]).max()) < 1e-6   # a sequence alone gives what it gives in a batch
    # the first output reads the LAST tap alone: the state before a sequence is zero rows
    bcu = layer.in_proj(x[:, :1])
    b, c, u = jnp.split(bcu, 3, axis=-1)
    assert float(jnp.abs(layer(x[:, :1]) - layer.out_proj(c * layer.taps[...][:, 2] * b * u)).max()) < 1e-6


def test_the_layer_is_the_references():
    layer = ShortConv(16, rngs=nnx.Rngs(3))
    x = jnp.asarray(np.random.default_rng(4).standard_normal((2, 9, 16)), jnp.float32)
    p = {'blocks.0.conv.in_proj.kernel': layer.in_proj.kernel[...], 'blocks.0.conv.taps': layer.taps[...],
         'blocks.0.conv.out_proj.kernel': layer.out_proj.kernel[...]}
    want = jnp.stack([ref.short_conv({'conv_L_cache': 3}, p, 'blocks.0.', x[n], 'float32') for n in range(2)])
    assert layer.taps[...].shape == (16, 3) and layer.in_proj.kernel[...].shape == (16, 48)
    assert float(jnp.abs(layer(x) - want).max()) < 1e-5
