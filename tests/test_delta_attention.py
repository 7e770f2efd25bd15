"""The gated delta-rule mixer (`layers/delta_attention.py`) on the CPU: the chunked core against the recurrence taken
position by position (`benchmarks/reference/solar_open2.py` `delta_rule`: a `lax.scan`, no chunk, no triangular system,
so the two share no algebra), outputs and every gradient, at two chunk sizes and at the corners that break a careless
solve; causality, sequences apart, the head shares' sum, and what precision the state needs."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from flax import nnx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.reference.solar_open2 import delta_rule  # noqa: E402
from timm_tpu.layers import KimiDeltaAttention, chunked_delta_rule  # noqa: E402
from timm_tpu.layers.delta_attention import _decayed_pairs, _unit_lower_inverse  # noqa: E402
from timm_tpu.layers.latent_attention import CORE_OUT  # noqa: E402
from timm_tpu.utils import tracing  # noqa: E402

B, H, S, D = 2, 2, 128, 32
TOL = 2e-5      # float32 on both sides: summation order alone (the gradients of q reach 18)


def recurrence(q, k, v, g, beta):
    """The reference's scan, a sequence at a time, on (B, H, S, ..) tensors."""
    t = lambda x: x.swapaxes(0, 1)  # noqa: E731
    return jax.vmap(lambda *a: t(delta_rule(*(t(x) for x in a))))(q, k, v, g, beta)


def inputs(kind: str, seed: int = 0, S: int = S):
    """q, k unit-norm a head (q scaled), v normal; the decay's log and beta by `kind`: 'mixed' spreads the decay a
    position and channel from 0.998 to e^-20 and beta over (0, 2); 'forget' decays near 0 under beta near 2; 'keep'
    decays near 1 under beta near 2 (eigenvalues near -1 for thousands of steps); 'gather' decays near 1 under a small beta
    (little is overwritten or forgotten: the state is a sum over the whole sequence)."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k, v = (jax.random.normal(ks[i], (B, H, S, D)) for i in range(3))
    q = q / jnp.linalg.norm(q, axis=-1, keepdims=True) * D ** -0.5
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    lo, hi, centre = {'mixed': (-6.0, 3.0, 0.0), 'forget': (2.0, 3.5, 6.0), 'keep': (-12.0, -7.0, 6.0), 'gather': (-12.0, -7.0, -4.0)}[kind]
    g = -jnp.exp(jax.random.uniform(ks[3], (B, H, S, D), minval=lo, maxval=hi))
    beta = 2 * jax.nn.sigmoid(centre + {'mixed': 4.0, 'gather': 0.5}.get(kind, 1.0) * jax.random.normal(ks[4], (B, H, S)))
    return q, k, v, g, beta


@pytest.mark.parametrize('kind,chunk,seq', [(kind, chunk, S) for kind in ('mixed', 'forget', 'keep', 'gather') for chunk in (16, 64)]
                         + [('mixed', 64, 32)], ids=str)
def test_the_chunked_core_is_the_recurrence_in_outputs_and_every_gradient(kind, chunk, seq):
    """`seq` 32 under a chunk of 64 is the one-chunk path: a sequence shorter than the chunk is its own chunk, and both
    scans make one step."""
    args = inputs(kind, S=seq)
    if kind in ('forget', 'keep'):
        assert float(args[4].max()) > 1.999 and float(args[4].mean()) > 1.98
    w = jax.random.normal(jax.random.key(9), (B, H, seq, D))
    got, got_grads = jax.jit(jax.value_and_grad(lambda *a: (chunked_delta_rule(*a, chunk=chunk) * w).sum(), argnums=range(5)))(*args)
    want, want_grads = jax.jit(jax.value_and_grad(lambda *a: (recurrence(*a) * w).sum(), argnums=range(5)))(*args)
    out, ref = jax.jit(lambda *a: chunked_delta_rule(*a, chunk=chunk))(*args), jax.jit(recurrence)(*args)
    assert out.dtype == jnp.float32 and bool(jnp.isfinite(out).all()) and float(jnp.abs(ref).max()) > 0.05
    assert float(jnp.abs(out - ref).max()) < TOL * max(1.0, float(jnp.abs(ref).max()))
    for name, a, b in zip('q k v g beta'.split(), got_grads, want_grads):
        assert bool(jnp.isfinite(a).all()) and float(jnp.abs(b).max()) > 1e-4, name
        assert float(jnp.abs(a - b).max()) < TOL * max(1.0, float(jnp.abs(b).max())), (name, float(jnp.abs(a - b).max()))
    assert abs(float(got) - float(want)) < TOL * max(1.0, abs(float(want)))


def test_a_checkpoint_that_keeps_the_cores_name_keeps_the_states_and_the_gradients_are_the_unwrapped_ones():
    """Under `save_only_these_names(CORE_OUT)`, a block's policy, the backward pass finds the chunk-boundary states and
    runs the reversed scan alone: two loops in the program, as without the checkpoint; under a policy that keeps
    nothing the forward scan is run again to make them: three. The gradients are the same to `TOL` either way, and the
    operands' dtypes are the cotangents' (a block hands the core bfloat16 q, k and v)."""
    args = inputs('mixed', seed=2)
    w = jax.random.normal(jax.random.key(9), (B, H, S, D))
    loss = lambda *a: (chunked_delta_rule(*a, chunk=16) * w).sum()  # noqa: E731
    grad = lambda f: jax.jit(jax.value_and_grad(f, argnums=range(5)))  # noqa: E731   the value too: its pass is then live
    plain = grad(loss)
    kept = grad(jax.checkpoint(loss, policy=jax.checkpoint_policies.save_only_these_names(CORE_OUT)))
    again = grad(jax.checkpoint(loss, policy=jax.checkpoint_policies.nothing_saveable))
    loops = lambda f: tracing.scope_loops(f.lower(*args).compile().as_text(), '')  # noqa: E731
    assert (loops(plain), loops(kept), loops(again)) == (2, 2, 3)
    want = plain(*args)[1]
    for got in (kept(*args)[1], again(*args)[1]):
        for name, a, b in zip('q k v g beta'.split(), got, want):
            assert float(jnp.abs(b).max()) > 1e-4 and float(jnp.abs(a - b).max()) < TOL * max(1.0, float(jnp.abs(b).max())), name
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    stored = kept(bf(args[0]), bf(args[1]), bf(args[2]), args[3], args[4])[1]
    assert [x.dtype for x in stored] == [jnp.bfloat16] * 3 + [jnp.float32] * 2
    assert all(float(jnp.abs(a.astype(jnp.float32) - b).max()) < 0.02 * float(jnp.abs(b).max()) for a, b in zip(stored, want))


def test_no_exponent_above_zero_is_taken_and_the_block_inverse_is_exact():
    """Decays down to e^-3000 a step: the pair sums stay finite (a factored e^{-G} would overflow at the third position)
    and equal the plain double sum wherever that is representable; the merged block inverse times the matrix is I."""
    k = jax.random.normal(jax.random.key(0), (3, 64, 8))
    k = k / jnp.linalg.norm(k, axis=-1, keepdims=True)
    q = jax.random.normal(jax.random.key(1), (3, 64, 8))
    g = -jnp.exp(jax.random.uniform(jax.random.key(2), (3, 64, 8), minval=-5.0, maxval=8.0))
    G = jnp.cumsum(g, axis=1)
    A, P = jax.jit(lambda k, q, G: _decayed_pairs((k, q), k, G, 16))(k, q, G)
    seen = jnp.arange(64)[:, None] >= jnp.arange(64)[None, :]
    plain = lambda x: jnp.where(seen, (x[:, :, None] * k[:, None] * jnp.exp(jnp.where(  # noqa: E731
        seen[:, :, None], G[:, :, None] - G[:, None], -jnp.inf))).sum(-1), 0.0)
    assert bool(jnp.isfinite(A).all() & jnp.isfinite(P).all()) and float(G.min()) < -3000
    assert float(jnp.abs(A - plain(k)).max()) < 1e-5 and float(jnp.abs(P - plain(q)).max()) < 1e-5
    assert float(jnp.abs(jnp.triu(A, 1)).max()) == 0.0
    L = jnp.eye(64) + 2.0 * jnp.tril(A, -1)
    for sub in (16, 64):
        T = jax.jit(_unit_lower_inverse, static_argnums=1)(L, sub)
        assert float(jnp.abs(jnp.matmul(T, L, precision='highest') - jnp.eye(64)).max()) < 1e-5
        assert float(jnp.abs(jnp.triu(T, 1)).max()) == 0.0


def test_the_core_is_causal_and_keeps_sequences_apart():
    q, k, v, g, beta = inputs('mixed', seed=3)
    core = jax.jit(lambda *a: chunked_delta_rule(*a, chunk=16))
    out = core(q, k, v, g, beta)
    t = 53                                              # inside a chunk, inside a sub-block
    later = lambda x, key: x.at[:, :, t + 1:].set(jax.random.normal(jax.random.key(key), x[:, :, t + 1:].shape))  # noqa: E731
    moved = core(later(q, 1), later(k, 2), later(v, 3), -jnp.abs(later(g, 4)), jnp.abs(later(beta, 5)) % 2)
    assert float(jnp.abs(moved[:, :, :t + 1] - out[:, :, :t + 1]).max()) == 0.0 and float(jnp.abs(moved[:, :, t + 1:] - out[:, :, t + 1:]).max()) > 1e-3
    other = core(*(x.at[1].set(x[1] * 0.5) for x in (q, k, v, g, beta)))
    assert float(jnp.abs(other[0] - out[0]).max()) == 0.0 and float(jnp.abs(other[1] - out[1]).max()) > 1e-3
    # a batch of two is two batches of one: no state crosses the batch
    alone = core(*(x[1:] for x in (q, k, v, g, beta)))
    assert float(jnp.abs(alone[0] - out[1]).max()) < 1e-6
    with pytest.raises(ValueError, match='chunk'):
        chunked_delta_rule(q, k, v, g, beta, chunk=48)


def test_a_float32_state_meets_the_tolerance_bfloat16_operands_meet_and_a_bfloat16_state_fails():
    """Where the state is a long sum (decays near 1, little overwritten) over 64 chunks: rounding q, k and v to bfloat16
    moves the output by 0.3 % of its largest value; carrying the STATE in bfloat16 rounds the whole sum once a chunk, loses
    what a chunk adds to it, and nothing decays the loss: 0.85 %. The tolerance is stated between the two. (Where beta is
    large the rule overwrites what it rounded and a bfloat16 state reads no worse than bfloat16 operands: 0.26 against
    0.38 % under 'keep'; the state's precision is for the layers that remember.)"""
    q, k, v, g, beta = inputs('gather', seed=5, S=1024)
    want = jax.jit(recurrence)(q, k, v, g, beta)
    scale, tolerance = float(jnp.abs(want).max()), 0.005
    gap = lambda out: float(jnp.abs(out - want).max()) / scale  # noqa: E731
    bf = lambda x: x.astype(jnp.bfloat16)  # noqa: E731
    core = jax.jit(chunked_delta_rule, static_argnames=('chunk', 'state_dtype'))
    exact = gap(core(q, k, v, g, beta, chunk=16))
    operands = gap(core(bf(q), bf(k), bf(v), g, beta, chunk=16))
    state = gap(core(q, k, v, g, beta, chunk=16, state_dtype=jnp.bfloat16))
    assert exact < 1e-4 and operands < 0.8 * tolerance and 1.4 * tolerance < state, (exact, operands, state)


def _whole_and_shares(shares: int):
    kw = dict(head_dim=16, conv_size=4, gate_rank=8, chunk=16)
    whole = KimiDeltaAttention(64, 8, rngs=nnx.Rngs(0), **kw)
    leaves = {'.'.join(map(str, path)): leaf for path, leaf in nnx.to_flat_state(nnx.state(whole, nnx.Param))}
    parts = []
    for rank in range(shares):
        part = KimiDeltaAttention(64, 8, heads_held=8 // shares, head_offset=rank * (8 // shares), rngs=nnx.Rngs(1), **kw)
        for path, leaf in nnx.to_flat_state(nnx.state(part, nnx.Param)):
            name = '.'.join(map(str, path))
            leaf[...] = part.take_heads(name, leaves[name][...])
        parts.append(part)
    return whole, parts, leaves


def test_the_eight_head_shares_parts_sum_to_the_whole_mixers_output():
    whole, parts, leaves = _whole_and_shares(8)
    assert {k: v.shape for k, v in leaves.items()} == {
        'q_proj.kernel': (64, 128), 'k_proj.kernel': (64, 128), 'v_proj.kernel': (64, 128), 'q_taps': (128, 4), 'k_taps': (128, 4),
        'v_taps': (128, 4), 'f_down.kernel': (64, 8), 'f_up.kernel': (8, 128), 'beta_proj.kernel': (64, 8), 'A_log': (8,),
        'dt_bias': (128,), 'g_down.kernel': (64, 8), 'g_up.kernel': (8, 128), 'o_norm.scale': (16,), 'o_proj.kernel': (128, 64)}
    A, dt = jnp.exp(whole.A_log[...]), jax.nn.softplus(whole.dt_bias[...])
    assert 1.0 <= float(A.min()) and float(A.max()) <= 16.0 and 1e-3 <= float(dt.min()) and float(dt.max()) <= 0.1 + 1e-6
    x = jax.random.normal(jax.random.key(2), (2, 48, 64))
    run = nnx.jit(lambda m, x: m(x))
    want = run(whole, x)
    total = sum(run(part, x) for part in parts)
    assert parts[3].q_proj.kernel.shape == (64, 16) and parts[3].A_log.shape == (1,) and parts[3].heads_held == 1
    assert float(jnp.abs(total - want).max()) < 1e-5 and float(jnp.abs(want).max()) > 1e-3
    assert float(jnp.abs(run(parts[0], x) - want).max()) > 1e-3
    with pytest.raises(ValueError, match='heads'):
        KimiDeltaAttention(64, 8, heads_held=4, head_offset=6, rngs=nnx.Rngs(0))


def test_the_mixer_is_causal_per_sequence_and_differentiable_through_its_barriers():
    whole, _, _ = _whole_and_shares(1)
    x = jax.random.normal(jax.random.key(4), (2, 32, 64))
    run = nnx.jit(lambda m, x: m(x))
    out = run(whole, x)
    t = 20
    moved = run(whole, x.at[:, t + 1:].set(0.0))
    assert float(jnp.abs(moved[:, :t + 1] - out[:, :t + 1]).max()) < 1e-6 and float(jnp.abs(moved[:, t + 1:] - out[:, t + 1:]).max()) > 1e-4
    assert float(jnp.abs(run(whole, x.at[1].set(0.0))[0] - out[0]).max()) < 1e-6
    grads = nnx.jit(nnx.grad(lambda m: (m(x) ** 2).sum()))(whole)
    norms = {'.'.join(map(str, p)): float(jnp.linalg.norm(g[...])) for p, g in nnx.to_flat_state(nnx.state(grads))}
    assert len(norms) == 15 and all(n > 0 and n == n for n in norms.values()), norms
