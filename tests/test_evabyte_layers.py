"""The chunk-pooled linear attention of EvaByte, piece by piece on the CPU
(`layers/chunked_linear_attention.py`, the chunk-window mask of
`kernels/causal_attention.py`): the mask against an explicit boolean array
written with loops, on the XLA path and on the interpreted kernel; the whole
mixer against a dense O(N^2) loop that builds each query's key set by hand; what
the two learned vectors, the unit offset and the order of turn and pooling do;
the tiles and pairs the step counts. float32 throughout.
"""
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

import timm_tpu  # noqa: E402
from benchmarks.reference import evabyte as ref  # noqa: E402
from timm_tpu.kernels import causal_flash_attention, causal_flash_supported  # noqa: E402
from timm_tpu.kernels.causal_attention import _tiles, chunk_window_seen  # noqa: E402
from timm_tpu.layers import (ChunkedLinearAttention, build_rotary_pos_embed_1d, chunk_summaries,  # noqa: E402
                             chunk_window_attention, chunk_window_pairs)
from timm_tpu.layers.attention import apply_rot_embed_cat  # noqa: E402
from timm_tpu.models.evabyte import UnitOffsetRmsNorm  # noqa: E402

from evabyte_common import C, N, SIZES, TOL, W  # noqa: E402


def explicit_mask(n: int, window: int, chunk: int) -> np.ndarray:
    """(n, n // chunk + n) booleans, summaries first, written with loops from the definition: query i sees the
    single keys of its own window up to itself and every chunk of every earlier window."""
    summaries = n // chunk
    seen = np.zeros((n, summaries + n), bool)
    for i in range(n):
        for t in range(n):
            if t // window == i // window and t <= i:
                seen[i, summaries + t] = True
        for j in range(summaries):
            if (j * chunk) // window < i // window:
                seen[i, j] = True
    return seen


def dense(q, k, v, seen, scale):
    """softmax(q k^T scale + mask) v over ALL keys under an explicit mask, float64 on the host."""
    s = np.einsum('bhqd,bhkd->bhqk', np.asarray(q, np.float64), np.asarray(k, np.float64)) * scale
    s = np.where(seen, s, -np.inf)
    p = np.exp(s - s.max(-1, keepdims=True))
    return np.einsum('bhqk,bhkd->bhqd', p / p.sum(-1, keepdims=True), np.asarray(v, np.float64))


def _qkv(seed, heads, n, chunk, d):
    rng = np.random.default_rng(seed)
    return tuple(jnp.asarray(rng.standard_normal((1, heads, rows, d)) * 0.5, jnp.float32)
                 for rows in (n, n // chunk + n, n // chunk + n))


@pytest.mark.parametrize('n,window,chunk', [(N, W, C), (96, 24, 3), (64, 64, 4)], ids=['toy', 'no_power_of_two', 'one_window'])
def test_the_mask_function_is_the_explicit_mask(n, window, chunk):
    want = explicit_mask(n, window, chunk)
    got = chunk_window_seen(np.arange(n)[:, None], np.arange(n // chunk + n)[None, :], n, window, chunk)
    assert got.dtype == bool and np.array_equal(got, want)
    # every query sees itself, nothing after itself, and no summary of its own window
    rows = np.arange(n)
    assert want[rows, n // chunk + rows].all() and not np.triu(want[:, n // chunk:], 1).any()
    assert not any(want[i, (i // window) * (window // chunk):n // chunk].any() for i in range(n))
    assert want.sum() == chunk_window_pairs(n, window, chunk)


@pytest.mark.parametrize('block_q', [16, 32, 8])
def test_the_xla_core_is_a_dense_softmax_under_the_explicit_mask_and_multiplies_no_empty_tile(block_q):
    q, k, v = _qkv(0, 2, N, C, 16)
    out, tiles = jax.jit(lambda q, k, v: chunk_window_attention(q, k, v, 0.25, W, C, block_q=block_q, with_tiles=True),
                         )(q, k, v)
    assert float(np.abs(np.asarray(out) - dense(q, k, v, explicit_mask(N, W, C), 0.25)).max()) < TOL
    # tiles of block_q x block_q that hold an unmasked pair, counted from the explicit mask with the summaries'
    # and the single keys' blocks apart (the slices concatenate them): the path multiplies exactly those
    seen, m = explicit_mask(N, W, C), N // C
    blocks = lambda part: sum(part[i:i + block_q, j:j + block_q].any()  # noqa: E731
                              for i in range(0, N, block_q) for j in range(0, part.shape[1], block_q))
    assert int(tiles) == blocks(seen[:, :m]) + blocks(seen[:, m:])
    with pytest.raises(ValueError, match='no 128 queries'):
        chunk_window_attention(q, k[:, :, 1:], v[:, :, 1:], 0.25, W, C)


def test_the_interpreted_kernel_is_a_dense_softmax_under_the_explicit_mask_forward_and_backward():
    """One head at the kernel's smallest shapes: 1024 queries in windows of 256 and chunks of 8 on 128 summaries
    and 1024 single keys, blocks of 128; values and the gradients of q, k, v against the XLA path's."""
    n, window, chunk = 1024, 256, 8
    q, k, v = _qkv(1, 1, n, chunk, 128)
    assert causal_flash_supported(q, k, v, chunk_window=(window, chunk))
    assert not causal_flash_supported(q, k[:, :, 1:], v[:, :, 1:], chunk_window=(window, chunk))
    assert not causal_flash_supported(*_qkv(1, 1, N, C, 16), chunk_window=(W, C))           # the toy takes the XLA path
    scale = 128 ** -0.5
    kernel = jax.jit(lambda q, k, v: causal_flash_attention(q, k, v, scale, chunk_window=(window, chunk)))
    want = dense(q, k, v, explicit_mask(n, window, chunk), scale)
    assert float(np.abs(np.asarray(kernel(q, k, v)) - want).max()) < 1e-3
    weigh = jnp.asarray(np.random.default_rng(2).standard_normal(q.shape), jnp.float32)
    grads = lambda f: jax.jit(jax.grad(lambda q, k, v: (f(q, k, v) * weigh).sum(), argnums=(0, 1, 2)))(q, k, v)  # noqa: E731
    for a, b in zip(grads(lambda q, k, v: causal_flash_attention(q, k, v, scale, chunk_window=(window, chunk))),
                    grads(lambda q, k, v: chunk_window_attention(q, k, v, scale, window, chunk, block_q=128))):
        assert float(jnp.abs(a - b).max()) < 1e-3
    # the tiles the kernel visits, from its own block map, are the tiles of the explicit mask that hold a pair
    seen = explicit_mask(n, window, chunk)
    assert _tiles(n, chunk_window=(window, chunk)) == sum(
        seen[i:i + 128, j:j + 128].any() for i in range(0, n, 128) for j in range(0, seen.shape[1], 128))
    # at the cell's shape: 16384 queries, 1024 summaries in ONE key block of 1024: 24 window tiles and 14 on the summaries
    assert _tiles(16384, chunk_window=(2048, 16)) == 38


def test_the_whole_mixer_is_a_dense_loop_over_each_querys_own_key_set():
    """The layer with random weights against a loop that, for every head and query, gathers by hand the single
    keys of the query's window up to it and the pooled keys of the earlier windows' chunks (pooled by hand too),
    and takes one softmax over the lot."""
    attn = ChunkedLinearAttention(64, num_heads=4, head_dim=16, window=W, chunk=C, block_q=16, rngs=nnx.Rngs(3))
    x = jnp.asarray(np.random.default_rng(4).standard_normal((1, N, 64)), jnp.float32)
    rope = build_rotary_pos_embed_1d(N, 16, 1e5)
    out, tiles = nnx.jit(lambda m, x: m(x, rope))(attn, x)
    q, k, v = (np.asarray(t[0], np.float64) for t in attn.qkv(x, rope))
    phi, mu = np.asarray(attn.phi[...], np.float64), np.asarray(attn.mu[...], np.float64)
    heads = np.zeros((4, N, 16))
    for h in range(4):
        pooled_k, pooled_v = [], []
        for j in range(N // C):
            rows = slice(j * C, (j + 1) * C)
            alpha = np.exp(k[h, rows] @ phi[h])
            alpha /= alpha.sum()
            pooled_k.append(alpha @ k[h, rows] + mu[h])
            pooled_v.append(alpha @ v[h, rows])
        for i in range(N):
            first = i // W * W
            keys = [k[h, t] for t in range(first, i + 1)] + pooled_k[:first // C]
            values = [v[h, t] for t in range(first, i + 1)] + pooled_v[:first // C]
            scores = np.array(keys) @ q[h, i] * 16 ** -0.5
            p = np.exp(scores - scores.max())
            heads[h, i] = (p / p.sum()) @ np.array(values)
    want = heads.transpose(1, 0, 2).reshape(N, 64) @ np.asarray(attn.proj.kernel[...], np.float64)
    assert float(np.abs(np.asarray(out[0]) - want).max()) < TOL and tiles == 4 * 3 + 2 * (0 + 1 + 1 + 2)
    # the reference's own summaries and core are the same numbers
    ks, vs = ref.summaries(SIZES, jnp.asarray(k, jnp.float32), jnp.asarray(v, jnp.float32), attn.phi[...], attn.mu[...], 'float32')
    core = ref.core(SIZES, jnp.asarray(q, jnp.float32), jnp.asarray(k, jnp.float32), jnp.asarray(v, jnp.float32), ks, vs, 'float32')
    assert float(np.abs(np.asarray(core) - heads).max()) < TOL


def test_mu_and_phi_matter_and_only_from_the_second_window_on():
    attn = ChunkedLinearAttention(64, num_heads=4, head_dim=16, window=W, chunk=C, block_q=16, rngs=nnx.Rngs(5))
    x = jnp.asarray(np.random.default_rng(6).standard_normal((1, N, 64)), jnp.float32)
    rope = build_rotary_pos_embed_1d(N, 16, 1e5)
    call = nnx.jit(lambda m, x: m(x, rope)[0])
    base = np.asarray(call(attn, x))
    assert attn.phi.shape == attn.mu.shape == (4, 16)
    assert float(jnp.abs(attn.phi[...]).max()) <= 16 ** -0.5 and float(jnp.abs(attn.mu[...]).max()) <= 16 ** -0.5
    for vector in (attn.phi, attn.mu):
        saved = vector[...]
        vector[...] = saved + 0.5
        moved = np.asarray(call(attn, x))
        vector[...] = saved
        assert np.array_equal(moved[0, :W], base[0, :W])                     # the first window sees no summary
        assert np.abs(moved[0, W:] - base[0, W:]).max() > 1e-4
    assert np.array_equal(np.asarray(call(attn, x)), base)


def test_the_summaries_pool_turned_keys_and_a_uniform_phi_is_the_chunk_mean():
    rng = np.random.default_rng(7)
    k, v = (jnp.asarray(rng.standard_normal((1, 2, N, 16)), jnp.float32) for _ in range(2))
    phi, mu = jnp.zeros((2, 16)), jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
    ks, vs = chunk_summaries(k, v, phi, mu, C)
    assert ks.shape == vs.shape == (1, 2, N // C, 16)
    assert float(jnp.abs(ks - (k.reshape(1, 2, N // C, C, 16).mean(3) + mu[:, None])).max()) < 1e-6
    assert float(jnp.abs(vs - v.reshape(1, 2, N // C, C, 16).mean(3)).max()) < 1e-6          # mu is the key's alone
    # the turn comes BEFORE the pooling: summaries of turned keys are not turned summaries (a chunk's positions
    # turn by different angles), so the layer must pool what it has turned
    rope = build_rotary_pos_embed_1d(N, 16, 1e5)
    turn = lambda t, table: apply_rot_embed_cat(t, table, half=True)  # noqa: E731
    phi = jnp.asarray(rng.standard_normal((2, 16)), jnp.float32)
    of_turned = chunk_summaries(turn(k, rope), v, phi, mu * 0, C)[0]
    turned_after = turn(chunk_summaries(k, v, phi, mu * 0, C)[0], rope[::C])
    assert float(jnp.abs(of_turned - turned_after).max()) > 1e-2
    attn = ChunkedLinearAttention(64, num_heads=2, head_dim=16, window=W, chunk=C, rngs=nnx.Rngs(8))
    x = jnp.asarray(rng.standard_normal((1, N, 64)), jnp.float32)
    q, kk, vv = attn.qkv(x, rope)
    plain = attn.k_proj(x).reshape(1, N, 2, 16).transpose(0, 2, 1, 3)
    assert float(jnp.abs(kk - turn(plain, rope)).max()) < 1e-6 and float(jnp.abs(kk - plain).max()) > 1e-2


def test_a_sequence_shorter_than_the_window_is_one_causal_window():
    """N = 32 under a window of 128: one window of 32, every key a single one, no summary seen: plain causal attention
    (what the zoo's sweep asks of the published entry points at 128 tokens)."""
    attn = ChunkedLinearAttention(64, num_heads=4, head_dim=16, window=128, chunk=C, rngs=nnx.Rngs(12))
    x = jnp.asarray(np.random.default_rng(13).standard_normal((1, 32, 64)), jnp.float32)
    rope = build_rotary_pos_embed_1d(32, 16, 1e5)
    out, tiles = nnx.jit(lambda m, x: m(x, rope))(attn, x)
    q, k, v = attn.qkv(x, rope)
    causal = dense(q, k, v, np.tril(np.ones((32, 32), bool)), 16 ** -0.5)
    want = causal[0].transpose(1, 0, 2).reshape(32, 64) @ np.asarray(attn.proj.kernel[...], np.float64)
    assert float(np.abs(np.asarray(out[0]) - want).max()) < TOL and tiles == 1
    assert chunk_window_pairs(32, 32, C) == 32 * 33 // 2


def test_the_norm_scales_by_one_plus_g_with_float32_statistics():
    norm = UnitOffsetRmsNorm(64, 1e-5, dtype=jnp.bfloat16, rngs=nnx.Rngs(0))
    x = jnp.asarray(np.random.default_rng(9).standard_normal((3, 64)) * 7, jnp.float32)
    unit = x * jax.lax.rsqrt(jnp.mean(jnp.square(x), -1, keepdims=True) + 1e-5)
    assert float(jnp.abs(norm.scale[...]).max()) == 0.0                                      # g = 0: the identity scale
    assert norm(x).dtype == jnp.bfloat16 and float(jnp.abs(norm(x).astype(jnp.float32) - unit).max()) < 2e-2
    norm.scale[...] = jnp.full((64,), 0.5)
    wide = UnitOffsetRmsNorm(64, 1e-5, rngs=nnx.Rngs(0))
    wide.scale[...] = jnp.full((64,), 0.5)
    assert wide(x).dtype == jnp.float32 and float(jnp.abs(wide(x) - 1.5 * unit).max()) < 1e-6
    assert float(jnp.abs(wide(x) - ref.rms_norm(x, jnp.full((64,), 0.5), 1e-5)).max()) < 1e-6


def test_the_residual_stream_is_float32_under_bfloat16_compute_and_the_logits_are_float32():
    model = timm_tpu.create_model('evabyte_toy', seed=0, dtype=jnp.bfloat16)
    ids = jnp.asarray(np.random.default_rng(10).integers(0, 320, (1, N)), jnp.int32)
    h = nnx.jit(lambda m, i: m.forward_features(i))(model, ids)
    assert h.dtype == jnp.float32 and model.embed(ids).dtype == jnp.float32
    assert model.blocks[0].norm1(h).dtype == jnp.bfloat16 and model.blocks[0].mlp.fc1_g(h).dtype == jnp.bfloat16
    logits = nnx.jit(lambda m, i: m(i))(model, ids)
    assert logits.dtype == jnp.float32
    wide = timm_tpu.create_model('evabyte_toy', seed=0)
    assert float(jnp.abs(logits - nnx.jit(lambda m, i: m(i))(wide, ids)).max()) < 0.1      # the same model, rounded operands
