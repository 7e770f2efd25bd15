"""The layers SDAR-30B-A3B-Chat forced, at a toy size on the CPU (`sdar_moe_common.py`): grouped-query attention
under the block-diffusion mask against an explicit (2 L, 2 L) mask written with loops, on the XLA path and on the
(interpreted) kernel path, with its per-head q/k norms and a rotary table read at r mod L; a last layer's noised
queries alone; the expert layer's share at softmax-top-k with SwiGLU experts; no dropped slot. The model, the task
and the feed are `test_sdar_moe.py`'s."""
import math
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

from benchmarks.harness import weights  # noqa: E402
from benchmarks.reference import sdar_moe as ref  # noqa: E402
from timm_tpu.kernels.causal_attention import _block_diffusion_mask, block_diffusion_seen  # noqa: E402
from timm_tpu.layers import (GroupedQueryAttention, SparseMoe, build_rotary_pos_embed_1d,  # noqa: E402
                             grouped_block_diffusion_attention)

from sdar_moe_common import K, L, SIZES, TOL, seen_by_loops  # noqa: E402


def test_the_three_masks_are_the_four_rules_written_with_loops():
    """The kernel's mask function (on NumPy grids, as splash attention builds its block map), the reference's,
    and a block length that does not divide the query block."""
    rows = np.arange(2 * L)
    for block in (K, 1, 16, L):
        want = seen_by_loops(L, block)
        assert (np.asarray(block_diffusion_seen(rows[:, None], rows[None, :], L, block)) == want).all()
        assert (np.asarray(ref.seen(jnp.asarray(rows)[:, None], jnp.asarray(rows)[None, :], L, block)) == want).all()
        # the kernel's own mask object: its query rows come as a code (a block's first position, negated for a clean
        # row), not as an index, and it answers a slice of the mask as splash attention asks for one
        for queries in (2 * L, L):
            assert (np.asarray(_block_diffusion_mask(queries, L, block)[:, :]) == want[:queries]).all()
    want = seen_by_loops(L, K)
    assert want.sum() == L * L + L * K and want[:L].sum() == L * K + L * (L - K) // 2      # the needed pairs, and a last layer's
    assert want[0, :K].all() and not want[0, K:].any() and want[L, L:L + K].all() and not want[L, :L].any()
    # the XLA path's key slices where a block is wider than a query block: still the mask's result
    q = jax.random.normal(jax.random.key(1), (1, 2, 2 * L, 16))
    kv = jax.random.normal(jax.random.key(2), (2, 1, 1, 2 * L, 16))
    got, tiles = jax.jit(lambda q, k, v: grouped_block_diffusion_attention(q, k, v, 0.25, 16, block_q=8, with_tiles=True))(q, kv[0], kv[1])
    scores = jnp.where(seen_by_loops(L, 16), jnp.einsum('bhqd,bkd->bhqk', q, kv[0][:, 0]) * 0.25, -jnp.inf)
    assert float(jnp.abs(got - jnp.einsum('bhqk,bkd->bhqd', jax.nn.softmax(scores, -1), kv[1][:, 0])).max()) < 1e-5
    assert int(tiles) == (2 + 2 + 4 + 4) + (2 + 2 + 4 + 4)         # noised: its block's 2 tiles and the 0 or 2 before; clean: up to its block's end


@pytest.mark.parametrize('queries', [None, L], ids=['all_rows', 'noised_queries'])
def test_a_layer_is_attention_under_its_explicit_mask_with_head_norms_and_positions_that_repeat(queries):
    """One (2 L, 2 L) softmax under the mask written with loops, queries and keys normalised per head and turned
    by r mod L; `queries=L` gives the noised rows of the same result and multiplies fewer tiles."""
    dim, H, KV, D = 64, 4, 2, 16
    attn = GroupedQueryAttention(dim, H, KV, D, block_q=8, qk_norm=True, block_diffusion=K, rngs=nnx.Rngs(3))
    attn.q_norm.scale[...] = 1.0 + 0.3 * jax.random.normal(jax.random.key(7), (D,))
    attn.k_norm.scale[...] = 1.0 + 0.3 * jax.random.normal(jax.random.key(8), (D,))
    x = jax.random.normal(jax.random.key(0), (2, 2 * L, dim))
    table = build_rotary_pos_embed_1d(L, D, 1e6)
    run = nnx.jit(lambda m, x, t: m(x, t, queries)[0])
    got, tiles = run(attn, x, table), attn(x, table, queries)[1]
    heads = lambda t, n: t.reshape(2, 2 * L, n, D).transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v = heads(x @ attn.q_proj.kernel[...], H), heads(x @ attn.k_proj.kernel[...], KV), heads(x @ attn.v_proj.kernel[...], KV)
    positions = jnp.concatenate([jnp.arange(L), jnp.arange(L)])
    q = ref.rope(ref.rms_norm(q, attn.q_norm.scale[...], 1e-6), 1e6, positions)
    k = ref.rope(ref.rms_norm(k, attn.k_norm.scale[...], 1e-6), 1e6, positions)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)      # query head g on key/value head g // 2
    scores = jnp.where(seen_by_loops(L, K), jnp.einsum('bhqd,bhkd->bhqk', q, k) / math.sqrt(D), -jnp.inf)
    want = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, -1), v).transpose(0, 2, 1, 3).reshape(2, 2 * L, H * D) @ attn.proj.kernel[...]
    assert float(jnp.abs(got - want[:, :queries]).max()) < 1e-5
    assert tiles == (14 if queries else 24)              # 8-wide tiles: 4 + 10 for the noised queries, 10 for the clean
    # the head norms are in the result, and so is the turn, read at r mod L: a table of 2 L rows read at r would differ
    saved = attn.q_norm.scale[...]
    attn.q_norm.scale[...] = jnp.ones((D,))
    assert float(jnp.abs(run(attn, x, table) - got).max()) > 1e-3
    attn.q_norm.scale[...] = saved
    plain = GroupedQueryAttention(dim, H, KV, D, block_q=8, block_diffusion=K, rngs=nnx.Rngs(3))      # the same products, no norms
    assert plain.q_norm is None and float(jnp.abs(run(plain, x, table) - got).max()) > 1e-3
    assert float(jnp.abs(run(attn, x, build_rotary_pos_embed_1d(L, D, 10.0)) - got).max()) > 1e-4
    # the query blocks change nothing, even narrower than the block length
    attn.block_q = 2
    assert float(jnp.abs(run(attn, x, table) - got).max()) < 1e-6
    with pytest.raises(ValueError, match='window'):
        GroupedQueryAttention(dim, H, KV, D, window=8, block_diffusion=K, rngs=nnx.Rngs(0))


@pytest.mark.parametrize('queries', [None, 256], ids=['all_rows', 'noised_queries'])
def test_the_layer_takes_the_pallas_kernel_where_its_shapes_apply_and_agrees_with_the_xla_path(queries):
    """Heads of width 128 over 2 x 256 rows, 4 query heads on 2 key/value heads: `causal_flash_supported`, so the
    core is the registered kernel's grouped form (interpreted here) with the block-diffusion mask as its own."""
    import timm_tpu.kernels as kernels
    attn = GroupedQueryAttention(64, 4, 2, 128, block_q=64, qk_norm=True, block_diffusion=K, rngs=nnx.Rngs(5))
    x = jax.random.normal(jax.random.key(0), (1, 512, 64))
    rope = build_rotary_pos_embed_1d(256, 128, 1e6)
    q, k, v = attn.qkv(x, rope, queries)
    assert q.shape == (1, 4, queries or 512, 128) and k.shape == v.shape == (1, 2, 512, 128)
    assert kernels.causal_flash_supported(q, k, v, block_diffusion=K) and not kernels.causal_flash_supported(q, k, v, window=8, block_diffusion=K)
    assert not kernels.causal_flash_supported(q, k, v, block_diffusion=3) and not kernels.causal_flash_supported(q[:, :, :128], k, v, block_diffusion=K)
    assert not kernels.causal_flash_supported(q[..., :16], k[..., :16], v[..., :16], block_diffusion=K)
    assert kernels.causal_flash_supported(q, k, v) == (queries is None)              # one S without the mask
    loss = lambda a, x: (a(x, rope, queries)[0] ** 2).sum()  # noqa: E731
    out, tiles = nnx.jit(lambda a, x: a(x, rope, queries)[0])(attn, x), attn(x, rope, queries)[1]
    value, grads = nnx.jit(nnx.value_and_grad(loss))(attn, x)
    saved = kernels.causal_flash_supported
    try:
        kernels.causal_flash_supported = lambda q, k, v, **mask: False     # the same layer on the XLA path
        want, want_tiles = nnx.jit(lambda a, x: a(x, rope, queries)[0])(attn, x), attn(x, rope, queries)[1]
        want_value, want_grads = nnx.jit(nnx.value_and_grad(loss))(attn, x)
    finally:
        kernels.causal_flash_supported = saved
    assert float(jnp.abs(out - want).max()) < TOL and abs(float(value) - float(want_value)) < TOL * float(want_value)
    gaps = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), nnx.state(grads), nnx.state(want_grads))
    assert max(jax.tree.leaves(gaps)) < 1e-3, gaps
    # 256-wide tiles in the kernel: noised on noised, noised on clean (blocks 0-62 of the one tile), clean on clean;
    # 64-wide tiles on the XLA path: 4 + 10 for the noised queries, 10 for the clean ones
    assert (tiles, want_tiles) == ((2, 14) if queries else (3, 24))


def test_the_kernels_block_map_skips_the_tiles_the_mask_leaves_empty():
    """At the cell's length (2 x 8192 rows, blocks of 4) the kernel's own forward block map holds 80 of 256 tiles
    for a whole layer (36 clean on clean, 36 noised on clean, 8 on the noised diagonal) and 44 for a last layer's
    noised queries; nothing runs here, the map is built when the call is traced. The other masks' maps are
    what they were."""
    from timm_tpu.kernels import causal_flash_attention
    seen = {}

    def trace(rows, **mask):
        def f(q, k, v):
            out, seen[(rows, *mask.values())] = causal_flash_attention(q, k, v, 0.1, with_tiles=True, **mask)
            return out
        q, kv = jax.ShapeDtypeStruct((1, 32, rows, 128), jnp.bfloat16), jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
        assert jax.eval_shape(f, q, kv, kv).shape == (1, 32, rows, 128)

    trace(16384, block_diffusion=4), trace(8192, block_diffusion=4), trace(16384, window=None)
    assert seen == {(16384, 4): 80, (8192, 4): 44, (16384, None): 136}
    q = jnp.zeros((1, 2, 64, 16))
    with pytest.raises(ValueError, match='no noised copy'):
        grouped_block_diffusion_attention(q, q[:, :1, :63], q[:, :1, :63], 0.25, K)


def _expert_layer(p, held, offset, seed=0):
    layer = SparseMoe(64, 32, 8, 2, experts_held=held, expert_offset=offset, n_shared=0, scoring='softmax_topk',
                      activation='silu', rngs=nnx.Rngs(seed))
    layer.router[...] = p['mlp.router']
    for name in ('w_gate', 'w_up', 'w_down'):
        getattr(layer, name)[...] = p['mlp.' + name][offset:offset + held]
    return layer


def test_the_parts_of_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: four shares of 2 SwiGLU experts each against the reference given all 8, whose weights are
    a softmax over all 8 renormalised over the 2 chosen."""
    cfg = dict(SIZES, experts_held=8)
    spec = {k[len('blocks.1.'):]: v for k, v in ref.init_spec(cfg).items() if k.startswith('blocks.1.mlp.')}
    p = weights.make(5, spec)
    e = jax.random.normal(jax.random.key(1), (2 * L, 64))
    whole, chosen = ref.experts(cfg, {'blocks.1.' + k: v for k, v in p.items()}, 'blocks.1.', e, 'float32')
    total, slots = 0.0, 0
    for rank in range(4):
        layer = _expert_layer(p, 2, 2 * rank)
        part, counters = layer.routed(e)
        total, slots = total + part, slots + int(counters['moe.local_slots'])
        assert int(counters['moe.dropped_slots']) == 0 and bool((layer.choose(e) == chosen).all())
    assert slots == 2 * L * 2                                         # every (row, choice) slot lives on exactly one share
    assert float(jnp.abs(total - whole).max()) < TOL
    one, _ = ref.experts(dict(SIZES, expert_offset=6), {'blocks.1.' + k: (v[6:] if k.startswith('mlp.w_') else v) for k, v in p.items()},
                         'blocks.1.', e, 'float32')
    assert float(jnp.abs(part - one).max()) < TOL
    # a softmax over all 8 renormalised over the chosen is a softmax over the chosen logits: they add up to 1
    idx, w = ref.routes(cfg, {'blocks.1.mlp.router': p['mlp.router']}, 'blocks.1.', e)
    logits = jnp.take_along_axis(e @ p['mlp.router'], idx, axis=-1)
    assert float(jnp.abs(w - jax.nn.softmax(logits, -1)).max()) < 1e-6 and float(jnp.abs(w.sum(-1) - 1.0).max()) < 1e-6


def test_no_slot_is_dropped_and_the_worst_case_buffer_is_taken_when_every_row_chooses_the_held_experts():
    """Top-2 of 8 with 2 held: the bounded buffer holds 2 x 2 x T x 2 / 8 = T rows; a router that sends every row
    to the two held experts brings 2 T local slots, so the layer falls back to all rows and drops nothing."""
    spec = {k[len('blocks.1.'):]: v for k, v in ref.init_spec(dict(SIZES, experts_held=8)).items() if k.startswith('blocks.1.mlp.')}
    p = weights.make(6, spec)
    p['mlp.router'] = jnp.zeros((64, 8)).at[:, 0].set(1.0).at[:, 1].set(0.5)
    layer = _expert_layer(p, 2, 0)
    T = 4 * L
    x = jnp.abs(jax.random.normal(jax.random.key(3), (1, T, 64))) + 0.1
    y, counters = jax.jit(lambda m, x: m(x))(layer, x)
    assert int(counters['moe.local_slots']) == 2 * T and int(counters['moe.dropped_slots']) == 0
    assert int(counters['moe.fallback_layers']) == 1 and int(counters['moe.load_max']) == T
    flat = x.reshape(T, 64)
    w = jax.nn.softmax((flat @ p['mlp.router'])[:, :2], axis=-1)
    dense = sum(w[:, i:i + 1] * ((jax.nn.silu(flat @ p['mlp.w_gate'][i]) * (flat @ p['mlp.w_up'][i])) @ p['mlp.w_down'][i])
                for i in range(2))
    assert float(jnp.abs(y.reshape(T, 64) - dense).max()) < TOL
    # a seeded router spreads the rows: the bounded buffer serves, no layer falls back
    y, counters = jax.jit(lambda m, x: m(x))(_expert_layer(weights.make(6, spec), 2, 0), x)
    assert int(counters['moe.fallback_layers']) == 0 and int(counters['moe.dropped_slots']) == 0 and int(counters['moe.local_slots']) > 0
