"""The layers SmallThinker-21BA3B forced, at a toy size on the CPU (`smallthinker_common.py`): grouped-query
attention with its window and its optional rotary turn against an explicit mask, on the XLA path and on the
(interpreted) kernel path; the expert layer's share under a router that reads another tensor; no dropped slot. The
model, the task and the feed are `test_smallthinker.py`'s (one file was over a minute of a tier-1 worker)."""
import math
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from flax import nnx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from benchmarks.harness import weights  # noqa: E402
from benchmarks.reference import smallthinker as ref  # noqa: E402
from timm_tpu.layers import GroupedQueryAttention, SparseMoe, build_rotary_pos_embed_1d, grouped_causal_attention  # noqa: E402

from smallthinker_common import S, SIZES, TOL  # noqa: E402


@pytest.mark.parametrize('kind', ['window', 'full'])
def test_a_layer_is_attention_under_its_explicit_mask_and_a_full_layer_reads_no_rotary_table(kind):
    """A window layer against one S x S softmax under the band mask j <= i, i - j < 8 (queries and keys turned);
    a full layer against the causal mask with NO turn: it is the same whatever table it is handed."""
    dim, H, KV, D, W = 64, 4, 2, 16, 8
    attn = GroupedQueryAttention(dim, H, KV, D, window=W if kind == 'window' else None, rotary=kind == 'window', block_q=8,
                                 rngs=nnx.Rngs(3))
    x = jax.random.normal(jax.random.key(0), (2, S, dim))
    table = build_rotary_pos_embed_1d(S, D, 1.5e6)
    run = nnx.jit(lambda m, x, t: m(x, t)[0])               # one program a layer, not every query block op by op
    got, tiles = run(attn, x, table), attn(x, table)[1]
    heads = lambda t, n: t.reshape(2, S, n, D).transpose(0, 2, 1, 3)  # noqa: E731
    q, k, v = heads(x @ attn.q_proj.kernel[...], H), heads(x @ attn.k_proj.kernel[...], KV), heads(x @ attn.v_proj.kernel[...], KV)
    if kind == 'window':
        q, k = ref.rope(q, 1.5e6), ref.rope(k, 1.5e6)
    k, v = jnp.repeat(k, H // KV, axis=1), jnp.repeat(v, H // KV, axis=1)      # query head g on key/value head g // 2
    i, j = jnp.arange(S)[:, None], jnp.arange(S)[None, :]
    seen = (j <= i) & (i - j < W) if kind == 'window' else j <= i
    scores = jnp.where(seen, jnp.einsum('bhqd,bhkd->bhqk', q, k) / math.sqrt(D), -jnp.inf)
    want = jnp.einsum('bhqk,bhkd->bhqd', jax.nn.softmax(scores, -1), v).transpose(0, 2, 1, 3).reshape(2, S, H * D) @ attn.proj.kernel[...]
    assert float(jnp.abs(got - want).max()) < 1e-5 and tiles == (7 if kind == 'window' else 10)
    if kind == 'full':
        other = build_rotary_pos_embed_1d(S, D, 10.0)
        assert float(jnp.abs(run(attn, x, other) - got).max()) == 0.0 and float(jnp.abs(run(attn, x, None) - got).max()) == 0.0
        x2 = x.at[:, 20:].set(0.0)                      # causal: a later token changes no earlier output
        assert float(jnp.abs(run(attn, x2, table)[:, :20] - got[:, :20]).max()) < 1e-6
    else:
        assert float(jnp.abs(run(attn, x, build_rotary_pos_embed_1d(S, D, 10.0)) - got).max()) > 1e-4    # it does turn
        # a token W or more positions back changes nothing: query 20 sees keys 13..20
        x2 = x.at[:, :13].set(0.0)
        moved = run(attn, x2, table)
        assert float(jnp.abs(moved[:, 20:] - got[:, 20:]).max()) < 1e-6 and float(jnp.abs(moved[:, 19] - got[:, 19]).max()) > 1e-4
        # the query blocks change nothing, and a window over the whole sequence is the causal mask
        attn.block_q = S
        assert float(jnp.abs(run(attn, x, table) - got).max()) < 1e-6
        q0 = jax.random.normal(jax.random.key(1), (1, H, S, D))
        kv = jax.random.normal(jax.random.key(2), (2, 1, KV, S, D))
        assert float(jnp.abs(grouped_causal_attention(q0, kv[0], kv[1], 0.25, window=S, block_q=8)
                             - grouped_causal_attention(q0, kv[0], kv[1], 0.25, block_q=8)).max()) == 0.0


def _expert_layer(p, held, offset, seed=0):
    layer = SparseMoe(64, 32, 8, 2, experts_held=held, expert_offset=offset, n_shared=0, scoring='softmax_topk',
                      activation='relu', rngs=nnx.Rngs(seed))
    layer.router[...] = p['mlp.router']
    for name in ('w_gate', 'w_up', 'w_down'):
        getattr(layer, name)[...] = p['mlp.' + name][offset:offset + held]
    return layer


def test_the_parts_of_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: four shares of 2 experts each against the reference given all 8, routed on another
    tensor than the experts read."""
    cfg = dict(SIZES, experts_held=8)
    spec = {k[len('blocks.1.'):]: v for k, v in ref.init_spec(cfg).items() if k.startswith('blocks.1.mlp.')}
    p = weights.make(5, spec)
    x, a = jax.random.normal(jax.random.key(1), (2, 2 * S, 64))
    whole, chosen = ref.experts(cfg, {'blocks.1.' + k: v for k, v in p.items()}, 'blocks.1.', x, a, 'float32')
    total, slots = 0.0, 0
    for rank in range(4):
        layer = _expert_layer(p, 2, 2 * rank)
        part, counters = layer.routed(x, a)
        total, slots = total + part, slots + int(counters['moe.local_slots'])
        assert int(counters['moe.dropped_slots']) == 0 and bool((layer.choose(a) == chosen).all())
    assert slots == 2 * S * 2                                         # every (token, choice) slot lives on exactly one share
    assert float(jnp.abs(total - whole).max()) < TOL
    # the reference given one share gives that share's part; routed on x itself it gives another result
    one, _ = ref.experts(dict(SIZES, expert_offset=6), {'blocks.1.' + k: (v[6:] if k.startswith('mlp.w_') else v) for k, v in p.items()},
                         'blocks.1.', x, a, 'float32')
    assert float(jnp.abs(part - one).max()) < TOL and float(jnp.abs(layer.routed(x)[0] - part).max()) > 1e-3
    # the weights are a softmax over the chosen logits: they add up to 1 over all chosen, held here or not
    idx, w = ref.routes(cfg, {'blocks.1.mlp.router': p['mlp.router']}, 'blocks.1.', a)
    assert float(jnp.abs(w.sum(-1) - 1.0).max()) < 1e-6 and idx.shape == (2 * S, 2)


def test_no_slot_is_dropped_when_the_router_sends_every_token_to_the_same_experts():
    spec = {k[len('blocks.1.'):]: v for k, v in ref.init_spec(dict(SIZES, experts_held=8)).items() if k.startswith('blocks.1.mlp.')}
    p = weights.make(6, spec)
    # no bias to steer with: positive inputs and a router whose first two columns dominate
    p['mlp.router'] = jnp.zeros((64, 8)).at[:, 0].set(1.0).at[:, 1].set(0.5)
    layer = _expert_layer(p, 2, 0)
    x = jnp.abs(jax.random.normal(jax.random.key(3), (2, S, 64))) + 0.1
    y, counters = jax.jit(lambda m, x: m(x))(layer, x)
    T = 2 * S
    assert int(counters['moe.local_slots']) == 2 * T and int(counters['moe.dropped_slots']) == 0
    assert int(counters['moe.load_max']) == T                          # the worst case: the buffer is full
    flat = x.reshape(T, 64)
    w = jax.nn.softmax((flat @ p['mlp.router'])[:, :2], axis=-1)
    dense = sum(w[:, e:e + 1] * ((jax.nn.relu(flat @ p['mlp.w_gate'][e]) * (flat @ p['mlp.w_up'][e])) @ p['mlp.w_down'][e])
                for e in range(2))
    assert float(jnp.abs(y.reshape(T, 64) - dense).max()) < TOL
    # and nothing held here is chosen: the layer's part is zero, no slot counted
    y, counters = _expert_layer(p, 2, 4)(x)
    assert int(counters['moe.local_slots']) == 0 and float(jnp.abs(y).max()) == 0.0


@pytest.mark.parametrize('window', [None, 128], ids=['full', 'window'])
def test_the_layer_takes_the_pallas_kernel_where_its_shapes_apply_and_agrees_with_the_xla_path(window):
    """Heads of width 128 over 256 positions, 4 query heads on 2 key/value heads: `causal_flash_supported`, so
    the core is the registered kernel's grouped form (interpreted here), with the window as its own mask."""
    import timm_tpu.kernels as kernels
    attn = GroupedQueryAttention(64, 4, 2, 128, window=window, rotary=window is not None, block_q=64, rngs=nnx.Rngs(5))
    x = jax.random.normal(jax.random.key(0), (1, 256, 64))
    rope = build_rotary_pos_embed_1d(256, 128, 1.5e6)
    q, k, v = attn.qkv(x, rope)
    assert q.shape == (1, 4, 256, 128) and k.shape == v.shape == (1, 2, 256, 128)
    assert kernels.causal_flash_supported(q, k, v, window=window) and kernels.causal_flash_supported(q, q, q)
    assert not kernels.causal_flash_supported(q[:, :3], k, v) and not kernels.causal_flash_supported(q[..., :16], k[..., :16], v[..., :16])
    assert not kernels.causal_flash_supported(q, k, v, window=0) and not kernels.causal_flash_supported(q[:, :, :64], k[:, :, :64], v[:, :, :64])
    loss = lambda a, x: (a(x, rope)[0] ** 2).sum()  # noqa: E731
    out, tiles = nnx.jit(lambda a, x: a(x, rope)[0])(attn, x), attn(x, rope)[1]
    value, grads = nnx.jit(nnx.value_and_grad(loss))(attn, x)
    saved = kernels.causal_flash_supported
    try:
        kernels.causal_flash_supported = lambda q, k, v, window=None: False     # the same layer on the XLA path
        want, want_tiles = nnx.jit(lambda a, x: a(x, rope)[0])(attn, x), attn(x, rope)[1]
        want_value, want_grads = nnx.jit(nnx.value_and_grad(loss))(attn, x)
    finally:
        kernels.causal_flash_supported = saved
    assert float(jnp.abs(out - want).max()) < TOL and abs(float(value) - float(want_value)) < TOL * float(want_value)
    gaps = jax.tree.map(lambda a, b: float(jnp.abs(a - b).max()), nnx.state(grads), nnx.state(want_grads))
    assert max(jax.tree.leaves(gaps)) < 1e-3, gaps
    # one 256-wide tile in the kernel; 64-wide tiles on the XLA path: 1+2+3+4, or 1+2+3+3 under the window
    assert tiles == 1 and want_tiles == (10 if window is None else 9)


def test_the_kernels_block_map_skips_the_tiles_outside_the_window():
    """At the cell's length the kernel's own forward block map holds 136 tiles for a full core (16 query blocks
    of 1024 against 1..16 key blocks) and 70 for a window of 4096 (at most 5 key blocks a query block); nothing
    runs here, the map is built when the call is traced."""
    from timm_tpu.kernels import causal_flash_attention
    seen = {}

    def trace(window):
        def f(q, k, v):
            out, seen[window] = causal_flash_attention(q, k, v, 0.1, window, with_tiles=True)
            return out
        q, kv = jax.ShapeDtypeStruct((1, 28, 16384, 128), jnp.bfloat16), jax.ShapeDtypeStruct((1, 4, 16384, 128), jnp.bfloat16)
        assert jax.eval_shape(f, q, kv, kv).shape == (1, 28, 16384, 128)

    trace(None), trace(4096), trace(16384)
    assert seen == {None: 136, 4096: 70, 16384: 136}


# a router row a kind of token: kind 0 chooses experts 0 and 1 (both held by a share of experts 0-1), kind 1 experts
# 0 and 2 (one held), kind 2 experts 2 and 3 (none held); a token shows its kind as a one-hot router input
KINDS = jnp.asarray([[2.0, 1, 0, 0, 0, 0, 0, 0], [2.0, 0, 1, 0, 0, 0, 0, 0], [0, 0, 2.0, 1, 0, 0, 0, 0]])

# (tokens of kind 0, of kind 1; the rest kind 2) -> local slots 2 * kind0 + kind1, of a buffer of 128 rows
DISPATCH_CASES = {'even': None, 'exactly_full': (64, 0), 'one_over': (64, 1), 'every_slot_local': (128, 0), 'none_local': (0, 0)}


@pytest.mark.parametrize('scoring', ['sigmoid_bias', 'softmax_topk'])
@pytest.mark.parametrize('case', list(DISPATCH_CASES))
def test_the_bounded_dispatch_is_the_worst_case_dispatch_result_and_every_gradient(case, scoring, monkeypatch):
    """The buffer follows the share of the experts held (`dispatch_rows`: 128 of 256 rows here); a layer whose
    local slots do not fit falls back to all rows, so result and gradients are the worst-case path's under any
    routing, no slot is dropped, and `moe.fallback_layers` says which branch ran."""
    from timm_tpu.layers import moe
    T = 128
    assert moe.dispatch_rows(T * 2, 2, 8) == 128
    # a share of 2 of 8 experts: 256 slots a call of 128 tokens, a dispatch buffer of 128 rows
    layer = SparseMoe(64, 32, 8, 2, experts_held=2, expert_offset=0, n_shared=0, routed_scaling_factor=1.8,
                      scoring=scoring, activation='relu' if scoring == 'softmax_topk' else 'silu', rngs=nnx.Rngs(7))
    x = jax.random.normal(jax.random.key(1), (T, 64))
    if DISPATCH_CASES[case] is None:
        layer.router[...] = jax.random.normal(jax.random.key(2), (64, 8))
        a, local = jax.random.normal(jax.random.key(3), (T, 64)), None
    else:
        both, one = DISPATCH_CASES[case]
        layer.router[...] = jnp.zeros((64, 8)).at[:3].set(KINDS)
        kind = jnp.where(jnp.arange(T) < both, 0, jnp.where(jnp.arange(T) < both + one, 1, 2))
        a, local = jax.nn.one_hot(jax.random.permutation(jax.random.key(4), kind), 64), 2 * both + one
    graphdef, state = nnx.split(layer)
    cot = jax.random.normal(jax.random.key(5), (T, 64))

    def traced():
        """Result, counters and gradients under remat, as a block runs the layer; traced anew at every call."""
        def loss(state, x, a):
            y, counters = jax.checkpoint(lambda s, x, a: nnx.merge(graphdef, s).routed(x, a))(state, x, a)
            return (y * cot).sum(), (y, counters)
        run = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))
        return run(state, x, a), 'stablehlo.case' in run.lower(state, x, a).as_text()

    ((_, (y, counters)), grads), branches = traced()
    with monkeypatch.context() as m:
        m.setattr(moe, 'dispatch_rows', lambda rows, held, num_experts: rows)      # the worst case: no conditional
        ((_, (y_worst, counters_worst)), grads_worst), branches_worst = traced()
    assert branches and not branches_worst
    if local is not None:
        assert int(counters['moe.local_slots']) == local
    assert int(counters['moe.local_slots']) == int(counters_worst['moe.local_slots'])
    assert int(counters['moe.dropped_slots']) == 0 and int(counters_worst['moe.dropped_slots']) == 0
    assert int(counters['moe.fallback_layers']) == int(int(counters['moe.local_slots']) > 128)
    assert int(counters['moe.fallback_layers']) == {'one_over': 1, 'every_slot_local': 1}.get(case, 0)
    assert int(counters_worst['moe.fallback_layers']) == 0
    assert float(jnp.abs(y - y_worst).max()) < 1e-5
    leaves, leaves_worst = jax.tree.leaves_with_path(grads), jax.tree.leaves(grads_worst)
    assert len(leaves) == 4 + 2 + (scoring == 'sigmoid_bias')       # router, three stacks, x, a (and the bias' zero)
    for (path, g), g_worst in zip(leaves, leaves_worst):
        assert float(jnp.abs(g - g_worst).max()) <= 1e-5 * max(1.0, float(jnp.abs(g_worst).max())), jax.tree_util.keystr(path)
    if case != 'none_local':
        assert float(jnp.abs(y).max()) > 0
        assert all(float(jnp.abs(g).max()) > 0 for path, g in leaves if 'score_bias' not in jax.tree_util.keystr(path))


@pytest.mark.parametrize('held', [8, 2])
def test_a_layer_that_holds_every_expert_builds_no_conditional(held):
    """`experts_held == num_experts`: the buffer is all `T * top_k` rows and the lowered program has no branch."""
    layer = SparseMoe(64, 32, 8, 2, experts_held=held, n_shared=0, rngs=nnx.Rngs(0))
    step = jax.jit(jax.grad(lambda m, x: m(x)[0].sum()))
    x = jnp.ones((2, 64, 64))          # 256 slots: a share of 2 of 8 gets a buffer of 128 rows
    assert ('stablehlo.case' in step.lower(layer, x).as_text()) == (held < 8)
    counters = layer(x)[1]
    assert set(counters) == {'moe.local_slots', 'moe.load_max', 'moe.dropped_slots', 'moe.fallback_layers'}
