"""`GroupedQueryAttention` told which heads it holds and given an output gate (Solar-Open2's attention layers), beside
`tests/test_smallthinker_layers.py`, where the layer's other tests live: a layer built without either is the layer it
was (the same leaves, the same lowered program), the gate is a sigmoid of one more product on the core's output, a
share holds whole key/value heads with their query groups, and the cell's shape takes the Pallas kernel."""
import os
import sys

import jax
import jax.numpy as jnp
import pytest
from flax import nnx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from timm_tpu.layers import GroupedQueryAttention, build_rotary_pos_embed_1d  # noqa: E402
from timm_tpu.layers.grouped_attention import grouped_causal_attention  # noqa: E402


def _leaves(layer):
    return {'.'.join(map(str, path)): leaf[...] for path, leaf in nnx.to_flat_state(nnx.state(layer, nnx.Param))}


def _lowered(layer, x, rope=None):
    graphdef, state = nnx.split(layer)
    return jax.jit(lambda st, x: nnx.merge(graphdef, st)(x, rope)[0]).lower(state, x).as_text()


@pytest.mark.parametrize('kw', [dict(rotary=True, window=16), dict(rotary=False), dict(rotary=True, qk_norm=True)],
                         ids=['window_rotary', 'full_position_free', 'qk_norm'])
def test_without_a_gate_or_a_share_the_layer_is_the_layer_it_was(kw):
    """`gate=False, heads_held=None` (the defaults SmallThinker, SDAR and LFM2 build with) against the same layer told it
    holds all its heads from head 0: the same leaves, and the same lowered program text, in which no sigmoid's
    division appears."""
    x = jax.random.normal(jax.random.key(0), (2, 32, 64))
    rope = build_rotary_pos_embed_1d(32, 16, 1e4) if kw['rotary'] else None
    plain = GroupedQueryAttention(64, 8, 2, 16, block_q=8, rngs=nnx.Rngs(0), **kw)
    told = GroupedQueryAttention(64, 8, 2, 16, block_q=8, heads_held=8, head_offset=0, gate=False, rngs=nnx.Rngs(0), **kw)
    assert plain.gate_proj is None and (plain.num_heads, plain.num_kv_heads, plain.head_offset) == (8, 2, 0)
    a, b = _leaves(plain), _leaves(told)
    assert set(a) == set(b) and not [k for k in a if 'gate' in k] and all(bool((a[k] == b[k]).all()) for k in a)
    text = _lowered(plain, x, rope)
    assert text == _lowered(told, x, rope) and text.count('stablehlo.divide') == _lowered(gated := GroupedQueryAttention(
        64, 8, 2, 16, block_q=8, gate=True, rngs=nnx.Rngs(0), **kw), x, rope).count('stablehlo.divide') - 1
    assert set(_leaves(gated)) == set(a) | {'gate_proj.kernel'}


def test_the_gate_is_a_sigmoid_of_one_more_product_on_the_cores_output():
    layer = GroupedQueryAttention(64, 8, 2, 16, rotary=False, gate=True, block_q=8, rngs=nnx.Rngs(3))
    x = jax.random.normal(jax.random.key(1), (2, 32, 64))
    q, k, v = layer.qkv(x)
    core = grouped_causal_attention(q, k, v, layer.scale, block_q=8).transpose(0, 2, 1, 3).reshape(2, 32, 128)
    want = (core * jax.nn.sigmoid(x @ layer.gate_proj.kernel[...])) @ layer.proj.kernel[...]
    got, tiles = layer(x)
    assert float(jnp.abs(got - want).max()) < 1e-5 and tiles == 10
    assert float(jnp.abs(got - core @ layer.proj.kernel[...]).max()) > 1e-3            # the gate is live
    grads = nnx.grad(lambda m: (m(x)[0] ** 2).sum())(layer)
    assert float(jnp.linalg.norm(grads.gate_proj.kernel[...])) > 0


def test_a_share_holds_whole_key_value_heads_with_their_groups_and_the_parts_add_up():
    """8 query heads on 4 key/value heads: shares of 2 query heads (one key/value head each) at offsets 0, 2, 4, 6, with
    the gate; each is the whole layer's slice by `take_heads`, and the four parts of the output product sum to it."""
    kw = dict(rotary=False, gate=True, block_q=8)
    whole = GroupedQueryAttention(64, 8, 4, 16, rngs=nnx.Rngs(0), **kw)
    leaves = _leaves(whole)
    x = jax.random.normal(jax.random.key(2), (2, 32, 64))
    want, total = whole(x)[0], 0.0
    for offset in (0, 2, 4, 6):
        part = GroupedQueryAttention(64, 8, 4, 16, heads_held=2, head_offset=offset, rngs=nnx.Rngs(1), **kw)
        assert (part.num_heads, part.num_kv_heads, part.head_offset) == (2, 1, offset)
        for path, leaf in nnx.to_flat_state(nnx.state(part, nnx.Param)):
            name = '.'.join(map(str, path))
            leaf[...] = part.take_heads(name, leaves[name])
        assert part.q_proj.kernel.shape == (64, 32) and part.k_proj.kernel.shape == (64, 16) and part.proj.kernel.shape == (32, 64)
        assert bool((part.k_proj.kernel[...] == leaves['k_proj.kernel'][:, offset // 2 * 16:(offset // 2 + 1) * 16]).all())
        total = total + part(x)[0]
    assert float(jnp.abs(total - want).max()) < 1e-5 and float(jnp.abs(want).max()) > 1e-3
    for bad in (dict(heads_held=3), dict(heads_held=2, head_offset=1), dict(heads_held=4, head_offset=6)):
        with pytest.raises(ValueError, match='key/value'):
            GroupedQueryAttention(64, 8, 4, 16, rngs=nnx.Rngs(0), **bad)


def test_the_cells_share_takes_the_kernels_multi_query_form():
    """8 query heads on ONE key/value head of width 128 over 8192 positions, no positions, plain causal mask: the shape
    `causal_flash_supported` must say yes to (the registry's `mqa_full_s8192_d128` case runs it interpreted)."""
    from timm_tpu.kernels import causal_flash_supported
    from timm_tpu.kernels.registry import get
    q = jax.ShapeDtypeStruct((1, 8, 8192, 128), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 1, 8192, 128), jnp.bfloat16)
    assert causal_flash_supported(q, kv, kv) and not causal_flash_supported(q, jax.ShapeDtypeStruct((1, 3, 8192, 128), jnp.bfloat16), kv)
    case = [c for c in get('causal_flash_attention').cases if c.name == 'mqa_full_s8192_d128'][0]
    assert case.live == dict(batch=1, heads=8, kv_heads=1, seq=8192, head_dim=128, dtype='bfloat16')
    layer = nnx.eval_shape(lambda: GroupedQueryAttention(4096, 64, 8, 128, rotary=False, gate=True, heads_held=8, rngs=nnx.Rngs(0)))
    assert (layer.num_heads, layer.num_kv_heads) == (8, 1) and layer.gate_proj.kernel.shape == (4096, 1024)
