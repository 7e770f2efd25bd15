"""The expert layer's route without a row scatter (`layers/moe.py`): rows go into the dispatch buffer by a gather
through `order` and come back by a gather through its inverse, a `custom_vjp` pair in which each move is the other's
derivative, and the group sizes are a comparison count. Held here against a plain formulation written with
`.at[].add`, as a transpose pair on random cotangents, against `jnp.bincount`, and in the text of the three
families' lowered training steps, which may hold no scatter of rows in an expert layer."""
import os
import re
import sys

import jax
import jax.numpy as jnp
import pytest
from flax import nnx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

import timm_tpu  # noqa: E402
from timm_tpu.layers import SparseMoe, moe  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.task import BlockDiffusionLMTask, CausalLMTask  # noqa: E402

T, DIM, HIDDEN, EXPERTS, K = 128, 64, 32, 8, 2
# a router row a kind of token: kind 0 chooses experts 0 and 1, kind 1 experts 0 and 2, kind 2 experts 2 and 3; a
# token shows its kind as a one-hot router input. A share of experts 0-1 has a buffer of 128 of the 256 slots.
KINDS = jnp.asarray([[2.0, 1, 0, 0, 0, 0, 0, 0], [2.0, 0, 1, 0, 0, 0, 0, 0], [0, 0, 2.0, 1, 0, 0, 0, 0]])
# (tokens of kind 0, of kind 1; the rest kind 2) -> 2 * kind0 + kind1 slots on experts 0-1; None: a random router
ROUTINGS = {'under': None, 'over': (64, 1)}


def plain(params, x, a, scoring, activation, held, scaling):
    """The layer's part of the result, written down slot by slot: every (token, choice) slot's gated MLP with its
    own expert's matrices, weighted, scatter-added onto its token; float32."""
    logits = jnp.matmul(a, params['router'], precision='highest')
    if scoring == 'softmax_topk':
        chosen, idx = jax.lax.top_k(logits, K)
        weights = jax.nn.softmax(chosen, axis=-1)
    else:
        s = jax.nn.sigmoid(logits)
        _, idx = jax.lax.top_k(s, K)                                   # the bias is zero
        chosen = jnp.take_along_axis(s, idx, axis=-1)
        weights = chosen / (chosen.sum(-1, keepdims=True) + 1e-20) * scaling
    expert, token = idx.reshape(-1), jnp.repeat(jnp.arange(x.shape[0]), K)
    local = expert < held
    e = jnp.where(local, expert, 0)
    xs = x[token]
    act = {'silu': jax.nn.silu, 'relu': jax.nn.relu}[activation]
    mm = lambda rows, w: jnp.einsum('sd,sdh->sh', rows, w[e], precision='highest')
    out = mm(act(mm(xs, params['w_gate'])) * mm(xs, params['w_up']), params['w_down'])
    return jnp.zeros_like(x).at[token].add(jnp.where(local, weights.reshape(-1), 0.0)[:, None] * out)


@pytest.mark.parametrize('scoring', ['sigmoid_bias', 'softmax_topk'])
@pytest.mark.parametrize('held', [2, 8], ids=['2of8', '8of8'])
@pytest.mark.parametrize('routing', list(ROUTINGS))
def test_result_and_every_gradient_are_the_plain_scatter_formulations(routing, held, scoring):
    """Both buffer sizes of a share (the bounded branch and the fall-back, by `moe.fallback_layers`) and the whole
    layer, which has one: y and the gradients with respect to x, the router's input, the router and the stacks."""
    activation = 'relu' if scoring == 'softmax_topk' else 'silu'
    layer = SparseMoe(DIM, HIDDEN, EXPERTS, K, experts_held=held, n_shared=0, routed_scaling_factor=1.8,
                      scoring=scoring, activation=activation, rngs=nnx.Rngs(7))
    x = jax.random.normal(jax.random.key(1), (T, DIM))
    if ROUTINGS[routing] is None:
        layer.router[...] = jax.random.normal(jax.random.key(2), (DIM, EXPERTS))
        a = jax.random.normal(jax.random.key(3), (T, DIM))
    else:
        both, one = ROUTINGS[routing]
        layer.router[...] = jnp.zeros((DIM, EXPERTS)).at[:3].set(KINDS)
        kind = jnp.where(jnp.arange(T) < both, 0, jnp.where(jnp.arange(T) < both + one, 1, 2))
        a = jax.nn.one_hot(jax.random.permutation(jax.random.key(4), kind), DIM) * 1.5
    graphdef, state = nnx.split(layer)
    params = {name: getattr(layer, name)[...] for name in ('router', 'w_gate', 'w_up', 'w_down')}
    cot = jax.random.normal(jax.random.key(5), (T, DIM))

    def loss(state, x, a):
        y, counters = jax.checkpoint(lambda s, x, a: nnx.merge(graphdef, s).routed(x, a))(state, x, a)
        return (y * cot).sum(), (y, counters)

    def loss_plain(params, x, a):
        y = plain(params, x, a, scoring, activation, held, 1.8)
        return (y * cot).sum(), y

    (_, (y, counters)), (g_state, g_x, g_a) = jax.jit(
        jax.value_and_grad(loss, argnums=(0, 1, 2), has_aux=True))(state, x, a)
    (_, y_plain), (g_params, g_x_plain, g_a_plain) = jax.jit(
        jax.value_and_grad(loss_plain, argnums=(0, 1, 2), has_aux=True))(params, x, a)
    assert int(counters['moe.fallback_layers']) == int(held < EXPERTS and routing == 'over')
    assert int(counters['moe.dropped_slots']) == 0 and int(counters['moe.local_slots']) > 0
    if routing == 'over':
        assert int(counters['moe.local_slots']) == (129 if held < EXPERTS else 2 * T)
    got = {'y': y, 'x': g_x, 'router_in': g_a, **{name: g_state[name][...] for name in params}}
    want = {'y': y_plain, 'x': g_x_plain, 'router_in': g_a_plain, **g_params}
    for name in got:
        scale = max(1.0, float(jnp.abs(want[name]).max()))
        assert float(jnp.abs(got[name] - want[name]).max()) <= 1e-5 * scale, name
        assert float(jnp.abs(want[name]).max()) > 0, name


def moves(expert, held, n):
    """What `SparseMoe.routed` hands `_dispatch` for the chosen experts `expert` (T, K) of a share holding ids
    below `held`, at a buffer of `n` rows."""
    slot_expert = jnp.where(expert < held, expert, held).reshape(-1)
    order = jnp.argsort(slot_expert, stable=True)
    pos = jnp.argsort(order).reshape(-1, K)
    covered = jnp.minimum((slot_expert < held).sum(), n).astype(jnp.int32)
    return order[:n] // K, pos, covered


ROUTED = {
    'dead_rows': lambda: jax.random.randint(jax.random.key(0), (T, K), 0, EXPERTS),
    'no_local_slot': lambda: jax.random.randint(jax.random.key(0), (T, K), 2, EXPERTS),
    'every_token_on_one_expert': lambda: jnp.stack([jnp.zeros(T, jnp.int32), jnp.full(T, 5)], 1),
    'every_slot_local': lambda: jnp.stack([jnp.zeros(T, jnp.int32), jnp.ones(T, jnp.int32)], 1),
}


@pytest.mark.parametrize('n', [128, 256], ids=['bounded', 'all_rows'])
@pytest.mark.parametrize('case', list(ROUTED))
def test_the_two_moves_are_each_others_transpose(case, n):
    """`jax.vjp` of the gather into the buffer is the gather-sum back, and the other way round, to the bit; both
    agree with what autodiff makes of the plain masked gather (a scatter-add); and <to_buffer(x), u> = <x,
    to_tokens(u)>. Rows past `covered` are NaN in what comes from the buffer: nothing of them reaches a token."""
    token, pos, covered = moves(ROUTED[case](), 2, n)
    assert int(covered) == {'dead_rows': int(covered), 'no_local_slot': 0, 'every_token_on_one_expert': T,
                            'every_slot_local': n}[case]
    x, dy = jax.random.normal(jax.random.key(1), (2, T, DIM))
    u = jax.random.normal(jax.random.key(2), (n, DIM))
    u_nan = jnp.where((jnp.arange(n) < covered)[:, None], u, jnp.nan)
    xs, back = jax.vjp(lambda x: moe._to_buffer(x, token, pos, covered), x)
    y, there = jax.vjp(lambda rows: moe._to_tokens(rows, token, pos, covered), u_nan)
    assert bool(jnp.isfinite(y).all()) and bool((back(u_nan)[0] == y).all())
    assert bool((there(dy)[0] == moe._to_buffer(dy, token, pos, covered)).all())
    live = (jnp.arange(n) < covered)[:, None]
    xs_plain, back_plain = jax.vjp(lambda x: jnp.where(live, x[token], 0), x)
    assert bool((xs == xs_plain).all()) and float(jnp.abs(back(u)[0] - back_plain(u)[0]).max()) < 1e-5
    assert abs(float((xs * u).sum()) - float((x * y).sum())) < 1e-3
    if case == 'no_local_slot':
        assert float(jnp.abs(xs).max()) == 0.0 and float(jnp.abs(y).max()) == 0.0


def test_the_pieces_of_a_source_are_whole_lane_columns_by_its_size():
    """The cells' bounded buffers (bfloat16: 120 / 128 / 64 MiB) in 3 / 3 / 1 pieces, a fall-back's whole, a
    width that is no multiple of 128 whole."""
    assert moe._column_pieces(24576, 2560, 2) == [0, 768, 1664, 2560]
    assert moe._column_pieces(32768, 2048, 2) == [0, 640, 1280, 2048]
    assert moe._column_pieces(16384, 2048, 2) == [0, 2048] and moe._column_pieces(98304, 2560, 2) == [0, 2560]
    assert moe._column_pieces(32768, 2000, 2) == [0, 2000]


@pytest.mark.parametrize('pieces', [2, 3])
def test_a_source_read_in_pieces_gives_the_sum_of_one_read_whole(pieces, monkeypatch):
    n, dim = 128, 384
    token, pos, covered = moves(ROUTED['dead_rows'](), 2, n)
    rows = jax.random.normal(jax.random.key(3), (n, dim))
    whole = moe._sum_rows(rows, token, pos, covered)
    monkeypatch.setattr(moe, 'FAST_BYTES', n * dim * 4)
    monkeypatch.setattr(moe, 'PIECE_BYTES', -(-n * dim * 4 // pieces))
    assert len(moe._column_pieces(n, dim, 4)) == pieces + 1
    assert bool((moe._sum_rows(rows, token, pos, covered) == whole).all())


@pytest.mark.parametrize('held', [2, 8])
@pytest.mark.parametrize('case', list(ROUTED))
def test_group_sizes_are_bincounts(case, held):
    expert = ROUTED[case]()
    slot_expert = jnp.where(expert < held, expert, held).reshape(-1)
    got = moe._group_sizes(slot_expert, held)
    assert got.dtype == jnp.int32 and bool((got == jnp.bincount(slot_expert, length=held + 1)[:held]).all())
    assert int(got.sum()) == int((expert < held).sum())


SCATTER = re.compile(r'^\s*(?:ROOT )?\S+ = (\w+)\[([\d,]*)\]\S* scatter\(([^)]*)\)(.*)$', re.M)
OPERAND = re.compile(r'(\w+)\[([\d,]*)\]')


def row_scatters(hlo: str, dim: int, embedding: tuple = ()) -> list:
    """The scatter instructions of a program's text that move rows of width `dim` (in the result or the updates)
    or count into integer bins, but for one whose result is the embedding's: [(result, updates, op name)]."""
    shapes = {m.group(1): (m.group(2), m.group(3))
              for m in re.finditer(r'^\s*(?:ROOT )?(\S+) = (\w+)\[([\d,]*)\]', hlo, re.M)}
    found = []
    for m in SCATTER.finditer(hlo):
        dtype, result = m.group(1), tuple(int(d) for d in m.group(2).split(',') if d)
        updates = shapes[m.group(3).split(',')[-1].strip().lstrip('%')][1]
        updates = tuple(int(d) for d in updates.split(',') if d)
        name = re.search(r'op_name="([^"]*)"', m.group(4))
        wide = (len(result) > 1 and result[-1] == dim) or (len(updates) > 1 and updates[-1] == dim)
        if result != tuple(embedding) and (wide or dtype.startswith(('s', 'u'))):
            found.append((dtype, result, updates, name.group(1) if name else ''))
    return found


def lowered_step(name: str) -> str:
    model = timm_tpu.create_model(name, seed=0)
    model.set_grad_checkpointing(True)
    task = (BlockDiffusionLMTask if name == 'sdar_moe_toy' else CausalLMTask)(
        model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1), clip_grad=1.0,
        loss_chunk=32)
    ids = jnp.zeros((2, 64), jnp.int32)
    step_fn, args = task._train_step_args({'input': ids, 'target': ids}, 1e-3, 0)
    return step_fn.lower(*args).as_text(dialect='hlo', debug_info=True)


@pytest.mark.parametrize('name', ['glm4_moe_lite_toy', 'smallthinker_toy', 'sdar_moe_toy'])
def test_the_lowered_training_step_scatters_no_row_in_an_expert_layer(name):
    """The engagement witness. The text of a toy's training step, forward and backward, both branches of every
    share's conditional: its only scatter of `dim`-wide rows is the embedding's gradient, into (vocabulary, dim);
    none has a (T, dim) result or buffer rows as updates, and none counts into integer bins (`bincount`). The
    scalar scatters stay (the derivatives of `top_k`, `take_along_axis`, `slot_weight[slots]`): every scatter the
    route's scope holds writes single elements."""
    hlo = lowered_step(name)
    assert len(SCATTER.findall(hlo)) >= 3 and 'glm.moe.route' in hlo      # the scalar ones and the embedding's are seen
    assert row_scatters(hlo, dim=64, embedding=(256, 64)) == []
    under_route = [m for m in SCATTER.finditer(hlo) if 'glm.moe.route' in m.group(4)]
    assert under_route and all('update_window_dims={}' in m.group(4) for m in under_route), under_route   # scalars
    # and the gathers that took their place carry the route's scope, in the backward pass too
    wide = [line for line in hlo.splitlines() if re.search(r' = \w+\[\d+,64\]\S* gather\(', line)]
    in_layer = [line for line in wide if 'glm.moe.route' in line]
    assert len(in_layer) >= 2 * (1 + K) and not [line for line in wide if 'op_name="' in line and 'glm.' not in line
                                                 and 'swa.' not in line], len(in_layer)


def test_the_witness_sees_a_scatter_formulation(monkeypatch):
    """The same reading of a layer whose combine and group sizes are written the old way finds both."""
    def to_tokens(rows, token, pos, covered):
        return jnp.zeros((pos.shape[0], rows.shape[1]), rows.dtype).at[token].add(rows)
    layer = SparseMoe(DIM, HIDDEN, EXPERTS, K, experts_held=2, n_shared=0, rngs=nnx.Rngs(0))
    x = jnp.ones((T, DIM))
    step = lambda: jax.jit(jax.value_and_grad(lambda m, x: m.routed(x)[0].sum(), argnums=1)).lower(layer, x).as_text(
        dialect='hlo', debug_info=True)
    assert row_scatters(step(), dim=DIM) == []
    with monkeypatch.context() as m:
        m.setattr(moe, '_to_tokens', to_tokens)
        m.setattr(moe, '_group_sizes', lambda s, held: jnp.bincount(s, length=held + 1)[:held].astype(jnp.int32))
        jax.clear_caches()                                              # `_dispatch` is traced once a shape
        found = row_scatters(step(), dim=DIM)
    jax.clear_caches()
    assert {(f[0], f[1]) for f in found} >= {('f32', (T, DIM)), ('s32', (3,))}, found
