"""The expert layer's route without a row scatter (`layers/moe.py`): rows go into the dispatch buffer by a gather
through `order` and come back by a gather through its inverse, a `custom_vjp` pair in which each move is the other's
derivative, and the group sizes are a comparison count. Held here against a plain formulation written with
`.at[].add`, as a transpose pair on random cotangents, against `jnp.bincount`, and in the text of the three
families' lowered training steps, which may hold no scatter of rows in an expert layer. Both moves read a large
source in column pieces (`_column_pieces`, by its bytes): the bounds at the cells' shapes, the results bitwise those
of one piece, and, in the text of the LFM2 cell's layer compiled for a described v5e, the gathers' sources in the
chip's fast memory (`tracing.scope_gathers`, which the step's two gauges and `train.py`'s log line read too)."""
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
from timm_tpu.utils import tracing  # noqa: E402

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


@pytest.mark.parametrize('pieces', [1, 2], ids=['whole', 'both_moves_in_two_pieces'])
@pytest.mark.parametrize('n', [128, 256], ids=['bounded', 'all_rows'])
@pytest.mark.parametrize('case', list(ROUTED))
def test_the_two_moves_are_each_others_transpose(case, n, pieces, monkeypatch):
    """`jax.vjp` of the gather into the buffer is the gather-sum back, and the other way round, to the bit; both
    agree with what autodiff makes of the plain masked gather (a scatter-add); and <to_buffer(x), u> = <x,
    to_tokens(u)>. Rows past `covered` are NaN in what comes from the buffer: nothing of them reaches a token. With
    both moves' sources read in column pieces every result is, to the bit, the one read whole."""
    token, pos, covered = moves(ROUTED[case](), 2, n)
    dim = 2 * moe.LANES
    assert int(covered) == {'dead_rows': int(covered), 'no_local_slot': 0, 'every_token_on_one_expert': T,
                            'every_slot_local': n}[case]
    x, dy = jax.random.normal(jax.random.key(1), (2, T, dim))
    u = jax.random.normal(jax.random.key(2), (n, dim))
    u_nan = jnp.where((jnp.arange(n) < covered)[:, None], u, jnp.nan)
    whole = moe._to_buffer(x, token, pos, covered), moe._to_tokens(u_nan, token, pos, covered)
    if pieces > 1:
        in_pieces(monkeypatch, min(T, n) * dim * 4, pieces)
        assert len(moe._column_pieces(T, dim, 4, moe.TAKE_WHOLE_BYTES)) == len(moe._column_pieces(n, dim, 4, moe.FAST_BYTES // 2)) == pieces + 1
    xs, back = jax.vjp(lambda x: moe._to_buffer(x, token, pos, covered), x)
    y, there = jax.vjp(lambda rows: moe._to_tokens(rows, token, pos, covered), u_nan)
    assert bool((xs == whole[0]).all()) and bool((y == whole[1]).all())
    assert bool(jnp.isfinite(y).all()) and bool((back(u_nan)[0] == y).all())
    assert bool((there(dy)[0] == moe._to_buffer(dy, token, pos, covered)).all())
    live = (jnp.arange(n) < covered)[:, None]
    xs_plain, back_plain = jax.vjp(lambda x: jnp.where(live, x[token], 0), x)
    assert bool((xs == xs_plain).all()) and float(jnp.abs(back(u)[0] - back_plain(u)[0]).max()) < 1e-5
    assert abs(float((xs * u).sum()) - float((x * y).sum())) < 1e-3
    if case == 'no_local_slot':
        assert float(jnp.abs(xs).max()) == 0.0 and float(jnp.abs(y).max()) == 0.0


SUM_WHOLE = moe.FAST_BYTES // 2     # what `_sum_rows` reads whole; `_take_rows`: `TAKE_WHOLE_BYTES`
PIECES = {
    # the gather-sum's rule (one piece up to half the fast memory): the cells' bounded buffers, bfloat16
    'smallthinker_bounded_120MiB': ((24576, 2560, 2), SUM_WHOLE, [0, 768, 1664, 2560]),
    'sdar_bounded_128MiB': ((32768, 2048, 2), SUM_WHOLE, [0, 640, 1280, 2048]),
    'glm_bounded_64MiB': ((16384, 2048, 2), SUM_WHOLE, [0, 2048]),
    'a_width_that_is_no_multiple_of_128': ((32768, 2000, 2), SUM_WHOLE, [0, 2000]),
    'lfm2_bounded_256MiB': ((65536, 2048, 2), SUM_WHOLE, [0, 256, 640, 1024, 1280, 1664, 2048]),
    # the fall-backs (`T * top_k` rows, 480 / 512 MiB: over `PIECED_BYTES`) whole, as the parent read them
    'smallthinker_fallback_480MiB': ((98304, 2560, 2), SUM_WHOLE, [0, 2560]),
    'sdar_lfm2_fallback_512MiB': ((131072, 2048, 2), SUM_WHOLE, [0, 2048]),
    'the_largest_pieced_256MiB_at_two_lane_columns': ((1 << 19, 256, 2), SUM_WHOLE, [0, 128, 256]),
    # the gather into the buffer's rule (one piece up to what the compiler places whole): the cells' token blocks
    'take_glm_sdar_x_64MiB': ((16384, 2048, 2), moe.TAKE_WHOLE_BYTES, [0, 2048]),
    'take_smallthinker_x_80MiB': ((16384, 2560, 2), moe.TAKE_WHOLE_BYTES, [0, 2560]),
    'take_lfm2_x_128MiB': ((32768, 2048, 2), moe.TAKE_WHOLE_BYTES, [0, 640, 1280, 2048]),
}


@pytest.mark.parametrize('case', list(PIECES))
def test_the_pieces_of_a_source_are_whole_lane_columns_by_its_size(case):
    """By the source's bytes alone: up to `FAST_BYTES` the bounds PR 42 shipped, digit for digit; pieces up to
    `PIECED_BYTES`, whole above; a piece is at most `PIECE_BYTES` and whole lane columns; a width that is no
    multiple of 128 whole."""
    (n, dim, itemsize), whole, bounds = PIECES[case]
    got = moe._column_pieces(n, dim, itemsize, whole)
    assert got == bounds
    if len(bounds) > 2 and not case.startswith('the_largest'):      # one lane column is the smallest piece there is
        assert all(lo % 128 == 0 and lo < hi and n * (hi - lo) * itemsize <= moe.PIECE_BYTES for lo, hi in zip(bounds, bounds[1:]))


def in_pieces(monkeypatch, n_bytes: int, pieces: int):
    """Both moves read a source of `n_bytes` in `pieces` column pieces."""
    monkeypatch.setattr(moe, 'FAST_BYTES', n_bytes)
    monkeypatch.setattr(moe, 'TAKE_WHOLE_BYTES', n_bytes // 2)
    monkeypatch.setattr(moe, 'PIECE_BYTES', -(-n_bytes // pieces))


@pytest.mark.parametrize('move', ['sum_rows', 'take_rows'])
@pytest.mark.parametrize('pieces', [2, 3])
def test_a_source_read_in_pieces_gives_the_sum_of_one_read_whole(pieces, move, monkeypatch):
    """To the bit: a column split changes no element's arithmetic."""
    n, dim = 128, 768
    token, pos, covered = moves(ROUTED['dead_rows'](), 2, n)
    source = jax.random.normal(jax.random.key(3), (n, dim))
    f = getattr(moe, '_' + move)
    whole = f(source, token, pos, covered)
    in_pieces(monkeypatch, n * dim * 4, pieces)
    assert len(moe._column_pieces(n, dim, 4, moe.TAKE_WHOLE_BYTES if move == 'take_rows' else moe.FAST_BYTES // 2)) == pieces + 1
    assert bool((f(source, token, pos, covered) == whole).all())


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


def parent_rule(n, dim, itemsize, whole):
    """`_column_pieces` as PR 42 shipped it: pieces only for a gather-sum's source between half and all of the fast
    memory; a larger one, and every source of the gather into the buffer, whole."""
    size = n * dim * itemsize
    pieced = whole == moe.FAST_BYTES // 2 < size <= moe.FAST_BYTES and dim % moe.LANES == 0
    pieces = -(-size // moe.PIECE_BYTES) if pieced else 1
    return [dim // moe.LANES * c // pieces * moe.LANES for c in range(pieces)] + [dim]


def test_the_cells_layer_compiled_for_a_v5e_gathers_its_rows_from_fast_memory(v5e_chip, monkeypatch):
    """The engagement witness of the size rule. One expert layer of the LFM2 cell (8 of 32 experts at top-4 over
    32768 tokens, bfloat16: a 256 MiB dispatch buffer, a 128 MiB token block, a 512 MiB fall-back buffer), loss and
    gradient, compiled for the described chip: of the gather fusions under `glm.moe.route`, both branches of the
    conditional, forward and backward, `tracing.scope_gathers` finds four in five on a source in the chip's fast
    memory (64 of 79: the fall-back's buffer is read whole from HBM by the rule, 8 gathers, and the compiler leaves
    a few reads of a piece there: PERF.md section 6, PR 45). Under the rule PR 42 shipped the same reader sees the
    row gathers' sources in HBM: only the scalar gathers are fast."""
    graphdef, state = nnx.split(nnx.eval_shape(lambda: SparseMoe(
        2048, 1792, 32, 4, experts_held=8, n_shared=0, scoring='sigmoid_bias', dtype=jnp.bfloat16,
        param_dtype=jnp.bfloat16, rngs=nnx.Rngs(0))))
    shapes = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip), state)
    x = jax.ShapeDtypeStruct((32768, 2048), jnp.bfloat16, sharding=v5e_chip)

    def compiled():
        jax.clear_caches()                                              # `_dispatch` is traced once a shape
        loss = lambda state, x: (nnx.merge(graphdef, state).routed(x)[0].astype(jnp.float32) ** 2).mean()  # noqa: E731
        return jax.jit(jax.value_and_grad(loss, argnums=(0, 1))).lower(shapes, x).compile().as_text()

    gathers, fast = tracing.scope_gathers(compiled(), 'glm.moe.route')
    # bounded: 3 takes + 4 x 6 sums forward, 3 + 4 x 6 + 4 x 3 backward; the fall-back 3 takes + 4 whole and so on
    assert gathers >= 70 and fast >= 0.75 * gathers, (gathers, fast)
    with monkeypatch.context() as m:
        m.setattr(moe, '_column_pieces', parent_rule)
        whole, whole_fast = tracing.scope_gathers(compiled(), 'glm.moe.route')
    jax.clear_caches()
    assert 20 <= whole < gathers and whole_fast <= 6, (whole, whole_fast)   # the chosen scores' and weights' gathers


def test_the_kept_step_program_sets_the_routes_two_gauges_and_the_log_line_prints_them():
    """Where the step program is compiled ahead of time and kept, the gather fusions under `glm.moe.route` in its
    text are counted once into two gauges, which `train.py`'s log line prints; on the CPU no source is in a fast
    memory. A program without an expert layer sets none (its reading is (0, 0))."""
    import train
    model = timm_tpu.create_model('glm4_moe_lite_toy', seed=0)
    task = CausalLMTask(model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1),
                        clip_grad=1.0, loss_chunk=32)
    ids = jnp.zeros((8, 64), jnp.int32)
    before = len(tracing.snapshot()['gauges'].get('moe.route_gathers', ()))
    text = task.lower_train_step({'input': ids, 'target': ids}, 1e-3, 0).as_text()
    gauges = tracing.snapshot()['gauges']
    assert len(gauges['moe.route_gathers']) == len(gauges['moe.route_gathers_fast']) == before + 1
    gathers, fast = gauges['moe.route_gathers'][-1][1], gauges['moe.route_gathers_fast'][-1][1]
    assert (gathers, fast) == tracing.scope_gathers(text, 'glm.moe.route') and gathers >= 2 * (1 + K) and fast == 0
    assert train._host_line(tracing.now_ns(), {})[0].endswith(f' route gathers {gathers} fast 0')
    assert tracing.scope_loops(text, 'kda.core') == 0        # no delta-rule layer: `kda.core_scans` is not set by this program
    assert tracing.scope_gathers(jax.jit(lambda x: x[::2] * 2).lower(ids).compile().as_text(), 'glm.moe.route') == (0, 0)
