"""Solar-Open2-250B at a toy size on the CPU, against the plain reference (`benchmarks/reference/solar_open2.py`): the
model with its two kinds of mixer (a gated delta rule computed in chunks against the reference's position-by-position
scan; a gated, position-free grouped-query attention), the expert layer with a shared expert under a placed selection
bias, the share of experts and of heads, the causal-LM task and the token feed. Seeded random weights, float32 on both
sides: they differ by summation order, so 1e-4 is a decade from a real difference.

Toy (`solar_open2_common.py`): the share's four layers at hidden 64, 4 of 8 heads of width 16 in either mixer.
"""
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

import timm_tpu  # noqa: E402
from benchmarks.harness import check, program, weights  # noqa: E402
from benchmarks.reference import lm_train_step  # noqa: E402
from benchmarks.reference import solar_open2 as ref  # noqa: E402
from timm_tpu.layers import GroupedQueryAttention, KimiDeltaAttention, SparseMoe  # noqa: E402
from timm_tpu.models.solar_open2 import PUBLISHED_GQA_LAYERS  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.task import CausalLMTask  # noqa: E402

from solar_open2_common import BIAS, S, SIZES, TOL, place_bias, seeded  # noqa: E402


def _batch(seed=0, rows=2):
    ids = np.random.default_rng(seed).integers(0, 256, (rows, S + 1))
    target = np.concatenate([ids[:, 1:S], np.full((rows, 1), -1)], axis=1)
    return jnp.asarray(ids[:, :S], jnp.int32), jnp.asarray(target, jnp.int32)


@pytest.fixture(scope='module')
def toy():
    """The toy model with the benchmark's seeded weights and bias, and the same weights for the reference."""
    params = seeded(ref, 11)
    model = place_bias(timm_tpu.create_model('solar_open2_toy', seed=0))
    program.load_weights(model, params)
    return model, params


def test_the_entry_points_hold_what_the_configuration_says():
    share = nnx.eval_shape(lambda: timm_tpu.create_model('solar_open2_250b_ep40'))
    leaves = program.named_leaves(nnx.state(share, nnx.Param))
    count = lambda names: sum(math.prod(leaves[k].shape) for k in names)  # noqa: E731
    assert count(leaves) == 840_871_320                                                   # ISSUE 47's count, part by part
    assert count(k for k in leaves if k.startswith('blocks.0.attn.')) == 13_631_488       # q, gate, o 3 x 4096 x 1024; k, v 2 x 4096 x 128
    assert count(k for k in leaves if k.startswith('blocks.1.kda.')) == 18_134_152
    assert count(k for k in leaves if k.startswith('blocks.2.mlp.')) == 142_868_480       # router 4096 x 320, 8 + 1 experts of 15,728,640
    assert count(k for k in leaves if k.startswith('blocks.')) == 639_540_632 and count(['embed.embedding', 'head.kernel', 'norm.scale']) == 201_330_688
    assert {k[len('blocks.3.kda.'):]: v.shape for k, v in leaves.items() if k.startswith('blocks.3.kda.')} == {
        'q_proj.kernel': (4096, 1024), 'k_proj.kernel': (4096, 1024), 'v_proj.kernel': (4096, 1024), 'q_taps': (1024, 4),
        'k_taps': (1024, 4), 'v_taps': (1024, 4), 'f_down.kernel': (4096, 128), 'f_up.kernel': (128, 1024),
        'beta_proj.kernel': (4096, 8), 'A_log': (8,), 'dt_bias': (1024,), 'g_down.kernel': (4096, 128), 'g_up.kernel': (128, 1024),
        'o_norm.scale': (128,), 'o_proj.kernel': (1024, 4096)}
    assert {k[len('blocks.0.attn.'):]: v.shape for k, v in leaves.items() if k.startswith('blocks.0.attn.')} == {
        'q_proj.kernel': (4096, 1024), 'k_proj.kernel': (4096, 128), 'v_proj.kernel': (4096, 128), 'gate_proj.kernel': (4096, 1024),
        'proj.kernel': (1024, 4096)}
    assert leaves['embed.embedding'].shape == (24576, 4096) and leaves['head.kernel'].shape == (4096, 24576)      # untied
    assert share.task_kind == 'causal_lm' and share.mtp is None and set(share.group_matcher()) == {'stem', 'blocks'}
    assert share.gqa_layers == (0,) and [b.attn is not None for b in share.blocks] == [True, False, False, False]
    assert (share.vocab_held, share.experts_held, share.heads_held, share.head_offset) == (24576, 8, 8, 0) and share.no_weight_decay() == set()
    attn, kda = share.blocks[0].attn, share.blocks[1].kda
    assert (attn.num_heads, attn.num_kv_heads, attn.head_dim, attn.rotary, attn.window, attn.scale, attn.head_offset) == (8, 1, 128, False, None, 128 ** -0.5, 0)
    assert attn.q_norm is None and attn.gate_proj is not None
    assert (kda.num_heads, kda.heads_held, kda.head_dim, kda.chunk) == (64, 8, 128, 64) and kda.o_norm.epsilon == 1e-5 == share.norm.epsilon
    for blk in share.blocks:
        m = blk.mlp
        assert (m.scoring, m.activation, m.top_k, m.num_experts, m.experts_held, m.scaling, m.norm_eps) == ('sigmoid_bias', 'silu', 8, 320, 8, 1.0, 1e-20)
        assert m.shared is not None and m.score_bias.shape == (320,)
    full = nnx.eval_shape(lambda: timm_tpu.create_model('solar_open2_250b'))
    full_leaves = program.named_leaves(nnx.state(full, nnx.Param))
    size = lambda prefix: sum(math.prod(v.shape) for k, v in full_leaves.items() if k.startswith(prefix))  # noqa: E731
    assert size('') == 250_287_794_944                                                    # the catalog's 250B, by shapes
    assert size('blocks.1.kda.') == 137_732_288 and size('blocks.0.attn.') == 109_051_904 and size('blocks.5.mlp.') == 5_050_204_160
    assert len(full.blocks) == 48 and full.vocab_held == 196608 and full.blocks[2].mlp.experts_held == 320
    assert full.gqa_layers == PUBLISHED_GQA_LAYERS == tuple(range(0, 48, 4))
    assert sum(b.kda is not None for b in full.blocks) == 36 and [i for i, b in enumerate(full.blocks) if b.attn is not None] == list(PUBLISHED_GQA_LAYERS)
    cell = dict(SIZES, vocab_held=24576, hidden_size=4096, num_attention_heads=64, num_key_value_heads=8, head_dim=128, heads_held=8,
                gate_rank=128, moe_intermediate_size=1280, n_routed_experts=320, experts_held=8)
    spec = ref.init_spec(cell)
    assert {k: v.shape for k, v in leaves.items()} == {k: tuple(shape) for k, (shape, _) in spec.items()}        # names and shapes the reference's weights carry
    with pytest.raises(ValueError, match='key/value'):
        timm_tpu.create_model('solar_open2_toy', heads_held=3)


def test_gqa_layers_decides_the_kind_of_every_layer():
    model = nnx.eval_shape(lambda: timm_tpu.create_model('solar_open2_toy', num_hidden_layers=6, gqa_layers=(1, 4)))
    assert [b.attn is not None for b in model.blocks] == [False, True, False, False, True, False]
    assert all((b.attn is None) != (b.kda is None) and isinstance(b.mlp, SparseMoe) for b in model.blocks)
    assert isinstance(model.blocks[1].attn, GroupedQueryAttention) and isinstance(model.blocks[0].kda, KimiDeltaAttention)
    assert [ref.layer_kind(dict(SIZES, gqa_layers=(1, 4)), i) for i in range(6)] == ['kda', 'attention', 'kda', 'kda', 'attention', 'kda']


def test_model_matches_the_reference_logits_loss_routes_and_every_gradient_leaf(toy):
    model, params = toy
    ids, target = _batch()
    task = CausalLMTask(model, loss_chunk=16)
    ref_forward = jax.jit(lambda p, i, t: ref.forward(SIZES, p, i, t, block_q=8))
    out = [ref_forward(params, ids[b], target[b]) for b in range(2)]
    logits, routes = nnx.jit(lambda m: (m(ids), m.routes(ids)))(model)
    assert float(jnp.abs(logits - jnp.stack([o['logits'] for o in out])).max()) < TOL
    assert routes.shape == (4, 2, S, 2) and bool((routes.transpose(1, 0, 2, 3) == jnp.stack([o['routes'] for o in out])).all())
    plain = nnx.jit(lambda m: m.routes(ids))(program.load_weights(m := timm_tpu.create_model('solar_open2_toy', seed=0), params) or m)
    assert float((np.asarray(plain) != np.asarray(routes)).mean()) > 0.1                      # the placed bias changes many choices: its path is live
    model.set_grad_checkpointing(True)                      # as the cell trains
    graphdef, state, rest = nnx.split(model, nnx.Param, ...)
    loss_fn = lambda st: task.loss_forward(nnx.merge(graphdef, st, rest, copy=True), {'input': ids, 'target': target})  # noqa: E731
    (loss, output), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(state)
    model.set_grad_checkpointing(False)
    n_main = 2 * (S - 1)
    ref_fn = lambda p: sum(ref.loss(SIZES, p, ids[b], target[b], n_main, None, block_q=8)[0] for b in range(2))  # noqa: E731
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref_fn))(params)
    assert abs(float(loss) - float(ref_loss)) < TOL and abs(float(ref_loss) - math.log(256)) < 0.5
    assert 'loss_mtp' not in output                         # no MTP module, no MTP term
    got = program.named_leaves(grads)
    assert set(got) == set(ref_grads) and {'head.kernel', 'blocks.1.kda.A_log', 'blocks.2.kda.dt_bias', 'blocks.0.attn.gate_proj.kernel'} <= set(got)
    gap, leaf = check.worst_leaf_gap({k: float(jnp.linalg.norm(v)) for k, v in got.items()},
                                     {k: float(jnp.linalg.norm(v)) for k, v in ref_grads.items()})
    assert gap < TOL, (gap, leaf)
    assert max(float(jnp.abs(got[k] - ref_grads[k]).max()) for k in got) < TOL
    assert all(float(jnp.linalg.norm(v)) > 0 for v in got.values())
    # the step's counters: three KDA layers' rows and chunks (2 sequences x 4 heads x 2 chunks of 16 a layer), the one
    # attention layer's tiles by the XLA path's slices (1+2+3+4 a sequence), the four expert layers'
    counters = {k: int(v) for k, v in output['counters'].items()}
    assert counters['kda.rows'] == 3 * 2 * S and counters['kda.chunks'] == 3 * 2 * 4 * 2 and counters['attn.full_blocks'] == 2 * 10
    assert counters['lm.tokens'] == 2 * S and counters['moe.dropped_slots'] == 0
    assert 0 < counters['moe.load_max'] <= counters['moe.local_slots'] <= 4 * 2 * S * 2


def test_the_seeded_decay_leaves_are_kimi_linears_and_the_same_for_program_and_reference(toy):
    _, params = toy
    plain = weights.make(11, ref.init_spec(SIZES))
    assert all(float(jnp.abs(plain[k]).max()) == 0.0 for k in plain if k.endswith(('A_log', 'dt_bias')))
    for i in (1, 2, 3):
        A, dt = jnp.exp(params[f'blocks.{i}.kda.A_log']), jax.nn.softplus(params[f'blocks.{i}.kda.dt_bias'])
        assert A.shape == (4,) and 1.0 <= float(A.min()) < float(A.max()) <= 16.0
        assert dt.shape == (64,) and 1e-3 * 0.999 <= float(dt.min()) < float(dt.max()) <= 0.1 * 1.001
    assert float(jnp.abs(params['blocks.1.kda.A_log'] - params['blocks.2.kda.A_log']).max()) > 0          # a draw a layer
    again, other = seeded(ref, 11), seeded(ref, 12)
    assert all(bool((again[k] == params[k]).all()) for k in params) and float(jnp.abs(other['blocks.1.kda.dt_bias'] - params['blocks.1.kda.dt_bias']).max()) > 0
    assert sorted(k for k in params if not bool((plain[k] == params[k]).all())) == sorted(
        f'blocks.{i}.kda.{leaf}' for i in (1, 2, 3) for leaf in ('A_log', 'dt_bias'))


def _expert_layer(p, held, offset):
    layer = SparseMoe(64, 32, 8, 2, experts_held=held, expert_offset=offset, n_shared=1, scoring='sigmoid_bias', rngs=nnx.Rngs(0))
    layer.router[...] = p['mlp.router']
    layer.score_bias[...] = jnp.asarray(BIAS)
    for name in ('w_gate', 'w_up', 'w_down'):
        getattr(layer, name)[...] = p['mlp.' + name][offset:offset + held]
    for name in ('fc1_g', 'fc1_x', 'fc2'):
        getattr(layer.shared, name).kernel[...] = p[f'mlp.shared.{name}.kernel']
    return layer


def test_the_parts_of_the_expert_shares_and_the_shared_expert_once_add_up_to_the_uncut_layer():
    """Four shares of 2 experts each (offsets 0, 2, 4, 6; the cell's are 0, 8, .., 312 of 320) against the reference given
    all 8, under the non-zero bias. Every chip computes the WHOLE shared expert: it is counted once."""
    cfg = dict(SIZES, experts_held=8)
    spec = {k[len('blocks.1.'):]: v for k, v in ref.init_spec(cfg).items() if k.startswith('blocks.1.mlp.')}
    p = weights.make(5, spec)
    named = {'blocks.1.' + k: v for k, v in p.items()}
    x = jax.random.normal(jax.random.key(1), (2 * S, 64))
    whole, chosen = ref.experts(cfg, named, 'blocks.1.', x, 'float32')
    shared = ref.swiglu(x, p['mlp.shared.fc1_g.kernel'], p['mlp.shared.fc1_x.kernel'], p['mlp.shared.fc2.kernel'], 'float32')
    total, slots = shared, 0
    for rank in range(4):
        layer = _expert_layer(p, 2, 2 * rank)
        part, counters = layer.routed(x)
        total, slots = total + part, slots + int(counters['moe.local_slots'])
        assert int(counters['moe.dropped_slots']) == 0 and bool((layer.choose(x) == chosen).all())
        both, _ = layer(x[None])
        assert float(jnp.abs(both[0] - (part + shared)).max()) < TOL             # a share's layer: its routed part + the shared expert
    assert slots == 2 * S * 2                                         # every (token, choice) slot lives on exactly one share
    assert float(jnp.abs(total - whole).max()) < TOL and float(jnp.abs(shared).max()) > 1e-4
    uncut, _ = _expert_layer(p, 8, 0)(x[None])
    assert float(jnp.abs(total - uncut[0]).max()) < TOL                  # and to the program's own uncut layer
    one, _ = ref.experts(dict(SIZES, expert_offset=6), {k: (v[6:] if '.mlp.w_' in k else v) for k, v in named.items()}, 'blocks.1.', x, 'float32')
    assert float(jnp.abs(part + shared - one).max()) < TOL and float(jnp.abs(part).max()) > 1e-4


@pytest.mark.parametrize('mixer', ['attn', 'kda'])
def test_the_head_shares_parts_of_either_mixer_add_up_to_the_uncut_mixer(mixer):
    """Two shares of 4 heads (the cell's: eight of 8 of 64) against the reference given all 8 heads: the output product is
    a sum over heads, so the parts add up; the low-rank gates' down-products and the head norm are whole on each."""
    cfg = dict(SIZES, heads_held=8)
    b = 'blocks.0.' if mixer == 'attn' else 'blocks.1.'
    p = {k: v for k, v in seeded(ref, 3, cfg).items() if k.startswith(b + mixer)}
    a = jax.random.normal(jax.random.key(1), (S, 64))
    whole = ref.attention(cfg, p, b, a, 'float32', 8) if mixer == 'attn' else ref.kda(cfg, p, b, a, 'float32')
    total = 0.0
    for offset in (0, 4):
        if mixer == 'attn':
            layer = GroupedQueryAttention(64, 8, 4, 16, rotary=False, gate=True, heads_held=4, head_offset=offset, block_q=8, rngs=nnx.Rngs(0))
        else:
            layer = KimiDeltaAttention(64, 8, 16, gate_rank=8, chunk=16, heads_held=4, head_offset=offset, rngs=nnx.Rngs(0))
        for path, leaf in nnx.to_flat_state(nnx.state(layer, nnx.Param)):
            name = '.'.join(map(str, path))
            leaf[...] = layer.take_heads(name, p[b + mixer + '.' + name])
        out = nnx.jit(lambda m, x: m(x))(layer, a[None])
        part = (out[0] if mixer == 'attn' else out)[0]
        total = total + part
        one_cfg = dict(SIZES, head_offset=offset)
        one_p = {k: layer.take_heads(k[len(b + mixer) + 1:], v) for k, v in p.items()}
        one = ref.attention(one_cfg, one_p, b, a, 'float32', 8) if mixer == 'attn' else ref.kda(one_cfg, one_p, b, a, 'float32')
        assert float(jnp.abs(part - one).max()) < TOL and float(jnp.abs(part - whole).max()) > 1e-4
    assert float(jnp.abs(total - whole).max()) < TOL and float(jnp.abs(whole).max()) > 1e-3


def test_causal_lm_task_two_steps_follow_the_reference(toy):
    _, params = toy
    model = place_bias(timm_tpu.create_model('solar_open2_toy', seed=0))
    model.set_grad_checkpointing(True)
    program.load_weights(model, params)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1, betas=(0.9, 0.95))
    task = CausalLMTask(model, optimizer=opt, clip_grad=1.0, loss_chunk=16)
    steps = [dict(zip(('input', 'target'), _batch(seed)), lr=1e-3) for seed in (1, 2)]
    losses, first = [], None
    for i, step in enumerate(steps):
        metrics = task.train_step({'input': step['input'], 'target': step['target']}, lr=step['lr'], step=i)
        losses.append(float(metrics['loss']))
        first = first or program.first_grad_norms(task)
        assert int(metrics['moe.dropped_slots']) == 0 and int(metrics['lm.tokens']) == 2 * S
        assert int(metrics['attn.full_blocks']) == 20 and int(metrics['kda.rows']) == 3 * 2 * S and int(metrics['kda.chunks']) == 48
    got = {'losses': losses, 'first_grad_norms': first,
           'param_change_norms': {k: float(jnp.linalg.norm(v - params[k]))
                                  for k, v in program.named_leaves(nnx.state(model, nnx.Param)).items()}}
    want = lm_train_step.follow(ref, SIZES, lambda: seeded(ref, 11), steps, clip=1.0, weight_decay=0.1, betas=(0.9, 0.95), block_q=8)
    numbers = check.training_numbers(got, want)
    assert all(v[0] < 1e-3 for v in numbers.values()), numbers      # Adam's division turns 1e-7 of gradient into 1e-4 of step
    assert want['routes'].shape == (2, 4, S, 2)
    assert all(float(jnp.abs(blk.mlp.score_bias[...] - jnp.asarray(BIAS)).max()) == 0.0 for blk in model.blocks)
    # the taps are decayed (matrices, by the optimizer's rank rule and the reference's mask alike); the vectors are not
    from timm_tpu.optim._param_groups import param_groups_weight_decay
    mask = program.named_leaves(param_groups_weight_decay(model, 0.1))
    assert mask['blocks.1.kda.q_taps'] and mask['embed.embedding'] and mask['head.kernel']
    assert not mask['blocks.1.kda.A_log'] and not mask['blocks.1.kda.dt_bias'] and not mask['blocks.1.kda.o_norm.scale'] and not mask['norm.scale']


def test_the_kept_step_program_holds_two_scans_a_delta_rule_layer_and_the_gauge_and_the_log_line_say_so():
    """The step as the cell trains it (blocks rematerialised under `save_only_these_names(CORE_OUT)`), four chunks a
    sequence, compiled ahead of time and kept: under `kda.core` one loop forward and one backward a KDA layer. The block's
    second forward pass finds the chunk-boundary states kept and holds no scan (before PR 48: three a layer).
    `tracing.scope_loops` is what the gauge `kda.core_scans` reads where a step program is kept, and `train.py`'s log
    line prints it."""
    import train
    from timm_tpu.utils import tracing
    assert tracing.SPANS['kda.core_scans'][0] == 'delta attention' and tracing.SPANS['kda.core_scans'][1].startswith('gauge: ')
    ids = jnp.zeros((8, 4 * 16), jnp.int32)
    model = timm_tpu.create_model('solar_open2_toy', seed=0)
    model.set_grad_checkpointing(True)
    task = CausalLMTask(model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1), clip_grad=1.0, loss_chunk=16)
    before = len(tracing.snapshot()['gauges'].get('kda.core_scans', ()))
    text = task.lower_train_step({'input': ids, 'target': ids}, 1e-3, 0).as_text()
    series = tracing.snapshot()['gauges']['kda.core_scans']
    assert len(series) == before + 1 and series[-1][1] == tracing.scope_loops(text, 'kda.core') == 2 * sum(b.kda is not None for b in model.blocks) == 6
    assert tracing.scope_loops(text, 'swa.attn') == 0 < tracing.scope_loops(text, '')
    assert ' kda scans 6' in train._host_line(tracing.now_ns(), {})[0]


def test_the_model_trains_through_train_main_on_the_token_feed_and_its_loss_falls(tmp_path):
    """A stream a model can learn (every id is the one before it plus 7): the loss of the last steps lies well under the
    first's ln 256."""
    import train
    stream = (np.arange(S * 72 + 7, dtype=np.int64) * 7 % 256).astype(np.int32)
    stream.tofile(tmp_path / 'train.bin')
    stream[:S * 8].tofile(tmp_path / 'validation.bin')
    out = train.main(['--model', 'solar_open2_toy', '--dataset', 'tokens', '--data-dir', str(tmp_path), '--seq-len', str(S),
                      '-b', '8', '--epochs', '3', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1', '--lr', '1e-2',
                      '--warmup-epochs', '0', '--sched', 'none', '--clip-grad', '1.0', '--grad-checkpointing', '--output', str(tmp_path / 'out'),
                      '--experiment', 't', '-j', '2', '--seed', '7'])
    assert out['loss'] < math.log(256) - 1.0 and 0.0 <= out['top1'] <= out['top5'] <= 100.0


def test_every_parameter_of_the_family_has_one_partition_rule():
    """No leaf falls to the catch-all: the taps share the short convolution's rule, `A_log` has its own, `dt_bias` is a
    bias, the head norm a norm scale, the nine products of a KDA layer plain kernels, the attention's gate among q/k/v."""
    from timm_tpu.parallel import create_mesh, default_partition_rules, match_rule
    from timm_tpu.parallel.sharding import spec_for_param
    from timm_tpu.utils.serialization import flatten_pytree
    rules = default_partition_rules()
    for name in ('solar_open2_toy', 'solar_open2_250b_ep40'):
        model = nnx.eval_shape(lambda n=name: timm_tpu.create_model(n))
        for path in flatten_pytree(nnx.state(model, nnx.Param)):
            assert sum(r.matches(path) for r in rules[:-1]) == 1, path
    named = {path: match_rule(path, rules)[1].name for path in (
        'blocks.1.kda.q_taps', 'blocks.1.kda.A_log', 'blocks.1.kda.dt_bias', 'blocks.1.kda.o_norm.scale', 'blocks.1.kda.q_proj.kernel',
        'blocks.1.kda.f_down.kernel', 'blocks.1.kda.o_proj.kernel', 'blocks.0.attn.gate_proj.kernel', 'blocks.0.attn.q_proj.kernel',
        'blocks.0.attn.proj.kernel', 'blocks.0.conv.taps', 'blocks.2.mlp.shared.fc1_g.kernel', 'blocks.2.mlp.router', 'head.kernel')}
    assert named == {'blocks.1.kda.q_taps': 'conv-taps', 'blocks.1.kda.A_log': 'decay-rate', 'blocks.1.kda.dt_bias': 'bias',
                     'blocks.1.kda.o_norm.scale': 'norm-scale', 'blocks.1.kda.q_proj.kernel': 'kernel', 'blocks.1.kda.f_down.kernel': 'kernel',
                     'blocks.1.kda.o_proj.kernel': 'kernel', 'blocks.0.attn.gate_proj.kernel': 'attn-qkv', 'blocks.0.attn.q_proj.kernel': 'attn-qkv',
                     'blocks.0.attn.proj.kernel': 'attn-out', 'blocks.0.conv.taps': 'conv-taps', 'blocks.2.mlp.shared.fc1_g.kernel': 'kernel',
                     'blocks.2.mlp.router': 'router', 'head.kernel': 'kernel'}
    mesh = create_mesh(devices=jax.devices()[:8], fsdp=8)
    assert tuple(spec_for_param('blocks.1.kda.q_taps', (1024, 4), mesh)) == () == tuple(spec_for_param('blocks.1.kda.A_log', (8,), mesh))
    assert tuple(spec_for_param('blocks.1.kda.q_proj.kernel', (4096, 1024), mesh)) == ('fsdp', None)
