"""LFM2-8B-A1B at a toy size on the CPU, against the plain reference (`benchmarks/reference/lfm2_moe.py`): the model
with its two kinds of mixer and two kinds of feed-forward, the tied embedding-and-head, the router's selection bias,
the expert layer's share, the D = 64 causal kernel, the causal-LM task and the token feed. Seeded random weights,
float32 on both sides: they differ by summation order, so 1e-4 is a decade from a real difference.

Toy (`lfm2_moe_common.py`): the share's five layers at hidden 64, 4 query heads on 2 key/value heads of width 16.
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
from benchmarks.reference import lfm2_moe as ref  # noqa: E402
from benchmarks.reference import lm_train_step  # noqa: E402
from timm_tpu.layers import GroupedQueryAttention, SparseMoe, build_rotary_pos_embed_1d  # noqa: E402
from timm_tpu.layers.moe import route  # noqa: E402
from timm_tpu.models.lfm2_moe import PUBLISHED_LAYER_TYPES  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.task import CausalLMTask  # noqa: E402

from lfm2_moe_common import BIAS, S, SIZES, TOL, place_bias  # noqa: E402


def _batch(seed=0, rows=2):
    ids = np.random.default_rng(seed).integers(0, 256, (rows, S + 1))
    target = np.concatenate([ids[:, 1:S], np.full((rows, 1), -1)], axis=1)
    return jnp.asarray(ids[:, :S], jnp.int32), jnp.asarray(target, jnp.int32)


@pytest.fixture(scope='module')
def toy():
    """The toy model with the benchmark's seeded weights and bias, and the same weights for the reference."""
    params = weights.make(11, ref.init_spec(SIZES))
    model = place_bias(timm_tpu.create_model('lfm2_moe_toy', seed=0))
    program.load_weights(model, params)
    return model, params


def test_the_entry_points_hold_what_the_configuration_says():
    share = nnx.eval_shape(lambda: timm_tpu.create_model('lfm2_8b_a1b_ep4'))
    leaves = program.named_leaves(nnx.state(share, nnx.Param))
    count = lambda names: sum(math.prod(leaves[k].shape) for k in names)  # noqa: E731
    assert count(leaves) == 507_820_160                                                   # ISSUE 43's table, part by part
    assert count(k for k in leaves if k.startswith('blocks.0.')) == 60_827_648            # conv 16,783,360 + SwiGLU 44,040,192 + norms
    assert count(k for k in leaves if k.startswith('blocks.1.')) == 98_635_904            # attention 10,485,888 + router + 8 experts + norms
    assert count(k for k in leaves if k.startswith('blocks.3.')) == 104_933_376 and count(['embed.embedding', 'norm.scale']) == 33_556_480
    assert {k: v.shape for k, v in leaves.items() if k.startswith('blocks.2.')} == {
        'blocks.2.norm1.scale': (2048,), 'blocks.2.norm2.scale': (2048,), 'blocks.2.conv.in_proj.kernel': (2048, 6144),
        'blocks.2.conv.taps': (2048, 3), 'blocks.2.conv.out_proj.kernel': (2048, 2048), 'blocks.2.mlp.router': (2048, 32),
        'blocks.2.mlp.w_gate': (8, 2048, 1792), 'blocks.2.mlp.w_up': (8, 2048, 1792), 'blocks.2.mlp.w_down': (8, 1792, 2048)}
    assert {k[len('blocks.1.attn.'):]: v.shape for k, v in leaves.items() if k.startswith('blocks.1.attn.')} == {
        'q_proj.kernel': (2048, 2048), 'k_proj.kernel': (2048, 512), 'v_proj.kernel': (2048, 512), 'proj.kernel': (2048, 2048),
        'q_norm.scale': (64,), 'k_norm.scale': (64,)}
    assert leaves['embed.embedding'].shape == (16384, 2048) and not [k for k in leaves if k.startswith('head')]   # tied: ONE leaf
    assert share.task_kind == 'causal_lm' and share.mtp is None and set(share.group_matcher()) == {'stem', 'blocks'}
    assert share.layer_types == ('conv', 'full_attention', 'conv', 'conv', 'conv') == PUBLISHED_LAYER_TYPES[1:6]
    assert [b.dense for b in share.blocks] == [True, False, False, False, False] and share.no_weight_decay() == set()
    assert (share.vocab_held, share.experts_held, share.expert_offset) == (16384, 8, 0) and share.get_classifier() is share.embed
    attn = share.blocks[1].attn
    assert (attn.num_heads, attn.num_kv_heads, attn.head_dim, attn.rotary, attn.window, attn.scale) == (32, 8, 64, True, None, 0.125)
    assert attn.q_norm.epsilon == 1e-5 == share.norm.epsilon and share.rope_theta == 1e6 and share.rope_dim == 64
    for blk in share.blocks[1:]:
        m = blk.mlp
        assert (m.scoring, m.activation, m.top_k, m.num_experts, m.experts_held, m.scaling, m.norm_eps) == ('sigmoid_bias', 'silu', 4, 32, 8, 1.0, 1e-6)
        assert m.shared is None and m.score_bias.shape == (32,)
    full = nnx.eval_shape(lambda: timm_tpu.create_model('lfm2_8b_a1b'))
    full_leaves = program.named_leaves(nnx.state(full, nnx.Param))
    assert sum(math.prod(v.shape) for v in full_leaves.values()) == 8_339_929_856         # the published 8.3B, tied
    assert len(full.blocks) == 24 and full.vocab_held == 65536 and full.blocks[2].mlp.experts_held == 32
    assert sum(b.conv is not None for b in full.blocks) == 18 and sum(b.attn is not None for b in full.blocks) == 6
    assert [i for i, b in enumerate(full.blocks) if b.attn is not None] == [2, 6, 10, 14, 18, 21] and sum(b.dense for b in full.blocks) == 2
    assert set(leaves) == set(ref.init_spec(dict(SIZES, experts_held=8)))                 # the names the reference's weights carry
    with pytest.raises(ValueError, match='layer_types'):
        timm_tpu.create_model('lfm2_moe_toy', layer_types=['conv', 'conv'])
    with pytest.raises(ValueError, match='layer_types'):
        timm_tpu.create_model('lfm2_moe_toy', layer_types=['conv', 'window', 'conv', 'conv', 'conv'])


def test_model_matches_the_reference_logits_loss_routes_and_every_gradient_leaf(toy):
    model, params = toy
    ids, target = _batch()
    task = CausalLMTask(model, loss_chunk=16)
    ref_forward = jax.jit(lambda p, i, t: ref.forward(SIZES, p, i, t, block_q=8))
    out = [ref_forward(params, ids[b], target[b]) for b in range(2)]
    logits, routes = nnx.jit(lambda m: (m(ids), m.routes(ids)))(model)
    assert float(jnp.abs(logits - jnp.stack([o['logits'] for o in out])).max()) < TOL
    assert routes.shape == (4, 2, S, 2) and bool((routes.transpose(1, 0, 2, 3) == jnp.stack([o['routes'] for o in out])).all())
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
    assert set(got) == set(ref_grads) and 'embed.embedding' in got and 'blocks.2.conv.taps' in got
    gap, leaf = check.worst_leaf_gap({k: float(jnp.linalg.norm(v)) for k, v in got.items()},
                                     {k: float(jnp.linalg.norm(v)) for k, v in ref_grads.items()})
    assert gap < TOL, (gap, leaf)
    assert max(float(jnp.abs(got[k] - ref_grads[k]).max()) for k in got) < TOL
    # the step's counters: four conv layers' rows, the one attention layer's tiles by the XLA path's slices
    # (1+2+3+4 a sequence), the four expert layers'
    counters = {k: int(v) for k, v in output['counters'].items()}
    assert counters['sconv.rows'] == 4 * 2 * S and counters['attn.full_blocks'] == 2 * 10 and counters['lm.tokens'] == 2 * S
    assert counters['moe.dropped_slots'] == 0 and 0 < counters['moe.load_max'] <= counters['moe.local_slots'] <= 4 * 2 * S * 2


def test_the_tied_leafs_gradient_is_the_sum_of_its_two_uses(toy):
    """The embedding read as a lookup and, transposed, as the head: the gradient of the ONE leaf is what the lookup's
    copy and the head's copy would get if they were two, added."""
    model, _ = toy
    ids, target = _batch(5)
    task = CausalLMTask(model, loss_chunk=16)
    graphdef, state, rest = nnx.split(model, nnx.Param, ...)
    loss_fn = lambda st: task.loss_forward(nnx.merge(graphdef, st, rest, copy=True), {'input': ids, 'target': target})[0]  # noqa: E731
    tied = program.named_leaves(jax.jit(jax.grad(loss_fn))(state))['embed.embedding']

    def two_uses(lookup, head):
        m = nnx.merge(graphdef, state, rest, copy=True)
        x, rope = lookup[ids], m._rope(S)
        for blk in m.blocks:
            x, _ = blk(x, rope)
        logits = m.norm(x) @ head.T
        valid = target != -1
        nll = jax.nn.logsumexp(logits, -1) - jnp.take_along_axis(logits, jnp.where(valid, target, 0)[..., None], -1)[..., 0]
        return jnp.where(valid, nll, 0.0).sum() / valid.sum()

    E = model.embed.embedding[...]
    d_lookup, d_head = jax.jit(jax.grad(two_uses, argnums=(0, 1)))(E, E)
    unread = np.setdiff1d(np.arange(256), np.asarray(ids).ravel())
    assert float(jnp.abs(d_lookup[unread]).max()) == 0.0 and float(jnp.abs(d_head[unread]).max()) > 0     # a row no id named: the head's use alone
    assert float(jnp.abs(d_lookup).max()) > 1e-4 and float(jnp.abs(tied - (d_lookup + d_head)).max()) < 1e-6
    assert float(jnp.abs(tied - d_head).max()) > 1e-4 and float(jnp.abs(tied - d_lookup).max()) > 1e-4


def test_the_bias_steers_the_choice_and_not_the_weights(toy):
    """s + b chooses, s alone weighs: under the non-zero bias the chosen experts differ from the unbiased choice on
    many tokens, the weights are the chosen SCORES over their sum + 1e-6, and the bias takes no gradient."""
    model, params = toy
    e = jax.random.normal(jax.random.key(2), (2 * S, 64))
    router = params['blocks.1.mlp.router']
    bias = jnp.asarray(BIAS)
    idx, w = route(e, router, bias, 2, 1.0, 'sigmoid_bias', 1e-6)
    plain_idx, _ = route(e, router, jnp.zeros(8), 2, 1.0, 'sigmoid_bias', 1e-6)
    s = jax.nn.sigmoid(jnp.matmul(e, router, precision='highest'))
    assert bool((idx == jax.lax.top_k(s + bias, 2)[1]).all()) and float((idx != plain_idx).mean()) > 0.2
    chosen = jnp.take_along_axis(s, idx, -1)
    assert float(jnp.abs(w - chosen / (chosen.sum(-1, keepdims=True) + 1e-6)).max()) < 1e-7
    assert float(jnp.abs(w.sum(-1) - 1.0).max()) < 1e-5 and float(jnp.abs(w.sum(-1) - 1.0).max()) > 0     # the epsilon is there
    ref_idx, ref_w = ref.routes(SIZES, params, 'blocks.1.', e)
    assert bool((ref_idx == idx).all()) and float(jnp.abs(ref_w - w).max()) < 1e-6
    assert bool((ref.routes(dict(SIZES, expert_bias=None), params, 'blocks.1.', e)[0] == plain_idx).all())
    layer = model.blocks[1].mlp
    assert bool((layer.choose(e) == idx).all()) and type(layer.score_bias) is nnx.Variable    # a buffer: no `nnx.Param`
    # GLM's epsilon stays what it was: the default
    assert SparseMoe(8, 4, 4, 2, n_shared=0, rngs=nnx.Rngs(0)).norm_eps == 1e-20


def _expert_layer(p, held, offset):
    layer = SparseMoe(64, 32, 8, 2, experts_held=held, expert_offset=offset, n_shared=0, scoring='sigmoid_bias',
                      norm_eps=1e-6, rngs=nnx.Rngs(0))
    layer.router[...] = p['mlp.router']
    layer.score_bias[...] = jnp.asarray(BIAS)
    for name in ('w_gate', 'w_up', 'w_down'):
        getattr(layer, name)[...] = p['mlp.' + name][offset:offset + held]
    return layer


def test_the_parts_of_the_four_shares_add_up_to_the_uncut_layer():
    """The share test: four shares of 2 experts each (offsets 0, 2, 4, 6; the cell's are 0, 8, 16, 24 of 32) against
    the reference given all 8, under the non-zero bias."""
    cfg = dict(SIZES, experts_held=8)
    spec = {k[len('blocks.1.'):]: v for k, v in ref.init_spec(cfg).items() if k.startswith('blocks.1.mlp.')}
    p = weights.make(5, spec)
    x = jax.random.normal(jax.random.key(1), (2 * S, 64))
    whole, chosen = ref.experts(cfg, {'blocks.1.' + k: v for k, v in p.items()}, 'blocks.1.', x, 'float32')
    total, slots = 0.0, 0
    for rank in range(4):
        layer = _expert_layer(p, 2, 2 * rank)
        part, counters = layer.routed(x)
        total, slots = total + part, slots + int(counters['moe.local_slots'])
        assert int(counters['moe.dropped_slots']) == 0 and bool((layer.choose(x) == chosen).all())
    assert slots == 2 * S * 2                                         # every (token, choice) slot lives on exactly one share
    assert float(jnp.abs(total - whole).max()) < TOL
    uncut, _ = _expert_layer(p, 8, 0).routed(x)
    assert float(jnp.abs(total - uncut).max()) < TOL                  # and to the program's own uncut layer
    # the reference given one share gives that share's part: the weights stay normalised over ALL chosen
    one, _ = ref.experts(dict(SIZES, expert_offset=6), {'blocks.1.' + k: (v[6:] if k.startswith('mlp.w_') else v) for k, v in p.items()},
                         'blocks.1.', x, 'float32')
    assert float(jnp.abs(part - one).max()) < TOL and float(jnp.abs(part).max()) > 1e-4


@pytest.mark.parametrize('head_dim', [64, 128])
def test_the_attention_layer_takes_the_pallas_kernel_at_both_head_widths_and_agrees_with_the_xla_path(head_dim):
    """32 query heads on 8 key/value heads with the q/k norms and the rotary turn over 256 positions:
    `causal_flash_supported` at width 64 (half a lane tile: LFM2's) as at 128, so the core is the registered kernel's
    grouped form (interpreted here), forward and backward against the XLA query-block path."""
    import timm_tpu.kernels as kernels
    attn = GroupedQueryAttention(64, 32, 8, head_dim, rotary=True, qk_norm=True, eps=1e-5, block_q=64, rngs=nnx.Rngs(5))
    x = jax.random.normal(jax.random.key(0), (1, 256, 64))
    rope = build_rotary_pos_embed_1d(256, head_dim, 1e6)
    q, k, v = attn.qkv(x, rope)
    assert q.shape == (1, 32, 256, head_dim) and k.shape == v.shape == (1, 8, 256, head_dim)
    assert kernels.causal_flash_supported(q, k, v)
    # half a lane tile under the plain causal mask alone; no other width under a lane tile
    assert kernels.causal_flash_supported(q, k, v, window=128) == (head_dim == 128)
    assert not any(kernels.causal_flash_supported(q[..., :d], k[..., :d], v[..., :d]) for d in (16, 32, 96) if d < head_dim)
    both = jnp.concatenate([k, k], axis=2)
    assert kernels.causal_flash_supported(q, both, both, block_diffusion=4) == (head_dim == 128)
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
    assert tiles == 1 and want_tiles == 10                  # one 256-wide tile in the kernel; 64-wide on the XLA path: 1+2+3+4


def test_causal_lm_task_two_steps_follow_the_reference(toy):
    _, params = toy
    model = place_bias(timm_tpu.create_model('lfm2_moe_toy', seed=0))
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
        assert int(metrics['attn.full_blocks']) == 20 and int(metrics['sconv.rows']) == 4 * 2 * S
    got = {'losses': losses, 'first_grad_norms': first,
           'param_change_norms': {k: float(jnp.linalg.norm(v - params[k]))
                                  for k, v in program.named_leaves(nnx.state(model, nnx.Param)).items()}}
    want = lm_train_step.follow(ref, SIZES, lambda: weights.make(11, ref.init_spec(SIZES)), steps, clip=1.0,
                                weight_decay=0.1, betas=(0.9, 0.95), block_q=8)
    numbers = check.training_numbers(got, want)
    assert all(v[0] < 1e-3 for v in numbers.values()), numbers      # Adam's division turns 1e-7 of gradient into 1e-4 of step
    assert want['routes'].shape == (2, 4, S, 2)
    # the bias rides in the step's non-parameter state and comes back as it went in: no update in the step
    assert all(float(jnp.abs(blk.mlp.score_bias[...] - jnp.asarray(BIAS)).max()) == 0.0 for blk in model.blocks[1:])
    # the taps are decayed (a matrix, by the optimizer's rank rule and the reference's mask alike), norm scales are not
    from timm_tpu.optim._param_groups import param_groups_weight_decay
    mask = program.named_leaves(param_groups_weight_decay(model, 0.1))
    assert mask['blocks.0.conv.taps'] and mask['embed.embedding'] and not mask['blocks.1.attn.q_norm.scale'] and not mask['norm.scale']


def test_the_model_trains_through_train_main_on_the_token_feed(tmp_path):
    import train
    rng = np.random.default_rng(0)
    rng.integers(0, 256, S * 24 + 7, dtype=np.int32).tofile(tmp_path / 'train.bin')
    rng.integers(0, 256, S * 8, dtype=np.int32).tofile(tmp_path / 'validation.bin')
    out = train.main(['--model', 'lfm2_moe_toy', '--dataset', 'tokens', '--data-dir', str(tmp_path), '--seq-len', str(S),
                      '-b', '8', '--epochs', '1', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1',
                      '--clip-grad', '1.0', '--grad-checkpointing', '--output', str(tmp_path / 'out'), '--experiment', 't',
                      '-j', '2', '--seed', '7'])
    assert abs(out['loss'] - math.log(256)) < 0.5 and 0.0 <= out['top1'] <= out['top5'] <= 100.0


def test_every_parameter_of_the_family_has_one_partition_rule():
    """What the zoo's partition sweep holds every family to, here for the toy and the share: no leaf falls to the
    catch-all; the taps have a rule of their own, the two products are plain kernels, the tied embedding is one leaf."""
    from timm_tpu.parallel import create_mesh, default_partition_rules, match_rule
    from timm_tpu.parallel.sharding import spec_for_param
    from timm_tpu.utils.serialization import flatten_pytree
    rules = default_partition_rules()
    for name in ('lfm2_moe_toy', 'lfm2_8b_a1b_ep4'):
        model = nnx.eval_shape(lambda n=name: timm_tpu.create_model(n))
        for path in flatten_pytree(nnx.state(model, nnx.Param)):
            assert sum(r.matches(path) for r in rules[:-1]) == 1, path
    named = {path: match_rule(path, rules)[1].name for path in ('blocks.0.conv.taps', 'blocks.0.conv.in_proj.kernel',
             'blocks.0.conv.out_proj.kernel', 'embed.embedding', 'blocks.1.attn.q_norm.scale', 'blocks.1.attn.q_proj.kernel',
             'blocks.2.mlp.w_up', 'blocks.2.mlp.router', 'blocks.0.mlp.fc1_g.kernel')}
    assert named == {'blocks.0.conv.taps': 'conv-taps', 'blocks.0.conv.in_proj.kernel': 'kernel', 'blocks.0.conv.out_proj.kernel': 'kernel',
                     'embed.embedding': 'token-embed', 'blocks.1.attn.q_norm.scale': 'norm-scale', 'blocks.1.attn.q_proj.kernel': 'attn-qkv',
                     'blocks.2.mlp.w_up': 'expert-stack', 'blocks.2.mlp.router': 'router', 'blocks.0.mlp.fc1_g.kernel': 'mlp-fc1'}
    mesh = create_mesh(devices=jax.devices()[:8], fsdp=8)
    assert tuple(spec_for_param('blocks.0.conv.taps', (2048, 3), mesh)) == ()
    assert tuple(spec_for_param('blocks.0.conv.in_proj.kernel', (2048, 6144), mesh)) == (None, 'fsdp')
