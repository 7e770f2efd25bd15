"""SmallThinker-21BA3B at a toy size on the CPU, against the plain reference
(`benchmarks/reference/smallthinker.py`): the model, the grouped-query layer
with its window and its optional rotary turn, the router that reads the
attention's input, the expert layer's share, the causal-LM task and the token
feed. Seeded random weights, float32 on both sides: they differ by summation
order (1e-6 was seen), so 1e-4 is a decade from a real difference.

Toy: hidden 64, 4 query heads on 2 key/value heads of width 16, window 8 at
S 32 in query blocks of 8, 8 experts top-2 with 2 held, vocabulary 256, 4 layers
= one period (full, window, window, window).
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
from benchmarks.reference import smallthinker as ref  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.task import CausalLMTask  # noqa: E402

from smallthinker_common import S, SIZES, TOL  # noqa: E402


def _batch(seed=0, rows=2):
    ids = np.random.default_rng(seed).integers(0, 256, (rows, S + 1))
    target = np.concatenate([ids[:, 1:S], np.full((rows, 1), -1)], axis=1)
    return jnp.asarray(ids[:, :S], jnp.int32), jnp.asarray(target, jnp.int32)


@pytest.fixture(scope='module')
def toy():
    """The toy model with the benchmark's seeded weights, and the same weights for the reference."""
    params = weights.make(11, ref.init_spec(SIZES))
    model = timm_tpu.create_model('smallthinker_toy', seed=0)
    program.load_weights(model, params)
    return model, params


def test_the_entry_points_hold_what_the_configuration_says():
    share = nnx.eval_shape(lambda: timm_tpu.create_model('smallthinker_21b_ep8'))
    leaves = program.named_leaves(nnx.state(share, nnx.Param))
    assert sum(math.prod(v.shape) for v in leaves.values()) == 643_852_800             # ISSUE 31's table
    assert {k: v.shape for k, v in leaves.items() if k.startswith('blocks.1.')} == {
        'blocks.1.norm1.scale': (2560,), 'blocks.1.norm2.scale': (2560,), 'blocks.1.attn.q_proj.kernel': (2560, 3584),
        'blocks.1.attn.k_proj.kernel': (2560, 512), 'blocks.1.attn.v_proj.kernel': (2560, 512),
        'blocks.1.attn.proj.kernel': (3584, 2560), 'blocks.1.mlp.router': (2560, 64),
        'blocks.1.mlp.w_gate': (8, 2560, 768), 'blocks.1.mlp.w_up': (8, 2560, 768), 'blocks.1.mlp.w_down': (8, 768, 2560)}
    assert leaves['embed.embedding'].shape == (18992, 2560) and leaves['head.kernel'].shape == (2560, 18992)
    assert share.task_kind == 'causal_lm' and share.mtp is None and set(share.group_matcher()) == {'stem', 'blocks'}
    kinds = [(b.attn.rotary, b.attn.window) for b in share.blocks]
    assert kinds == [(False, None), (True, 4096), (True, 4096), (True, 4096)] * 2       # two periods
    assert all(b.mlp.scoring == 'softmax_topk' and b.mlp.activation == 'relu' and b.mlp.shared is None
               and b.mlp.score_bias is None and b.mlp.top_k == 6 for b in share.blocks)
    full = nnx.eval_shape(lambda: timm_tpu.create_model('smallthinker_21b'))
    assert len(full.blocks) == 52 and full.vocab_held == 151936 and full.blocks[1].mlp.experts_held == 64
    assert sum(b.attn.window is None for b in full.blocks) == 13 and set(leaves) == set(ref.init_spec(dict(
        SIZES, num_hidden_layers=8, rope_layout=[0, 1, 1, 1] * 2, sliding_window_layout=[0, 1, 1, 1] * 2)))
    with pytest.raises(ValueError, match='layouts'):
        timm_tpu.create_model('smallthinker_toy', rope_layout=[0, 1])


def test_model_matches_the_reference_logits_loss_routes_and_every_gradient_leaf(toy):
    model, params = toy
    ids, target = _batch()
    task = CausalLMTask(model, loss_chunk=16)
    ref_forward = jax.jit(lambda p, i, t: ref.forward(SIZES, p, i, t, block_q=8))
    out = [ref_forward(params, ids[b], target[b]) for b in range(2)]
    logits, routes = nnx.jit(lambda m: (m(ids), m.routes(ids)))(model)
    assert float(jnp.abs(logits - jnp.stack([o['logits'] for o in out])).max()) < TOL
    assert bool((routes.transpose(1, 0, 2, 3) == jnp.stack([o['routes'] for o in out])).all())
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
    assert set(got) == set(ref_grads)
    gap, leaf = check.worst_leaf_gap({k: float(jnp.linalg.norm(v)) for k, v in got.items()},
                                     {k: float(jnp.linalg.norm(v)) for k, v in ref_grads.items()})
    assert gap < TOL, (gap, leaf)
    assert max(float(jnp.abs(got[k] - ref_grads[k]).max()) for k in got) < TOL
    # the step's counters: tiles by the XLA path's slices (one full layer: 1+2+3+4 a sequence; three window
    # layers: 1+2+2+2), and the expert layer's
    counters = {k: int(v) for k, v in output['counters'].items()}
    assert counters['attn.full_blocks'] == 2 * 10 and counters['attn.window_blocks'] == 2 * 3 * 7
    assert counters['moe.dropped_slots'] == 0 and counters['lm.tokens'] == 2 * S
    assert 0 < counters['moe.load_max'] <= counters['moe.local_slots'] <= 4 * 2 * S * 2


def test_the_router_reads_the_attentions_input_not_its_output(toy):
    """Another output projection in layer 1 moves that layer's attention output and everything after it, and
    leaves layer 1's own routing where it was: the routing of a layer is known before its attention runs."""
    model, params = toy
    ids, _ = _batch(3)
    routes = nnx.jit(lambda m, i: m.routes(i))
    before = np.asarray(routes(model, ids))
    saved = model.blocks[1].attn.proj.kernel[...]
    model.blocks[1].attn.proj.kernel[...] = saved + 0.5 * jax.random.normal(jax.random.key(9), saved.shape)
    try:
        after = np.asarray(routes(model, ids))
    finally:
        model.blocks[1].attn.proj.kernel[...] = saved
    assert (after[:2] == before[:2]).all() and (after[2] != before[2]).any()
    # and the reference routes on the same tensor: RMSNorm_1 of the layer's input
    a = ref.rms_norm(params['embed.embedding'][ids[0]], params['blocks.0.norm1.scale'], 1e-6)
    assert (np.asarray(ref.routes(SIZES, params, 'blocks.0.', a)[0]) == before[0, 0]).all()


def test_causal_lm_task_two_steps_follow_the_reference(toy):
    _, params = toy
    model = timm_tpu.create_model('smallthinker_toy', seed=0)
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
        assert int(metrics['attn.full_blocks']) == 20 and int(metrics['attn.window_blocks']) == 42
    got = {'losses': losses, 'first_grad_norms': first,
           'param_change_norms': {k: float(jnp.linalg.norm(v - params[k]))
                                  for k, v in program.named_leaves(nnx.state(model, nnx.Param)).items()}}
    want = lm_train_step.follow(ref, SIZES, lambda: weights.make(11, ref.init_spec(SIZES)), steps, clip=1.0,
                                weight_decay=0.1, betas=(0.9, 0.95), block_q=8)
    numbers = check.training_numbers(got, want)
    assert all(v[0] < 1e-3 for v in numbers.values()), numbers      # Adam's division turns 1e-7 of gradient into 1e-4 of step
    assert want['routes'].shape == (2, 4, S, 2)


def test_the_model_trains_through_train_main_on_the_token_feed(tmp_path):
    import train
    from timm_tpu.utils import tracing
    rng = np.random.default_rng(0)
    rng.integers(0, 256, S * 24 + 7, dtype=np.int32).tofile(tmp_path / 'train.bin')
    rng.integers(0, 256, S * 8, dtype=np.int32).tofile(tmp_path / 'validation.bin')
    mark = tracing.now_ns()
    out = train.main(['--model', 'smallthinker_toy', '--dataset', 'tokens', '--data-dir', str(tmp_path), '--seq-len', str(S),
                      '-b', '8', '--epochs', '1', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1',
                      '--clip-grad', '1.0', '--grad-checkpointing', '--output', str(tmp_path / 'out'), '--experiment', 't',
                      '-j', '2', '--seed', '7'])
    assert abs(out['loss'] - math.log(256)) < 0.5 and 0.0 <= out['top1'] <= out['top5'] <= 100.0
    spans = [s for s in tracing.snapshot()['spans'] if s.start_ns >= mark]
    assert sum(s.name == 'task.train_step' for s in spans) == 3 and any(s.name == 'loader.batch_wait' for s in spans)


def test_every_parameter_of_the_family_has_one_partition_rule_and_heads_split_over_the_model_axis():
    """What the zoo's partition sweep holds every family to, here for the toy and the share: no leaf falls to
    the catch-all; the four attention products carry the names the tensor-parallel rules know."""
    from timm_tpu.parallel import create_mesh, default_partition_rules, match_rule
    from timm_tpu.parallel.sharding import spec_for_param
    from timm_tpu.utils.serialization import flatten_pytree
    rules = default_partition_rules()
    for name in ('smallthinker_toy', 'smallthinker_21b_ep8'):
        model = nnx.eval_shape(lambda n=name: timm_tpu.create_model(n))
        for path in flatten_pytree(nnx.state(model, nnx.Param)):
            assert sum(r.matches(path) for r in rules[:-1]) == 1, path
    named = {p: match_rule(p, rules)[1].name for p in ('blocks.1.attn.q_proj.kernel', 'blocks.1.attn.k_proj.kernel',
                                                       'blocks.1.attn.v_proj.kernel', 'blocks.1.attn.proj.kernel',
                                                       'blocks.1.mlp.w_down', 'blocks.1.mlp.router', 'blocks.0.norm1.scale')}
    assert list(named.values()) == ['attn-qkv', 'attn-qkv', 'attn-qkv', 'attn-out', 'expert-stack', 'router', 'norm-scale']
    mesh = create_mesh(devices=jax.devices()[:8], fsdp=4, tp=2)
    assert 'model' in tuple(spec_for_param('blocks.1.attn.k_proj.kernel', (2560, 512), mesh))       # 4 key/value heads over 2
    assert tuple(spec_for_param('blocks.1.mlp.w_gate', (8, 2560, 768), create_mesh(devices=jax.devices()[:8], fsdp=8))) == (None, None, 'fsdp')
