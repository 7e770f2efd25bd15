"""EvaByte at a toy size on the CPU, against the plain reference
(`benchmarks/reference/evabyte.py`): the model's logits, loss, head 0's loss and
every gradient leaf (the two learned vectors a head among them), causality
through the query's own chunk and window, the two head shares adding up to the
uncut attention block, the entry points' parameter counts, the partition rules.
Seeded random weights, float32 on both sides: they differ by summation order, so
1e-4 is a decade from a real difference. Nothing here has to do with
`models/eva.py`, the image model.

Toy: hidden 64, 4 heads of width 16, windows of 32 and chunks of 4 at N = 128,
a SwiGLU of 160, 2 layers, the 320 ids, 8 prediction heads.
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
from benchmarks.reference import evabyte as ref  # noqa: E402
from timm_tpu.task import CausalLMTask  # noqa: E402

from evabyte_common import N, SIZES, TOL, W, batch as _batch  # noqa: E402
from remat_common import block_grad_dots  # noqa: E402
from timm_tpu.layers.latent_attention import CORE_OUT  # noqa: E402
from timm_tpu.layers.mlp import FFN_UP  # noqa: E402


@pytest.fixture(scope='module')
def toy():
    """The toy model with the benchmark's seeded weights, and the same weights for the reference."""
    params = weights.make(11, ref.init_spec(SIZES))
    model = timm_tpu.create_model('evabyte_toy', seed=0)
    program.load_weights(model, params)
    return model, params


def test_the_entry_points_hold_what_the_configuration_says():
    count = lambda m: sum(math.prod(v.shape) for v in jax.tree.leaves(nnx.state(m, nnx.Param)))  # noqa: E731
    share = nnx.eval_shape(lambda: timm_tpu.create_model('evabyte_6b5_hp2'))
    leaves = program.named_leaves(nnx.state(share, nnx.Param))
    assert count(share) == 687_132_672 == 4 * 168_833_024 + 1_310_720 + 10_485_760 + 4_096       # ISSUE 41's table
    assert {k: v.shape for k, v in leaves.items() if k.startswith('blocks.1.')} == {
        'blocks.1.norm1.scale': (4096,), 'blocks.1.norm2.scale': (4096,), 'blocks.1.attn.q_proj.kernel': (4096, 2048),
        'blocks.1.attn.k_proj.kernel': (4096, 2048), 'blocks.1.attn.v_proj.kernel': (4096, 2048),
        'blocks.1.attn.proj.kernel': (2048, 4096), 'blocks.1.attn.phi': (16, 128), 'blocks.1.attn.mu': (16, 128),
        'blocks.1.mlp.fc1_g.kernel': (4096, 11008), 'blocks.1.mlp.fc1_x.kernel': (4096, 11008),
        'blocks.1.mlp.fc2.kernel': (11008, 4096)}
    assert leaves['embed.embedding'].shape == (320, 4096) and leaves['head.kernel'].shape == (4096, 8 * 320)
    assert share.task_kind == 'causal_lm' and share.mtp is None and not hasattr(share, 'routes')
    assert (share.heads_held, share.head_offset, share.num_pred_heads, share.vocab_held) == (16, 0, 8, 320)
    attn = share.blocks[0].attn
    assert (attn.num_heads, attn.heads_held, attn.window, attn.chunk, attn.head_dim) == (32, 16, 2048, 16, 128)
    assert share.no_weight_decay() == {f'blocks.{i}.attn.{v}' for i in range(4) for v in ('phi', 'mu')}
    full = nnx.eval_shape(lambda: timm_tpu.create_model('evabyte_6b5'))
    assert len(full.blocks) == 32 and full.blocks[0].attn.heads_held == 32
    assert count(full) == 32 * 202_391_552 + 1_310_720 + 10_485_760 + 4_096 == 6_488_330_240    # the published 6.5B
    toy = nnx.eval_shape(lambda: timm_tpu.create_model('evabyte_toy'))
    assert set(program.named_leaves(nnx.state(toy, nnx.Param))) == set(ref.init_spec(SIZES))
    with pytest.raises(ValueError, match='not among'):
        timm_tpu.create_model('evabyte_toy', heads_held=3, head_offset=2)


def test_model_matches_the_reference_logits_loss_head_losses_and_every_gradient_leaf(toy):
    model, params = toy
    ids, target = _batch()
    task = CausalLMTask(model, loss_chunk=32)
    ref_forward = jax.jit(lambda p, i, t: ref.forward(SIZES, p, i, t))
    out = [ref_forward(params, ids[b], target[b]) for b in range(2)]
    logits = nnx.jit(lambda m: m(ids))(model)
    assert logits.shape == (2, N, 8 * 320) and logits.dtype == jnp.float32
    assert float(jnp.abs(logits - jnp.stack([o['logits'] for o in out])).max()) < TOL
    model.set_grad_checkpointing(True)                      # as the cell trains
    graphdef, state, rest = nnx.split(model, nnx.Param, ...)
    loss_fn = lambda st: task.loss_forward(nnx.merge(graphdef, st, rest, copy=True), {'input': ids, 'target': target})  # noqa: E731
    (loss, output), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(state)
    model.set_grad_checkpointing(False)
    n_main = 2 * (N - 1)
    ref_fn = lambda p: sum(ref.loss(SIZES, p, ids[b], target[b], n_main)[0] for b in range(2))  # noqa: E731
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref_fn))(params)
    assert abs(float(loss) - float(ref_loss)) < TOL and abs(float(ref_loss) - math.log(320)) < 0.5
    # each head's mean over its own valid positions; head 0's is the next-byte loss the step returns beside the loss
    heads = sum(o['loss_heads_sum'] for o in out) / sum(o['n_heads'] for o in out)
    assert [int(n) for n in out[0]['n_heads']] == [N - 1 - p for p in range(8)]
    assert float(jnp.abs(output['counters']['lm.head_nll'] - heads).max()) < TOL
    assert abs(float(output['loss_main']) - float(heads[0])) < TOL and abs(float(loss) - float(heads.mean())) < TOL
    got = program.named_leaves(grads)
    assert set(got) == set(ref_grads) and {'blocks.0.attn.phi', 'blocks.1.attn.mu'} <= set(got)
    gap, leaf = check.worst_leaf_gap({k: float(jnp.linalg.norm(v)) for k, v in got.items()},
                                     {k: float(jnp.linalg.norm(v)) for k, v in ref_grads.items()})
    assert gap < TOL, (gap, leaf)
    assert max(float(jnp.abs(got[k] - ref_grads[k]).max()) for k in got) < TOL
    assert all(float(jnp.linalg.norm(ref_grads[f'blocks.{i}.attn.{v}'])) > 1e-6 for i in range(2) for v in ('phi', 'mu'))
    # the step's counters: tiles by the XLA path's slices in blocks of 16 (a window is 2 query blocks of 1 and 2
    # key blocks, window w has 8 w summaries before it: ceil(8 w / 16) blocks for each of its 2 query blocks),
    # the needed pairs from the shapes, all 4 heads, 2 layers and 2 sequences
    counters = output['counters']
    assert int(counters['attn.eva_blocks']) == 2 * 2 * (4 * 3 + 2 * (0 + 1 + 1 + 2)) and int(counters['lm.tokens']) == 2 * N
    assert float(counters['attn.eva_pairs']) == 2 * 2 * 4 * (4 * 32 * 33 // 2 + 32 * 8 * (0 + 1 + 2 + 3))
    assert not [k for k in counters if k.startswith('moe.')]


@pytest.mark.parametrize('names', [(CORE_OUT,), (CORE_OUT, FFN_UP)], ids=['core_out', 'core_out+ffn_up'])
def test_what_a_block_keeps_moves_no_loss_or_gradient_bit_and_the_two_up_products_are_not_formed_again(toy, names, monkeypatch):
    """The toy's loss and every gradient leaf with each block rematerialised under `save_only_these_names(*names)`
    against the model's own `_run_block`: EXACTLY equal, under the policy PR 49 left (the core's output alone) and the
    one it set (the feed-forward block's two up-products beside it): the kept values are the bits the second forward
    pass would form again, and on the CPU the compiler rounds them no differently. And the products of one block's
    gradient: 95 `dot_general` equations with the core's output alone kept, two fewer with `FFN_UP` listed, which is
    what the model's own block holds (`fc2`'s second forward is dead code before the jaxpr is written)."""
    model, _ = toy
    ids, target = _batch()
    task = CausalLMTask(model, loss_chunk=32)
    monkeypatch.setattr(model, 'grad_checkpointing', True)      # as the cell trains
    graphdef, state, rest = nnx.split(model, nnx.Param, ...)
    loss_fn = lambda st: task.loss_forward(nnx.merge(graphdef, st, rest, copy=True), {'input': ids, 'target': target})[0]  # noqa: E731
    own_loss, own_grads = jax.jit(jax.value_and_grad(loss_fn))(state)
    assert block_grad_dots(model, 0, N, names) == 95 - 2 * (FFN_UP in names) and block_grad_dots(model, 0, N) == 93
    policy = jax.checkpoint_policies.save_only_these_names(*names)
    monkeypatch.setattr(type(model), '_run_block',
                        lambda self, blk, x, rope: nnx.remat(lambda b, x, rope: b(x, rope), policy=policy)(blk, x, rope))
    loss, grads = jax.jit(jax.value_and_grad(loss_fn))(state)
    assert float(loss) == float(own_loss)
    got, want = program.named_leaves(grads), program.named_leaves(own_grads)
    assert set(got) == set(want) and all(bool((got[k] == want[k]).all()) for k in want)


def test_the_kept_step_program_holds_nine_products_a_feed_forward_block_and_the_gauge_and_the_log_line_say_so():
    """The step as the cell trains it (blocks rematerialised under `_run_block`'s policy), compiled ahead of time and
    kept: under `evabyte.ffn` three products forward and six backward a layer, none under `rematted_computation` (the
    block's second forward pass finds the two up-products kept; eleven a layer before PR 49). `tracing.scope_products`
    is what the gauge `ffn.products` reads where a step program is kept, and `train.py`'s log line prints it."""
    import train
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.utils import tracing
    ids = jnp.zeros((8, N), jnp.int32)
    model = timm_tpu.create_model('evabyte_toy', seed=0)
    model.set_grad_checkpointing(True)
    task = CausalLMTask(model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1), clip_grad=1.0, loss_chunk=32)
    before = len(tracing.snapshot()['gauges'].get('ffn.products', ()))
    text = task.lower_train_step({'input': ids, 'target': ids}, 1e-3, 0).as_text()
    series = tracing.snapshot()['gauges']['ffn.products']
    assert len(series) == before + 1 and series[-1][1] == tracing.scope_products(text, 'evabyte.ffn') == 9 * len(model.blocks) == 18
    assert tracing.scope_products(text, 'rematted_computation/evabyte.ffn') == 0 < tracing.scope_products(text, 'rematted_computation/evabyte.attn.proj')
    assert tracing.scope_products(text, 'glm.dense_ffn') == 0 and ' ffn products 18' in train._host_line(tracing.now_ns(), {})[0]


def test_no_position_sees_a_later_id_through_its_own_chunk_window_or_a_summary(toy):
    """Position i's output is unchanged when any id AFTER i changes: ids of i's own chunk and of i's own window
    among them (a summary of the query's own window must never reach it, and a summary of an earlier window
    holds only positions before i); and it does change when an id of an earlier window changes, which reaches it
    only through that window's summary."""
    model, _ = toy
    ids, _ = _batch(5, rows=1)
    forward = nnx.jit(lambda m, i: m(i))
    base = np.asarray(forward(model, ids))
    for i in (37, 38, 63, 64, 100):                      # mid-chunk, a chunk's last, a window's last, a window's first
        for later in sorted({i + 1, i // 4 * 4 + 3, i // W * W + W - 1, N - 1} - set(range(i + 1))):
            changed = ids.at[0, later].set((ids[0, later] + 1) % 320)
            out = np.asarray(forward(model, changed))
            assert np.array_equal(out[0, :later], base[0, :later]), (i, later)
            assert not np.array_equal(out[0, later], base[0, later])
    # an id of window 0 reaches a position of window 2 (2 layers would also pass it on through window 1: take the
    # first layer's mixer alone, where the only road is window 0's summaries)
    attn, rope = model.blocks[0].attn, model._rope(N)
    mix = nnx.jit(lambda a, x: a(x, rope)[0])
    x = model.embed(ids)
    far = np.asarray(mix(attn, x))
    moved = np.asarray(mix(attn, x.at[0, 5].add(1.0)))
    assert not np.allclose(moved[0, 2 * W + 3], far[0, 2 * W + 3], atol=1e-7)       # window 2 sees chunk 1 of window 0
    assert np.array_equal(moved[0, :5], far[0, :5])


def test_the_two_head_shares_add_up_to_the_uncut_attention_block(toy):
    """Two shares of 2 heads each, built by the entry point and handed their slices of the whole 4-head weights
    (`take_heads`): the parts of the attention block's output product add up to what the uncut reference gives for
    the block; the feed-forward block is whole on every chip and is counted once (the reference's, on the sum)."""
    _, params = toy
    ids, _ = _batch(7, rows=1)
    b, eps = 'blocks.0.', SIZES['rms_norm_eps']
    h = params['embed.embedding'][ids[0]]
    a = ref.rms_norm(h, params[b + 'norm1.scale'], eps)
    whole = jax.jit(lambda p, a: ref.attention(SIZES, p, b, a, 'float32'))(params, a)
    parts = []
    for offset in (0, 2):
        share = timm_tpu.create_model('evabyte_toy', seed=0, heads_held=2, head_offset=offset)
        attn = share.blocks[0].attn
        assert (attn.heads_held, attn.head_offset, attn.q_proj.kernel.shape, attn.phi.shape) == (2, offset, (64, 32), (2, 16))
        for name in ('q_proj.kernel', 'k_proj.kernel', 'v_proj.kernel', 'proj.kernel', 'phi', 'mu'):
            leaf = attn
            for key in name.split('.'):
                leaf = getattr(leaf, key)
            leaf[...] = attn.take_heads(name, params[b + 'attn.' + name])
        part, _ = nnx.jit(lambda m, x: m(x, share._rope(N)))(attn, a[None])
        parts.append(part[0])
        # and the reference given the same share computes the same part
        sizes = dict(SIZES, heads_held=2, head_offset=offset)
        held = {b + 'attn.' + name: attn.take_heads(name, params[b + 'attn.' + name])
                for name in ('q_proj.kernel', 'k_proj.kernel', 'v_proj.kernel', 'proj.kernel', 'phi', 'mu')}
        assert float(jnp.abs(ref.attention(sizes, held, b, a, 'float32') - part[0]).max()) < TOL
    assert float(jnp.abs(parts[0] + parts[1] - whole).max()) < TOL
    assert float(jnp.abs(parts[0] - whole).max()) > 100 * TOL              # one share alone is not the block
    after_attn = h + parts[0] + parts[1]
    want = jax.jit(lambda p, x: ref.layer(SIZES, p, 0, x, 'float32'))(params, h)
    got = after_attn + ref.feed_forward(params, b, ref.rms_norm(after_attn, params[b + 'norm2.scale'], eps), 'float32')
    assert float(jnp.abs(got - want).max()) < TOL


def test_every_parameter_of_the_family_has_one_partition_rule_and_heads_split_over_the_model_axis():
    from timm_tpu.parallel import create_mesh, default_partition_rules, match_rule
    from timm_tpu.parallel.sharding import spec_for_param
    from timm_tpu.utils.serialization import flatten_pytree
    rules = default_partition_rules()
    for name in ('evabyte_toy', 'evabyte_6b5_hp2'):
        model = nnx.eval_shape(lambda n=name: timm_tpu.create_model(n))
        for path in flatten_pytree(nnx.state(model, nnx.Param)):
            assert sum(r.matches(path) for r in rules[:-1]) == 1, path
    named = {p: match_rule(p, rules)[1].name for p in (
        'blocks.1.attn.q_proj.kernel', 'blocks.1.attn.proj.kernel', 'blocks.1.attn.phi', 'blocks.1.attn.mu',
        'blocks.1.mlp.fc1_g.kernel', 'blocks.1.mlp.fc2.kernel', 'blocks.0.norm1.scale', 'head.kernel')}
    assert list(named.values()) == ['attn-qkv', 'attn-out', 'head-vector', 'head-vector', 'mlp-fc1', 'mlp-fc2',
                                    'norm-scale', 'kernel']
    mesh = create_mesh(devices=jax.devices()[:8], fsdp=4, tp=2)
    assert 'model' in tuple(spec_for_param('blocks.1.attn.k_proj.kernel', (4096, 2048), mesh))
    assert 'model' in tuple(spec_for_param('blocks.1.mlp.fc1_x.kernel', (4096, 11008), mesh))
