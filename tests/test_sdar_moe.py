"""SDAR-30B-A3B-Chat at a toy size on the CPU, against the plain reference
(`benchmarks/reference/sdar_moe.py`): the model over a noised copy beside the
clean sequence, the block-diffusion task with its noise, the token feed through
`train.main`. Seeded random weights, float32 on both sides: they differ by
summation order, so 1e-4 is a decade from a real difference. The layers are
`test_sdar_moe_layers.py`'s.

Toy: `sdar_moe_common.py`.
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
from benchmarks.reference import sdar_moe as ref  # noqa: E402
from timm_tpu.task import BlockDiffusionLMTask  # noqa: E402
from timm_tpu.task.block_diffusion_lm import draw_noise, noise_key  # noqa: E402

from sdar_moe_common import L, SIZES, TOL  # noqa: E402


def _clean(seed=0, rows=2):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 255, (rows, L)), jnp.int32)      # never the mask token


def _toy(params, **kwargs):
    model = timm_tpu.create_model('sdar_moe_toy', seed=0, **kwargs)
    program.load_weights(model, params)
    return model


@pytest.fixture(scope='module')
def toy():
    """The toy model with the benchmark's seeded weights, and the same weights for the reference."""
    params = weights.make(11, ref.init_spec(SIZES))
    return _toy(params), params


def test_the_entry_points_hold_what_the_configuration_says():
    share = nnx.eval_shape(lambda: timm_tpu.create_model('sdar_30b_a3b_ep8'))
    leaves = program.named_leaves(nnx.state(share, nnx.Param))
    assert sum(math.prod(v.shape) for v in leaves.values()) == 645_623_296             # ISSUE 37's arithmetic
    assert {k: v.shape for k, v in leaves.items() if k.startswith('blocks.5.')} == {
        'blocks.5.norm1.scale': (2048,), 'blocks.5.norm2.scale': (2048,), 'blocks.5.attn.q_proj.kernel': (2048, 4096),
        'blocks.5.attn.k_proj.kernel': (2048, 512), 'blocks.5.attn.v_proj.kernel': (2048, 512),
        'blocks.5.attn.proj.kernel': (4096, 2048), 'blocks.5.attn.q_norm.scale': (128,), 'blocks.5.attn.k_norm.scale': (128,),
        'blocks.5.mlp.router': (2048, 128), 'blocks.5.mlp.w_gate': (16, 2048, 768), 'blocks.5.mlp.w_up': (16, 2048, 768),
        'blocks.5.mlp.w_down': (16, 768, 2048)}
    assert leaves['embed.embedding'].shape == (18992, 2048) and leaves['head.kernel'].shape == (2048, 18992)
    assert share.task_kind == 'block_diffusion_lm' and share.block_length == 4 and share.mask_token_id == 18991
    assert set(share.group_matcher()) == {'stem', 'blocks'} and share.no_weight_decay() == set() and len(share.blocks) == 6
    assert all(b.attn.block_diffusion == 4 and b.attn.rotary and b.attn.window is None and b.attn.q_norm is not None
               and b.mlp.scoring == 'softmax_topk' and b.mlp.activation == 'silu' and b.mlp.shared is None
               and b.mlp.score_bias is None and b.mlp.top_k == 8 and b.mlp.experts_held == 16 for b in share.blocks)
    full = nnx.eval_shape(lambda: timm_tpu.create_model('sdar_30b_a3b'))
    assert len(full.blocks) == 48 and full.vocab_held == 151936 and full.blocks[1].mlp.experts_held == 128
    assert full.mask_token_id == 151669 and full.get_classifier() is full.head
    assert set(leaves) == set(ref.init_spec(dict(SIZES, num_hidden_layers=6)))
    with pytest.raises(ValueError, match='mask token'):
        timm_tpu.create_model('sdar_moe_toy', mask_token_id=256)


def test_model_matches_the_reference_logits_loss_routes_and_every_gradient_leaf(toy):
    model, params = toy
    clean = _clean()
    task = BlockDiffusionLMTask(model, loss_chunk=16)
    noised, masked, p = draw_noise(noise_key(model), clean, 255, 1e-3)          # what the task's next draw will be
    assert bool(((noised == 255) == masked).all()) and bool((jnp.where(masked, clean, noised) == clean).all())
    ref_forward = jax.jit(lambda w, n, c, pr: ref.forward(SIZES, w, n, c, pr, block_q=8))
    out = [ref_forward(params, noised[b], clean[b], p[b]) for b in range(2)]
    logits, routes = nnx.jit(lambda m: (m(noised, clean), m.routes(noised, clean)))(model)
    assert logits.shape == (2, L, 256) and routes.shape == (3, 2, 2 * L, 2)
    assert float(jnp.abs(logits - jnp.stack([o['logits'] for o in out])).max()) < TOL
    assert bool((routes.transpose(1, 0, 2, 3) == jnp.stack([o['routes'] for o in out])).all())
    model.set_grad_checkpointing(True)                      # as the cell trains
    graphdef, state, rest = nnx.split(model, nnx.Param, ...)
    loss_fn = lambda st: task.loss_forward(nnx.merge(graphdef, st, rest, copy=True), {'input': clean, 'target': clean})  # noqa: E731
    (loss, output), grads = jax.jit(jax.value_and_grad(loss_fn, has_aux=True))(state)
    model.set_grad_checkpointing(False)
    ref_fn = lambda w: sum(ref.loss(SIZES, w, noised[b], clean[b], p[b], clean.size, block_q=8)[0] for b in range(2))  # noqa: E731
    ref_loss, ref_grads = jax.jit(jax.value_and_grad(ref_fn))(params)
    assert abs(float(loss) - float(ref_loss)) < TOL * max(1.0, float(ref_loss))
    got = program.named_leaves(grads)
    assert set(got) == set(ref_grads)
    gap, leaf = check.worst_leaf_gap({k: float(jnp.linalg.norm(v)) for k, v in got.items()},
                                     {k: float(jnp.linalg.norm(v)) for k, v in ref_grads.items()})
    assert gap < TOL, (gap, leaf)
    assert max(float(jnp.abs(got[k] - ref_grads[k]).max()) for k in got) < TOL
    # the step's counters: tiles by the XLA path's slices in 8-wide blocks (a whole layer: 4 + 10 for the noised
    # queries, 10 for the clean ones; the last layer its noised queries only), the masked positions, their plain
    # cross-entropy (an untrained head owes ln V a position), the expert layers'
    counters = {k: float(v) for k, v in output['counters'].items()}
    assert counters['attn.bd_blocks'] == 2 * (24 + 24 + 14) and counters['lm.tokens'] == 2 * L
    assert counters['lm.noised_masked'] == int(masked.sum()) == sum(int(o['masked']) for o in out)
    assert abs(counters['lm.masked_nll'] - sum(float(o['nll_masked_sum']) for o in out)) < 1e-3
    assert abs(counters['lm.masked_nll'] / counters['lm.noised_masked'] - math.log(256)) < 0.5
    assert counters['moe.dropped_slots'] == 0 and 0 < counters['moe.load_max'] <= counters['moe.local_slots'] <= (2 + 2 + 1) * 2 * L * 2


def test_a_clean_row_reads_nothing_noised_and_a_noised_row_no_clean_token_of_its_own_or_a_later_block(toy):
    model, _ = toy
    clean = _clean(3, rows=1)
    noised = jnp.where(jnp.arange(L) % 3 == 0, 255, clean)
    run = nnx.jit(lambda m, n, c: (m.forward_features(n, c), m.routes(n, c)))
    h, routes = run(model, noised, clean)
    # every noised id changed: the clean half's routing (which reads its rows in every layer) stays where it was
    h2, routes2 = run(model, (noised + 7) % 255, clean)
    assert (np.asarray(routes2)[:, :, L:] == np.asarray(routes)[:, :, L:]).all() and float(jnp.abs(h2 - h).max()) > 1e-3
    # a clean id of block 5 changed (positions 20-23): the noised rows of blocks 0-5 read no clean token of their own
    # or a later block and stay; those of block 6 on move
    h3, _ = run(model, noised, clean.at[0, 21].set((clean[0, 21] + 1) % 255))
    assert float(jnp.abs(h3[:, :24] - h[:, :24]).max()) < 1e-6 and float(jnp.abs(h3[:, 24:] - h[:, 24:]).max()) > 1e-4
    # a noised id of block 2 changed: only that block's noised rows move
    h4, _ = run(model, noised.at[0, 9].set(17), clean)
    moved = np.abs(np.asarray(h4 - h)).max(-1)[0] > 1e-6
    assert moved[8:12].all() and not moved[:8].any() and not moved[12:].any()
    # the model's own first L rows are the noised rows of an uncut last layer: its clean queries change nothing
    def uncut(m, n, c):
        x, rope = m._inputs(n, c)
        for blk in m.blocks:
            x, _ = blk(x, rope)
        return x
    assert float(jnp.abs(nnx.jit(uncut)(model, noised, clean)[:, :L] - h).max()) < 1e-5


def test_every_parameter_of_the_family_has_one_partition_rule_and_the_head_norms_are_norm_scales():
    """What the zoo's partition sweep holds every family to, here for the toy and the share: no leaf falls to
    the catch-all; `q_norm` / `k_norm` are norm scales (replicated: one scale of `head_dim`, every head alike)."""
    from timm_tpu.parallel import default_partition_rules, match_rule
    from timm_tpu.utils.serialization import flatten_pytree
    rules = default_partition_rules()
    for name in ('sdar_moe_toy', 'sdar_30b_a3b_ep8'):
        model = nnx.eval_shape(lambda n=name: timm_tpu.create_model(n))
        for path in flatten_pytree(nnx.state(model, nnx.Param)):
            assert sum(r.matches(path) for r in rules[:-1]) == 1, path
    named = {p: match_rule(p, rules)[1].name for p in ('blocks.1.attn.q_proj.kernel', 'blocks.1.attn.proj.kernel',
                                                       'blocks.1.attn.q_norm.scale', 'blocks.1.attn.k_norm.scale',
                                                       'blocks.1.mlp.w_down', 'blocks.1.mlp.router')}
    assert list(named.values()) == ['attn-qkv', 'attn-out', 'norm-scale', 'norm-scale', 'expert-stack', 'router']
