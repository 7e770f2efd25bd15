"""The block-diffusion task on the CPU at the toy size (`sdar_moe_common.py`): two training steps whose noise
differs and is the seed's, followed by the plain reference (`benchmarks/reference/bd_lm_train_step.py`) given the
noise the task drew; evaluation from a fixed key; and the token feed through `train.main`, which finds task and
feed by the model's kind."""
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
from benchmarks.reference import bd_lm_train_step  # noqa: E402
from benchmarks.reference import sdar_moe as ref  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.task import BlockDiffusionLMTask  # noqa: E402

from sdar_moe_common import L, SIZES, TOL  # noqa: E402


def _clean(seed=0, rows=2):
    return jnp.asarray(np.random.default_rng(seed).integers(0, 255, (rows, L)), jnp.int32)      # never the mask token


def _toy(params, **kwargs):
    model = timm_tpu.create_model('sdar_moe_toy', seed=0, **kwargs)
    program.load_weights(model, params)
    return model


def test_two_steps_draw_different_noise_the_same_seed_the_same_and_follow_the_reference():
    params = weights.make(11, ref.init_spec(SIZES))
    model = _toy(params)
    model.set_grad_checkpointing(True)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1, betas=(0.9, 0.95))
    task = BlockDiffusionLMTask(model, optimizer=opt, clip_grad=1.0, loss_chunk=16)
    steps, losses, nlls, first = [], [], [], None
    for i, seed in enumerate((1, 2)):
        clean = _clean(seed)
        noised, masked, p = jax.device_get(task.next_noise(clean))              # the noise the step is about to draw
        steps.append({'noised': noised, 'clean': np.asarray(clean), 'p': p, 'lr': 1e-3})
        metrics = task.train_step({'input': clean, 'target': clean}, lr=1e-3, step=i)
        losses.append(float(metrics['loss']))
        nlls.append(float(metrics['lm.masked_nll']) / int(metrics['lm.noised_masked']))
        first = first or program.first_grad_norms(task)
        assert int(metrics['moe.dropped_slots']) == 0 and int(metrics['lm.tokens']) == 2 * L
        assert int(metrics['lm.noised_masked']) == int(masked.sum()) and int(metrics['attn.bd_blocks']) == 124
    assert int(model.noise_count[...]) == 2 and not np.allclose(steps[0]['p'], steps[1]['p'])
    got = {'losses': losses, 'first_grad_norms': first,
           'param_change_norms': {k: float(jnp.linalg.norm(v - params[k]))
                                  for k, v in program.named_leaves(nnx.state(model, nnx.Param)).items()}}
    want = bd_lm_train_step.follow(ref, SIZES, lambda: weights.make(11, ref.init_spec(SIZES)), steps, clip=1.0,
                                   weight_decay=0.1, betas=(0.9, 0.95), block_q=8)
    numbers = check.training_numbers(got, want)
    assert all(v[0] < 1e-3 for v in numbers.values()), numbers      # Adam's division turns 1e-7 of gradient into 1e-4 of step
    assert want['routes'].shape == (2, 3, 2 * L, 2) and max(abs(a - b) for a, b in zip(nlls, want['masked_nll'])) < TOL
    # the same seed draws the same noise, step for step; another seed another; two steps never the same
    again, other = (BlockDiffusionLMTask(timm_tpu.create_model('sdar_moe_toy', seed=s)) for s in (0, 1))
    clean = _clean(1)
    first_again, first_other = again.next_noise(clean), other.next_noise(clean)
    assert (np.asarray(first_again[0]) == steps[0]['noised']).all() and np.allclose(first_again[2], steps[0]['p'])
    assert not np.allclose(first_other[2], steps[0]['p'])
    again.model.noise_count[...] += 1
    assert np.allclose(again.next_noise(_clean(2))[2], steps[1]['p'])
    # evaluation masks about half of the positions from one fixed key, whatever the stream has counted
    sums = [{k: float(v) for k, v in t.eval_step({'input': clean, 'target': clean}).items()} for t in (again, again, other)]
    assert sums[0] == sums[1] and sums[0]['count'] == sums[2]['count'] and abs(sums[0]['count'] - L) < 5 * math.sqrt(2 * L / 4)
    assert abs(sums[0]['loss_sum'] / sums[0]['count'] - math.log(256)) < 0.5 and 0 <= sums[0]['top1'] <= sums[0]['top5']


def test_the_model_trains_through_train_main_on_the_token_feed(tmp_path):
    import train
    from timm_tpu.utils import tracing
    rng = np.random.default_rng(0)
    rng.integers(0, 255, L * 24 + 7, dtype=np.int32).tofile(tmp_path / 'train.bin')
    rng.integers(0, 255, L * 8, dtype=np.int32).tofile(tmp_path / 'validation.bin')
    mark = tracing.now_ns()
    argv = ['--model', 'sdar_moe_toy', '--dataset', 'tokens', '--data-dir', str(tmp_path), '--seq-len', str(L),
            '-b', '8', '--epochs', '1', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1',
            '--clip-grad', '1.0', '--grad-checkpointing', '--output', str(tmp_path / 'out'), '--experiment', 't',
            '-j', '2', '--seed', '7']
    out = train.main(argv)
    assert abs(out['loss'] - math.log(256)) < 0.5 and 0.0 <= out['top1'] <= out['top5'] <= 100.0
    spans = [s for s in tracing.snapshot()['spans'] if s.start_ns >= mark]
    assert sum(s.name == 'task.train_step' for s in spans) == 3 and any(s.name == 'loader.batch_wait' for s in spans)
    # one table from a model's kind to its task and feed, and the error names the kinds it knows
    assert train.TASK_KINDS['block_diffusion_lm'] == ('BlockDiffusionLMTask', 'tokens') and train.TOKEN_KINDS == ('causal_lm', 'block_diffusion_lm')
    with pytest.raises(ValueError, match='causal_lm, block_diffusion_lm'):
        train.main(['--model', 'sdar_moe_toy', '--synthetic-data', '--epochs', '1', '--output', str(tmp_path / 'out2')])
