"""EvaByte's 8 prediction heads under `CausalLMTask`, at the toy size on the CPU:
the 8 targets a position, which targets move which head's loss and which
positions weigh nothing, two optimizer steps against the plain reference's
(`reference/lm_train_step.py` takes the family's loss without an edit), and the
model through `train.main` on the token feed.
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
from benchmarks.reference import lm_train_step  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.task import CausalLMTask  # noqa: E402
from timm_tpu.task.causal_lm import IGNORE, _ce_sums, head_targets  # noqa: E402

from evabyte_common import N, SIZES, batch as _batch  # noqa: E402


@pytest.fixture(scope='module')
def head_losses():
    """(the toy model with seeded weights, a jitted function batch -> the step's `lm.head_nll`, loss and `loss_main`)."""
    model = timm_tpu.create_model('evabyte_toy', seed=0)
    program.load_weights(model, weights.make(11, ref.init_spec(SIZES)))
    task = CausalLMTask(model, loss_chunk=32)

    @nnx.jit
    def run(m, ids, target):
        loss, output = task.loss_forward(m, {'input': ids, 'target': target})
        return output['counters']['lm.head_nll'], loss, output['loss_main']
    return model, run


def test_head_p_is_given_the_id_p_plus_one_positions_on_and_nothing_past_the_window():
    ids, target = _batch(1)
    targets = np.asarray(head_targets(target, 8))
    assert targets.shape == (2, N, 8) and np.array_equal(targets[..., 0], np.asarray(target))
    for p in range(8):
        assert np.array_equal(targets[:, :N - 1 - p, p], np.asarray(ids)[:, 1 + p:])      # the input p + 1 on
        # the positions weighted 0 are exactly the last p of a window, beside the last one, which has no next id at all
        assert (targets[:, N - 1 - p:, p] == IGNORE).all() and (targets[:, :N - 1 - p, p] != IGNORE).all()
    assert np.array_equal(targets[0], np.asarray(ref.head_targets(SIZES, target[0])))


@pytest.mark.parametrize('p', range(8))
def test_head_ps_loss_moves_only_with_the_targets_p_plus_one_on(head_losses, p):
    """Head q reads `target[j]` at position j - q, which exists only for q <= j: another id in `target[p]` (the
    inputs left as they are) moves the losses of heads 0 .. p and of no head after p, so head p's loss is the last
    to see the id p + 1 positions on from position 0."""
    model, run = head_losses
    ids, target = _batch(2)
    base, loss, main = run(model, ids, target)
    assert base.shape == (8,) and abs(float(loss) - float(base.mean())) < 1e-6 and float(main) == float(base[0])
    moved = np.asarray(run(model, ids, target.at[:, p].set((target[:, p] + 1) % 320))[0])
    changed = np.abs(moved - np.asarray(base)) > 1e-7
    assert changed[:p + 1].all() and not changed[p + 1:].any()
    # and the last valid target, j = N - 2, is read by every head (head q at N - 2 - q); j = N - 1 is IGNORE
    last = np.asarray(run(model, ids, target.at[:, N - 2].set((target[:, N - 2] + 1) % 320))[0])
    assert (np.abs(last - np.asarray(base)) > 1e-7).all()


def test_the_positions_that_weigh_nothing_are_the_last_of_the_window(head_losses):
    """The final hidden state of a position moves head p's loss iff that position has a target for head p: read
    from the gradient of each head's loss with respect to the hidden states."""
    model, _ = head_losses
    ids, target = _batch(3, rows=1)
    h = model.forward_features(ids)
    targets = head_targets(target, 8)
    graphdef, state = nnx.split(model)

    def head_loss(h, p):
        sums = _ce_sums(nnx.merge(graphdef, state).forward_head(h), targets)
        return (sums['loss_sum'] / jnp.maximum((targets != IGNORE).sum((0, 1)), 1))[p]

    for p in (0, 3, 7):
        g = np.asarray(jax.grad(head_loss)(h, p))[0]
        weighs = np.abs(g).max(-1) > 0
        assert weighs[:N - 1 - p].all() and not weighs[N - 1 - p:].any()


def test_causal_lm_task_two_steps_follow_the_reference():
    params = weights.make(11, ref.init_spec(SIZES))
    model = timm_tpu.create_model('evabyte_toy', seed=0)
    model.set_grad_checkpointing(True)
    program.load_weights(model, params)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1, betas=(0.9, 0.95))
    task = CausalLMTask(model, optimizer=opt, clip_grad=1.0, loss_chunk=32)
    steps = [dict(zip(('input', 'target'), _batch(seed)), lr=1e-3) for seed in (1, 2)]
    losses, first = [], None
    for i, step in enumerate(steps):
        metrics = task.train_step({'input': step['input'], 'target': step['target']}, lr=step['lr'], step=i)
        losses.append(float(metrics['loss']))
        first = first or program.first_grad_norms(task)
        assert int(metrics['lm.tokens']) == 2 * N and int(metrics['attn.eva_blocks']) == 80
        assert metrics['lm.head_nll'].shape == (8,) and abs(float(metrics['lm.head_nll'].mean()) - losses[-1]) < 1e-5
    got = {'losses': losses, 'first_grad_norms': first,
           'param_change_norms': {k: float(jnp.linalg.norm(v - params[k]))
                                  for k, v in program.named_leaves(nnx.state(model, nnx.Param)).items()}}
    want = lm_train_step.follow(ref, SIZES, lambda: weights.make(11, ref.init_spec(SIZES)), steps, clip=1.0,
                                weight_decay=0.1, betas=(0.9, 0.95))
    numbers = check.training_numbers(got, want)
    assert all(v[0] < 1e-3 for v in numbers.values()), numbers      # Adam's division turns 1e-7 of gradient into 1e-4 of step
    assert want['routes'].size == 0                                  # no router: nothing chosen
    # the two learned vectors are not decayed, on either side: with a zero gradient they would stand still
    mask = {k: ref.no_weight_decay(k) for k in params}
    assert sum(mask.values()) == 4 and model.no_weight_decay() == {k for k, v in mask.items() if v}


def test_the_model_trains_through_train_main_on_the_token_feed_and_the_loss_falls_on_a_repeated_batch(tmp_path, monkeypatch):
    """Three steps of 8 windows each through `train.main`; the stream repeats with the window's period, so every
    window of every batch holds the same ids and the three losses are of one batch."""
    import train
    from timm_tpu.utils import tracing
    window = np.random.default_rng(0).integers(0, 320, N, dtype=np.int32)
    np.tile(window, 24).tofile(tmp_path / 'train.bin')
    np.tile(window, 8).tofile(tmp_path / 'validation.bin')
    seen, inner = [], CausalLMTask.train_step

    def step(task, batch, lr, step=0):
        metrics = inner(task, batch, lr, step)
        seen.append((np.asarray(batch['input']), float(metrics['loss']), np.asarray(metrics['lm.head_nll'])))
        return metrics

    monkeypatch.setattr(CausalLMTask, 'train_step', step)
    mark = tracing.now_ns()
    out = train.main(['--model', 'evabyte_toy', '--dataset', 'tokens', '--data-dir', str(tmp_path), '--seq-len', str(N),
                      '-b', '8', '--epochs', '1', '--opt', 'adamw', '--opt-betas', '0.9', '0.95', '--weight-decay', '0.1',
                      '--clip-grad', '1.0', '--grad-checkpointing', '--lr', '1e-3', '--warmup-epochs', '0', '--sched', 'none',
                      '--output', str(tmp_path / 'out'), '--experiment', 't', '-j', '2', '--seed', '7'])
    losses = [loss for _, loss, _ in seen]
    assert len(seen) == 3 and all(math.isfinite(x) for x in losses) and losses[0] > losses[1] > losses[2]
    assert all((ids == ids[0]).all() for ids, _, _ in seen) and abs(losses[0] - math.log(320)) < 0.5
    assert all(nll.shape == (8,) and np.isfinite(nll).all() for _, _, nll in seen)
    assert math.isfinite(out['loss']) and 0.0 <= out['top1'] <= out['top5'] <= 100.0      # head 0 scored on the validation ids
    spans = [s for s in tracing.snapshot()['spans'] if s.start_ns >= mark]
    assert sum(s.name == 'task.train_step' for s in spans) == 3
