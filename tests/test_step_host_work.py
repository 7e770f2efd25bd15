"""The host's work around a step: the sentinel's counters are outputs of the
step program (no eager slice after the call), the host reads them one step
behind the dispatch (call N reads step N-1's, `drain()` the last), a bad step
commits nothing, and `train.main` freezes the collector's generations for the
run and thaws them."""
import logging

import jax
import numpy as np
import pytest
from flax import nnx

pytestmark = pytest.mark.resilience


def _task(mesh, opt='adamw', **kwargs):
    import timm_tpu
    from timm_tpu.loss import LabelSmoothingCrossEntropy
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import ClassificationTask
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    return ClassificationTask(model, optimizer=create_optimizer_v2(model, opt=opt, lr=1e-3), mesh=mesh,
                              train_loss_fn=LabelSmoothingCrossEntropy(0.1), **kwargs)


@pytest.fixture(scope='module')
def task(mesh8):
    task = _task(mesh8, nonfinite_tolerance=3)
    task.setup_ema(decay=0.9)
    return task


def _batch(mesh, nan=False, seed=0):
    import jax.numpy as jnp
    from timm_tpu.parallel import shard_batch
    rng = np.random.RandomState(seed)
    x = rng.rand(8, 32, 32, 3).astype(np.float32) * (np.nan if nan else 1.0)
    return shard_batch({'input': jnp.asarray(x), 'target': jnp.asarray(rng.randint(0, 10, 8))}, mesh)


def test_the_counters_in_metrics_are_outputs_of_the_program(mesh8, task, monkeypatch):
    """No eager op between the jitted call and the return: what `metrics` holds
    ARE the arrays the program returned, all of them and no other; the state the
    task keeps is the program's own array, and what the sentinel is handed are the
    PREVIOUS call's two counters out of its metrics (the state array itself is
    donated to the next call): the first call polls nothing."""
    task.train_step(_batch(mesh8), lr=1e-3, step=0)      # builds and compiles the step
    task.reset_nonfinite()
    real, outs, polled = task._train_step, [], []

    def spy(*args):
        out = real(*args)
        outs.append((out, dict(out[5])))                # the metrics as the program returned them
        return out

    monkeypatch.setattr(task, '_train_step', spy)
    observe = task.sentinel.observe
    monkeypatch.setattr(task.sentinel, 'observe', lambda state, step=0: (polled.append((state, step)), observe(state, step=step))[1])
    metrics = task.train_step(_batch(mesh8, nan=True), lr=1e-3, step=1)
    (out, returned), = outs
    assert set(metrics) == set(returned) == {'loss', 'grad_norm', 'nonfinite', 'nonfinite_count', 'nonfinite_total'}
    assert all(metrics[k] is returned[k] and isinstance(metrics[k], jax.Array) for k in metrics)
    assert int(metrics['nonfinite_count']) == 1 and int(metrics['nonfinite_total']) == 1 and bool(metrics['nonfinite'])
    assert task._sentinel_state is out[4] and polled == []
    later = task.train_step(_batch(mesh8), lr=1e-3, step=2)
    assert task._sentinel_state is outs[1][0][4]
    (state, step), = polled
    assert step == 1 and len(state) == 2
    assert state[0] is metrics['nonfinite_count'] and state[1] is metrics['nonfinite_total']
    assert state[0] is not later['nonfinite_count'] and int(later['nonfinite_count']) == 0
    task.reset_nonfinite()


def _counters():
    from timm_tpu.utils import tracing
    c = tracing.snapshot()['counters']
    return c.get('task.sentinel_polls', 0), c.get('task.polls_host_ahead', 0)


def test_call_n_observes_step_n_minus_1_and_drain_the_last_once(mesh8, task, monkeypatch):
    task.reset_nonfinite()
    polled, observe = [], task.sentinel.observe
    monkeypatch.setattr(task.sentinel, 'observe', lambda state, step=0: (
        polled.append((step, [int(x) for x in state])), observe(state, step=step))[1])
    polls, ahead = _counters()
    for step, nan in enumerate([False, True, False, True]):
        task.train_step(_batch(mesh8, nan=nan), lr=1e-3, step=10 + step)
        assert [s for s, _ in polled] == list(range(10, 10 + step))        # every step before this one, in order, once
    assert polled == [(10, [0, 0]), (11, [1, 1]), (12, [0, 1])]
    assert task.sentinel.consecutive == 0 and task.sentinel.total == 1     # as of step 12: step 13 is still unread
    task.drain()
    assert polled[3:] == [(13, [1, 2])] and (task.sentinel.consecutive, task.sentinel.total) == (1, 2)
    task.drain()                                                           # nothing is read twice
    assert len(polled) == 4
    polls_now, ahead_now = _counters()
    assert polls_now - polls == 4 and 0 <= ahead_now - ahead <= 4           # both count, in the program's ring
    task.reset_nonfinite()


@pytest.mark.parametrize('how', ['next_call', 'drain'])
def test_tolerance_consecutive_bad_steps_raise_one_call_later_with_the_bad_steps_number(mesh8, task, how):
    from timm_tpu.resilience import NonFiniteError
    task.reset_nonfinite()
    task.train_step(_batch(mesh8), lr=1e-3, step=0)
    for step in (1, 2, 3):                                                 # the third bad step's own call reads the second
        task.train_step(_batch(mesh8, nan=True), lr=1e-3, step=step)
    assert task.sentinel.consecutive == 2
    with pytest.raises(NonFiniteError) as ei:
        if how == 'drain':
            task.drain()
        else:
            task.train_step(_batch(mesh8, seed=1), lr=1e-3, step=4)
    assert (ei.value.consecutive, ei.value.total, ei.value.step, ei.value.tolerance) == (3, 3, 3, 3)
    task.reset_nonfinite()
    task.drain()                                                           # the rollback's reset forgot step 4
    assert task.sentinel.consecutive == 0


@pytest.mark.parametrize('forget', ['reset_nonfinite', 'load_checkpoint_state'])
def test_a_rollback_forgets_the_unread_step(mesh8, task, monkeypatch, forget):
    task.reset_nonfinite()
    state = task.get_checkpoint_state()
    task.train_step(_batch(mesh8, nan=True), lr=1e-3, step=0)
    assert task._unread is not None and task._unread[0] == 0
    polled = []
    monkeypatch.setattr(task.sentinel, 'observe', lambda state, step=0: polled.append(step))
    if forget == 'reset_nonfinite':
        task.reset_nonfinite()
    else:
        task.load_checkpoint_state(state, strict=False)
    assert task._unread is None
    task.drain()
    task.train_step(_batch(mesh8), lr=1e-3, step=1)
    assert polled == []                                                    # step 0 went with the state it belonged to
    monkeypatch.undo()
    task.reset_nonfinite()


@pytest.mark.parametrize('rebuild', ['set_grad_accum', 'set_block_scan', 'setup_ema'])
def test_what_drops_the_step_binding_reads_the_unread_step_first(mesh8, rebuild):
    task = _task(mesh8, opt='sgd', nonfinite_tolerance=3)
    task.train_step(_batch(mesh8, nan=True), lr=1e-3, step=7)
    assert task._unread[0] == 7 and task.sentinel.total == 0
    {'set_grad_accum': lambda: task.set_grad_accum(2), 'set_block_scan': lambda: task.set_block_scan(True),
     'setup_ema': lambda: task.setup_ema(decay=0.9)}[rebuild]()
    assert task._unread is None and task._train_step is None and (task.sentinel.consecutive, task.sentinel.total) == (1, 1)


def test_a_bad_step_followed_by_a_good_one_logs_the_warning_once_with_its_own_step(mesh8, task, caplog):
    task.reset_nonfinite()
    with caplog.at_level(logging.WARNING, logger='timm_tpu.resilience.sentinel'):
        for step, nan in ((20, False), (21, True), (22, False), (23, False)):
            task.train_step(_batch(mesh8, nan=nan), lr=1e-3, step=step)
        task.drain()
    lines = [r.getMessage() for r in caplog.records if 'Non-finite' in r.getMessage()]
    assert len(lines) == 1 and 'at update 21:' in lines[0] and '(1 consecutive, 1 total)' in lines[0]
    task.reset_nonfinite()


def test_without_the_guard_nothing_is_kept_or_polled(mesh8):
    task = _task(mesh8, opt='sgd', nonfinite_guard=False)
    polls = _counters()[0]
    metrics = task.train_step(_batch(mesh8), lr=1e-3, step=0)
    task.drain()
    assert 'nonfinite_count' not in metrics and task._unread is None and _counters()[0] == polls


def test_a_bad_step_commits_nothing_of_parameters_optimizer_state_or_ema(mesh8, task):
    task.reset_nonfinite()
    task.train_step(_batch(mesh8), lr=1e-3, step=0)
    state = lambda: [np.asarray(x) for x in jax.tree.leaves(  # noqa: E731
        (nnx.state(task.model, nnx.Param), task.opt_state, task.ema_params))]
    before = state()
    metrics = task.train_step(_batch(mesh8, nan=True), lr=1e-3, step=1)
    after = state()
    assert len(before) == len(after) and all(np.array_equal(a, b) for a, b in zip(before, after))
    assert int(metrics['nonfinite_count']) == 1
    metrics = task.train_step(_batch(mesh8, seed=1), lr=1e-3, step=2)
    moved = state()
    assert int(metrics['nonfinite_count']) == 0 and int(metrics['nonfinite_total']) == 1
    assert not all(np.array_equal(a, b) for a, b in zip(after, moved))


def test_train_main_freezes_what_setup_built_for_the_run_and_thaws_it_after(tmp_path):
    """After a run's first step `train.py` freezes the collector's generations:
    a full collection no longer walks the model and JAX's caches (over 100 ms
    with the interpreter lock held, every few seconds, finding nothing). When
    `main` returns they are thawed: the task sits in a cycle with its jitted
    step, and frozen it would keep its device state for the process's life.
    `main` twice in one process leaves no array alive. Its own process: the
    frozen set is the interpreter's."""
    import os
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = f"""
import gc, json, jax, train
argv = ['--synthetic-data', '--model', 'test_vit', '--img-size', '32', '-b', '8', '--synthetic-len', '16',
        '--epochs', '1', '--workers', '1', '--output', {str(tmp_path)!r}, '--experiment', 'frozen']
during, validate = [], train.validate
train.validate = lambda *a, **k: (during.append(gc.get_freeze_count()), validate(*a, **k))[1]   # after the epoch's steps
gc.collect()
after = [(gc.get_freeze_count(), len(jax.live_arrays()))]
for _ in range(2):
    train.main(argv)
    gc.collect()
    after.append((gc.get_freeze_count(), len(jax.live_arrays())))
print('FROZEN', json.dumps([during, after]))
"""
    r = subprocess.run([sys.executable, '-c', code], capture_output=True, text=True, cwd=root, timeout=300,
                       env=dict(os.environ, JAX_PLATFORMS='cpu'))
    assert r.returncode == 0, r.stderr[-2000:]
    import json
    during, after = json.loads(r.stdout.split('FROZEN')[1])
    assert len(during) == 2 and min(during) > 50000, during          # both runs froze, the second one anew
    assert all(frozen < 5000 for frozen, _ in after), after
    assert [live for _, live in after] == [after[0][1]] * 3, after   # nothing of either run's state is left
