"""The host's work around a step: the sentinel's counters are outputs of the
step program (no eager slice after the call), a bad step commits nothing, and
`train.main` freezes the collector's generations for the run and thaws them."""
import jax
import numpy as np
import pytest
from flax import nnx

pytestmark = pytest.mark.resilience


@pytest.fixture(scope='module')
def task(mesh8):
    import timm_tpu
    from timm_tpu.loss import LabelSmoothingCrossEntropy
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import ClassificationTask
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    task = ClassificationTask(
        model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3), mesh=mesh8,
        train_loss_fn=LabelSmoothingCrossEntropy(0.1), nonfinite_tolerance=3)
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
    ARE the arrays the program returned, all of them and no other, and the
    sentinel is handed the program's own state array."""
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
    assert task._sentinel_state is out[4] and polled == [(out[4], 1)]
    task.reset_nonfinite()


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
