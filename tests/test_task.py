"""Task / train-step tests (reference: tests/test_task.py — checkpoint schema,
EMA; plus multi-device sharded step tests the reference lacks)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

import timm_tpu
from timm_tpu.loss import LabelSmoothingCrossEntropy
from timm_tpu.optim import create_optimizer_v2
from timm_tpu.parallel import shard_batch
from timm_tpu.task import ClassificationTask, LogitDistillationTask


def _make_task(mesh, **kwargs):
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05)
    return ClassificationTask(
        model, optimizer=opt, mesh=mesh,
        train_loss_fn=LabelSmoothingCrossEntropy(0.1), **kwargs)


def _batch(mesh, n=16, seed=0):
    rng = np.random.RandomState(seed)
    return shard_batch({
        'input': jnp.asarray(rng.rand(n, 32, 32, 3), jnp.float32),
        'target': jnp.asarray(rng.randint(0, 10, n)),
    }, mesh)


def test_train_step_decreases_loss(mesh8):
    task = _make_task(mesh8, clip_grad=1.0)
    batch = _batch(mesh8)
    losses = [float(task.train_step(batch, lr=1e-3, step=i)['loss']) for i in range(6)]
    assert losses[-1] < losses[0]


def test_train_step_sharded_over_mesh(mesh8):
    assert mesh8.size == 8
    task = _make_task(mesh8)
    batch = _batch(mesh8)
    # input actually sharded across devices
    assert len(batch['input'].sharding.device_set) == 8
    metrics = task.train_step(batch, lr=1e-3)
    assert np.isfinite(float(metrics['loss']))


def test_grad_accumulation_matches_large_batch(mesh8):
    # same data: accum over 2 microbatches ≈ one step on full batch
    t1 = _make_task(mesh8)
    t2 = _make_task(mesh8, grad_accum_steps=2)
    batch = _batch(mesh8, n=16)
    l1 = float(t1.train_step(batch, lr=1e-3)['loss'])
    l2 = float(t2.train_step(batch, lr=1e-3)['loss'])
    assert l1 == pytest.approx(l2, abs=1e-3)


def test_ema_update_and_eval(mesh8):
    task = _make_task(mesh8)
    task.setup_ema(decay=0.5)
    batch = _batch(mesh8)
    for i in range(3):
        task.train_step(batch, lr=1e-2, step=i + 1)
    out = task.eval_step({'input': batch['input']})
    out_ema = task.eval_step({'input': batch['input']}, use_ema=True)
    assert out.shape == (16, 10)
    assert not bool(jnp.allclose(out, out_ema))


def test_checkpoint_schema_and_roundtrip(mesh8):
    task = _make_task(mesh8)
    task.setup_ema(decay=0.9)
    task.train_step(_batch(mesh8), lr=1e-3, step=1)
    state = task.get_checkpoint_state()
    assert any(k.startswith('state_dict.') for k in state)
    assert any(k.startswith('state_dict_ema.') for k in state)
    assert any(k.startswith('optimizer.') for k in state)
    assert not any('rngs' in k for k in state)
    # roundtrip into a fresh task
    task2 = _make_task(mesh8)
    task2.setup_ema(decay=0.9)
    task2.train_step(_batch(mesh8, seed=3), lr=1e-3, step=1)
    task2.load_checkpoint_state(state)
    x = _batch(mesh8)['input']
    a = task.eval_step({'input': x})
    b = task2.eval_step({'input': x})
    assert bool(jnp.allclose(a, b, atol=1e-5))


def test_checkpoint_saver(tmp_path, mesh8):
    from timm_tpu.utils import CheckpointSaver
    task = _make_task(mesh8)
    saver = CheckpointSaver(task, checkpoint_dir=str(tmp_path), recovery_dir=str(tmp_path), max_history=2)
    for ep, metric in [(0, 10.0), (1, 30.0), (2, 20.0)]:
        best, best_ep = saver.save_checkpoint(ep, metric)
    assert best == 30.0 and best_ep == 1
    files = {f.name for f in tmp_path.iterdir()}
    assert 'last.npz' in files and 'model_best.npz' in files
    # retention: only 2 epoch checkpoints kept (each with a manifest sidecar)
    assert len([f for f in files if f.startswith('checkpoint-') and f.endswith('.npz')]) == 2
    assert len([f for f in files if f.startswith('checkpoint-') and f.endswith('.manifest.json')]) == 2
    # recovery
    saver.save_recovery(2, batch_idx=5)
    assert saver.find_recovery()


def test_logit_distillation(mesh8):
    student = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    teacher = timm_tpu.create_model('test_vit2', num_classes=10, img_size=32)
    opt = create_optimizer_v2(student, opt='adamw', lr=1e-3)
    task = LogitDistillationTask(
        student, teacher, optimizer=opt, mesh=mesh8,
        train_loss_fn=LabelSmoothingCrossEntropy(0.1), distill_alpha=0.5, distill_temperature=2.0)
    m = task.train_step(_batch(mesh8), lr=1e-3)
    assert np.isfinite(float(m['loss']))


def test_dryrun_multichip_entry():
    import importlib.util
    import os
    spec = importlib.util.spec_from_file_location(
        'graft_entry', os.path.join(os.path.dirname(__file__), '..', '__graft_entry__.py'))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.dryrun_multichip(8)


def test_ema_decay_zero_syncs_to_model(mesh8):
    """decay==0 must copy model params into EMA (reference ModelEmaV3 lerp
    weight 1.0 during the update_after_step window), not freeze the EMA
    (ADVICE r1 medium)."""
    task = _make_task(mesh8)
    task.setup_ema(decay=0.999, warmup=True, update_after_step=100)
    batch = _batch(mesh8)
    # inside the update_after_step window → get_decay == 0 → EMA tracks model
    assert task.ema.get_decay(1) == 0.0
    for i in range(2):
        task.train_step(batch, lr=1e-2, step=i + 1)
    params = jax.tree.leaves(nnx.state(task.model, nnx.Param))
    ema = jax.tree.leaves(task.ema_params)
    assert all(np.allclose(np.asarray(p), np.asarray(e)) for p, e in zip(params, ema))


class _TinyNet(nnx.Module):
    def __init__(self, rngs):
        self.fc1 = nnx.Linear(24, 48, rngs=rngs)
        self.fc2 = nnx.Linear(48, 10, rngs=rngs)
        self.num_classes = 10

    def __call__(self, x):
        return self.fc2(nnx.relu(self.fc1(x.reshape(x.shape[0], -1))))


@pytest.mark.parametrize('mu_dtype', ['float32', 'bfloat16'], ids=['fp32', 'mu_bf16'])
def test_step_update_follows_hand_written_adamw_and_ema(mesh8, mu_dtype):
    """Five donated steps of the task's `step.update` / `step.ema` against AdamW and EMA written out in plain
    jax.numpy and fed the step's own gradients: bias-corrected moments, decoupled decay on the matrices only (the
    factory's mask), the first moment kept in `mu_dtype` between steps, ema = d * ema + (1 - d) * p at the
    controller's d."""
    lr, wd, b1, b2, eps = 0.01, 0.05, 0.9, 0.999, 1e-8
    model = _TinyNet(nnx.Rngs(0))
    opt = create_optimizer_v2(model, opt='adamw', lr=lr, weight_decay=wd, mu_dtype=mu_dtype)
    grads_seen, chain_update = [], opt.update

    def update(grads, *args, **kwargs):  # the gradients as the chain gets them, out of the jitted step
        jax.debug.callback(lambda g: grads_seen.append(jax.tree.map(jnp.array, g)), grads)
        return chain_update(grads, *args, **kwargs)

    opt.update = update
    task = ClassificationTask(model, optimizer=opt)
    task.setup_ema(decay=0.99)

    p = ema = jax.tree.map(jnp.asarray, jax.device_get(nnx.state(model, nnx.Param)))
    m = jax.tree.map(lambda a: jnp.zeros_like(a, dtype=mu_dtype), p)
    v = jax.tree.map(jnp.zeros_like, p)
    # optax writes `b1 * mu` with a python b1, so the product is taken in mu's dtype: a bfloat16 moment decays by
    # bfloat16(0.9) = 0.8984375 (the compiled step keeps the product itself in float32)
    b1_kept = float(jnp.asarray(b1, mu_dtype))
    rng = np.random.RandomState(0)
    for t in range(1, 6):
        batch = {'input': jnp.asarray(rng.rand(8, 2, 2, 6), jnp.float32), 'target': jnp.asarray(rng.randint(0, 10, 8))}
        task.train_step(batch, lr=lr, step=t)
        jax.effects_barrier()
        g = grads_seen[-1]
        m = jax.tree.map(lambda m_, g_: b1_kept * m_.astype(jnp.float32) + (1 - b1) * g_, m, g)
        v = jax.tree.map(lambda v_, g_: b2 * v_ + (1 - b2) * g_ * g_, v, g)
        p = jax.tree.map(
            lambda p_, m_, v_: p_ - lr * (m_ / (1 - b1 ** t) / (jnp.sqrt(v_ / (1 - b2 ** t)) + eps)
                                          + wd * p_ * (p_.ndim > 1)), p, m, v)
        m = jax.tree.map(lambda m_: m_.astype(mu_dtype), m)
        d = task.ema.get_decay(t)  # the controller's schedule: 0 (a copy) at step 1, then 0.99
        ema = jax.tree.map(lambda e_, p_: d * e_ + (1 - d) * p_, ema, p)
    assert len(grads_seen) == 5
    for got, want in ((nnx.state(task.model, nnx.Param), p), (task.ema_params, ema)):
        for a, b in zip(jax.tree.leaves(jax.device_get(got)), jax.tree.leaves(want), strict=True):
            assert float(np.abs(a - b).max()) <= 1e-6
