"""The step's state carried flat between steps: `TrainingTask.train_step` binds the model's
`Variable`s once, reads their arrays, calls the jitted step on flat tuples and writes the
returned arrays back. Held here against an oracle kept in this file, the loop the task had
before (`nnx.split` -> the same jitted function -> `nnx.update`), bit for bit, and against
what the outside sees of the task between two steps."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

import timm_tpu
from benchmarks.harness import program
from timm_tpu.loss import LabelSmoothingCrossEntropy
from timm_tpu.optim import create_optimizer_v2
from timm_tpu.task import CausalLMTask, ClassificationTask
from timm_tpu.utils import tracing

S = 64      # the LM toy's sequence length


def _vit(mesh, accum=1, ema=True):
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32, drop_path_rate=0.1)
    task = ClassificationTask(model, optimizer=create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05),
                              mesh=mesh, clip_grad=1.0, grad_accum_steps=accum, train_loss_fn=LabelSmoothingCrossEntropy(0.1))
    if ema:
        task.setup_ema(decay=0.9)
    return task


def _batchnorm(mesh):
    model = timm_tpu.create_model('test_resnet', num_classes=10)
    task = ClassificationTask(model, optimizer=create_optimizer_v2(model, opt='sgd', lr=0.1, momentum=0.9), mesh=mesh)
    task.setup_ema(decay=0.9)
    return task


def _lm(mesh):
    model = timm_tpu.create_model('glm4_moe_lite_toy', seed=0)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.1, betas=(0.9, 0.95))
    return CausalLMTask(model, optimizer=opt, mesh=mesh, clip_grad=1.0, loss_chunk=32)


@pytest.fixture(scope='module')
def mesh1():
    """One device: there `_train_step._cache_size() == 1` says one program exactly (as in `test_chip_smoke.py`)."""
    from timm_tpu.parallel import create_mesh
    return create_mesh(devices=jax.devices()[:1])


MAKERS = {'vit': _vit, 'vit_accum2': lambda mesh: _vit(mesh, accum=2), 'batchnorm': _batchnorm, 'lm': _lm}


def _batch(task, mesh, seed):
    from timm_tpu.parallel import shard_batch
    rng = np.random.RandomState(seed)
    if isinstance(task, CausalLMTask):
        ids = rng.randint(0, 256, (2, S + 1))
        return shard_batch({'input': jnp.asarray(ids[:, :-1]), 'target': jnp.asarray(ids[:, 1:])}, mesh)
    size = 32 if hasattr(task.model, 'patch_embed') else 64
    return shard_batch({'input': jnp.asarray(rng.rand(8, size, size, 3), jnp.float32),
                        'target': jnp.asarray(rng.randint(0, 10, 8))}, mesh)


def oracle_step(task, batch, lr, step):
    """The loop `train_step` ran before the state was carried flat, around the same jitted function:
    set the mode, split the live model, flatten, call, rebuild the trees, merge them back."""
    if task._train_step is None:
        task._train_step = task._build_train_step()
    task.model.train()
    _, params, rest = nnx.split(task.model, nnx.Param, ...)
    trees = (params, rest, task.opt_state, task.ema_params)
    flat = [jax.tree.flatten(t) for t in trees]
    decay = task.ema.get_decay(step) if task.ema is not None else 0.0
    sent = task._sentinel_state if task._sentinel_state is not None else ()
    *out, sent, metrics = task._train_step(*(tuple(leaves) for leaves, _ in flat), sent, batch,
                                           jnp.asarray(lr, jnp.float32), jnp.asarray(decay, jnp.float32))
    params, rest, task.opt_state, task.ema_params = (jax.tree.unflatten(d, o) for (_, d), o in zip(flat, out))
    nnx.update(task.model, params, rest)
    if task._sentinel_state is not None:
        task._sentinel_state = sent
    return metrics


def _named(tree):
    return {jax.tree_util.keystr(p): np.asarray(jax.random.key_data(v) if jnp.issubdtype(v.dtype, jax.dtypes.prng_key) else v)
            for p, v in jax.tree_util.tree_leaves_with_path(tree)}


def _state(task, rng=True):
    """Everything a step carries, by name, on the host."""
    state = _named({'params': nnx.state(task.model, nnx.Param), 'rest': nnx.state(task.model, nnx.Not(nnx.Param)),
                    'opt': task.opt_state, 'ema': task.ema_params, 'sentinel': task._sentinel_state})
    return state if rng else {k: v for k, v in state.items() if "['rngs']" not in k and 'sentinel' not in k}


def _assert_same(a, b):
    assert a.keys() == b.keys()
    differ = [k for k in a if not np.array_equal(a[k], b[k], equal_nan=True)]
    assert not differ, differ[:8]


def _binds():
    return tracing.snapshot()['counters'].get('task.state_binds', 0)


def _twins(maker, mesh):
    """Two tasks from the same seeds: `train_step` drives the first, the oracle the second."""
    task, twin = maker(mesh), maker(mesh)
    _assert_same(_state(task), _state(twin))
    return task, twin


def _step_both(task, twin, mesh, step, lr=1e-3):
    batch = _batch(task, mesh, seed=step)
    got, want = task.train_step(batch, lr=lr, step=step), oracle_step(twin, _batch(twin, mesh, seed=step), lr, step)
    _assert_same({k: np.asarray(v) for k, v in got.items()}, {k: np.asarray(v) for k, v in want.items()})
    _assert_same(_state(task), _state(twin))
    return got


# ---- (b) three steps, bit for bit ----------------------------------------------------------------------------

@pytest.mark.parametrize('kind', list(MAKERS))
def test_three_steps_are_bit_identical_to_the_split_and_update_loop(mesh1, kind):
    task, twin = _twins(MAKERS[kind], mesh1)
    start, binds = _state(task), _binds()
    for step in range(3):
        metrics = _step_both(task, twin, mesh1, step)
        assert np.isfinite(float(metrics['loss']))
    now = _state(task)
    moved = {k.split("'")[1] for k in now if not np.array_equal(now[k], start[k])}
    assert {'params', 'opt'} <= moved and ('ema' in moved) == (task.ema is not None)
    assert ('rest' in moved) == (kind != 'lm')                      # RNG counts, batch statistics: carried, not dropped (the LM toy draws nothing)
    assert task._train_step._cache_size() == 1 and _binds() - binds == 2      # one bind a task, the twin's included


# ---- (a) nothing of the module graph on the per-step path -----------------------------------------------------

@pytest.fixture(scope='module')
def pair(mesh1):
    """A ViT task with an EMA and its oracle twin, one step in: the tests below keep them in step."""
    task, twin = _twins(_vit, mesh1)
    binds = _binds()
    _step_both(task, twin, mesh1, 0)
    assert _binds() - binds == 2
    return task, twin


WALKS = {'split': lambda m: nnx.split(m), 'update': lambda m: nnx.update(m, nnx.state(m)), 'merge': lambda m: nnx.merge(*nnx.split(m)),
         'state': lambda m: nnx.state(m), 'train': lambda m: m.train()}


@pytest.mark.parametrize('name', list(WALKS))
def test_after_the_first_step_train_step_never_walks_the_module_graph(mesh1, pair, monkeypatch, name):
    task, twin = pair
    calls = []
    owner, attr = (nnx.Module, 'train') if name == 'train' else (nnx, name)
    real = getattr(owner, attr)
    monkeypatch.setattr(owner, attr, lambda *a, **k: (calls.append(name), real(*a, **k))[1])
    batches, binds = [_batch(task, mesh1, seed=100 + i) for i in range(2)], _binds()
    for i, batch in enumerate(batches):
        task.train_step(batch, lr=1e-3, step=1 + i)
    assert calls == [] and _binds() == binds and task._train_step._cache_size() == 1
    WALKS[name](task.model)
    assert calls                                                    # the spy does see such a call
    monkeypatch.undo()
    for i, batch in enumerate(batches):                             # the twin catches up, for the tests that follow
        oracle_step(twin, batch, 1e-3, 1 + i)
    _assert_same(_state(task), _state(twin))


# ---- (c) what the outside sees between two steps ------------------------------------------------------------

def test_the_models_variables_keep_their_identity_and_hold_what_the_program_returned(mesh1, pair, monkeypatch):
    task, twin = pair
    is_var = lambda x: isinstance(x, nnx.Variable)  # noqa: E731
    variables = lambda: (jax.tree.leaves(nnx.state(task.model, nnx.Param), is_leaf=is_var)  # noqa: E731
                         + jax.tree.leaves(nnx.state(task.model, nnx.Not(nnx.Param)), is_leaf=is_var))
    before, real, outs = variables(), task._train_step, []
    old = [v.get_raw_value() for v in before]
    monkeypatch.setattr(task, '_train_step', lambda *a: (outs.append(real(*a)), outs[-1])[1])
    batch = _batch(task, mesh1, seed=7)
    metrics = task.train_step(batch, lr=1e-3, step=3)
    monkeypatch.undo()
    (out,), after = outs, variables()
    assert len(after) == len(before) and all(a is b for a, b in zip(after, before))
    returned = list(out[0]) + list(out[1])
    assert len(returned) == len(after) and all(v.get_raw_value() is r for v, r in zip(after, returned))
    assert all(o.is_deleted() for o in old if o.size > 1) and not any(r.is_deleted() for r in returned)   # donated, replaced
    assert jax.tree.leaves(task.opt_state) == list(out[2]) and jax.tree.leaves(task.ema_params) == list(out[3])
    assert task._sentinel_state is out[4] and metrics is out[5]
    oracle_step(twin, batch, 1e-3, 3)
    _assert_same(_state(task), _state(twin))


def test_the_benchmarks_readers_see_the_trees_and_names_they_always_did(mesh1, pair):
    task, twin = pair
    names = set(program.named_leaves(nnx.state(task.model, nnx.Param)))
    assert 'blocks.0.attn.qkv.kernel' in names
    assert set(program.named_leaves(task.ema_params)) == names == set(program.named_leaves(program._adam_mu(task.opt_state)))
    assert set(program.first_grad_norms(task)) == names
    assert isinstance(task.ema_params, nnx.State) and type(task.opt_state) is type(twin.opt_state)
    start = _host(task.ema_params)
    keys, counts = program.drop_path_keys(task.model), program.drop_path_counts(task.model)
    _step_both(task, twin, mesh1, 4)
    assert max(program.ema_change_norms(task, start).values()) > 0 and max(program.param_change_norms(task, start).values()) > 0
    after = program.drop_path_counts(task.model)
    assert counts and all(after[k] == counts[k] + 1 for k in counts)             # every stream one key further a step
    assert all(not np.array_equal(jax.random.key_data(program.drop_path_keys(task.model)[k]), jax.random.key_data(keys[k])) for k in keys)


def _host(tree):
    """Host copies by the benchmark's names: the step donates what the task holds."""
    return {k: np.array(v) for k, v in program.named_leaves(tree).items()}


def _one_step_away(change, start, lr=1e-3):
    """Every leaf moved, and by no more than one Adam step can move it: lr (1 - b1) / sqrt(1 - b2) = 3.2 lr an element."""
    return all(0 < change[k] < 4 * lr * np.sqrt(start[k].size) for k in start)


def _weights(task, seed):
    rng = np.random.RandomState(seed)
    return {k: jnp.asarray(rng.standard_normal(v.shape) * 0.02, jnp.float32)
            for k, v in program.named_leaves(nnx.state(task.model, nnx.Param)).items()}


def test_load_task_weights_before_the_first_step_is_what_the_first_step_starts_from(mesh1):
    task, twin = _twins(_vit, mesh1)
    weights = _weights(task, 1)
    for t in (task, twin):
        program.load_task_weights(t, weights)
    _assert_same(_host(task.ema_params), _host(weights))
    _step_both(task, twin, mesh1, 0)
    assert _one_step_away(program.param_change_norms(task, weights), weights)


def test_load_task_weights_between_two_steps_is_what_the_next_step_starts_from(mesh1, pair):
    task, twin = pair
    weights = _weights(task, 2)
    for t in (task, twin):
        program.load_task_weights(t, weights)
    _assert_same(_host(nnx.state(task.model, nnx.Param)), _host(weights))
    _step_both(task, twin, mesh1, 5)
    assert _one_step_away(program.param_change_norms(task, weights), weights)
    assert _one_step_away(program.ema_change_norms(task, weights), weights)
    assert task._train_step._cache_size() == 1


# ---- (d) the task's other entry points between two steps ---------------------------------------------------------

def _eval(task, mesh, tmp):
    out = task.eval_step(_batch(task, mesh, seed=50))
    ema_out = task.eval_step(_batch(task, mesh, seed=50), use_ema=True)
    assert out.shape == ema_out.shape == (8, 10) and not np.array_equal(np.asarray(out), np.asarray(ema_out))


def _sync_ema(task, mesh, tmp):
    assert task.sync_model(use_ema=True) is task.model
    _assert_same(_host(nnx.state(task.model, nnx.Param)), _host(task.ema_params))
    # the model now shares its arrays with the EMA, and one buffer cannot be donated twice: as after
    # `load_task_weights`, the parameters get copies of their own before the next step
    nnx.update(task.model, jax.tree.map(lambda x: jnp.array(x, copy=True), nnx.state(task.model, nnx.Param)))


def _load_other_checkpoint(task, mesh, tmp):
    donor = _vit(mesh)
    donor.train_step(_batch(donor, mesh, seed=60), lr=1e-2, step=0)
    task.load_checkpoint_state(donor.get_checkpoint_state())
    _assert_same(_state(task, rng=False), _state(donor, rng=False))


def _save_load_round_trip(task, mesh, tmp):
    from timm_tpu.resilience.durable import atomic_write_npz, load_verified
    path = str(tmp.mktemp('flat_carry') / 'checkpoint-0.npz')
    before = _state(task, rng=False)
    atomic_write_npz(path, task.get_checkpoint_state())
    task.train_step(_batch(task, mesh, seed=61), lr=1e-2, step=0)            # move away, then come back
    task.load_checkpoint_state(load_verified(path)[0])
    _assert_same(_state(task, rng=False), before)


BETWEEN = {'eval_step': _eval, 'sync_model_use_ema': _sync_ema, 'load_checkpoint_state': _load_other_checkpoint,
           'save_load_round_trip': _save_load_round_trip}


@pytest.mark.parametrize('between', list(BETWEEN))
def test_between_two_steps_one_program_stays_and_the_next_step_uses_what_was_written(mesh1, pair, between, tmp_path_factory):
    task, twin = pair
    binds = _binds()
    for t in (task, twin):
        BETWEEN[between](t, mesh1, tmp_path_factory)
    _assert_same(_state(task), _state(twin))
    start = _host(nnx.state(task.model, nnx.Param))
    _step_both(task, twin, mesh1, 6)
    assert _one_step_away(program.param_change_norms(task, start), start)     # it started from what was written
    assert task._train_step._cache_size() == 1 and twin._train_step._cache_size() == 1
    assert _binds() - binds == (2 if between == 'load_checkpoint_state' else 0)      # the two donors' own, no other


# ---- (e) what rebuilds the step rebuilds the binding --------------------------------------------------------------

REBUILDS = {'setup_ema': lambda t: t.setup_ema(decay=0.9) or True, 'set_block_scan': lambda t: t.set_block_scan(True),
            'set_grad_accum': lambda t: t.set_grad_accum(2)}


@pytest.mark.parametrize('rebuild', list(REBUILDS))
def test_what_invalidates_the_step_rebuilds_the_binding_and_the_step_still_agrees(mesh1, rebuild):
    task, twin = _twins(lambda mesh: _vit(mesh, ema=False), mesh1)
    binds = _binds()
    _step_both(task, twin, mesh1, 0)
    assert _binds() - binds == 2
    for t in (task, twin):
        assert REBUILDS[rebuild](t) and t._train_step is None and t._step_vars is None
    binds = _binds()
    for step in (1, 2):
        _step_both(task, twin, mesh1, step)
    assert _binds() - binds == 2 and task._train_step._cache_size() == 1     # bound once more a task, then carried
    assert (task.ema_params is not None) == (rebuild == 'setup_ema')
