"""Sharded-execution tests.

1. BatchNorm semantics under a sharded batch (SURVEY §7 hard part (c)).
2. FSDP partition rules: m/v optimizer slots mirror their param's spec
   (what makes donation aliasing legal). The disjoint/exhaustive rule-table
   lint moved to timm_tpu/analysis (rule `partition-rules`).
3. Donated jitted steps: re-using a donated buffer raises. The source and
   compiled-HLO donation lints moved to timm_tpu/analysis (rules
   `donation-declared`, `donation-alias`).
4. Scanned grad accumulation: grad parity ≤1e-6 vs the legacy unroll, and
   jaxpr trace size is O(1) in grad_accum_steps.
5. 8-CPU-device subprocess drills: ('data','fsdp') train parity vs a single
   device ≤1e-6 after 3 updates, and checkpoint save-on-8-device →
   load-on-1-device with a byte-stable SHA-256 sidecar.
"""
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx
from jax.sharding import PartitionSpec as P

import timm_tpu
from timm_tpu.layers import BatchNormAct2d
from timm_tpu.loss import LabelSmoothingCrossEntropy
from timm_tpu.optim import create_optimizer_v2
from timm_tpu.parallel import (
    build_opt_shardings, build_param_shardings, create_mesh,
    param_bytes_per_device, path_specs, shard_batch, spec_for_param,
)
from timm_tpu.task import ClassificationTask

pytestmark = pytest.mark.sharding

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), '..'))


# ---- BatchNorm under a sharded batch (pre-FSDP coverage, kept) --------------

def test_bn_sharded_stats_match_global(mesh8):
    """Train-mode BN over an 8-way sharded batch: running stats and outputs
    must match the single-device global-batch computation (XLA inserts the
    cross-device reductions for the batch mean/var)."""
    rng = np.random.RandomState(0)
    x_np = rng.rand(16, 8, 8, 6).astype(np.float32) * 3.0 + 1.0

    def run(shard: bool):
        bn = BatchNormAct2d(6, rngs=nnx.Rngs(0))
        bn.train()
        graphdef, state = nnx.split(bn)

        @jax.jit
        def step(state, x):
            m = nnx.merge(graphdef, state)
            y = m(x)
            _, new_state = nnx.split(m)
            return y, new_state

        x = jnp.asarray(x_np)
        if shard:
            x = shard_batch(x, mesh8)
        y, new_state = step(state, x)
        return np.asarray(y), jax.tree.map(np.asarray, nnx.to_pure_dict(new_state))

    y_global, state_global = run(shard=False)
    y_sharded, state_sharded = run(shard=True)

    np.testing.assert_allclose(y_sharded, y_global, rtol=1e-5, atol=1e-5)
    flat_g = jax.tree_util.tree_leaves_with_path(state_global)
    flat_s = dict(jax.tree_util.tree_leaves_with_path(state_sharded))
    checked = 0
    for path, leaf_g in flat_g:
        leaf_s = flat_s[path]
        np.testing.assert_allclose(leaf_s, leaf_g, rtol=1e-5, atol=1e-6,
                                   err_msg=f'BN state diverged at {path}')
        checked += 1
    assert checked >= 2  # at least running mean + var compared


def test_bn_model_sharded_train_step_matches_global(mesh8):
    """Full jitted train step of a BN trunk (test_resnet) through the REAL
    task path: loss, grad norm, and updated BN running stats identical
    whether the batch is 8-way sharded or unsharded."""
    rng = np.random.RandomState(0)
    x_np = rng.rand(16, 64, 64, 3).astype(np.float32)
    t_np = rng.randint(0, 10, 16)

    def run(shard: bool):
        model = timm_tpu.create_model('test_resnet', num_classes=10)
        task = ClassificationTask(
            model, optimizer=create_optimizer_v2(model, opt='sgd', lr=0.1), mesh=mesh8)
        batch = {'input': jnp.asarray(x_np), 'target': jnp.asarray(t_np)}
        if shard:
            batch = shard_batch(batch, mesh8)
        metrics = task.train_step(batch, lr=0.1, step=1)
        stats = jax.tree.map(np.asarray, nnx.to_pure_dict(nnx.state(model, nnx.BatchStat)))
        return float(metrics['loss']), float(metrics.get('grad_norm', 0.0)), stats

    loss_g, gnorm_g, stats_g = run(shard=False)
    loss_s, gnorm_s, stats_s = run(shard=True)
    assert abs(loss_s - loss_g) < 1e-4, f'sharded loss {loss_s} != global {loss_g}'
    assert abs(gnorm_s - gnorm_g) / max(gnorm_g, 1e-8) < 1e-3
    flat_g = jax.tree_util.tree_leaves_with_path(stats_g)
    flat_s = dict(jax.tree_util.tree_leaves_with_path(stats_s))
    assert flat_g, 'model must expose BatchStat state'
    for path, leaf_g in flat_g:
        np.testing.assert_allclose(
            flat_s[path], leaf_g, rtol=1e-4, atol=1e-5,
            err_msg=f'sharded BN running stats diverged at {path}')


# ---- FSDP partition rules ----------------------------------------------------

def _fsdp_mesh(fsdp=4):
    return create_mesh(fsdp=fsdp)


def _param_paths(model_name, **kwargs):
    model = timm_tpu.create_model(model_name, **kwargs)
    from timm_tpu.utils.serialization import flatten_pytree
    return flatten_pytree(nnx.state(model, nnx.Param))


def test_rule_specs_shard_large_kernels_replicate_small(mesh8):
    mesh = _fsdp_mesh(4)
    specs = path_specs(_param_paths('test_vit', num_classes=10, img_size=32), mesh)
    # large matmul weights shard on 'fsdp'
    for path in ('blocks.0.attn.qkv.kernel', 'blocks.0.mlp.fc1.kernel', 'blocks.1.mlp.fc2.kernel'):
        assert any(ax == 'fsdp' for ax in specs[path]), f'{path}: {specs[path]}'
    # norm scales / biases / tokens stay replicated
    for path in ('blocks.0.norm1.scale', 'blocks.0.attn.qkv.bias', 'cls_token', 'pos_embed', 'norm.bias'):
        assert specs[path] == P(), f'{path}: {specs[path]}'
    # a 1-axis data mesh replicates everything (exact pre-FSDP behaviour)
    flat_specs = path_specs(_param_paths('test_vit', num_classes=10, img_size=32), mesh8)
    assert all(s == P() for s in flat_specs.values())


def test_opt_state_specs_mirror_param_specs():
    """AdamW m/v (and any other param-shaped slot) must inherit the param's
    spec leaf-for-leaf — donation aliasing requires input and output
    placement to agree, and m/v live exactly where their param lives."""
    mesh = _fsdp_mesh(4)
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05)
    params = nnx.state(model, nnx.Param)
    pspecs = path_specs(params, mesh)
    opt_sh, abstract = build_opt_shardings(opt, params, mesh)

    from jax.tree_util import tree_flatten_with_path
    from timm_tpu.parallel.sharding import _kp_str
    flat, _ = tree_flatten_with_path(opt_sh)
    mirrored = 0
    for kp, sharding in flat:
        path = _kp_str(kp)
        for ppath, pspec in pspecs.items():
            if path == ppath or path.endswith('.' + ppath):
                assert sharding.spec == pspec, f'{path}: {sharding.spec} != param {pspec}'
                mirrored += 1
                break
        else:
            assert sharding.spec == P(), f'non-param slot {path} must be replicated'
    # at least mu+nu for every param mirrored
    assert mirrored >= 2 * len(pspecs)


def test_param_bytes_per_device_accounting():
    mesh = _fsdp_mesh(4)
    params = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    tree = nnx.state(params, nnx.Param)
    rep, shard = param_bytes_per_device(tree, mesh)
    assert shard < rep, (rep, shard)
    # every sharded kernel contributes bytes/4; the floor is all-replicated
    assert shard > rep // 4


# ---- 2-axis mesh + batch divisibility ---------------------------------------

def test_create_mesh_fsdp_shapes(mesh8):
    mesh = create_mesh(fsdp=4)
    assert mesh.axis_names == ('data', 'fsdp')
    assert dict(mesh.shape) == {'data': 2, 'fsdp': 4}
    assert create_mesh().axis_names == ('data',)  # fsdp=1 keeps the 1-axis mesh
    with pytest.raises(ValueError, match='fsdp=3'):
        create_mesh(fsdp=3)


def test_shard_batch_2axis_and_divisibility_error(mesh8):
    mesh = _fsdp_mesh(4)
    batch = shard_batch({'input': jnp.ones((16, 4, 4, 3)), 'target': jnp.zeros((16,), jnp.int32)}, mesh)
    # batch shards over the data x fsdp product
    assert len(batch['input'].sharding.device_set) == 8
    # loud error instead of an opaque XLA reshape failure
    with pytest.raises(ValueError, match='not divisible by the mesh batch-shard count 8'):
        shard_batch(jnp.ones((12, 4)), mesh)
    with pytest.raises(ValueError, match='divisible'):
        shard_batch({'input': jnp.ones((6, 2))}, mesh8)


# ---- donated jitted steps ----------------------------------------------------

def _make_task(mesh, opt='sgd', model_args=('test_vit', dict(num_classes=10, img_size=32)), **kwargs):
    model = timm_tpu.create_model(model_args[0], **model_args[1])
    optimizer = create_optimizer_v2(model, opt=opt, lr=0.1, momentum=0.9)
    return ClassificationTask(model, optimizer=optimizer, mesh=mesh,
                              train_loss_fn=LabelSmoothingCrossEntropy(0.1), **kwargs)


def _batch(mesh, n=16, seed=0):
    rng = np.random.RandomState(seed)
    return shard_batch({'input': jnp.asarray(rng.rand(n, 32, 32, 3), jnp.float32),
                        'target': jnp.asarray(rng.randint(0, 10, n))}, mesh)


def test_train_step_donates_param_and_opt_buffers(mesh8):
    """The jitted step donates params/opt state/EMA: after one step the OLD
    buffers are deleted, and touching one raises instead of silently reading
    stale memory."""
    task = _make_task(mesh8)
    task.setup_ema(decay=0.5)
    old_param = jax.tree.leaves(nnx.state(task.model, nnx.Param))[0]
    old_opt = next(l for l in jax.tree.leaves(task.opt_state)
                   if hasattr(l, 'shape') and l.size > 1)
    old_ema = jax.tree.leaves(task.ema_params)[0]
    task.train_step(_batch(mesh8), lr=0.1, step=1)
    for name, buf in [('param', old_param), ('opt', old_opt), ('ema', old_ema)]:
        with pytest.raises(RuntimeError):
            np.asarray(buf)
            pytest.fail(f'donated {name} buffer was still readable')


def test_eval_after_donated_train_step(mesh8):
    """Donation must not leave the task holding deleted arrays: eval (incl.
    EMA eval) works right after a donated train step."""
    task = _make_task(mesh8)
    task.setup_ema(decay=0.5)
    batch = _batch(mesh8)
    for i in range(2):
        task.train_step(batch, lr=0.1, step=i + 1)
    out = task.eval_step({'input': batch['input']})
    out_ema = task.eval_step({'input': batch['input']}, use_ema=True)
    assert np.isfinite(np.asarray(out)).all() and np.isfinite(np.asarray(out_ema)).all()


# The in-test donation lints that lived here (source regex over timm_tpu/task/
# and donation_evidence on compiled artifacts) are now analysis rules
# `donation-declared` (Tier A) and `donation-alias` (Tier C) — see
# timm_tpu/analysis and tests/test_analysis.py.


# ---- scanned grad accumulation ----------------------------------------------

def test_scanned_accum_matches_unrolled(mesh8):
    """Grad parity: one SGD step at lr=0.1 makes the param delta a scaled
    gradient, so param agreement ≤1e-6 is gradient agreement ≤1e-5."""
    batch = _batch(mesh8)
    results = {}
    for scan in (True, False):
        task = _make_task(mesh8, grad_accum_steps=4, grad_accum_scan=scan)
        m = task.train_step(batch, lr=0.1, step=1)
        results[scan] = (float(m['loss']),
                         jax.tree.map(np.asarray, nnx.state(task.model, nnx.Param)))
    assert results[True][0] == pytest.approx(results[False][0], abs=1e-6)
    diffs = jax.tree.leaves(jax.tree.map(
        lambda a, b: float(np.abs(a - b).max()), results[True][1], results[False][1]))
    assert max(diffs) <= 1e-6, f'scan vs unroll param diff {max(diffs)}'


def test_scanned_accum_matches_single_large_batch(mesh8):
    t1 = _make_task(mesh8)
    t2 = _make_task(mesh8, grad_accum_steps=2)
    batch = _batch(mesh8, n=16)
    l1 = float(t1.train_step(batch, lr=1e-3)['loss'])
    l2 = float(t2.train_step(batch, lr=1e-3)['loss'])
    assert l1 == pytest.approx(l2, abs=1e-3)


def test_accum_trace_size_o1_in_steps(mesh8):
    """Acceptance: grad_accum_steps=8 no longer scales trace size ~8x vs
    grad_accum_steps=2 (the old Python unroll did)."""
    from timm_tpu.utils.compile_cache import count_jaxpr_eqns
    batch = _batch(mesh8)

    def eqns(accum, scan):
        task = _make_task(mesh8, grad_accum_steps=accum, grad_accum_scan=scan)
        return count_jaxpr_eqns(task.trace_train_step(batch, lr=0.1))

    from timm_tpu.perfbudget import check_ratio_max, check_ratio_min

    scan2, scan8 = eqns(2, True), eqns(8, True)
    check_ratio_max('scanned trace cost vs accum steps (eqns a8/a2)', scan8, scan2, 2.0)
    unroll8 = eqns(8, False)
    check_ratio_min('unrolled jaxpr vs scanned (eqns unroll8/scan8)', unroll8, scan8, 2.0)


# ---- fsdp end-to-end in-process ---------------------------------------------

def test_fsdp_task_train_eval_checkpoint_roundtrip(mesh8):
    """('data','fsdp') task: params/opt actually sharded, train+eval run, and
    a checkpoint saved from the fsdp task loads into a plain data-mesh task
    with identical eval outputs (round-trip across mesh shapes, in-process)."""
    mesh = _fsdp_mesh(4)
    task = _make_task(mesh, opt='adamw')
    qkv = nnx.state(task.model, nnx.Param)['blocks'][0]['attn']['qkv']['kernel'].value
    assert any(ax == 'fsdp' for ax in qkv.sharding.spec)
    sharded_opt = [l for l in jax.tree.leaves(task.opt_state)
                   if hasattr(l, 'sharding') and any(ax is not None for ax in l.sharding.spec)]
    assert sharded_opt, 'optimizer m/v must be fsdp-sharded'
    batch = _batch(mesh)
    for i in range(2):
        m = task.train_step(batch, lr=1e-3, step=i + 1)
    assert np.isfinite(float(m['loss']))
    state = task.get_checkpoint_state()

    task2 = _make_task(mesh8, opt='adamw')
    task2.load_checkpoint_state(state)
    x = _batch(mesh8)['input']
    a = np.asarray(task.eval_step({'input': shard_batch(np.asarray(x), mesh)}))
    b = np.asarray(task2.eval_step({'input': x}))
    # params round-trip bit-exactly; the tolerance is fp32 reduction-order
    # noise from evaluating under different mesh shapes
    np.testing.assert_allclose(a, b, atol=1e-5)


def test_create_sharded_model_abstract_init(caplog):
    """`nnx.eval_shape`-based init creates params directly on-mesh (no eager
    replicated copy, no fallback warning) with rule-conformant placement."""
    import logging
    from timm_tpu.parallel import create_sharded_model
    mesh = _fsdp_mesh(4)
    with caplog.at_level(logging.WARNING, logger='timm_tpu.parallel.sharding'):
        model = create_sharded_model(
            lambda: timm_tpu.create_model('test_vit', num_classes=10, img_size=32), mesh)
    assert not any('abstract init failed' in r.message for r in caplog.records), \
        'abstract init silently fell back to eager construction'
    qkv = nnx.state(model, nnx.Param)['blocks'][0]['attn']['qkv']['kernel'].value
    assert any(ax == 'fsdp' for ax in qkv.sharding.spec)
    x = shard_batch(jnp.zeros((8, 32, 32, 3)), mesh)
    model.eval()
    out = model(x)
    assert out.shape == (8, 10) and np.isfinite(np.asarray(out)).all()


# ---- subprocess drills: forced 8-device mesh parity + 1-device reload -------

_DRILL = os.path.join(os.path.dirname(__file__), 'fsdp_drill.py')


def _run_drill(mode, workdir, devices):
    env = dict(
        os.environ,
        JAX_PLATFORMS='cpu',
        XLA_FLAGS=f'--xla_force_host_platform_device_count={devices}',
        TIMM_TPU_DRILL_DEVICES=str(devices),
        TF_CPP_MIN_LOG_LEVEL='3',
        # a cache of the child's own: an 8-device step READ from a cache another process filled dies in
        # XLA:CPU's collective rendezvous (rc -6 after 40 s); one the child compiles does not
        JAX_COMPILATION_CACHE_DIR=os.path.join(str(workdir), f'jax_cache_{mode}'),
    )
    r = subprocess.run([sys.executable, _DRILL, mode, str(workdir)],
                       capture_output=True, text=True, env=env, cwd=REPO_ROOT, timeout=300)
    assert r.returncode == 0, f'{mode} drill failed rc={r.returncode}:\n{r.stderr[-3000:]}'
    out = [l for l in r.stdout.strip().splitlines() if l.startswith('{')]
    assert out, f'no JSON result from {mode} drill:\n{r.stdout[-2000:]}'
    return json.loads(out[-1])


def test_fsdp_8device_parity_and_cross_mesh_checkpoint(tmp_path):
    """Acceptance drill: under a forced 8-CPU-device ('data','fsdp') mesh the
    golden-fixture train step matches the single-device step ≤1e-6 (params
    after 3 updates), the durable checkpoint written from the sharded task
    carries the same SHA-256 sidecar a single-device save produces, and a
    fresh 1-device process verifies + loads it (save-on-8 → load-on-1)."""
    res = _run_drill('parity8', tmp_path, devices=8)
    assert res['devices'] == 8 and res['mesh'] == [2, 4]
    assert res['max_param_diff'] <= 1e-6, res
    assert res['max_ema_diff'] <= 1e-6, res
    assert os.path.exists(tmp_path / 'ckpt_fsdp.npz')
    # sidecar is byte-stable across mesh shapes: sharded-save hashes equal
    # the unsharded-save hashes computed in the same child
    assert res['manifest_matches_unsharded'], res

    res1 = _run_drill('load1', tmp_path, devices=1)
    assert res1['devices'] == 1
    assert res1['verified'] and res1['loaded'], res1
    assert res1['resave_manifest_matches'], res1
    # logits re-computed on a different mesh shape: fp32 reduction-order noise
    # only (params themselves round-trip bit-exactly, proven by the manifest)
    assert res1['eval_matches_saved_logits'] <= 1e-5, res1


# ---- 3-axis mesh: tensor parallelism -----------------------------------------

def _tp_mesh(fsdp=2, tp=2):
    return create_mesh(fsdp=fsdp, tp=tp)


@pytest.fixture
def restore_global_mesh():
    """The activation constraints read the GLOBAL mesh; tests that set it must
    put back whatever was there (it leaks across tests otherwise)."""
    from timm_tpu.parallel import peek_global_mesh, set_global_mesh
    from timm_tpu.parallel import mesh as mesh_mod
    saved = peek_global_mesh()
    yield
    mesh_mod._GLOBAL_MESH = saved


def test_create_mesh_tp_shapes_and_error_names_all_axes(mesh8):
    mesh = _tp_mesh()
    assert mesh.axis_names == ('data', 'fsdp', 'model')
    assert dict(mesh.shape) == {'data': 2, 'fsdp': 2, 'model': 2}
    # tp without fsdp still gets its axis; tp=1 keeps today's meshes exactly
    assert create_mesh(tp=2).axis_names == ('data', 'model')
    assert create_mesh(fsdp=2, tp=1).axis_names == ('data', 'fsdp')
    assert create_mesh(tp=1).axis_names == ('data',)
    with pytest.raises(ValueError, match=r'fsdp=2 x tp=3'):
        create_mesh(fsdp=2, tp=3)
    # the builder error names every requested axis and the device count
    with pytest.raises(ValueError, match=r'8 devices'):
        create_mesh(fsdp=2, tp=3)


def test_create_mesh_tp_env(monkeypatch, mesh8):
    monkeypatch.setenv('TIMM_TPU_TP', '2')
    monkeypatch.setenv('TIMM_TPU_FSDP', '2')
    mesh = create_mesh()
    assert mesh.axis_names == ('data', 'fsdp', 'model')
    assert dict(mesh.shape) == {'data': 2, 'fsdp': 2, 'model': 2}


def test_shard_batch_3axis_error_names_axes_and_nearest_batch(mesh8):
    mesh = _tp_mesh()
    batch = shard_batch({'input': jnp.ones((16, 4, 4, 3))}, mesh)
    assert len(batch['input'].sharding.device_set) == 8
    with pytest.raises(ValueError) as ei:
        shard_batch(jnp.ones((12, 4)), mesh)
    msg = str(ei.value)
    # names ALL axes with sizes, keeps the historical phrase, suggests the fix
    assert 'not divisible by the mesh batch-shard count 8' in msg
    assert 'data=2' in msg and 'fsdp=2' in msg and 'model=2' in msg
    assert 'Nearest legal global batch: 8 or 16' in msg


# The tp disjoint/exhaustive + every-model-rule-exercised lint is now the
# analysis rule `partition-rules` (timm_tpu/analysis/source_rules.py).


def test_tp1_specs_bit_identical_to_fsdp_only():
    """tp=1 must reproduce the 2-axis placement exactly — same spec for every
    param, so programs, donation aliasing, and checkpoints are unchanged."""
    paths = _param_paths('test_vit', num_classes=10, img_size=32)
    a = path_specs(paths, _fsdp_mesh(4))
    b = path_specs(paths, create_mesh(fsdp=4, tp=1))
    assert a == b


def test_tp_nondivisible_dims_warn_not_silent(caplog):
    """A head/hidden dim not divisible by the 'model' axis replicates with a
    logged WARNING (once per path), never silently."""
    import logging
    from timm_tpu.parallel.sharding import _WARNED_PATHS
    mesh = _tp_mesh()
    _WARNED_PATHS.discard('blocks.9.attn.qkv.kernel')
    with caplog.at_level(logging.WARNING, logger='timm_tpu.parallel.sharding'):
        spec = spec_for_param('blocks.9.attn.qkv.kernel', (192, 575), mesh)
    assert spec == P()
    warned = [r for r in caplog.records if 'not divisible' in r.message
              and 'blocks.9.attn.qkv.kernel' in r.message]
    assert warned, 'non-divisible tp dim must log a warning'
    # warn-once: a second resolve stays quiet
    caplog.clear()
    with caplog.at_level(logging.WARNING, logger='timm_tpu.parallel.sharding'):
        spec_for_param('blocks.9.attn.qkv.kernel', (192, 575), mesh)
    assert not [r for r in caplog.records if 'blocks.9.attn.qkv.kernel' in r.message]


def test_tp_opt_state_mirrors_2d_param_specs():
    """m/v of a (fsdp x model)-sharded kernel inherit the full 2-D spec —
    donation aliasing under tensor parallelism needs leaf-for-leaf agreement
    exactly as it did for 1-D fsdp."""
    mesh = _tp_mesh()
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3, weight_decay=0.05)
    params = nnx.state(model, nnx.Param)
    pspecs = path_specs(params, mesh)
    assert any(len([ax for ax in s if ax is not None]) == 2 for s in pspecs.values())
    opt_sh, _ = build_opt_shardings(opt, params, mesh)
    from jax.tree_util import tree_flatten_with_path
    from timm_tpu.parallel.sharding import _kp_str
    mirrored_2d = 0
    for kp, sharding in tree_flatten_with_path(opt_sh)[0]:
        path = _kp_str(kp)
        for ppath, pspec in pspecs.items():
            if path == ppath or path.endswith('.' + ppath):
                assert sharding.spec == pspec, f'{path}: {sharding.spec} != {pspec}'
                if len([ax for ax in pspec if ax is not None]) == 2:
                    mirrored_2d += 1
                break
    assert mirrored_2d > 0


def test_param_and_activation_bytes_tp_accounting():
    """2-D specs divide param bytes by fsdp*tp, and the activation estimate
    shows the constraints' ~1/tp scaling (equal numbers at tp=1)."""
    from timm_tpu.parallel import activation_bytes_per_device
    tree = nnx.state(timm_tpu.create_model('test_vit', num_classes=10, img_size=32), nnx.Param)
    rep2, shard2 = param_bytes_per_device(tree, _fsdp_mesh(4))
    rep3, shard3 = param_bytes_per_device(tree, _tp_mesh())
    assert rep2 == rep3
    # both meshes have 4-way sharding of the big kernels (4 fsdp vs 2x2), so
    # the per-device bytes land in the same ballpark and well under replicated
    assert shard3 < rep3 and abs(shard3 - shard2) < rep3 // 4

    u, c = activation_bytes_per_device(
        _tp_mesh(), batch_size=64, seq_len=197, width=192, depth=12)
    assert u == 2 * c  # tp=2, all dims divisible -> constraints halve activations
    u1, c1 = activation_bytes_per_device(
        _fsdp_mesh(4), batch_size=64, seq_len=197, width=192, depth=12)
    assert u1 == c1  # no 'model' axis -> estimate unchanged


def test_shard_activation_noop_paths(restore_global_mesh, mesh8):
    """shard_activation must be identity when it can't apply: no 'model'
    axis, wrong rank, or a non-divisible batch dim."""
    from timm_tpu.parallel import set_global_mesh, shard_activation
    x = jnp.ones((8, 17, 192))
    set_global_mesh(mesh8)
    assert shard_activation(x, 'residual') is x  # no 'model' axis
    mesh = _tp_mesh()
    set_global_mesh(mesh)
    x2 = jnp.ones((8, 17))
    assert shard_activation(x2, 'residual') is x2  # rank guard
    y = shard_activation(x, 'residual')
    assert y.sharding.spec == P(('data', 'fsdp'), None, 'model')
    # heads: 3 heads not divisible by tp=2 -> heads dim left unsharded
    h = shard_activation(jnp.ones((8, 3, 17, 64)), 'heads')
    assert all(ax != 'model' for ax in h.sharding.spec)
    with pytest.raises(ValueError):
        shard_activation(x, 'bogus')


def _find_scan_constraint(jaxpr):
    """True iff some scan body in `jaxpr` contains a sharding_constraint eqn."""
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == 'scan':
            body = eqn.params['jaxpr'].jaxpr
            if any(e.primitive.name == 'sharding_constraint' for e in body.eqns) or \
                    _find_scan_constraint(body):
                return True
        else:
            for v in eqn.params.values():
                inner = getattr(getattr(v, 'jaxpr', v), 'jaxpr', None) or getattr(v, 'jaxpr', None)
                if inner is not None and hasattr(inner, 'eqns') and _find_scan_constraint(inner):
                    return True
    return False


def test_tp_constraint_in_scan_body_and_no_involuntary_remat(restore_global_mesh):
    """Acceptance (compiled evidence, regression-tested): for vit_tiny at
    fsdp x tp = (2,2) with block_scan on,
      1. the scanned block body's jaxpr contains the residual-stream
         sharding_constraint (the carry is explicitly pinned), and
      2. the compiled HLO's while-loop runs on the PER-DEVICE residual
         f32[2,17,96] (batch 8/(data*fsdp)=2, width 192/tp=96) and the full
         replicated residual f32[8,17,192] never materializes — which is the
         involuntary-remat pattern PERF.md documented."""
    from timm_tpu.parallel import set_global_mesh
    mesh = _tp_mesh()
    set_global_mesh(mesh)
    model = timm_tpu.create_model('vit_tiny_patch16_224', img_size=64)
    model.set_block_scan(True)
    model.eval()
    graphdef, state = nnx.split(model)
    state = jax.device_put(state, build_param_shardings(state, mesh))

    def fwd(state, x):
        return nnx.merge(graphdef, state)(x)

    x = shard_batch(jnp.zeros((8, 64, 64, 3), jnp.float32), mesh)
    closed = jax.make_jaxpr(fwd)(state, x)
    assert _find_scan_constraint(closed.jaxpr), \
        'residual sharding_constraint missing from the scanned block body'

    compiled = jax.jit(fwd).lower(state, x).compile()
    hlo = compiled.as_text()
    assert 'f32[2,17,96]' in hlo, \
        'per-device (batch/4, tokens, width/2) residual not found in compiled HLO'
    assert 'f32[8,17,192]' not in hlo, \
        'full replicated residual materialized: involuntary-remat pattern is back'
    out = compiled(state, x)
    assert out.shape == (8, 1000) and bool(jnp.isfinite(out).all())


@pytest.mark.parametrize('model_args', [
    ('test_vit', dict(num_classes=10, img_size=32)),
    # the tp compile smoke at a zoo model's widths: 3 heads over tp=2, 1000 classes (two blocks: depth adds compile time only)
    ('vit_tiny_patch16_224', dict(img_size=32, depth=2)),
], ids=['test_vit', 'vit_tiny-fsdp2-tp2'])
def test_tp_task_train_eval_in_process(restore_global_mesh, model_args):
    """(2,2,2) task end-to-end in-process: kernels 2-D sharded, donated train
    steps run, eval finite, and loss tracks the fsdp-only task closely (fp
    reduction-order noise only — constraints change layout, not math)."""
    from timm_tpu.parallel import set_global_mesh
    mesh = _tp_mesh()
    set_global_mesh(mesh)
    task = _make_task(mesh, opt='adamw', model_args=model_args)
    qkv = nnx.state(task.model, nnx.Param)['blocks'][0]['attn']['qkv']['kernel'].value
    assert 'model' in tuple(qkv.sharding.spec) and 'fsdp' in tuple(qkv.sharding.spec)
    batch = _batch(mesh)
    losses_tp = [float(task.train_step(batch, lr=1e-3, step=i + 1)['loss']) for i in range(2)]
    out = task.eval_step({'input': batch['input']})
    assert np.isfinite(np.asarray(out)).all()

    set_global_mesh(_fsdp_mesh(4))
    task_f = _make_task(_fsdp_mesh(4), opt='adamw', model_args=model_args)
    batch_f = _batch(_fsdp_mesh(4))
    losses_f = [float(task_f.train_step(batch_f, lr=1e-3, step=i + 1)['loss']) for i in range(2)]
    # step 1 runs on identical params: pure forward reduction-order noise.
    # step 2 runs after one AdamW update, which amplifies that noise — the
    # tight ≤1e-5 parity acceptance lives in the 8-device subprocess drill.
    np.testing.assert_allclose(losses_tp[0], losses_f[0], atol=1e-4)
    np.testing.assert_allclose(losses_tp[1], losses_f[1], rtol=5e-2)


@pytest.mark.slow
def test_tp_8device_parity_and_cross_mesh_checkpoint(tmp_path):
    """Acceptance drill: ('data','fsdp','model')=(2,2,2) golden-fixture train
    matches single-device params ≤1e-5 after 3 updates, the qkv/proj/fc1/fc2
    kernels are verifiably (fsdp x model)-sharded, the durable checkpoint's
    sidecar is mesh-shape-agnostic, and a fresh 1-device process loads + evals
    it within fp reduction-order noise.

    `-m slow` since the autotune PR (tier-1 headroom): two cold subprocesses
    cost ~146 s — the single most expensive tier-1 item — while every
    property except the 1-device process boundary is covered in-process by
    `test_tp_task_train_eval_in_process` (loose train parity vs fsdp) and
    `test_tp_cross_mesh_checkpoint_in_process` (sharded-save manifest
    stability + cross-mesh-shape reload, below). The process-boundary +
    1-device reload acceptance for the SAME save/load code path stays in
    tier-1 via the fsdp drill above."""
    res = _run_drill('parity_tp', tmp_path, devices=8)
    assert res['devices'] == 8 and res['mesh'] == [2, 2, 2]
    assert res['max_param_diff'] <= 1e-5, res
    assert res['max_ema_diff'] <= 1e-5, res
    assert res['tp_sharded'] and all(res['tp_sharded'].values()), res
    assert res['manifest_matches_unsharded'], res

    res1 = _run_drill('load1_tp', tmp_path, devices=1)
    assert res1['devices'] == 1
    assert res1['verified'] and res1['loaded'], res1
    assert res1['eval_matches_saved_logits'] <= 1e-5, res1


def test_tp_cross_mesh_checkpoint_in_process(restore_global_mesh, tmp_path):
    """In-process twin of the `-m slow` tp subprocess drill: the durable
    checkpoint written with raw (fsdp x model)-sharded param leaves hashes
    identically to a host-array save (the gather-to-host path is manifest-
    stable), verifies, and loads into a task on a DIFFERENT mesh shape
    ((2,4) fsdp-only, same 8 devices) with bit-exact params and eval logits
    matching within fp reduction-order noise.

    Runs the usual img_size=32 again: the 5-token (2,2,2)-mesh eval
    divergence that forced this twin onto img_size=64 was bisected to an
    XLA:CPU SPMD miscompile of the constrained-residual + megatron-MLP add
    at tiny token extents, and `shard_activation` now skips constraints
    below its observed-safe floor (constraints._MIN_TOKENS) — see
    test_tp_tiny_geometry_eval_parity below and the PERF.md note."""
    from jax.tree_util import tree_flatten_with_path
    from timm_tpu.parallel import set_global_mesh
    from timm_tpu.parallel.sharding import _kp_str
    from timm_tpu.resilience import load_with_fallback
    from timm_tpu.resilience.durable import atomic_write_npz, read_manifest, verify_checkpoint
    from timm_tpu.utils.serialization import flatten_pytree

    def _task32(mesh):
        model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
        opt = create_optimizer_v2(model, opt='adamw', lr=0.1)
        return ClassificationTask(model, optimizer=opt, mesh=mesh,
                                  train_loss_fn=LabelSmoothingCrossEntropy(0.1))

    def _batch32(mesh):
        rng = np.random.RandomState(0)
        return shard_batch(
            {'input': jnp.asarray(rng.rand(16, 32, 32, 3), jnp.float32),
             'target': jnp.asarray(rng.randint(0, 10, 16))}, mesh)

    mesh = _tp_mesh()
    set_global_mesh(mesh)
    task = _task32(mesh)
    batch = _batch32(mesh)
    task.train_step(batch, lr=1e-3, step=1)
    logits_tp = np.asarray(task.eval_step({'input': batch['input']}))

    # durable save with raw 2-D-sharded leaves, exactly like the drill: the
    # gathered sidecar must equal the one a pure-host save produces
    state = task.get_checkpoint_state()
    raw = dict(state)
    for kp, leaf in tree_flatten_with_path(nnx.state(task.model, nnx.Param))[0]:
        raw['state_dict.' + _kp_str(kp)] = leaf.value if hasattr(leaf, 'value') else leaf
    ckpt = str(tmp_path / 'ckpt_tp.npz')
    atomic_write_npz(ckpt, raw, meta={'epoch': 0, 'mesh': '2x2x2'})
    host = str(tmp_path / 'ckpt_host.npz')
    atomic_write_npz(host, {k: np.asarray(v) for k, v in raw.items()}, meta={'epoch': 0})
    assert {k: v['sha256'] for k, v in read_manifest(ckpt)['arrays'].items()} == \
        {k: v['sha256'] for k, v in read_manifest(host)['arrays'].items()}
    ok, reason = verify_checkpoint(ckpt)
    assert ok, reason

    mesh_f = _fsdp_mesh(4)
    set_global_mesh(mesh_f)
    task_f = _task32(mesh_f)
    loaded, _meta, used = load_with_fallback(ckpt)
    assert used == ckpt
    task_f.load_checkpoint_state(loaded)
    a = {k: np.asarray(v) for k, v in flatten_pytree(nnx.state(task.model, nnx.Param)).items()}
    b = {k: np.asarray(v) for k, v in flatten_pytree(nnx.state(task_f.model, nnx.Param)).items()}
    assert a.keys() == b.keys()
    assert max(float(np.abs(a[k] - b[k]).max()) for k in a) == 0.0
    logits_f = np.asarray(task_f.eval_step({'input': _batch32(mesh_f)['input']}))
    np.testing.assert_allclose(logits_f, logits_tp, atol=1e-5)


def test_tp_tiny_geometry_eval_parity(restore_global_mesh):
    """Regression for the PERF.md tiny-geometry tp divergence: the jitted
    (2,2,2)-mesh eval of test_vit@32 (5 tokens) now matches the eager model
    to fp noise, because `shard_activation` skips its constraints below the
    observed-safe token floor. Before the guard this diverged ~6e-2 (an
    XLA:CPU SPMD miscompile of the constrained residual + megatron-sharded
    MLP add, corrupting the interior batch shards' patch tokens)."""
    from timm_tpu.parallel import build_param_shardings, set_global_mesh
    from timm_tpu.parallel.constraints import _MIN_TOKENS, shard_activation

    mesh = _tp_mesh()
    set_global_mesh(mesh)
    model = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    model.eval()
    graphdef, state = nnx.split(model)
    sharded = jax.device_put(state, build_param_shardings(state, mesh))
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.rand(8, 32, 32, 3), jnp.float32)

    def fwd(s, xx):
        return nnx.merge(graphdef, s)(xx)

    eager = fwd(state, x)
    jitted = jax.jit(fwd)(sharded, shard_batch(x, mesh))
    np.testing.assert_allclose(np.asarray(eager), np.asarray(jitted), atol=1e-5)

    # the guard itself: below the floor the constraint is an identity even
    # inside jit; at/above the floor it still pins the tp layout
    tiny = jnp.zeros((8, _MIN_TOKENS - 1, 64))
    big = jnp.zeros((8, _MIN_TOKENS, 64))
    jaxpr_tiny = jax.make_jaxpr(lambda t: shard_activation(t, 'residual'))(tiny)
    jaxpr_big = jax.make_jaxpr(lambda t: shard_activation(t, 'residual'))(big)
    assert 'sharding_constraint' not in str(jaxpr_tiny)
    assert 'sharding_constraint' in str(jaxpr_big)
