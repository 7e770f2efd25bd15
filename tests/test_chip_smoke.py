"""chip_smoke.py rehearsed on the CPU, and the guards that keep it passing.

The script itself has no CPU branch: these tests steer it by calling its phase
functions with `test_vit` and small sizes under the platform conftest forces
(rehearsals 1 and 2 of the on-chip-measurement guide). Rehearsal 3 — compiling
for a described, unattached v5e chip — guards every registered Pallas kernel
at its declared live shapes.
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

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO_ROOT)

import chip_smoke  # noqa: E402
import timm_tpu  # noqa: E402
from timm_tpu.kernels import harness  # noqa: E402
from timm_tpu.utils.compile_cache import CHECKOUT_CACHE_DIR  # noqa: E402


@pytest.fixture(autouse=True)
def _leave_no_global_state():
    """train.main and the sharded phase install their own global mesh and
    root-logger handlers, as the entry scripts do; a test must hand the next
    file the process it found."""
    import logging

    from timm_tpu.parallel import mesh as mesh_mod
    saved_mesh, saved_handlers, saved_level = (
        mesh_mod.peek_global_mesh(), list(logging.root.handlers), logging.root.level)
    yield
    mesh_mod._GLOBAL_MESH = saved_mesh
    logging.root.handlers[:] = saved_handlers
    logging.root.setLevel(saved_level)


def _phase_line(capsys, phase):
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines() if l.startswith('{"phase"')]
    assert [l['phase'] for l in lines] == [phase]
    return lines[0]


# ---- (a) the script, rehearsed end to end at a tiny size ---------------------

def test_rehearse_train_phase(tmp_path, capsys):
    """train.main in-process on synthetic batches, then on the seeded JPEG
    folder through the device-augment loader — the phase's own checks (finite
    losses, ln(C) start, params/EMA/RNG carried, no compile after step 1)
    are live."""
    chip_smoke.phase_train('test_vit', batch_size=8, synthetic_steps=3, loader_steps=3,
                           out_dir=str(tmp_path), extra_args=('--num-classes', '10', '--workers', '2'))
    line = _phase_line(capsys, 'train')
    seen = line['checked']
    assert seen['synthetic']['steps'] == 3 and seen['loader']['steps'] == 3
    assert seen['synthetic']['compiles_after_step_1'] == seen['loader']['compiles_after_step_1'] == 0
    assert seen['synthetic']['step_program']['compile_requests'] >= 1  # the first call built it
    assert seen['loader']['batch_donate_argnums'] == []  # CPU; (0,) is asserted on the chip
    assert line['compile_cache']['dir'] == jax.config.jax_compilation_cache_dir
    assert line['compile_cache']['compile_requests'] > 0
    assert not os.path.exists(tmp_path / 'train'), 'the smoke keeps no checkpoints'


def test_rehearse_serve_phase(capsys):
    chip_smoke.phase_serve('test_vit', buckets=(2, 8), n_requests=12)
    seen = _phase_line(capsys, 'serve')['checked']
    assert seen['requests'] >= 12 and seen['compiles_after_prewarm'] == 0
    assert set(seen['steps_by_bucket']) == {'2', '8'}
    assert seen['block_scan_length'] == 2
    assert seen['max_abs_diff'] <= 1e-5 * seen['max_abs_logit']  # float32 on the CPU


def test_serve_phase_fails_when_scan_falls_back():
    """A block stack that cannot scan (one block) runs the loop inside the
    model with a log line; the caller that requires scan sees a failure."""
    with pytest.raises(chip_smoke.SmokeFailure, match='block scan fell back'):
        chip_smoke.phase_serve('test_vit', buckets=(2,), n_requests=2, model_kwargs={'depth': 1})


def test_rehearse_kernels_phase(capsys):
    chip_smoke.phase_kernels(live=False)
    seen = _phase_line(capsys, 'kernels')['checked']
    assert sorted(seen['kernels']) == ['causal_flash_attention', 'flash_attention']
    assert not any(k['tpu_custom_call'] for k in seen['kernels'].values())  # interpreted here


def test_unsteered_script_fails_without_a_tpu():
    """`python chip_smoke.py` as the driver runs it: on a machine whose JAX
    finds no TPU it exits non-zero after the device phase and prints no
    result line."""
    env = dict(os.environ, JAX_PLATFORMS='cpu')
    r = subprocess.run([sys.executable, os.path.join(REPO_ROOT, 'chip_smoke.py')],
                       env=env, capture_output=True, text=True, timeout=120)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout and '"phase"' not in r.stdout
    assert 'need "tpu"' in r.stderr


# ---- (b) every registered kernel compiles for the described chip -------------

# `v5e_devices` / `v5e_chip`: tests/conftest.py (a described, not attached, v5e:2x2)

@pytest.mark.kernels
@pytest.mark.parametrize('spec,case', harness.parity_cases(),
                         ids=[f'{s.name}-{c.name}' for s, c in harness.parity_cases()])
def test_kernel_live_case_compiles_for_v5e(spec, case, v5e_chip, monkeypatch):
    """Interpret mode cannot see what the TPU compiler refuses (block shapes,
    casts, memory spaces). The kernels pick interpret mode from
    jax.default_backend(); the test, not the program, says 'tpu' here."""
    from timm_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    monkeypatch.setattr(mesh_mod, '_GLOBAL_MESH', None)   # one described chip: not the 8 CPU devices an earlier file's mesh may span
    shapes = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=v5e_chip),
        jax.eval_shape(lambda: spec.make_inputs(seed=0, **case.live)))
    compiled = harness._jit_arm(spec.kernel_fn, case.statics).lower(shapes).compile()
    assert 'tpu_custom_call' in compiled.as_text()


@pytest.mark.kernels
def test_attention_pair_compiles_for_four_v5e_chips_under_a_data_mesh_without_a_collective(v5e_devices, monkeypatch):
    """ViT-B's shape, batch 128 over a ('data',) mesh of the 2x2 host: forward and backward kernels under
    `shard_map`, a device its 32 images, and nothing gathered or reduced between the devices."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec
    from timm_tpu.kernels import packed_attention
    from timm_tpu.parallel import mesh as mesh_mod
    monkeypatch.setattr(jax, 'default_backend', lambda: 'tpu')
    mesh = Mesh(np.array(v5e_devices).reshape(4), ('data',))
    monkeypatch.setattr(mesh_mod, '_GLOBAL_MESH', mesh)
    qkv = jax.ShapeDtypeStruct((128, 197, 3 * 12 * 64), jnp.bfloat16, sharding=NamedSharding(mesh, PartitionSpec('data')))
    grad = jax.grad(lambda x: packed_attention(x, 12).astype(jnp.float32).sum())
    text = jax.jit(grad).lower(qkv).compile().as_text()
    assert text.count('tpu_custom_call') == 2
    assert not any(op in text for op in ('all-gather(', 'all-reduce(', 'all-to-all(', 'collective-permute('))


# ---- (c) where the compile cache lives ---------------------------------------

_PLACEMENT_PROBE = r'''
import jax
updated = []
real_update = jax.config.update
jax.config.update = lambda name, value: (updated.append(name), real_update(name, value))[1]
from timm_tpu.utils.compile_cache import configure_compile_cache
print('DIR', configure_compile_cache())
print('SET_IN_CODE', 'jax_compilation_cache_dir' in updated)
'''


def _placement(cwd, cache_env=None):
    env = dict(os.environ, JAX_PLATFORMS='cpu', PYTHONPATH=REPO_ROOT)
    env.pop('JAX_COMPILATION_CACHE_DIR', None)
    if cache_env:
        env['JAX_COMPILATION_CACHE_DIR'] = cache_env
    r = subprocess.run([sys.executable, '-c', _PLACEMENT_PROBE], env=env, cwd=cwd,
                       capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, r.stderr[-2000:]
    out = dict(l.split(' ', 1) for l in r.stdout.splitlines() if l.startswith(('DIR', 'SET_IN_CODE')))
    return out['DIR'], out['SET_IN_CODE'] == 'True'


@pytest.mark.compilecache
def test_cache_dir_from_jax_env_is_honoured_and_not_set_in_code(tmp_path):
    where = str(tmp_path / 'placed_from_outside')
    assert _placement(str(tmp_path), cache_env=where) == (where, False)


@pytest.mark.compilecache
def test_cache_dir_default_is_fixed_inside_the_checkout(tmp_path):
    """Unset: one directory resolved from the package's location, the same
    from any working directory (the path is part of the cache key)."""
    assert _placement(str(tmp_path)) == (CHECKOUT_CACHE_DIR, True)
    assert _placement(REPO_ROOT) == (CHECKOUT_CACHE_DIR, True)
    assert os.path.dirname(CHECKOUT_CACHE_DIR) == REPO_ROOT


@pytest.mark.compilecache
def test_one_call_site_sets_the_cache_dir_and_no_private_names_remain():
    hits, stale = [], []
    for root, dirs, files in os.walk(REPO_ROOT):
        dirs[:] = [d for d in dirs if not d.startswith('.') and d not in ('tests', 'output', 'chiprun_out', '__pycache__')]
        for name in files:
            if not name.endswith(('.py', '.sh')):
                continue
            path = os.path.join(root, name)
            with open(path) as f:
                for n, text in enumerate(f, 1):
                    if 'jax_compilation_cache_dir' in text and 'config.update' in text:
                        hits.append(f'{os.path.relpath(path, REPO_ROOT)}:{n}')
                    if 'TIMM_TPU_COMPILE_CACHE' in text or 'TIMM_TPU_XLA_CACHE' in text:
                        stale.append(f'{os.path.relpath(path, REPO_ROOT)}:{n}')
    assert [h.split(':')[0] for h in hits] == ['timm_tpu/utils/compile_cache.py'], hits
    assert not stale, stale


# ---- (d) nested scan + value_and_grad over mutable model state ---------------

@pytest.mark.parametrize('model_name,kwargs,kind', [
    ('test_vit', dict(drop_path_rate=0.1), nnx.RngCount),
    ('test_resnet', {}, nnx.BatchStat),
], ids=['droppath', 'batchnorm'])
def test_two_accumulated_train_steps_carry_mutable_state(model_name, kwargs, kind, mesh8):
    """RNG counters (DropPath) and batch statistics (BatchNorm) are mutated
    under value_and_grad inside the grad-accumulation scan inside jit: under
    flax 0.12 that needs fresh Variables per merge (nnx.merge(copy=True))."""
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.parallel import create_mesh, shard_batch
    from timm_tpu.task import ClassificationTask
    mesh = create_mesh(devices=jax.devices()[:1])
    model = timm_tpu.create_model(model_name, num_classes=10, **kwargs)
    task = ClassificationTask(model, optimizer=create_optimizer_v2(model, opt='sgd', lr=0.1),
                              mesh=mesh, grad_accum_steps=2)
    before = [np.array(x) for x in jax.tree.leaves(nnx.state(model, kind))]
    rng = np.random.RandomState(0)
    size = 160 if model_name == 'test_vit' else 64
    for step in range(2):
        batch = shard_batch({'input': jnp.asarray(rng.rand(4, size, size, 3), jnp.float32),
                             'target': jnp.asarray(rng.randint(0, 10, 4))}, mesh)
        assert np.isfinite(float(task.train_step(batch, lr=0.1, step=step)['loss']))
    after = [np.array(x) for x in jax.tree.leaves(nnx.state(model, kind))]
    assert before and any(not np.array_equal(a, b) for a, b in zip(before, after))
    # the state the first step hands back has the types of the state it was
    # given (sentinel counters included): the second step re-uses its program
    assert task._train_step._cache_size() == 1


# ---- (e) the --chips 4 form on four virtual CPU devices ----------------------

def test_rehearse_sharded_step_on_four_virtual_devices(capsys):
    chip_smoke.phase_sharded('vit_tiny_patch16_224', batch_size=8, devices=jax.devices()[:4],
                             model_kwargs={'img_size': 64, 'num_classes': 10, 'depth': 2})
    seen = _phase_line(capsys, 'sharded_step')['checked']
    assert seen['mesh'] == {'data': 1, 'fsdp': 2, 'model': 2}
    assert seen['sharded_param_leaves'] > 0
    assert seen['param_mb_per_device'] < 0.5 * seen['param_mb_replicated']
