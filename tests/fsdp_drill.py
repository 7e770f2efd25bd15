"""Subprocess drill for the FSDP acceptance tests (tests/test_sharding.py).

Run with XLA_FLAGS=--xla_force_host_platform_device_count=N (the parent test
sets it). Modes:

  parity8 <dir>  — 8 virtual CPU devices: train the tiny ViT (seeded input) 3
                   steps under a ('data','fsdp')=(2,4) mesh AND on a single
                   device; assert param/EMA parity ≤1e-6; durably save the
                   sharded task's checkpoint twice (raw sharded jax arrays vs
                   pre-gathered host arrays) and prove the SHA-256 sidecars
                   are byte-identical.
  load1 <dir>    — 1 device: verify the 8-device checkpoint, load it into a
                   single-device task, compare eval logits against the ones
                   the sharded task recorded, and re-save to prove the
                   manifest is stable across a save→load→save round trip.
  parity_tp <dir> — 8 virtual CPU devices: same tiny-ViT train under a
                   full ('data','fsdp','model')=(2,2,2) mesh (tensor
                   parallelism + activation sharding constraints) vs a single
                   device; assert parity, assert the attention/MLP kernels
                   are ACTUALLY sharded over 'model' (NamedSharding specs),
                   and durably save the 2-D-sharded checkpoint.
  load1_tp <dir> — 1 device: verify + load the (2,2,2) checkpoint and eval —
                   the save is mesh-shape-agnostic.
  elastic8to4 <dir> — elastic resume drill: an 8-device ('data','fsdp')=(2,4)
                   train.py run is resize-faulted (`resize@3:4` → SIGTERM)
                   mid-epoch, then restarted as a FRESH 4-device process with
                   `--resume auto --elastic`; the planner holds the global
                   batch constant, the mesh rebuilds as (1,4), and final
                   params/optimizer state must match an uninterrupted run to
                   ≤1e-6. Spawns 3 train.py subprocesses with XLA_FLAGS
                   overridden per topology.
  elastic4to8 <dir> — same drill scaling UP from 4 to 8 devices.

Prints one JSON line with the results; exit 0 on success.
"""
import json
import os
import sys

os.environ.setdefault('XLA_FLAGS', '--xla_force_host_platform_device_count=8')

import jax

try:
    jax.config.update('jax_platforms', 'cpu')
    jax.config.update('jax_num_cpu_devices', int(os.environ.get('TIMM_TPU_DRILL_DEVICES', '8')))
except Exception:
    pass

import jax.numpy as jnp
import numpy as np
from flax import nnx

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import timm_tpu  # noqa: E402
from seeded_vit import seeded_input  # noqa: E402
from timm_tpu.loss import LabelSmoothingCrossEntropy  # noqa: E402
from timm_tpu.optim import create_optimizer_v2  # noqa: E402
from timm_tpu.parallel import create_mesh, shard_batch  # noqa: E402
from timm_tpu.resilience import load_with_fallback  # noqa: E402
from timm_tpu.resilience.durable import atomic_write_npz, read_manifest, verify_checkpoint  # noqa: E402
from timm_tpu.task import ClassificationTask  # noqa: E402
from timm_tpu.utils import configure_compile_cache  # noqa: E402
from timm_tpu.utils.serialization import flatten_pytree  # noqa: E402

configure_compile_cache()

MODEL, IMG, CLASSES = 'vit_tiny_patch16_224', 64, 1000
STEPS, BATCH = 3, 8


def seeded_batch(mesh):
    x = np.tile(seeded_input(), (BATCH // 2, 1, 1, 1))
    t = np.random.RandomState(0).randint(0, CLASSES, BATCH)
    return shard_batch({'input': jnp.asarray(x), 'target': jnp.asarray(t)}, mesh)


def make_task(mesh):
    model = timm_tpu.create_model(MODEL, img_size=IMG)
    # block_scan composes with fsdp sharding + scanned accumulation (PR 4);
    # it also keeps the drill's compile cost O(1) in depth
    model.set_block_scan(True)
    opt = create_optimizer_v2(model, opt='sgd', lr=0.05, momentum=0.9)
    task = ClassificationTask(model, optimizer=opt, mesh=mesh,
                              train_loss_fn=LabelSmoothingCrossEntropy(0.1))
    task.setup_ema(decay=0.9)
    return task


def train(task, mesh):
    batch = seeded_batch(mesh)
    for i in range(STEPS):
        metrics = task.train_step(batch, lr=0.05, step=i + 1)
    assert np.isfinite(float(metrics['loss'])), metrics
    return task


def host_params(task):
    return {k: np.asarray(v) for k, v in flatten_pytree(nnx.state(task.model, nnx.Param)).items()}


def max_diff(a, b):
    assert set(a) == set(b)
    return max(float(np.abs(a[k] - b[k]).max()) for k in a)


def parity8(workdir):
    assert len(jax.devices()) == 8, jax.devices()
    mesh_fsdp = create_mesh(fsdp=4)
    task_f = train(make_task(mesh_fsdp), mesh_fsdp)

    mesh_1 = create_mesh(devices=jax.devices()[:1])
    task_1 = train(make_task(mesh_1), mesh_1)

    p_diff = max_diff(host_params(task_f), host_params(task_1))
    e_diff = max_diff({k: np.asarray(v) for k, v in flatten_pytree(task_f.ema_params).items()},
                      {k: np.asarray(v) for k, v in flatten_pytree(task_1.ema_params).items()})

    # eval logits recorded for the cross-mesh reload drill
    batch = seeded_batch(mesh_fsdp)
    logits = np.asarray(task_f.eval_step({'input': batch['input']}))
    np.save(os.path.join(workdir, 'logits_fsdp.npy'), logits)

    # durable save #1: the full checkpoint schema, with the PARAM leaves left
    # as raw fsdp-sharded jax.Arrays — exercising durable._gather_to_host
    state = task_f.get_checkpoint_state()
    raw = dict(state)
    from jax.tree_util import tree_flatten_with_path
    from timm_tpu.parallel.sharding import _kp_str
    for kp, leaf in tree_flatten_with_path(nnx.state(task_f.model, nnx.Param))[0]:
        raw['state_dict.' + _kp_str(kp)] = leaf  # sharded jax.Array, NOT gathered
    ckpt_f = os.path.join(workdir, 'ckpt_fsdp.npz')
    atomic_write_npz(ckpt_f, raw, meta={'epoch': 0, 'mesh': '2x4'})
    # durable save #2: same content pre-gathered to host — the sidecars must
    # be byte-identical or checkpoint hashes would depend on the mesh shape
    ckpt_h = os.path.join(workdir, 'ckpt_host.npz')
    atomic_write_npz(ckpt_h, {k: np.asarray(v) for k, v in raw.items()}, meta={'epoch': 0})
    mf, mh = read_manifest(ckpt_f), read_manifest(ckpt_h)
    same = {k: v['sha256'] for k, v in mf['arrays'].items()} == \
           {k: v['sha256'] for k, v in mh['arrays'].items()}

    print(json.dumps({
        'devices': len(jax.devices()),
        'mesh': [int(mesh_fsdp.shape['data']), int(mesh_fsdp.shape['fsdp'])],
        'max_param_diff': p_diff,
        'max_ema_diff': e_diff,
        'manifest_matches_unsharded': bool(same),
    }))


def load1(workdir):
    assert len(jax.devices()) == 1, jax.devices()
    ckpt = os.path.join(workdir, 'ckpt_fsdp.npz')
    ok, reason = verify_checkpoint(ckpt)
    state, meta, used = load_with_fallback(ckpt)
    mesh = create_mesh()
    task = make_task(mesh)
    task.load_checkpoint_state(state)
    x = np.tile(seeded_input(), (BATCH // 2, 1, 1, 1))
    logits = np.asarray(task.eval_step({'input': shard_batch(jnp.asarray(x), mesh)}))
    saved = np.load(os.path.join(workdir, 'logits_fsdp.npy'))
    eval_diff = float(np.abs(logits - saved).max())

    resaved = os.path.join(workdir, 'ckpt_resaved.npz')
    atomic_write_npz(resaved, {k: np.asarray(v) for k, v in state.items()}, meta={'epoch': 0})
    m0, m1 = read_manifest(ckpt), read_manifest(resaved)
    stable = {k: v['sha256'] for k, v in m0['arrays'].items()} == \
             {k: v['sha256'] for k, v in m1['arrays'].items()}

    print(json.dumps({
        'devices': len(jax.devices()),
        'verified': bool(ok), 'verify_reason': reason,
        'loaded': used == ckpt,
        'eval_matches_saved_logits': eval_diff,
        'resave_manifest_matches': bool(stable),
    }))


def parity_tp(workdir):
    assert len(jax.devices()) == 8, jax.devices()
    from timm_tpu.parallel import set_global_mesh
    mesh_tp = create_mesh(fsdp=2, tp=2)
    assert mesh_tp.axis_names == ('data', 'fsdp', 'model'), mesh_tp
    # the activation constraints inside the model read the GLOBAL mesh
    set_global_mesh(mesh_tp)
    task_t = train(make_task(mesh_tp), mesh_tp)

    # acceptance: qkv / proj / fc1 / fc2 kernels really carry 'model' in
    # their NamedSharding (not just a rule-table claim)
    blk = nnx.state(task_t.model, nnx.Param)['blocks'][0]
    tp_sharded = {}
    for mod, name in (('attn', 'qkv'), ('attn', 'proj'), ('mlp', 'fc1'), ('mlp', 'fc2')):
        spec = blk[mod][name]['kernel'].value.sharding.spec
        tp_sharded[f'{mod}.{name}'] = 'model' in tuple(spec) and 'fsdp' in tuple(spec)

    mesh_1 = create_mesh(devices=jax.devices()[:1])
    set_global_mesh(mesh_1)
    task_1 = train(make_task(mesh_1), mesh_1)

    p_diff = max_diff(host_params(task_t), host_params(task_1))
    e_diff = max_diff({k: np.asarray(v) for k, v in flatten_pytree(task_t.ema_params).items()},
                      {k: np.asarray(v) for k, v in flatten_pytree(task_1.ema_params).items()})

    set_global_mesh(mesh_tp)
    batch = seeded_batch(mesh_tp)
    logits = np.asarray(task_t.eval_step({'input': batch['input']}))
    np.save(os.path.join(workdir, 'logits_tp.npy'), logits)

    # durable save with raw 2-D-sharded (fsdp x model) param leaves: the
    # gather-to-host path must produce the same sidecar a host save does
    state = task_t.get_checkpoint_state()
    raw = dict(state)
    from jax.tree_util import tree_flatten_with_path
    from timm_tpu.parallel.sharding import _kp_str
    for kp, leaf in tree_flatten_with_path(nnx.state(task_t.model, nnx.Param))[0]:
        raw['state_dict.' + _kp_str(kp)] = leaf  # sharded jax.Array, NOT gathered
    ckpt_t = os.path.join(workdir, 'ckpt_tp.npz')
    atomic_write_npz(ckpt_t, raw, meta={'epoch': 0, 'mesh': '2x2x2'})
    ckpt_h = os.path.join(workdir, 'ckpt_tp_host.npz')
    atomic_write_npz(ckpt_h, {k: np.asarray(v) for k, v in raw.items()}, meta={'epoch': 0})
    mf, mh = read_manifest(ckpt_t), read_manifest(ckpt_h)
    same = {k: v['sha256'] for k, v in mf['arrays'].items()} == \
           {k: v['sha256'] for k, v in mh['arrays'].items()}

    print(json.dumps({
        'devices': len(jax.devices()),
        'mesh': [int(mesh_tp.shape[a]) for a in mesh_tp.axis_names],
        'max_param_diff': p_diff,
        'max_ema_diff': e_diff,
        'tp_sharded': tp_sharded,
        'manifest_matches_unsharded': bool(same),
    }))


def load1_tp(workdir):
    assert len(jax.devices()) == 1, jax.devices()
    ckpt = os.path.join(workdir, 'ckpt_tp.npz')
    ok, reason = verify_checkpoint(ckpt)
    state, meta, used = load_with_fallback(ckpt)
    mesh = create_mesh()
    task = make_task(mesh)
    task.load_checkpoint_state(state)
    x = np.tile(seeded_input(), (BATCH // 2, 1, 1, 1))
    logits = np.asarray(task.eval_step({'input': shard_batch(jnp.asarray(x), mesh)}))
    saved = np.load(os.path.join(workdir, 'logits_tp.npy'))
    print(json.dumps({
        'devices': len(jax.devices()),
        'verified': bool(ok), 'verify_reason': reason,
        'loaded': used == ckpt,
        'eval_matches_saved_logits': float(np.abs(logits - saved).max()),
    }))


def serve8(workdir):
    """Sharded serving: an InferenceEngine on an 8-device ('data','fsdp')
    mesh loads the SAME mesh-shape-agnostic checkpoint as a single-device
    engine and must produce identical logits (≤1e-5) for identical requests —
    the serving tier can scale out without touching the checkpoint format."""
    assert len(jax.devices()) == 8, jax.devices()
    from timm_tpu.models import model_state_dict, save_state_dict
    from timm_tpu.serve import InferenceEngine

    serve_model, img = 'test_vit', 32
    ckpt = os.path.join(workdir, 'serve_ckpt.npz')
    save_state_dict(model_state_dict(timm_tpu.create_model(serve_model, img_size=img)), ckpt)

    rng = np.random.RandomState(0)
    imgs = rng.standard_normal((8, img, img, 3)).astype(np.float32)

    def engine_logits(mesh):
        # bucket 8 divides every mesh shard count used here (1 and 8); a long
        # admission wait means all 8 requests coalesce into ONE device step
        eng = InferenceEngine(buckets=(8,), max_wait_ms=2000.0, mesh=mesh)
        eng.add_model(serve_model, checkpoint=ckpt, img_size=img)
        eng.start()
        try:
            futs = [eng.submit(im) for im in imgs]
            rows = np.stack([f.result(timeout=300.0) for f in futs])
        finally:
            eng.shutdown(drain=True)
        return rows, eng

    logits_1, _ = engine_logits(None)  # engine default: single-device mesh
    mesh_fsdp = create_mesh(fsdp=4)
    logits_8, eng8 = engine_logits(mesh_fsdp)

    # the 8-device engine really sharded the weights over 'fsdp'
    res = eng8.pool.acquire(serve_model)
    param_sharded = any(
        'fsdp' in tuple(getattr(getattr(l, 'sharding', None), 'spec', ()) or ())
        for l in jax.tree.leaves(res.state))

    diff = float(np.abs(logits_8 - logits_1).max())
    print(json.dumps({
        'devices': len(jax.devices()),
        'mesh': [int(mesh_fsdp.shape[a]) for a in mesh_fsdp.axis_names],
        'buckets': [8],
        'param_sharded_over_fsdp': bool(param_sharded),
        'steps_by_bucket': eng8.snapshot_stats()['steps_by_bucket'],
        'logits_max_diff': diff,
    }))
    assert diff <= 1e-5, f'sharded serving logits diverged: {diff}'


def quant_save8(workdir):
    """Weight-only int8 under a real ('data','fsdp') mesh: the quantized
    pytree places via build_quant_shardings (scales riding their kernels'
    specs), the int8 checkpoint saves mesh-shape-agnostically, and a
    quantized engine on the SAME mesh serves from it — logits recorded for
    the 1-device reload drill."""
    assert len(jax.devices()) == 8, jax.devices()
    from timm_tpu.parallel import build_quant_shardings, set_global_mesh
    from timm_tpu.quantize import quantize_tree, quantized_paths, save_quantized, tree_bytes
    from timm_tpu.serve import InferenceEngine

    serve_model, img = 'test_vit', 32
    mesh = create_mesh(fsdp=4)
    set_global_mesh(mesh)
    model = timm_tpu.create_model(serve_model, img_size=img)
    model.eval()
    _, state = nnx.split(model)
    qstate = quantize_tree(state)
    placed = jax.device_put(qstate, build_quant_shardings(qstate, mesh))
    qvalues_sharded = any(
        'fsdp' in tuple(getattr(getattr(l, 'sharding', None), 'spec', ()) or ())
        for l in jax.tree.leaves(placed['qvalues']))
    ckpt = os.path.join(workdir, 'quant_ckpt.npz')
    save_quantized(placed, ckpt)

    rng = np.random.RandomState(0)
    imgs = rng.standard_normal((8, img, img, 3)).astype(np.float32)
    eng = InferenceEngine(buckets=(8,), max_wait_ms=2000.0, mesh=mesh)
    eng.add_model(serve_model, img_size=img, quantize='int8', quantized_checkpoint=ckpt)
    eng.start()
    try:
        futs = [eng.submit(im) for im in imgs]
        rows = np.stack([f.result(timeout=300.0) for f in futs])
    finally:
        eng.shutdown(drain=True)
    np.save(os.path.join(workdir, 'logits_quant8.npy'), rows)
    res = eng.pool.acquire(serve_model)
    print(json.dumps({
        'devices': len(jax.devices()),
        'mesh': [int(mesh.shape[a]) for a in mesh.axis_names],
        'num_quantized': len(quantized_paths(placed)),
        'qvalues_sharded_over_fsdp': bool(qvalues_sharded),
        'quantize': res.quantize,
        'param_bytes': int(res.param_bytes),
        'dense_bytes': int(tree_bytes(state)),
    }))


def quant_load1(workdir):
    """1 device: the int8 checkpoint saved on 8 devices loads into a
    single-device quantized engine and serves identical logits (the dequant
    math is deterministic; only matmul reduction order can differ)."""
    assert len(jax.devices()) == 1, jax.devices()
    from timm_tpu.serve import InferenceEngine

    serve_model, img = 'test_vit', 32
    ckpt = os.path.join(workdir, 'quant_ckpt.npz')
    rng = np.random.RandomState(0)
    imgs = rng.standard_normal((8, img, img, 3)).astype(np.float32)
    eng = InferenceEngine(buckets=(8,), max_wait_ms=2000.0)
    eng.add_model(serve_model, img_size=img, quantize='int8', quantized_checkpoint=ckpt)
    eng.start()
    try:
        futs = [eng.submit(im) for im in imgs]
        rows = np.stack([f.result(timeout=300.0) for f in futs])
    finally:
        eng.shutdown(drain=True)
    saved = np.load(os.path.join(workdir, 'logits_quant8.npy'))
    diff = float(np.abs(rows - saved).max())
    res = eng.pool.acquire(serve_model)
    print(json.dumps({
        'devices': len(jax.devices()),
        'quantize': res.quantize,
        'param_bytes': int(res.param_bytes),
        'logits_max_diff': diff,
    }))
    assert diff <= 1e-5, f'quantized cross-mesh serving diverged: {diff}'


REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _elastic_train(workdir, experiment, devices, *extra):
    """One train.py child pinned to a virtual CPU topology of `devices`."""
    import subprocess
    cmd = [
        sys.executable, os.path.join(REPO, 'train.py'),
        '--synthetic-data', '--model', 'test_vit', '--img-size', '32', '-b', '8',
        '--synthetic-len', '64', '--epochs', '1', '--opt', 'sgd', '--lr', '0.05',
        '--sched', 'cosine', '--warmup-epochs', '0', '--workers', '1',
        '--log-interval', '50', '--fsdp', '4',
        '--output', str(workdir), '--experiment', experiment, *extra,
    ]
    env = dict(os.environ, JAX_PLATFORMS='cpu',
               XLA_FLAGS=f'--xla_force_host_platform_device_count={devices}')
    return subprocess.run(cmd, capture_output=True, text=True, env=env,
                          cwd=REPO, timeout=420)


def _host_ckpt(path):
    with np.load(path, allow_pickle=False) as d:
        return {k: d[k] for k in d.files if k.startswith(('state_dict.', 'optimizer.'))}


def _elastic(workdir, n_from, n_to):
    """Resize drill: uninterrupted run at n_from devices vs a run resize-
    faulted mid-epoch and resumed as a fresh n_to-device process. `--fsdp 4`
    on every leg (4 divides both topologies: (2,4) on 8 devices, (1,4) on 4)
    and batch geometry 8x1 is held constant so the synthetic loader stream —
    and hence the final state — is reproducible across the resize."""
    r = _elastic_train(workdir, 'base', n_from)
    assert r.returncode == 0, r.stderr[-2000:]
    r = _elastic_train(workdir, 'pre', n_from, '--fault-inject', f'resize@3:{n_to}')
    assert r.returncode == 0, r.stderr[-2000:]
    pre_dir = os.path.join(workdir, 'pre')
    recs = [n for n in os.listdir(pre_dir) if n.startswith('recovery-') and n.endswith('.npz')]
    assert recs, (sorted(os.listdir(pre_dir)), r.stderr[-2000:])
    # the recovery checkpoint advertises the dead run's batch geometry
    with np.load(os.path.join(pre_dir, recs[0])) as d:
        saved_global = int(d['_resume.global_batch'])
        saved_devices = int(d['_resume.device_count'])
    assert saved_global == 8 and saved_devices == n_from, (saved_global, saved_devices)

    r = _elastic_train(workdir, 'pre', n_to, '--resume', 'auto', '--elastic')
    assert r.returncode == 0, r.stderr[-2000:]
    assert 'Resumed mid-epoch' in r.stderr, r.stderr[-2000:]
    assert '[elastic] live topology' in r.stderr, r.stderr[-2000:]

    base = _host_ckpt(os.path.join(workdir, 'base', 'last.npz'))
    resumed = _host_ckpt(os.path.join(pre_dir, 'last.npz'))
    assert set(base) == set(resumed)
    diff = max(float(np.abs(base[k].astype(np.float64) - resumed[k].astype(np.float64)).max())
               for k in base)
    print(json.dumps({
        'from_devices': n_from, 'to_devices': n_to,
        'saved_global_batch': saved_global,
        'max_param_diff': diff,
        'recovery_pruned': not [n for n in os.listdir(pre_dir) if n.startswith('recovery-')],
    }))
    assert diff <= 1e-6, f'elastic resume diverged from uninterrupted run: {diff}'


def elastic8to4(workdir):
    _elastic(workdir, 8, 4)


def elastic4to8(workdir):
    _elastic(workdir, 4, 8)


if __name__ == '__main__':
    mode, workdir = sys.argv[1], sys.argv[2]
    {'parity8': parity8, 'load1': load1, 'parity_tp': parity_tp, 'load1_tp': load1_tp,
     'serve8': serve8, 'quant_save8': quant_save8, 'quant_load1': quant_load1,
     'elastic8to4': elastic8to4, 'elastic4to8': elastic4to8}[mode](workdir)
