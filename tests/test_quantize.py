"""Int8 post-training weight-only quantization + distill-to-serve.

Covers the PR-11 acceptance surface:

  1. quantize→dequantize round-trip error bounds per layer kind (symmetric
     per-output-channel scales bound elementwise error by scale/2), with
     biases / norms / tokens provably NOT quantized;
  2. quantized-vs-fp32 logits tolerance on seeded weights (vit_tiny,
     img 64) — the checked-in constant the quantize-then-validate gate pins;
  3. scale-spec inheritance lint: every quantized kernel's scale resolves to
     its kernel's PartitionSpec last axis (or replicates), the qvalues ride
     the UNCHANGED partition-rule table, and the rule table stays disjoint +
     exhaustive over the quantized pytree's paths;
  4. engine serve parity through a padded bucket, and residency byte
     accounting charging the real int8 footprint (oversized warn reports
     both the int8 and dense numbers);
  5. cross-mesh drill: a quantized checkpoint saved on 8 devices loads and
     serves on 1 (subprocess, like the fsdp parity drills);
  6. distillation smoke: the dormant LogitDistillationTask /
     FeatureDistillationTask run under the functional donated train step.
"""
import json
import logging
import os
import subprocess
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from flax import nnx
from jax.sharding import PartitionSpec as P

import seeded_vit
import timm_tpu
from timm_tpu.parallel import (
    build_quant_shardings, create_mesh, quant_path_specs, quant_scale_spec,
    set_global_mesh, shard_batch,
)
from timm_tpu.parallel.sharding import (
    _kp_str, default_partition_rules, spec_for_param,
)
from timm_tpu.quantize import (
    QUANT_QVALUES, QUANT_SCALES, dequantize_tree, load_quantized,
    quantization_stats, quantize_tree, quantized_paths, save_quantized,
    tree_bytes,
)

pytestmark = pytest.mark.quant

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# measured 0.0206 max-abs on the seeded weights of tests/seeded_vit.py (logit range ~±0.90);
# 0.05 gives headroom for compiler drift while still catching a broken scale
GOLDEN_LOGITS_TOL = 0.05


def _split_eval(name, **kwargs):
    model = timm_tpu.create_model(name, **kwargs)
    model.eval()
    return nnx.split(model)


# ---- 1. core transform -------------------------------------------------------

def test_round_trip_error_bounds_per_layer_kind():
    """Symmetric per-output-channel int8: |w - dequant(q)| <= scale/2
    elementwise for EVERY quantized kernel (absmax maps to exactly ±127, so
    clipping never bites), across attention, MLP, and patch-embed kernels."""
    _, state = _split_eval('test_vit', num_classes=10, img_size=32)
    qstate = quantize_tree(state)
    paths = quantized_paths(qstate)
    # every transformer layer kind is represented
    for kind in ('attn.qkv.kernel', 'attn.proj.kernel',
                 'mlp.fc1.kernel', 'mlp.fc2.kernel', 'patch_embed.proj.kernel'):
        assert any(p.endswith(kind) or kind in p for p in paths), \
            f'no quantized kernel of kind {kind}: {sorted(paths)}'

    flat = {_kp_str(kp): leaf for kp, leaf in
            jax.tree_util.tree_flatten_with_path(state)[0]}
    dense = dequantize_tree(qstate)
    dflat = {_kp_str(kp): leaf for kp, leaf in
             jax.tree_util.tree_flatten_with_path(dense)[0]}
    for path in paths:
        w = np.asarray(flat[path])
        wq = np.asarray(dflat[path])
        scale = np.asarray(qstate[QUANT_SCALES][path])
        bound = scale.reshape((1,) * (w.ndim - 1) + (-1,)) / 2.0
        err = np.abs(w - wq)
        assert (err <= bound + 1e-7).all(), \
            f'{path}: max err {err.max()} exceeds scale/2 bound {bound.max()}'
        assert wq.dtype == w.dtype


def test_biases_norms_tokens_not_quantized():
    _, state = _split_eval('test_vit', num_classes=10, img_size=32)
    qstate = quantize_tree(state)
    paths = quantized_paths(qstate)
    assert all(p.endswith('.kernel') for p in paths)
    for bad in ('bias', 'norm', 'cls_token', 'pos_embed', 'scale'):
        assert not any(bad in p.rsplit('.', 1)[-1] for p in paths)
    # untouched leaves survive bit-exactly with their dtype
    flat_q = {_kp_str(kp): leaf for kp, leaf in
              jax.tree_util.tree_flatten_with_path(qstate[QUANT_QVALUES])[0]}
    flat_s = {_kp_str(kp): leaf for kp, leaf in
              jax.tree_util.tree_flatten_with_path(state)[0]}
    for path, leaf in flat_q.items():
        if path not in paths:
            assert leaf.dtype == flat_s[path].dtype
            assert (np.asarray(leaf) == np.asarray(flat_s[path])).all()
    # the head kernel (64x10 = 640 < MIN_QUANT_SIZE) stays dense
    assert not any('head' in p for p in paths)


def test_quantization_stats_halve_bytes():
    _, state = _split_eval('test_vit', num_classes=10, img_size=32)
    qstate = quantize_tree(state)
    stats = quantization_stats(state, qstate)
    assert stats['num_quantized'] >= 9
    assert stats['bytes_ratio'] <= 0.35, stats
    assert tree_bytes(qstate) == stats['quantized_bytes']


def test_save_load_round_trip(tmp_path):
    _, state = _split_eval('test_vit', num_classes=10, img_size=32)
    qstate = quantize_tree(state)
    path = str(tmp_path / 'q.npz')
    save_quantized(qstate, path)
    loaded = load_quantized(path, state)
    for (kp_a, a), (kp_b, b) in zip(
            jax.tree_util.tree_flatten_with_path(qstate)[0],
            jax.tree_util.tree_flatten_with_path(loaded)[0]):
        assert _kp_str(kp_a) == _kp_str(kp_b)
        assert a.dtype == b.dtype
        assert (np.asarray(a) == np.asarray(b)).all(), _kp_str(kp_a)
    # wrong template (different arch) must refuse, not silently mis-load
    _, other = _split_eval('test_vit3', num_classes=10, img_size=32)
    with pytest.raises((KeyError, ValueError)):
        load_quantized(path, other)


# ---- 2. golden fixture -------------------------------------------------------

def test_golden_fixture_quantized_logits_tolerance():
    """The quantized forward of the seeded tiny ViT stays within the
    checked-in tolerance of the plain reference's float32 logits on the same
    weights — the same bound `validate.py --quantize int8` gates on (top-1
    can only move if logits move; here even the raw logits barely do)."""
    weights = seeded_vit.seeded_weights()
    x = seeded_vit.seeded_input()
    expected = seeded_vit.reference_logits(weights, x)
    gd, state = nnx.split(seeded_vit.build(weights))
    qstate = quantize_tree(state)
    stats = quantization_stats(state, qstate)
    assert stats['bytes_ratio'] <= 0.30, stats
    qlogits = np.asarray(nnx.merge(gd, dequantize_tree(qstate))(jnp.asarray(x)))
    diff = np.abs(qlogits - expected)
    assert diff.max() <= GOLDEN_LOGITS_TOL, \
        f'quantized logits drifted {diff.max():.4f} > {GOLDEN_LOGITS_TOL}'
    assert (qlogits.argmax(-1) == expected.argmax(-1)).all()


# ---- 3. scale-spec inheritance lint ------------------------------------------

def test_scale_specs_inherit_kernel_last_axis():
    """Every quantized kernel's scale resolves to P(kernel_spec[-1]) when the
    kernel's last axis is sharded (so dequant needs NO collective: each shard
    holds exactly the scale rows of its output channels), else P()."""
    mesh = create_mesh(fsdp=2, tp=2)
    _, state = _split_eval('test_vit', num_classes=10, img_size=32)
    qstate = quantize_tree(state)
    specs = quant_path_specs(qstate, mesh)
    rules = default_partition_rules()
    axis_sizes = {a: int(mesh.shape[a]) for a in mesh.axis_names}
    checked_sharded = 0
    for path in quantized_paths(qstate):
        q = {_kp_str(kp): l for kp, l in
             jax.tree_util.tree_flatten_with_path(qstate[QUANT_QVALUES])[0]}[path]
        kernel_spec = spec_for_param(path, q.shape, mesh, rules)
        scale = qstate[QUANT_SCALES][path]
        expect = quant_scale_spec(kernel_spec, scale.shape, mesh)
        got = specs[f'{QUANT_SCALES}.{path}']
        assert got == expect, f'{path}: scale spec {got} != {expect}'
        last = kernel_spec[-1] if len(kernel_spec) else None
        if last is not None:
            axes = (last,) if isinstance(last, str) else tuple(last)
            if scale.shape[0] % int(np.prod([axis_sizes[a] for a in axes])) == 0:
                assert got == P(last), f'{path}: sharded kernel but scale {got}'
                checked_sharded += 1
        # the qvalues spec is the kernel's own rule-table spec, unchanged
        assert specs[f'{QUANT_QVALUES}.{path}'] == kernel_spec
    assert checked_sharded >= 4, 'lint never saw a sharded-last-axis kernel'


def test_rules_disjoint_exhaustive_over_quantized_paths():
    """The rule table needs NO quant-specific entries: flattened qvalue paths
    still end `.kernel` etc., so each matches EXACTLY one non-catch-all rule
    (or the catch-all) exactly like its dense twin."""
    _, state = _split_eval('test_vit', num_classes=10, img_size=32)
    qstate = quantize_tree(state)
    rules = default_partition_rules()
    specific, catchall = rules[:-1], rules[-1]
    assert catchall.pattern == '.*'
    for kp, _ in jax.tree_util.tree_flatten_with_path(qstate[QUANT_QVALUES])[0]:
        path = _kp_str(kp)
        n = sum(1 for r in specific if r.matches(path))
        assert n <= 1, f'{path} matched {n} specific rules'


def test_quant_shardings_place_every_leaf(mesh8):
    """build_quant_shardings covers the WHOLE qstate (qvalues + scales) and
    device_put under it succeeds on the data mesh (all-replicated) — the
    placement path the serve pool uses on every load."""
    _, state = _split_eval('test_vit', num_classes=10, img_size=32)
    qstate = quantize_tree(state)
    placed = jax.device_put(qstate, build_quant_shardings(qstate, mesh8))
    n_leaves = len(jax.tree.leaves(qstate))
    assert len(jax.tree.leaves(placed)) == n_leaves
    for leaf in jax.tree.leaves(placed):
        assert tuple(getattr(leaf.sharding, 'spec', ())) in ((), tuple(P()))


# ---- 4. serve engine + residency accounting ----------------------------------

def test_engine_quantized_serve_parity_through_padded_bucket():
    """5 requests pad into the bucket-8 program; the served logits must match
    a direct dequantized forward <= 1e-5, and the resident entry must be the
    int8 pytree with the int8 byte accounting."""
    from timm_tpu.serve import InferenceEngine

    set_global_mesh(create_mesh())
    eng = InferenceEngine(buckets=(8,), max_wait_ms=1500.0)
    eng.add_model('test_vit', num_classes=10, img_size=32, quantize='int8')
    res = eng.pool.acquire('test_vit')
    assert res.quantize == 'int8'
    dense_bytes = tree_bytes(dequantize_tree(res.state))
    assert res.param_bytes <= 0.35 * dense_bytes

    rng = np.random.RandomState(0)
    imgs = rng.standard_normal((5, 32, 32, 3)).astype(np.float32)
    eng.start()
    try:
        futs = [eng.submit(im, model='test_vit') for im in imgs]
        rows = np.stack([f.result(timeout=120.0) for f in futs])
    finally:
        eng.shutdown(drain=True)
    direct = np.asarray(
        nnx.merge(res.graphdef, dequantize_tree(res.state))(jnp.asarray(imgs)))
    assert np.abs(rows - direct).max() <= 1e-5
    # the padded-bucket program really ran (bucket 8 for 5 requests)
    assert 8 in eng.snapshot_stats()['steps_by_bucket']


def test_residency_budget_sees_int8_footprint(caplog):
    """The LRU budget must charge the ACTUAL loaded pytree's bytes: an int8
    model fits where its fp32 twin cannot, and the oversized warn reports
    both the int8 and the dense number."""
    from timm_tpu.serve.residency import ModelPool, _state_bytes_per_device

    mesh = create_mesh()

    def factory():
        return timm_tpu.create_model('test_vit', num_classes=10, img_size=32)

    m = factory()
    m.eval()
    _, state = nnx.split(m)
    fp32_bytes = _state_bytes_per_device(state, mesh)
    int8_bytes = _state_bytes_per_device(quantize_tree(state), mesh)
    assert int8_bytes <= 0.35 * fp32_bytes

    # budget between the two footprints: int8 loads cleanly...
    pool = ModelPool(mesh, budget_bytes=int(int8_bytes * 1.2))
    pool.register('tv_q', factory, quantize='int8')
    res = pool.acquire('tv_q')
    assert abs(res.param_bytes - int8_bytes) <= 0.02 * int8_bytes
    assert pool.stats['evictions'] == 0

    # ...and a budget below even the int8 footprint warns with BOTH numbers
    pool2 = ModelPool(mesh, budget_bytes=int(int8_bytes * 0.5))
    pool2.register('tv_q', factory, quantize='int8')
    with caplog.at_level(logging.WARNING, logger='timm_tpu.serve.residency'):
        pool2.acquire('tv_q')
    warn = [r.message for r in caplog.records if 'exceeds the HBM budget' in r.message]
    assert warn and 'dense' in warn[0], warn


# ---- 5. cross-mesh drill: quantize on 8 devices, serve on 1 ------------------

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


def test_quantized_checkpoint_saved_on_8_serves_on_1(tmp_path):
    """Acceptance drill: quantize + place on a ('data','fsdp')=(2,4) mesh
    (qvalues really sharded over 'fsdp'), save the int8 checkpoint, then a
    fresh 1-device process loads it into a quantized engine and serves
    logits identical to the 8-device engine's."""
    res8 = _run_drill('quant_save8', tmp_path, devices=8)
    assert res8['devices'] == 8 and res8['mesh'] == [2, 4]
    assert res8['qvalues_sharded_over_fsdp'], res8
    assert res8['quantize'] == 'int8'
    assert res8['num_quantized'] >= 9
    assert os.path.exists(tmp_path / 'quant_ckpt.npz')

    res1 = _run_drill('quant_load1', tmp_path, devices=1)
    assert res1['devices'] == 1 and res1['quantize'] == 'int8'
    assert res1['logits_max_diff'] <= 1e-5, res1
    # per-device int8 bytes: the fsdp=4 engine holds ~1/4 of the 1-device tree
    assert res8['param_bytes'] < res1['param_bytes']
    assert res1['param_bytes'] <= 0.35 * res8['dense_bytes']


# ---- 6. distillation smoke (the dormant task classes) ------------------------

def _dense_batch(mesh, n=8, img=32, classes=10, seed=0):
    rng = np.random.RandomState(seed)
    return shard_batch({'input': jnp.asarray(rng.rand(n, img, img, 3).astype(np.float32)),
                        'target': jnp.asarray(rng.randint(0, classes, n))}, mesh)


def test_logit_distillation_loss_decreases_and_donates():
    """The dormant LogitDistillationTask under the functional donated train
    step: repeated steps on one batch decrease the blended CE+KD loss, and
    the compiled step's HLO header declares the state-buffer aliases."""
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.perfbudget.probe import donation_evidence
    from timm_tpu.task import LogitDistillationTask

    mesh = create_mesh()
    set_global_mesh(mesh)
    student = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    teacher = timm_tpu.create_model('test_vit2', num_classes=10, img_size=32)
    opt = create_optimizer_v2(student, opt='sgd', lr=0.05)
    task = LogitDistillationTask(student, teacher=teacher, optimizer=opt, mesh=mesh,
                                 distill_alpha=0.5, distill_temperature=2.0)
    batch = _dense_batch(mesh)
    losses = [float(task.train_step(batch, lr=0.05)['loss']) for _ in range(6)]
    assert all(np.isfinite(l) for l in losses), losses
    assert losses[-1] < losses[0], f'distill loss did not decrease: {losses}'
    ev = donation_evidence(task.lower_train_step(batch))
    assert ev['aliases'] > 0, ev


def test_feature_distillation_projection_and_step():
    """FeatureDistillationTask with mismatched widths (64 -> 96): prepare_model
    attaches the projection BEFORE the optimizer captures the tree, the step
    is finite, and the projection's own kernel receives a gradient update."""
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import FeatureDistillationTask

    mesh = create_mesh()
    set_global_mesh(mesh)
    student = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    teacher = timm_tpu.create_model('test_vit3', num_classes=10, img_size=32)
    assert student.num_features != teacher.num_features
    FeatureDistillationTask.prepare_model(student, teacher)
    assert hasattr(student, 'distill_proj')
    opt = create_optimizer_v2(student, opt='sgd', lr=0.05)
    task = FeatureDistillationTask(student, teacher=teacher, optimizer=opt, mesh=mesh,
                                   distill_alpha=0.5, feat_loss='cosine')
    before = np.asarray(nnx.state(student, nnx.Param)['distill_proj']['kernel'].value).copy()
    m = task.train_step(_dense_batch(mesh), lr=0.05)
    assert np.isfinite(float(m['loss'])), m
    after = np.asarray(nnx.state(task.model, nnx.Param)['distill_proj']['kernel'].value)
    assert np.abs(after - before).max() > 0, 'projection kernel never updated'


def test_distillation_teacher_placed_on_mesh():
    """The frozen teacher's weights are device_put under the task's mesh
    partition rules — a big teacher shards instead of riding along as a
    single-device constant inside the SPMD step."""
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import LogitDistillationTask

    mesh = create_mesh(fsdp=2, tp=2)
    set_global_mesh(mesh)
    student = timm_tpu.create_model('test_vit', num_classes=10, img_size=32)
    teacher = timm_tpu.create_model('test_vit3', num_classes=10, img_size=32)
    opt = create_optimizer_v2(student, opt='sgd', lr=0.05)
    task = LogitDistillationTask(student, teacher=teacher, optimizer=opt, mesh=mesh)
    tparams, _ = task._teacher_state
    sharded = [l for l in jax.tree.leaves(tparams)
               if any(s is not None for s in tuple(getattr(l.sharding, 'spec', ()) or ()))]
    assert sharded, 'teacher weights stayed replicated/single-device on the mesh'
    m = task.train_step(_dense_batch(mesh), lr=0.05)
    assert np.isfinite(float(m['loss'])), m
