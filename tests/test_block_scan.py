"""Scan-over-layers (block_scan) parity + trace-cost regression, persistent
compile cache, and device-prefetch pipeline tests (ISSUE 4).

Promises guarded here:

1. `block_scan=True` is numerically the Python loop: forward within fp32
   fusion noise (≤1e-6) on seeded weights (tests/seeded_vit.py), grads within ≤1e-5, for
   ViT / DeiT / BEiT / EVA (incl. mixed rope), with DropPath, LayerScale,
   remat-inside-scan, forward_intermediates and pruned stacks.
2. Trace cost is O(1) in depth: a scanned depth-12 ViT's jaxpr equation count
   is < 2x the depth-2 count (the loop's is ~6x).
3. Heterogeneous stacks (depth-dependent statics) fall back to the loop with
   identical outputs — never silently wrong numbers.
4. The persistent compile cache writes executables a second cold process
   reuses, and DevicePrefetcher preserves batch order/contents with clean
   early-termination drain.
"""
import os
import subprocess
import sys
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from flax import nnx

import seeded_vit
import timm_tpu
from timm_tpu.models._manipulate import (
    BlockStackError, build_block_stack, drop_path_scan_inputs, scan_block_stack,
)
from timm_tpu.utils.compile_cache import configure_compile_cache, count_jaxpr_eqns



def _fixture_x():
    return jnp.asarray(seeded_vit.seeded_input())


def _grads(model, x):
    graphdef, params, rest = nnx.split(model, nnx.Param, ...)

    def loss(p):
        return (nnx.merge(graphdef, p, rest)(x) ** 2).mean()

    return jax.jit(jax.grad(loss))(params)


# ---- 1. scan-vs-loop parity --------------------------------------------------

@pytest.mark.blockscan
def test_scan_parity_golden_fixture():
    """Acceptance: block_scan matches the loop forward within ≤1e-6 fp32 on
    seeded weights, and loop and scan both agree with the plain float32
    reference (`benchmarks/reference/vit.py`) on the same weights and input.
    Under jit — the production mode — scan vs loop is typically bit-identical
    (XLA resolves both to the same fused program); the ≤1e-6 bound is the
    contract."""
    weights = seeded_vit.seeded_weights()
    x = _fixture_x()
    expected = seeded_vit.reference_logits(weights, x)
    model = seeded_vit.build(weights)

    def jit_fwd(m):
        graphdef, state = nnx.split(m)
        f = jax.jit(lambda s, xx: nnx.merge(graphdef, s).forward_features(xx))
        f2 = jax.jit(lambda s, xx: nnx.merge(graphdef, s)(xx))
        return np.asarray(f(state, x)), np.asarray(f2(state, x))

    feats_loop, logits_loop = jit_fwd(model)
    model.set_block_scan(True)
    feats_scan, logits_scan = jit_fwd(model)
    for name, logits in (('loop', logits_loop), ('scan', logits_scan)):
        assert float(np.abs(logits - expected).max()) <= seeded_vit.REFERENCE_TOL, \
            f'{name} path vs the plain reference: {np.abs(logits - expected).max()}'
    assert float(np.abs(feats_scan - feats_loop).max()) <= 1e-6, \
        f'feats: {np.abs(feats_scan - feats_loop).max()}'
    assert float(np.abs(logits_scan - logits_loop).max()) <= 1e-6, \
        f'logits: {np.abs(logits_scan - logits_loop).max()}'


@pytest.mark.blockscan
def test_scan_grad_parity():
    """Acceptance: grads under scan match the loop within ≤1e-5."""
    x = _fixture_x()
    model = timm_tpu.create_model('vit_tiny_patch16_224', img_size=64, depth=4)
    model.train()
    g_loop = _grads(model, x)
    model.set_block_scan(True)
    g_scan = _grads(model, x)
    err = max(float(jnp.abs(a - b).max())
              for a, b in zip(jax.tree.leaves(g_loop), jax.tree.leaves(g_scan)))
    assert err < 1e-5, f'grad divergence {err}'


@pytest.mark.blockscan
def test_scan_remat_grad_parity():
    """set_grad_checkpointing composes with scan (remat-inside-scan replaces
    checkpoint_seq) without changing gradients."""
    x = _fixture_x()
    model = timm_tpu.create_model('vit_tiny_patch16_224', img_size=64, depth=4)
    model.train()
    g_ref = _grads(model, x)
    model.set_grad_checkpointing(True)
    model.set_block_scan(True)
    g_scan = _grads(model, x)
    err = max(float(jnp.abs(a - b).max())
              for a, b in zip(jax.tree.leaves(g_ref), jax.tree.leaves(g_scan)))
    assert err < 1e-5, f'remat-in-scan grad divergence {err}'


@pytest.mark.blockscan
def test_scan_drop_path_rates():
    """Per-layer DropPath rates ride the scanned rate vector: train mode runs
    (stochastic, finite), eval mode is exactly the loop."""
    x = _fixture_x()
    model = timm_tpu.create_model(
        'vit_tiny_patch16_224', img_size=64, depth=4, drop_path_rate=0.3)
    model.train()
    model.set_block_scan(True)
    out = model(x)
    assert bool(jnp.isfinite(out).all())
    # scan inputs exist in train mode and carry the linear ramp incl. rate-0 layer 0
    dp = drop_path_scan_inputs(list(model.blocks))
    assert dp is not None
    rates, keys = dp
    assert rates.shape == (4, 2) and float(rates[0, 0]) == 0.0 and float(rates[-1, 0]) > 0.0
    assert keys.shape[:2] == (4, 2)
    model.eval()
    assert drop_path_scan_inputs(list(model.blocks)) is None
    ref = model(x)
    model.set_block_scan(False)
    loop = model(x)
    assert np.allclose(np.asarray(ref), np.asarray(loop), rtol=1e-6, atol=1e-6)


@pytest.mark.blockscan
def test_scan_parity_model_families():
    """BEiT (shared rel-pos bias constant) and EVA (incl. per-depth mixed rope
    threaded through the scan) inherit block_scan via the shared helper."""
    x = jnp.asarray(np.random.RandomState(0).rand(2, 56, 56, 3), jnp.float32)
    for name in ('beit_base_patch16_224.in22k_ft_in22k_in1k',
                 'eva02_tiny_patch14_224.mim_in22k',
                 'vit_small_patch16_rope_mixed_224.naver_in1k'):
        model = timm_tpu.create_model(name, img_size=56, depth=2)
        model.eval()
        ref = np.asarray(model(x))
        model.set_block_scan(True)
        out = np.asarray(model(x))
        assert np.allclose(out, ref, rtol=1e-6, atol=1e-6), \
            f'{name}: {np.abs(out - ref).max()}'


@pytest.mark.blockscan
def test_scan_forward_intermediates_and_prune():
    x = _fixture_x()
    model = timm_tpu.create_model('vit_tiny_patch16_224', img_size=64, depth=6)
    model.eval()
    xf, inter_loop = model.forward_intermediates(x, indices=[1, 3, 5])
    model.set_block_scan(True)
    xs, inter_scan = model.forward_intermediates(x, indices=[1, 3, 5])
    assert len(inter_scan) == len(inter_loop) == 3
    for a, b in zip(inter_loop, inter_scan):
        assert np.allclose(np.asarray(a), np.asarray(b), rtol=1e-6, atol=1e-6)
    assert np.allclose(np.asarray(xf), np.asarray(xs), rtol=1e-6, atol=1e-6)

    # stop_early slices self.blocks — must not silently disagree with scan:
    # it always takes the loop and matches the loop-mode result exactly
    early_scan = model.forward_intermediates(x, indices=[1], stop_early=True,
                                             intermediates_only=True)
    model.set_block_scan(False)
    early_loop = model.forward_intermediates(x, indices=[1], stop_early=True,
                                             intermediates_only=True)
    assert (np.asarray(early_scan[0]) == np.asarray(early_loop[0])).all()

    # pruning rebuilds self.blocks; the call-time stack follows transparently
    model.prune_intermediate_layers([3], prune_head=True)
    ref = model.forward_features(x)
    model.set_block_scan(True)
    out = model.forward_features(x)
    assert len(model.blocks) == 4
    assert np.allclose(np.asarray(out), np.asarray(ref), rtol=1e-6, atol=1e-6)


@pytest.mark.blockscan
def test_scan_fallback_heterogeneous_is_exact():
    """Depth-dependent statics (diff-attention lambda_init) must NOT be
    silently scanned with block 0's constants: the stack check rejects them
    and the loop fallback output is bit-identical to block_scan=False."""
    x = _fixture_x()
    model = timm_tpu.create_model(
        'vit_tiny_patch16_224', img_size=64, depth=3, attn_layer='diff')
    model.eval()
    ref = np.asarray(model(x))
    with pytest.raises(BlockStackError):
        build_block_stack(list(model.blocks))
    model.set_block_scan(True)
    out = np.asarray(model(x))
    assert (out == ref).all()


@pytest.mark.blockscan
def test_scan_rejects_active_inner_dropout():
    """Train-mode attention/proj dropout consumes RNG inside the block — the
    scan body cannot advance those streams, so the stack must refuse."""
    model = timm_tpu.create_model(
        'vit_tiny_patch16_224', img_size=64, depth=2, proj_drop_rate=0.1)
    model.train()
    with pytest.raises(BlockStackError, match='dropout'):
        build_block_stack(list(model.blocks))
    model.eval()  # deterministic: scannable again
    build_block_stack(list(model.blocks))


@pytest.mark.blockscan
def test_task_block_scan_toggle():
    """TrainingTask.set_block_scan toggles the owned model and invalidates
    the jitted steps, so the next step runs in the new execution mode."""
    from timm_tpu.optim import create_optimizer_v2
    from timm_tpu.task import ClassificationTask
    x = _fixture_x()
    model = timm_tpu.create_model('vit_tiny_patch16_224', img_size=64, depth=2)
    opt = create_optimizer_v2(model, opt='adamw', lr=1e-3)
    task = ClassificationTask(model, optimizer=opt)
    batch = {'input': x, 'target': jnp.zeros((x.shape[0],), jnp.int32)}
    ref = np.asarray(task.eval_step(batch))
    assert task.set_block_scan(True)
    assert model.block_scan and task._eval_step is None
    out = np.asarray(task.eval_step(batch))
    assert np.allclose(out, ref, rtol=1e-6, atol=1e-6)
    m = task.train_step(batch, lr=1e-3)
    assert bool(np.isfinite(np.asarray(m['loss'])))


# ---- 2. trace-cost regression ------------------------------------------------

@pytest.mark.blockscan
def test_trace_cost_o1_in_depth():
    """Acceptance: scanned depth-12 jaxpr equation count < 2x the depth-2
    count (the Python loop's grows ~linearly in depth)."""
    x = jnp.zeros((1, 64, 64, 3), jnp.float32)

    def eqns(depth, scan):
        model = timm_tpu.create_model('vit_tiny_patch16_224', img_size=64, depth=depth)
        model.set_block_scan(scan)
        model.eval()
        graphdef, state = nnx.split(model)
        jaxpr = jax.make_jaxpr(lambda s, xx: nnx.merge(graphdef, s)(xx))(state, x)
        return count_jaxpr_eqns(jaxpr)

    from timm_tpu.perfbudget import check_ratio_max, check_ratio_min

    scan2, scan12 = eqns(2, True), eqns(12, True)
    check_ratio_max('scanned trace cost vs depth (eqns d12/d2)', scan12, scan2, 2.0)
    loop12 = eqns(12, False)
    check_ratio_min('loop jaxpr vs scanned (eqns loop12/scan12)', loop12, scan12, 2.0)


# ---- 3. persistent compile cache ---------------------------------------------

_CACHE_PROBE = r'''
import importlib.util, os, sys
import jax, jax.numpy as jnp
spec = importlib.util.spec_from_file_location('cc_mod', sys.argv[1])
mod = importlib.util.module_from_spec(spec)
spec.loader.exec_module(mod)
cache_dir = mod.configure_compile_cache(min_compile_time_secs=0.0)
assert cache_dir == os.environ['JAX_COMPILATION_CACHE_DIR']
f = jax.jit(lambda a: ((a @ a) @ a).sum())
with mod.collect_cache_events() as events:
    f(jnp.ones((128, 128), jnp.float32)).block_until_ready()
print('CACHE_HITS', mod.cache_event_total(events, 'cache_hits'))
'''


@pytest.mark.compilecache
def test_compile_cache_survives_processes(tmp_path):
    """Acceptance: a second cold process with JAX_COMPILATION_CACHE_DIR set
    reuses the first process's on-disk executable (observed via JAX's
    cache-hit event), instead of recompiling."""
    cache_dir = str(tmp_path / 'xla_cache')
    mod_path = os.path.join(os.path.dirname(__file__), '..',
                            'timm_tpu', 'utils', 'compile_cache.py')
    env = dict(os.environ, JAX_PLATFORMS='cpu', JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.pop('XLA_FLAGS', None)  # keep the probe processes single-device/cheap

    def run():
        r = subprocess.run([sys.executable, '-c', _CACHE_PROBE, mod_path],
                           capture_output=True, text=True, timeout=240, env=env)
        assert r.returncode == 0, r.stderr[-2000:]
        return int(r.stdout.strip().splitlines()[-1].split()[-1])

    hits_cold = run()
    entries = os.listdir(cache_dir)
    assert entries, 'first (cold) process persisted no executables'
    hits_warm = run()
    assert hits_cold == 0 and hits_warm >= 1, (hits_cold, hits_warm)


@pytest.mark.compilecache
def test_compile_cache_thresholds_and_nested_collectors():
    """configure_compile_cache sets the persistence thresholds it is given,
    and nested collectors each count the compiles inside their own block."""
    from timm_tpu.utils import compile_cache as cc
    before = (jax.config.jax_persistent_cache_min_entry_size_bytes,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    try:
        cc.configure_compile_cache(min_entry_size_bytes=7, min_compile_time_secs=0.25)
        assert jax.config.jax_persistent_cache_min_entry_size_bytes == 7
        assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.25
        with cc.collect_cache_events() as outer:
            with cc.collect_cache_events() as inner:
                jax.jit(lambda a: a * 3 + 1)(jnp.ones((3, 5))).block_until_ready()
            jax.jit(lambda a: a * 5 + 2)(jnp.ones((3, 5))).block_until_ready()
        requests = 'backend_compile_duration'
        assert cc.cache_event_total(inner, requests) >= 1
        assert cc.cache_event_total(outer, requests) > cc.cache_event_total(inner, requests)
    finally:
        cc.configure_compile_cache(*before)


@pytest.mark.compilecache
def test_tier1_uses_the_configured_compile_cache():
    """The conftest goes through configure_compile_cache, so tier-1 and the
    subprocess tests that call it hit one fixed directory: JAX's own variable
    where it is set, the in-checkout directory otherwise."""
    from timm_tpu.utils.compile_cache import CHECKOUT_CACHE_DIR
    expected = os.environ.get('JAX_COMPILATION_CACHE_DIR') or CHECKOUT_CACHE_DIR
    assert jax.config.jax_compilation_cache_dir == expected


# ---- 4. device prefetch ------------------------------------------------------

class _CountingLoader:
    """4 deterministic numpy batches + a close-observable iterator."""

    def __init__(self, n=4, batch=8):
        self.n, self.batch = n, batch
        self.pulled = 0
        self.closed = False
        self.mean = np.zeros(3, np.float32)  # attribute-delegation probe

    def __len__(self):
        return self.n

    def set_epoch(self, epoch):
        self.epoch = epoch

    def __iter__(self):
        try:
            for i in range(self.n):
                self.pulled += 1
                yield (np.full((self.batch, 4, 4, 3), i, np.float32),
                       np.full((self.batch,), i, np.int32))
        finally:
            self.closed = True


@pytest.mark.compilecache
def test_device_prefetcher_contents_and_order():
    from timm_tpu.data.loader import DevicePrefetcher
    from timm_tpu.parallel import create_mesh, set_global_mesh
    set_global_mesh(create_mesh())
    inner = _CountingLoader()
    pf = DevicePrefetcher(inner, size=2)
    assert len(pf) == 4 and pf.mean.shape == (3,)  # delegation
    pf.set_epoch(3)
    assert inner.epoch == 3
    batches = list(pf)
    assert len(batches) == 4
    for i, (x, t) in enumerate(batches):
        assert isinstance(x, jax.Array) and isinstance(t, jax.Array)
        assert float(x[0, 0, 0, 0]) == i and int(t[0]) == i
    assert inner.closed


@pytest.mark.compilecache
def test_device_prefetcher_early_stop_drains():
    """Breaking out mid-epoch (preemption) must close the inner iterator and
    drop in-flight batches without hanging — and prefetch depth must not
    shift which batches were yielded."""
    from timm_tpu.data.loader import DevicePrefetcher
    from timm_tpu.parallel import create_mesh, set_global_mesh
    set_global_mesh(create_mesh())
    inner = _CountingLoader(n=10)
    pf = DevicePrefetcher(inner, size=3)
    seen = []
    for x, t in pf:
        seen.append(int(t[0]))
        if len(seen) == 2:
            break
    assert seen == [0, 1]
    assert inner.closed
    assert inner.pulled <= 2 + 3 + 1  # yielded + prefetch depth (+1 in flight)


@pytest.mark.compilecache
def test_shard_batch_scalar_and_nonarray_leaves():
    from timm_tpu.parallel import create_mesh, set_global_mesh, shard_batch
    set_global_mesh(create_mesh())
    batch = {'x': np.ones((8, 2), np.float32), 'seq_len': 196, 'step': np.int32(7)}
    out = shard_batch(batch)
    assert isinstance(out['x'], jax.Array)
    assert out['seq_len'] == 196            # non-array passes through
    assert int(out['step']) == 7            # 0-d array replicated, not sharded
