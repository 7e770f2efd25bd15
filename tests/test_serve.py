"""Serving engine tests: bucketing, admission queue, AOT prewarm + compile
cache, padded-slot handling, LRU residency, drain semantics, load drill."""
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from timm_tpu.serve import (
    InferenceEngine, RequestQueue, batch_bucket, pad_rows, select_bucket,
    strip_rows, validate_buckets,
)

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

pytestmark = pytest.mark.serve


# ---- 1. bucket selection -----------------------------------------------------

def test_select_bucket_smallest_fitting():
    buckets = (1, 4, 16, 64, 256)
    assert select_bucket(1, buckets) == 1
    assert select_bucket(2, buckets) == 4
    assert select_bucket(4, buckets) == 4
    assert select_bucket(5, buckets) == 16
    assert select_bucket(17, buckets) == 64
    assert select_bucket(256, buckets) == 256


def test_select_bucket_rejects_out_of_range():
    with pytest.raises(ValueError, match='largest declared bucket'):
        select_bucket(257, (1, 4, 16, 64, 256))
    with pytest.raises(ValueError):
        select_bucket(0, (1, 4))


def test_validate_buckets():
    assert validate_buckets((16, 4, 4, 1)) == (1, 4, 16)
    with pytest.raises(ValueError, match='at least one'):
        validate_buckets(())
    with pytest.raises(ValueError, match='positive'):
        validate_buckets((0, 4))
    # mesh divisibility is checked at construction, not serve time
    with pytest.raises(ValueError, match='not divisible'):
        validate_buckets((1, 4, 16), divisor=8)
    assert validate_buckets((8, 16), divisor=8) == (8, 16)


def test_batch_bucket_rounds_to_shard_count():
    assert batch_bucket(256, 1) == 256
    assert batch_bucket(100, 8) == 104
    assert batch_bucket(8, 8) == 8
    assert batch_bucket(1, 8) == 8


def test_engine_rejects_indivisible_buckets():
    from timm_tpu.parallel import create_mesh
    mesh = create_mesh()  # all 8 virtual CPU devices
    assert mesh.size == 8
    with pytest.raises(ValueError, match='not divisible'):
        InferenceEngine(buckets=(1, 4), mesh=mesh)


# ---- 2. padding / stripping --------------------------------------------------

def test_pad_rows_and_strip_rows():
    x = np.arange(12, dtype=np.float32).reshape(3, 4)
    t = np.array([7, 8, 9])
    xp, tp, valid = pad_rows(x, 8, t)
    assert xp.shape == (8, 4) and tp.shape == (8,)
    assert valid.tolist() == [True] * 3 + [False] * 5
    # padded slots repeat row 0 (finite, in-distribution — not zeros/NaN)
    assert np.array_equal(xp[3:], np.repeat(x[:1], 5, axis=0))
    np.testing.assert_array_equal(strip_rows(xp, 3), x)
    # exact fit: arrays pass through unchanged
    xs, v2 = pad_rows(x, 3)
    assert xs is x and v2.all()
    with pytest.raises(ValueError, match='does not fit'):
        pad_rows(x, 2)


# ---- 3. admission queue ------------------------------------------------------

def test_queue_full_bucket_admitted_immediately():
    q = RequestQueue(max_bucket=4, max_wait_s=10.0)  # deadline far away
    for _ in range(4):
        q.submit('m', np.zeros(2))
    t0 = time.perf_counter()
    model, reqs = q.wait_admission(timeout=5.0)
    assert model == 'm' and len(reqs) == 4
    assert time.perf_counter() - t0 < 1.0  # did NOT wait for the deadline


def test_queue_never_starves_past_deadline():
    """A partial run is admitted once its oldest request's deadline expires —
    a lone request never waits for batch-mates that aren't coming."""
    q = RequestQueue(max_bucket=64, max_wait_s=0.03)
    for _ in range(3):
        q.submit('m', np.zeros(2))
    t0 = time.perf_counter()
    admission = q.wait_admission(timeout=2.0)
    waited = time.perf_counter() - t0
    assert admission is not None, 'request starved past its deadline'
    model, reqs = admission
    assert len(reqs) == 3  # partial: far fewer than max_bucket
    assert 0.02 <= waited < 1.0, f'deadline admission took {waited:.3f}s'


def test_queue_oldest_model_first():
    q = RequestQueue(max_bucket=8, max_wait_s=0.0)  # everything ready at once
    q.submit('b', np.zeros(2), now=1.0)
    q.submit('a', np.zeros(2), now=2.0)
    q.submit('b', np.zeros(2), now=3.0)
    model, reqs = q.wait_admission(timeout=1.0)
    assert model == 'b' and len(reqs) == 2  # oldest head wins, run coalesces
    model, reqs = q.wait_admission(timeout=1.0)
    assert model == 'a' and len(reqs) == 1


def test_queue_close_without_drain_fails_pending():
    q = RequestQueue(max_bucket=4, max_wait_s=10.0)
    fut = q.submit('m', np.zeros(2))
    q.close(drain=False)
    with pytest.raises(RuntimeError, match='shut down'):
        fut.result(timeout=1.0)
    with pytest.raises(RuntimeError, match='no new requests'):
        q.submit('m', np.zeros(2))
    assert q.wait_admission(timeout=0.1) is None and q.finished()


def test_queue_capacity_sheds_load():
    q = RequestQueue(max_bucket=4, max_wait_s=10.0, max_pending=2)
    q.submit('m', np.zeros(2))
    q.submit('m', np.zeros(2))
    with pytest.raises(RuntimeError, match='over capacity'):
        q.submit('m', np.zeros(2))


# ---- 4. engine end-to-end (single device, in-process) ------------------------

@pytest.fixture(scope='module')
def engine():
    eng = InferenceEngine(buckets=(2, 4), max_wait_ms=10.0)
    eng.add_model('test_vit', img_size=32)
    eng.start()
    yield eng
    eng.shutdown(drain=True)


def test_engine_padded_slot_outputs_dropped(engine):
    """3 requests into the 4-bucket: every caller gets its own row back and
    the padded slot's output goes nowhere."""
    import jax.numpy as jnp
    from flax import nnx

    rng = np.random.RandomState(0)
    imgs = rng.standard_normal((3, 32, 32, 3)).astype(np.float32)
    before = dict(engine.stats)
    futs = [engine.submit(im) for im in imgs]
    rows = [f.result(timeout=120.0) for f in futs]
    assert all(r.ndim == 1 for r in rows)
    assert engine.stats['padded_slots'] > before['padded_slots']

    # padding must not change the answer: compare against a direct forward
    res = engine.pool.acquire('test_vit')
    direct = np.asarray(nnx.merge(res.graphdef, res.state)(jnp.asarray(imgs)))
    np.testing.assert_allclose(np.stack(rows), direct, atol=1e-5, rtol=1e-5)


def test_engine_only_declared_buckets_dispatch(engine):
    futs = [engine.submit(np.zeros((32, 32, 3), np.float32)) for _ in range(7)]
    for f in futs:
        f.result(timeout=120.0)
    assert set(engine.stats['steps_by_bucket']) <= set(engine.buckets)


def test_engine_bad_input_shape_fails_that_request(engine):
    fut = engine.submit(np.zeros((16, 16, 3), np.float32))  # wrong image size
    with pytest.raises(Exception):
        fut.result(timeout=120.0)
    # the engine survives: a good request still completes
    ok = engine.submit(np.zeros((32, 32, 3), np.float32))
    assert ok.result(timeout=120.0).ndim == 1


def test_engine_submit_requires_start():
    eng = InferenceEngine(buckets=(2,))
    with pytest.raises(RuntimeError, match='start'):
        eng.submit(np.zeros((32, 32, 3), np.float32))


def test_engine_clean_drain_on_shutdown():
    """Requests in the queue at shutdown(drain=True) all complete."""
    eng = InferenceEngine(buckets=(2, 4), max_wait_ms=10_000.0)  # deadline far off
    eng.add_model('test_vit', img_size=32)
    eng.start()
    # 5 requests: one full 4-bucket + a 1-remainder that only drain can flush
    futs = [eng.submit(np.zeros((32, 32, 3), np.float32)) for _ in range(5)]
    eng.shutdown(drain=True)
    for f in futs:
        assert f.result(timeout=1.0).ndim == 1  # already done; no waiting
    stats = eng.snapshot_stats()
    assert stats['completed'] == 5 and stats['failed'] == 0
    assert eng.pending() == 0


# ---- 5. LRU residency / HBM budget -------------------------------------------

def test_lru_eviction_respects_hbm_budget():
    eng = InferenceEngine(buckets=(2,), hbm_budget_bytes=None)
    eng.add_model('test_vit', img_size=32, prewarm=False)
    eng.add_model('test_vit2', img_size=32, prewarm=False)
    a = eng.pool.acquire('test_vit')
    # budget fits exactly one of the pair
    eng.pool.budget_bytes = int(1.25 * a.param_bytes)
    eng.pool.acquire('test_vit2')
    assert eng.pool.resident_names == ('test_vit2',), 'LRU victim not evicted'
    assert eng.pool.stats['evictions'] == 1
    assert eng.pool.resident_bytes() <= eng.pool.budget_bytes
    # re-acquiring the victim reloads it and evicts the other way
    eng.pool.acquire('test_vit')
    assert eng.pool.resident_names == ('test_vit',)
    assert eng.pool.stats['evictions'] == 2


def test_eviction_keeps_oversized_model():
    """A single model larger than the whole budget is kept (with a warning),
    not evict-looped into a livelock."""
    eng = InferenceEngine(buckets=(2,), hbm_budget_bytes=1)  # absurd budget
    eng.add_model('test_vit', img_size=32, prewarm=False)
    res = eng.pool.acquire('test_vit')
    assert res.param_bytes > 1
    assert eng.pool.resident_names == ('test_vit',)


def test_executables_survive_weight_eviction():
    """AOT programs hold code, not parameters: re-admitting an evicted model
    must not recompile (the exec cache hit is the reload fast path)."""
    eng = InferenceEngine(buckets=(2,))
    eng.add_model('test_vit', img_size=32)
    from timm_tpu.perfbudget import check_counter

    first = dict(eng.pool.acquire('test_vit').prewarm_stats)
    eng.pool.evict('test_vit')
    second = dict(eng.pool.acquire('test_vit').prewarm_stats)
    check_counter('first admit exec_cache_hits', first['exec_cache_hits'], 0)
    check_counter('re-admit exec_cache_hits', second['exec_cache_hits'], len(eng.buckets))
    check_counter('re-admit fresh_compiles', second['fresh_compiles'], 0)


# ---- 6. AOT warmup × persistent compile cache (two cold processes) -----------

_AOT_PROBE = r'''
import json, sys
from timm_tpu.serve import InferenceEngine
eng = InferenceEngine(buckets=(2, 4), persist_all_programs=True)
eng.add_model('test_vit', img_size=32)
print('PREWARM ' + json.dumps(eng.stats['prewarm']['test_vit']))
'''


@pytest.mark.serve
def test_aot_warmup_hits_compile_cache_on_second_startup(tmp_path):
    """Acceptance: the second engine startup performs ZERO fresh XLA compiles
    for pre-declared buckets — every bucket program comes back from the
    persistent compile cache (observed via JAX's cache-hit events)."""
    cache_dir = str(tmp_path / 'serve_xla_cache')
    env = dict(os.environ, JAX_PLATFORMS='cpu', JAX_COMPILATION_CACHE_DIR=cache_dir)
    env.pop('XLA_FLAGS', None)  # single-device probe processes, cheap compiles

    def startup():
        r = subprocess.run([sys.executable, '-c', _AOT_PROBE], env=env,
                           cwd=REPO_ROOT, capture_output=True, text=True, timeout=300)
        assert r.returncode == 0, r.stderr[-2000:]
        line = [l for l in r.stdout.splitlines() if l.startswith('PREWARM ')][-1]
        return json.loads(line[len('PREWARM '):])

    from timm_tpu.perfbudget import check_counter, check_counter_min

    cold = startup()
    check_counter('cold startup programs', cold['programs'], 2)
    check_counter('cold startup fresh_compiles', cold['fresh_compiles'], 2)
    assert os.listdir(cache_dir), 'cold startup persisted no executables'
    warm = startup()
    check_counter('warm startup fresh_compiles', warm['fresh_compiles'], 0)
    check_counter_min('warm startup cache_hits', warm['cache_hits'], warm['programs'])


# ---- 7. sharded serving (8-device subprocess drill) --------------------------

@pytest.mark.serve
def test_sharded_serving_matches_single_device(tmp_path):
    """fsdp_drill serve8: an engine on a ('data','fsdp')=(2,4) 8-device mesh
    loads the same mesh-shape-agnostic checkpoint as a single-device engine
    and serves identical logits (≤1e-5) for identical requests."""
    env = dict(os.environ, JAX_PLATFORMS='cpu', TIMM_TPU_DRILL_DEVICES='8',
               XLA_FLAGS='--xla_force_host_platform_device_count=8')
    r = subprocess.run(
        [sys.executable, os.path.join(REPO_ROOT, 'tests', 'fsdp_drill.py'),
         'serve8', str(tmp_path)],
        env=env, cwd=REPO_ROOT, capture_output=True, text=True, timeout=420)
    assert r.returncode == 0, r.stderr[-3000:]
    d = json.loads(r.stdout.strip().splitlines()[-1])
    assert d['devices'] == 8 and d['mesh'] == [2, 4]
    assert d['param_sharded_over_fsdp'] is True
    assert set(map(int, d['steps_by_bucket'])) == {8}  # one declared bucket
    assert d['logits_max_diff'] <= 1e-5, d


# ---- 8. load-drill smoke -------------------------------------------------------

@pytest.mark.serve
def test_serve_drill_smoke():
    """`canonical_drill`: the A/B drill (two buckets, two models, eviction)
    gives a continuous-vs-per-request speedup > 1.0 (its own acceptance), and
    `summary_line` prints the p50/p99 line for both arms."""
    from timm_tpu.serve import canonical_drill, summary_line

    ab = canonical_drill()
    assert ab['speedup'] > 1.0, ab
    assert ab['continuous']['evictions'] >= 1, ab
    line = summary_line(ab)
    assert line.startswith('serve-drill:') and 'p50' in line and 'p99' in line, line
    assert 'eviction' in line and f'{ab["speedup"]}x' in line, line
