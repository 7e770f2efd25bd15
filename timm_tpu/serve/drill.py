"""Open-loop load drill for the serving engine.

Synthetic Poisson traffic (open-loop: arrival times are drawn up front and do
NOT wait for completions, so queueing delay is measured honestly — a
closed-loop generator would throttle itself and hide it) is replayed against
an :class:`~timm_tpu.serve.engine.InferenceEngine`, reporting p50/p99 request
latency and sustained img/s against the offered load.

``canonical_drill`` is the tier-1 A/B smoke (tests/test_serve.py calls it in process):
the SAME arrival schedule replayed twice —

  * **continuous batching**: declared buckets, deadline-bounded admission,
    double-buffered dispatch, two models sharing an HBM budget sized to hold
    only one (forcing exactly the LRU eviction path);
  * **per-request baseline**: bucket set ``(1,)`` with zero wait — every
    request is its own device step, the service the engine replaces.

It asserts continuous batching sustains strictly higher img/s at equal
offered load, that every dispatched shape was a declared bucket, and that
the eviction path fired. CPU-runnable end to end.
"""
from __future__ import annotations

import time
from typing import Dict, Optional, Sequence

import numpy as np

from .engine import InferenceEngine

__all__ = ['run_load_drill', 'canonical_drill', 'summary_line']


def _poisson_arrivals(num: int, rate_per_s: float, seed: int) -> np.ndarray:
    rng = np.random.RandomState(seed)
    gaps = rng.exponential(1.0 / rate_per_s, size=num)
    gaps[0] = 0.0  # first request arrives at t=0
    return np.cumsum(gaps)


def run_load_drill(
        model_names: Sequence[str] = ('test_vit',),
        buckets: Sequence[int] = (4, 16),
        num_requests: int = 96,
        rate_per_s: float = 2000.0,
        img_size: int = 32,
        max_wait_ms: float = 15.0,
        hbm_budget_bytes: Optional[int] = None,
        per_request: bool = False,
        seed: int = 0,
        mesh=None,
        persist_all_programs: bool = False,
        result_timeout: float = 300.0,
) -> Dict:
    """Replay one Poisson schedule against one engine configuration.

    ``per_request=True`` turns the engine into the baseline it replaces:
    bucket set ``(1,)``, zero admission wait, no transfer overlap.
    """
    if per_request:
        buckets, max_wait_ms, transfer_depth = (1,), 0.0, 1
    else:
        transfer_depth = 2
    engine = InferenceEngine(
        buckets=buckets, max_wait_ms=max_wait_ms, mesh=mesh,
        transfer_depth=transfer_depth, hbm_budget_bytes=hbm_budget_bytes,
        persist_all_programs=persist_all_programs)

    t_warm0 = time.perf_counter()
    for name in model_names:
        engine.add_model(name, img_size=img_size)
    startup_ms = (time.perf_counter() - t_warm0) * 1e3

    arrivals = _poisson_arrivals(num_requests, rate_per_s, seed)
    # a small pool of distinct in-distribution images, reused round-robin
    rng = np.random.RandomState(seed + 1)
    images = rng.standard_normal((8, img_size, img_size, 3)).astype(np.float32)
    # phase split across models: all model-A traffic, then all model-B — the
    # access pattern that exercises LRU residency (B's load evicts cold A
    # under a one-model budget) without thrashing on every step
    n_models = len(model_names)
    model_of = [model_names[min(i * n_models // num_requests, n_models - 1)]
                for i in range(num_requests)]

    engine.start()
    futures, submit_ts = [], []
    t0 = time.perf_counter()
    try:
        for i in range(num_requests):
            lag = (t0 + arrivals[i]) - time.perf_counter()
            if lag > 0:
                time.sleep(lag)
            futures.append(engine.submit(images[i % len(images)], model=model_of[i]))
            submit_ts.append(time.perf_counter())
        results = [f.result(timeout=result_timeout) for f in futures]
    finally:
        engine.shutdown(drain=True)

    stats = engine.snapshot_stats()
    # acceptance guard: nothing outside the declared bucket set ever reached
    # the compiler (the engine's AOT executables enforce this per step; the
    # drill re-checks the ledger end-to-end)
    dispatched = set(stats['steps_by_bucket'])
    assert dispatched <= set(engine.buckets), \
        f'off-bucket shapes dispatched: {sorted(dispatched - set(engine.buckets))}'
    assert stats['failed'] == 0 and stats['completed'] == num_requests, \
        f'drill lost requests: {stats["completed"]}/{num_requests} ok, {stats["failed"]} failed'
    for r in results:
        assert np.all(np.isfinite(r)), 'non-finite logits in drill output'

    lat_ms = np.array([(f.done_at - t) * 1e3 for f, t in zip(futures, submit_ts)])
    t_end = max(f.done_at for f in futures)
    p50, p99 = np.percentile(lat_ms, [50, 99])
    return {
        'mode': 'per_request' if per_request else 'continuous',
        'models': list(model_names),
        'buckets': list(engine.buckets),
        'num_requests': num_requests,
        'offered_rps': round(num_requests / max(arrivals[-1], 1e-9), 1),
        'img_per_s': round(num_requests / max(t_end - t0, 1e-9), 1),
        'p50_ms': round(float(p50), 2),
        'p99_ms': round(float(p99), 2),
        'steps': stats['steps'],
        'steps_by_bucket': stats['steps_by_bucket'],
        'padded_slots': stats['padded_slots'],
        'evictions': stats['pool']['evictions'],
        'resident': stats['resident'],
        'startup_ms': round(startup_ms, 1),
        'prewarm': stats['prewarm'],
    }


def _param_bytes(name: str, img_size: int) -> int:
    """Host-side parameter byte count for sizing the drill's HBM budget
    (models here are tiny; building one on CPU to measure is cheap)."""
    import jax
    import timm_tpu
    from flax import nnx

    _, state = nnx.split(timm_tpu.create_model(name, img_size=img_size))
    return int(sum(
        int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
        for leaf in jax.tree.leaves(state) if hasattr(leaf, 'shape')))


def canonical_drill(
        model_names: Sequence[str] = ('test_vit', 'test_vit2'),
        buckets: Sequence[int] = (4, 16),
        num_requests: int = 256,
        rate_per_s: float = 2000.0,
        img_size: int = 32,
        seed: int = 0,
        persist_all_programs: bool = False,
) -> Dict:
    """The tier-1 A/B drill: two models, two buckets, budget forces one
    eviction; continuous batching must beat the per-request baseline."""
    # budget holds the larger model alone but never both → loading the second
    # model exercises the LRU eviction path exactly once per phase change
    budget = int(1.25 * max(_param_bytes(n, img_size) for n in model_names))
    common = dict(model_names=model_names, num_requests=num_requests,
                  rate_per_s=rate_per_s, img_size=img_size, seed=seed,
                  hbm_budget_bytes=budget,
                  persist_all_programs=persist_all_programs)
    continuous = run_load_drill(buckets=buckets, **common)
    baseline = run_load_drill(per_request=True, **common)

    assert continuous['evictions'] >= 1, \
        f'HBM budget {budget} failed to trigger LRU eviction: {continuous}'
    assert continuous['img_per_s'] > baseline['img_per_s'], (
        f'continuous batching ({continuous["img_per_s"]} img/s) did not beat the '
        f'per-request baseline ({baseline["img_per_s"]} img/s) at equal offered load')
    return {
        'continuous': continuous,
        'per_request': baseline,
        'speedup': round(continuous['img_per_s'] / max(baseline['img_per_s'], 1e-9), 2),
        'hbm_budget_bytes': budget,
    }


def summary_line(ab: Dict) -> str:
    c, b = ab['continuous'], ab['per_request']
    return (
        f'serve-drill: continuous {c["img_per_s"]} img/s '
        f'(p50 {c["p50_ms"]}ms / p99 {c["p99_ms"]}ms, buckets {tuple(c["buckets"])}, '
        f'{c["evictions"]} eviction(s)) vs per-request {b["img_per_s"]} img/s '
        f'(p50 {b["p50_ms"]}ms / p99 {b["p99_ms"]}ms) -> {ab["speedup"]}x '
        f'at {c["offered_rps"]} req/s offered')
